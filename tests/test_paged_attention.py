"""PagedAttentionHelper seam: the XLA backend against the Pallas kernel.

Ports the reference's helper-vs-stock parity discipline (cuDNN
``*Helper`` vs pure ND4J under deeplearning4j-cuda/) to the paged-KV
decode read: the Pallas block-table kernel
(nn/conf/layers/paged_attention.py) against the stock gather-then-attend
backend across f32/int8 pools, greedy and sampled serving, and the edge
geometries the block-table walk can get wrong — a row's position exactly
on a page boundary, a prefill chunk straddling two pages, an all-masked
chunk whose writes route to garbage page 0, rows of one batch at contexts
from one key to the table's capacity, dead table slots that point at a
page of NaN, and a frozen row whose table is all garbage.

What parity means: the updated pool (the write side, shared by both
backends) is BITWISE equal; served tokens are equal; the layer's output
agrees to float32 rounding (``OUT_RTOL`` below). The kernel walks a row's
live pages in key blocks and sums them by the online softmax recurrence,
the stock path takes one softmax over the whole capacity: the same
float32 sums in another order.

Parity is asserted UNDER JIT on both sides — the production
configuration (every serving program is jitted).

On the CPU suite the kernel runs in ``interpret=True`` mode (parity
gating only; the Mosaic-compiled kernel is compared with the stock path
on the chip by chip_smoke.py, and tests/test_tpu_lowering.py lowers every
variant for TPU here).
"""

import numpy as np
import pytest
from tests.serving_helpers import V

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from deeplearning4j_tpu.nn.conf.layers import (  # noqa: E402
    paged_attention as ppa)
from deeplearning4j_tpu.nn.conf.layers.attention import (  # noqa: E402
    SERVED_BY, SelfAttentionLayer)

pytestmark = pytest.mark.pallas

#: float32 rounding of sums taken in another order, with scores of order
#: one: measured <= 6e-7 of the output's largest entry (a few units in its
#: last place)
OUT_RTOL = 1e-6


def assert_output_close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, rtol=OUT_RTOL,
                               atol=OUT_RTOL * np.abs(want).max())


def _layer(n_heads=4, ps_cap=32):
    return SelfAttentionLayer(n_in=32, n_out=32, n_heads=n_heads,
                              causal=True, max_cache=ps_cap, bias_init=0.0)


def _paged_state(rs, *, pages, ps, NP, B, H=4, d=8, quant=False):
    """A pool with random resident content, distinct per-row pages (page
    0 reserved as the garbage sink), and a [B, NP] block table."""
    if quant:
        state = {
            "kpages": jnp.asarray(rs.randint(
                -127, 128, (pages, ps, H * d)), jnp.int8),
            "vpages": jnp.asarray(rs.randint(
                -127, 128, (pages, ps, H * d)), jnp.int8),
            "kscales": jnp.asarray(rs.rand(pages, H, ps) * 0.05,
                                   jnp.float32),
            "vscales": jnp.asarray(rs.rand(pages, H, ps) * 0.05,
                                   jnp.float32),
        }
    else:
        state = {
            "kpages": jnp.asarray(rs.randn(pages, ps, H * d), jnp.float32),
            "vpages": jnp.asarray(rs.randn(pages, ps, H * d), jnp.float32),
        }
    perm = rs.permutation(pages - 1)[:B * NP] + 1
    state["block_table"] = jnp.asarray(perm.reshape(B, NP), jnp.int32)
    return state


def _attend(helper, state, q, bt, pos, mask=None):
    """One jitted read of ``state``'s pool through ``helper``."""
    return jax.jit(
        lambda q, kp, vp, bt, pos, mask, ks, vs: helper.attend(
            q, kp, vp, bt, pos, mask=mask, kscales=ks, vscales=vs))(
        q, state["kpages"], state["vpages"], jnp.asarray(bt),
        jnp.asarray(pos), mask, state.get("kscales"), state.get("vscales"))


class TestLayerParity:
    """One layer, jitted under the xla and under the pallas backend, each
    handed to it as a server hands it (a static entry of the state, put
    in inside the traced function): the updated pool bitwise equal and
    the output equal to float32 rounding, across the edge geometries the
    kernel must match."""

    def _run_both(self, state, x, mask=None, seed=0, ps_cap=32):
        lyr = _layer(ps_cap=ps_cap)
        params = lyr.init_params(jax.random.PRNGKey(seed))

        def fwd(backend):
            def served(s):
                return {**s, SERVED_BY: (backend, None)}

            if mask is None:
                return jax.jit(
                    lambda p, s, xx: lyr.forward(p, served(s), xx))(
                    params, state, x)
            return jax.jit(
                lambda p, s, xx, m: lyr.forward(p, served(s), xx, mask=m))(
                params, state, x, mask)

        (out_x, st_x) = fwd("xla")
        (out_p, st_p) = fwd("pallas")
        assert_output_close(out_p, out_x)
        assert set(st_p) == set(st_x) == set(state)
        for k in st_x:
            np.testing.assert_array_equal(np.asarray(st_p[k]),
                                          np.asarray(st_x[k]))
        return out_x, st_x

    @pytest.mark.parametrize("quant", [False, True])
    def test_decode_at_page_boundary(self, quant):
        """cache_pos exactly on a page boundary: the freshest resident
        token is the last slot of the previous page and the write lands
        at offset 0 of the next — both sides of the boundary walk."""
        rs = np.random.RandomState(0)
        ps, NP, B = 8, 4, 3
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B,
                             quant=quant)
        # rows pinned to offsets {0, ps, 2*ps}: page-boundary-exact
        state["cache_pos"] = jnp.asarray([0, ps, 2 * ps], jnp.int32)
        x = jnp.asarray(rs.randn(B, 1, 32), jnp.float32)
        self._run_both(state, x)

    @pytest.mark.parametrize("quant", [False, True])
    def test_prefill_chunk_straddles_two_pages(self, quant):
        rs = np.random.RandomState(1)
        ps, NP, B, T = 8, 4, 2, 6
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B,
                             quant=quant)
        # offset 5 + 6 tokens crosses into the next page at offset 8
        state["cache_pos"] = jnp.asarray([5, ps + 5], jnp.int32)
        x = jnp.asarray(rs.randn(B, T, 32), jnp.float32)
        self._run_both(state, x)

    @pytest.mark.parametrize("quant", [False, True])
    def test_all_masked_chunk_routes_to_garbage_page(self, quant):
        """A fully-masked row's chunk writes pool page 0 (the garbage
        sink) and leaves its REAL pages untouched — under both backends,
        bitwise."""
        rs = np.random.RandomState(2)
        ps, NP, B, T = 8, 4, 2, 4
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B,
                             quant=quant)
        state["cache_pos"] = jnp.asarray([3, 9], jnp.int32)
        x = jnp.asarray(rs.randn(B, T, 32), jnp.float32)
        mask = jnp.asarray([[0, 0, 0, 0], [1, 1, 0, 0]], jnp.float32)
        _, st = self._run_both(state, x, mask=mask)
        # row 0 (all masked): its own pages hold their prior content
        bt = np.asarray(state["block_table"])
        for key in ("kpages", "vpages"):
            np.testing.assert_array_equal(
                np.asarray(st[key])[bt[0]],
                np.asarray(state[key])[bt[0]])
            # and the garbage page moved (the masked columns landed there)
            assert not np.array_equal(np.asarray(st[key])[0],
                                      np.asarray(state[key])[0])

    def test_decode_with_garbage_page_refs_in_table(self):
        """Unallocated tail entries of a block table legitimately point
        at page 0; the causal mask keeps them out of the attend."""
        rs = np.random.RandomState(3)
        ps, NP, B = 8, 4, 2
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B)
        bt = np.asarray(state["block_table"]).copy()
        bt[:, 2:] = 0  # only the first two pages are real
        state["block_table"] = jnp.asarray(bt)
        state["cache_pos"] = jnp.asarray([7, 2 * ps - 1], jnp.int32)
        x = jnp.asarray(rs.randn(B, 1, 32), jnp.float32)
        self._run_both(state, x)


    @pytest.mark.parametrize("quant", [False, True])
    def test_rows_at_contexts_from_one_key_to_capacity(self, quant):
        """Rows of one batch at contexts 1, ps - 1, ps, ps + 1,
        mid-capacity and the whole table: each walks its own number of
        pages (1 to 40) and of key blocks (1 to 3: a block is 16 pages of
        8), the last block cut short."""
        rs = np.random.RandomState(6)
        ps, NP = 8, 40
        ctx = [1, ps - 1, ps, ps + 1, 20 * ps + 3, NP * ps]
        B = len(ctx)
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B,
                             quant=quant)
        state["cache_pos"] = jnp.asarray(ctx, jnp.int32) - 1
        x = jnp.asarray(rs.randn(B, 1, 32), jnp.float32)
        self._run_both(state, x, ps_cap=NP * ps)

    @pytest.mark.parametrize("quant", [False, True])
    def test_frozen_row_reads_the_garbage_page(self, quant):
        """A frozen row as the decode program makes it: its whole table
        row swapped for garbage page 0 at ``pos = cap - 1``. Every slot is
        live and every slot is page 0."""
        rs = np.random.RandomState(7)
        ps, NP, B = 8, 4, 2
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B,
                             quant=quant)
        bt = np.asarray(state["block_table"]).copy()
        bt[0] = 0
        state["block_table"] = jnp.asarray(bt)
        state["cache_pos"] = jnp.asarray([NP * ps - 1, 11], jnp.int32)
        x = jnp.asarray(rs.randn(B, 1, 32), jnp.float32)
        self._run_both(state, x)


class TestDeadSlotsAreNeverRead:
    """Table slots at or past ``ceil((pos + T) / ps)`` hold no key a query
    of the chunk may see. The kernel neither fetches them nor lets what
    its buffers hold in their place reach a product: with every dead slot
    pointing at a page full of NaN (scales of NaN for an int8 pool) the
    output is finite and equals the stock backend's on the clean table."""

    @pytest.mark.parametrize("quant", [False, True])
    @pytest.mark.parametrize("T", [1, 6])
    def test_dead_slots_point_at_a_page_of_nan(self, T, quant):
        rs = np.random.RandomState(8 + T)
        ps, NP, B, H, d = 8, 24, 3, 4, 8
        pages = B * NP + 2
        state = _paged_state(rs, pages=pages, ps=ps, NP=NP, B=B, H=H, d=d,
                             quant=quant)
        poison = pages - 1             # no table row names it
        for key in (("kscales", "vscales") if quant
                    else ("kpages", "vpages")):
            state[key] = state[key].at[poison].set(jnp.nan)
        # one page, a block's worth and a bit, two blocks and a bit
        pos = np.array([2, 16 * ps + 1, 21 * ps - T], np.int32)
        live = -(-(pos + T) // ps)
        slot = np.arange(NP)[None, :]
        clean = np.asarray(state["block_table"])
        clean = np.where(slot < live[:, None], clean, 0)
        dirty = np.where(slot < live[:, None], clean, poison)
        q = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        mask = None
        if T > 1:
            lens = np.array([T, 2, T - 1])
            mask = jnp.asarray(np.arange(T)[None, :] < lens[:, None],
                               jnp.float32)

        want = _attend(ppa.XlaPagedAttention(), state, q, clean, pos, mask)
        got = _attend(ppa.PallasPagedAttention(interpret=True), state, q,
                      dirty, pos, mask)
        assert_output_close(got, want)


class TestHeadGroups:
    """A program takes as many heads as fit VMEM; with less of it the same
    call runs as more programs of fewer heads, each copying its own slice
    of every page, and the output does not change."""

    @pytest.mark.parametrize("quant", [False, True])
    @pytest.mark.parametrize("T", [1, 40])
    def test_fewer_heads_a_program_give_the_same_output(self, T, quant,
                                                        monkeypatch):
        rs = np.random.RandomState(9)
        ps, NP, B, H, d = 16, 24, 3, 4, 128
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B, H=H,
                             d=d, quant=quant)
        pos = jnp.asarray([100, 300, 5], jnp.int32)
        # keys of the int8 pool spread by 3.5, so queries a quarter as
        # wide keep the scores of order one, as 1/sqrt(d) presumes: a
        # rounding of a score is multiplied by its size on its way out
        q = jnp.asarray(0.25 * rs.randn(B, H, T, d), jnp.float32)
        mask = None if T == 1 else jnp.asarray(
            np.arange(T)[None, :] < np.array([[T], [7], [T - 1]]),
            jnp.float32)
        geometry = dict(page_size=ps, head_dim=d, n_pages=NP, chunk=T,
                        quant=quant)

        def attend(helper):
            return _attend(helper, state, q, state["block_table"], pos,
                           mask)

        assert ppa._heads_per_program(H, **geometry) == H
        whole = attend(ppa.PallasPagedAttention(interpret=True))
        monkeypatch.setattr(ppa, "VMEM_LIMIT_BYTES",
                            ppa.vmem_bytes(heads=2, **geometry))
        assert ppa._heads_per_program(H, **geometry) == 2
        halves = attend(ppa.PallasPagedAttention(interpret=True))
        np.testing.assert_array_equal(np.asarray(halves), np.asarray(whole))
        assert_output_close(whole, attend(ppa.XlaPagedAttention()))


class TestPoolOrder:
    """A value plane is ``[pages, page_size, heads * d]``: a token's heads
    side by side in one row of a page. What the page write puts there is
    what a contiguous cache would hold, read back through the layer's own
    dense view, and a tensor-parallel pool split along the lanes
    (``P(None, None, "model")``: a contiguous ``H / tp`` heads of every
    row) writes and reads the same bits as the whole one."""

    PS, NP, B = 4, 4, 2

    def _layer(self, grouped):
        lyr = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4,
                                 n_kv_heads=2 if grouped else 0,
                                 causal=True, max_cache=self.PS * self.NP,
                                 bias_init=0.0)
        return lyr, lyr.init_params(jax.random.PRNGKey(11))

    def _chunks(self, rs):
        """(x, true lengths) of two right-padded chunks: the second starts
        off a page boundary in both rows (5 and 7 of 4-row pages), crosses
        one, and row 1's has a masked tail."""
        return [(jnp.asarray(rs.randn(self.B, 8, 32), jnp.float32),
                 np.array([5, 7])),
                (jnp.asarray(rs.randn(self.B, 6, 32), jnp.float32),
                 np.array([6, 3]))]

    @staticmethod
    def _mask(T, lens):
        return jnp.asarray(np.arange(T)[None, :] < lens[:, None],
                           jnp.float32)

    def _view_equals_contiguous_cache(self, kv, grouped):
        rs = np.random.RandomState(12)
        lyr, params = self._layer(grouped)
        dtype = jnp.bfloat16 if kv == "bfloat16" else jnp.float32
        kv_dtype = "int8" if kv == "int8" else None
        fwd = jax.jit(lambda s, x, m: lyr.forward(params, s, x, mask=m))
        planes = lyr.init_paged_carry(self.B * self.NP + 1, self.PS, dtype,
                                      kv_dtype=kv_dtype)
        H = lyr.kv_heads
        assert planes["kpages"].shape == (self.B * self.NP + 1, self.PS,
                                          H * lyr.d_head)
        bt = jnp.asarray(1 + rs.permutation(self.B * self.NP).reshape(
            self.B, self.NP), jnp.int32)
        dense = lyr.init_streaming_carry(self.B, dtype, kv_dtype=kv_dtype)
        pos = np.zeros(self.B, np.int32)
        for x, lens in self._chunks(rs):
            mask = self._mask(x.shape[1], lens)
            at = jnp.asarray(pos)
            out_p, st = fwd({**planes, "block_table": bt, "cache_pos": at},
                            x, mask)
            planes = {k: st[k] for k in planes}
            out_d, st = fwd({**dense, "cache_pos": at}, x, mask)
            dense = {k: st[k] for k in dense}
            np.testing.assert_array_equal(np.asarray(out_p),
                                          np.asarray(out_d))
            pos = pos + lens           # the caller's watermark, per row
        views = jax.jit(lyr.paged_views)(planes, bt)
        assert set(views) == set(dense) - {"cache_pos"}
        for name, view in views.items():
            assert view.shape == dense[name].shape
            for b in range(self.B):
                np.testing.assert_array_equal(
                    np.asarray(view)[b, :, :pos[b]],
                    np.asarray(dense[name])[b, :, :pos[b]])
                # a masked tail went to the garbage page, not past the row
                assert not np.asarray(view, np.float32)[b, :, pos[b]:].any()

    def _tp2_equals_tp1(self, kv, backend):
        """The layer's head-parallel write and read (``shard_map`` over a
        two-device ``model`` axis) against the single-device ones on the
        same fresh keys and values: the projections around them are not
        part of the split."""
        from deeplearning4j_tpu.nn.conf.layers.attention import _write_chunk
        from deeplearning4j_tpu.parallel.mesh import model_mesh

        rs = np.random.RandomState(13)
        lyr, _ = self._layer(False)
        H, d, T = lyr.kv_heads, lyr.d_head, 6
        quant = kv == "int8"
        dtype = jnp.bfloat16 if kv == "bfloat16" else jnp.float32
        planes = lyr.init_paged_carry(self.B * self.NP + 1, self.PS, dtype,
                                      kv_dtype="int8" if quant else None)
        # resident content, so that the read has more than the chunk
        planes = {k: jnp.asarray(rs.randint(-90, 90, a.shape), a.dtype)
                  if a.dtype == jnp.int8
                  else jnp.asarray(0.1 * rs.rand(*a.shape), a.dtype)
                  for k, a in planes.items()}
        q, k, v = (jnp.asarray(rs.randn(self.B, H, T, d), dtype)
                   for _ in range(3))
        ksc = vsc = None
        if quant:
            k, ksc = lyr._quantize_kv(k)
            v, vsc = lyr._quantize_kv(v)
        bt = jnp.asarray(1 + rs.permutation(self.B * self.NP).reshape(
            self.B, self.NP), jnp.int32)
        pos = jnp.asarray([5, 7], jnp.int32)
        mask = self._mask(T, np.array([6, 3]))
        t_abs = pos[:, None] + jnp.arange(T)[None, :]
        pg = jnp.where(mask.astype(bool), jnp.take_along_axis(
            bt, t_abs // self.PS, axis=1), 0)
        off = t_abs % self.PS
        pool = (planes["kpages"], planes["vpages"], planes.get("kscales"),
                planes.get("vscales"))

        @jax.jit
        def whole(q, k, v, ksc, vsc, pool):
            kp, vp, ksp, vsp = _write_chunk(*pool, k, v, ksc, vsc, pg, off)
            return kp, vp, ksp, vsp, ppa.paged_attend(
                backend, q, kp, vp, bt, pos, mask=mask, kscales=ksp,
                vscales=vsp)

        @jax.jit
        def split(q, k, v, ksc, vsc, pool):
            return lyr._sharded_write_attend(
                backend, model_mesh(2), q, k, v, ksc, vsc, *pool, bt, pos,
                pg, off, mask, quant)

        want = whole(q, k, v, ksc, vsc, pool)
        got = split(q, k, v, ksc, vsc, pool)
        for name, g, w in zip(("kpages", "vpages", "kscales", "vscales",
                               "out"), got, want):
            assert (g is None) == (w is None)
            if g is None:
                continue
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            if name in planes:
                # half the heads of every page row on each device
                heads = lyr.PAGED_HEAD_AXIS[name]
                assert g.sharding.shard_shape(g.shape)[heads] \
                    == g.shape[heads] // 2

    @pytest.mark.parametrize("check,kv,how", [
        ("view", kv, heads) for kv in ("float32", "bfloat16", "int8")
        for heads in ("plain", "grouped")] + [
        ("tp2", "float32", "xla"), ("tp2", "bfloat16", "xla"),
        ("tp2", "int8", "xla"), ("tp2", "float32", "pallas")])
    def test_a_tokens_heads_lie_side_by_side(self, check, kv, how):
        if check == "view":
            self._view_equals_contiguous_cache(kv, how == "grouped")
        else:
            self._tp2_equals_tp1(kv, how)


class TestBackendSelection:
    def test_auto_resolution_per_platform(self):
        geo = dict(page_size=16, head_dim=128, n_pages=32)
        assert ppa.resolve_paged_backend(
            "auto", platform="tpu", **geo) == "pallas"
        assert ppa.resolve_paged_backend(
            "auto", platform="cpu", **geo) == "xla"
        # forced knobs: off-TPU "pallas" is the interpreted kernel; on a
        # TPU it is Mosaic or a loud refusal, never a quiet XLA
        assert ppa.resolve_paged_backend(
            "pallas", platform="cpu", **geo) == "pallas"
        assert ppa.resolve_paged_backend(
            "xla", platform="tpu", **geo) == "xla"
        # the table's length is no limit any more (the kernel walks it);
        # the query chunk a program attends is
        assert ppa.resolve_paged_backend(
            "pallas", platform="tpu", page_size=16, head_dim=128,
            n_pages=1024) == "pallas"
        with pytest.raises(ValueError, match="cannot take"):
            ppa.resolve_paged_backend("pallas", platform="tpu",
                                      page_size=16, head_dim=128,
                                      n_pages=128, chunk=4096)

    def test_supports_geometry_gates(self):
        ok = dict(platform="tpu")
        assert ppa.supports(page_size=16, head_dim=128, n_pages=32, **ok)
        # whole (8, 128) tiles of the pool are what the kernel can copy
        assert not ppa.supports(page_size=10, head_dim=128, n_pages=32,
                                **ok)
        assert not ppa.supports(page_size=16, head_dim=8, n_pages=32,
                                **ok)
        assert not ppa.supports(page_size=16, head_dim=64, n_pages=32,
                                **ok)
        assert ppa.supports(page_size=16, head_dim=256, n_pages=32, **ok)
        # an int8 page's scales lie in one aligned 128-lane window
        assert ppa.supports(page_size=24, head_dim=128, n_pages=32, **ok)
        assert not ppa.supports(page_size=24, head_dim=128, n_pages=32,
                                quant=True, **ok)
        assert ppa.supports(page_size=256, head_dim=128, n_pages=32,
                            quant=True, **ok)
        # scoped-VMEM model, held against Mosaic's verdicts: the ceiling
        # is on the query chunk one head attends, whatever the table holds
        assert ppa.supports(page_size=16, head_dim=128, n_pages=4096,
                            chunk=256, **ok)
        assert ppa.supports(page_size=16, head_dim=128, n_pages=128,
                            chunk=2048, **ok)
        assert not ppa.supports(page_size=16, head_dim=128, n_pages=128,
                                chunk=3072, **ok)
        assert not ppa.supports(page_size=16, head_dim=256, n_pages=128,
                                chunk=2048, **ok)
        # off-TPU: interpret mode is never a serving win
        assert not ppa.supports(page_size=16, head_dim=128, n_pages=32,
                                platform="cpu")

    @pytest.mark.parametrize("chunk,quant,hb", [
        (1, False, 12), (1, True, 12), (256, False, 6), (256, True, 6),
        (512, False, 4), (1024, False, 2), (2048, True, 1)])
    def test_head_group_follows_the_chunk(self, chunk, quant, hb):
        """One copy brings a page for as many heads as fit VMEM beside the
        chunk's state: all twelve at a decode step, fewer under a prefill
        chunk (the groups the cgpt cell's programs run with)."""
        assert ppa._heads_per_program(
            12, page_size=16, head_dim=128, n_pages=128, chunk=chunk,
            quant=quant) == hb

    def test_unknown_backend_raises(self):
        with pytest.raises(ValueError, match="unknown paged_attention"):
            ppa.resolve_paged_backend("cudnn", page_size=16, head_dim=64,
                                      n_pages=4)
        with pytest.raises(ValueError, match="unknown paged_attention"):
            ppa.get_paged_helper("auto")  # must be RESOLVED first

    def test_traced_choice_raises(self):
        """The retrace hazard the graftcheck fixture pins: a backend
        chosen on a traced value must fail loudly at trace time."""

        def bad(x):
            return ppa.resolve_paged_backend(
                x, page_size=16, head_dim=64, n_pages=4)

        with pytest.raises(TypeError, match="static host config"):
            jax.jit(bad)(jnp.float32(1.0))


class TestDebugOverflowAssert:
    """The per-dispatch host-sync capacity check is debug-opt-in only
    (the hot path must not pay a device->host sync; admission lives in
    the caller's page accounting)."""

    def _overflowing_call(self):
        rs = np.random.RandomState(4)
        ps, NP, B = 8, 2, 1
        lyr = _layer()
        params = lyr.init_params(jax.random.PRNGKey(0))
        state = _paged_state(rs, pages=B * NP + 1, ps=ps, NP=NP, B=B)
        state["cache_pos"] = jnp.asarray([NP * ps - 1], jnp.int32)
        x = jnp.asarray(rs.randn(B, 2, 32), jnp.float32)  # 1 past cap
        return lyr.forward(params, state, x)

    def test_silent_by_default(self, monkeypatch):
        monkeypatch.delenv("DL4J_TPU_PAGED_DEBUG", raising=False)
        self._overflowing_call()  # no host sync, no raise

    def test_debug_mode_asserts(self, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_PAGED_DEBUG", "1")
        with pytest.raises(ValueError, match="paged KV overflow"):
            self._overflowing_call()


class TestServerParity:
    """End-to-end serving parity: a paged_attention="pallas" server must
    emit the exact token streams of the stock server, greedy AND
    sampled, and tag its program-cache keys with the backend so the
    families never share traces."""

    def _serve(self, lm, backend, reqs):
        from deeplearning4j_tpu.parallel.generation import GenerationServer

        srv = GenerationServer(lm, V, slots=3, paged_attention=backend)
        try:
            assert srv._pa == backend
            futs = [srv.submit(p, s, temperature=t, top_k=k, seed=seed)
                    for p, s, t, k, seed in reqs]
            outs = [f.result(timeout=120) for f in futs]
            cached = [key for key in lm._output_cache
                      if key and key[0] in ("gen_decode", "gen_prefill")]
        finally:
            srv.close()
        return outs, cached

    def test_greedy_and_sampled_token_parity(self, lm):
        rs = np.random.RandomState(5)
        reqs = [(rs.randint(0, V, 3), 6, 0.0, 0, 0),
                (rs.randint(0, V, 5), 5, 0.8, 5, 7),
                (rs.randint(0, V, 9), 4, 1.2, 0, 11)]
        outs_x, keys_x = self._serve(lm, "xla", reqs)
        outs_p, keys_p = self._serve(lm, "pallas", reqs)
        for got, ref in zip(outs_p, outs_x):
            np.testing.assert_array_equal(got, ref)
        # backend-tagged program cache: each family traced its OWN
        # programs — the tag is the last key element
        assert all(k[-1] == "xla" for k in keys_x)
        assert any(k[-1] == "pallas" for k in keys_p)

    def test_viewed_counter_books_the_live_pages(self, lm):
        """What a decode dispatch's reads fetched, as the loop books it:
        under ``pallas`` each advancing row's live pages times the page
        size, ``ceil(ctx / ps) * ps`` a micro-step and paged layer, so it
        lies within a page a row of the live keys; the dense view's
        booking (rows x capacity, pinned in tests/test_falcon_h1.py) is
        far above it."""
        from deeplearning4j_tpu.parallel.generation import GenerationServer

        ps, m_steps, layers = 4, 2, 1
        reqs = [(np.arange(1, 1 + n) % V, k) for n, k in ((3, 5), (6, 4),
                                                          (9, 7))]
        srv = GenerationServer(lm, V, slots=3, page_size=ps,
                               steps_per_dispatch=m_steps,
                               paged_attention="pallas")
        try:
            assert srv._paged_names == ["attn0"]
            for f in [srv.submit(p, k) for p, k in reqs]:
                f.result(timeout=120)
            snap = srv.metrics.snapshot()
        finally:
            srv.close()
        live = snap["generation_kv_live_tokens_total"]["program=decode"]
        viewed = snap["generation_kv_viewed_tokens_total"]["program=decode"]
        steps = snap["generation_decode_steps_total"]
        # a decoded token at context c had c keys to read; a dispatch of
        # two may run one micro-step past a request's end
        need = layers * sum(sum(range(len(p) + 1, len(p) + k))
                            for p, k in reqs)
        assert need <= live <= need + layers * sum(len(p) + k
                                                   for p, k in reqs)
        assert viewed % ps == 0
        assert live < viewed < live + 3 * steps * m_steps * layers * ps
        # the contexts are known, so is the sum: each request decodes from
        # len(p) + 1 keys on, in dispatches of two micro-steps
        def booked(n, k):
            stop = n + 1 + -(-(k - 1) // m_steps) * m_steps
            return sum(-(-c // ps) * ps for c in range(n + 1, stop))
        assert viewed == layers * sum(booked(len(p), k) for p, k in reqs)
        assert viewed < steps * m_steps * 3 * 16 * layers

    def test_invalid_knob_rejected(self, lm):
        from deeplearning4j_tpu.parallel.generation import GenerationServer

        with pytest.raises(ValueError, match="paged_attention"):
            GenerationServer(lm, V, slots=2, paged_attention="cudnn")

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_int8_greedy_parity_between_backends(self, backend, lm):
        """int8 pools through each backend agree with the OTHER backend's
        int8 stream bitwise (the quantization delta itself is covered by
        test_quantize.py — here both families see identical pools)."""
        from deeplearning4j_tpu.parallel.generation import GenerationServer

        prompt = np.array([2, 5, 7, 1], np.int64)
        srv = GenerationServer(lm, V, slots=2, kv_dtype="int8",
                               paged_attention=backend)
        try:
            out = srv.submit(prompt, 5).result(timeout=120)
        finally:
            srv.close()
        if not hasattr(type(self), "_int8_ref"):
            type(self)._int8_ref = {}
        type(self)._int8_ref[backend] = out
        if len(type(self)._int8_ref) == 2:
            np.testing.assert_array_equal(type(self)._int8_ref["xla"],
                                          type(self)._int8_ref["pallas"])
