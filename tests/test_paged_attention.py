"""PagedAttentionHelper seam: XLA-vs-Pallas(interpret) bit-exactness.

Ports the reference's helper-vs-stock parity discipline (cuDNN
``*Helper`` vs pure ND4J under deeplearning4j-cuda/) to the paged-KV
decode read: the Pallas block-table kernel
(nn/conf/layers/paged_attention.py) must be BITWISE identical to the
stock gather-then-attend backend across f32/int8 pools, greedy and
sampled serving, and the edge geometries the block-table walk can get
wrong — a row's position exactly on a page boundary, a prefill chunk
straddling two pages, and an all-masked chunk whose writes route to
garbage page 0.

Parity is asserted UNDER JIT on both sides — the production
configuration (every serving program is jitted), and the only honest
one: XLA rewrites ``x / const`` to a reciprocal multiply inside any
compiled program, including the interpreted kernel body, so an eager
stock reference would differ from BOTH compiled paths by one ulp at
head dims whose ``sqrt`` is not a power of two.

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



def _layer(n_heads=4, ps_cap=32):
    return SelfAttentionLayer(n_in=32, n_out=32, n_heads=n_heads,
                              causal=True, max_cache=ps_cap, bias_init=0.0)


def _paged_state(rs, *, pages, ps, NP, B, H=4, d=8, quant=False):
    """A pool with random resident content, distinct per-row pages (page
    0 reserved as the garbage sink), and a [B, NP] block table."""
    if quant:
        state = {
            "kpages": jnp.asarray(rs.randint(
                -127, 128, (pages, H, ps, d)), jnp.int8),
            "vpages": jnp.asarray(rs.randint(
                -127, 128, (pages, H, ps, d)), jnp.int8),
            "kscales": jnp.asarray(rs.rand(pages, H, ps) * 0.05,
                                   jnp.float32),
            "vscales": jnp.asarray(rs.rand(pages, H, ps) * 0.05,
                                   jnp.float32),
        }
    else:
        state = {
            "kpages": jnp.asarray(rs.randn(pages, H, ps, d), jnp.float32),
            "vpages": jnp.asarray(rs.randn(pages, H, ps, d), jnp.float32),
        }
    perm = rs.permutation(pages - 1)[:B * NP] + 1
    state["block_table"] = jnp.asarray(perm.reshape(B, NP), jnp.int32)
    return state


class TestLayerParity:
    """One layer, jitted under the xla and under the pallas backend, each
    handed to it as a server hands it (a static entry of the state, put
    in inside the traced function): output AND updated pool bitwise
    equal, across the edge geometries the kernel must match."""

    def _run_both(self, state, x, mask=None, seed=0):
        lyr = _layer()
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
        np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_x))
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
        with pytest.raises(ValueError, match="cannot take"):
            ppa.resolve_paged_backend("pallas", platform="tpu",
                                      page_size=16, head_dim=128,
                                      n_pages=1024)

    def test_supports_geometry_gates(self):
        ok = dict(platform="tpu")
        assert ppa.supports(page_size=16, head_dim=128, n_pages=32, **ok)
        # sublane / lane alignment
        assert not ppa.supports(page_size=10, head_dim=128, n_pages=32,
                                **ok)
        assert not ppa.supports(page_size=16, head_dim=8, n_pages=32,
                                **ok)
        # scoped-VMEM model, fitted to Mosaic's verdicts at d=128: the
        # ceiling moves with the query chunk the program attends
        assert ppa.supports(page_size=16, head_dim=128, n_pages=256,
                            chunk=512, **ok)
        assert ppa.supports(page_size=16, head_dim=128, n_pages=512,
                            chunk=16, **ok)
        assert not ppa.supports(page_size=16, head_dim=128, n_pages=512,
                                chunk=256, **ok)
        assert not ppa.supports(page_size=16, head_dim=128, n_pages=1024,
                                **ok)
        # off-TPU: interpret mode is never a serving win
        assert not ppa.supports(page_size=16, head_dim=128, n_pages=32,
                                platform="cpu")

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
