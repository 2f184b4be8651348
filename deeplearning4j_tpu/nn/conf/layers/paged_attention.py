"""Paged-attention helper seam: XLA fallback + Pallas block-table kernel.

The reference ships accelerated layer math behind ``*Helper`` seams with an
always-available stock fallback (ConvolutionLayer.java:68-79 reflective
cuDNN load; helper-vs-stock parity tests under deeplearning4j-cuda/). This
module is that seam for the paged-KV decode path, the hottest serving loop
in the repo:

- :class:`XlaPagedAttention` — the stock backend. Gathers each row's block
  table into a dense ``[B, H, Tmax, d]`` view and attends; this IS the math
  that used to live inline in ``SelfAttentionLayer._paged_forward``, so it
  is bit-exact by construction and runs anywhere XLA does. A layer with
  grouped heads or a stated scale reads the view through
  ``attention.chunk_read``: on a TPU a prefill chunk takes its blocked
  Pallas kernel there, which this seam does not select.
- :class:`PallasPagedAttention` — the accelerated backend. A Pallas kernel
  whose DMA traffic and arithmetic follow each row's LIVE pages,
  ``n_live[b] = min(NP, ceil((cache_pos[b] + T) / ps))``, not the table's
  capacity. The grid is ``(B, H / hb)``: one program a row and head group
  (``hb`` is the largest divisor of the head count whose buffers fit VMEM:
  all 12 heads of the cgpt cell at a decode step, 6 under a 256-row
  prefill chunk). Block table and positions are scalar-prefetched; the
  pool stays in HBM and the program copies the pages its table row names,
  one ``[ps, hb * d]`` copy a page (the whole contiguous page, 96 KB at
  12 float32 heads; a tile-aligned lane window of it under a smaller
  group), a key block of 128 keys at a time, double-buffered, in a loop
  whose trip count is ``ceil(n_live / pages a block)``. Table slots at or
  past ``n_live`` are never dereferenced. A head's keys are a static lane
  slice of the block; scores, softmax and the weighted sum run block by
  block through the online recurrence in float32. An int8
  pool is dequantised in the kernel against its f32 scales on the score
  side, ``(q . k8) * ks`` and ``(p * vs) . v8`` (a page's scales arrive as one
  aligned window of each head's row of the head-major scale plane, which
  XLA lays out once a call).
  Per-row ``cache_pos`` causal masking and the chunk-validity plane use
  the stock path's expressions. Query heads may share a key/value head
  (the ``G`` heads of one stacked as a program head's rows, so a page copy
  serves them all), the score factor may be stated, and a window makes
  the walk begin at a row's first live page: what a grouped, scaled or
  windowed layer hands it at a decode step (its wider chunks keep the
  stock path's reads).

The pool's order. A value plane is ``[pages, page_size, heads * d]``: a
page is ``page_size`` rows, a row one token's heads side by side, head
``h`` in lanes ``[h * d, (h + 1) * d)``. It is the order XLA's page write
(a scatter of ``[B, T, heads * d]`` rows) runs in place on the chip, and
row-major, so a Mosaic call takes the plane as it lies: writer and reader
agree, and no program transposes the pool between them (with heads before
page rows every serving program did, twice a layer a step). With
``d % 128 == 0`` and ``page_size % 8 == 0`` a page is whole (8, 128)
tiles and nothing pads. An int8 pool's scale planes stay
``[pages, heads, page_size]`` (1/32 of the bytes; see the kernel).

What parity means (tests/test_paged_attention.py pins it): the updated
pool, which the kernel never writes, is bitwise equal under both backends;
the attended output is the same float32 sums taken block by block instead
of over one ``Tmax``-wide row, so it agrees with the stock path to float32
rounding (``rtol`` 1e-6), not bit for bit; served tokens are equal. A dead
table slot may point anywhere (a page of NaN in the tests): nothing of it
reaches the output.

Selection is per-platform and by structure: ``resolve_paged_backend
("auto")`` picks the kernel on TPU when :func:`supports` accepts the
geometry and what the layers ask of it (``group`` query heads a key/value
head; ``None`` for a plane with no head axis), the stock path everywhere
else. CPU CI exercises the kernel in ``interpret=True`` mode for
parity gating only — interpret mode is not a performance path.

Only the READ side (attend over resident pages) lives behind the seam. The
write side — scattering the fresh chunk through the block table, including
the garbage-page-0 routing for masked columns — stays shared in
``_paged_forward`` so COW/prefix-sharing/snapshot semantics are identical
under every backend.

Tensor-parallel (mesh-sharded) serving hands BOTH backends a *local head
shard* of the pool instead of the full pool: ``SelfAttentionLayer``
handed a mesh by its server runs the write + attend inside ``shard_map``, so
``attend`` sees ``kp``/``vp`` as ``[P, ps, (H/tp) * d]`` (scale planes
``[P, H/tp, ps]``) and ``q`` as ``[B, H/tp, T, d]`` with the block table
and ``cache_pos`` replicated. Neither backend needs to know: every shape
here is taken from the operands, so the XLA gather runs over the local
pool shard and the Pallas kernel groups the ``H/tp`` local heads. Head
contexts are independent, so per-shard outputs concatenate exactly (the
same values at every tp).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: Scoped VMEM a Mosaic kernel may use on a v5e core unless it raises
#: ``vmem_limit_bytes`` (this kernel does not).
VMEM_LIMIT_BYTES = 16 << 20

#: What Mosaic's own stack took beyond the buffers :func:`vmem_bytes`
#: counts in the closest fit of the sweep its docstring names.
MOSAIC_STACK_BYTES = 1 << 20


BACKENDS = ("xla", "pallas")
CHOICES = ("auto",) + BACKENDS


def _key_valid_plane(mask, pos, T, Tmax):
    """[B, Tmax] key validity over the cache axis for a masked chunk:
    columns belonging to this chunk take the chunk mask, everything older
    stays valid. Shared by both backends (the Pallas kernel consumes the
    plane as an input) so the masking arithmetic cannot drift."""
    colv = jnp.arange(Tmax)[None, :]
    rel = colv - pos[:, None]                                # [B, Tmax]
    chunk_valid = jnp.take_along_axis(
        mask.astype(bool), jnp.clip(rel, 0, T - 1), axis=1)
    return jnp.where((rel >= 0) & (rel < T), chunk_valid, True)


def dense_values(rows, head_dim):
    """The pages each row's table names, ``plane[bt]`` of a value plane
    (``[B, NP, ps, H * d]``), as the cache a contiguous layout would hold:
    ``[B, H, NP * ps, d]``."""
    B, NP, ps, _ = rows.shape
    return rows.reshape(B, NP * ps, -1, head_dim).transpose(0, 2, 1, 3)


def dense_scales(rows):
    """The same for an int8 pool's scale plane: ``[B, NP, H, ps]`` ->
    ``[B, H, NP * ps]``."""
    B, NP, H, ps = rows.shape
    return rows.transpose(0, 2, 1, 3).reshape(B, H, NP * ps)


class PagedAttentionHelper:
    """One paged-attention read backend: attend a ``[B, H, T, d]`` query
    chunk over the pool pages its block table names. ``attend`` returns
    the pre-projection context ``[B, H, T, d]``; writing the fresh chunk
    into the pool is NOT the helper's job (the seam covers reads only)."""

    name = "base"

    def attend(self, q, kp, vp, bt, pos, *, mask=None,
               kscales=None, vscales=None, scale=None, barrier=False,
               window=0):
        raise NotImplementedError


class XlaPagedAttention(PagedAttentionHelper):
    """Stock backend: gather-then-attend, verbatim the math that shipped
    inline in ``_paged_forward`` — the always-available fallback every
    accelerated backend must match bit-for-bit. For a plain layer only:
    a grouped or scaled read goes through ``attention.chunk_read``, which
    on a TPU takes a prefill chunk through its Pallas kernel (the same
    sums in another order, to float32 rounding, not bit for bit)."""

    name = "xla"

    def attend(self, q, kp, vp, bt, pos, *, mask=None,
               kscales=None, vscales=None, scale=None, barrier=False,
               window=0):
        B, _H, T, d = q.shape
        Tmax = bt.shape[1] * kp.shape[1]
        # gather each row's logical cache view:
        # [B,NP,ps,H*d] -> [B,H,Tmax,d]
        kc = dense_values(kp[bt], d)
        vc = dense_values(vp[bt], d)
        if kscales is not None:
            ksv = dense_scales(kscales[bt])
            vsv = dense_scales(vscales[bt])
            kc = kc.astype(q.dtype) * ksv[..., None].astype(q.dtype)
            vc = vc.astype(q.dtype) * vsv[..., None].astype(q.dtype)
        if scale is not None:
            # grouped heads / a stated scale (the layer is not ``plain``):
            # the pool holds the key/value heads only, nothing is repeated
            from deeplearning4j_tpu.nn.conf.layers.attention import (
                chunk_read)

            return chunk_read(q, kc, vc, pos, mask, scale, window,
                              barrier=barrier)
        logits = jnp.einsum("bhtd,bhkd->bhtk", q, kc) / jnp.sqrt(
            jnp.asarray(d, q.dtype))
        col = jnp.arange(Tmax)[None, None, None, :]
        row = jnp.arange(T)[None, None, :, None]
        logits = jnp.where(col <= pos.reshape(-1, 1, 1, 1) + row,
                           logits, NEG_INF)
        if mask is not None:
            key_valid = _key_valid_plane(mask, pos, T, Tmax)
            logits = jnp.where(key_valid[:, None, None, :], logits,
                               NEG_INF)
        return jnp.einsum("bhtk,bhkd->bhtd",
                          jax.nn.softmax(logits, axis=-1), vc)


def first_live_page(pos, window, page_size):
    """The logical page of the oldest key the query at ``pos`` sees under
    a ``window`` of at least one key; no later query sees a key before
    it."""
    return jnp.maximum(pos - window + 1, 0) // page_size


def _paged_attn_kernel(bt_ref, pos_ref, *refs, T, d, ps, NP, kbp, hb,
                       quant, has_mask, G=1, scale=None, window=0):
    """One (row, head group) program: walk the row's LIVE pages.

    ``n_live = min(NP, ceil((pos + T) / ps))`` table slots hold keys a
    query of this chunk may see; only those are dereferenced. They are
    fetched ``kbp`` pages (one key block) at a time, each page one copy of
    ``[ps, hb * d]``, the group's lanes of the page's rows (the whole page
    where the group is every head), double-buffered so block ``j + 1``
    lands while block ``j`` is attended. A head's keys are lanes
    ``[h * d, (h + 1) * d)`` of the block, sliced out and stacked
    ``[hb, bk, d]`` for products batched over the heads (a loop of
    per-head products measured 20-50% slower at a decode step: PERF.md,
    PR 35). The softmax is the online recurrence over key blocks (running
    max ``m``, denominator ``l``, unnormalised context ``acc``, all
    float32), divided once at the end.

    ``G`` query heads a key/value head: a program's head is then a
    key/value head, and its ``G * T`` query rows are the ``G`` heads that
    read it stacked, row ``r`` being query column ``r % T``, so a key block
    serves all of them. ``scale``: the factor on the scores (``None``: a
    division by ``sqrt(d)``). ``window`` (0: none): a key is seen only
    from the ``window`` latest positions, and the walk begins at the row's
    first live page (:func:`first_live_page` of its query 0), never
    dereferencing a table slot before it.

    A block's tail past ``n_live`` is never written: its buffer rows hold
    whatever was there. Their scores are masked by the causal test (a dead
    column lies past ``pos + T``) and their value rows are zeroed before
    the product (an int8 pool's get a scale of 0), since ``0 * NaN`` is
    NaN."""
    refs = iter(refs)

    def take(n, present=True):
        return [next(refs) if present else None for _ in range(n)]

    q_ref, kp_hbm, vp_hbm = take(3)
    ks_hbm, vs_hbm = take(2, quant)
    kv_ref, = take(1, has_mask)
    o_ref, kbuf, vbuf = take(3)
    ksbuf, vsbuf = take(2, quant)
    sems, m_sc, l_sc, acc_sc = refs
    b = pl.program_id(0)
    h0 = pl.program_id(1) * hb
    bk = kbp * ps
    rows = G * T
    sw = _scale_window(ps)
    pos = pos_ref[b]
    n_live = jnp.minimum(NP, (pos + T + ps - 1) // ps)
    # the walk's first table slot: 0, or the window's first live page
    first = first_live_page(pos, window, ps) if window else 0
    n_blk = (n_live - first + kbp - 1) // kbp if window \
        else (n_live + kbp - 1) // kbp

    def start_of(j):
        """The table slot of key block ``j``'s first page."""
        return first + j * kbp if window else j * kbp

    def first_key(j):
        """The position of key block ``j``'s first key."""
        return start_of(j) * ps if window else j * bk

    def live_pages(j):
        return jnp.minimum(kbp, n_live - start_of(j))

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    def block_copies(j, slot, go):
        """Start (or wait for) the copies of block ``j``'s live pages."""
        lanes = pl.ds(pl.multiple_of(h0 * d, 128), hb * d)

        def page_copies(i, _):
            page = bt_ref[b, start_of(j) + i]
            for src, dst, which in ((kp_hbm, kbuf, 0), (vp_hbm, vbuf, 1)):
                go(pltpu.make_async_copy(
                    src.at[page, :, lanes], dst.at[slot, i],
                    sems.at[which, slot]))
            if quant:
                # the aligned window of each head's scale row that holds
                # this page's ps scales
                at = pl.multiple_of(page * ps // sw * sw, sw)
                for src, dst, which in ((ks_hbm, ksbuf, 0),
                                        (vs_hbm, vsbuf, 1)):
                    go(pltpu.make_async_copy(
                        src.at[pl.ds(h0, hb), :, pl.ds(at, sw)],
                        dst.at[slot, i], sems.at[which, slot]))
            return _

        jax.lax.fori_loop(0, live_pages(j), page_copies, None)

    def block(buf, slot):
        """A block as ``[hb, bk, d]``: head ``h``'s keys (values) are
        lanes ``[h * d, (h + 1) * d)`` of every row."""
        return jnp.stack([
            buf[slot, :, :, h * d:(h + 1) * d].astype(jnp.float32).reshape(
                bk, d) for h in range(hb)])

    def scales(sbuf, j, slot):
        """Block ``j``'s f32 scales as ``[hb, 1, bk]``, a key a lane: each
        live page's ps of them rotated from where they lie in their window
        to the page's place in the block; 0 past the live pages. An int8
        block is dequantised by them AFTER its product, on the score side
        (``(q . k8) * ks`` and ``(p * vs) . v8``): no relayout of scales
        into columns, and int8 values are exact in the product."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, sw), 2)

        def page(i, rows):
            off = bt_ref[b, start_of(j) + i] * ps % sw
            here = pltpu.roll(sbuf[slot, i], (i * ps - off + sw) % sw, 2)
            return jnp.where((lane >= i * ps) & (lane < (i + 1) * ps),
                             here, rows)

        return jax.lax.fori_loop(0, live_pages(j), page,
                                 jnp.zeros((hb, 1, sw), jnp.float32))

    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)
    block_copies(0, 0, start)

    def step(j, _):
        slot = j % 2

        @pl.when(j + 1 < n_blk)
        def _():
            block_copies(j + 1, 1 - slot, start)

        block_copies(j, slot, wait)
        q = q_ref[...].astype(jnp.float32)
        s = jnp.einsum("htd,hkd->htk", q, block(kbuf, slot),
                       preferred_element_type=jnp.float32)
        if quant:
            s = s * scales(ksbuf, j, slot)
        if scale is None:
            s = s / jnp.sqrt(jnp.asarray(d, jnp.float32))
        else:
            s = s * scale
        col = first_key(j) + jax.lax.broadcasted_iota(jnp.int32, (rows, bk),
                                                      1)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
        if G > 1:
            # the G heads' rows are stacked: row r is query column r % T
            row = row % T
        # per-row cache_pos causal mask: garbage pages (unallocated /
        # page-0 slots in the table) sit past pos+row and mask out here
        valid = col <= pos + row
        if window:
            valid = valid & (pos + row - col < window)
        if has_mask:
            valid = valid & (kv_ref[j] != 0)
        s = jnp.where(valid[None], s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_sc[...] = m_new
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = block(vbuf, slot)
        if quant:
            # a dead row's int8 bits are finite and its scale is 0
            p = p * scales(vsbuf, j, slot)
        else:
            key = first_key(j) + jax.lax.broadcasted_iota(
                jnp.int32, (1, bk, 1), 1)
            v = jnp.where(key < n_live * ps, v, 0.0)
        acc_sc[...] = alpha * acc_sc[...] + jnp.einsum(
            "htk,hkd->htd", p, v, preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(0, n_blk, step, None)
    o_ref[...] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


def _pages_per_block(page_size, group=1, quant=False):
    """Pages in one key block: 128 keys, the width of a score tile; 256
    where several query heads read a key/value head of a float pool. Such
    a page copy is a few heads wide, and a block of twice the keys halves
    the loop's steps: a trinity decode dispatch's reads took 8.5 ms
    against 10.2 at 128 (TPU v5e; PERF.md §6), 512 keys 8.7. An
    int8 pool's block keeps the one 128-lane window its scales are placed
    in."""
    keys = 256 if group > 1 and not quant else 128
    return max(1, keys // page_size)


def _scale_window(page_size):
    """Lanes of the head-major scale plane fetched for one page: the
    aligned 128 that hold its scales, or the page's own where it is
    longer."""
    return max(128, page_size)


def _pad(n, to):
    return -(-n // to) * to


def vmem_bytes(*, page_size, head_dim, n_pages, chunk, heads=1,
               quant=False, group=1):
    """Scoped VMEM one program of the kernel needs when it attends a
    query chunk of ``chunk`` columns for a group of ``heads`` key/value
    heads, each read by ``group`` query heads (``group * chunk`` rows): the
    double-buffered key and value blocks, the double-buffered query and
    output blocks, the float32 ``m`` / ``l`` / ``acc`` state, and the
    score-sized temporaries of a step; lanes padded to 128 and rows to 8.
    It does not grow with the table: a longer context is more trips of the
    same loop (``n_pages`` sizes the chunk-validity plane alone).

    Held against what Mosaic (libtpu 0.0.34) reports when it compiles for
    a described v5e, over head sizes 128 / 256, pages of 8 to 256 rows,
    chunks of 1 to 3,072, float32 and int8 pools: every group
    :func:`_heads_per_program` picks from it compiled. Nearest the limit
    the buffers alone count less than Mosaic asks (d=128, ps=16, chunk
    3,072, one int8 head: 16.28 MiB asked, 15.56 counted), by less than
    the ``MOSAIC_STACK_BYTES`` added here. Far over the limit it is the
    more pessimistic of the two (chunk 512 with 12 heads: 18.0 MiB asked,
    35.6 counted), which costs a prefill program a smaller head group,
    never a refusal."""
    kbp = _pages_per_block(page_size, group, quant)
    bk = kbp * page_size
    lanes = _pad(head_dim, 128)
    rows = _pad(group * chunk, 8)
    # a pool block as it lies in VMEM (an int8 page of 16 rows fills half
    # a (32, 128) tile) and as float32 for the products
    block = bk * lanes * 4
    pool = 4 * heads * kbp * (_pad(page_size, 32) * lanes if quant
                              else page_size * lanes * 4)
    if quant:
        # the scale windows (a tile a head and page), the widened block
        pool += 4 * heads * kbp * 8 * _scale_window(page_size) * 4 \
            + heads * block
    state = heads * rows * (4 * lanes + lanes + 2 * 128) * 4
    step = heads * (3 * rows * _pad(bk, 128) * 4 + 2 * block)
    plane = 2 * 8 * _pad(n_pages * page_size, bk) * 4
    return pool + state + step + plane + MOSAIC_STACK_BYTES


def _heads_per_program(n_heads, **geometry):
    """The largest divisor of the head count whose group fits VMEM: one
    copy then brings a page for that many heads."""
    for hb in range(n_heads, 0, -1):
        if n_heads % hb == 0 and vmem_bytes(
                heads=hb, **geometry) <= VMEM_LIMIT_BYTES:
            return hb
    raise ValueError(f"no head group of the paged read fits VMEM: "
                     f"{geometry}")


def _in_hbm(pool):
    """Hold a pool operand to the memory its BlockSpec names. Without it
    XLA is free to stage the operand in VMEM where one fits (it did: the
    cgpt decode program's 100 MB K pool, a layout copy away from the call),
    and the kernel's "HBM" page copies then start in VMEM."""
    return pltpu.with_memory_space_constraint(pool, pltpu.HBM)


@functools.partial(jax.jit, static_argnames=("interpret", "scale",
                                             "window"))
def _pallas_paged_attention(q, kp, vp, bt, pos, key_valid, kscales,
                            vscales, *, interpret, scale=None, window=0):
    """The kernel's call. Jitted so that a serving program traces and
    lowers it once, not once a layer: its 18 calls share one jaxpr (0.5 s
    a program otherwise, 13 s of a server's set-up over 24 programs).
    The pool's heads are key/value heads; ``q``'s ``H`` query heads read
    them ``G = H / Hkv`` at a time, stacked as one program head's rows."""
    B, H, T, d = q.shape
    ps = kp.shape[1]
    NP = bt.shape[1]
    Hkv = kp.shape[2] // d
    G = H // Hkv
    rows = G * T
    quant = kscales is not None
    has_mask = key_valid is not None
    kbp = _pages_per_block(ps, G, quant)
    bk = kbp * ps
    hb = _heads_per_program(Hkv, page_size=ps, head_dim=d, n_pages=NP,
                            chunk=T, quant=quant, group=G)
    kernel = functools.partial(_paged_attn_kernel, T=T, d=d, ps=ps, NP=NP,
                               kbp=kbp, hb=hb, quant=quant,
                               has_mask=has_mask, G=G, scale=scale,
                               window=window)
    if G > 1:
        # query head j reads key/value head j // G: the G heads of one
        # key/value head, stacked, are one program head's G * T rows
        q = q.reshape(B, Hkv, rows, d)
    # index maps receive (*grid, *prefetch_refs). The pool stays in HBM:
    # the kernel copies the pages its table row names.
    qo_spec = pl.BlockSpec((None, hb, rows, d),
                           lambda b, g, bt, pos: (b, g, 0, 0))
    in_specs = [qo_spec] + [pl.BlockSpec(memory_space=pltpu.HBM)] * (
        4 if quant else 2)
    # (the interpreter knows no memory spaces)
    hold = (lambda pool: pool) if interpret else _in_hbm
    args = [q, hold(kp), hold(vp)]
    scratch = [pltpu.VMEM((2, kbp, ps, hb * d), kp.dtype),
               pltpu.VMEM((2, kbp, ps, hb * d), vp.dtype)]
    if quant:
        # [P, H, ps] -> head-major [H, 1, P * ps], lanes padded to whole
        # windows: a head's scales for a page are then inside one aligned
        # window of its row (Mosaic cuts an HBM operand in whole tiles
        # only, and [P, H, ps] has none to cut). XLA reads the plane once
        # a call for it, 1/32 of the pool's bytes
        sw = _scale_window(ps)

        def head_major(planes):
            flat = planes.transpose(1, 0, 2).reshape(Hkv, 1, -1)
            return jnp.pad(flat, ((0, 0), (0, 0),
                                  (0, -flat.shape[2] % sw)))

        args += [hold(head_major(kscales)), hold(head_major(vscales))]
        scratch += [pltpu.VMEM((2, kbp, hb, 1, sw), jnp.float32),
                    pltpu.VMEM((2, kbp, hb, 1, sw), jnp.float32)]
    if has_mask:
        # [B, blocks, 1, bk]: a key block's validity is one (1, bk) row
        n_blk = -(-NP // kbp)
        plane = key_valid.astype(jnp.float32)
        if window:
            # the walk's key blocks start at the row's first live page:
            # so does its plane (columns past the table hold no key)
            start = first_live_page(pos, window, ps) * ps
            plane = jnp.take_along_axis(
                jnp.pad(plane, ((0, 0), (0, NP * ps))),
                start[:, None] + jnp.arange(NP * ps)[None, :], axis=1)
        plane = jnp.pad(plane, ((0, 0), (0, n_blk * bk - NP * ps)))
        in_specs.append(pl.BlockSpec((None, n_blk, 1, bk),
                                     lambda b, g, bt, pos: (b, 0, 0, 0)))
        args.append(plane.reshape(B, n_blk, 1, bk))
    scratch += [pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hb, rows, 1), jnp.float32),
                pltpu.VMEM((hb, rows, 1), jnp.float32),
                pltpu.VMEM((hb, rows, d), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv // hb),
        in_specs=in_specs,
        out_specs=qo_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, d), q.dtype),
        interpret=interpret,
    )(bt, pos.astype(jnp.int32), *args)
    return out.reshape(B, H, T, d) if G > 1 else out


class PallasPagedAttention(PagedAttentionHelper):
    """Accelerated backend: the Pallas kernel that walks each row's live
    pages. Query heads may share a key/value head (``H`` a multiple of the
    pool's heads), ``scale`` states the score factor and ``window`` limits
    what a query sees to its latest keys (the walk then starts at the
    row's first live page): what a grouped, scaled or windowed layer hands
    it at a decode step.

    ``interpret=None`` auto-selects interpreter mode off-TPU (the CPU CI
    parity configuration); pass ``False`` to require a real Mosaic
    compile."""

    name = "pallas"

    def __init__(self, interpret=None):
        self.interpret = interpret

    def attend(self, q, kp, vp, bt, pos, *, mask=None,
               kscales=None, vscales=None, scale=None, barrier=False,
               window=0):
        interpret = self.interpret
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        T = q.shape[2]
        Tmax = bt.shape[1] * kp.shape[1]
        key_valid = None
        if mask is not None:
            # the chunk-validity plane is tiny [B, Tmax] XLA math shared
            # with the stock path; the kernel consumes it as an input
            key_valid = _key_valid_plane(mask, pos, T, Tmax)
        return _pallas_paged_attention(q, kp, vp, bt, pos, key_valid,
                                       kscales, vscales, interpret=interpret,
                                       scale=scale, window=window)


_HELPERS = {
    "xla": XlaPagedAttention(),
    "pallas": PallasPagedAttention(),
}


def supports(*, page_size, head_dim, n_pages, chunk=1, quant=False,
             platform=None, group=1):
    """Can the Pallas backend take this pool geometry, at query chunks of
    up to ``chunk`` columns read by ``group`` query heads a key/value head
    (a program attends ``group * chunk`` rows a head), on this platform?
    ``group=None``: a read the kernel cannot take at all (a pool plane with
    no head axis). Static shapes only, so the answer is the same at server
    construction and at trace time."""
    if platform is None:
        platform = jax.default_backend()
    if platform != "tpu" or group is None:
        # off-TPU the kernel would run interpreted — a debugging mode,
        # never a serving win: auto falls back to stock
        return False
    # the kernel copies a head group's lanes of a page, [ps, heads * d], out
    # of the pool itself, and Mosaic cuts an HBM operand in whole (8, 128)
    # tiles: with these two the folded plane is whole tiles, nothing pads
    if page_size % 8 or head_dim % 128:
        return False
    # an int8 pool's scales are fetched as the aligned 128-lane window of
    # the head-major plane that holds a page's ps of them
    if quant and 128 % page_size and page_size % 128:
        return False
    return vmem_bytes(page_size=page_size, head_dim=head_dim,
                      n_pages=n_pages, chunk=chunk, quant=quant,
                      group=group) <= VMEM_LIMIT_BYTES


def resolve_paged_backend(choice, *, page_size, head_dim, n_pages,
                          chunk=1, quant=False, platform=None, group=1):
    """Resolve a ``paged_attention`` knob to a concrete backend name.

    ``choice``: "auto" (Pallas on TPU when :func:`supports` accepts the
    geometry, XLA everywhere else), or a forced "xla"/"pallas". A forced
    "pallas" raises for a read the kernel cannot take (``group=None``)
    and, on a TPU, for a geometry :func:`supports` declines (a query
    chunk over the VMEM limit, where Mosaic would refuse it less legibly,
    or an alignment Mosaic cannot copy); off-TPU it selects the
    interpreted kernel (the CPU parity configuration). The
    result is a trace-time constant — callers key program caches on it so
    backend families never share traces. The knob must be host config,
    never data: choosing on a traced value would retrace per value (the
    graftcheck jax-retrace-hazard rule flags that pattern)."""
    if isinstance(choice, jax.core.Tracer):
        raise TypeError(
            "paged_attention backend must be static host config, got a "
            "traced value — branching on it would retrace per value")
    if choice not in CHOICES:
        raise ValueError(f"unknown paged_attention backend {choice!r} "
                         f"(expected one of {CHOICES})")
    if choice == "xla":
        return choice
    if platform is None:
        platform = jax.default_backend()
    ok = supports(page_size=page_size, head_dim=head_dim, n_pages=n_pages,
                  chunk=chunk, quant=quant, platform=platform, group=group)
    if choice == "pallas":
        if group is None:
            raise ValueError(
                "paged_attention='pallas' reads pages of key/value heads "
                "on one chip; a pool plane with no head axis, or grouped "
                "heads split over a mesh, is served through 'xla' ('auto' "
                "selects it)")
        if platform == "tpu" and not ok:
            need = vmem_bytes(page_size=page_size, head_dim=head_dim,
                              n_pages=n_pages, chunk=chunk, quant=quant,
                              group=group)
            raise ValueError(
                f"paged_attention='pallas' cannot take page_size="
                f"{page_size}, head_dim={head_dim}, n_pages={n_pages}, "
                f"chunk={chunk} x {group} query heads on a TPU: it needs "
                f"page_size % 8 == 0 (a divisor or a multiple of 128 for "
                f"an int8 pool), head_dim % 128 == 0 and {need} <= "
                f"{VMEM_LIMIT_BYTES} bytes of VMEM for one key/value "
                "head; 'auto' serves such a pool through XLA")
        return choice
    return "pallas" if ok else "xla"


def get_paged_helper(backend) -> PagedAttentionHelper:
    try:
        return _HELPERS[backend]
    except KeyError:
        raise ValueError(f"unknown paged_attention backend {backend!r} "
                         f"(expected one of {BACKENDS})") from None


def paged_attend(backend, q, kp, vp, bt, pos, *, mask=None,
                 kscales=None, vscales=None, scale=None, barrier=False,
                 window=0):
    """Dispatch one paged-attention read through the selected backend.
    ``backend`` is a resolved name (see :func:`resolve_paged_backend`),
    static at trace time. ``barrier``: ``grouped_attention``'s (the XLA
    backend's grouped read alone). ``window`` (0: none): a query sees its
    ``window`` latest keys alone."""
    helper = get_paged_helper(backend)
    return helper.attend(q, kp, vp, bt, pos, mask=mask, kscales=kscales,
                         vscales=vscales, scale=scale, barrier=barrier,
                         window=window)
