"""Paged-attention helper seam: XLA fallback + Pallas block-table kernel.

The reference ships accelerated layer math behind ``*Helper`` seams with an
always-available stock fallback (ConvolutionLayer.java:68-79 reflective
cuDNN load; helper-vs-stock parity tests under deeplearning4j-cuda/). This
module is that seam for the paged-KV decode path, the hottest serving loop
in the repo:

- :class:`XlaPagedAttention` — the stock backend. Gathers each row's block
  table into a dense ``[B, H, Tmax, d]`` view and attends; this IS the math
  that used to live inline in ``SelfAttentionLayer._paged_forward``, so it
  is bit-exact by construction and runs anywhere XLA does.
- :class:`PallasPagedAttention` — the accelerated backend. A Pallas kernel
  that walks the block table via scalar prefetch and streams K/V pages from
  the pool straight into VMEM (no materialized ``[B, H, Tmax, d]`` gather in
  HBM — the gather cost that dominates long-context decode). int8 dequant
  against the f32 ``kscales``/``vscales`` planes happens in-kernel as pages
  load; per-row ``cache_pos`` causal masking and the chunk-validity plane
  use the same expressions as the stock path, so interpret-mode output is
  bitwise identical to it (tests/test_paged_attention.py pins this).

Selection is per-platform: ``resolve_paged_backend("auto")`` picks the
kernel on TPU when :func:`supports` accepts the geometry and the stock path
everywhere else. CPU CI exercises the kernel in ``interpret=True`` mode for
parity gating only — interpret mode is not a performance path.

Only the READ side (attend over resident pages) lives behind the seam. The
write side — scattering the fresh chunk through the block table, including
the garbage-page-0 routing for masked columns — stays shared in
``_paged_forward`` so COW/prefix-sharing/snapshot semantics are identical
under every backend.

Tensor-parallel (mesh-sharded) serving hands BOTH backends a *local head
shard* of the pool instead of the full pool: ``SelfAttentionLayer``
handed a mesh by its server runs the write + attend inside ``shard_map``, so
``attend`` sees ``kp``/``vp`` as ``[P, H/tp, ps, d]`` (scale planes
``[P, H/tp, ps]``) and ``q`` as ``[B, H/tp, T, d]`` with the block table
and ``cache_pos`` replicated. Neither backend needs to know: every shape
here is taken from the operands, so the XLA gather runs over the local
pool shard and the Pallas grid becomes ``(B, H/tp, NP)`` — the natural
head-axis cut of its ``(B, H, NP)`` grid. Head contexts are independent,
so per-shard outputs concatenate exactly (bit-exact at every tp).
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


def vmem_bytes(*, page_size, head_dim, n_pages, chunk):
    """Scoped VMEM one (b, h) program of the kernel needs for a query
    chunk of ``chunk`` rows: the two f32 ``[Tmax, d]`` K/V scratch rows,
    one f32 ``[chunk, Tmax]`` score matrix, and the double-buffered
    query/output blocks, lanes padded to 128 and rows to 8. Fitted to
    what Mosaic (libtpu 0.0.34, v5e) reports at the limit — d=128:
    Tmax=7680 chunk=256 and Tmax=4096 chunk=640 compile, Tmax=8192
    chunk=256 asks for 16.4 MiB and Tmax=16384 for 16.0 MiB at chunk 1 —
    and never below it (narrower heads are charged full lanes although
    Mosaic sometimes packs them)."""
    tmax = n_pages * page_size
    lanes = -(-head_dim // 128) * 128
    rows = -(-chunk // 8) * 8
    return 4 * (2 * tmax * lanes + rows * tmax + 4 * rows * lanes)


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


class PagedAttentionHelper:
    """One paged-attention read backend: attend a ``[B, H, T, d]`` query
    chunk over the pool pages its block table names. ``attend`` returns
    the pre-projection context ``[B, H, T, d]``; writing the fresh chunk
    into the pool is NOT the helper's job (the seam covers reads only)."""

    name = "base"

    def attend(self, q, kp, vp, bt, pos, *, mask=None,
               kscales=None, vscales=None, scale=None):
        raise NotImplementedError


class XlaPagedAttention(PagedAttentionHelper):
    """Stock backend: gather-then-attend, verbatim the math that shipped
    inline in ``_paged_forward`` — the always-available fallback every
    accelerated backend must match bit-for-bit."""

    name = "xla"

    def attend(self, q, kp, vp, bt, pos, *, mask=None,
               kscales=None, vscales=None, scale=None):
        B, _H, T, d = q.shape
        ps = kp.shape[2]
        NP = bt.shape[1]
        Tmax = NP * ps
        # gather each row's logical cache view:
        # [B,NP,H,ps,d] -> [B,H,Tmax,d]
        kc = kp[bt].transpose(0, 2, 1, 3, 4).reshape(B, -1, Tmax,
                                                     kp.shape[-1])
        vc = vp[bt].transpose(0, 2, 1, 3, 4).reshape(B, -1, Tmax,
                                                     vp.shape[-1])
        if kscales is not None:
            ksv = kscales[bt].transpose(0, 2, 1, 3).reshape(B, -1, Tmax)
            vsv = vscales[bt].transpose(0, 2, 1, 3).reshape(B, -1, Tmax)
            kc = kc.astype(q.dtype) * ksv[..., None].astype(q.dtype)
            vc = vc.astype(q.dtype) * vsv[..., None].astype(q.dtype)
        if scale is not None:
            # grouped heads / a stated scale (the layer is not ``plain``):
            # the pool holds the key/value heads only, nothing is repeated
            from deeplearning4j_tpu.nn.conf.layers.attention import (
                grouped_attention)

            valid = (jnp.arange(Tmax)[None, None, None, :]
                     <= pos.reshape(-1, 1, 1, 1)
                     + jnp.arange(T)[None, None, :, None])
            if mask is not None:
                valid = valid & _key_valid_plane(mask, pos, T,
                                                 Tmax)[:, None, None, :]
            return grouped_attention(q, kc, vc, valid, scale)
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


def _row_to_col(row):
    """``[1, n]`` lane-oriented row -> ``[n, 1]`` sublane-oriented column
    without a transpose: mask the sublane-broadcast row down to its
    diagonal and reduce over lanes. Every sum is one value plus zeros, so
    the column is bit-equal to the row. (Mosaic has no relayout for a
    sub-tile ``[n] -> [n, 1]`` reshape; broadcast, iota-compare, select
    and a lane reduction it compiles everywhere.)"""
    n = row.shape[1]
    diag = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(diag, row, 0.0), axis=1, keepdims=True)


def _paged_attn_kernel(bt_ref, pos_ref, *refs, T, d, ps, NP, quant,
                       has_mask):
    """One (b, h, page) grid step. The BlockSpec index maps already
    resolved ``bt[b, i]`` through scalar prefetch, so ``kp_ref``/``vp_ref``
    hold THIS row's i-th logical page ``[ps, d]`` — the pool is never
    gathered in HBM. Pages accumulate (dequantized) into VMEM scratch;
    the final page step runs the whole attention row. The scores use the
    exact expressions of the stock path (full dot, max-subtract softmax —
    NOT the online/flash recurrence) so interpret-mode output is bitwise
    identical to :class:`XlaPagedAttention`."""
    if quant:
        if has_mask:
            (q_ref, kp_ref, vp_ref, ks_ref, vs_ref, kv_ref, o_ref,
             k_sc, v_sc) = refs
        else:
            (q_ref, kp_ref, vp_ref, ks_ref, vs_ref, o_ref,
             k_sc, v_sc) = refs
    else:
        if has_mask:
            q_ref, kp_ref, vp_ref, kv_ref, o_ref, k_sc, v_sc = refs
        else:
            q_ref, kp_ref, vp_ref, o_ref, k_sc, v_sc = refs
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)
    Tmax = NP * ps
    k_pg = kp_ref[...].astype(jnp.float32)
    v_pg = vp_ref[...].astype(jnp.float32)
    if quant:
        # in-kernel dequant: int8 page values widen against the page's
        # f32 scale row as it lands in VMEM — elementwise identical to
        # the stock path's post-gather dequant. The scale block is the
        # page's whole [H, ps] plane (the smallest block of a [P, H, ps]
        # array the TPU lowering accepts); this head's row is picked
        # here and turned into a column.
        k_pg = k_pg * _row_to_col(ks_ref[pl.ds(h, 1), :])
        v_pg = v_pg * _row_to_col(vs_ref[pl.ds(h, 1), :])
    row0 = pl.multiple_of(i * ps, ps)
    k_sc[pl.ds(row0, ps), :] = k_pg
    v_sc[pl.ds(row0, ps), :] = v_pg

    @pl.when(i == NP - 1)
    def _attend():
        q = q_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_sc[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / jnp.sqrt(
                jnp.asarray(d, jnp.float32))
        col = jax.lax.broadcasted_iota(jnp.int32, (T, Tmax), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (T, Tmax), 0)
        # per-row cache_pos causal mask: garbage pages (unallocated /
        # page-0 slots in the table) sit past pos+row and mask out here
        s = jnp.where(col <= pos_ref[b] + row, s, NEG_INF)
        if has_mask:
            s = jnp.where(kv_ref[...] != 0, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o_ref[...] = jax.lax.dot_general(
            w, v_sc[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _pallas_paged_attention(q, kp, vp, bt, pos, key_valid, kscales,
                            vscales, *, interpret):
    B, H, T, d = q.shape
    ps = kp.shape[2]
    NP = bt.shape[1]
    Tmax = NP * ps
    quant = kscales is not None
    has_mask = key_valid is not None
    kernel = functools.partial(_paged_attn_kernel, T=T, d=d, ps=ps, NP=NP,
                               quant=quant, has_mask=has_mask)
    # index maps receive (*grid, *prefetch_refs); the page maps pick pool
    # page bt[b, i] per grid step — the block-table walk lives HERE.
    # Every block's last two dimensions equal the array's (the TPU
    # lowering's alternative to (8, 128)-divisible blocks).
    in_specs = [
        pl.BlockSpec((None, None, T, d),
                     lambda b, h, i, bt, pos: (b, h, 0, 0)),
        pl.BlockSpec((None, None, ps, d),
                     lambda b, h, i, bt, pos: (bt[b, i], h, 0, 0)),
        pl.BlockSpec((None, None, ps, d),
                     lambda b, h, i, bt, pos: (bt[b, i], h, 0, 0)),
    ]
    args = [q, kp, vp]
    if quant:
        in_specs += [
            pl.BlockSpec((None, H, ps),
                         lambda b, h, i, bt, pos: (bt[b, i], 0, 0)),
            pl.BlockSpec((None, H, ps),
                         lambda b, h, i, bt, pos: (bt[b, i], 0, 0)),
        ]
        args += [kscales, vscales]
    if has_mask:
        # [B, 1, Tmax]: the unit axis makes the row a (1, Tmax) block
        in_specs.append(pl.BlockSpec((None, 1, Tmax),
                                     lambda b, h, i, bt, pos: (b, 0, 0)))
        args.append(key_valid.astype(jnp.float32)[:, None, :])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, NP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, T, d),
                               lambda b, h, i, bt, pos: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Tmax, d), jnp.float32),
                        pltpu.VMEM((Tmax, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, d), q.dtype),
        interpret=interpret,
    )(bt, pos.astype(jnp.int32), *args)


class PallasPagedAttention(PagedAttentionHelper):
    """Accelerated backend: block-table-walking Pallas kernel.

    ``interpret=None`` auto-selects interpreter mode off-TPU (the CPU CI
    parity configuration); pass ``False`` to require a real Mosaic
    compile."""

    name = "pallas"

    def __init__(self, interpret=None):
        self.interpret = interpret

    def attend(self, q, kp, vp, bt, pos, *, mask=None,
               kscales=None, vscales=None, scale=None):
        if scale is not None:
            raise NotImplementedError(
                "the Pallas paged read takes one key/value head per query "
                "head at the 1/sqrt(d) scale; resolve_paged_backend(plain="
                "False) never selects it")
        interpret = self.interpret
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        T = q.shape[2]
        Tmax = bt.shape[1] * kp.shape[2]
        key_valid = None
        if mask is not None:
            # the chunk-validity plane is tiny [B, Tmax] XLA math shared
            # with the stock path; the kernel consumes it as an input
            key_valid = _key_valid_plane(mask, pos, T, Tmax)
        return _pallas_paged_attention(q, kp, vp, bt, pos, key_valid,
                                       kscales, vscales,
                                       interpret=interpret)


_HELPERS = {
    "xla": XlaPagedAttention(),
    "pallas": PallasPagedAttention(),
}


def supports(*, page_size, head_dim, n_pages, chunk=1, quant=False,
             platform=None, plain=True):
    """Can the Pallas backend take this pool geometry, at query chunks of
    up to ``chunk`` rows, on this platform? Static shapes only, so the
    answer is the same at server construction and at trace time."""
    if platform is None:
        platform = jax.default_backend()
    if platform != "tpu" or not plain:
        # off-TPU the kernel would run interpreted — a debugging mode,
        # never a serving win: auto falls back to stock. A layer with
        # fewer key/value heads than query heads or a stated score scale
        # (``plain=False``) is read through XLA everywhere
        return False
    # Mosaic tiling: page rows land in VMEM scratch at sublane offsets
    # i*ps, and head_dim is the lane dimension of every block
    if page_size % 8 or head_dim % 64:
        return False
    return vmem_bytes(page_size=page_size, head_dim=head_dim,
                      n_pages=n_pages, chunk=chunk) <= VMEM_LIMIT_BYTES


def resolve_paged_backend(choice, *, page_size, head_dim, n_pages,
                          chunk=1, quant=False, platform=None, plain=True):
    """Resolve a ``paged_attention`` knob to a concrete backend name.

    ``choice``: "auto" (Pallas on TPU when :func:`supports` accepts the
    geometry, XLA everywhere else), or a forced "xla"/"pallas". A forced
    "pallas" on a TPU raises for a geometry :func:`supports` declines
    (over the VMEM limit, where Mosaic would refuse it less legibly, or
    an alignment nothing has run on a chip); off-TPU it selects the
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
                  chunk=chunk, quant=quant, platform=platform, plain=plain)
    if choice == "pallas":
        if not plain:
            raise ValueError(
                "paged_attention='pallas' reads one key/value head per "
                "query head at the 1/sqrt(d) scale; a layer with "
                "n_kv_heads < n_heads or a score_scale is served through "
                "'xla' ('auto' selects it)")
        if platform == "tpu" and not ok:
            need = vmem_bytes(page_size=page_size, head_dim=head_dim,
                              n_pages=n_pages, chunk=chunk)
            raise ValueError(
                f"paged_attention='pallas' cannot take page_size="
                f"{page_size}, head_dim={head_dim}, n_pages={n_pages}, "
                f"chunk={chunk} on a TPU: it needs page_size % 8 == 0, "
                f"head_dim % 64 == 0 and {need} <= {VMEM_LIMIT_BYTES} "
                "bytes of VMEM; 'auto' serves such a pool through XLA")
        return choice
    return "pallas" if ok else "xla"


def get_paged_helper(backend) -> PagedAttentionHelper:
    try:
        return _HELPERS[backend]
    except KeyError:
        raise ValueError(f"unknown paged_attention backend {backend!r} "
                         f"(expected one of {BACKENDS})") from None


def paged_attend(backend, q, kp, vp, bt, pos, *, mask=None,
                 kscales=None, vscales=None, scale=None):
    """Dispatch one paged-attention read through the selected backend.
    ``backend`` is a resolved name (see :func:`resolve_paged_backend`),
    static at trace time."""
    helper = get_paged_helper(backend)
    return helper.attend(q, kp, vp, bt, pos, mask=mask,
                         kscales=kscales, vscales=vscales, scale=scale)
