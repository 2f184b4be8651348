"""Pallas flash-attention forward kernel — the accelerated-kernel stage.

Role in the framework (SURVEY §7 stage 4): the reference accelerates hot
layer math through optional cuDNN helpers discovered at runtime
(nn/layers/convolution/ConvolutionLayer.java:68-79 reflective load,
deeplearning4j-cuda/CudnnConvolutionHelper.java:54), validated by
helper-vs-stock comparison tests (deeplearning4j-cuda/src/test/). The TPU
equivalent: most ops lower optimally through XLA already, but attention is
the documented exception — the stock softmax(QK^T)V program materialises the
[B, H, T, T] score matrix in HBM, so at long T it is HBM-bandwidth-bound.
This kernel computes attention with the online-softmax (flash) recurrence:
K/V stream through VMEM in blocks, scores never leave the chip, O(T) memory
instead of O(T^2).

Scope: forward + backward, optionally causal, optional [B, T] key-padding
mask (per-batch key-validity row broadcast over heads — the same
semantics as the stock path; round 5 closed the last helper-vs-stock
routing gap). The
backward is the standard flash recompute-by-block scheme (dq kernel over
q-blocks streaming K/V; dk/dv kernel over k-blocks streaming Q/dO), so
long-T *training* keeps O(T) memory — scores are rebuilt from the saved
row-logsumexp L and never materialise in HBM.

Parity contract (the cuDNN-test pattern): tests/test_pallas_attention.py
compares kernel output and gradients against ``scaled_dot_attention`` in
interpret mode on CPU; no cell of ``benchmarks/`` measures these kernels
yet (PERF.md §7, ``cgpt590m_fit_t1024``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _causal_mask(s, iq, ik, block_q, block_k):
    rows = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            + iq * block_q)
    cols = (jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            + ik * block_k)
    return jnp.where(rows >= cols, s, NEG_INF)


def _attn_fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale: float,
                     causal: bool, has_mask: bool, block_q: int,
                     block_k: int, seq_len: int):
    """One (batch*head, q-block) program: stream K/V blocks with the online
    softmax recurrence. q_ref: [block_q, d]; k_ref/v_ref: [T, d] (VMEM);
    o_ref: [block_q, d]; lse_ref: [block_q, 1] row logsumexp (saved for the
    backward recompute). With ``has_mask``, mask_ref is a [1, T] f32 key
    validity row (shared by all heads of the batch)."""
    if has_mask:
        mask_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    iq = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * sm_scale
    d = q.shape[-1]
    nk = seq_len // block_k
    if causal:
        # blocks strictly above the diagonal contribute nothing: the last
        # key block needed is the one containing column (iq+1)*block_q - 1
        nk_eff = jnp.minimum(jnp.int32(nk),
                             ((iq + 1) * block_q - 1) // block_k + 1)
    else:
        nk_eff = nk

    def body(i, carry):
        acc, m, l = carry
        k_blk = k_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, iq, i, block_q, block_k)
        if has_mask:
            # Mosaic requires lane-dim dynamic slices provably 128-aligned;
            # flash_attention guarantees block_k % 128 == 0 (or one block)
            # whenever a mask is present. != 0 matches the stock path's
            # mask.astype(bool) semantics (any nonzero = valid).
            km = (mask_ref[:] if block_k == seq_len
                  else mask_ref[:, pl.ds(i * block_k, block_k)])
            s = jnp.where(km != 0, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    acc = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, nk_eff, body, (acc, m0, l0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    lse_ref[:] = (m + jnp.log(jnp.maximum(l, 1e-30)))[:, None]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_forward(q, k, v, key_mask, *, causal: bool, block_q: int,
                   block_k: int, interpret: bool):
    B, H, T, d = q.shape
    sm_scale = 1.0 / (d ** 0.5)
    qf = q.reshape(B * H, T, d)
    kf = k.reshape(B * H, T, d)
    vf = v.reshape(B * H, T, d)
    has_mask = key_mask is not None
    kernel = functools.partial(
        _attn_fwd_kernel, sm_scale=sm_scale, causal=causal,
        has_mask=has_mask, block_q=block_q, block_k=block_k, seq_len=T)
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, T, d), lambda b, i: (b, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, T, d), lambda b, i: (b, 0, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [qf, kf, vf]
    if has_mask:
        # [B, 1, T]: one validity row per batch, shared across its heads
        # (program b belongs to batch b // H)
        in_specs.append(pl.BlockSpec(
            (None, 1, T), lambda b, i: (b // H, 0, 0),
            memory_space=pltpu.VMEM))
        args.append(key_mask.astype(jnp.float32).reshape(B, 1, T))
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, T // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, d), q.dtype),
            jax.ShapeDtypeStruct((B * H, T, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    return out.reshape(B, H, T, d), lse


def _attn_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    sm_scale: float, causal: bool, has_mask: bool,
                    block_q: int, block_k: int, seq_len: int):
    """dQ for one (batch*head, q-block): stream K/V, recompute P from the
    saved logsumexp, accumulate dS K. All VMEM-resident, f32 accumulation."""
    if has_mask:
        mask_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    iq = pl.program_id(1)
    q = q_ref[:].astype(jnp.float32) * sm_scale
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:].astype(jnp.float32)          # [block_q, 1]
    delta = delta_ref[:].astype(jnp.float32)      # [block_q, 1]
    d = q.shape[-1]
    nk = seq_len // block_k
    if causal:
        nk_eff = jnp.minimum(jnp.int32(nk),
                             ((iq + 1) * block_q - 1) // block_k + 1)
    else:
        nk_eff = nk

    def body(i, dq):
        k_blk = k_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, iq, i, block_q, block_k)
        if has_mask:
            km = (mask_ref[:] if block_k == seq_len
                  else mask_ref[:, pl.ds(i * block_k, block_k)])
            s = jnp.where(km != 0, s, NEG_INF)
        p = jnp.exp(s - lse)                      # normalized probabilities
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk_eff, body, jnp.zeros((block_q, d),
                                                      jnp.float32))
    dq_ref[:] = (dq * sm_scale).astype(dq_ref.dtype)


def _attn_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     *rest, sm_scale: float, causal: bool, has_mask: bool,
                     block_q: int, block_k: int, seq_len: int):
    """dK/dV for one (batch*head, k-block): stream Q/dO blocks, recompute
    P^T, accumulate dV = P^T dO and dK = dS^T Q * scale."""
    if has_mask:
        mask_ref, dk_ref, dv_ref = rest  # mask_ref: [1, block_k]
    else:
        dk_ref, dv_ref = rest
    ik = pl.program_id(1)
    k_blk = k_ref[:].astype(jnp.float32)          # [block_k, d]
    v_blk = v_ref[:].astype(jnp.float32)
    d = k_blk.shape[-1]
    nq = seq_len // block_q
    if causal:
        # q-blocks strictly above (before) this k-block's diagonal see none
        # of its columns: start at the block containing row ik*block_k
        iq0 = (ik * block_k) // block_q
    else:
        iq0 = 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32) \
            * sm_scale
        do = do_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        delta = delta_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, i, ik, block_q, block_k)
        if has_mask:
            s = jnp.where(mask_ref[:] != 0, s, NEG_INF)
        p = jnp.exp(s - lse)                      # [block_q, block_k]
        dv_new = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    zeros = jnp.zeros((k_blk.shape[0], d), jnp.float32)
    dk, dv = jax.lax.fori_loop(iq0, nq, body, (zeros, zeros))
    # dk = dS^T (q * sm_scale): q was loaded pre-scaled, no extra factor
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def _flash_backward(q, k, v, o, lse, do, key_mask, *, causal: bool,
                    block_q: int, block_k: int, interpret: bool):
    B, H, T, d = q.shape
    sm_scale = 1.0 / (d ** 0.5)
    flat = lambda a: a.reshape(B * H, T, d)
    qf, kf, vf, dof = flat(q), flat(k), flat(v), flat(do)
    # D_i = dO_i . O_i — one fused elementwise-reduce in XLA, O(T d) reads
    delta = jnp.sum(dof.astype(jnp.float32)
                    * flat(o).astype(jnp.float32), axis=-1, keepdims=True)
    has_mask = key_mask is not None
    if has_mask:
        mf = key_mask.astype(jnp.float32).reshape(B, 1, T)

    blk_q = pl.BlockSpec((None, block_q, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    blk_q1 = pl.BlockSpec((None, block_q, 1), lambda b, i: (b, i, 0),
                          memory_space=pltpu.VMEM)
    blk_k = pl.BlockSpec((None, block_k, d), lambda b, i: (b, i, 0),
                         memory_space=pltpu.VMEM)
    full = pl.BlockSpec((None, T, d), lambda b, i: (b, 0, 0),
                        memory_space=pltpu.VMEM)
    full1 = pl.BlockSpec((None, T, 1), lambda b, i: (b, 0, 0),
                         memory_space=pltpu.VMEM)
    # program b belongs to batch b // H; dq streams ALL key columns (full
    # mask row), dkv sees only its own k-block's columns
    mask_full = pl.BlockSpec((None, 1, T), lambda b, i: (b // H, 0, 0),
                             memory_space=pltpu.VMEM)
    mask_blk = pl.BlockSpec((None, 1, block_k), lambda b, i: (b // H, 0, i),
                            memory_space=pltpu.VMEM)

    dq_in = [blk_q, full, full, blk_q, blk_q1, blk_q1]
    dq_args = [qf, kf, vf, dof, lse, delta]
    if has_mask:
        dq_in.append(mask_full)
        dq_args.append(mf)
    dq = pl.pallas_call(
        functools.partial(_attn_dq_kernel, sm_scale=sm_scale, causal=causal,
                          has_mask=has_mask, block_q=block_q,
                          block_k=block_k, seq_len=T),
        grid=(B * H, T // block_q),
        in_specs=dq_in,
        out_specs=blk_q,
        out_shape=jax.ShapeDtypeStruct((B * H, T, d), q.dtype),
        interpret=interpret,
    )(*dq_args)

    dkv_in = [full, blk_k, blk_k, full, full1, full1]
    dkv_args = [qf, kf, vf, dof, lse, delta]
    if has_mask:
        dkv_in.append(mask_blk)
        dkv_args.append(mf)
    dk, dv = pl.pallas_call(
        functools.partial(_attn_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, has_mask=has_mask, block_q=block_q,
                          block_k=block_k, seq_len=T),
        grid=(B * H, T // block_k),
        in_specs=dkv_in,
        out_specs=[blk_k, blk_k],
        out_shape=[jax.ShapeDtypeStruct((B * H, T, d), k.dtype),
                   jax.ShapeDtypeStruct((B * H, T, d), v.dtype)],
        interpret=interpret,
    )(*dkv_args)

    unflat = lambda a: a.reshape(B, H, T, d)
    return unflat(dq), unflat(dk), unflat(dv)


DEFAULT_BLOCK = 512  # tuned on v5e: T=2048 1.5x, T=4096 2.9x over stock

# Each program holds full K and V [T, d] blocks in VMEM as f32 (~2*T*d*4
# bytes) plus the q/o blocks and accumulators; cap T*d so long sequences
# fall back to stock instead of crashing. Limit set EMPIRICALLY on v5e:
# T=4096, d=128 (T*d = 2^19) compiles (training needs the vjp block_q
# shrink below); T=8192, d=128 (2^20) fails scoped-VMEM even forward-only.
VMEM_SEQ_ELEMS_LIMIT = 1 << 19  # inclusive T * d ceiling (4096 * 128)


def supports(q_shape, *, mask, dtype=jnp.float32,
             block_q: int = DEFAULT_BLOCK,
             block_k: int = DEFAULT_BLOCK, backend: str | None = None) -> bool:
    """Whether the ``auto`` helper should route here (callers fall back to
    the stock XLA path otherwise). Declines when:

    - a key mask is present whose shape is not the [B, T] per-batch key
      validity row the kernels understand (round 5: masked workloads no
      longer force the stock path);
    - dtype is wider than float32 — the kernel casts to and accumulates in
      f32, so a float64 network would silently lose precision (breaks
      gradchecks); bf16/f16 inputs are fine (they gain precision);
    - the backend is not TPU — off-TPU the kernel runs in interpret mode,
      orders of magnitude slower than stock (``helper='pallas'`` still
      forces it, which is what the parity tests use);
    - T*d exceeds the VMEM ceiling (full K/V live in VMEM per program);
    - T is not divisible by the (T-clamped) block sizes.
    """
    if len(q_shape) != 4:
        return False
    if mask is not None and tuple(getattr(mask, "shape", ())) != \
            (q_shape[0], q_shape[2]):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float16)):
        return False
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu":
        return False
    T, d = q_shape[2], q_shape[3]
    if T * d > VMEM_SEQ_ELEMS_LIMIT:
        return False
    return T % min(block_q, T) == 0 and T % min(block_k, T) == 0


def flash_attention(q, k, v, *, causal: bool = False, mask=None,
                    block_q: int = DEFAULT_BLOCK,
                    block_k: int = DEFAULT_BLOCK, interpret=None):
    """softmax(q k^T / sqrt(d)) v with the flash recurrence.

    q/k/v: [B, H, T, d], T divisible by the (T-clamped) block sizes.
    ``mask``: optional [B, T] key-validity row (1 = attend, 0 = pad),
    broadcast over heads — same semantics as ``scaled_dot_attention``.
    ``interpret=None`` auto-selects interpreter mode off-TPU (so the same
    call works in the CPU test mesh). Gradients: Pallas recompute-by-block
    backward (dq / dk+dv kernels) from the saved row-logsumexp — O(T)
    memory for training too, unlike a stock-XLA vjp which would
    re-materialise the [B,H,T,T] score matrix in HBM."""
    T = q.shape[2]
    d = q.shape[3]
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    if mask is not None:
        B = q.shape[0]
        if tuple(mask.shape) != (B, T):
            raise ValueError(
                f"key mask shape {tuple(mask.shape)} != (B, T) = "
                f"({B}, {T}) — a [B, T] key-validity row is required")
        # the in-kernel mask row is dynamically sliced on the LANE dim,
        # which Mosaic only compiles when the slice start is provably a
        # multiple of 128 — force a conforming block_k (or one full-row
        # block; VMEM already holds the full K/V so [1, T] is free)
        if block_k != T and (block_k % 128 or T % block_k):
            block_k = next((c for c in range(min(block_k, T) // 128 * 128,
                                             0, -128) if T % c == 0), T)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    fwd = functools.partial(_flash_forward, causal=causal, block_q=block_q,
                            block_k=block_k, interpret=interpret)
    # The DIFFERENTIATED forward compiles in a jvp context where XLA's
    # scoped-VMEM accounting is tighter: at T=4096, d=128 the default
    # block_q=512 exceeds the 16 MiB limit by ~84 KiB (measured OOM) while
    # the primal-only call compiles fine. Shrink block_q for the vjp
    # forward only — the primal path keeps the faster big block (measured:
    # fwd 512/512 4.60 ms vs 256/512 5.26 ms; training 256/512 11.3 ms
    # where 512/512 cannot compile at all).
    vjp_block_q = block_q
    if T * d >= (1 << 19) and block_q > 256 and T % 256 == 0:
        # only when 256 keeps the grid covering T exactly — a non-divisor
        # would silently drop tail rows; shapes the shrink cannot help
        # keep the old block and fail loudly at compile instead
        vjp_block_q = 256
    vjp_fwd = functools.partial(_flash_forward, causal=causal,
                                block_q=vjp_block_q, block_k=block_k,
                                interpret=interpret)
    bwd = functools.partial(_flash_backward, causal=causal,
                            block_q=vjp_block_q, block_k=block_k,
                            interpret=interpret)

    if mask is None:
        @jax.custom_vjp
        def attn(q, k, v):
            return fwd(q, k, v, None)[0]

        def attn_fwd(q, k, v):
            o, lse = vjp_fwd(q, k, v, None)
            return o, (q, k, v, o, lse)

        def attn_bwd(res, g):
            q, k, v, o, lse = res
            return bwd(q, k, v, o, lse, g, None)

        attn.defvjp(attn_fwd, attn_bwd)
        return attn(q, k, v)

    m = jnp.asarray(mask, jnp.float32)  # float: a bool cotangent is invalid

    @jax.custom_vjp
    def attn_m(q, k, v, m):
        return fwd(q, k, v, m)[0]

    def attn_m_fwd(q, k, v, m):
        o, lse = vjp_fwd(q, k, v, m)
        return o, (q, k, v, m, o, lse)

    def attn_m_bwd(res, g):
        q, k, v, m, o, lse = res
        dq, dk, dv = bwd(q, k, v, o, lse, g, m)
        return dq, dk, dv, jnp.zeros_like(m)

    attn_m.defvjp(attn_m_fwd, attn_m_bwd)
    return attn_m(q, k, v, m)
