"""Sequence/context parallelism: ring attention over a mesh axis.

No reference equivalent (SURVEY §5: the reference's only long-sequence tool
is truncated BPTT) — this is the TPU-native capability the task requires for
long contexts: shard the TIME axis of attention across devices and rotate
key/value blocks around the ring with ``lax.ppermute`` while accumulating a
streaming (flash-attention-style) softmax — peak memory per device drops from
O(T^2) to O(T * T/n), and the block rotations ride the ICI ring concurrently
with the blockwise matmuls (Liu et al. 2023, Ring Attention).

All ops are differentiable (scan + ppermute), so the same code path serves
training; gradients flow around the ring in reverse automatically.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

SEQ_AXIS = "seq"
_MIN_LOGIT = -1e4  # running-max clamp: keeps exp() well-defined for
_MASKED = -1e30    # fully-masked blocks (see _block_update)


def _block_update(q_blk, k_cur, v_cur, m, l, acc, q_off, k_off, causal):
    """One blockwise softmax accumulation step (online softmax)."""
    d = q_blk.shape[-1]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q_blk, k_cur) / jnp.sqrt(
        jnp.asarray(d, q_blk.dtype))
    if causal:
        Tq, Tk = logits.shape[-2], logits.shape[-1]
        qpos = q_off + jnp.arange(Tq)
        kpos = k_off + jnp.arange(Tk)
        keep = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(keep, logits, _MASKED)
    row_max = jnp.max(logits, axis=-1)                       # [B,H,Tq]
    new_m = jnp.maximum(jnp.maximum(m, row_max), _MIN_LOGIT)
    p = jnp.exp(logits - new_m[..., None])                   # [B,H,Tq,Tk]
    scale = jnp.exp(m - new_m)                               # [B,H,Tq]
    l = l * scale + jnp.sum(p, axis=-1)
    acc = acc * scale[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v_cur)
    return new_m, l, acc


def ring_attention(q, k, v, *, mesh: Mesh, axis: str = SEQ_AXIS,
                   causal: bool = False):
    """Exact attention with the time axis sharded over ``axis``.

    q/k/v: [B, H, T, d] global arrays (T divisible by the axis size).
    Returns [B, H, T, d], numerically equal to single-device
    softmax(qk^T/sqrt(d))v up to float tolerance.
    """
    n = mesh.shape[axis]

    def shard_fn(q_blk, k_blk, v_blk):
        i = lax.axis_index(axis)
        Tl = q_blk.shape[2]
        q_off = i * Tl
        m0 = jnp.full(q_blk.shape[:3], _MIN_LOGIT, q_blk.dtype)
        l0 = jnp.zeros(q_blk.shape[:3], q_blk.dtype)
        acc0 = jnp.zeros_like(q_blk)
        perm = [(s, (s + 1) % n) for s in range(n)]

        def body(carry, step):
            k_cur, v_cur, m, l, acc = carry
            # after `step` rotations, this device holds block (i - step) % n
            blk = (i - step) % n
            m, l, acc = _block_update(q_blk, k_cur, v_cur, m, l, acc,
                                      q_off, blk * Tl, causal)
            k_nxt = lax.ppermute(k_cur, axis, perm)
            v_nxt = lax.ppermute(v_cur, axis, perm)
            return (k_nxt, v_nxt, m, l, acc), 0

        (_, _, _, l, acc), _ = lax.scan(
            body, (k_blk, v_blk, m0, l0, acc0), jnp.arange(n))
        return acc / jnp.maximum(l, 1e-12)[..., None]

    spec = P(None, None, axis, None)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q, k, v, *, mesh: Mesh, axis: str = SEQ_AXIS,
                      causal: bool = False):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Where ring attention keeps the TIME axis sharded and rotates K/V blocks
    ``n`` times around the ring, this re-shards with two collectives: an
    ``all_to_all`` turns the layout from sequence-sharded [B, H, T/n, d]
    into HEAD-sharded [B, H/n, T, d], each device runs ordinary
    full-sequence attention for its own heads, and a second ``all_to_all``
    restores sequence sharding. Communication is 2 all-to-alls per tensor
    (vs n ppermute rounds) — the better trade when heads >= devices and the
    per-device time block is small; ring wins when T is huge and H is
    small (Jacobs et al. 2023, DeepSpeed-Ulysses). Requires H divisible by
    the axis size. Differentiable (all_to_all has a transpose rule), so
    training works through it unchanged.

    q/k/v: [B, H, T, d] global arrays; returns [B, H, T, d], numerically
    equal to single-device softmax(qk^T/sqrt(d))v up to float tolerance.
    """
    n = mesh.shape[axis]
    H = q.shape[1]
    if H % n != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({H}) divisible by the "
            f"'{axis}' axis size ({n}); use ring_attention otherwise")

    def shard_fn(q_blk, k_blk, v_blk):
        # seq-sharded -> head-sharded: split heads, concat time blocks
        # (device order == time order, so concatenation restores the
        # global sequence)
        def to_heads(x):
            return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                  tiled=True)

        from deeplearning4j_tpu.nn.conf.layers.attention import (
            scaled_dot_attention,
        )

        ql, kl, vl = to_heads(q_blk), to_heads(k_blk), to_heads(v_blk)
        o = scaled_dot_attention(ql, kl, vl, causal=causal)
        # head-sharded -> seq-sharded
        return lax.all_to_all(o, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    spec = P(None, None, axis, None)
    fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    return fn(q, k, v)


def sequence_parallel_self_attention(layer, params, x, *, mesh: Mesh,
                                     axis: str = SEQ_AXIS,
                                     causal=None, impl: str = "ring"):
    """Run a SelfAttentionLayer forward with the sequence axis sharded:
    pointwise projections stay local to each time shard; the attention core
    is the ring (``impl='ring'``) or two all-to-alls (``impl='ulysses'``,
    needs heads divisible by the axis size). Inference-mode equal to
    ``layer.forward`` (incl. the output activation; no mask support — pad
    to multiples of the axis size instead, standard for long-context)."""
    causal = layer.causal if causal is None else causal
    if layer.rope_theta or layer.key_scale or layer.head_dim:
        raise NotImplementedError(
            "the sequence-parallel forward projects the classic layer: no "
            "rotary positions, key factor or head size of its own yet")
    H = layer.n_heads

    def project(W):
        y = jnp.einsum("btf,fo->bto", x, W)
        B, T, O = y.shape
        return y.reshape(B, T, H, O // H).transpose(0, 2, 1, 3)

    q, k, v = (project(params["Wq"]), project(params["Wk"]),
               project(params["Wv"]))
    impls = {"ring": ring_attention, "ulysses": ulysses_attention}
    if impl not in impls:
        raise ValueError(f"impl must be one of {sorted(impls)}, "
                         f"got '{impl}'")
    o = impls[impl](q, k, v, mesh=mesh, axis=axis, causal=causal)
    B, H_, T, d = o.shape
    o = o.transpose(0, 2, 1, 3).reshape(B, T, H_ * d)
    out = jnp.einsum("bto,op->btp", o, params["Wo"]) + params["b"]
    return layer.act()(out)
