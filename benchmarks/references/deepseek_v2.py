"""Plain reference of DeepSeek-V2 (``deepseek_v2``) as the zoo's
``DeepSeekV2LM`` builds one chip's share of it: float32, every product at
``highest`` precision, latent attention in its NON-absorbed form (keys and
values rebuilt for every position), one full causal pass over a whole
sequence, rotation by ``arange(T)``, no cache, no pages, no slots, no
batching, importing nothing of the program.

    x = E[ids] + b_E
    per block:  x = x + MLA(RMSNorm(x));  x = x + FFN(RMSNorm'(x))
    logits = RMSNorm_f(x) W_head + b_head
    RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)

MLA(h), no biases: c_Q = RMSNorm(h W_DQ); [q_nope | q_rope] = c_Q W_UQ per
head; [c_KV | k_r] = h W_DKV; c_KV = RMSNorm(c_KV); q_rope and k_r rotated
at the token's position p, k_r one key for all heads; k_nope = c_KV W_UK,
v = c_KV W_UV per head; score = s (q_nope . k_nope + q_rope . k_r), causal
softmax, o = sum p v, out = concat(o) W_O.

Rotation (YaRN): f_i = theta^(-2i/R) for i < R/2 (R = rope_dim);
corr(b) = R ln(original / (2 pi b)) / (2 ln theta); low = floor(corr(
beta_fast)), high = ceil(corr(beta_slow)); r_i = clip((i - low) / (high -
low), 0, 1); frequency g_i = f_i (1 - r_i) + (f_i / factor) r_i. The pair
is ADJACENT channels (t_2i, t_2i+1) -> (t_2i cos(p g_i) - t_2i+1 sin(p
g_i), t_2i+1 cos(p g_i) + t_2i sin(p g_i)), left de-interleaved (first
members in the first half), as the published code leaves it. With m(a) =
0.1 a ln(factor) + 1, cos and sin carry m(mscale) / m(mscale_all_dim) and
s = (nope_dim + rope_dim)^-1/2 m(mscale_all_dim)^2.

FFN: block i < dense_layers: ((h W_gate) silu * (h W_up)) W_down, the
program's layout W1 = [W_gate | W_up], W2 = W_down. Later blocks: p =
softmax(h W_r) over ALL ``experts``; group g holds experts g*E/G ..
(g+1)*E/G - 1; the ``groups_kept`` groups with the largest max p stay; the
``top_k`` largest p inside them, as a written-out selection (each
candidate struck out once taken), NOT renormalised; y = routed_scale * sum
p_e Expert_e(h) + Shared(h). Only the experts ``experts_held = [first,
count]`` exist here: a loop over them with every token through each and a
weight that is zero where the token did not choose it; what the absent
experts would add is left out, as the program leaves it out.

W_UK and W_UV are the program's two factors of the published
``kv_b_proj``: ``Wuk [heads, nope_dim, kv_rank]``, ``Wuv [heads, kv_rank,
v_dim]`` (a layout; drawn so).

Weights are the benchmark's: ``make_params`` draws them on the device from
the seed by the configuration file's ``init`` and returns bfloat16 leaves,
named as the program's vertices are, leaf by leaf. The driver hands the
same tree to the program and to this reference, which upcasts a piece at a
time: attention's matrices inside one jitted call with the heads in blocks
(the scores of all 128 heads over 5,888 positions would be 17.7 GB), an
expert at a time, the head's rows after the window is cut: no float32 copy
of the model ever exists.

``mode``: ``"float32"`` is the reference. ``"fp8"`` is the control: the
same pass with every weight and every product's input and result rounded
to float8_e4m3fn, the nearest precision below the bfloat16 that the
configuration states. ``"bf16"`` is the same pass rounded to bfloat16, the
configuration's own precision: what a sound program may read against the
reference when routing flips at near-ties (a reading, not a control).
"""

from __future__ import annotations

import functools
import gc
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16
#: float32 scores [heads in a block, T, T] are held to this many bytes
SCORE_BYTES = 1 << 29


# -------------------------------------------------------------- weights
def _names(sizes):
    """(vertex name, kind) in the program's order."""
    out = [("embed", "embed")]
    for i in range(sizes["layers"]):
        out += [(f"n{i}a", "norm"), (f"mla{i}", "mla"), (f"n{i}b", "norm"),
                (f"ffn{i}", "mlp" if i < sizes["dense_layers"] else "moe")]
    return out + [("n_f", "norm"), ("output", "head")]


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, F32)).astype(BF16)


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    d, v, H = sizes["d_model"], sizes["vocab"], sizes["heads"]
    rq, c = sizes["q_rank"], sizes["kv_rank"]
    n, r, dv = sizes["nope_dim"], sizes["rope_dim"], sizes["v_dim"]
    E, held = sizes["experts"], sizes["experts_held"][1]
    fe, fs, fm = (sizes["expert_width"], sizes["shared_width"],
                  sizes["mlp_width"])
    std = init["std"]
    # The weights fill most of a chip, and a released program's copy of them
    # sits in reference cycles (a network's cached programs close over the
    # network): collect those first, or the second copy does not fit.
    gc.collect()
    # seeds pass 2**31: fold the high bits in instead of truncating them
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    count = iter(range(1 << 20))

    def draw(which, *shape):
        return _normal(jax.random.fold_in(root, next(count)), shape,
                       float(std[which]))

    ones = lambda k: jnp.ones((k,), BF16)  # noqa: E731
    p = {}
    for name, kind in _names(sizes):
        if kind == "embed":
            p[name] = {"W": draw("embed", v, d), "b": jnp.zeros((d,), BF16)}
        elif kind == "norm":
            p[name] = {"gamma": ones(d)}
        elif kind == "mla":
            p[name] = {"Wdq": draw("dq", d, rq), "q_gamma": ones(rq),
                       "Wuq": draw("uq", rq, H * (n + r)),
                       "Wdkv": draw("dkv", d, c + r), "kv_gamma": ones(c),
                       "Wuk": draw("uk", H, n, c),
                       "Wuv": draw("uv", H, c, dv),
                       "Wo": draw("o", H * dv, d)}
        elif kind == "mlp":
            p[name] = {"W1": draw("mlp_in", d, 2 * fm),
                       "W2": draw("mlp_down", fm, d)}
        elif kind == "moe":
            p[name] = {"Wg": draw("router", d, E),
                       "W1": draw("expert_in", held, d, 2 * fe),
                       "W2": draw("expert_down", held, fe, d),
                       "Ws1": draw("shared_in", d, 2 * fs),
                       "Ws2": draw("shared_down", fs, d)}
        else:
            p[name] = {"W": draw("head", d, v), "b": jnp.zeros((v,), BF16)}
    return p


# -------------------------------------------------------------- forward
def _round(x, mode):
    if mode == "fp8":
        return jnp.clip(x, -448.0, 448.0).astype(
            jnp.float8_e4m3fn).astype(F32)
    if mode == "bf16":
        return x.astype(BF16).astype(F32)
    return x.astype(F32)


def _mm(a, w, mode):
    return _round(jnp.dot(_round(a, mode), _round(w, mode), precision=HI),
                  mode)


def _rms(x, w, eps):
    return w.astype(F32) * x * lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _m(factor, a):
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 and a else 1.0


def yarn_frequencies(sz) -> np.ndarray:
    R, theta, y = sz["rope_dim"], float(sz["rope_theta"]), sz["yarn"]
    i = np.arange(R // 2)
    f = theta ** (-2.0 * i / R)
    if not y:
        return f

    def corr(b):
        return R * math.log(y["original_positions"] / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), R - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return f * (1 - ramp) + f / y["factor"] * ramp


def score_scale(sz) -> float:
    y = sz["yarn"] or {"factor": 1, "mscale_all_dim": 0}
    return (sz["nope_dim"] + sz["rope_dim"]) ** -0.5 \
        * _m(y["factor"], y["mscale_all_dim"]) ** 2


def _rotate(t, sz):
    """``t [T, ..., R]`` at positions 0..T-1: adjacent pairs, the result
    de-interleaved."""
    T = t.shape[0]
    ang = jnp.arange(T, dtype=F32)[:, None] \
        * jnp.asarray(yarn_frequencies(sz), F32)[None, :]     # [T, R/2]
    ang = ang.reshape((T,) + (1,) * (t.ndim - 2) + (-1,))
    y = sz["yarn"] or {"factor": 1, "mscale": 0, "mscale_all_dim": 0}
    factor = _m(y["factor"], y["mscale"]) / _m(y["factor"],
                                               y["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = t[..., 0::2], t[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mla(p, h, sz, mode):
    t = h.shape[0]
    H, c = sz["heads"], sz["kv_rank"]
    n, dv = sz["nope_dim"], sz["v_dim"]
    cq = _round(_rms(_mm(h, p["Wdq"], mode), p["q_gamma"], sz["rms_eps"]),
                mode)
    q = _mm(cq, p["Wuq"], mode).reshape(t, H, -1)
    q_nope = q[..., :n]
    q_rope = _round(_rotate(q[..., n:], sz), mode)
    kv = _mm(h, p["Wdkv"], mode)
    lat = _round(_rms(kv[:, :c], p["kv_gamma"], sz["rms_eps"]), mode)
    k_r = _round(_rotate(kv[:, c:], sz), mode)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = score_scale(sz)
    hb = H
    while hb > 1 and (hb * t * t * 4 > SCORE_BYTES or H % hb):
        hb -= 1

    def heads(args):
        qn, qr, wuk, wuv = args                    # a block of hb heads
        k = _round(jnp.einsum("sc,hnc->hsn", lat, _round(wuk, mode),
                              precision=HI), mode)
        v = _round(jnp.einsum("sc,hcv->hsv", lat, _round(wuv, mode),
                              precision=HI), mode)
        sc = (jnp.einsum("thn,hsn->hts", qn, k, precision=HI)
              + jnp.einsum("thr,sr->hts", qr, k_r, precision=HI)) * s
        w = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        return _round(jnp.einsum("hts,hsv->thv", w, v, precision=HI), mode)

    blocks = lambda a, axis: jnp.moveaxis(  # noqa: E731
        a.reshape(a.shape[:axis] + (H // hb, hb) + a.shape[axis + 1:]),
        axis, 0)
    o = lax.map(heads, (blocks(q_nope, 1), blocks(q_rope, 1),
                        blocks(p["Wuk"], 0), blocks(p["Wuv"], 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(t, H * dv)
    return _mm(o, p["Wo"], mode)


def _ffn(h, w_in, w_out, mode):
    ab = _mm(h, w_in, mode)
    half = ab.shape[-1] // 2
    return _mm(_round(jax.nn.silu(ab[:, :half]) * ab[:, half:], mode),
               w_out, mode)


def route(probs, sz):
    """``probs [t, E]`` -> the weight of every expert for every token,
    zero where it was not chosen: the groups kept, then ``top_k`` picks,
    each candidate struck out once taken."""
    t, E = probs.shape
    G = sz["expert_groups"] or 1
    cand = probs
    if sz["expert_groups"]:
        best = probs.reshape(t, G, E // G).max(axis=-1)
        left, keep = best, jnp.zeros((t, G), bool)
        for _ in range(sz["groups_kept"]):
            g = jnp.argmax(left, axis=-1)
            keep = keep.at[jnp.arange(t), g].set(True)
            left = left.at[jnp.arange(t), g].set(-1.0)
        cand = jnp.where(jnp.repeat(keep, E // G, axis=1), probs, -1.0)
    gates = jnp.zeros_like(probs)
    for _ in range(sz["top_k"]):
        e = jnp.argmax(cand, axis=-1)
        gates = gates.at[jnp.arange(t), e].set(probs[jnp.arange(t), e])
        cand = cand.at[jnp.arange(t), e].set(-2.0)
    return gates


def _moe(p, h, sz, mode):
    first, count = sz["experts_held"]
    logits = jnp.dot(_round(h, mode), _round(p["Wg"], mode), precision=HI)
    gates = route(jax.nn.softmax(logits, axis=-1), sz)
    gates = gates[:, first:first + count]

    def one(acc, inp):
        w_in, w_out, g_e = inp
        return acc + g_e[:, None] * _ffn(h, w_in, w_out, mode), None

    out, _ = lax.scan(one, jnp.zeros_like(h), (p["W1"], p["W2"], gates.T))
    return sz["routed_scale"] * out + _ffn(h, p["Ws1"], p["Ws2"], mode)


def _static(sizes: dict) -> str:
    return json.dumps(sizes, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("key", "mode"))
def _attention_half(x, na, mla, key: str, mode: str):
    sz = json.loads(key)
    h = _round(_rms(x, na["gamma"], sz["rms_eps"]), mode)
    return x + _mla(mla, h, sz, mode)


@functools.partial(jax.jit, static_argnames=("kind", "key", "mode"))
def _ffn_half(x, nb, ffn, kind: str, key: str, mode: str):
    sz = json.loads(key)
    h = _round(_rms(x, nb["gamma"], sz["rms_eps"]), mode)
    if kind == "mlp":
        return x + _ffn(h, ffn["W1"], ffn["W2"], mode)
    return x + _moe(ffn, h, sz, mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def _embed(p, ids, mode: str):
    return _round(p["W"][ids], mode) + p["b"].astype(F32)


@functools.partial(jax.jit, static_argnames=("rows", "key", "mode"))
def _head(x, nf, out, first, rows: int, key: str, mode: str):
    sz = json.loads(key)
    h = lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    h = _round(_rms(h, nf["gamma"], sz["rms_eps"]), mode)
    return _mm(h, out["W"], mode) + out["b"].astype(F32)


def hidden_states(params, ids, sizes: dict, mode="float32"):
    """The residual stream [T, d_model] after the last block."""
    key = _static(sizes)
    x = _embed(params["embed"], jnp.asarray(ids, jnp.int32), mode=mode)
    for i in range(sizes["layers"]):
        x = _attention_half(x, params[f"n{i}a"], params[f"mla{i}"], key=key,
                            mode=mode)
        x = _ffn_half(x, params[f"n{i}b"], params[f"ffn{i}"],
                      kind="mlp" if i < sizes["dense_layers"] else "moe",
                      key=key, mode=mode)
    return x


def sequence_logits(params, ids, first: int, count: int, sizes: dict,
                    mode="float32", pad_to=None, rows=None):
    """Logits [count, vocab] of positions ``first..first+count-1`` of the
    sequence ``ids``: position p's row predicts token p+1. ``pad_to`` and
    ``rows`` pad the sequence and the window (every layer is causal, so
    padding stays out of every earlier position), so that one compiled
    program serves every length; ``pad_to`` has to be at least
    ``len(ids) + rows``."""
    ids = np.asarray(ids, np.int32)
    rows = rows or count
    pad_to = pad_to or ids.shape[0] + rows
    if pad_to < ids.shape[0] + rows:
        raise ValueError("pad_to has to cover the sequence and the window")
    ids = np.concatenate([ids, np.zeros(pad_to - ids.shape[0], np.int32)])
    x = hidden_states(params, ids, sizes, mode)
    return _head(x, params["n_f"], params["output"],
                 jnp.asarray(first, jnp.int32), rows=rows,
                 key=_static(sizes), mode=mode)[:count]
