"""Plain reference of granite-4.0-h-small (``granitemoehybrid``) as the
zoo's ``GraniteMoeHybridLM`` builds one chip's share of it: float32, every
product at ``highest`` precision, one full causal pass over a whole
sequence, no cache, no pages, no slots, no batching, importing nothing of
the program.

    x = (E[ids] + b_E) * embedding_multiplier
    per layer:  x = x + residual_multiplier * Mixer(RMSNorm(x))
                h = RMSNorm(x);  x = x + residual_multiplier * (MoE(h) + Shared(h))
    logits = (RMSNorm(x) W_head + b_head) / logits_scaling
    RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)

Attention mixer (no positions, no biases): q = h Wq (heads x head_dim),
k = h Wk, v = h Wv (kv_heads x head_dim); softmax_causal(attention_multiplier
* q k^T) v with query head j reading key/value head j // (heads //
kv_heads); then Wo.

Mamba-2 mixer: [z | xBC | dt] = h W_in; xBC_t = silu(b_c + sum_j w_c[:, j]
xBC_{t-K+1+j}) (depthwise, causal, zeros before the start); xBC = [x | B |
C]; dt = softplus(dt + dt_bias); A = -exp(A_log); the recurrence as a
plain ``lax.scan`` over time, S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer)
B_t, y_t = S_t C_t + D x_t (NOT the chunked form the program runs);
y = RMSNorm(y * silu(z)) over all of d_inner; then W_out.

Experts: l = h W_r over ALL ``experts``; the ``top_k`` largest; g = softmax
over those; expert e: [a | b] = h W_in,e, (silu(a) * b) W_out,e. Only the
experts ``experts_held = [first, count]`` exist here: the layer's result is
sum over the HELD chosen experts of g_e y_e, as a loop over the held
experts with every token through each and a gate that is zero where the
token did not choose it; what the absent experts would add is left out,
as the program leaves it out. Shared(h) has the same form, always on.

Weights are the benchmark's: ``make_params`` draws them on the device in
one jitted call from the seed by the configuration file's ``init`` and
returns bfloat16 leaves, named as the program's vertices are; the driver
hands the same tree to the program and to this reference, which upcasts
one layer at a time (a layer is one jitted call), so no float32 copy of
the whole model ever exists.

``mode``: ``"float32"`` is the reference. ``"fp8"`` is the control: the
same pass with every weight and every product's input and result rounded
to float8_e4m3fn, the nearest precision below the bfloat16 that the
configuration states.
"""

from __future__ import annotations

import functools
import gc
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32


# -------------------------------------------------------------- weights
def _names(sizes):
    """(vertex name, kind) in the program's order."""
    out = [("embed", "embed")]
    for i, kind in enumerate(sizes["layer_types"]):
        out += [(f"n{i}a", "norm"), (f"mix{i}", kind), (f"n{i}b", "norm"),
                (f"moe{i}", "moe")]
    return out + [("n_f", "norm"), ("output", "head")]


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    hd = sizes["head_dim"]
    H, P, N = sizes["mamba_heads"], sizes["mamba_head_dim"], sizes["d_state"]
    G, K = sizes["n_groups"], sizes["d_conv"]
    di = H * P
    cd = di + 2 * G * N
    E, held = sizes["experts"], sizes["experts_held"][1]
    fe, fs = sizes["expert_width"], sizes["shared_width"]
    std, hstd = float(init["kernel_std"]), float(init["head_std"])
    bf = jnp.bfloat16

    def normal(key, shape, s=std):
        return (s * jax.random.normal(key, shape, F32)).astype(bf)

    def draw(key):
        p = {}
        for name, kind in _names(sizes):
            key, k0 = jax.random.split(key)
            ks = iter(jax.random.split(k0, 8))
            if kind == "embed":
                p[name] = {"W": normal(next(ks), (v, d)),
                           "b": jnp.zeros((d,), bf)}
            elif kind == "norm":
                p[name] = {"gamma": jnp.ones((d,), bf)}
            elif kind == "attention":
                kv = sizes["kv_heads"] * hd
                p[name] = {"Wq": normal(next(ks), (d, sizes["heads"] * hd)),
                           "Wk": normal(next(ks), (d, kv)),
                           "Wv": normal(next(ks), (d, kv)),
                           "Wo": normal(next(ks), (sizes["heads"] * hd, d))}
            elif kind == "mamba":
                a = jax.random.uniform(next(ks), (H,), F32,
                                       init["a_min"], init["a_max"])
                lo, hi = math.log(init["dt_min"]), math.log(init["dt_max"])
                dt = jnp.exp(jax.random.uniform(next(ks), (H,), F32)
                             * (hi - lo) + lo)
                bound = 1.0 / math.sqrt(K)
                p[name] = {
                    "W_in": normal(next(ks), (d, 2 * di + 2 * G * N + H)),
                    "conv_w": jax.random.uniform(
                        next(ks), (cd, K), F32, -bound, bound).astype(bf),
                    "conv_b": jnp.zeros((cd,), bf),
                    # inverse softplus: softplus(dt_bias) == dt
                    "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(bf),
                    "A_log": jnp.log(a).astype(bf),
                    "D": jnp.ones((H,), bf),
                    "norm_w": jnp.ones((di,), bf),
                    "W_out": normal(next(ks), (di, d))}
            elif kind == "moe":
                p[name] = {"Wg": normal(next(ks), (d, E)),
                           "W1": normal(next(ks), (held, d, 2 * fe)),
                           "W2": normal(next(ks), (held, fe, d)),
                           "Ws1": normal(next(ks), (d, 2 * fs)),
                           "Ws2": normal(next(ks), (fs, d))}
            else:
                p[name] = {"W": normal(next(ks), (d, v), hstd),
                           "b": jnp.zeros((v,), bf)}
        return p

    # The weights fill most of a chip, and a released program's copy of them
    # sits in reference cycles (a network's cached programs close over the
    # network): collect those first, or the second copy does not fit.
    gc.collect()
    # seeds pass 2**31: fold the high bits in instead of truncating them
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(draw)(key)


# -------------------------------------------------------------- forward
def _round(x, mode):
    if mode == "fp8":
        return jnp.clip(x, -448.0, 448.0).astype(
            jnp.float8_e4m3fn).astype(F32)
    return x.astype(F32)


def _mm(a, w, mode):
    return _round(jnp.dot(_round(a, mode), _round(w, mode), precision=HI),
                  mode)


def _rms(x, w, eps):
    return w.astype(F32) * x * lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _attention(p, h, sz, mode):
    t = h.shape[0]
    kvh, hd = sz["kv_heads"], sz["head_dim"]
    g = sz["heads"] // kvh
    q = _mm(h, p["Wq"], mode).reshape(t, kvh, g, hd)
    k = _mm(h, p["Wk"], mode).reshape(t, kvh, hd)
    v = _mm(h, p["Wv"], mode).reshape(t, kvh, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) \
        * sz["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", w, v, precision=HI)
    return _mm(_round(o, mode).reshape(t, -1), p["Wo"], mode)


def _mamba(p, h, sz, mode):
    t = h.shape[0]
    H, P, N = sz["mamba_heads"], sz["mamba_head_dim"], sz["d_state"]
    K = sz["d_conv"]
    if sz["n_groups"] != 1:
        raise ValueError("the reference is written for one group")
    di = H * P
    cd = di + 2 * N
    proj = _mm(h, p["W_in"], mode)
    z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xbc], axis=0)
    w = _round(p["conv_w"], mode)
    acc = _round(p["conv_b"], mode)
    for j in range(K):
        acc = acc + w[:, j] * pad[j:j + t]
    xbc = _round(jax.nn.silu(acc), mode)
    x = xbc[:, :di].reshape(t, H, P)
    bm, cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))        # [t, H]
    a = -jnp.exp(p["A_log"].astype(F32))                       # [H]

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = jnp.exp(dt_t * a)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return S, jnp.sum(S * c_t[None, None, :], axis=-1)

    _, y = lax.scan(step, jnp.zeros((H, P, N), F32), (x, bm, cm, dt))
    y = y + p["D"].astype(F32)[None, :, None] * x
    y = y.reshape(t, di) * jax.nn.silu(z)
    y = _round(_rms(y, p["norm_w"], sz["rms_eps"]), mode)
    return _mm(y, p["W_out"], mode)


def _ffn(h, w_in, w_out, mode):
    ab = _mm(h, w_in, mode)
    half = ab.shape[-1] // 2
    return _mm(_round(jax.nn.silu(ab[:, :half]) * ab[:, half:], mode),
               w_out, mode)


def _moe(p, h, sz, mode):
    first, count = sz["experts_held"]
    logits = jnp.dot(_round(h, mode), _round(p["Wg"], mode), precision=HI)
    top, idx = lax.top_k(logits, sz["top_k"])
    g = jax.nn.softmax(top, axis=-1)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(h.shape[0])[:, None], idx].set(g)           # [t, E]
    gates = gates[:, first:first + count]

    def one(acc, inp):
        w_in, w_out, g_e = inp
        return acc + g_e[:, None] * _ffn(h, w_in, w_out, mode), None

    out, _ = lax.scan(one, jnp.zeros_like(h),
                      (p["W1"], p["W2"], gates.T))
    return out + _ffn(h, p["Ws1"], p["Ws2"], mode)


def _static(sizes: dict) -> str:
    return json.dumps(sizes, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("kind", "key", "mode"))
def _layer(x, na, mix, nb, moe, kind: str, key: str, mode: str):
    """One whole layer; its weights are upcast here and nowhere else."""
    sz = json.loads(key)
    mixer = _attention if kind == "attention" else _mamba
    r = sz["residual_multiplier"]
    h = _round(_rms(x, na["gamma"], sz["rms_eps"]), mode)
    x = x + r * mixer(mix, h, sz, mode)
    h = _round(_rms(x, nb["gamma"], sz["rms_eps"]), mode)
    return x + r * _moe(moe, h, sz, mode)


@functools.partial(jax.jit, static_argnames=("key", "mode"))
def _embed(p, ids, key: str, mode: str):
    sz = json.loads(key)
    return (_round(p["W"], mode)[ids] + p["b"].astype(F32)) \
        * sz["embedding_multiplier"]


@functools.partial(jax.jit, static_argnames=("rows", "key", "mode"))
def _head(x, nf, out, first, rows: int, key: str, mode: str):
    sz = json.loads(key)
    h = lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    h = _round(_rms(h, nf["gamma"], sz["rms_eps"]), mode)
    return (_mm(h, out["W"], mode) + out["b"].astype(F32)) \
        / sz["logits_scaling"]


def hidden_states(params, ids, sizes: dict, mode="float32"):
    """The residual stream [T, d_model] after the last layer."""
    key = _static(sizes)
    x = _embed(params["embed"], jnp.asarray(ids, jnp.int32), key=key,
               mode=mode)
    for i, kind in enumerate(sizes["layer_types"]):
        x = _layer(x, params[f"n{i}a"], params[f"mix{i}"], params[f"n{i}b"],
                   params[f"moe{i}"], kind=kind, key=key, mode=mode)
    return x


def sequence_logits(params, ids, first: int, count: int, sizes: dict,
                    mode="float32", pad_to=None, rows=None):
    """Logits [count, vocab] of positions ``first..first+count-1`` of the
    sequence ``ids``: position p's row predicts token p+1. ``pad_to`` and
    ``rows`` pad the sequence and the window (every layer is causal, so
    padding stays out of every earlier position), so that one compiled
    program serves every length; ``pad_to`` has to be at least
    ``len(ids) + rows``."""
    import numpy as np

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
