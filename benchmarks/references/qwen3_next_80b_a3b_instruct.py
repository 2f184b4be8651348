"""Plain reference of Qwen3-Next-80B-A3B-Instruct (``qwen3_next``) as the
zoo's ``Qwen3NextLM`` builds one chip's share of it: float32, every
product at ``highest`` precision, one causal pass over a whole sequence, no
cache, no pages, no slots, no chunks, no batching, importing nothing of the
program. Written from transformers' ``modeling_qwen3_next.py``.

    x = E[ids] + b_E
    per block:  h = x + Mixer(N1(x));  x = h + MoE(N2(h))
    logits = N_f(x) W_head + b_head
    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)      zero-centred, w its own

A ``"full"`` mixer, ``heads`` query and ``kv_heads`` key/value heads of
``head_dim``, no biases: q = Nq(x Wq) and k = Nk(x Wk) per head (one
zero-centred weight of ``head_dim`` each, shared by the heads), the first
``rope_dims`` channels of each head rotated at positions 0..T-1 (the two
halves of those channels paired, frequencies ``theta^(-2i / rope_dims)``)
and the rest passed through; v = x Wv; query head j reads key/value head
j // (heads / kv_heads); score = head_dim^-1/2 q . k over every key at or
before the query, softmax, o = sum p v; out = (concat(o) * sigmoid(x Wg))
Wo. The published ``q_proj`` gives each head's query and gate side by
side; here they are the two matrices the program holds, ``Wq`` and ``Wg``.

A ``"linear"`` mixer (the gated delta rule): ``linear_key_heads`` query and
key heads of ``linear_key_dim`` (dk), ``linear_heads`` value heads of
``linear_value_dim`` (dv), the program's layout ``W_in = [Wq | Wk | Wv |
Wz | Wa | Wb]`` (the published ``in_proj_qkvz`` and ``in_proj_ba``
de-interleaved by head)::

    [q~ | k~ | v~] = silu(sum_j w_c[:, j] [q~ | k~ | v~]_{t-K+1+j})   zeros before 0
    q = q~ / sqrt(|q~|^2 + 1e-6) * dk^-1/2,  k = k~ / sqrt(|k~|^2 + 1e-6)
    value head j reads query and key head j // (linear_heads / linear_key_heads)
    beta = sigmoid(x Wb)
    alpha = exp(-exp(A_log) softplus(x Wa + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,  S_0 = 0
    o_t = S_t q_t
    out = (Nh(o) * silu(x Wz)) W_out     Nh over each head's dv, weight w (not 1 + w)

The recurrence runs POSITION BY POSITION (a scan of the equation above,
not the program's chunked form); attention is computed a key/value head
and a block of queries at a time; the head's rows after the window is cut.

MoE: p = softmax(x W_r) over ALL ``experts`` router outputs, the ``top_k``
largest kept and divided by their sum (``norm_topk_prob``); the held
experts ``experts_held = (first, count)`` each add ``p_e ((x W1_a) silu *
(x W1_b)) W2`` where chosen (a loop over the held experts, each over every
token); the shared expert adds ``sigmoid(x w_sg) ((x Ws1_a) silu * (x
Ws1_b)) Ws2``. What the absent experts would add is left out, as the
program leaves it out.

Weights are the benchmark's: ``make_params`` draws them on the device from
the seed by the configuration file's ``init`` and returns bfloat16 leaves,
named as the program's vertices are, leaf by leaf: kernels normal(0, std)
by matrix, the convolution uniform in +-``conv_bound``, ``A_log`` the log
of a uniform draw in ``A_range``, ``dt_bias`` the inverse softplus of a
log-uniform draw in ``dt_range``, the q/k norms' ``w`` ``qk_norm_w``, the
block norms' ``w`` 0 (as published), the gated head norm's weight 1.

``mode``: ``"float32"`` is the reference. ``"fp8"`` is the control: the
same pass with every weight and every product's input and result rounded
to float8_e4m3fn, the nearest precision below the bfloat16 that the
configuration states. ``"bf16"`` is the same pass rounded to bfloat16, the
configuration's own precision (a reading, not a control). The state of the
recurrence is float32 in every mode; its inputs and outputs are rounded.
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
#: float32 scores [queries in a block, T] of one key/value head's query
#: heads are held to this many bytes
SCORE_BYTES = 1 << 28
L2_EPS = 1e-6


# -------------------------------------------------------------- weights
def _names(sizes):
    """(vertex name, kind) in the program's order."""
    out = [("embed", "embed")]
    for i, kind in enumerate(sizes["layer_types"]):
        out += [(f"n{i}a", "norm"),
                (f"delta{i}" if kind == "linear" else f"attn{i}", kind),
                (f"n{i}b", "norm"), (f"moe{i}", "moe")]
    return out + [("n_f", "norm"), ("output", "head")]


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, F32)).astype(BF16)


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi"))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, F32, lo, hi)


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    H, G, dh = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
    Hk, Hv = sizes["linear_key_heads"], sizes["linear_heads"]
    dk, dv = sizes["linear_key_dim"], sizes["linear_value_dim"]
    K = sizes["linear_conv"]
    E, held = sizes["experts"], sizes["experts_held"][1]
    fe, fs = sizes["expert_width"], sizes["shared_width"]
    std = init["std"]
    # the weights fill most of a chip, and a released program's copy of them
    # sits in reference cycles (a network's cached programs close over the
    # network): collect those first, or the second copy does not fit
    gc.collect()
    # seeds pass 2**31: fold the high bits in instead of truncating them
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    count = iter(range(1 << 20))

    def key():
        return jax.random.fold_in(root, next(count))

    def draw(which, *shape):
        return _normal(key(), shape, float(std[which]))

    def fill(k, value):
        return jnp.full((k,), value, BF16)

    qk_w = float(init["qk_norm_w"])
    p = {}
    for name, kind in _names(sizes):
        if kind == "embed":
            p[name] = {"W": draw("embed", v, d), "b": jnp.zeros((d,), BF16)}
        elif kind == "norm":
            p[name] = {"gamma": fill(d, 0.0)}
        elif kind == "full":
            p[name] = {"Wq": draw("q", d, H * dh), "Wk": draw("k", d, G * dh),
                       "Wv": draw("v", d, G * dh), "Wo": draw("o", H * dh, d),
                       "Wg": draw("gate", d, H * dh),
                       "q_norm": fill(dh, qk_w), "k_norm": fill(dh, qk_w)}
        elif kind == "linear":
            w_in = jnp.concatenate(
                [draw("lin_q", d, Hk * dk), draw("lin_k", d, Hk * dk),
                 draw("lin_v", d, Hv * dv), draw("lin_z", d, Hv * dv),
                 draw("lin_a", d, Hv), draw("lin_b", d, Hv)], axis=1)
            bound = float(init["conv_bound"])
            a = _uniform(key(), (Hv,), *map(float, init["A_range"]))
            lo, hi = (math.log(float(x)) for x in init["dt_range"])
            dt = jnp.exp(_uniform(key(), (Hv,), lo, hi))
            p[name] = {
                "W_in": w_in,
                "conv_w": _uniform(key(), (2 * Hk * dk + Hv * dv, K), -bound,
                                   bound).astype(BF16),
                # inverse softplus: softplus(dt_bias) == dt
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(BF16),
                "A_log": jnp.log(a).astype(BF16),
                "norm_w": fill(dv, 1.0),
                "W_out": draw("lin_o", Hv * dv, d)}
        elif kind == "moe":
            p[name] = {"Wg": draw("router", d, E),
                       "W1": draw("expert_in", held, d, 2 * fe),
                       "W2": draw("expert_down", held, fe, d),
                       "Ws1": draw("shared_in", d, 2 * fs),
                       "Ws2": draw("shared_down", fs, d),
                       "Wsg": draw("shared_gate", d, 1)}
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


def _norm(x, w, eps):
    """The zero-centred RMS norm: ``x / rms(x) * (1 + w)``."""
    return (1.0 + w.astype(F32)) * x * lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _rotate(t, theta, r):
    """``t [T, heads, d]`` at positions 0..T-1: the first ``r`` channels
    turned (their two halves paired), the rest as they are."""
    T = t.shape[0]
    f = jnp.asarray(np.power(float(theta), -np.arange(r // 2) * 2.0 / r),
                    F32)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * f[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :r // 2], t[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            t[..., r:]], axis=-1)


def query_block(T: int, group: int) -> int:
    """Queries a block: the largest divisor of ``T`` whose float32 scores
    for one key/value head's ``group`` query heads fit ``SCORE_BYTES``."""
    tq = T
    while tq > 1 and (group * tq * T * 4 > SCORE_BYTES or T % tq):
        tq -= 1
    return tq


def _full(p, u, sz, mode):
    T = u.shape[0]
    H, G, dh = sz["heads"], sz["kv_heads"], sz["head_dim"]
    eps = sz["rms_eps"]
    q = _norm(_mm(u, p["Wq"], mode).reshape(T, H, dh), p["q_norm"], eps)
    k = _norm(_mm(u, p["Wk"], mode).reshape(T, G, dh), p["k_norm"], eps)
    v = _mm(u, p["Wv"], mode).reshape(T, G, dh)
    gate = jax.nn.sigmoid(_mm(u, p["Wg"], mode))
    q = _round(_rotate(q, sz["rope_theta"], sz["rope_dims"]), mode)
    k = _round(_rotate(k, sz["rope_theta"], sz["rope_dims"]), mode)
    tq = query_block(T, H // G)
    key_pos = jnp.arange(T)[None, :]

    def kv_head(args):
        qh, kh, vh = args              # [T, H/G, dh], [T, dh], [T, dh]

        def block(args):
            qb, q0 = args              # [tq, H/G, dh], the first's position
            seen = (q0 + jnp.arange(tq))[:, None] >= key_pos
            sc = jnp.einsum("tnd,sd->nts", qb, kh, precision=HI) * dh ** -0.5
            w = jax.nn.softmax(jnp.where(seen[None], sc, -1e30), axis=-1)
            return _round(jnp.einsum("nts,sd->tnd", w, vh, precision=HI),
                          mode)

        o = lax.map(block, (qh.reshape(T // tq, tq, H // G, dh),
                            jnp.arange(T // tq) * tq))
        return o.reshape(T, H // G, dh)

    # query head n = g * H/G + j reads key/value head g
    o = lax.map(kv_head, (jnp.moveaxis(q.reshape(T, G, H // G, dh), 1, 0),
                          jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * dh)
    return _mm(_round(o * gate, mode), p["Wo"], mode)


def _linear(p, u, sz, mode):
    T = u.shape[0]
    Hk, Hv = sz["linear_key_heads"], sz["linear_heads"]
    dk, dv = sz["linear_key_dim"], sz["linear_value_dim"]
    K = sz["linear_conv"]
    Kw, Vw = Hk * dk, Hv * dv
    proj = _mm(u, p["W_in"], mode)
    qkv = proj[:, :2 * Kw + Vw]
    z = proj[:, 2 * Kw + Vw:2 * Kw + 2 * Vw].reshape(T, Hv, dv)
    a = proj[:, 2 * Kw + 2 * Vw:2 * Kw + 2 * Vw + Hv]
    b = proj[:, 2 * Kw + 2 * Vw + Hv:]
    # the causal depthwise convolution, zeros before position 0
    w = _round(p["conv_w"], mode)
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), F32), qkv])
    acc = sum(w[:, j] * padded[j:j + T] for j in range(K))
    qkv = jax.nn.silu(_round(acc, mode))
    q = _round(_l2(qkv[:, :Kw].reshape(T, Hk, dk)) * dk ** -0.5, mode)
    k = _round(_l2(qkv[:, Kw:2 * Kw].reshape(T, Hk, dk)), mode)
    v = _round(qkv[:, 2 * Kw:].reshape(T, Hv, dv), mode)
    # value head j reads query and key head j // (Hv / Hk)
    q = jnp.repeat(q, Hv // Hk, axis=1)
    k = jnp.repeat(k, Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(b)
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(F32))
                    * jax.nn.softplus(a + p["dt_bias"].astype(F32)))

    def step(S, inp):                      # S: [Hv, dv, dk]
        qt, kt, vt, at, bt = inp
        S = at[:, None, None] * (S - bt[:, None, None] * jnp.einsum(
            "hvd,hd,he->hve", S, kt, kt, precision=HI)) \
            + bt[:, None, None] * vt[:, :, None] * kt[:, None, :]
        return S, jnp.einsum("hvd,hd->hv", S, qt, precision=HI)

    _, o = lax.scan(step, jnp.zeros((Hv, dv, dk), F32), (q, k, v, alpha,
                                                           beta))
    o = _round(o, mode)
    y = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + sz["rms_eps"]) * p["norm_w"].astype(F32)
    y = y * jax.nn.silu(z)
    return _mm(_round(y.reshape(T, Vw), mode), p["W_out"], mode)


def _ffn(h, w_in, w_out, mode):
    ab = _mm(h, w_in, mode)
    half = ab.shape[-1] // 2
    return _mm(_round(jax.nn.silu(ab[:, :half]) * ab[:, half:], mode),
               w_out, mode)


def route(logits, sz):
    """``logits [t, E]`` -> the weight of every expert for every token,
    zero where it was not chosen: the softmax over all ``E``, its
    ``top_k`` largest kept and divided by their sum."""
    probs = jax.nn.softmax(logits, axis=-1)
    top, idx = lax.top_k(probs, sz["top_k"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    t = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros_like(probs).at[t, idx].set(top)


def _moe(p, h, sz, mode):
    first, count = sz["experts_held"]
    gates = route(_mm(h, p["Wg"], mode), sz)[:, first:first + count]

    def one(acc, inp):
        w_in, w_out, g_e = inp
        return acc + g_e[:, None] * _ffn(h, w_in, w_out, mode), None

    out, _ = lax.scan(one, jnp.zeros_like(h), (p["W1"], p["W2"], gates.T))
    shared = jax.nn.sigmoid(_mm(h, p["Wsg"], mode)) \
        * _ffn(h, p["Ws1"], p["Ws2"], mode)
    return out + shared


def _static(sizes: dict) -> str:
    return json.dumps(sizes, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("kind", "key", "mode"))
def _block(x, na, mixer, nb, moe, kind: str, key: str, mode: str):
    sz = json.loads(key)
    u = _round(_norm(x, na["gamma"], sz["rms_eps"]), mode)
    m = _linear(mixer, u, sz, mode) if kind == "linear" \
        else _full(mixer, u, sz, mode)
    h = _round(x + m, mode)
    y = _moe(moe, _round(_norm(h, nb["gamma"], sz["rms_eps"]), mode), sz,
             mode)
    return _round(h + y, mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def _embed(p, ids, mode: str):
    return _round(_round(p["W"][ids], mode) + p["b"].astype(F32), mode)


@functools.partial(jax.jit, static_argnames=("rows", "key", "mode"))
def _head(x, nf, out, first, rows: int, key: str, mode: str):
    sz = json.loads(key)
    h = lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    h = _round(_norm(h, nf["gamma"], sz["rms_eps"]), mode)
    return _mm(h, out["W"], mode) + out["b"].astype(F32)


def hidden_states(params, ids, sizes: dict, mode="float32"):
    """The residual stream [T, d_model] after the last block."""
    key = _static(sizes)
    x = _embed(params["embed"], jnp.asarray(ids, jnp.int32), mode=mode)
    for i, kind in enumerate(sizes["layer_types"]):
        mixer = params[f"delta{i}" if kind == "linear" else f"attn{i}"]
        x = _block(x, params[f"n{i}a"], mixer, params[f"n{i}b"],
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
