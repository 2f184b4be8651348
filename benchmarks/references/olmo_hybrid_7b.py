"""Plain reference of Olmo-Hybrid-7B (``olmo_hybrid``) as the zoo's
``OlmoHybridLM`` builds one chip's share of it: float32, every product at
``highest`` precision, one causal pass over a whole sequence, no cache, no
pages, no slots, no chunks, no batching, importing nothing of the program.

    x = E[ids] + b_E
    per block:  h = x + N1(Mixer(x));  x = h + N2(MLP(h))
    logits = N_f(x) W_head + b_head
    N(x) = w * x / sqrt(mean(x^2) + eps), a weight vector of its own each

A ``"full"`` mixer, ``heads`` heads of ``head_dim`` for queries, keys and
values, no biases, no positions: q = Nq(x Wq) and k = Nk(x Wk), each norm
over ALL ``heads * head_dim`` channels of its projection with a weight that
spans them; v = x Wv; score = head_dim^-1/2 q . k over every key at or
before the query, softmax, o = sum p v, out = concat(o) Wo.

A ``"linear"`` mixer (the gated delta rule), ``linear_heads`` heads, keys of
``linear_key_dim`` (dk), values of ``linear_value_dim`` (dv), the program's
layout ``W_in = [Wq | Wk | Wv | Wz | Wa | Wb]``::

    [q~ | k~ | v~] = silu(sum_j w_c[:, j] [q~ | k~ | v~]_{t-K+1+j})   zeros before 0
    q = q~ / sqrt(|q~|^2 + 1e-6) * dk^-1/2,  k = k~ / sqrt(|k~|^2 + 1e-6)
    beta = 2 sigmoid(x Wb)   (1 sigmoid without allow_neg_eigval)
    alpha = exp(-exp(A_log) softplus(x Wa + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,  S_0 = 0
    o_t = S_t q_t
    out = (Nh(o) * silu(x Wz)) W_out      Nh over each head's dv, one weight

The recurrence runs POSITION BY POSITION (a scan of the equation above,
not the program's chunked form); attention is computed a head and a block
of queries at a time; the head's rows after the window is cut.

MLP: ((h W_gate) silu * (h W_up)) W_down, the program's layout W1 =
[W_gate | W_up], W2 = W_down.

Weights are the benchmark's: ``make_params`` draws them on the device from
the seed by the configuration file's ``init`` and returns bfloat16 leaves,
named as the program's vertices are, leaf by leaf: kernels normal(0, std)
by matrix, the convolution uniform in +-``conv_bound``, ``A_log`` the log
of a uniform draw in ``A_range``, ``dt_bias`` the inverse softplus of a
log-uniform draw in ``dt_range`` (the published initialisation), the full
layers' query and key norm weights ``qk_norm_gain``, every other norm's 1.

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
#: float32 scores [queries in a block, T] of one head are held to this many
#: bytes
SCORE_BYTES = 1 << 28
L2_EPS = 1e-6


# -------------------------------------------------------------- weights
def _names(sizes):
    """(vertex name, kind) in the program's order."""
    out = [("embed", "embed")]
    for i, kind in enumerate(sizes["layer_types"]):
        out += [(f"delta{i}" if kind == "linear" else f"attn{i}", kind),
                (f"n{i}a", "norm"), (f"mlp{i}", "mlp"), (f"n{i}b", "norm")]
    return out + [("n_f", "norm"), ("output", "head")]


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, F32)).astype(BF16)


@functools.partial(jax.jit, static_argnames=("shape", "lo", "hi"))
def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, F32, lo, hi)


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    A = sizes["heads"] * sizes["head_dim"]
    H, dk, dv = (sizes["linear_heads"], sizes["linear_key_dim"],
                 sizes["linear_value_dim"])
    K, F = sizes["linear_conv"], sizes["mlp_width"]
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

    ones = lambda k, gain=1.0: jnp.full((k,), gain, BF16)  # noqa: E731
    gain = float(init.get("qk_norm_gain", 1.0))
    p = {}
    for name, kind in _names(sizes):
        if kind == "embed":
            p[name] = {"W": draw("embed", v, d), "b": jnp.zeros((d,), BF16)}
        elif kind == "norm":
            p[name] = {"gamma": ones(d)}
        elif kind == "full":
            p[name] = {"Wq": draw("q", d, A), "Wk": draw("k", d, A),
                       "Wv": draw("v", d, A), "Wo": draw("o", A, d),
                       "q_norm": ones(A, gain), "k_norm": ones(A, gain)}
        elif kind == "linear":
            w_in = jnp.concatenate(
                [draw("lin_q", d, H * dk), draw("lin_k", d, H * dk),
                 draw("lin_v", d, H * dv), draw("lin_z", d, H * dv),
                 draw("lin_a", d, H), draw("lin_b", d, H)], axis=1)
            bound = float(init["conv_bound"])
            a = _uniform(key(), (H,), *map(float, init["A_range"]))
            lo, hi = (math.log(float(x)) for x in init["dt_range"])
            dt = jnp.exp(_uniform(key(), (H,), lo, hi))
            p[name] = {
                "W_in": w_in,
                "conv_w": _uniform(key(), (2 * H * dk + H * dv, K), -bound,
                                   bound).astype(BF16),
                # inverse softplus: softplus(dt_bias) == dt
                "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(BF16),
                "A_log": jnp.log(a).astype(BF16),
                "norm_w": ones(dv),
                "W_out": draw("lin_o", H * dv, d)}
        elif kind == "mlp":
            p[name] = {"W1": draw("mlp_in", d, 2 * F),
                       "W2": draw("mlp_down", F, d)}
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


def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def query_block(T: int) -> int:
    """Queries a block: the largest divisor of ``T`` whose float32 scores
    for one head fit ``SCORE_BYTES``."""
    tq = T
    while tq > 1 and (tq * T * 4 > SCORE_BYTES or T % tq):
        tq -= 1
    return tq


def _full(p, x, sz, mode):
    T = x.shape[0]
    H, dh = sz["heads"], sz["head_dim"]
    q = _rms(_mm(x, p["Wq"], mode), p["q_norm"], sz["rms_eps"])
    k = _rms(_mm(x, p["Wk"], mode), p["k_norm"], sz["rms_eps"])
    v = _mm(x, p["Wv"], mode).reshape(T, H, dh)
    q = _round(q, mode).reshape(T, H, dh)
    k = _round(k, mode).reshape(T, H, dh)
    tq = query_block(T)
    key_pos = jnp.arange(T)[None, :]

    def head(args):
        qh, kh, vh = args                  # [T, dh] each

        def block(args):
            qb, q0 = args                  # [tq, dh], the first's position
            seen = (q0 + jnp.arange(tq))[:, None] >= key_pos
            sc = jnp.dot(qb, kh.T, precision=HI) * dh ** -0.5
            w = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
            return _round(jnp.dot(w, vh, precision=HI), mode)

        o = lax.map(block, (qh.reshape(T // tq, tq, dh),
                            jnp.arange(T // tq) * tq))
        return o.reshape(T, dh)

    o = lax.map(head, (jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0),
                       jnp.moveaxis(v, 1, 0)))
    return _mm(jnp.moveaxis(o, 0, 1).reshape(T, H * dh), p["Wo"], mode)


def _linear(p, x, sz, mode):
    T = x.shape[0]
    H, dk, dv = (sz["linear_heads"], sz["linear_key_dim"],
                 sz["linear_value_dim"])
    K = sz["linear_conv"]
    Kw, Vw = H * dk, H * dv
    proj = _mm(x, p["W_in"], mode)
    qkv = proj[:, :2 * Kw + Vw]
    z = proj[:, 2 * Kw + Vw:2 * Kw + 2 * Vw].reshape(T, H, dv)
    a = proj[:, 2 * Kw + 2 * Vw:2 * Kw + 2 * Vw + H]
    b = proj[:, 2 * Kw + 2 * Vw + H:]
    # the causal depthwise convolution, zeros before position 0
    w = _round(p["conv_w"], mode)
    padded = jnp.concatenate([jnp.zeros((K - 1, qkv.shape[1]), F32), qkv])
    acc = sum(w[:, j] * padded[j:j + T] for j in range(K))
    qkv = jax.nn.silu(_round(acc, mode))
    q = _round(_l2(qkv[:, :Kw].reshape(T, H, dk)) * dk ** -0.5, mode)
    k = _round(_l2(qkv[:, Kw:2 * Kw].reshape(T, H, dk)), mode)
    v = _round(qkv[:, 2 * Kw:].reshape(T, H, dv), mode)
    beta = jax.nn.sigmoid(b) * (2.0 if sz["allow_neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(F32))
                    * jax.nn.softplus(a + p["dt_bias"].astype(F32)))

    def step(S, inp):                      # S: [H, dv, dk]
        qt, kt, vt, at, bt = inp
        S = at[:, None, None] * (S - bt[:, None, None] * jnp.einsum(
            "hvd,hd,he->hve", S, kt, kt, precision=HI)) \
            + bt[:, None, None] * vt[:, :, None] * kt[:, None, :]
        return S, jnp.einsum("hvd,hd->hv", S, qt, precision=HI)

    _, o = lax.scan(step, jnp.zeros((H, dv, dk), F32), (q, k, v, alpha,
                                                          beta))
    o = _round(o, mode)
    y = _rms(o, p["norm_w"], sz["rms_eps"]) * jax.nn.silu(z)
    return _mm(y.reshape(T, Vw), p["W_out"], mode)


def _ffn(h, w_in, w_out, mode):
    ab = _mm(h, w_in, mode)
    half = ab.shape[-1] // 2
    return _mm(_round(jax.nn.silu(ab[:, :half]) * ab[:, half:], mode),
               w_out, mode)


def _static(sizes: dict) -> str:
    return json.dumps(sizes, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("kind", "key", "mode"))
def _block(x, mixer, na, mlp, nb, kind: str, key: str, mode: str):
    sz = json.loads(key)
    m = _linear(mixer, x, sz, mode) if kind == "linear" \
        else _full(mixer, x, sz, mode)
    h = x + _round(_rms(m, na["gamma"], sz["rms_eps"]), mode)
    y = _ffn(h, mlp["W1"], mlp["W2"], mode)
    return h + _round(_rms(y, nb["gamma"], sz["rms_eps"]), mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def _embed(p, ids, mode: str):
    return _round(_round(p["W"][ids], mode) + p["b"].astype(F32), mode)


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
    for i, kind in enumerate(sizes["layer_types"]):
        mixer = params[f"delta{i}" if kind == "linear" else f"attn{i}"]
        x = _block(x, mixer, params[f"n{i}a"], params[f"mlp{i}"],
                   params[f"n{i}b"], kind=kind, key=key, mode=mode)
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
