"""Plain reference of Trinity-Large-Preview (``afmoe``) as the zoo's
``TrinityLM`` builds one chip's share of it: float32, every product at
``highest`` precision, one full causal pass over a whole sequence with a
``[T, T]`` mask per layer type, rotation by ``arange(T)``, no cache, no
pages, no window of pages, no slots, no batching, importing nothing of the
program.

    x = (E[ids] + b_E) * embedding_multiplier
    per block:  h = x + N2(Attn(N1(x)));  x = h + N4(FFN(N3(h)))
    logits = N_f(x) W_head + b_head
    N(x) = w * x / sqrt(mean(x^2) + eps), a weight vector of its own each

Attn(u), ``heads`` query and ``kv_heads`` key/value heads of ``head_dim``,
no biases: q = Nq(u Wq), k = Nk(u Wk) (Nq, Nk the same norm over the
``head_dim`` channels of one head, one weight vector each, shared by the
heads), v = u Wv, g = u Wg. A ``sliding`` block turns q and k by the
token's position p: with f_i = theta^(-2i/head_dim) for i < head_dim/2,
the pair (t_i, t_{i + head_dim/2}) becomes (t_i cos(p f_i) - t_{i+d/2}
sin(p f_i), t_{i+d/2} cos(p f_i) + t_i sin(p f_i)); key j is visible to
query i iff 0 <= i - j < window. A ``full`` block turns nothing and is
causal over everything. Query head n reads key/value head n // (heads /
kv_heads). score = head_dim^-1/2 q . k, softmax over the visible keys, o =
sum p v, out = (concat(o) * sigmoid(g)) Wo.

FFN: block i < dense_layers: ((h W_gate) silu * (h W_up)) W_down, the
program's layout W1 = [W_gate | W_up], W2 = W_down. Later blocks: s =
sigmoid(h W_r) over ALL ``experts``; the ``top_k`` experts with the largest
s + b (b the stored ``select_bias``), as a written-out selection (each
candidate struck out once taken); w_e = routed_scale * s_e / (sum of the
chosen s + 1e-20): b picks, it does not weigh; y = sum w_e Expert_e(h) +
Shared(h). Only the experts ``experts_held = [first, count]`` exist here: a
loop over them with every token through each and a weight that is zero
where the token did not choose it; what the absent experts would add is
left out, as the program leaves it out.

Attention is computed a key/value head and a block of queries at a time
(the float32 scores of one head over 15,104 positions are 0.9 GB), experts
one at a time, the head's rows after the window is cut: no float32 copy of
the model ever exists.

Weights are the benchmark's: ``make_params`` draws them on the device from
the seed by the configuration file's ``init`` and returns bfloat16 leaves,
named as the program's vertices are, leaf by leaf; ``select_bias`` is drawn
non-zero, so that a bias that leaks into the weights is a visible fault;
the two head norms' weights are ``init["head_norm_gain"]`` (1 if absent),
the stream norms' 1.

``mode``: ``"float32"`` is the reference. ``"fp8"`` is the control: the
same pass with every weight and every product's input and result rounded
to float8_e4m3fn, the nearest precision below the bfloat16 that the
configuration states. ``"bf16"`` is the same pass rounded to bfloat16, the
configuration's own precision (a reading, not a control).
"""

from __future__ import annotations

import functools
import gc
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16
#: float32 scores [query heads of a group, queries in a block, T] are held
#: to this many bytes
SCORE_BYTES = 1 << 28


# -------------------------------------------------------------- weights
def _names(sizes):
    """(vertex name, kind) in the program's order."""
    out = [("embed", "embed")]
    for i in range(sizes["layers"]):
        out += [(f"n{i}a", "norm"), (f"attn{i}", "attn"), (f"n{i}c", "norm"),
                (f"n{i}b", "norm"),
                (f"ffn{i}", "mlp" if i < sizes["dense_layers"] else "moe"),
                (f"n{i}d", "norm")]
    return out + [("n_f", "norm"), ("output", "head")]


@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, F32)).astype(BF16)


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    H, G, dh = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
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

    ones = lambda k, gain=1.0: jnp.full((k,), gain, BF16)  # noqa: E731
    gain = float(init.get("head_norm_gain", 1.0))
    p = {}
    for name, kind in _names(sizes):
        if kind == "embed":
            p[name] = {"W": draw("embed", v, d), "b": jnp.zeros((d,), BF16)}
        elif kind == "norm":
            p[name] = {"gamma": ones(d)}
        elif kind == "attn":
            p[name] = {"Wq": draw("q", d, H * dh), "Wk": draw("k", d, G * dh),
                       "Wv": draw("v", d, G * dh), "Wo": draw("o", H * dh, d),
                       "Wg": draw("gate", d, H * dh),
                       "q_norm": ones(dh, gain), "k_norm": ones(dh, gain)}
        elif kind == "mlp":
            p[name] = {"W1": draw("mlp_in", d, 2 * fm),
                       "W2": draw("mlp_down", fm, d)}
        elif kind == "moe":
            p[name] = {"Wg": draw("router", d, E),
                       "W1": draw("expert_in", held, d, 2 * fe),
                       "W2": draw("expert_down", held, fe, d),
                       "Ws1": draw("shared_in", d, 2 * fs),
                       "Ws2": draw("shared_down", fs, d),
                       "select_bias": draw("select_bias", E)}
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


def _rotate(t, theta):
    """``t [T, heads, d]`` at positions 0..T-1: the two halves paired."""
    T, _, d = t.shape
    f = jnp.asarray(np.power(float(theta), -np.arange(d // 2) * 2.0 / d),
                    F32)
    ang = jnp.arange(T, dtype=F32)[:, None, None] * f[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :d // 2], t[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def query_block(T: int, group: int) -> int:
    """Queries a block: the largest divisor of ``T`` whose float32 scores
    for one key/value head's ``group`` query heads fit ``SCORE_BYTES``."""
    tq = T
    while tq > 1 and (group * tq * T * 4 > SCORE_BYTES or T % tq):
        tq -= 1
    return tq


def _attention(p, u, sz, mode, sliding):
    T = u.shape[0]
    H, G, dh = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = _mm(u, p["Wq"], mode).reshape(T, H, dh)
    k = _mm(u, p["Wk"], mode).reshape(T, G, dh)
    v = _mm(u, p["Wv"], mode).reshape(T, G, dh)
    gate = jax.nn.sigmoid(_mm(u, p["Wg"], mode))
    q = _rms(q, p["q_norm"], sz["rms_eps"])
    k = _rms(k, p["k_norm"], sz["rms_eps"])
    if sliding:
        q, k = _rotate(q, sz["rope_theta"]), _rotate(k, sz["rope_theta"])
    q, k = _round(q, mode), _round(k, mode)
    tq = query_block(T, H // G)
    key_pos = jnp.arange(T)[None, :]

    def kv_head(args):
        qh, kh, vh = args              # [T, H/G, dh], [T, dh], [T, dh]

        def block(args):
            qb, q0 = args              # [tq, H/G, dh], the first's position
            delta = (q0 + jnp.arange(tq))[:, None] - key_pos
            seen = delta >= 0
            if sliding:
                seen = seen & (delta < sz["window"])
            sc = jnp.einsum("tnd,sd->nts", qb, kh, precision=HI) * dh ** -0.5
            w = jax.nn.softmax(jnp.where(seen[None], sc, -1e30), axis=-1)
            return _round(jnp.einsum("nts,sd->tnd", w, vh, precision=HI),
                          mode)

        o = lax.map(block, (qh.reshape(T // tq, tq, H // G, dh),
                            jnp.arange(T // tq) * tq))
        return o.reshape(T, H // G, dh)

    o = lax.map(kv_head, (jnp.moveaxis(q.reshape(T, G, H // G, dh), 1, 0),
                          jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(T, H * dh)     # head n = g * H/G + j
    return _mm(_round(o * gate, mode), p["Wo"], mode)


def _ffn(h, w_in, w_out, mode):
    ab = _mm(h, w_in, mode)
    half = ab.shape[-1] // 2
    return _mm(_round(jax.nn.silu(ab[:, :half]) * ab[:, half:], mode),
               w_out, mode)


def route(scores, bias, sz):
    """``scores [t, E]`` (each expert's sigmoid) -> the weight of every
    expert for every token, zero where it was not chosen: ``top_k`` picks by
    score + bias, each candidate struck out once taken; the chosen weigh
    their unbiased score over the chosen scores' sum."""
    t = scores.shape[0]
    cand = scores + bias.astype(F32)[None, :]
    chosen = jnp.zeros_like(scores)
    for _ in range(sz["top_k"]):
        e = jnp.argmax(cand, axis=-1)
        chosen = chosen.at[jnp.arange(t), e].set(scores[jnp.arange(t), e])
        cand = cand.at[jnp.arange(t), e].set(-jnp.inf)
    return sz["routed_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _moe(p, h, sz, mode):
    first, count = sz["experts_held"]
    logits = jnp.dot(_round(h, mode), _round(p["Wg"], mode), precision=HI)
    gates = route(jax.nn.sigmoid(logits), p["select_bias"], sz)
    gates = gates[:, first:first + count]

    def one(acc, inp):
        w_in, w_out, g_e = inp
        return acc + g_e[:, None] * _ffn(h, w_in, w_out, mode), None

    out, _ = lax.scan(one, jnp.zeros_like(h), (p["W1"], p["W2"], gates.T))
    return out + _ffn(h, p["Ws1"], p["Ws2"], mode)


def _static(sizes: dict) -> str:
    return json.dumps(sizes, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("sliding", "key", "mode"))
def _attention_half(x, na, attn, nc, sliding: bool, key: str, mode: str):
    sz = json.loads(key)
    u = _round(_rms(x, na["gamma"], sz["rms_eps"]), mode)
    a = _attention(attn, u, sz, mode, sliding)
    return x + _round(_rms(a, nc["gamma"], sz["rms_eps"]), mode)


@functools.partial(jax.jit, static_argnames=("kind", "key", "mode"))
def _ffn_half(x, nb, ffn, nd, kind: str, key: str, mode: str):
    sz = json.loads(key)
    h = _round(_rms(x, nb["gamma"], sz["rms_eps"]), mode)
    y = _ffn(h, ffn["W1"], ffn["W2"], mode) if kind == "mlp" \
        else _moe(ffn, h, sz, mode)
    return x + _round(_rms(y, nd["gamma"], sz["rms_eps"]), mode)


@functools.partial(jax.jit, static_argnames=("scale", "mode"))
def _embed(p, ids, scale: float, mode: str):
    return _round((_round(p["W"][ids], mode) + p["b"].astype(F32)) * scale,
                  mode)


@functools.partial(jax.jit, static_argnames=("rows", "key", "mode"))
def _head(x, nf, out, first, rows: int, key: str, mode: str):
    sz = json.loads(key)
    h = lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    h = _round(_rms(h, nf["gamma"], sz["rms_eps"]), mode)
    return _mm(h, out["W"], mode) + out["b"].astype(F32)


def hidden_states(params, ids, sizes: dict, mode="float32"):
    """The residual stream [T, d_model] after the last block."""
    key = _static(sizes)
    x = _embed(params["embed"], jnp.asarray(ids, jnp.int32),
               scale=float(sizes["embedding_multiplier"]), mode=mode)
    for i, kind in enumerate(sizes["layer_types"]):
        x = _attention_half(x, params[f"n{i}a"], params[f"attn{i}"],
                            params[f"n{i}c"], sliding=kind == "sliding",
                            key=key, mode=mode)
        x = _ffn_half(x, params[f"n{i}b"], params[f"ffn{i}"],
                      params[f"n{i}d"],
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
