"""Plain reference of Falcon-H1-34B-Instruct (``falcon_h1``) as the zoo's
``FalconH1LM`` builds it: float32, every product at ``highest`` precision,
one full causal pass over a whole sequence, rotation by ``arange(T)``, no
cache, no pages, no slots, no batching, importing nothing of the program.

    x = (E[ids] + b_E) * embedding_multiplier
    per block:  h = RMSNorm(x)
                x = x + ssm_out * Mamba(ssm_in * h) + attention_out * Attn(attention_in * h)
                x = x + MLP(RMSNorm'(x))
    logits = (RMSNorm_f(x) W_head + b_head) * lm_head_multiplier
    RMSNorm(x) = w * x / sqrt(mean(x^2) + eps)

Attn(u) (no biases): q = u Wq (heads x head_dim), k = (u Wk) * key_multiplier,
v = u Wv (kv_heads x head_dim); q and k rotated at the token's position p:
with f_i = theta^(-2i/head_dim), i < head_dim/2, the pair (t_i, t_{i+head_dim/2})
becomes (t_i cos(p f_i) - t_{i+head_dim/2} sin(p f_i), t_{i+head_dim/2} cos(p f_i)
+ t_i sin(p f_i)) (the half-split pairing of ``rotate_half``);
softmax_causal(q k^T / sqrt(head_dim)) v, query head j reading key/value
head j // (heads // kv_heads); then Wo (heads * head_dim x d_model).

Mamba(u): [z | x | B | C | dt] = (u W_in) * m, m holding ``ssm_multipliers[s]``
over segment s; [x|B|C]_t = silu(b_c + sum_j w_c[:, j] [x|B|C]_{t-K+1+j})
(depthwise, causal, zeros before the start); x as H heads of P, B and C as
G groups of N, head j reading group j // (H // G); dt = softplus(dt +
dt_bias); A = -exp(A_log); the recurrence as a plain ``lax.scan`` over time,
S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t, y_t = S_t C_t + D x_t (NOT
the chunked form the program runs); y = w * GroupRMSNorm(y * silu(z)), gate
first, the mean of squares over each group's H P / G channels; then W_out.

MLP(g) = ((g W_up) * silu((g W_gate) * mlp_multipliers[0])) W_down *
mlp_multipliers[1]; the program's layout: W1 = [W_gate | W_up], W2 = W_down.

Weights are the benchmark's: ``make_params`` draws them on the device from
the seed by the configuration file's ``init`` and returns bfloat16 leaves,
named as the program's vertices are. It draws leaf by leaf, and a leaf of
more than 2**28 elements (the embedding and the head, 1.34 B each) in row
blocks written into the leaf in place, so that no float32 draw of a whole
such leaf ever exists beside the 10.5 GB of results. The driver hands the
same tree to the program and to this reference, which upcasts a block's
mixers and a block's feed-forward each inside one jitted call, gathers the
embedding's rows before it upcasts them, and computes the head in column
blocks: no float32 copy of the model, or of a leaf of the vocabulary's
size, ever exists.

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
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
F32 = jnp.float32
BF16 = jnp.bfloat16
#: a leaf larger than this is drawn in row blocks of at most half of it
WHOLE_DRAW = 2 ** 28
#: columns of the head computed at a time
HEAD_COLUMNS = 32768


# -------------------------------------------------------------- weights
@functools.partial(jax.jit, static_argnames=("shape", "std"))
def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, F32)).astype(BF16)


@functools.partial(jax.jit, static_argnames=("shape", "std"),
                   donate_argnums=(0,))
def _fill_rows(leaf, key, row, shape, std):
    block = (std * jax.random.normal(key, shape, F32)).astype(BF16)
    return lax.dynamic_update_slice(leaf, block, (row, 0))


def _kernel(key, shape, std):
    """normal(0, std) in bfloat16; a large leaf in row blocks, in place."""
    std = float(std)
    if shape[0] * shape[1] <= WHOLE_DRAW:
        return _normal(key, shape, std)
    rows = max(1, (WHOLE_DRAW // 2) // shape[1])
    leaf = jnp.zeros(shape, BF16)
    for i, row in enumerate(range(0, shape[0], rows)):
        n = min(rows, shape[0] - row)
        leaf = _fill_rows(leaf, jax.random.fold_in(key, i),
                          jnp.asarray(row, jnp.int32), (n, shape[1]), std)
    return leaf


def _mamba_scalars(key, sizes, init):
    """Mamba-2's published initialisation of what is not a kernel."""
    H, P, N = sizes["mamba_heads"], sizes["mamba_head_dim"], sizes["d_state"]
    G, K = sizes["n_groups"], sizes["d_conv"]
    di = H * P
    cd = di + 2 * G * N
    ka, kd, kc = jax.random.split(key, 3)
    a = jax.random.uniform(ka, (H,), F32, init["a_min"], init["a_max"])
    lo, hi = math.log(init["dt_min"]), math.log(init["dt_max"])
    dt = jnp.exp(jax.random.uniform(kd, (H,), F32) * (hi - lo) + lo)
    bound = 1.0 / math.sqrt(K)
    return {"conv_w": jax.random.uniform(kc, (cd, K), F32, -bound,
                                         bound).astype(BF16),
            "conv_b": jnp.zeros((cd,), BF16),
            # inverse softplus: softplus(dt_bias) == dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(BF16),
            "A_log": jnp.log(a).astype(BF16),
            "D": jnp.ones((H,), BF16),
            "norm_w": jnp.ones((di,), BF16)}


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    d, v = sizes["d_model"], sizes["vocab"]
    H, P, N = sizes["mamba_heads"], sizes["mamba_head_dim"], sizes["d_state"]
    G = sizes["n_groups"]
    di = H * P
    qw, kvw = sizes["heads"] * sizes["head_dim"], \
        sizes["kv_heads"] * sizes["head_dim"]
    f = sizes["mlp_width"]
    std = init["std"]
    # The weights fill most of a chip, and a released program's copy of them
    # sits in reference cycles (a network's cached programs close over the
    # network): collect those first, or the second copy does not fit.
    gc.collect()
    # seeds pass 2**31: fold the high bits in instead of truncating them
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
    count = iter(range(1 << 20))

    def key():
        return jax.random.fold_in(root, next(count))

    ones = lambda: {"gamma": jnp.ones((d,), BF16)}  # noqa: E731
    p = {"embed": {"W": _kernel(key(), (v, d), std["embed"]),
                   "b": jnp.zeros((d,), BF16)}}
    for i in range(sizes["layers"]):
        p[f"n{i}a"] = ones()
        p[f"ssm{i}"] = {
            "W_in": _kernel(key(), (d, 2 * di + 2 * G * N + H),
                            std["ssm_in"]),
            **_mamba_scalars(key(), sizes, init),
            "W_out": _kernel(key(), (di, d), std["ssm_out"])}
        p[f"attn{i}"] = {"Wq": _kernel(key(), (d, qw), std["q"]),
                         "Wk": _kernel(key(), (d, kvw), std["k"]),
                         "Wv": _kernel(key(), (d, kvw), std["v"]),
                         "Wo": _kernel(key(), (qw, d), std["o"])}
        p[f"n{i}b"] = ones()
        p[f"mlp{i}"] = {"W1": _kernel(key(), (d, 2 * f), std["mlp_in"]),
                        "W2": _kernel(key(), (f, d), std["mlp_down"])}
    p["n_f"] = ones()
    p["output"] = {"W": _kernel(key(), (d, v), std["head"]),
                   "b": jnp.zeros((v,), BF16)}
    return p


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


def _rotate(t, theta):
    """[T, heads, head_dim] turned at positions 0..T-1, half-split pairs."""
    n, _, hd = t.shape
    half = hd // 2
    freq = np.power(float(theta), -np.arange(half) * 2.0 / hd)
    ang = jnp.arange(n, dtype=F32)[:, None, None] \
        * jnp.asarray(freq, F32)                               # [T, 1, hd/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, u, sz, mode):
    t = u.shape[0]
    kvh, hd = sz["kv_heads"], sz["head_dim"]
    g = sz["heads"] // kvh
    q = _mm(u, p["Wq"], mode).reshape(t, kvh * g, hd)
    k = (_mm(u, p["Wk"], mode) * sz["key_multiplier"]).reshape(t, kvh, hd)
    v = _mm(u, p["Wv"], mode).reshape(t, kvh, hd)
    q = _round(_rotate(q, sz["rope_theta"]), mode).reshape(t, kvh, g, hd)
    k = _round(_rotate(k, sz["rope_theta"]), mode)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", w, v, precision=HI)
    return _mm(_round(o, mode).reshape(t, -1), p["Wo"], mode)


def _mamba(p, u, sz, mode):
    t = u.shape[0]
    H, P, N = sz["mamba_heads"], sz["mamba_head_dim"], sz["d_state"]
    G, K = sz["n_groups"], sz["d_conv"]
    di, gn = H * P, G * N
    cd = di + 2 * gn
    m = np.repeat(sz["ssm_multipliers"], (di, di, gn, gn, H))
    proj = _round(_mm(u, p["W_in"], mode) * jnp.asarray(m, F32), mode)
    z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cd), F32), xbc], axis=0)
    w = _round(p["conv_w"], mode)
    acc = _round(p["conv_b"], mode)
    for j in range(K):
        acc = acc + w[:, j] * pad[j:j + t]
    xbc = _round(jax.nn.silu(acc), mode)
    x = xbc[:, :di].reshape(t, G, H // G, P)
    bm = xbc[:, di:di + gn].reshape(t, G, N)
    cm = xbc[:, di + gn:].reshape(t, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32)).reshape(t, G, H // G)
    a = -jnp.exp(p["A_log"].astype(F32)).reshape(G, H // G)

    def step(S, inp):
        x_t, b_t, c_t, dt_t = inp
        S = jnp.exp(dt_t * a)[:, :, None, None] * S \
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :]
        return S, jnp.sum(S * c_t[:, None, None, :], axis=-1)

    _, y = lax.scan(step, jnp.zeros((G, H // G, P, N), F32),
                    (x, bm, cm, dt))
    y = y + p["D"].astype(F32).reshape(G, H // G, 1) * x
    y = y.reshape(t, G, di // G) * jax.nn.silu(z).reshape(t, G, di // G)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                      + sz["rms_eps"])
    y = _round(y.reshape(t, di) * p["norm_w"].astype(F32), mode)
    return _mm(y, p["W_out"], mode)


def _static(sizes: dict) -> str:
    return json.dumps(sizes, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("key", "mode"))
def _mixers(x, na, ssm, attn, key: str, mode: str):
    """Both mixers of a block on one normed input; their weights are
    upcast here and nowhere else."""
    sz = json.loads(key)
    h = _rms(x, na["gamma"], sz["rms_eps"])
    return x \
        + sz["ssm_out_multiplier"] * _mamba(
            ssm, _round(sz["ssm_in_multiplier"] * h, mode), sz, mode) \
        + sz["attention_out_multiplier"] * _attention(
            attn, _round(sz["attention_in_multiplier"] * h, mode), sz, mode)


@functools.partial(jax.jit, static_argnames=("key", "mode"))
def _mlp(x, nb, mlp, key: str, mode: str):
    sz = json.loads(key)
    gate_m, down_m = sz["mlp_multipliers"]
    g = _round(_rms(x, nb["gamma"], sz["rms_eps"]), mode)
    ab = _mm(g, mlp["W1"], mode)
    f = ab.shape[-1] // 2
    hidden = _round(jax.nn.silu(ab[:, :f] * gate_m) * ab[:, f:], mode)
    return x + _mm(hidden, mlp["W2"], mode) * down_m


@functools.partial(jax.jit, static_argnames=("key", "mode"))
def _embed(p, ids, key: str, mode: str):
    sz = json.loads(key)
    # the rows first, then their upcast: never the table's
    return (_round(p["W"][ids], mode) + p["b"].astype(F32)) \
        * sz["embedding_multiplier"]


@functools.partial(jax.jit, static_argnames=("rows", "key", "mode"))
def _final_norm(x, nf, first, rows: int, key: str, mode: str):
    sz = json.loads(key)
    h = lax.dynamic_slice_in_dim(x, first, rows, axis=0)
    return _round(_rms(h, nf["gamma"], sz["rms_eps"]), mode)


@functools.partial(jax.jit, static_argnames=("cols", "key", "mode"))
def _head_columns(h, out, col, cols: int, key: str, mode: str):
    sz = json.loads(key)
    w = lax.dynamic_slice_in_dim(out["W"], col, cols, axis=1)
    b = lax.dynamic_slice_in_dim(out["b"], col, cols, axis=0)
    return (_mm(h, w, mode) + b.astype(F32)) * sz["lm_head_multiplier"]


def hidden_states(params, ids, sizes: dict, mode="float32"):
    """The residual stream [T, d_model] after the last block."""
    key = _static(sizes)
    x = _embed(params["embed"], jnp.asarray(ids, jnp.int32), key=key,
               mode=mode)
    for i in range(sizes["layers"]):
        x = _mixers(x, params[f"n{i}a"], params[f"ssm{i}"],
                    params[f"attn{i}"], key=key, mode=mode)
        x = _mlp(x, params[f"n{i}b"], params[f"mlp{i}"], key=key, mode=mode)
    return x


def sequence_logits(params, ids, first: int, count: int, sizes: dict,
                    mode="float32", pad_to=None, rows=None):
    """Logits [count, vocab] of positions ``first..first+count-1`` of the
    sequence ``ids``: position p's row predicts token p+1. ``pad_to`` and
    ``rows`` pad the sequence and the window (every layer is causal, so
    padding stays out of every earlier position), so that one compiled
    program serves every length; ``pad_to`` has to be at least
    ``len(ids) + rows``. The head is computed ``HEAD_COLUMNS`` columns at a
    time: its kernel is never upcast whole."""
    ids = np.asarray(ids, np.int32)
    rows = rows or count
    pad_to = pad_to or ids.shape[0] + rows
    if pad_to < ids.shape[0] + rows:
        raise ValueError("pad_to has to cover the sequence and the window")
    ids = np.concatenate([ids, np.zeros(pad_to - ids.shape[0], np.int32)])
    key = _static(sizes)
    x = hidden_states(params, ids, sizes, mode)
    h = _final_norm(x, params["n_f"], jnp.asarray(first, jnp.int32),
                    rows=rows, key=key, mode=mode)
    v = sizes["vocab"]
    parts = []
    for col in range(0, v, HEAD_COLUMNS):
        parts.append(_head_columns(
            h, params["output"], jnp.asarray(col, jnp.int32),
            cols=min(HEAD_COLUMNS, v - col), key=key, mode=mode))
    return jnp.concatenate(parts, axis=1)[:count]
