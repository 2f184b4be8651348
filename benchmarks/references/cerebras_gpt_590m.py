"""Plain reference of the GPT block stack the zoo's ``TransformerLM``
builds, in float32 with every product at ``highest`` precision: one full
causal forward pass over a whole sequence, no cache, no pages, no
batching, importing nothing of the program.

tokens -> row of the embedding kernel + its bias -> + sinusoid
(sin | cos halves, wavelengths 10000^(i/half)) -> ``layers`` pre-norm
blocks [LN(eps 1e-5) -> q, k, v = x Wq, x Wk, x Wv (no biases), heads of
``head_dim``, softmax(q k^T / sqrt(head_dim)) causal, v -> Wo + b ->
+residual -> LN -> Dense(ffn, gelu tanh) -> Dense(d_model) -> +residual]
-> LN -> output kernel + bias = logits.

The weights are the benchmark's: ``make_params`` draws them on the device
in one jitted call from the seed by the configuration file's ``init``, and
the driver hands the same tree to the program and to this reference.

``mode``: ``"float32"`` is the reference. ``"bfloat16"`` is the control:
the same pass with weights and activations held in bfloat16, the nearest
precision below the float32 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    v, d, f = sizes["vocab"], sizes["d_model"], sizes["ffn"]
    std, estd = float(init["kernel_std"]), float(init["embed_std"])

    def dense(key, shape, s):
        return {"W": s * jax.random.normal(key, shape, jnp.float32),
                "b": jnp.zeros((shape[1],), jnp.float32)}

    def ln():
        return {"gamma": jnp.ones((d,), jnp.float32),
                "beta": jnp.zeros((d,), jnp.float32)}

    def draw(key):
        ks = iter(jax.random.split(key, 2 + 6 * sizes["layers"]))
        p = {"embed": dense(next(ks), (v, d), estd)}
        for i in range(sizes["layers"]):
            p[f"ln{i}a"] = ln()
            p[f"attn{i}"] = {
                "Wq": std * jax.random.normal(next(ks), (d, d), jnp.float32),
                "Wk": std * jax.random.normal(next(ks), (d, d), jnp.float32),
                "Wv": std * jax.random.normal(next(ks), (d, d), jnp.float32),
                "Wo": std * jax.random.normal(next(ks), (d, d), jnp.float32),
                "b": jnp.zeros((d,), jnp.float32)}
            p[f"ln{i}b"] = ln()
            p[f"ff{i}a"] = dense(next(ks), (d, f), std)
            p[f"ff{i}b"] = dense(next(ks), (f, d), std)
        p["ln_f"] = ln()
        p["output"] = dense(next(ks), (d, v), std)
        return p

    # seeds pass 2**31: fold the high bits in instead of truncating them
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(draw)(key)


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + 1e-5) * p["gamma"] + p["beta"]


def _sinusoid(t, d):
    half = (d + 1) // 2
    freq = jnp.exp(-math.log(10000.0)
                   * jnp.arange(half, dtype=jnp.float32) / max(half, 1))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[:, :d]


@functools.partial(jax.jit, static_argnames=("heads", "layers", "mode"))
def hidden_states(params, ids, heads: int, layers: int, mode="float32"):
    """Final-norm hidden states [T, d_model] of one sequence of ids [T]."""
    dt = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    cast = (lambda a: a.astype(dt))
    t = ids.shape[0]
    emb = params["embed"]
    d = emb["W"].shape[1]
    x = cast(emb["W"][ids]) + cast(emb["b"])
    x = x + cast(_sinusoid(t, d))
    hd = d // heads
    causal = jnp.tril(jnp.ones((t, t), bool))

    def mm(a, w):
        return jnp.dot(a, cast(w), precision=HI,
                       preferred_element_type=dt)

    for i in range(layers):
        a = params[f"attn{i}"]
        h = _ln(x, jax.tree_util.tree_map(cast, params[f"ln{i}a"]))
        q = mm(h, a["Wq"]).reshape(t, heads, hd).transpose(1, 0, 2)
        k = mm(h, a["Wk"]).reshape(t, heads, hd).transpose(1, 0, 2)
        v = mm(h, a["Wv"]).reshape(t, heads, hd).transpose(1, 0, 2)
        s = jnp.einsum("hqd,hkd->hqk", q, k, precision=HI,
                       preferred_element_type=dt) / jnp.sqrt(
                           jnp.asarray(hd, dt))
        s = jnp.where(causal, s, jnp.asarray(-1e30, dt))
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hqk,hkd->hqd", w, v, precision=HI,
                       preferred_element_type=dt)
        o = o.transpose(1, 0, 2).reshape(t, d)
        x = x + mm(o, a["Wo"]) + cast(a["b"])
        h = _ln(x, jax.tree_util.tree_map(cast, params[f"ln{i}b"]))
        fa, fb = params[f"ff{i}a"], params[f"ff{i}b"]
        h = jax.nn.gelu(mm(h, fa["W"]) + cast(fa["b"]))
        x = x + mm(h, fb["W"]) + cast(fb["b"])
    return _ln(x, jax.tree_util.tree_map(cast, params["ln_f"]))


@functools.partial(jax.jit, static_argnames=("rows", "mode"))
def logits_window(params, hidden, first, rows: int, mode="float32"):
    """Logits [rows, vocab] of ``rows`` consecutive positions of
    ``hidden`` [T, d_model] from position ``first`` (a traced scalar)."""
    dt = jnp.bfloat16 if mode == "bfloat16" else jnp.float32
    out = params["output"]
    h = lax.dynamic_slice_in_dim(hidden, first, rows, axis=0)
    return (jnp.dot(h.astype(dt), out["W"].astype(dt), precision=HI,
                    preferred_element_type=dt)
            + out["b"].astype(dt)).astype(jnp.float32)


def sequence_logits(params, ids, first: int, count: int, sizes: dict,
                    mode="float32", pad_to=None, rows=None):
    """Logits [count, vocab] of positions ``first..first+count-1`` of the
    sequence ``ids``: position p's row predicts token p+1. ``pad_to`` and
    ``rows`` pad the sequence and the window (causality keeps padding out
    of every earlier position), so that one compiled program serves every
    length; ``pad_to`` has to be at least ``len(ids) + rows``."""
    import numpy as np

    ids = np.asarray(ids, np.int32)
    rows = rows or count
    pad_to = pad_to or ids.shape[0] + rows
    if pad_to < ids.shape[0] + rows:
        raise ValueError("pad_to has to cover the sequence and the window")
    ids = np.concatenate([ids, np.zeros(pad_to - ids.shape[0], np.int32)])
    hid = hidden_states(params, jnp.asarray(ids), heads=sizes["heads"],
                        layers=sizes["layers"], mode=mode)
    return logits_window(params, hid, jnp.asarray(first, jnp.int32),
                         rows=rows, mode=mode)[:count]
