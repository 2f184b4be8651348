"""Plain reference of the zoo ResNet50's training step, in float32.

Follows ``deeplearning4j_tpu/models/zoo.py`` ``ResNet50`` (after DL4J's
``ResNet50.java``) as its layers are written, in straightforward
``jax.numpy``/``lax``, every product at ``highest`` precision, importing
nothing of the program: zero-pad 3, 7x7/2 conv, BN, relu, 3x3/2 max pool;
bottleneck blocks (1x1, 3x3 same, 1x1; a strided 1x1 shortcut on the
first block of a stage; add; relu), every conv with a bias and followed by
batch norm over (N, H, W) with biased variance, eps 1e-5, running
statistics 0.9 old + 0.1 batch; global average pool; dense softmax head;
loss = mean cross entropy + 0.5*l2*sum(W^2) + l1*sum|W| over conv and
dense kernels (biases, gamma, beta carry no penalty); gradient of the data
loss + l2*W + l1*sign(W); RmsProp h = d*h + (1-d)*g^2,
p -= lr*g/sqrt(h + eps).

Departures of the program that the reference shares: none. Departures that
it does not share, and that the comparison therefore measures: the program
computes every layer but the head in bfloat16 (parameters cast on the fly,
batch-norm statistics in bfloat16 too).

The weights are the benchmark's: ``make_params`` draws them on the device
in one jitted call from the seed, by the configuration file's ``init``
(normal, std 0.5, for kernels; zero biases; gamma 1, beta 0), and the
driver hands the same tree to the program and to this reference.

``mode`` selects the arithmetic: ``"float32"`` is the reference.
``"fp8"`` is the control: as the program computes every layer but the
head in bfloat16 (parameters cast, activations held and batch-norm
statistics taken in it), the control computes every layer but the head in
float8_e4m3fn, the nearest precision below: kernels, every layer's
output (convolution, batch norm, relu, the residual sum, the pooling) and
the batch's mean and variance are rounded to it, as a compute_dtype of
that type would do, saturating at its largest number; products accumulate in
float32, the backward pass is straight through the roundings, the head is
float32 as the program's is. ``"high"`` is the reference with its
products at three bfloat16 passes instead of six: not a control, but the
look that shows which numbers swing by themselves over four steps.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmarks.harness.loader import load_module  # noqa: E402

HI = lax.Precision.HIGHEST


def _table(sizes):
    return load_module("ops", "resnet50_imagenet").layer_table(sizes)


def make_params(seed: int, sizes: dict, init: dict) -> dict:
    """{layer: {W, b} | {gamma, beta}} in float32, one jitted call."""
    table = _table(sizes)
    std = float(init["kernel_std"])

    def draw(key):
        params = {}
        for i, r in enumerate(table):
            k = jax.random.fold_in(key, i)
            if r["kind"] == "conv":
                shape = (r["k"], r["k"], r["cin"], r["cout"])
                params[r["name"]] = {
                    "W": std * jax.random.normal(k, shape, jnp.float32),
                    "b": jnp.zeros((r["cout"],), jnp.float32)}
            elif r["kind"] == "bn":
                params[r["name"]] = {
                    "gamma": jnp.ones((r["c"],), jnp.float32),
                    "beta": jnp.zeros((r["c"],), jnp.float32)}
            elif r["kind"] == "dense":
                params[r["name"]] = {
                    "W": std * jax.random.normal(
                        k, (r["cin"], r["cout"]), jnp.float32),
                    "b": jnp.zeros((r["cout"],), jnp.float32)}
        return params

    # seeds pass 2**31: fold the high bits in instead of truncating them
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.jit(draw)(key)


def init_bn_state(sizes: dict) -> dict:
    return {r["name"]: {"mean": jnp.zeros((r["c"],), jnp.float32),
                        "var": jnp.ones((r["c"],), jnp.float32)}
            for r in _table(sizes) if r["kind"] == "bn"}


def _round(x, mode):
    """Rounds a product's input to the control's precision. The rounding
    is straight-through in the backward pass, where cotangents of any
    size pass: only the values that are multiplied are rounded."""
    if mode == "fp8":
        r = jnp.clip(x, -448.0, 448.0).astype(jnp.float8_e4m3fn).astype(
            jnp.float32)
        return x + lax.stop_gradient(r - x)
    return x


def _precision(mode):
    return lax.Precision.HIGH if mode == "high" else HI


def _conv(x, p, r, mode):
    pad = "SAME" if r["mode"] == "same" else [(r["pad"], r["pad"])] * 2
    y = lax.conv_general_dilated(
        _round(x, mode), _round(p["W"], mode), (r["stride"],) * 2, pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(mode))
    return _round(y + p["b"], mode)


def _bn(x, p, st, mode):
    mean = _round(jnp.mean(x, axis=(0, 1, 2)), mode)
    var = _round(jnp.var(x, axis=(0, 1, 2)), mode)
    y = (x - mean) * lax.rsqrt(var + 1e-5) * p["gamma"] + p["beta"]
    return _round(y, mode), {"mean": 0.9 * st["mean"] + 0.1 * mean,
               "var": 0.9 * st["var"] + 0.1 * var}


def forward(params, bn_state, x, sizes, mode="float32"):
    """Logits and the new running statistics of one training forward."""
    rows = {r["name"]: r for r in _table(sizes)}
    new_state = {}

    def cba(name, h, p, st, out, relu=True):
        y = _conv(h, p[name], rows[name], mode)
        y, out[name + "_bn"] = _bn(y, p[name + "_bn"], st[name + "_bn"],
                                   mode)
        return jnp.maximum(y, 0.0) if relu else y

    # each piece is rematerialised in the backward pass, so that float32
    # activations of a 256-image batch fit beside nothing else on the chip
    @jax.checkpoint
    def stem(x, p, st):
        out = {}
        z = sizes["stem_zero_pad"]
        h = jnp.pad(x, ((0, 0), (z, z), (z, z), (0, 0)))
        h = cba("stem_cnn1", h, p, st, out)
        h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "VALID")
        return h, out

    def pick(tree, names):
        return {n: tree[n] for n in names if n in tree}

    h, out = stem(x, pick(params, ["stem_cnn1", "stem_cnn1_bn"]),
                  pick(bn_state, ["stem_cnn1_bn"]))
    new_state.update(out)
    for si, (_, _, _, blocks) in enumerate(sizes["stages"]):
        for bi in range(blocks):
            n = f"res{si + 2}{'abcdefgh'[bi]}"
            first = bi == 0

            @jax.checkpoint
            def block(h, p, st, n=n, first=first):
                out = {}
                y = cba(n + "_2a", h, p, st, out)
                y = cba(n + "_2b", y, p, st, out)
                y = cba(n + "_2c", y, p, st, out, relu=False)
                s = cba(n + "_1", h, p, st, out, relu=False) if first else h
                return _round(jnp.maximum(y + s, 0.0), mode), out

            names = [n + t + u for t in ("_2a", "_2b", "_2c", "_1")
                     for u in ("", "_bn")]
            h, out = block(h, pick(params, names), pick(bn_state, names))
            new_state.update(out)
    h = jnp.mean(h, axis=(1, 2))
    out = params["output"]
    logits = jnp.dot(_round(h, mode), out["W"],
                     precision=_precision(mode)) + out["b"]
    return logits, new_state


def loss_fn(params, bn_state, x, y, sizes, hyper, mode):
    logits, new_state = forward(params, bn_state, x, sizes, mode)
    data = jnp.mean(-jnp.sum(y * jax.nn.log_softmax(logits, axis=-1),
                             axis=-1))
    sq = sum(jnp.sum(p["W"] ** 2) for p in params.values() if "W" in p)
    ab = sum(jnp.sum(jnp.abs(p["W"])) for p in params.values() if "W" in p)
    penalty = 0.5 * hyper["l2"] * sq + hyper["l1"] * ab
    return data, (data + lax.stop_gradient(penalty), new_state)


@functools.partial(jax.jit, static_argnames=("sizes_key", "mode"))
def _step(params, h, bn_state, x, y, hyper, sizes_key, mode):
    sizes = _SIZES[sizes_key]
    (_, (score, new_state)), grads = jax.value_and_grad(
        lambda p: loss_fn(p, bn_state, x, y, sizes, hyper, mode),
        has_aux=True)(params)
    grads = {n: {k: (g + hyper["l2"] * params[n][k]
                     + hyper["l1"] * jnp.sign(params[n][k]))
                 if k == "W" else g for k, g in sub.items()}
             for n, sub in grads.items()}
    d = hyper["rms_decay"]
    new_h = jax.tree_util.tree_map(lambda a, g: d * a + (1 - d) * g * g,
                                   h, grads)
    new_p = jax.tree_util.tree_map(
        lambda p, g, a: p - hyper["learning_rate"] * g
        / jnp.sqrt(a + hyper["epsilon"]), params, grads, new_h)
    gnorm = jax.tree_util.tree_map(lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
    return new_p, new_h, new_state, score, gnorm


_SIZES: dict = {}


def train_steps(params, xs, ys, sizes: dict, hyper: dict, steps: int,
                mode: str = "float32", rows=None) -> dict:
    """``steps`` optimizer steps from ``params`` over ``xs[i], ys[i]``.

    Returns ``losses`` (the score the program reports: data loss plus the
    penalty's value), the first step's gradient norms per leaf, and the
    parameters, RmsProp state and running statistics after the last step.
    ``rows`` (a slice) plants the half-batch fault: only those rows of
    every batch are used."""
    import json

    key = json.dumps(sizes, sort_keys=True)
    _SIZES[key] = sizes
    hyper = {k: float(v) for k, v in hyper.items()}
    h = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = init_bn_state(sizes)
    losses, g1 = [], None
    p = params
    for i in range(steps):
        x = jnp.asarray(xs[i] if rows is None else xs[i][rows])
        y = jnp.asarray(ys[i] if rows is None else ys[i][rows])
        p, h, state, score, gnorm = _step(p, h, state, x, y, hyper,
                                          sizes_key=key, mode=mode)
        losses.append(float(score))
        if i == 0:
            g1 = gnorm
    return {"losses": losses, "grad1_norms": g1, "params": p, "h": h,
            "bn_state": state}
