"""Flat-parameter-view helpers.

The reference keeps ALL network params in one contiguous buffer with per-layer views
(MultiLayerNetwork.java:103 flattenedParams, init :443-493) — that is what makes
parameter averaging and serialization one-array ops. Here the canonical form is the
pytree; these helpers provide the equivalent flat view with a deterministic order
(layer index, then the layer's param_order) for checkpoints and averaging parity.
"""

from __future__ import annotations

import numpy as np


def _ordered_items(layer_params: dict, layer):
    order = layer.param_order() if layer is not None else sorted(layer_params)
    for name in order:
        if name in layer_params:
            yield name, layer_params[name]


def flatten_params(params: dict, layers=None) -> np.ndarray:
    """params: {layer_key: {name: array}} -> 1-D float array."""
    chunks = []
    for i in sorted(params, key=lambda k: int(k)):
        layer = layers[int(i)] if layers is not None else None
        for _, v in _ordered_items(params[i], layer):
            chunks.append(np.asarray(v).ravel())
    if not chunks:
        return np.zeros((0,), np.float32)
    return np.concatenate(chunks)


def unflatten_params(flat, params_template: dict, layers=None) -> dict:
    """Inverse of flatten_params; shapes/dtypes come from the template pytree."""
    import jax.numpy as jnp

    flat = np.asarray(flat).ravel()
    out: dict = {}
    off = 0
    for i in sorted(params_template, key=lambda k: int(k)):
        layer = layers[int(i)] if layers is not None else None
        out[i] = dict(params_template[i])
        for name, v in _ordered_items(params_template[i], layer):
            n = int(np.prod(v.shape)) if v.shape else 1
            out[i][name] = jnp.asarray(
                flat[off:off + n].reshape(v.shape), dtype=v.dtype)
            off += n
    if off != flat.size:
        raise ValueError(f"Flat param size {flat.size} != expected {off}")
    return out


def run_fused_on_tpu(fn, *args):
    """Run ``fn(*args)`` jitted on TPU, eagerly elsewhere.

    Network param init is the user: per-layer eager sampling costs one XLA
    compile + one dispatch per distinct shape, while one fused program
    compiles once; on CPU the relation inverts (tiny per-op programs are
    cached across architectures, a fused per-architecture compile is
    not). Values are bitwise identical either way."""
    import jax

    if jax.default_backend() == "tpu":
        fn = jax.jit(fn)
    return fn(*args)
