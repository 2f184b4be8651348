"""Closed-form L1/L2 gradient application, shared by every gradient path.

The nets' ``_loss`` reports the penalty VALUE but stop_gradients it
(autodiff through the per-tensor reductions measured 30% of the ResNet50
train step, record deleted at PR 21); every consumer of ``jax.grad`` over a
net loss must therefore add the closed form ``l2*W + l1*sign(W)`` back.
This is also the reference's own architecture: DL4J applies l1/l2 inside
the updater (nn/updater/BaseUpdater postApply), not through backprop.

One helper, four call sites (MultiLayerNetwork/ComputationGraph steps,
gradient checker, solvers, ParallelWrapper) — the bug class this kills is
a fifth gradient path silently training without weight decay.
"""

from __future__ import annotations


def penalty_value(net, params):
    """The reported L1/L2 penalty VALUE (reference: computeScore adds
    fullNetworkL1 + fullNetworkL2), computed in ONE fused reduction per
    distinct (l1, l2, dtype) coefficient group over concatenated raveled
    params — NOT one reduction per tensor.

    Per-tensor reductions measured 43% of the bf16 ResNet50 b128 train
    step on a v5e (round-5 trace): ~160 param tensors x {abs-reduce,
    square-reduce, convert} is ~480 launch-overhead-bound micro-kernels
    per step, while the same math over a few concatenated vectors is a
    handful of bandwidth-bound passes. Same value (up to float reduction
    order), so score parity holds.

    Layers that override ``regularization`` beyond the BaseLayer form
    (e.g. MoE's load-balance term) keep their own (slow-path) method so
    the reported value stays exact.
    """
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, Layer

    def layer_param_pairs():
        layers = getattr(net, "layers", None)
        if isinstance(layers, list):
            for i, layer in enumerate(layers):
                yield layer, params.get(str(i), {})
            return
        vertices = getattr(getattr(net, "conf", None), "vertices", None)
        if isinstance(vertices, dict):
            for name, v in vertices.items():
                layer = getattr(v, "layer", None)
                yield (layer if layer is not None else v), \
                    params.get(name, {})

    groups: dict = {}  # (l1, l2, dtype) -> [raveled tensors]
    reg = 0.0
    for layer, sub in layer_param_pairs():
        if not sub:
            continue
        meth = getattr(type(layer), "regularization", None)
        if meth is None or meth is Layer.regularization:
            continue  # no penalty (base Layer / bare vertex returns 0)
        if meth is not BaseLayer.regularization:
            # custom form (MoE load-balance, BN's explicit 0) — keep exact
            reg = reg + layer.regularization(sub)
            continue
        l1 = layer.l1 or 0.0
        l2 = layer.l2 or 0.0
        l1b = layer.l1_bias or 0.0
        l2b = layer.l2_bias or 0.0
        biases = layer.bias_param_names()
        for k, v in sub.items():
            # same ``> 0`` gating as BaseLayer.regularization
            c2, c1 = (l2b, l1b) if k in biases else (l2, l1)
            c1 = c1 if c1 > 0 else 0.0
            c2 = c2 if c2 > 0 else 0.0
            if c1 == 0.0 and c2 == 0.0:
                continue
            groups.setdefault((c1, c2, v.dtype), []).append(jnp.ravel(v))
    for (c1, c2, _), vs in groups.items():
        flat = vs[0] if len(vs) == 1 else jnp.concatenate(vs)
        if c2 > 0:
            reg = reg + 0.5 * c2 * jnp.sum(flat * flat)
        if c1 > 0:
            reg = reg + c1 * jnp.sum(jnp.abs(flat))
    return reg


def add_regularization_grads(net, params, grads):
    """Return ``grads`` with each layer's analytic penalty gradient added.

    Works for MultiLayerNetwork (int-keyed layers) and ComputationGraph
    (vertex-name keys); mutates the (freshly autodiff-produced) ``grads``
    dict trees in place and returns them.
    """
    layers = getattr(net, "layers", None)
    if isinstance(layers, list):
        for i, layer in enumerate(layers):
            sub = params.get(str(i), {})
            for k, g in layer.regularization_grad(sub).items():
                grads[str(i)][k] = grads[str(i)][k] + g
        return grads
    vertices = getattr(getattr(net, "conf", None), "vertices", None)
    if isinstance(vertices, dict):
        for name, v in vertices.items():
            sub = params.get(name, {})
            for k, g in v.regularization_grad(sub).items():
                grads[name][k] = grads[name][k] + g
    return grads
