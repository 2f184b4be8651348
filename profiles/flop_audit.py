"""Reproducible FLOP audit for the bench models (round-5 correction).

Round 4 recorded ResNet50 train = 12.8 GFLOP/img by assuming XLA's
``cost_analysis()['flops']`` counts 1 per MAC and doubling it. That
assumption was WRONG, and it hid a real architecture fact:

1. XLA (CPU backend) counts **2 flops per MAC** for spatial convolutions —
   verified here by a single-conv microcheck whose analytic MAC count is
   known exactly (ratio measured 1.99).
2. The reference's zoo ResNet50 is ~2x LIGHTER than canonical
   torchvision ResNet50: ``ResNet50.java`` applies stride 2 in the
   stage-2a convBlock (after the stem maxpool already reached 56x56), so
   every residual stage runs at half the canonical spatial size
   (28->14->7->4 instead of 56->28->14->7). The repo matches it
   (models/zoo.py stage-2a stride (2,2)) — parity, not a bug. Canonical
   "4.1 GFLOP forward" therefore does NOT apply to this model.

This script computes, per bench model:
- exact conv+dot MACs/img of the forward pass, walked from the jaxpr
  (shape-exact, counting-convention-free);
- XLA cost_analysis flops/img for forward and full train step;
- the train-step GFLOP/img figure the MFU numbers should use
  (XLA count at 2/MAC == multiply+add, the same convention as the
  v5e 197 TFLOP/s bf16 peak).

Usage: python profiles/flop_audit.py   (CPU backend; writes the summary
to stdout; numbers are recorded in bench.py)
"""

from __future__ import annotations

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _conv_dot_macs(jaxpr):
    """Exact MACs of every conv_general_dilated / dot_general in a jaxpr."""
    macs = 0

    def walk(jx):
        nonlocal macs
        for eq in jx.eqns:
            if eq.primitive.name == "conv_general_dilated":
                kh, kw, cin, cout = eq.invars[1].aval.shape  # HWIO
                n, h, w, c = eq.outvars[0].aval.shape        # NHWC
                macs += n * h * w * c * kh * kw * cin
            elif eq.primitive.name == "dot_general":
                a = eq.invars[0].aval.shape
                b = eq.invars[1].aval.shape
                (lc, rc), _ = eq.params["dimension_numbers"]
                keep_b = [b[i] for i in range(len(b)) if i not in rc]
                macs += int(np.prod(a)) * int(np.prod(keep_b, dtype=np.int64))
            for sub in eq.params.values():
                if hasattr(sub, "jaxpr"):
                    walk(sub.jaxpr)

    walk(jaxpr.jaxpr)
    return macs


def single_conv_check():
    """XLA flop-counting convention vs an analytically known conv."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers.convolution import ConvolutionLayer
    from deeplearning4j_tpu.nn.conf.layers.core import OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Sgd

    conf = (NeuralNetConfiguration.builder().seed(0)
            .updater(Sgd(learning_rate=0.1)).activation("relu")
            .list(ConvolutionLayer(n_out=64, kernel_size=(3, 3),
                                   stride=(1, 1), padding=(1, 1)),
                  OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional(56, 56, 64)).build())
    net = MultiLayerNetwork(conf).init()
    B = 4
    x = jnp.zeros((B, 56, 56, 64), jnp.float32)

    def fwd(params, state):
        out, _, _, _ = net._forward(params, state, x, None, train=True,
                                    rng=jax.random.PRNGKey(0))
        return jnp.mean(out)

    ca = jax.jit(fwd).lower(net.params, net.state).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    theory_macs = 56 * 56 * 64 * 64 * 9 + 56 * 56 * 64 * 10  # conv + dense
    ratio = ca["flops"] / B / theory_macs
    print(f"single-conv check: XLA flops/analytic MACs = {ratio:.2f} "
          "(2.0 => XLA counts multiply+add separately)")
    return ratio


def audit(name, net, graph: bool):
    import jax
    import jax.numpy as jnp

    B = 2
    x = jnp.zeros((B, 224, 224, 3), jnp.float32)
    y = jnp.zeros((B, 1000), jnp.float32)

    if graph:
        def fwd(params, state):
            outs, _, _, _, _ = net._forward(params, state, [x], None,
                                            train=True,
                                            rng=jax.random.PRNGKey(0))
            return jnp.mean(outs[0])
    else:
        def fwd(params, state):
            out, _, _, _ = net._forward(params, state, x, None, train=True,
                                        rng=jax.random.PRNGKey(0))
            return jnp.mean(out)

    macs = _conv_dot_macs(jax.make_jaxpr(fwd)(net.params, net.state)) / B

    ca = jax.jit(fwd).lower(net.params, net.state).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    fwd_flops = ca["flops"] / B

    step = net._get_step((x.shape, y.shape, False, False, False))
    args = (net.params, net.updater_state, net.state, jax.random.PRNGKey(0),
            jnp.float32(1), x, y, None, None, {})
    ca2 = jax.jit(lambda *a: step(*a)).lower(*args).compile().cost_analysis()
    ca2 = ca2[0] if isinstance(ca2, list) else ca2
    step_flops = ca2["flops"] / B

    print(f"{name}: fwd {macs / 1e9:.2f} GMACs/img (jaxpr-exact), "
          f"XLA fwd {fwd_flops / 1e9:.2f} G, "
          f"XLA train step {step_flops / 1e9:.2f} GFLOP/img "
          f"(multiply+add; use THIS for MFU)")
    return step_flops


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")
    single_conv_check()
    from deeplearning4j_tpu.models import VGG16, ResNet50

    audit("resnet50 (zoo/DL4J variant, stride-2 stage2a)",
          ResNet50(num_labels=1000, dtype="float32").init(), graph=True)
    audit("vgg16 (conv-only head)",
          VGG16(num_labels=1000, dtype="float32").init(), graph=False)


if __name__ == "__main__":
    main()
