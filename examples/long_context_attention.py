"""Long-context attention: flash-kernel training + sequence parallelism.

Two capabilities in one runnable demo:
1. Train a causal self-attention network with ``helper="auto"`` — on TPU
   the Pallas flash kernel serves the layer (O(T) training memory);
   elsewhere the stock XLA path runs.
2. Shard the SEQUENCE axis of attention across a device mesh with ring
   attention (lax.ppermute K/V rotation) and with Ulysses all-to-all, and
   check both match single-device attention.

Run: python examples/long_context_attention.py
Env: EXAMPLES_SMOKE=1 -> CPU, T=64, 4 virtual devices for the SP part.
"""

import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = bool(os.environ.get("EXAMPLES_SMOKE"))
import jax

if SMOKE:  # hermetic: CPU with a virtual 4-device mesh for the SP demo
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)

import numpy as np
import jax.numpy as jnp

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import (
    SelfAttentionLayer,
    scaled_dot_attention,
)
from deeplearning4j_tpu.nn.conf.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam

T = 64 if SMOKE else 2048
F = 32 if SMOKE else 128


def train_with_auto_helper():
    conf = (NeuralNetConfiguration.builder().seed(1)
            .updater(Adam(learning_rate=1e-3))
            .list(SelfAttentionLayer(n_out=F, n_heads=4, causal=True,
                                     helper="auto", activation="identity"),
                  RnnOutputLayer(n_out=8, activation="softmax",
                                 loss="mcxent"))
            .set_input_type(InputType.recurrent(F, T)).build())
    net = MultiLayerNetwork(conf).init()
    rs = np.random.RandomState(0)
    x = rs.randn(2, T, F).astype(np.float32)
    y = np.eye(8, dtype=np.float32)[rs.randint(0, 8, (2, T))]
    ds = DataSet(x, y)
    s0 = net.score(ds)
    epochs = 2 if SMOKE else 10
    net.fit(ds, epochs=epochs)
    s1 = net.score(ds)
    print(f"causal attention T={T} (helper=auto, "
          f"{jax.default_backend()}): score {s0:.4f} -> {s1:.4f}")
    assert s1 < s0
    return net.iteration


def sequence_parallel_demo():
    n = min(8, len(jax.devices()))
    if n < 2:
        print(f"sequence-parallel demo skipped: {n} device(s)")
        return
    from jax.sharding import Mesh

    from deeplearning4j_tpu.parallel.sequence import (
        ring_attention,
        ulysses_attention,
    )

    mesh = Mesh(np.asarray(jax.devices()[:n]), ("seq",))
    rs = np.random.RandomState(1)
    B, H, d = 2, n, 32
    Tsp = 16 * n
    q = jnp.asarray(rs.randn(B, H, Tsp, d), jnp.float32)
    k = jnp.asarray(rs.randn(B, H, Tsp, d), jnp.float32)
    v = jnp.asarray(rs.randn(B, H, Tsp, d), jnp.float32)
    dense = scaled_dot_attention(q, k, v, causal=True)
    for name, fn in (("ring", ring_attention), ("ulysses",
                                                ulysses_attention)):
        # jitted, as a train step would run it: one program per scheme
        out = jax.jit(partial(fn, mesh=mesh, axis="seq", causal=True))(
            q, k, v)
        err = float(jnp.max(jnp.abs(out - dense)))
        print(f"{name} attention over {n} devices: max |diff| vs dense "
              f"= {err:.2e}")
        assert err < 1e-4


def main():
    iters = train_with_auto_helper()
    sequence_parallel_demo()
    print("TRAINED iterations:", iters)


if __name__ == "__main__":
    main()
