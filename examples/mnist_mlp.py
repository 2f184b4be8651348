"""Train an MLP on MNIST and evaluate — the dl4j-examples
MLPMnistSingleLayerExample analog.

Run: python examples/mnist_mlp.py  (on jax's default backend, which it prints
first: the TPU on a machine that has one, the CPU where JAX_PLATFORMS=cpu)
Env: EXAMPLES_SMOKE=1 shrinks sizes for the test-suite smoke run.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = bool(os.environ.get("EXAMPLES_SMOKE"))
if SMOKE:  # the smoke run must be hermetic: never touch a real device
    import jax
    jax.config.update("jax_platforms", "cpu")

from deeplearning4j_tpu.datasets.mnist import MnistDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.optimize.listeners import ScoreIterationListener



def main():
    import jax

    dev = jax.devices()[0]
    print(f"backend: {dev.platform} ({dev.device_kind})")
    n = 2048 if SMOKE else 60000
    epochs = 1 if SMOKE else 5
    conf = (NeuralNetConfiguration.builder()
            .seed(123)
            .updater(Adam(learning_rate=1e-3))
            .list(DenseLayer(n_out=256, activation="relu"),
                  DenseLayer(n_out=128, activation="relu"),
                  OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    net.set_listeners(ScoreIterationListener(20))
    train = MnistDataSetIterator(batch_size=128, num_examples=n)
    net.fit(train, epochs=epochs)
    test = MnistDataSetIterator(batch_size=512, train=False,
                                num_examples=min(n, 10000))
    ev = net.evaluate(test)
    print(ev.stats())
    print("TRAINED iterations:", net.iteration)
    return ev.accuracy()


if __name__ == "__main__":
    main()
