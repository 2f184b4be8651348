"""Data-parallel LeNet training over the device mesh — the dl4j-examples
ParallelWrapper MultiGpuLenetMnistExample analog (one mesh instead of
replica threads).

Run: python examples/lenet_mesh_dataparallel.py
Env: EXAMPLES_SMOKE=1 shrinks sizes and forces a 4-device CPU mesh.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SMOKE = bool(os.environ.get("EXAMPLES_SMOKE"))
if SMOKE:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 4)

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.mnist import MnistDataSetIterator
from deeplearning4j_tpu.models import LeNet
from deeplearning4j_tpu.parallel import ParallelWrapper, data_mesh


def main():
    net = LeNet(num_labels=10).init()
    mesh = data_mesh()  # every visible device
    n_dev = mesh.devices.size
    # stream minibatches are PER-WORKER: each averaging round consumes
    # n_dev * averaging_frequency batches, so size the corpus to whole
    # rounds or the trailing partial round is dropped (with a warning)
    pw = ParallelWrapper(net, mesh=mesh, averaging_frequency=1)
    batch = 64
    n = 512 if SMOKE else (60000 // (batch * n_dev)) * batch * n_dev

    def image_batches(**kw):
        # MNIST iterator yields flat [B, 784] (the reference's contract);
        # the zoo LeNet takes NHWC images
        return [DataSet(ds.features.reshape(-1, 28, 28, 1), ds.labels)
                for ds in MnistDataSetIterator(**kw)]

    pw.fit(image_batches(batch_size=batch, num_examples=n),
           epochs=1 if SMOKE else 3)
    ev = net.evaluate(image_batches(batch_size=512, train=False,
                                    num_examples=min(n, 10000)))
    print(f"devices: {n_dev}")
    print(ev.stats())
    print("TRAINED iterations:", net.iteration)
    return ev.accuracy()


if __name__ == "__main__":
    main()
