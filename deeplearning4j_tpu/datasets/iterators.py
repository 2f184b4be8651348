"""DataSet iterators.

Reference: DataSetIterator contract + AsyncDataSetIterator (background prefetch
thread with a blocking queue, datasets/iterator/AsyncDataSetIterator.java:30,40 —
auto-wrapped inside fit at MultiLayerNetwork.java:1051-1053). The async variant here
does the same host-side prefetch so input pipeline time overlaps device compute; on
TPU the jitted step's async dispatch already overlaps one step, so the queue mainly
hides slow ETL (e.g. record readers / augmentation).
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Optional

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet


class DataSetIterator:
    """Iterable over DataSet minibatches; subclasses implement _generate()."""

    def __iter__(self):
        self.reset()
        return self._iterate()

    def _iterate(self):
        raise NotImplementedError

    def reset(self):
        pass

    def total_examples(self) -> Optional[int]:
        return None


class ListDataSetIterator(DataSetIterator):
    """Batches over an in-memory DataSet or list of DataSets."""

    def __init__(self, data, batch_size: int = 32, shuffle: bool = False, seed: int = 0):
        if isinstance(data, (list, tuple)):
            data = DataSet.merge(list(data))
        self.data = data
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.seed = seed
        self._epoch = 0

    def _iterate(self):
        data = self.data
        if self.shuffle:
            order = np.random.default_rng(self.seed + self._epoch).permutation(
                data.num_examples())
            self._epoch += 1
        else:
            order = np.arange(data.num_examples())
        for s in range(0, len(order), self.batch_size):
            idx = order[s:s + self.batch_size]
            yield DataSet(
                data.features[idx], data.labels[idx],
                None if data.features_mask is None else data.features_mask[idx],
                None if data.labels_mask is None else data.labels_mask[idx])

    def total_examples(self):
        return self.data.num_examples()


class AsyncDataSetIterator(DataSetIterator):
    """Wraps another iterator with a background prefetch thread + bounded queue."""

    def __init__(self, base: Iterable, queue_size: int = 4):
        self.base = base
        self.queue_size = queue_size

    def reset(self):
        if hasattr(self.base, "reset"):
            self.base.reset()

    def _iterate(self):
        q: queue.Queue = queue.Queue(maxsize=self.queue_size)
        DONE = object()
        err: list = []

        def worker():
            try:
                it = (self.base._iterate() if isinstance(self.base, DataSetIterator)
                      else iter(self.base))
                for ds in it:
                    q.put(ds)
            except BaseException as e:  # surface on the consumer side
                err.append(e)
            finally:
                q.put(DONE)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is DONE:
                break
            yield item
        t.join()
        if err:
            raise err[0]

    def total_examples(self):
        return self.base.total_examples() if hasattr(self.base, "total_examples") else None


class DevicePrefetchIterator(DataSetIterator):
    """Keeps the next ``depth`` minibatches already ON DEVICE while the
    current one trains — the TPU-native second half of async prefetch.

    ``AsyncDataSetIterator`` overlaps host-side batch PRODUCTION with
    compute; this overlaps the host->device TRANSFER too, by issuing
    ``jax.device_put`` ``depth`` batches ahead on the consuming thread (the
    flax ``prefetch_to_device`` pattern, expressed over the DataSetIterator
    contract; reference analog: AsyncDataSetIterator,
    datasets/iterator/AsyncDataSetIterator.java:30). Each batch goes up as
    one ``device_put`` over its pytree.

    Measured on a TPU v5e host (chip run, PR 27; 308 MB float32 batches):
    the ``device_put`` call returns in 0.5 ms and the copy proceeds behind
    it, so issuing ahead does overlap; one copy in flight moves 5.2 GB/s,
    four issued together 9.1, but every copy in flight beyond one delays
    the small transfers beside it (``PLACE_WORKERS`` in
    ``optimize/fused_fit.py``). ``fit()`` over host batches does not need
    this iterator: its own feed places every microbatch from a worker
    thread. What it is for is a consumer that places nothing itself
    (``fused_steps=1``, ``ParallelWrapper`` with ``sharding=``); batches
    it hands to ``fit()`` pass through the feed untouched.

    ``sharding`` (optional ``jax.sharding.Sharding``) places each batch for
    mesh training — compose with ``ParallelWrapper``/``ShardedTrainer``
    data shardings.
    """

    def __init__(self, base: Iterable, depth: int = 2, sharding=None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.base = base
        self.depth = depth
        self.sharding = sharding

    def reset(self):
        if hasattr(self.base, "reset"):
            self.base.reset()

    def _put(self, ds):
        import jax

        from deeplearning4j_tpu.datasets.dataset import DataSet

        arrs = tuple(None if a is None else np.asarray(a)
                     for a in (ds.features, ds.labels, ds.features_mask,
                               ds.labels_mask))
        if self.sharding is not None:
            # fail with a clear message on a trailing partial batch the
            # mesh cannot split — the raw jax error would surface `depth`
            # batches away from the offending data
            try:
                self.sharding.shard_shape(np.shape(arrs[0]))
            except ValueError as e:
                raise ValueError(
                    f"batch shape {np.shape(arrs[0])} is not divisible "
                    f"onto sharding {self.sharding} (trailing partial "
                    "batch? drop it or pad before prefetching)") from e
        placed = (jax.device_put(arrs, self.sharding)
                  if self.sharding is not None else jax.device_put(arrs))
        return DataSet.on_device(*placed)

    def _iterate(self):
        from deeplearning4j_tpu.optimize.fused_fit import device_put_ahead

        it = (self.base._iterate() if isinstance(self.base, DataSetIterator)
              else iter(self.base))
        return device_put_ahead(it, self.depth, self._put)

    def total_examples(self):
        return self.base.total_examples() \
            if hasattr(self.base, "total_examples") else None


class MultipleEpochsIterator(DataSetIterator):
    """Replays a base iterator N times as one pass (reference:
    datasets/iterator/MultipleEpochsIterator.java)."""

    def __init__(self, base: DataSetIterator, num_epochs: int):
        self.base = base
        self.num_epochs = num_epochs

    def reset(self):
        self.base.reset()

    def _iterate(self):
        for _ in range(self.num_epochs):
            self.base.reset()
            yield from self.base._iterate()


class SamplingDataSetIterator(DataSetIterator):
    """Random-with-replacement sampling batches from a DataSet."""

    def __init__(self, data: DataSet, batch_size: int, total_batches: int, seed: int = 0):
        self.data = data
        self.batch_size = batch_size
        self.total_batches = total_batches
        self.seed = seed
        self._calls = 0

    def _iterate(self):
        rng = np.random.default_rng(self.seed + self._calls)
        self._calls += 1
        n = self.data.num_examples()
        for _ in range(self.total_batches):
            idx = rng.integers(0, n, self.batch_size)
            yield DataSet(self.data.features[idx], self.data.labels[idx])
