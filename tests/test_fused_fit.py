"""Fused multi-step fit driver tests (optimize/fused_fit.py).

Covers the ISSUE-1 acceptance surface: fused-vs-unfused loss-trajectory and
parameter equivalence (same seeds, K in {1, 4}), trailing-partial-batch
correctness under shape bucketing, the one-program-per-ragged-epoch
guarantee, the score_value contract, and the block-level listener semantics.
"""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam
from deeplearning4j_tpu.metrics.registry import global_registry
from deeplearning4j_tpu.optimize import fused_fit
from deeplearning4j_tpu.optimize.fused_fit import (
    DEFAULT_FUSED_STEPS_CPU,
    FusedFitDriver,
    device_put_ahead,
    resolve_fused_steps,
)
from deeplearning4j_tpu.optimize.health import DivergenceError, HealthPolicy
from deeplearning4j_tpu.optimize.listeners import (
    CollectScoresIterationListener,
    TrainingListener,
)

TOL = 1e-5


def _mln(seed=12345):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.02))
            .weight_init("xavier").activation("relu")
            .list(DenseLayer(n_out=16), DenseLayer(n_out=16),
                  OutputLayer(n_out=3, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _graph(seed=12345):
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Adam(0.02))
            .weight_init("xavier").activation("relu")
            .graph_builder().add_inputs("in")
            .add_layer("d1", DenseLayer(n_out=16), "in")
            .add_layer("out",
                       OutputLayer(n_out=3, loss="mcxent", activation="softmax"),
                       "d1")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(4)).build())
    return ComputationGraph(conf).init()


def _iris_like(n, seed=0):
    rs = np.random.RandomState(seed)
    x = rs.randn(n, 4).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, n)]
    return DataSet(x, y)


def _max_param_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y))) for x, y in
               zip(jax.tree_util.tree_leaves(a.params),
                   jax.tree_util.tree_leaves(b.params)))


# ------------------------------------------------------------- equivalence
class TestFusedEquivalence:
    @pytest.mark.parametrize("k", [1, 4])
    def test_fused_matches_unfused_mln(self, k):
        """Same seeds: K-fused training equals the per-minibatch path, both
        in the per-iteration score trajectory and the final parameters."""
        it = ListDataSetIterator(_iris_like(128), batch_size=32)
        ref, fus = _mln(), _mln()
        ref_scores = CollectScoresIterationListener()
        fus_scores = CollectScoresIterationListener()
        ref.set_listeners(ref_scores)
        fus.set_listeners(fus_scores)
        ref.fit(it, epochs=2, fused_steps=1)
        fus.fit(it, epochs=2, fused_steps=k)
        assert fus.iteration == ref.iteration == 8
        assert _max_param_diff(ref, fus) <= TOL
        ref_traj = [float(s) for _, s in ref_scores.scores]
        fus_traj = [float(s) for _, s in fus_scores.scores]
        assert [i for i, _ in ref_scores.scores] == [i for i, _ in fus_scores.scores]
        np.testing.assert_allclose(fus_traj, ref_traj, atol=TOL)

    def test_fused_matches_unfused_graph(self):
        it = ListDataSetIterator(_iris_like(128), batch_size=32)
        ref, fus = _graph(), _graph()
        ref.fit(it, epochs=2, fused_steps=1)
        fus.fit(it, epochs=2, fused_steps=4)
        assert fus.iteration == ref.iteration == 8
        assert _max_param_diff(ref, fus) <= TOL
        assert abs(ref.score() - fus.score()) <= TOL

    def test_tail_group_runs_unfused(self):
        """A stream whose length is not a multiple of K: the trailing group
        of fewer than K microbatches takes the per-minibatch path, and the
        result still matches the unfused reference exactly."""
        it = ListDataSetIterator(_iris_like(192), batch_size=32)  # 6 batches
        ref, fus = _mln(), _mln()
        ref.fit(it, epochs=1, fused_steps=1)
        fus.fit(it, epochs=1, fused_steps=4)  # 1 block + 2-batch tail
        assert fus.iteration == ref.iteration == 6
        assert _max_param_diff(ref, fus) <= TOL
        fused_keys = [kk for kk in fus._step_cache if kk[0] == "fused"]
        unfused_keys = [kk for kk in fus._step_cache if kk[0] != "fused"]
        assert len(fused_keys) == 1 and len(unfused_keys) == 1


# --------------------------------------------------- bucketing / recompiles
class TestShapeBucketing:
    def test_trailing_partial_batch_correctness(self):
        """118 examples at batch 32 -> 32,32,32,22: the undersized batch is
        padded to the bucket with zeroed label-mask rows, and training
        matches the unfused path (which sees the raw 22-row batch)."""
        it = ListDataSetIterator(_iris_like(118), batch_size=32)
        ref, fus = _mln(), _mln()
        ref.fit(it, epochs=3, fused_steps=1)
        fus.fit(it, epochs=3, fused_steps=4)
        assert fus.iteration == ref.iteration == 12
        assert _max_param_diff(ref, fus) <= TOL

    def test_ragged_epoch_single_program(self):
        """The recompile-count guarantee: a ragged-batch epoch compiles ONE
        fused program — the padded tail batch reuses the full-block key."""
        it = ListDataSetIterator(_iris_like(118), batch_size=32)
        net = _mln()
        net.fit(it, epochs=3, fused_steps=4)
        assert len(net._step_cache) == 1
        (key,) = net._step_cache
        assert key[0] == "fused" and key[1] == 4

    def test_masked_stream_buckets(self):
        """Streams that already carry a labels_mask bucket too (the pad rows
        extend the existing mask with zeros)."""
        rs = np.random.RandomState(3)
        n = 80  # batch 32 -> 32,32,16
        x = rs.randn(n, 4).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, n)]
        lm = np.ones(n, np.float32)
        lm[::7] = 0.0
        ds = DataSet(x, y, None, lm)
        ref, fus = _mln(), _mln()
        ref.fit(ListDataSetIterator(ds, batch_size=32), epochs=3, fused_steps=1)
        fus.fit(ListDataSetIterator(ds, batch_size=32), epochs=3, fused_steps=3)
        assert fus.iteration == ref.iteration == 9
        assert _max_param_diff(ref, fus) <= TOL
        assert len([k for k in fus._step_cache if k[0] == "fused"]) == 1


# ------------------------------------------------------- score_value contract
class TestScoreValueContract:
    def test_score_value_stays_device_side(self):
        """score_value holds the device scalar after training (no per-step
        host sync); score() with no arguments coerces it to a float."""
        net = _mln()
        assert isinstance(net.score(), float) and np.isnan(net.score())
        net.fit(ListDataSetIterator(_iris_like(64), batch_size=32),
                epochs=1, fused_steps=2)
        assert isinstance(net.score_value, jax.Array)
        s = net.score()
        assert isinstance(s, float) and np.isfinite(s)

    def test_score_no_arg_graph(self):
        net = _graph()
        net.fit(ListDataSetIterator(_iris_like(64), batch_size=32),
                epochs=1, fused_steps=2)
        s = net.score()
        assert isinstance(s, float) and np.isfinite(s)

    def test_listener_path_scores_are_host_values(self):
        """With listeners attached the block's stacked losses come back in
        ONE device fetch; iteration_done then observes host-side scores."""
        net = _mln()
        seen = []

        class Probe(TrainingListener):
            def iteration_done(self, model, iteration):
                seen.append((iteration, model.score_value))

        net.set_listeners(Probe())
        net.fit(ListDataSetIterator(_iris_like(128), batch_size=32),
                epochs=1, fused_steps=4)
        assert [i for i, _ in seen] == [1, 2, 3, 4]
        assert all(isinstance(s, np.floating) for _, s in seen)


# ----------------------------------------------------------- block listeners
class TestBlockListeners:
    def test_on_block_done_fires_once_per_block(self):
        net = _mln()
        blocks = []
        iters = []

        class Probe(TrainingListener):
            def on_block_done(self, model, iterations, scores):
                blocks.append((list(iterations), np.asarray(scores)))

            def iteration_done(self, model, iteration):
                iters.append(iteration)

        net.set_listeners(Probe())
        net.fit(ListDataSetIterator(_iris_like(256), batch_size=32),
                epochs=1, fused_steps=4)  # 8 batches -> 2 full blocks
        assert len(blocks) == 2
        assert blocks[0][0] == [1, 2, 3, 4] and blocks[1][0] == [5, 6, 7, 8]
        assert all(s.shape == (4,) for _, s in blocks)
        # per-iteration hooks still fire once per iteration, after the block
        assert iters == list(range(1, 9))


# ------------------------------------------------------------- driver bits
class TestDriverPlumbing:
    def test_fused_steps_validation(self):
        net = _mln()
        with pytest.raises(ValueError):
            net.fit(_iris_like(32), fused_steps=0)
        with pytest.raises(ValueError):
            FusedFitDriver(net, 0)

    def test_cpu_default_fused_steps(self):
        assert jax.default_backend() == "cpu"
        assert resolve_fused_steps(_mln(), None) == DEFAULT_FUSED_STEPS_CPU

    def test_device_put_ahead_order_and_depth(self):
        placed = []
        out = list(device_put_ahead(range(7), 3, lambda v: placed.append(v) or v))
        assert out == list(range(7)) and placed == out
        with pytest.raises(ValueError):
            list(device_put_ahead(range(3), 0, lambda v: v))


# -------------------------------------------------------------- device feed
def _feed_counters():
    reg = global_registry()
    placed = reg.counter("fit_microbatches_placed_total", labels=("how",))
    return {"worker": placed.labels(how="worker").value,
            "block": placed.labels(how="block").value,
            "passthrough": placed.labels(how="passthrough").value,
            "blocks": reg.counter("fit_blocks_dispatched_total").value,
            "feed_wait": reg.counter("fit_feed_wait_seconds_total").value,
            "fetch_wait": reg.counter("fit_fetch_wait_seconds_total").value}


def _since(before):
    return {k: v - before[k] for k, v in _feed_counters().items()}


def _live_workers():
    return [t for t in threading.enumerate()
            if t.name.startswith("fit-place-")]


def _batches(sizes, seed=0):
    return [_iris_like(n, seed + i) for i, n in enumerate(sizes)]


class TestDeviceFeed:
    """The per-microbatch feed: placement on worker threads, the bounded
    look-ahead, shutdown, pass-through of device arrays, the counters."""

    @pytest.fixture(autouse=True)
    def _every_microbatch_by_worker(self, monkeypatch):
        # these tests' batches are a few hundred bytes, which fit() would
        # place itself (``WORKER_MIN_BYTES``): send them to the workers
        monkeypatch.setattr(fused_fit, "WORKER_MIN_BYTES", 0)

    #: at K=3: full blocks only; a padded ragged batch in the second block
    #: and a tail group of two; a batch larger than the bucket ("raw")
    #: after two pending microbatches, which are flushed as a tail before
    #: it; many small batches, under a short switch interval
    STREAMS = {"full_blocks": [32] * 9,
               "ragged_tail": [32] * 4 + [32, 22] + [32, 32],
               "raw_midstream": [32] * 5 + [48] + [32] * 5,
               "many_small": [8] * 60}

    @pytest.mark.parametrize("stream", sorted(STREAMS))
    def test_bit_identical_when_placements_finish_out_of_order(
            self, stream, monkeypatch):
        """Workers that finish in any order change nothing: blocks take
        their microbatches in the order pulled, so N fused steps through
        the feed leave the parameters of the per-minibatch path, bit for
        bit."""
        real = FusedFitDriver._place_micro
        calls = []

        def late_first(arrays):
            calls.append(threading.current_thread().name)
            # every other placement is held back past its successor's
            time.sleep(0.03 if len(calls) % 2 else 0.0)
            return real(arrays)

        monkeypatch.setattr(FusedFitDriver, "_place_micro",
                            staticmethod(late_first))
        monkeypatch.setattr(fused_fit, "PLACE_WORKERS", 4)
        data = _batches(self.STREAMS[stream])
        ref, fus = _mln(), _mln()
        ref.fit(data, epochs=1, fused_steps=1)
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            fus.fit(data, epochs=1, fused_steps=3)
        finally:
            sys.setswitchinterval(interval)
        assert fus.iteration == ref.iteration == len(data)
        assert len(set(calls)) > 1  # more than one worker did place
        for a, b in zip(jax.tree_util.tree_leaves(ref.params),
                        jax.tree_util.tree_leaves(fus.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not _live_workers()

    @pytest.mark.parametrize("depth", [1, 2, 3])
    def test_look_ahead_is_bounded(self, depth, monkeypatch):
        """Never more than ``prefetch_depth x K`` microbatches placed or
        being placed beyond the block that is next to run: counted where
        they are handed over, and again inside the placement stub."""
        K = 2
        lock = threading.Lock()
        count = {"handed": 0, "placing": 0, "run": 0}
        ahead = {"handed": [], "placing": []}
        real_place = FusedFitDriver._place_micro
        real_hand, real_run = FusedFitDriver._hand_over, FusedFitDriver._run_block

        def note(what):
            with lock:
                count[what] += 1
                # the block next to run is the (run + 1)-th
                ahead[what].append(count[what] - K * (count["run"] + 1))

        def place(arrays):
            note("placing")
            return real_place(arrays)

        def hand_over(self, feed, f, l):
            assert feed is not None
            note("handed")
            return real_hand(self, feed, f, l)

        def run_block(self, *block):
            real_run(self, *block)
            with lock:
                count["run"] += 1

        monkeypatch.setattr(FusedFitDriver, "_place_micro",
                            staticmethod(place))
        monkeypatch.setattr(FusedFitDriver, "_hand_over", hand_over)
        monkeypatch.setattr(FusedFitDriver, "_run_block", run_block)
        _mln().fit(_batches([16] * 24), epochs=1, fused_steps=K,
                   prefetch_depth=depth)
        assert count == {"handed": 24, "placing": 24, "run": 12}
        assert max(ahead["handed"]) == depth * K
        assert max(ahead["placing"]) <= depth * K

    @pytest.mark.parametrize("fault", ["iterable", "placement", "divergence"])
    def test_no_worker_outlives_a_failed_fit(self, fault, monkeypatch):
        """However fit() is left, its placement workers are gone."""
        data = _batches([16] * 12)
        net = _mln()
        policy = True

        def stream():
            for i, ds in enumerate(data):
                if fault == "iterable" and i == 7:
                    raise OSError("the disk went away")
                yield ds

        if fault == "placement":
            real = FusedFitDriver._place_micro
            seen = []

            def place(arrays):
                seen.append(1)
                if len(seen) == 5:
                    raise MemoryError("no room on the device")
                return real(arrays)

            monkeypatch.setattr(FusedFitDriver, "_place_micro",
                                staticmethod(place))
        if fault == "divergence":
            for ds in data:
                ds.features[0, 0] = np.nan
            policy = HealthPolicy(skip_threshold=2, lr_backoff=None)
        raised = {"iterable": OSError, "placement": MemoryError,
                  "divergence": DivergenceError}[fault]
        with pytest.raises(raised):
            net.fit(stream(), epochs=1, fused_steps=2, health_guard=policy)
        assert not _live_workers()
        # and the next fit() on the same net starts a feed of its own
        clean = _batches([16] * 4, seed=50)
        net.fit(clean, epochs=1, fused_steps=2)
        assert not _live_workers()

    def test_device_batches_pass_through(self, monkeypatch):
        """A ``DataSet.on_device`` batch is not fetched back: its arrays
        reach the fused program as they are, no worker is started, and
        the microbatch counts as ``how="passthrough"``."""
        host = _batches([32] * 4)
        placed = [DataSet.on_device(jnp.asarray(d.features),
                                    jnp.asarray(d.labels)) for d in host]
        monkeypatch.setattr(
            FusedFitDriver, "_place_micro",
            staticmethod(lambda arrays: pytest.fail("a worker placed")))
        seen = []
        real = FusedFitDriver._stack

        def stack(self, items):
            seen.extend(items)
            return real(self, items)

        monkeypatch.setattr(FusedFitDriver, "_stack", stack)
        before = _feed_counters()
        ref, fus = _mln(), _mln()
        fus.fit(placed, epochs=1, fused_steps=2)
        got = _since(before)
        assert (got["passthrough"], got["worker"], got["blocks"]) == (4, 0, 2)
        assert all(item[0] is d.features and item[1] is d.labels
                   for item, d in zip(seen, placed))
        ref.fit(host, epochs=1, fused_steps=1)
        assert _max_param_diff(ref, fus) == 0.0

    def test_small_microbatches_are_placed_without_a_worker(
            self, monkeypatch):
        """Under ``WORKER_MIN_BYTES`` a ``device_put`` costs by the array,
        not by the byte: the block is one host array placed by the calling
        thread, no worker starts, and the result is the per-minibatch
        path's."""
        monkeypatch.setattr(fused_fit, "WORKER_MIN_BYTES", 1 << 20)
        monkeypatch.setattr(
            FusedFitDriver, "_place_micro",
            staticmethod(lambda arrays: pytest.fail("a worker placed")))
        blocks = []
        real = FusedFitDriver._stack

        def stack(self, items):
            blocks.append(real(self, items))
            return blocks[-1]

        monkeypatch.setattr(FusedFitDriver, "_stack", stack)
        data = _batches([32] * 4 + [22] + [32] * 2)
        before = _feed_counters()
        ref, fus = _mln(), _mln()
        fus.fit(data, epochs=1, fused_steps=3)
        got = _since(before)
        assert (got["block"], got["worker"], got["blocks"]) == (7, 0, 2)
        assert [type(b[0]) for b in blocks] == [np.ndarray] * 2
        assert blocks[1][0].shape == (3, 32, 4)
        ref.fit(data, epochs=1, fused_steps=1)
        assert _max_param_diff(ref, fus) == 0.0

    def test_counters_add_up_over_a_stream(self):
        """Every microbatch handed over is counted once, every fused block
        once, and the two waits are seconds of this fit()."""
        # K=3: 3 + 3 fused, [32, 22] flushed as a tail by the 48 (raw), 3
        # fused, 1 left as a tail: 12 handed over, 9 of them in 3 blocks
        data = _batches([32] * 6 + [32, 22] + [48] + [32] * 4)
        host, dev = data[:9], [DataSet.on_device(jnp.asarray(d.features),
                                                 jnp.asarray(d.labels))
                               for d in data[9:]]
        net = _mln()
        net.set_listeners(CollectScoresIterationListener())
        before = _feed_counters()
        t0 = time.perf_counter()
        net.fit(host + dev, epochs=1, fused_steps=3)
        wall = time.perf_counter() - t0
        got = _since(before)
        assert net.iteration == 13
        assert (got["worker"], got["block"], got["passthrough"]) == (8, 0, 4)
        assert got["blocks"] == 3
        assert got["worker"] + got["passthrough"] == 3 * got["blocks"] + 2 + 1
        assert 0.0 <= got["feed_wait"] <= wall
        assert 0.0 < got["fetch_wait"] <= wall


# ------------------------------------------------------------------ e2e perf
@pytest.mark.slow
def test_fit_e2e_fused_not_slower():
    """End-to-end fit() wall clock (dispatch + transfer + listener round-trip
    included): the fused path must not regress the per-minibatch path. No
    cell measures fused against unfused on the chip; this guard uses a
    loose floor because single-core CI boxes time with +/-15% noise."""
    data = _iris_like(512)

    def run(k):
        it = ListDataSetIterator(data, batch_size=8)
        net = _mln()
        net.fit(it, epochs=1, fused_steps=k)  # warm both programs
        t0 = time.perf_counter()
        net.fit(it, epochs=4, fused_steps=k)
        float(net.score())
        return time.perf_counter() - t0

    unfused, fused = run(1), run(2)
    assert fused <= unfused * 1.25, (
        f"fused e2e {fused:.3f}s vs unfused {unfused:.3f}s")
