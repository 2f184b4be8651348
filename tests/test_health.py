"""Self-healing training tests (optimize/health.py + the guarded step paths).

The ISSUE-3 acceptance surface: a NaN minibatch mid-stream is skipped on
device with the surviving updates identical between the fused and unfused
paths; a skipped step preserves params/updater-state EXACTLY; the recovery
ladder walks LR backoff -> checkpoint rollback -> DivergenceError; periodic
checkpoints are healthy-gated; the guard composes with ParallelWrapper and
leaves early stopping's invalid-score telemetry untouched.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.earlystopping import (
    DataSetLossCalculator,
    EarlyStoppingConfiguration,
    EarlyStoppingTrainer,
    InMemoryModelSaver,
    InvalidScoreIterationTerminationCondition,
    MaxEpochsTerminationCondition,
)
from deeplearning4j_tpu.earlystopping.config import TerminationReason
from deeplearning4j_tpu.optimize.health import (
    DivergenceError,
    HealthPolicy,
    all_finite,
    resolve_health_policy,
    tree_select,
)
from deeplearning4j_tpu.optimize.listeners import HealthListener
from deeplearning4j_tpu.parallel.elastic import (CheckpointListener,
                                                 CheckpointStore)
from deeplearning4j_tpu.parallel.trainer import (AVERAGING, SHARED_GRADIENTS,
                                                 ParallelWrapper)

from tests.test_fused_fit import TOL, _graph, _max_param_diff, _mln

pytestmark = pytest.mark.health


def _batches(n, batch=16, nan_at=None, seed=0, scale=1.0):
    rs = np.random.RandomState(seed)
    out = []
    for i in range(n):
        x = (rs.randn(batch, 4) * scale).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, batch)]
        if i == nan_at:
            x[0, 0] = np.nan
        out.append(DataSet(x, y))
    return out


def _sgd_mln(seed=12345):
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Sgd
    conf = (NeuralNetConfiguration.builder().seed(seed).updater(Sgd(0.05))
            .weight_init("xavier").activation("relu")
            .list(DenseLayer(n_out=16),
                  OutputLayer(n_out=3, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    return MultiLayerNetwork(conf).init()


def _params_flat(net):
    return np.concatenate([np.asarray(p).ravel()
                           for p in jax.tree_util.tree_leaves(net.params)])


# -------------------------------------------------------- device primitives
class TestDevicePrimitives:
    def test_all_finite(self):
        good = {"w": jnp.ones((3,)), "b": jnp.zeros(())}
        assert bool(all_finite(jnp.float32(1.0), good))
        assert not bool(all_finite(jnp.float32(np.nan), good))
        bad = {"w": jnp.array([1.0, np.inf, 0.0]), "b": jnp.zeros(())}
        assert not bool(all_finite(jnp.float32(1.0), bad))

    def test_tree_select(self):
        new = {"a": jnp.ones((2,))}
        old = {"a": jnp.zeros((2,))}
        np.testing.assert_array_equal(
            np.asarray(tree_select(jnp.bool_(True), new, old)["a"]), 1.0)
        np.testing.assert_array_equal(
            np.asarray(tree_select(jnp.bool_(False), new, old)["a"]), 0.0)

    def test_tree_select_structure_mismatch_passes_new(self):
        # the TBPTT first-segment carry: old is the {} seed
        new = {"h": jnp.ones((2,))}
        assert tree_select(jnp.bool_(False), new, {}) is new

    def test_resolve_health_policy(self):
        assert resolve_health_policy(None) is None
        assert resolve_health_policy(False) is None
        assert isinstance(resolve_health_policy(True), HealthPolicy)
        p = HealthPolicy()
        assert resolve_health_policy(p) is p
        with pytest.raises(TypeError):
            resolve_health_policy("on")


# ------------------------------------------------------------ guarded steps
class TestGuardedStep:
    def test_skipped_step_preserves_params_exactly(self):
        """The acceptance bit-identity: a skipped step is the identity
        update — params, updater state, and iteration RNG alignment all
        pass through unchanged (diff == 0, not just small)."""
        net = _mln()
        before = _params_flat(net)
        # materialize host-side: the jitted step donates the device buffers
        opt_before = [np.asarray(x)
                      for x in jax.tree_util.tree_leaves(net.updater_state)]
        net.fit(_batches(1, nan_at=0)[0],
                health_guard=HealthPolicy(skip_threshold=100))
        assert np.array_equal(before, _params_flat(net))
        for a, b in zip(opt_before,
                        jax.tree_util.tree_leaves(net.updater_state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert net.iteration == 1  # the slot is consumed, only the update isn't

    def test_guard_off_poisons_params(self):
        """The failure mode the guard exists for: without it one NaN batch
        destroys the weights."""
        net = _mln()
        net.fit(_batches(1, nan_at=0)[0], health_guard=None)
        assert not np.isfinite(_params_flat(net)).all()

    def test_guard_on_equals_guard_off_on_clean_data(self):
        """On all-finite data the guarded program selects every real
        update. Guarded and unguarded are DIFFERENT compiled programs, so
        agreement is to compile-level rounding (~1e-8 observed), not bitwise
        — bit-exactness of the select itself is pinned by
        test_skipped_step_preserves_params_exactly."""
        it = ListDataSetIterator(_batches(8), batch_size=16)
        on, off = _mln(), _mln()
        on.fit(it, epochs=1, health_guard=HealthPolicy(skip_threshold=100))
        off.fit(it, epochs=1, health_guard=None)
        assert _max_param_diff(on, off) <= TOL

    @pytest.mark.parametrize("k", [1, 4])
    def test_nan_midstream_fused_matches_unfused(self, k):
        """A NaN batch mid-stream: the fused (K>1) and unfused (K=1) guarded
        paths skip the SAME step and agree on every surviving update."""
        batches = _batches(8, nan_at=2, seed=5)
        ref, fus = _mln(), _mln()
        pol_ref = HealthPolicy(skip_threshold=100)
        pol_fus = HealthPolicy(skip_threshold=100)
        ref.fit(ListDataSetIterator(batches, batch_size=16), epochs=1,
                fused_steps=1, health_guard=pol_ref)
        fus.fit(ListDataSetIterator(batches, batch_size=16), epochs=1,
                fused_steps=k, health_guard=pol_fus)
        assert pol_ref.total_skips == pol_fus.total_skips == 1
        assert ref.iteration == fus.iteration == 8
        assert np.isfinite(_params_flat(fus)).all()
        assert _max_param_diff(ref, fus) <= TOL

    def test_skipped_batch_equals_batch_never_seen(self):
        """Under an iteration-clock-free updater (plain SGD; Adam's bias
        correction rides the iteration counter, which a skipped slot still
        advances) the skipped step is a true no-op: training [b0, b1, NaN,
        b3..] under the guard ends bit-identical to training the same
        stream with the NaN batch removed."""
        batches = _batches(6, nan_at=2, seed=9)
        clean = [b for i, b in enumerate(batches) if i != 2]
        guarded, never = _sgd_mln(), _sgd_mln()
        for b in batches:
            guarded.fit(b, health_guard=HealthPolicy(skip_threshold=100))
        for b in clean:  # same guarded program: same shapes, guard on
            never.fit(b, health_guard=HealthPolicy(skip_threshold=100))
        assert guarded.iteration == 6 and never.iteration == 5
        assert _max_param_diff(guarded, never) == 0.0

    def test_graph_guarded_skip(self):
        """ComputationGraph shares the guarded step core."""
        net = _graph()
        before = _params_flat(net)
        pol = HealthPolicy(skip_threshold=100)
        net.fit(ListDataSetIterator(_batches(4, nan_at=1), batch_size=16),
                epochs=1, health_guard=pol)
        assert pol.total_skips == 1
        assert np.isfinite(_params_flat(net)).all()
        assert not np.array_equal(before, _params_flat(net))  # clean steps ran

    def test_raw_nan_score_still_reported(self):
        """The guard protects the weights, not the telemetry: the skipped
        step's raw non-finite loss stays visible to score consumers."""
        net = _mln()
        net.fit(_batches(1, nan_at=0)[0],
                health_guard=HealthPolicy(skip_threshold=100))
        assert not np.isfinite(net.score())


# ----------------------------------------------------------- recovery ladder
class TestRecoveryLadder:
    def test_lr_backoff_first_rung(self):
        """Rung 1: consecutive skips past the threshold halve the LR and
        drop the compiled step programs (the base LR is baked in)."""
        net = _mln()
        lr0 = net.conf.updater.learning_rate
        pol = HealthPolicy(skip_threshold=2, lr_backoff=0.5,
                           max_recoveries=5)
        events = []
        for b in _batches(3, nan_at=None, seed=1):
            b.features[0, 0] = np.nan  # every batch skips
            net.fit(b, health_guard=pol)
            events = [e["action"] for e in pol.events]
            if "lr_backoff" in events:
                break
        assert "lr_backoff" in events
        assert net.conf.updater.learning_rate == pytest.approx(lr0 * 0.5)
        assert len(net._step_cache) == 0  # invalidated for re-trace
        # training continues (recompiles) after the backoff
        net.fit(_batches(1, seed=2)[0], health_guard=pol)
        assert np.isfinite(_params_flat(net)).all()

    def test_spike_triggers_rollback(self, tmp_path):
        """Rung 2: with LR backoff disabled a loss spike rolls the live net
        back to the newest healthy checkpoint in-place."""
        store = CheckpointStore(str(tmp_path), keep=3)
        pol = HealthPolicy(store=store, save_frequency=4, warmup_steps=3,
                           spike_factor=5.0, skip_threshold=100,
                           lr_backoff=None)
        net = _mln()
        for b in _batches(8, seed=3):
            net.fit(b, health_guard=pol)
        assert store.latest() is not None  # healthy-gated periodic saves ran
        # finite but enormous loss -> EMA spike detector fires
        spike = _batches(1, seed=4, scale=400.0)[0]
        net.fit(spike, health_guard=pol)
        actions = [e["action"] for e in pol.events]
        assert actions == ["rollback"]
        rolled = [e for e in pol.events if e["action"] == "rollback"][0]
        assert net.iteration == rolled["restored_iteration"] < 9
        assert rolled["checkpoint_meta"]["healthy"] is True
        assert np.isfinite(_params_flat(net)).all()

    def test_ladder_exhaustion_raises_divergence_error(self):
        """Bounded retries: once max_recoveries is spent the next trigger
        raises instead of thrashing forever."""
        net = _mln()
        pol = HealthPolicy(skip_threshold=2, lr_backoff=0.5,
                           max_recoveries=2)
        with pytest.raises(DivergenceError, match="exhausted"):
            for b in _batches(12, seed=6):
                b.features[0, 0] = np.nan
                net.fit(b, health_guard=pol)
        assert pol.events[-1]["action"] == "raise"
        assert pol.recoveries == 3

    def test_no_rung_available_raises(self):
        """lr_backoff=None and no checkpoint store: the first trigger has
        nowhere to go and must say so rather than loop."""
        net = _mln()
        pol = HealthPolicy(skip_threshold=2, lr_backoff=None)
        with pytest.raises(DivergenceError, match="no recovery rung"):
            for b in _batches(6, seed=7):
                b.features[0, 0] = np.nan
                net.fit(b, health_guard=pol)

    def test_lr_backoff_validation(self):
        with pytest.raises(ValueError):
            HealthPolicy(lr_backoff=1.5)
        with pytest.raises(ValueError):
            HealthPolicy(skip_threshold=0)


# ------------------------------------------------- healthy-gated checkpoints
class TestHealthyGatedCheckpoints:
    def test_unhealthy_window_not_saved(self, tmp_path):
        """A save window containing a skipped step is dropped: the store
        never holds a checkpoint whose window saw non-finite steps."""
        store = CheckpointStore(str(tmp_path), keep=10)
        pol = HealthPolicy(store=store, save_frequency=4, skip_threshold=100)
        net = _mln()
        for b in _batches(4, seed=8):          # clean window -> saved
            net.fit(b, health_guard=pol)
        n_clean = len(store.checkpoints())
        assert n_clean == 1
        for b in _batches(4, nan_at=1, seed=9):  # dirty window -> dropped
            net.fit(b, health_guard=pol)
        assert len(store.checkpoints()) == n_clean
        for b in _batches(4, seed=10):         # clean again -> saved
            net.fit(b, health_guard=pol)
        assert len(store.checkpoints()) == n_clean + 1

    def test_checkpoint_listener_health_gated(self, tmp_path):
        """elastic.CheckpointListener consults the active policy: save
        opportunities inside an unhealthy window are passed over."""
        store = CheckpointStore(str(tmp_path), keep=10)
        listener = CheckpointListener(store, frequency=1)
        net = _mln()
        net.set_listeners(listener)
        net.fit(_batches(1, nan_at=0)[0],
                health_guard=HealthPolicy(skip_threshold=100))
        assert listener.skipped_unhealthy == 1 and listener.saved == 0
        net.set_listeners()
        net.fit(_batches(1)[0], health_guard=None)  # no guard: no gating
        net.set_listeners(listener)
        net.fit(_batches(1, seed=2)[0], health_guard=None)
        assert listener.saved == 1


# ------------------------------------------------------------- observability
class TestHealthListener:
    def test_on_health_reports(self):
        net = _mln()
        hl = HealthListener(log_events=False)
        net.set_listeners(hl)
        pol = HealthPolicy(skip_threshold=100)
        net.fit(ListDataSetIterator(_batches(4, nan_at=1), batch_size=16),
                epochs=1, health_guard=pol)
        skips = [r for r in hl.reports if r["action"] == "skip"]
        assert len(skips) == 1
        assert skips[0]["total_skips"] == 1
        # the policy's own event log matches what listeners saw
        assert [e["action"] for e in pol.events] == \
            [r["action"] for r in hl.reports]


# ------------------------------------------------------------ ParallelWrapper
class TestParallelWrapperGuard:
    @pytest.mark.parametrize("mode", [AVERAGING, SHARED_GRADIENTS])
    def test_guarded_round_skips_nan(self, mode):
        net = _mln()
        pol = HealthPolicy(skip_threshold=100)
        pw = ParallelWrapper(net, workers=4, mode=mode, health_guard=pol)
        pw.fit(_batches(8, nan_at=2), epochs=1)
        assert pol.total_skips >= 1
        assert np.isfinite(_params_flat(net)).all()
        assert np.isfinite(net.score_value)

    def test_guard_on_equals_guard_off_clean(self):
        batches = _batches(8, seed=11)
        on, off = _mln(), _mln()
        ParallelWrapper(on, workers=4, health_guard=True).fit(
            list(batches), epochs=1)
        ParallelWrapper(off, workers=4, health_guard=None).fit(
            list(batches), epochs=1)
        # The guarded and the unguarded step are two XLA programs, so the
        # guard being a no-op on clean data shows as agreement to a few
        # float32 ulps (measured gap: at most 2, 1.49e-8), not as bit equality.
        for a, b in zip(jax.tree_util.tree_leaves(on.params),
                        jax.tree_util.tree_leaves(off.params)):
            np.testing.assert_array_max_ulp(np.asarray(a), np.asarray(b),
                                            maxulp=4)
        assert on.score_value == pytest.approx(off.score_value, abs=1e-12)


# -------------------------------------------------------------- early stopping
class TestEarlyStoppingInteraction:
    def _es_config(self):
        return EarlyStoppingConfiguration(
            epoch_termination_conditions=[MaxEpochsTerminationCondition(3)],
            iteration_termination_conditions=[
                InvalidScoreIterationTerminationCondition()],
            score_calculator=DataSetLossCalculator(
                ListDataSetIterator(_batches(2, seed=12), batch_size=16)),
            model_saver=InMemoryModelSaver())

    def test_invalid_score_termination_with_guard_disabled(self):
        """ES defaults to guard OFF; a NaN batch terminates the run through
        InvalidScoreIterationTerminationCondition exactly as before."""
        trainer = EarlyStoppingTrainer(
            self._es_config(), _mln(),
            ListDataSetIterator(_batches(4, nan_at=1, seed=13),
                                batch_size=16))
        assert trainer.health_guard is None  # the documented default
        result = trainer.fit()
        assert result.termination_reason == \
            TerminationReason.ITERATION_TERMINATION_CONDITION
        assert "InvalidScore" in result.termination_details

    def test_guard_protects_weights_but_not_telemetry(self):
        """With a policy passed through, the run STILL terminates on the
        honest NaN score — but the weights survive finite."""
        net = _mln()
        trainer = EarlyStoppingTrainer(
            self._es_config(), net,
            ListDataSetIterator(_batches(4, nan_at=1, seed=13),
                                batch_size=16),
            health_guard=HealthPolicy(skip_threshold=100))
        result = trainer.fit()
        assert result.termination_reason == \
            TerminationReason.ITERATION_TERMINATION_CONDITION
        assert np.isfinite(_params_flat(net)).all()
