"""Self-healing training: numerical-health guard + host-side recovery.

The reference framework has essentially no numerical failure handling — a
NaN minibatch silently poisons the weights and every iteration after it,
and the fused multi-step driver (optimize/fused_fit.py) amplifies the blast
radius: K minibatches run as ONE donated XLA program, so the host cannot
even observe the corruption until the whole block is done. Production-scale
trainers treat divergence as an expected event with an automated recovery
path (the PaLM training report's loss-spike rewind practice; the
skip-nonfinite update in Optax/T5X-style stacks). This module is that path:

- **Device side** (``all_finite`` / ``tree_select``, fused into the step
  core by ``optimize.fused_fit.build_step_core(guarded=True)``): one
  all-finite reduction over the loss and the gradients per microbatch;
  when non-finite, the identity update is selected for that microbatch —
  params/opt-state/layer-state pass through unchanged inside the scan, so
  the other K-1 steps of a fused block stay good. The per-slot skip flags
  ride back with the block's stacked losses, so a guarded block still
  costs ONE small host fetch.
- **Host side** (``HealthPolicy``): consumes per-block (scores, skips) and
  runs an escalating recovery ladder — an EMA loss-spike detector and a
  consecutive-skip threshold trigger (1) learning-rate backoff via the
  updater's ``scale_lr`` hook, then (2) rollback to the last
  *healthy-gated* checkpoint in an ``elastic.CheckpointStore`` (the
  policy's periodic saves are gated on "no skips since the last save", so
  the newest checkpoint is a true last-known-good), then (3)
  ``DivergenceError`` after ``max_recoveries`` bounded retries.

Wired default-on through ``MultiLayerNetwork.fit`` / ``ComputationGraph
.fit`` (opt-out ``health_guard=None``) and available to ``ParallelWrapper``
mesh training through the same shared step core. Every observation and
recovery action is surfaced through the standard listener interface as
``on_health(model, report)`` (optimize/listeners.py).

Reported scores stay HONEST: a skipped step reports its raw (non-finite)
loss, so score listeners and ``InvalidScoreIterationTerminationCondition``
(earlystopping/termination.py) observe exactly what they always did — the
guard protects the weights, not the telemetry.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.metrics.registry import global_registry


class DivergenceError(RuntimeError):
    """Training diverged and the recovery ladder is exhausted."""


# ---------------------------------------------------------- device helpers
def all_finite(loss, grads):
    """Scalar bool: the loss and every gradient leaf are all-finite.

    One ``isfinite``+``all`` reduction per leaf, combined with logical-and —
    O(num_params) reads against a step that already does O(num_params *
    batch) compute. ``resnet50_fit_b256`` runs with the guard on (its
    ``is-finite_reduce_fusion`` line, PERF.md §5); guard on against
    guard off is measured by no cell."""
    ok = jnp.all(jnp.isfinite(loss))
    for leaf in jax.tree_util.tree_leaves(grads):
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(leaf)))
    return ok


def tree_select(ok, new, old):
    """``new`` where ``ok`` else ``old``, leafwise over matching pytrees.

    When the structures differ (a TBPTT carry being seeded from ``{}`` on
    the first segment) there is nothing to pass through — return ``new``;
    a poisoned carry only NaNs the remaining segments of that one
    sequence, each of which is then itself skipped, while the parameters
    stay protected."""
    tu = jax.tree_util
    if tu.tree_structure(new) != tu.tree_structure(old):
        return new
    return tu.tree_map(lambda n, o: jnp.where(ok, n, o), new, old)


# ------------------------------------------------------------- host policy
class HealthPolicy:
    """Host-side recovery policy over per-block (score, skipped) streams.

    Recovery ladder, walked once per trigger (consecutive-skip threshold or
    EMA loss spike), bounded by ``max_recoveries``:

    1. LR backoff — ``net.conf.updater.scale_lr(lr_backoff)`` + invalidate
       the compiled step programs (the base lr is baked in at trace time).
    2. Rollback — restore params/updater-state/layer-state/iteration from
       the newest checkpoint in ``store`` (healthy-gated by this policy's
       own saves). The backed-off LR is kept: rewinding to the same
       weights with the same LR would replay the same divergence.
    3. ``DivergenceError`` once ``max_recoveries`` is exhausted (or when
       no rung is available: ``lr_backoff=None`` and no checkpoint).

    Periodic saves: every ``save_frequency`` iterations, IF the window
    since the previous save opportunity saw zero skipped steps — a save
    window containing a skip is dropped (window resets, no checkpoint), so
    ``store.latest()`` is always a last-known-good.
    """

    def __init__(self, *, store=None, save_frequency: int = 100,
                 skip_threshold: int = 8, spike_factor: float = 10.0,
                 ema_alpha: float = 0.1, warmup_steps: int = 20,
                 lr_backoff: Optional[float] = 0.5,
                 max_recoveries: int = 3, registry=None):
        if lr_backoff is not None and not 0.0 < lr_backoff < 1.0:
            raise ValueError(
                f"lr_backoff must be in (0, 1) or None, got {lr_backoff}")
        if skip_threshold < 1:
            raise ValueError("skip_threshold must be >= 1")
        self.store = store
        self.save_frequency = int(save_frequency)
        self.skip_threshold = int(skip_threshold)
        self.spike_factor = float(spike_factor)
        self.ema_alpha = float(ema_alpha)
        self.warmup_steps = int(warmup_steps)
        self.lr_backoff = lr_backoff
        self.max_recoveries = int(max_recoveries)
        # health state — persists across blocks and epochs within one
        # policy instance
        self.ema: Optional[float] = None
        self.warmup_seen = 0
        self.consecutive_skips = 0
        self.total_skips = 0
        self.recoveries = 0
        self.skips_in_window = 0
        self.events: list = []  # every emitted report, for observability
        self._window_start: Optional[int] = None
        self._invalidate = None
        # publish into the shared registry (default: the process-global
        # one, so a serving process scrapes its training health too)
        self.metrics = registry if registry is not None \
            else global_registry()
        self._m_events = self.metrics.counter(
            "health_events_total", "health-guard reports by action",
            labels=("action",))
        self._m_ema = self.metrics.gauge(
            "health_loss_ema", "EMA loss baseline of the spike detector")
        self._m_consecutive = self.metrics.gauge(
            "health_consecutive_skips", "current consecutive skipped steps")
        self._m_total_skips = self.metrics.gauge(
            "health_total_skips", "total device-skipped steps")
        self._m_recoveries = self.metrics.gauge(
            "health_recoveries", "recovery-ladder rungs walked")

    # ------------------------------------------------------------- binding
    def bind(self, net, invalidate=None) -> "HealthPolicy":
        """Attach to a fit loop. ``invalidate`` is an extra program-cache
        invalidation hook for drivers that compile outside the net's
        ``_step_cache`` (ParallelWrapper's round cache)."""
        self._invalidate = invalidate
        return self

    def healthy_to_save(self) -> bool:
        """Gate for external checkpointers (elastic.CheckpointListener):
        True iff no step has been skipped in the current save window."""
        return self.skips_in_window == 0 and self.consecutive_skips == 0

    # --------------------------------------------------------- observation
    def observe(self, net, scores, skips, it0: Optional[int] = None):
        """Consume one block of per-iteration (score, skipped) pairs.

        ``scores``/``skips`` are host arrays (one element per iteration of
        the block — length K fused, 1 unfused, F per ParallelWrapper
        round; ``skips`` entries > 0 mean the device selected the identity
        update). May mutate ``net`` (LR backoff, rollback) and raises
        ``DivergenceError`` when the ladder is exhausted."""
        scores = np.atleast_1d(np.asarray(scores, np.float64))
        skips = np.atleast_1d(np.asarray(skips, np.float64))
        if self._window_start is None:
            self._window_start = (it0 if it0 is not None
                                  else net.iteration - len(scores))
        block_skips = 0
        spike_score = None
        for s, sk in zip(scores, skips):
            if sk > 0:
                block_skips += 1
                self.total_skips += 1
                self.consecutive_skips += 1
                continue
            self.consecutive_skips = 0
            if not np.isfinite(s):
                # cannot happen through the device guard (the loss is part
                # of the all-finite check); defensive for direct callers
                continue
            if (spike_score is None and self.ema is not None
                    and self.warmup_seen >= self.warmup_steps
                    and self.ema > 0
                    and s > self.spike_factor * self.ema):
                # a spike triggers recovery and must NOT drag the EMA
                # baseline up toward itself
                spike_score = float(s)
                continue
            a = self.ema_alpha
            self.ema = (float(s) if self.ema is None
                        else (1.0 - a) * self.ema + a * float(s))
            self.warmup_seen += 1
        self.skips_in_window += block_skips
        if block_skips:
            self._emit(net, {
                "action": "skip", "reason": "nonfinite",
                "iteration": net.iteration,
                "skipped_in_block": block_skips,
                "consecutive_skips": self.consecutive_skips,
                "total_skips": self.total_skips,
            })
        recovered = False
        if self.consecutive_skips >= self.skip_threshold:
            self.recover(net, "skip_threshold",
                         {"consecutive_skips": self.consecutive_skips})
            recovered = True
        elif spike_score is not None:
            self.recover(net, "loss_spike",
                         {"score": spike_score, "ema": self.ema})
            recovered = True
        # healthy-gated periodic checkpoint: an unhealthy window is
        # dropped (no save) and the window restarts, so the newest
        # checkpoint in the store is always a last-known-good
        if (not recovered and self.store is not None
                and net.iteration - self._window_start
                >= self.save_frequency):
            if self.skips_in_window == 0:
                self.store.save(net, {"healthy": True,
                                      "total_skips": self.total_skips})
            self._window_start = net.iteration
            self.skips_in_window = 0

    # ------------------------------------------------------------ recovery
    def recover(self, net, reason: str, detail: dict):
        """Walk one rung of the recovery ladder. Raises DivergenceError
        when retries are exhausted or no rung is available."""
        self.recoveries += 1
        self.consecutive_skips = 0
        report = {"reason": reason, "iteration": net.iteration,
                  "recoveries": self.recoveries,
                  "total_skips": self.total_skips, **detail}
        if self.recoveries > self.max_recoveries:
            self._emit(net, {**report, "action": "raise"})
            raise DivergenceError(
                f"training diverged ({reason} at iteration "
                f"{net.iteration}) and the recovery ladder is exhausted "
                f"after {self.max_recoveries} recoveries "
                f"({self.total_skips} steps skipped in total)")
        if self.recoveries == 1 and self.lr_backoff is not None:
            done = self._do_backoff(net, report)
        else:
            done = (self._do_rollback(net, report)
                    or (self.lr_backoff is not None
                        and self._do_backoff(net, report)))
        if not done:
            self._emit(net, {**report, "action": "raise"})
            raise DivergenceError(
                f"training diverged ({reason} at iteration "
                f"{net.iteration}) and no recovery rung is available "
                "(lr_backoff disabled and no checkpoint to roll back to)")
        # fresh spike baseline after any recovery — the post-recovery loss
        # scale is a new regime
        self.ema = None
        self.warmup_seen = 0

    def _do_backoff(self, net, report: dict) -> bool:
        updater = getattr(net.conf, "updater", None)
        if updater is None or not getattr(updater, "learning_rate", None):
            return False
        lr_before = updater.learning_rate
        lr_after = updater.scale_lr(self.lr_backoff)
        self._invalidate_programs(net)
        self._emit(net, {**report, "action": "lr_backoff",
                         "lr_before": lr_before, "lr_after": lr_after})
        return True

    def _do_rollback(self, net, report: dict) -> bool:
        if self.store is None:
            return False
        restored = self.store.restore()
        if restored is None:
            return False
        ckpt, meta = restored
        # in-place rewind: the live net keeps its conf (and thus the
        # backed-off LR), listeners, and compiled programs — only the
        # trajectory state rewinds
        net.params = ckpt.params
        net.updater_state = ckpt.updater_state
        net.state = ckpt.state
        net.iteration = ckpt.iteration
        self._window_start = net.iteration
        self.skips_in_window = 0
        self._emit(net, {**report, "action": "rollback",
                         "restored_iteration": net.iteration,
                         "checkpoint_meta": meta})
        return True

    def _invalidate_programs(self, net):
        # the base lr is a trace-time constant of every compiled step
        cache = getattr(net, "_step_cache", None)
        if cache is not None:
            cache.clear()
        if self._invalidate is not None:
            self._invalidate()

    # -------------------------------------------------------------- events
    def _emit(self, net, report: dict):
        self.events.append(report)
        self._m_events.labels(action=report.get("action", "unknown")).inc()
        self._m_ema.set(self.ema if self.ema is not None else 0.0)
        self._m_consecutive.set(self.consecutive_skips)
        self._m_total_skips.set(self.total_skips)
        self._m_recoveries.set(self.recoveries)
        for listener in getattr(net, "listeners", []) or []:
            hook = getattr(listener, "on_health", None)
            if hook is not None:
                hook(net, dict(report))


def resolve_health_policy(health_guard) -> Optional[HealthPolicy]:
    """``fit(health_guard=...)`` coercion: True -> a default policy,
    None/False -> guard off, a HealthPolicy -> itself."""
    if health_guard is None or health_guard is False:
        return None
    if health_guard is True:
        return HealthPolicy()
    if isinstance(health_guard, HealthPolicy):
        return health_guard
    raise TypeError(
        "health_guard must be True (default policy), None/False (guard "
        f"off), or a HealthPolicy instance; got {health_guard!r}")
