"""ParallelWrapper: data-parallel training as one sharded XLA program.

Reference semantics reproduced (parallelism/ParallelWrapper.java:53):

- ``AVERAGING`` mode (:148-305): each worker takes ``averaging_frequency`` local
  SGD steps on its own replica, then parameters — and optionally updater state
  (:273-305 averageUpdatersState) — are averaged across workers
  (Nd4j.averageAndPropagate :261). Here: `lax.scan` of local steps inside
  `shard_map`, then `lax.pmean` on params/updater-state over the ``data`` axis.
- ``SHARED_GRADIENTS`` mode (:54-69, SymmetricTrainer.java:23-88 +
  EncodingHandler threshold broadcast): gradients are shared every step. Here:
  `lax.pmean` on gradients inside the step — the idiomatic TPU path (replicas
  never diverge, no separate broadcast needed; ICI carries the reduction).

Unlike the reference there are no worker threads, no replica re-sync, and no
blocking queues: the whole averaging round (W workers x F local steps) is ONE
jitted program; XLA overlaps the per-device compute and the ICI collectives.

Equivalence contract (ported from
TestCompareParameterAveragingSparkVsSingleMachine.java): with
averaging_frequency=1 and SGD, training on N devices with per-device batch B
equals single-device training on the concatenated N*B batch, to float tolerance.
"""

from __future__ import annotations

import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.optimize.fused_fit import (build_step_core,
                                                   make_scan_body)
from deeplearning4j_tpu.optimize.listeners import TrainingListener

from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, data_mesh

AVERAGING = "averaging"
SHARED_GRADIENTS = "shared_gradients"


class ParallelWrapper:
    """Data-parallel trainer wrapping any net exposing the functional contract
    ``_loss(params, state, x, y, input_mask, label_mask, *, train, rng)`` plus
    ``params / state / updater_state / conf.updater`` (MultiLayerNetwork and
    ComputationGraph both qualify).
    """

    def __init__(self, net, workers: Optional[int] = None,
                 averaging_frequency: int = 1, mode: str = AVERAGING,
                 average_updaters: bool = True, mesh: Optional[Mesh] = None,
                 report_score: bool = True, health_guard=True):
        if mode not in (AVERAGING, SHARED_GRADIENTS):
            raise ValueError(f"Unknown mode '{mode}'")
        if averaging_frequency < 1:
            raise ValueError("averaging_frequency must be >= 1")
        self.net = net
        self.mesh = mesh if mesh is not None else data_mesh(workers)
        self.workers = self.mesh.devices.size
        self.averaging_frequency = averaging_frequency
        self.mode = mode
        self.average_updaters = average_updaters
        self.report_score = report_score
        # numerical-health guard (optimize/health.py): the guarded step core
        # skips non-finite worker steps on device and the policy handles
        # divergence host-side. True -> default policy per fit() call,
        # None/False -> off, or pass a configured HealthPolicy.
        self.health_guard = health_guard
        self._policy = None  # active policy, set for the duration of fit()
        # mid-stream batches whose size didn't match the stream's (dropped
        # with a warning — see fit); genuine trailing partials not counted
        self.dropped_batches = 0
        # last round's phase wall times (SparkTrainingStats analog)
        self.last_phase_timings: dict = {}
        self._round_cache: dict = {}

    # ------------------------------------------------------------------ build
    def _build_round(self, has_im: bool, has_lm: bool, guarded: bool):
        net = self.net
        pmean_grads = self.mode == SHARED_GRADIENTS
        avg_params = self.mode == AVERAGING
        average_updaters = self.average_updaters
        # the shared step core (forward, reg grads, normalization, updater,
        # center-loss update) — identical to the single-device fit paths; the
        # pmean hook runs between regularization and normalization, so
        # SHARED_GRADIENTS normalizes the GLOBAL gradient exactly as a single
        # device would on the concatenated batch (the module's parity
        # contract) while AVERAGING normalizes each worker's local step.
        # Under the guard the same ordering means a SHARED_GRADIENTS pmean
        # poisons every replica identically, so all replicas skip the same
        # step and stay in lockstep.
        core = build_step_core(
            net,
            grad_transform=((lambda g: lax.pmean(g, DATA_AXIS))
                            if pmean_grads else None),
            guarded=guarded)

        def device_round(params, opt, state, rng, it0, xs, ys, ims, lms):
            """Runs on ONE device's shard: F local steps, then averaging.

            xs/ys/ims/lms: [F, B_local, ...] stacks of this device's minibatches.
            """
            didx = lax.axis_index(DATA_AXIS)

            def sharded_core(params, opt_state, st, step_rng, it, x, y, im,
                             lm, carry):
                # the host stacks zero-filled placeholder masks for unmasked
                # streams (one scan signature); drop them before the loss
                return core(params, opt_state, st, step_rng, it, x, y,
                            im if has_im else None,
                            lm if has_lm else None, carry)

            body = make_scan_body(
                sharded_core,
                rng_fn=lambda it: jax.random.fold_in(
                    jax.random.fold_in(rng, it.astype(jnp.int32)), didx),
                guarded=guarded)
            (params, opt, state, _), scanned = lax.scan(
                body, (params, opt, state, it0), (xs, ys, ims, lms))
            if guarded:
                losses, skip_flags = scanned
            else:
                losses = scanned
            if avg_params:
                params = lax.pmean(params, DATA_AXIS)
                if average_updaters:
                    opt = lax.pmean(opt, DATA_AXIS)
            # persistent layer state (e.g. BN running stats) is averaged like the
            # reference's full-model averaging
            state = lax.pmean(state, DATA_AXIS)
            if guarded:
                # per-step stats kept: [F] mean losses + [F] skip fractions
                # (fraction of workers that skipped that local step) — one
                # pair of small fetches per round for the health policy
                losses = lax.pmean(losses, DATA_AXIS)
                skips = lax.pmean(skip_flags, DATA_AXIS)
                return params, opt, state, losses, skips
            loss = lax.pmean(jnp.mean(losses), DATA_AXIS)
            return params, opt, state, loss

        batch_spec = P(None, DATA_AXIS)
        n_out = 5 if guarded else 4
        fn = jax.shard_map(
            device_round, mesh=self.mesh,
            in_specs=(P(), P(), P(), P(), P(),
                      batch_spec, batch_spec, batch_spec, batch_spec),
            out_specs=(P(),) * n_out, check_vma=False)
        # params/opt/state are rebound from the round's outputs
        return jax.jit(fn, donate_argnums=(0, 1, 2))

    def _get_round(self, key):
        if key not in self._round_cache:
            self._round_cache[key] = self._build_round(key[-3], key[-2],
                                                       key[-1])
        return self._round_cache[key]

    def _invalidate_programs(self):
        """Health-policy hook: the base LR is baked into the compiled round
        (and step) programs, so an LR backoff must drop them."""
        self._round_cache.clear()

    # -------------------------------------------------------------------- fit
    def fit(self, iterator, epochs: int = 1):
        """Feed W*F minibatches per averaging round (reference: ParallelWrapper
        .fit :409-487 — each worker consumes its own minibatches; incomplete
        final rounds are dropped, matching the reference's skip of trailing
        partial worker groups)."""
        from deeplearning4j_tpu.optimize.health import resolve_health_policy

        net = self.net
        W, F = self.workers, self.averaging_frequency
        need = W * F
        expected_batch = None
        policy = resolve_health_policy(self.health_guard)
        prev_health = getattr(net, "_health", None)
        self._policy = policy
        if policy is not None:
            policy.bind(net, invalidate=self._invalidate_programs)
            # expose on the net too, so health-gated checkpoint listeners
            # (elastic.CheckpointListener) see the active policy
            net._health = policy
        try:
            for _ in range(epochs):
                for listener in getattr(net, "listeners", []):
                    listener.on_epoch_start(net)
                if hasattr(iterator, "reset"):
                    iterator.reset()
                buf = []
                stream = iter(iterator)
                ds = next(stream, None)
                while ds is not None:
                    nxt = next(stream, None)
                    b = np.asarray(ds.features).shape[0]
                    if expected_batch is None:
                        expected_batch = b
                    if b != expected_batch:
                        # a genuinely-final undersized minibatch is a trailing
                        # partial: skipped silently like trailing partial
                        # worker groups (static shapes keep one XLA program).
                        # Any OTHER mismatch is data the caller expects to
                        # train on — count it and warn instead of silently
                        # losing it.
                        if not (nxt is None and b < expected_batch):
                            self.dropped_batches += 1
                            warnings.warn(
                                f"ParallelWrapper dropped a mid-stream "
                                f"minibatch of size {b} (expected "
                                f"{expected_batch}): all non-trailing "
                                f"minibatches must share one batch size "
                                f"({self.dropped_batches} dropped so far)",
                                stacklevel=2)
                        ds = nxt
                        continue
                    buf.append(ds)
                    if len(buf) == need:
                        self._fit_round(buf)
                        buf = []
                    ds = nxt
                # trailing partial group: dropped (reference parity)
                for listener in getattr(net, "listeners", []):
                    listener.on_epoch_end(net)
                if hasattr(net, "epoch"):
                    net.epoch += 1
            return self.net
        finally:
            self._policy = None
            if policy is not None:
                net._health = prev_health

    def _fit_round(self, batches):
        """One averaging round from W*F host minibatches."""
        net = self.net
        W, F = self.workers, self.averaging_frequency
        t_prep0 = time.perf_counter()
        feats = np.stack([np.asarray(b.features) for b in batches])  # [W*F, B, ...]
        labs = np.stack([np.asarray(b.labels) for b in batches])
        has_im = any(b.features_mask is not None for b in batches)
        has_lm = any(b.labels_mask is not None for b in batches)
        if has_im and not all(b.features_mask is not None for b in batches):
            raise ValueError("Mixed masked/unmasked feature batches in one "
                             "averaging round are not supported")
        if has_lm and not all(b.labels_mask is not None for b in batches):
            raise ValueError("Mixed masked/unmasked label batches in one "
                             "averaging round are not supported")
        ims = (np.stack([np.asarray(b.features_mask) for b in batches])
               if has_im else np.zeros(feats.shape[:2], np.float32))
        lms = (np.stack([np.asarray(b.labels_mask) for b in batches])
               if has_lm else np.zeros(feats.shape[:2], np.float32))

        # [W*F, B, ...] -> [F, W*B, ...]: round-robin assignment of minibatches
        # to workers (batch i goes to worker i % W, matching the reference's
        # round-robin feeding), so along the sharded axis each worker's F
        # batches are contiguous per step.
        def regroup(a):
            # [W*F, B, ...] -> [F, W, B, ...] -> [F, W*B, ...]
            fwb = a.reshape(F, W, *a.shape[1:])
            return fwb.reshape(F, W * a.shape[1], *a.shape[2:])

        feats, labs, ims, lms = map(regroup, (feats, labs, ims, lms))
        guarded = self._policy is not None
        key = (feats.shape, labs.shape, has_im, has_lm, guarded)
        rnd = self._get_round(key)
        t_dev0 = time.perf_counter()
        base = (net._rng_base() if hasattr(net, "_rng_base")
                else jax.random.PRNGKey(net.conf.seed))
        rng = jax.random.fold_in(base, net.iteration)
        out = rnd(
            net.params, net.updater_state, net.state, rng,
            jnp.asarray(net.iteration, jnp.float32), feats, labs, ims, lms)
        scores_h = skips_h = None
        if guarded:
            params, opt, state, losses, skips = out
        else:
            params, opt, state, loss = out
        net.params, net.updater_state, net.state = params, opt, state
        it0 = net.iteration
        net.iteration += F
        listeners = getattr(net, "listeners", [])
        # timings need a device sync; report_score already pays one — as
        # does the guarded round's stats fetch. report_score=False exists
        # precisely to let the next round's host prep overlap the device
        # compute — only the guard or a listener that actually consumes
        # phase timings may re-introduce the block.
        want_timings = self.report_score or any(
            type(ls).on_phase_timings is not TrainingListener.on_phase_timings
            for ls in listeners)
        if guarded:
            # ONE small host fetch per round: [F] mean losses + [F] skip
            # fractions together
            scores_h, skips_h = map(np.asarray,
                                    jax.device_get((losses, skips)))
            if self.report_score:
                # mean over the round's F per-step pmean'd losses — equal to
                # the unguarded round's pmean(mean(losses)) scalar
                net.score_value = float(np.mean(scores_h))
        elif self.report_score:
            net.score_value = float(loss)  # forces device round completion
        elif want_timings:
            jax.block_until_ready(loss)
        if want_timings:
            t_end = time.perf_counter()
            # per-round phase stats (reference: SparkTrainingStats —
            # data-fetch / fit / aggregation per worker round). Averaging
            # is INSIDE the jitted device round here (one pmean), so it
            # cannot be timed separately from fit — reported as part of
            # device_round_ms, with the key present so consumers see the
            # design, not a hole.
            self.last_phase_timings = {
                "host_prep_ms": (t_dev0 - t_prep0) * 1e3,
                "device_round_ms": (t_end - t_dev0) * 1e3,
                "averaging": "in-device-round",
                "round_iterations": F,
                "workers": W,
            }
            for listener in listeners:
                listener.on_phase_timings(net, dict(self.last_phase_timings))
        it_done = net.iteration
        if guarded:
            # may back off the LR (dropping cached rounds), roll back, or
            # raise — BEFORE the listener round, so gated checkpoint
            # listeners see this round's skip state
            self._policy.observe(net, scores_h, skips_h, it0)
        for listener in listeners:
            listener.iteration_done(net, it_done)

    # ------------------------------------------------------------- utilities
    def average_models(self):
        """No-op: params live once, replicated by XLA (reference needed explicit
        averageModelsParams across replicas; here averaging happens inside the
        jitted round)."""
        return self.net
