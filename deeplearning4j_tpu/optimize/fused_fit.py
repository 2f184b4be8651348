"""Fused multi-step training driver: K SGD steps as ONE XLA program.

The per-minibatch ``do_step`` path pays one Python dispatch, one host->device
transfer, and one listener round-trip per minibatch. The reference hides the
ETL half of that with ``AsyncDataSetIterator`` background prefetch
(datasets/iterator/AsyncDataSetIterator.java:30); the TPU-idiomatic completion
implemented here fuses the dispatch half too:

- ``build_step_core`` — the single functional SGD step (forward, loss,
  jax.grad, regularization, gradient normalization, updater, center-loss
  update) shared by the unfused jitted step (``MultiLayerNetwork._make_step``
  and the ComputationGraph twin), the fused K-step scan below, and
  ``ParallelWrapper``'s data-parallel device round — one definition, three
  drivers, no drift.
- ``build_fused_step`` — K placed microbatches compiled as one jitted,
  buffer-donating program (stacked on the device, then ``lax.scan``;
  unrolled at trace time on CPU, where XLA pessimizes compute inside
  control-flow bodies). Only FULL K-blocks are dispatched to it; a
  trailing group of fewer than K microbatches takes the per-minibatch
  path, which beats any in-program dead-slot skip (see ``FusedFitDriver``).
- ``FusedFitDriver`` — batch-shape BUCKETING (trailing partial batches are
  padded up to the bucket batch size with zeroed label-mask rows, so
  ``_step_cache`` holds ONE program across a ragged epoch) plus the device
  feed: every microbatch goes to the device on its own as soon as it is
  pulled, carried by a worker thread, up to ``prefetch_depth`` blocks
  ahead of the block that runs. No block of megabytes is ever assembled
  on the host, and the thread that dispatches (and blocks in the block's
  score fetch) never copies one (the measurements behind this:
  ``PLACE_WORKERS``, ``WORKER_MIN_BYTES``).

Listener semantics under fusion: listeners still fire once per iteration,
but scores materialize per BLOCK — one device fetch of the stacked loss
array per K steps instead of one per step. Listener hooks therefore observe
end-of-block parameters. Listeners wanting the whole stacked array get it
via ``TrainingListener.on_block_done``.
"""

from __future__ import annotations

import queue
import threading
from collections import deque
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.metrics.registry import global_registry
from deeplearning4j_tpu.metrics.spans import SpanClock
from deeplearning4j_tpu.nn.gradient_normalization import (
    apply_gradient_normalization,
    layer_map_for,
)
from deeplearning4j_tpu.nn.regularization import add_regularization_grads

#: default K for ``fit(..., fused_steps=None)`` — the fused fast path is the
#: default; pass ``fused_steps=1`` to opt out (pure per-minibatch do_step).
DEFAULT_FUSED_STEPS = 4

#: CPU default. Measured on XLA:CPU (LeNet, single core): per-step cost of
#: the fused program grows with the unroll factor (K=2 is ~flat, K=4 is
#: 1.4-2x a single step — LLVM code-size/cache effects), so larger K LOSES
#: throughput. K=2 keeps the one-program-per-ragged-epoch property and the
#: block-level score fetch while staying at the measured sweet spot.
DEFAULT_FUSED_STEPS_CPU = 2


def resolve_fused_steps(net, fused_steps):
    """Effective K for a fit call. TBPTT carries hidden state across
    segments host-side, so it stays on the unfused path regardless."""
    if fused_steps is None:
        k = (DEFAULT_FUSED_STEPS_CPU if jax.default_backend() == "cpu"
             else DEFAULT_FUSED_STEPS)
    else:
        k = int(fused_steps)
        if k < 1:
            raise ValueError(f"fused_steps must be >= 1, got {fused_steps}")
    if getattr(net.conf, "backprop_type", "standard") == "tbptt":
        return 1
    return k


# --------------------------------------------------------------- step core
def _center_spec(net):
    """(kind, key(s)) of CenterLossOutputLayer heads needing the non-gradient
    center update, or None. Works for both MultiLayerNetwork (layers list)
    and ComputationGraph (vertices dict)."""
    from deeplearning4j_tpu.nn.conf.layers.misc import CenterLossOutputLayer

    layers = getattr(net, "layers", None)
    if isinstance(layers, list):
        if layers and isinstance(layers[-1], CenterLossOutputLayer):
            return ("mln", str(len(layers) - 1))
        return None
    conf = net.conf
    if hasattr(conf, "network_outputs") and hasattr(conf, "vertices"):
        from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertex

        outs = [n for n in conf.network_outputs
                if isinstance(conf.vertices[n], LayerVertex)
                and isinstance(conf.vertices[n].layer, CenterLossOutputLayer)]
        if outs:
            return ("graph", outs)
    return None


def build_step_core(net, *, grad_transform=None, guarded=False):
    """One functional SGD step over ``net``'s ``_loss`` contract.

    Returns ``core(params, opt_state, state, rng, iteration, x, y,
    input_mask, label_mask, carry) -> (new_params, new_opt, new_states,
    new_carry, loss)``. ``grad_transform`` (e.g. a ``lax.pmean``) is applied
    between the closed-form regularization grads and gradient normalization
    — the ordering ParallelWrapper's SHARED_GRADIENTS parity contract needs.

    With ``guarded=True`` the core additionally runs the numerical-health
    guard (optimize/health.py): one all-finite reduction over the loss and
    the post-transform gradients; when non-finite, the IDENTITY update is
    selected (params/opt-state/layer-state/carry pass through unchanged)
    and the returned tuple gains a trailing ``skip`` scalar (1.0 when the
    step was skipped) — ``(..., loss, skip)``. The finite check sits after
    ``grad_transform`` so a SHARED_GRADIENTS ``pmean`` poisons (and skips)
    all replicas identically, keeping them in lockstep. The raw (possibly
    non-finite) loss is still reported: the guard protects the weights,
    not the telemetry. On the all-finite path the select returns the new
    trees exactly, so guarded and unguarded trajectories are bit-identical.
    """
    from deeplearning4j_tpu.optimize.health import all_finite, tree_select

    updater = net.conf.updater
    lr_mults = net._lr_mult_tree() if hasattr(net, "_lr_mult_tree") else None
    layer_map = layer_map_for(net)
    center = _center_spec(net)

    def core(params, opt_state, state, rng, iteration, x, y, input_mask,
             label_mask, carry):
        def loss_fn(p):
            return net._loss(p, state, x, y, input_mask, label_mask,
                             train=True, rng=rng, carry=carry)

        (loss, (new_states, new_carry, last_in)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        grads = add_regularization_grads(net, params, grads)
        if grad_transform is not None:
            grads = grad_transform(grads)
        if guarded:
            ok = all_finite(loss, grads)
        grads = apply_gradient_normalization(layer_map, grads)
        if lr_mults is not None:
            steps, opt_state2 = updater.step(grads, opt_state, iteration,
                                             lr_mults)
        else:
            steps, opt_state2 = updater.step(grads, opt_state, iteration)
        new_params = jax.tree_util.tree_map(lambda p, s: p - s, params, steps)
        if center is not None:
            kind, keys = center
            if kind == "mln":
                new_states[keys] = net.layers[-1].update_centers(
                    state[keys], last_in, y)
            else:
                outs = net.conf.network_outputs
                for name in keys:
                    j = outs.index(name)
                    yy = y[j] if isinstance(y, (list, tuple)) else y
                    new_states[name] = net.conf.vertices[name].layer \
                        .update_centers(state[name], last_in[name], yy)
        if guarded:
            # identity update on a poisoned step: everything the step
            # would have mutated passes through unchanged
            new_params = tree_select(ok, new_params, params)
            opt_state2 = tree_select(ok, opt_state2, opt_state)
            new_states = tree_select(ok, new_states, state)
            new_carry = tree_select(ok, new_carry, carry)
            skip = 1.0 - ok.astype(jnp.float32)
            return (new_params, opt_state2, new_states, new_carry, loss,
                    skip)
        return new_params, opt_state2, new_states, new_carry, loss

    return core


def make_scan_body(core, *, rng_fn, guarded=False):
    """``lax.scan`` body over ``core``. Carry is ``(params, opt_state,
    state, iteration)``; each scan slot is ``(x, y, im, lm)``. Every slot
    is a real step — the fused driver only dispatches FULL K-blocks
    through the scan (a trailing partial block takes the per-minibatch
    path instead), so the body needs no per-slot dead-slot machinery: a
    ``lax.cond`` skip was measured to pessimize the whole body 5x on
    XLA:CPU, and a select-based skip pays full dead-slot FLOPs plus a
    param-tree copy on every live step. (The health guard's where-select
    is different: it fires only on NON-FINITE steps, a correctness
    feature; ``resnet50_fit_b256`` runs with it on, PERF.md §5 has its
    share of the chip's busy time.)

    With ``guarded=True`` (a ``build_step_core(guarded=True)`` core) the
    per-slot output is the ``(loss, skip)`` pair instead of the bare loss,
    so a fused block surfaces its per-step skip flags stacked alongside
    the stacked losses — still one host fetch per block. The iteration
    counter advances on skipped steps too, keeping the ``fold_in(base_key,
    iteration)`` RNG stream — and therefore fused/unfused bit-parity —
    independent of where the bad batch landed."""

    def body(carry, inp):
        params, opt_state, state, it = carry
        x, y, im, lm = inp
        rng = rng_fn(it)
        if guarded:
            p2, o2, s2, _, loss, skip = core(params, opt_state, state, rng,
                                             it, x, y, im, lm, None)
            return (p2, o2, s2, it + 1.0), (loss, skip)
        p2, o2, s2, _, loss = core(params, opt_state, state, rng, it,
                                   x, y, im, lm, None)
        return (p2, o2, s2, it + 1.0), loss

    return body


def _unroll_fused() -> bool:
    """Whether the fused program should be traced as straight-line code.

    XLA:CPU pessimizes compute inside ``while`` bodies — a LeNet train step
    measured 5x slower under ``lax.scan`` than the identical step as
    top-level HLO, and ``unroll=K`` does not help (the single-trip while
    remains). On CPU the K steps are therefore unrolled at trace time
    (program size O(K), per-step cost identical to the unfused step); on
    TPU/GPU the rolled scan is kept for O(1) program size and compile
    time."""
    return jax.default_backend() == "cpu"


def build_fused_step(net, guarded=False):
    """The fused K-step program: one jitted, buffer-donating K-step loop
    (``lax.scan``, unrolled at trace time on CPU — see ``_unroll_fused``).

    ``fused(params, opt_state, state, base_key, it0, xs, ys, ims, lms)
    -> (params, opt_state, state, losses[K])`` — with ``guarded=True``
    the health guard rides inside the program and the outputs gain a
    trailing ``skips[K]`` stack (see ``build_step_core``). ``xs/ys`` are
    K-tuples of per-microbatch [B, ...] arrays, each placed on its own:
    the program stacks them on the device (one HBM copy, 4.4 ms for the
    1.23 GB of four 512x224x224x3 float32 batches against 300 ms of
    steps; chip run, PR 27) so that ``lax.scan`` sees the [K, B, ...]
    operand it always has. A small bucket's block comes stacked on the
    host, as [K, B, ...] arrays, and is scanned as it is. ``ims/lms`` are
    [K, B, ...] stacks (or None — static, baked per jit signature). The
    per-slot rng is ``fold_in(base_key, iteration)`` — bit-identical to
    the unfused ``do_step`` path, so fused and unfused trajectories
    match."""
    core = build_step_core(net, guarded=guarded)

    def fused(params, opt_state, state, base_key, it0, xs, ys, ims, lms):
        body = make_scan_body(
            core,
            rng_fn=lambda it: jax.random.fold_in(base_key,
                                                 it.astype(jnp.int32)),
            guarded=guarded)
        carry = (params, opt_state, state, it0)
        if _unroll_fused():
            outs = []
            for k in range(len(xs)):  # static index -> straight-line HLO
                carry, out = body(carry, (xs[k], ys[k],
                                          None if ims is None else ims[k],
                                          None if lms is None else lms[k]))
                outs.append(out)
            if guarded:
                losses = jnp.stack([o[0] for o in outs])
                skips = jnp.stack([o[1] for o in outs])
            else:
                losses = jnp.stack(outs)
        else:
            if isinstance(xs, tuple):
                xs, ys = jnp.stack(xs), jnp.stack(ys)
            carry, scanned = lax.scan(body, carry, (xs, ys, ims, lms))
            if guarded:
                losses, skips = scanned
            else:
                losses = scanned
        params, opt_state, state, _ = carry
        if guarded:
            return params, opt_state, state, losses, skips
        return params, opt_state, state, losses

    # params/opt/state are dead after the call (the driver rebinds them from
    # the outputs) — donation updates the model in place across all K steps
    return jax.jit(fused, donate_argnums=(0, 1, 2))


# ------------------------------------------------------------ host pipeline
def device_put_ahead(items, depth: int, place):
    """Bounded look-ahead device placement: keep ``depth`` placed items in
    flight while the consumer works on the current one. ``jax.device_put``
    dispatches asynchronously, so issuing the puts ahead pipelines the
    host->device copies behind the running computation — the on-device
    analogue of AsyncDataSetIterator's host-side queue."""
    if depth < 1:
        raise ValueError("depth must be >= 1")
    it = iter(items)
    buf: deque = deque()
    try:
        for _ in range(depth):
            buf.append(place(next(it)))
    except StopIteration:
        pass
    while buf:
        nxt = buf.popleft()
        try:
            buf.append(place(next(it)))  # dispatch ahead, async
        except StopIteration:
            pass
        yield nxt


#: worker threads that carry microbatches to the device, each with one
#: copy in flight. ONE, by the cell it was measured in (chip runs, PR 27;
#: ResNet50, four 308 MB float32 batches a block, 0.31 s of steps a block):
#: 1 worker 6,484 samples/s, 2 workers 6,067, 3 workers 5,250, 4 workers
#: 4,846. Several copies do cross the link faster than one (1 thread
#: 5.3 GB/s, 2 threads 9.8, 4 threads 12.4) and cost the device's compute
#: nothing, but a block's dispatch and its score fetch are small transfers
#: on the same link, serial with the device, and they queue behind the
#: copies in flight: a 2 KB ``device_put`` is there after 0.8 ms beside
#: one bulk copy, 7 ms beside two, 25 ms beside three, 47 ms beside four.
#: One copy at a time carries 5.3 GB/s, which is 8,800 float32 images of
#: 224 x 224 x 3 a second. A stream whose device outruns that reads it in
#: ``fit_feed_wait_seconds_total``.
PLACE_WORKERS = 1

#: a bucket whose microbatch (features and labels) is smaller than this is
#: stacked on the host and placed with its block, by one ``device_put`` on
#: the calling thread: a ``device_put`` costs the caller 0.2-0.25 ms an
#: array whatever its size (3 arrays 0.65 ms, 9 arrays 1.7 ms), and
#: ``np.stack`` of four 200 KB batches 0.03 ms. Zoo LeNet over 28 x 28 x 1
#: float32, batches a second, host stack against worker (chip runs, PR 27):
#: 0.2 MB a batch 1,000 against 700; 1.6 MB 556 against 534 (four workers);
#: 3.2 MB 414-425 against 467-474; 6.4 MB 200-202 against 234-236; 12.8 MB
#: 42 against 108.
WORKER_MIN_BYTES = 1 << 21


class _PlacementWorkers:
    """The daemon threads of ONE ``fit_stream`` call that carry its
    microbatches to the device. ``submit`` hands a tuple of arrays over
    and returns one future per array at once; ``place`` runs on a worker
    and its result, or its exception, resolves them. Threads start with
    the first submission, so a stream that is already on the device
    starts none."""

    def __init__(self, workers: int, place):
        self._n = workers
        self._place = place
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._threads: list = []
        self._closed = False

    def submit(self, arrays) -> tuple:
        if not self._threads:
            self._threads = [
                threading.Thread(target=self._work, daemon=True,
                                 name=f"fit-place-{i}")
                for i in range(self._n)]
            for t in self._threads:
                t.start()
        futures = tuple(Future() for _ in arrays)
        self._jobs.put((arrays, futures))
        return futures

    def _work(self):
        while True:
            job = self._jobs.get()
            if job is None:
                return
            if self._closed:
                continue  # nobody is left to read it: drop, do not copy
            arrays, futures = job
            try:
                placed = self._place(arrays)
            except BaseException as e:  # noqa: BLE001 — handed to the reader
                for f in futures:
                    f.set_exception(e)
            else:
                for f, a in zip(futures, placed):
                    f.set_result(a)

    def close(self):
        """Drops what is queued and joins the threads: a placement that
        is under way is waited for, none is started after this."""
        self._closed = True
        for _ in self._threads:
            self._jobs.put(None)
        for t in self._threads:
            t.join()
        self._threads = []


def _array(a):
    """``a`` as the feed takes it: a device array as it is (never fetched
    back to be sent up again), anything else as host numpy."""
    return a if isinstance(a, jax.Array) else np.asarray(a)


def _placed(a):
    """A microbatch's array once it is on the device: waits for the
    worker that carries it, and raises what that worker raised."""
    return a.result() if isinstance(a, Future) else a


class FusedFitDriver:
    """Consumes a stream of DataSets as fused K-step blocks.

    Shape bucketing: the first usable batch fixes the bucket (batch size +
    trailing dims + mask signature). Undersized batches — the ragged tail
    of an epoch — are padded UP to the bucket batch size by replicating the
    last row (real data, so no degenerate activations) with ZEROED
    label-mask rows, so the masked loss mean and its gradients are exactly
    those of the unpadded batch and ``_step_cache`` keeps ONE program
    across a ragged epoch. A label mask is synthesized (all ones) for
    unmasked streams so full and padded blocks share one jit signature.

    Only FULL K-blocks go through the fused program: a trailing group of
    fewer than K microbatches runs through the per-minibatch ``_fit_batch``
    path instead. Skipping dead scan slots in-program costs more than it
    saves — ``lax.cond`` pessimizes the whole body 5x on XLA:CPU, and
    select-masking pays full dead-slot FLOPs plus a param-tree copy per
    live step — while the unfused tail pays at most K-1 per-step
    dispatches once per stream.

    The one stream shape bucketing does NOT cover: features_mask present
    without labels_mask — a synthesized label mask would override the
    propagated feature mask the loss otherwise uses, so undersized batches
    there fall back to the unfused ``_fit_batch`` path (correct, one extra
    compile). Batches that don't fit the bucket at all (MultiDataSet,
    different trailing dims, larger than bucket) also fall back, after the
    pending microbatches are flushed so update order is preserved.

    The device feed: the stream is pulled on the thread that called
    ``fit()``, and each bucketed microbatch is handed to the placement
    workers the moment it is pulled — features and labels by
    ``jax.device_put`` in the dtype they came in; arrays that are on the
    device already (``DataSet.on_device``) pass through untouched. Blocks
    take their microbatches strictly in the order pulled and wait for one
    only when it is needed. The look-ahead is ``prefetch_depth`` blocks:
    at most ``prefetch_depth x K`` microbatches are placed or being placed
    beyond the block that is next to run. The microbatches of a "tail"
    group (at most K-1 a stream) were handed over before the stream showed
    it had no K-th: they train from their DataSets and their copies are
    dropped. However ``fit_stream`` is left — the iterable, a placement or
    the guard's ``DivergenceError`` raising — no worker outlives it.
    The one adaptation, on the bytes of the first microbatch, which fix
    the bucket: under ``WORKER_MIN_BYTES`` the K microbatches are stacked
    on the host and placed with their block by the calling thread.
    ``fit_feed_wait_seconds_total`` against ``fit_fetch_wait_seconds_total``
    (``global_registry()``) says whether the feed or the device sets the
    pace.
    """

    def __init__(self, net, fused_steps: int, prefetch_depth: int = 2):
        if fused_steps < 1:
            raise ValueError("fused_steps must be >= 1")
        self.net = net
        self.K = fused_steps
        self.depth = max(1, prefetch_depth)
        reg = global_registry()
        placed = reg.counter(
            "fit_microbatches_placed_total",
            "microbatches handed to fit()'s device feed", labels=("how",))
        self._m_placed = {how: placed.labels(how=how)
                          for how in ("worker", "block", "passthrough")}
        # the dispatching thread's spans (``fit:<name>`` in a profiler's
        # trace) and the counters their seconds and counts go to
        sinks = {
            "feed_wait": ([reg.counter(
                "fit_feed_wait_seconds_total",
                "seconds the dispatching thread waited for a microbatch "
                "that was not on the device yet")], []),
            "dispatch": ([], [reg.counter(
                "fit_blocks_dispatched_total",
                "fused K-step blocks dispatched")]),
            "fetch_wait": ([reg.counter(
                "fit_fetch_wait_seconds_total",
                "seconds the dispatching thread was blocked in a block's "
                "score fetch")], []),
        }
        self._clock = SpanClock("fit:", sinks.__getitem__)

    # ------------------------------------------------------------- assembly
    def _blocks(self, batches, feed):
        """Pulls ``batches`` on the calling thread and hands every fitting
        microbatch to ``feed`` at once; yields ``("block", _stack(K
        items))``, ``("tail", [DataSet])`` and ``("raw", DataSet)`` in
        stream order."""
        from deeplearning4j_tpu.datasets.dataset import DataSet

        bucket = None
        by_worker = False
        pend: list = []  # (handed-over arrays, original DataSet) pairs
        for ds in batches:
            item = None
            if isinstance(ds, DataSet) and ds.labels is not None:
                f, l = _array(ds.features), _array(ds.labels)
                im = (None if ds.features_mask is None
                      else np.asarray(ds.features_mask))
                lm = (None if ds.labels_mask is None
                      else np.asarray(ds.labels_mask))
                if bucket is None:
                    bucket = (f.shape[0], f.shape[1:], l.shape[1:],
                              im is not None, lm is not None)
                    by_worker = f.nbytes + l.nbytes >= WORKER_MIN_BYTES
                B, ftail, ltail, has_im, has_lm = bucket
                fits = (f.shape[1:] == ftail and l.shape[1:] == ltail
                        and (im is not None) == has_im
                        and (lm is not None) == has_lm
                        and f.shape[0] <= B)
                # synthesizing a label mask is only sound when it cannot
                # shadow a propagated feature mask (see class docstring)
                synth_lm = not has_lm and not has_im
                if fits and (f.shape[0] == B or has_lm or synth_lm):
                    f, l, im, lm = self._pad_micro(f, l, im, lm, B, ltail,
                                                   synth_lm)
                    item = self._hand_over(feed if by_worker else None,
                                           f, l) + (im, lm)
            if item is not None:
                pend.append((item, ds))
                if len(pend) == self.K:
                    xs, ys, ims, lms = self._stack([it for it, _ in pend])
                    # what no worker carries goes up now, in one call: the
                    # masks, and with them a small bucket's whole block
                    if by_worker:
                        block = (xs, ys) + jax.device_put((ims, lms))
                    else:
                        block = jax.device_put((xs, ys, ims, lms))
                    yield ("block", block)
                    pend = []
                continue
            if pend:  # flush before the fallback batch: updates stay ordered
                yield ("tail", [d for _, d in pend])
                pend = []
            yield ("raw", ds)
        if pend:
            # fewer than K microbatches left: the per-minibatch path (see
            # class docstring — cheaper than dead scan slots)
            yield ("tail", [d for _, d in pend])

    @staticmethod
    def _pad_micro(f, l, im, lm, B, ltail, synth_lm):
        pad = B - f.shape[0]
        if synth_lm or (lm is None and pad):
            lm = np.ones((f.shape[0],) + ltail[:-1], np.float32)
        if pad:
            def rep(a):
                xp = jnp if isinstance(a, jax.Array) else np
                return xp.concatenate([a, xp.repeat(a[-1:], pad, axis=0)])

            f, l = rep(f), rep(l)
            if im is not None:
                im = rep(im)
            if lm is not None:
                lm = np.concatenate(
                    [lm, np.zeros((pad,) + lm.shape[1:], lm.dtype)])
        return (f, l, im, lm)

    def _hand_over(self, feed, f, l) -> tuple:
        """Features and labels of one microbatch on their way to the
        device: the arrays themselves where both are there already,
        futures from the workers of ``feed``, or, with no ``feed`` (a
        small bucket), the host arrays: ``_stack`` makes one of them and
        it goes up with the block."""
        if isinstance(f, jax.Array) and isinstance(l, jax.Array):
            self._m_placed["passthrough"].inc()
            return (f, l)
        if feed is None:
            self._m_placed["block"].inc()
            return (f, l)
        self._m_placed["worker"].inc()
        return feed.submit((f, l))

    @staticmethod
    def _place_micro(arrays):
        """Runs on a worker: one microbatch's arrays to the device, as the
        dtype they came in. Returns once they ARE there, so that a
        resolved future means a placed microbatch (what
        ``fit_feed_wait_seconds_total`` counts) and each worker has one
        copy in flight."""
        return jax.block_until_ready(jax.device_put(arrays))

    def _stack(self, items):
        """The seam between assembly and execution: K ``(x, y, im, lm)``
        microbatches become one block ``(xs, ys, ims, lms)``. The masks,
        KB to MB, are stacked here as host numpy ``[K, B, ...]``. ``xs``
        and ``ys`` stay K-tuples where anything in them is a future or on
        the device (the program stacks them there); the host arrays of a
        small bucket are stacked here too, because a ``device_put`` costs
        by the array (``WORKER_MIN_BYTES``)."""
        def column(j):
            col = tuple(r[j] for r in items)
            if all(isinstance(a, np.ndarray) for a in col):
                return np.stack(col)
            return col

        def stack(j):
            if items[0][j] is None:
                return None
            return np.stack([r[j] for r in items])

        return (column(0), column(1), stack(2), stack(3))

    # ------------------------------------------------------------ execution
    def fit_stream(self, batches) -> int:
        """Train over one stream of DataSets; returns iterations run."""
        net = self.net
        start = net.iteration
        feed = _PlacementWorkers(PLACE_WORKERS, self._place_micro)
        try:
            # the look-ahead is over blocks whose microbatches the workers
            # are already carrying: nothing is left to place here
            for tag, payload in device_put_ahead(
                    self._blocks(batches, feed), self.depth, lambda t: t):
                if tag == "block":
                    # a step of a profile's step view is one fused block
                    with jax.profiler.StepTraceAnnotation(
                            "fit:block", step_num=net.iteration):
                        self._run_block(*payload)
                elif tag == "tail":
                    for ds in payload:
                        net._fit_batch(ds)
                else:
                    net._fit_batch(payload)
        finally:
            feed.close()
        return net.iteration - start

    def _run_block(self, xs, ys, ims, lms):
        net = self.net
        K = self.K
        span = self._clock.span
        health = getattr(net, "_health", None)
        guarded = health is not None
        if isinstance(xs, tuple):
            with span("feed_wait"):
                xs, ys = tuple(map(_placed, xs)), tuple(map(_placed, ys))
            shapes = (xs[0].shape, ys[0].shape)
        else:
            shapes = (xs.shape, ys.shape)
        it0 = net.iteration
        with span("dispatch", steps=K):
            key = ("fused", K, *shapes,
                   ims is not None, lms is not None, guarded)
            fused = net._get_step(key)
            out = fused(
                net.params, net.updater_state, net.state, net._rng_base(),
                jnp.asarray(it0, jnp.float32), xs, ys, ims, lms)
        if guarded:
            net.params, net.updater_state, net.state, losses, skips = out
        else:
            net.params, net.updater_state, net.state, losses = out
        net.iteration += K
        listeners = net.listeners
        if not listeners and not guarded:
            # device scalar, no host sync — see the score_value contract
            net.score_value = losses[K - 1]
            return
        # ONE device fetch per block (not one per step): the whole stacked
        # loss array comes back, the stacked skip flags with it, then
        # listeners fire per step
        with span("fetch_wait"):
            if guarded:
                scores, skips_h = map(np.asarray,
                                      jax.device_get((losses, skips)))
            else:
                scores = np.asarray(losses)
        if guarded:
            # observe BEFORE the listener round so health-gated checkpoint
            # listeners see this block's skip state, and a recovery (or
            # DivergenceError) precedes — or suppresses — the block's
            # listener dispatch
            health.observe(net, scores, skips_h, it0)
        if not listeners:
            # no listeners: score_value keeps the device-side contract
            net.score_value = losses[K - 1]
        else:
            iters = list(range(it0 + 1, it0 + K + 1))
            for listener in listeners:
                if hasattr(listener, "on_block_done"):
                    listener.on_block_done(net, iters, scores)
            for k, it in enumerate(iters):
                net.score_value = scores[k]
                for listener in listeners:
                    listener.iteration_done(net, it)
