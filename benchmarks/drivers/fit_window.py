"""Plays a ``fit_window`` traffic file against a network's ``fit()``.

Traffic parameters: ``batch``, ``distinct_batches`` (float32 inputs and
one-hot labels made from the seed, held as host numpy ``DataSet``s and
cycled), ``stop_multiple`` (the stream stops at the first multiple of this
many batches after the window's seconds have passed, so that no ragged
tail compiles inside it), ``warmup_batches``, ``trace_seconds``.

Set-up builds one network, gives it the benchmark's weights, and drives it
through ``fit()`` over ``warmup_batches`` batches with a listener that
keeps every step's score and copies parameters, updater state and running
statistics as they stand after the first fused block. ``fit()`` fuses K
steps into one program and shows its state only between blocks, and the
zoo's RmsProp(0.1) moves every weight by about its own size a step, so
that after four steps every norm has swung by a tenth or more whatever the
arithmetic (PERF.md, PR 25: the reference against itself at three passes
reads the same). So the first block holds one real batch and K-1 batches
of NaN: the health guard, which is on, selects the identity update for
those inside the same compiled program, and the block's end is the state
after one step, through the window's own call and feed. The second block
is K real batches. The window is one more ``fit()`` call on that same
object over the cycled stream. After the window the program's state is
freed and the plain reference follows the first step from the same weights.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.harness import loader
from benchmarks.harness.clock import rate
from benchmarks.harness.program import build_net, merged

#: what an idle gap is called that no span of the benchmark's covers
UNATTRIBUTED = "fit_loop_unattributed"


def make_batches(seed: int, traffic: dict, sizes: dict):
    rng = np.random.default_rng([int(seed), 25])
    n, b = traffic["distinct_batches"], traffic["batch"]
    h, w, c = sizes["image"]
    classes = sizes["num_labels"]
    xs = [rng.standard_normal((b, h, w, c), dtype=np.float32)
          for _ in range(n)]
    eye = np.eye(classes, dtype=np.float32)
    ys = [eye[rng.integers(0, classes, b)] for _ in range(n)]
    return xs, ys


class Stream:
    """The cycled stream of one window. Iterated by ``fit()`` on the main
    thread; starts and stops the trace slice between batches."""

    def __init__(self, datasets, seconds, multiple, trace=None,
                 trace_seconds=0.0, annotate=None):
        self.datasets = datasets
        self.seconds = seconds
        self.multiple = multiple
        self.trace = trace
        self.trace_seconds = trace_seconds
        self.annotate = annotate
        self.count = 0
        self.t_open = None

    def __iter__(self):
        self.t_open = time.perf_counter()
        n = len(self.datasets)
        while True:
            t = time.perf_counter() - self.t_open
            if self.count % self.multiple == 0:
                if t >= self.seconds:
                    break
                tr = self.trace
                if tr is not None and tr.started is None \
                        and t >= self.seconds - self.trace_seconds:
                    # the slice is the window's last seconds; it is
                    # stopped once the window has closed, so that writing
                    # the profile out costs the window nothing
                    tr.start()
            if self.annotate is None:
                yield self.datasets[self.count % n]
            else:
                # open while fit() works on what it pulled: stacking,
                # padding, device_put, dispatch, the block's score fetch
                with self.annotate("fit_between_pulls"):
                    yield self.datasets[self.count % n]
            self.count += 1


def make_recorder(snapshot_at: int):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class Recorder(TrainingListener):
        def __init__(self):
            self.scores = []
            self.skipped = 0
            self.actions = []
            self.snapshot = None

        def on_block_done(self, model, iterations, scores):
            self.scores.extend(float(s) for s in scores)
            if self.snapshot is None and iterations[-1] == snapshot_at:
                self.snapshot = jax.tree_util.tree_map(
                    jnp.copy, {"params": model.params,
                               "h": model.updater_state["h"],
                               "bn_state": model.state})

        def on_health(self, model, report):
            self.actions.append(report.get("action"))
            if report.get("action") == "skip":
                self.skipped += int(report.get("skipped_in_block", 0))

    return Recorder()


def fused_steps(net) -> int:
    from deeplearning4j_tpu.optimize.fused_fit import resolve_fused_steps

    return resolve_fused_steps(net, None)


def setup(run):
    from deeplearning4j_tpu.datasets.dataset import DataSet

    t_setup = time.perf_counter()
    config = merged(run.config, run.rehearse)
    traffic = merged(run.traffic, run.rehearse)
    ref = loader.load_module("references", run.cell["config"])
    params = ref.make_params(run.seed, config["sizes"], config["init"])
    net = build_net(config, params)
    k = fused_steps(net)
    if not run.rehearse and k != traffic["fused_steps"]:
        raise RuntimeError(f"fit() would fuse {k} steps, the traffic file "
                           f"states {traffic['fused_steps']}")
    t_data = time.perf_counter()
    xs, ys = make_batches(run.seed, traffic, config["sizes"])
    datasets = [DataSet(x, y) for x, y in zip(xs, ys)]
    run.log(f"set-up: weights and network {t_data - t_setup:.1f} s, "
            f"batches {time.perf_counter() - t_data:.1f} s")
    # a copy of the first weights for the change's norm: fit() donates them
    import jax
    import jax.numpy as jnp

    first = {n: jax.tree_util.tree_map(jnp.copy, sub)
             for n, sub in net.params.items() if sub}
    rec = make_recorder(snapshot_at=k)
    net.set_listeners(rec)
    skipped = DataSet(np.full_like(xs[0], np.nan), ys[0])
    warm = [datasets[0]] + [skipped] * (k - 1) \
        + [datasets[1 + i % (len(datasets) - 1)]
           for i in range(traffic["warmup_batches"] - k)]
    t_warm = time.perf_counter()
    with run.annotate("warmup"):
        net.fit(warm, epochs=1)
        jax.block_until_ready(net.params)
    run.log(f"set-up: warm-up of {len(warm)} batches "
            f"{time.perf_counter() - t_warm:.1f} s")
    if rec.snapshot is None:
        raise RuntimeError("the warm-up never finished a fused block")
    if rec.skipped != k - 1:
        raise RuntimeError(f"the guard skipped {rec.skipped} of the first "
                           f"block's {k - 1} NaN batches")
    return {"net": net, "config": config, "traffic": traffic, "k": k,
            "datasets": datasets, "first": first, "warm": rec,
            "batches": (xs[:1], ys[:1])}


def window(run, st):
    import jax

    net, traffic = st["net"], st["traffic"]
    rec = make_recorder(snapshot_at=-1)
    net.set_listeners(rec)
    stream = Stream(st["datasets"], run.seconds, traffic["stop_multiple"],
                    trace=run.trace, trace_seconds=traffic["trace_seconds"],
                    annotate=run.annotate if run.trace else None)
    it0 = net.iteration
    t_open = time.perf_counter()
    net.fit(stream, epochs=1)
    jax.block_until_ready(net.params)
    t_close = time.perf_counter()
    if run.trace is not None and run.trace.active:
        run.trace.stop()
    steps = net.iteration - it0
    bad = sum(1 for s in rec.scores if not np.isfinite(s))
    failed = max(rec.skipped, bad)
    samples = stream.count * traffic["batch"]
    run.log(f"window: {steps} steps, {stream.count} batches, "
            f"{t_close - t_open:.3f} s, skipped {rec.skipped}, non-finite "
            f"scores {bad}, guard actions {sorted(set(rec.actions))}, "
            f"last score {rec.scores[-1] if rec.scores else None}")
    st["window_facts"] = {
        "samples": samples, "steps": steps, "t_open": t_open,
        "t_close": t_close, "window_s": t_close - t_open,
        "samples_per_s": rate(samples, t_open, t_close)}
    return {"attempted": steps, "failed": failed,
            "end_to_end": {"train_samples_per_s":
                           st["window_facts"]["samples_per_s"]},
            "facts": st["window_facts"]}


def release(st):
    """Frees the program's state; keeps what the comparison reads."""
    st["net"].set_listeners()
    for k in ("net", "datasets"):
        st.pop(k, None)


# ------------------------------------------------------------ comparison
def leaf_norms(tree) -> dict:
    import jax
    import jax.numpy as jnp

    flat = {}
    for name, sub in tree.items():
        for k, v in sub.items():
            flat[f"{name}/{k}"] = jnp.sqrt(jnp.sum(
                jnp.square(v.astype(jnp.float32))))
    return {k: float(v) for k, v in jax.device_get(flat).items()}


def gaps(got: dict, want: dict, keep=None) -> dict:
    """Per leaf, |got norm - reference norm| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    names = [n for n in want if keep is None or n in keep]
    med = float(np.median([want[n] for n in names]))
    return {n: abs(got[n] - want[n]) / max(want[n], med, 1e-30)
            for n in names}


def worst(table: dict, only=None):
    """(largest gap, its leaf) over the leaves whose name ends in one of
    ``only``; NaN if a gap is not finite."""
    rows = {n: g for n, g in table.items()
            if only is None or n.rsplit("/", 1)[1] in only}
    if any(not np.isfinite(g) for g in rows.values()):
        return float("nan"), None
    at = max(rows, key=rows.get)
    return rows[at], at


#: the leaves that are kernels of a product (convolution, dense)
KERNELS = ("W",)


def numbers(got: dict, want: dict, first) -> dict:
    """Every number the comparison knows, ``got`` against the reference
    ``want`` after one step: both hold ``losses``, ``params``, ``h``
    (RmsProp's state) and ``bn_state``. Names that start with ``_`` say
    where a worst leaf was."""
    import jax

    out = {"loss_step1": abs(got["losses"][0] - want["losses"][0])
           / abs(want["losses"][0])}
    # leaves whose gradient is nought to rounding in the reference move by
    # round-off alone: out of the change by a rule on the reference's
    # first gradient, under a thousandth of the median leaf's
    g1 = {f"{n}/{k}": float(v) for n, sub in jax.device_get(
        want["grad1_norms"]).items() for k, v in sub.items()}
    med = float(np.median(list(g1.values())))
    keep = {n for n, v in g1.items() if v >= 1e-3 * med}
    diff = lambda t: jax.tree_util.tree_map(lambda a, b: a - b, t, first)  # noqa: E731
    upd = gaps(leaf_norms(diff(got["params"])),
               leaf_norms(diff(want["params"])), keep)
    # sqrt of the summed RmsProp state after one step: the first
    # gradient's size as the optimizer got it
    sq = lambda t: {n: {k: abs(v) ** 0.5 for k, v in s.items()}  # noqa: E731
                    for n, s in t.items()}
    grad = gaps(leaf_norms(sq(got["h"])), leaf_norms(sq(want["h"])), keep)
    for name, table in (("update_norm", upd), ("grad_norm", grad)):
        out[f"{name}_worst_leaf"], out[f"_{name}_worst_leaf_at"] = \
            worst(table)
        out[f"{name}_worst_kernel"], out[f"_{name}_worst_kernel_at"] = \
            worst(table, KERNELS)
        out[f"{name}_median_leaf"] = float(np.median(list(table.values())))
    for stat in ("mean", "var"):
        pick = lambda t: {n: {stat: s[stat]} for n, s in t.items()}  # noqa: E731
        table = gaps(leaf_norms(pick(got["bn_state"])),
                     leaf_norms(pick(want["bn_state"])))
        out[f"bn_{stat}_worst_leaf"], out[f"_bn_{stat}_worst_leaf_at"] = \
            worst(table)
        out[f"bn_{stat}_median_leaf"] = float(np.median(
            list(table.values())))
    return out


def reference_block(st, mode="float32", rows=None):
    ref = loader.load_module("references", st["cell_config"])
    xs, ys = st["batches"]
    config = st["config"]
    return ref.train_steps(st["first"], xs, ys, config["sizes"],
                           config["hyper"], 1, mode=mode, rows=rows)


def program_block(st) -> dict:
    rec = st["warm"]
    snap = {n: {k: v for k, v in sub.items()}
            for n, sub in rec.snapshot["bn_state"].items() if sub}
    return {"losses": rec.scores[:1],
            "params": {n: s for n, s in rec.snapshot["params"].items() if s},
            "h": {n: s for n, s in rec.snapshot["h"].items() if s},
            "bn_state": snap}


def check(run, st, compared):
    """The program's first step against the reference's."""
    st["cell_config"] = run.cell["config"]
    want = reference_block(st)
    got = program_block(st)
    nums = numbers(got, want, st["first"])
    compared.take(nums, run.limits, run.log)
    return nums


# ------------------------------------------------- readings for the limits
def readings(run, kinds):
    """For ``benchmarks/readings.py``: one seed's numbers, with no window,
    for each kind asked: ``program`` (the timed path's first block),
    ``control`` (the reference in the configuration's ``control``
    precision, put in the program's place) and the faults planted in the
    reference put in the program's place: ``fault_half_batch`` (half of
    the rows left out, the mean taken over the rest) and
    ``fault_state_unchanged`` (a step that returns its state unchanged)."""
    import jax

    st = setup(run)
    st["cell_config"] = run.cell["config"]
    got = program_block(st)
    release(st)
    want = reference_block(st)
    out = {}
    for kind in kinds:
        if kind == "program":
            side = got
        elif kind == "control":
            side = reference_block(st, mode=st["config"]["control"])
        elif kind == "reference_high":
            # the look at the later steps: the reference itself, its
            # products at three passes instead of six
            side = reference_block(st, mode="high")
        elif kind == "fault_half_batch":
            half = st["traffic"]["batch"] // 2
            side = reference_block(st, rows=slice(0, half))
        elif kind == "fault_state_unchanged":
            zeros = jax.tree_util.tree_map(lambda a: a * 0, want["h"])
            side = {"losses": [want["losses"][0]],
                    "params": st["first"], "h": zeros,
                    "bn_state": loader.load_module(
                        "references", run.cell["config"]).init_bn_state(
                            st["config"]["sizes"])}
        else:
            raise ValueError(f"fit_window has no reading {kind!r}")
        out[kind] = {k: v for k, v in numbers(side, want,
                                              st["first"]).items()}
    return out
