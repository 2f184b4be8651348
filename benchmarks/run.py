#!/usr/bin/env python3
"""Runs one cell of ``BENCHMARK.json`` once, on the chip.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run: refuses without the TPU chips the cell asks for
(exit 2, nothing on stdout), makes inputs and weights from ``--seed``,
warms the cell's own shapes (counted as ``setup_s``), measures for
``--seconds`` seconds with nothing compiling inside, reads the peak
memory, frees the program, compares what the timed path produced with the
configuration's plain reference, and prints the result as the last line
of stdout. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics and the breakdown.

Everything that belongs to one cell is found by name: the configuration
(``configs/``), the traffic mix (``traffic/``, which names its driver in
``drivers/``), the limits of the comparison (``workloads/``), the
reference (``references/``), operation counts (``ops/``) and one reader
per per-layer metric (``metrics/``). This file holds no name of any.

``--rehearse`` is the CPU dry run at the sizes the files give under
``rehearse``: it prints its own summary and never the result line.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks.harness import device as devices  # noqa: E402
from benchmarks.harness import loader  # noqa: E402
from benchmarks.harness.clock import CompileClock  # noqa: E402
from benchmarks.harness.result import Compared, result_line  # noqa: E402
from benchmarks.harness.trace import TraceSlice  # noqa: E402


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def make_run(bench, cell_name, seed, seconds, trace, rehearse=False,
             device=None):
    """Everything a driver and a metric reader may look at."""
    import jax

    cell = loader.find_cell(bench, cell_name)
    workload = loader.load_json("workloads", cell["name"])
    config = loader.load_json("configs", cell["config"])
    traffic = loader.load_json("traffic", cell["traffic"])
    device = device or devices.stamp()
    run = types.SimpleNamespace(
        bench=bench, cell=cell, workload=workload, config=config,
        traffic=traffic, limits=workload.get("limits", {}), seed=int(seed),
        seconds=float(seconds), rehearse=rehearse, device=device,
        chips=cell["chips"], log=log, trace=None, driver=None,
        peaks=None if rehearse else devices.peaks(device["kind"]))
    if trace:
        run.trace = TraceSlice(os.path.join(
            HERE, "out", "trace", f"{cell['name']}-{os.getpid()}"))

    @contextlib.contextmanager
    def annotate(name):
        with jax.profiler.TraceAnnotation("bench:" + name):
            yield

    run.annotate = annotate
    return run


def execute(run, t_start=None):
    """Set-up, window, release, comparison. Returns the result's parts."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    driver = run.driver = loader.load_module("drivers",
                                             run.traffic["driver"])
    clock = CompileClock()
    state = driver.setup(run)
    setup_s = time.perf_counter() - t_start
    log(f"set-up: {setup_s:.3f} s, {clock.count} compiles "
        f"{clock.seconds:.1f} s")
    mark = clock.mark()
    if run.trace is not None:
        run.trace.unattributed = getattr(driver, "UNATTRIBUTED",
                                         "unattributed")
    meas = driver.window(run, state)
    _, compiles = clock.since(mark)
    meas["end_to_end"]["setup_s"] = setup_s
    memory_peak = devices.memory_peak_bytes(run.chips)
    driver.release(state)
    jax.clear_caches()
    compared = Compared()
    if compiles:
        compared.fail("compiles_in_window",
                      f"{compiles} programs compiled inside the window")
    t0 = time.perf_counter()
    try:
        driver.check(run, state, compared)
    except Exception as exc:  # a check that cannot finish is not correct
        import traceback

        traceback.print_exc()
        compared.fail("comparison", repr(exc))
    log(f"comparison: {time.perf_counter() - t0:.1f} s")
    return meas, state, memory_peak, compared


def per_layer(run, meas, state, memory_peak, reduction):
    ctx = types.SimpleNamespace(
        run=run, meas=meas, facts=meas.get("facts", {}), state=state,
        memory_peak_bytes=memory_peak, trace=reduction, peaks=run.peaks,
        ops=None)
    try:
        ctx.ops = loader.load_module("ops", run.cell["config"])
    except FileNotFoundError:
        pass
    out = {}
    for m in loader.metrics_for(run.bench, "per_layer", run.cell):
        value = loader.load_module("metrics", m["name"]).read(ctx)
        if value is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def start_program(cell, rehearse):
    """The device stamp, after the refusals: no program in this checkout
    (``None``), no TPU chips for the cell (``SystemExit(2)``). Turns the
    compile cache on for every program of the run, the small ones too."""
    try:
        import deeplearning4j_tpu
    except ImportError:
        log("benchmark: the program (deeplearning4j_tpu) is not in this "
            "checkout; nothing to measure")
        return None
    import jax

    device = devices.stamp() if rehearse \
        else devices.require_chips(cell["chips"])
    log(f"device: {device}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"compile cache: {deeplearning4j_tpu.enable_compile_cache()}")
    return device


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, args.workload)
    device = start_program(cell, args.rehearse)
    if device is None:
        return 3

    run = make_run(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), args.rehearse, device)
    meas, state, memory_peak, compared = execute(run, T_START)

    names = {m["name"]: m for m in
             loader.metrics_for(bench, "end_to_end", cell)}
    end_to_end = {n: {"value": float(v), "unit": names[n]["unit"]}
                  for n, v in meas["end_to_end"].items() if n in names}
    missing = set(names) - set(end_to_end)
    if missing:
        compared.fail("metrics", f"the driver gave no {sorted(missing)}")
    out_device = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    metrics = end_to_end
    if args.trace and args.rehearse:
        # a CPU's trace has no device plane: nothing to reduce
        run.trace.discard()
    elif args.trace:
        reduction = run.trace.reduce(run.chips)
        out_device["busy_s"] = reduction["busy_s"]
        out_device["window_s"] = reduction["window_s"]
        breakdown = {"device_ops": reduction["device_ops"],
                     "idle_gaps": reduction["idle_gaps"]}
        metrics = per_layer(run, meas, state, memory_peak, reduction)
        log("end to end (traced run, not reported): "
            + json.dumps(end_to_end))
    line = result_line(correct=compared.correct, attempted=meas["attempted"],
                       failed=meas["failed"], metrics=metrics,
                       device=out_device, breakdown=breakdown,
                       compared=compared.as_dict())
    compared.print_stderr()
    if args.rehearse:
        # a rehearsal ends on its own summary, never on the result line
        print("rehearsal: " + line, flush=True)
        return 0
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
