#!/usr/bin/env python3
"""Readings that the comparison's limits are set from, many seeds in one
process and with no measured window where the driver needs none.

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 \\
        --kinds program,control,fault_half_batch [--seconds 8]

For each seed the cell's driver gives, against the plain reference, the
numbers of each kind asked: ``program`` is a sound run of the timed path
(the lower reading is the largest over a dozen seeds), ``control`` is the
reference in the nearest precision below the configuration's, put in the
program's place (the upper reading is the smallest), and the faults are
the driver's. One JSON line per seed and kind, on stdout and appended to
``<out>/readings-<cell>.jsonl``. A benchmark run never runs this.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default="program,control")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--out", default="chiprun_out/readings")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import run as runner
    from benchmarks.harness import loader

    bench = loader.load_benchmark()
    cell = loader.find_cell(bench, args.workload)
    device = runner.start_program(cell, args.rehearse)
    if device is None:
        return 3
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"readings-{cell['name']}.jsonl")
    kinds = [k for k in args.kinds.split(",") if k]
    for seed in (int(s) for s in args.seeds.split(",") if s):
        run = runner.make_run(bench, cell["name"], seed, args.seconds,
                              False, args.rehearse, device)
        driver = loader.load_module("drivers", run.traffic["driver"])
        for kind, nums in driver.readings(run, kinds).items():
            line = json.dumps({"cell": cell["name"], "seed": seed,
                               "kind": kind, "device": device["kind"],
                               "numbers": nums})
            print(line, flush=True)
            with open(path, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
