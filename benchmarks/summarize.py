#!/usr/bin/env python3
"""Medians and spreads of the runs that ``prove.sh`` left in a directory:
for each set and each end-to-end metric the median and the spread (distance
between the quartiles of ``statistics.quantiles(values, n=4)`` as a share
of the median), the wider spread over the sets, and five times it."""

import glob
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmarks.harness.clock import spread  # noqa: E402


def main(directory, cell):
    sets = {}
    wrong = []
    for path in sorted(glob.glob(os.path.join(directory, cell + ".*.json"))):
        tag = os.path.basename(path)[len(cell) + 1:].split(".")[0]
        try:
            with open(path) as f:
                line = json.loads(f.read())
        except ValueError:
            wrong.append(path + " (no result line)")
            continue
        if not line.get("correct"):
            wrong.append(path)
        for name, m in line["metrics"].items():
            sets.setdefault(tag, {}).setdefault(name, []).append(m["value"])
    widest = {}
    for tag, metrics in sorted(sets.items()):
        for name, values in sorted(metrics.items()):
            sp = spread(values) if len(values) >= 2 else float("nan")
            print(f"{cell} {tag} {name}: n={len(values)} median "
                  f"{statistics.median(values)!r} spread {sp:.5f} "
                  f"values {values}")
            if tag.startswith("set") and sp == sp:
                widest[name] = max(widest.get(name, 0.0), sp)
    for name, sp in sorted(widest.items()):
        print(f"{cell} widest spread of {name}: {sp:.5f}; five times: "
              f"{5 * sp:.4f}")
    print(f"{cell} runs not correct: {wrong if wrong else 'none'}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
