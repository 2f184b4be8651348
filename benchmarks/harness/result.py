"""The result line, and the numbers compared beside their limits."""

from __future__ import annotations

import json
import math
import sys


class Compared:
    """Numbers compared, each with the limit it is held to. A number is
    within its limit when ``value <= limit`` (an exact comparison has the
    limit 0). A value that is not finite, or a check that raised, fails."""

    def __init__(self):
        self.rows = []          # (name, value, limit)

    def add(self, name: str, value, limit):
        self.rows.append((name, float(value), float(limit)))

    def take(self, numbers: dict, limits: dict, log):
        """A driver's numbers against the cell's limits: one with a limit
        is compared, one without is logged as observed, a limit whose
        number is missing fails. Names that start with ``_`` are notes
        (where a worst leaf was, how many tokens were compared)."""
        for name, value in numbers.items():
            if name.startswith("_"):
                log(f"observed {name[1:]}: {value}")
            elif name in limits:
                self.add(name, value, limits[name])
            else:
                log(f"observed {name}: {value!r} (no limit: not compared)")
        for name in limits:
            if name not in numbers:
                self.fail(name, "the comparison did not produce it")

    def fail(self, name: str, why: str):
        print(f"compare: {name} gave no number: {why}", file=sys.stderr)
        self.rows.append((name, float("nan"), 0.0))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": (v if math.isfinite(v) else None), "limit": lim}
                for n, v, lim in self.rows}

    def print_stderr(self):
        for n, v, lim in self.rows:
            ok = math.isfinite(v) and v <= lim
            print(f"compared {n}: value {v!r} limit {lim!r} "
                  f"{'ok' if ok else 'NOT WITHIN'}", file=sys.stderr)
        sys.stderr.flush()


def result_line(*, correct, attempted, failed, metrics, device, compared,
                breakdown=None) -> str:
    """One JSON object: the contract's keys, ``compared`` last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return json.dumps(line)
