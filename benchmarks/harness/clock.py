"""Host clocks: compile counting, the percentile, the spread."""

from __future__ import annotations

import math


class CompileClock:
    """Seconds and count of XLA backend compiles (persistent-cache
    retrievals included), read off jax's own monitoring events. (After
    ``chip_smoke.py``'s class of the same name.)"""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self):
        return self.seconds, self.count

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between the
    closest ranks, as ``numpy.percentile`` does by default."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def rate(count: float, t_open: float, t_close: float) -> float:
    """Work over all the time of the window."""
    if t_close <= t_open:
        raise ValueError("a window has to close after it opens")
    return count / (t_close - t_open)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(values, n=4)``: the contract's."""
    import statistics

    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
