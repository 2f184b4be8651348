"""Share of the traced slice of the window (its last seconds) in which no
operation ran on the device: 1 - union of device-op intervals / slice."""

from benchmarks.harness.trace import idle_pct


def read(ctx):
    return idle_pct(ctx.trace)
