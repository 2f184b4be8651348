"""The server's own busy seconds (``generation_busy_seconds_total``, read
as a delta of ``stats()``: tokens over tokens per busy second) over the
window's seconds. A host-clock share, not a device time; the program adds
a wave's seconds once per slot it admits, so a wide wave can count more
than its wall time (PERF.md, Open questions)."""


def read(ctx):
    f = ctx.facts
    if not f.get("window_s") or f.get("busy_s") is None:
        return None
    return 100.0 * f["busy_s"] / f["window_s"]
