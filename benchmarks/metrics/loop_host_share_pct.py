"""Share of the server loop thread's serving seconds that it spent on the
host and not blocked on the device: 100 x the seconds of every phase of
``generation_loop_seconds_total{phase}`` but ``decode_fetch``,
``prefill_fetch`` (blocked in the one fetch of a dispatch), ``idle_wait``
(nothing to do) and ``compile`` (a program's first call: set-up), over the
seconds of every phase but ``idle_wait`` and ``compile``. The loop is one thread and dispatches are fetched before
the next is built, so while the loop is not in a fetch the device has
nothing queued: this share should lie within a few points of
``device_idle_pct.serve`` of the same run, and the phases split it by
name. Read from the program's process-wide registry, because the server
and its own registry are gone when the readers run: so it covers warm-up,
window and drain together. Returns nothing where the program publishes no
such counter (a program whose loop has no named phases)."""

BLOCKED_ON_DEVICE = ("phase=decode_fetch", "phase=prefill_fetch")
NOT_SERVING = ("phase=idle_wait", "phase=compile")


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    seconds = global_registry().snapshot().get(
        "generation_loop_seconds_total")
    if not isinstance(seconds, dict):
        return None
    working = sum(v for k, v in seconds.items() if k not in NOT_SERVING)
    if not working:
        return None
    blocked = sum(seconds.get(k, 0.0) for k in BLOCKED_ON_DEVICE)
    return 100.0 * (working - blocked) / working
