"""Host milliseconds a decode dispatch costs the server's loop thread
beside the wait for the device: 1e3 x the seconds of the phases
``decode_reserve`` (program lookup, page reservation, the active mask),
``decode_dispatch`` (the jitted call returning: argument copies and the
enqueue) and ``decode_walk`` (the layers' counts, the Python walk over
slots and tokens, retiring: page release and the futures' callbacks) of
``generation_loop_seconds_total{phase}``, over
``generation_loop_spans_total{phase="decode_dispatch"}``. ``decode_fetch``
is left out: that is the device's time. This is what overlapping the
fetch with the next dispatch could hide behind the device, less the part
that must stay ahead of a dispatch. A program's first call (tracing,
compiling or loading) is booked under the phase ``compile`` and is in none
of these. Read from the program's process-wide registry after the server
is gone: warm-up, window and drain together. Returns nothing where the
program publishes no such counter."""

HOST_PHASES = ("phase=decode_reserve", "phase=decode_dispatch",
               "phase=decode_walk")


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    snap = global_registry().snapshot()
    seconds = snap.get("generation_loop_seconds_total")
    spans = snap.get("generation_loop_spans_total")
    if not isinstance(seconds, dict) or not isinstance(spans, dict):
        return None
    dispatches = spans.get("phase=decode_dispatch")
    if not dispatches:
        return None
    return 1e3 * sum(seconds.get(k, 0.0) for k in HOST_PHASES) / dispatches
