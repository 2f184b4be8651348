"""Host milliseconds a prefill dispatch costs the server's loop thread
beside the wait for the device: 1e3 x the seconds of the phases ``admit``
(taking requests off the queue, matching the prefix cache, staging
pages), ``prefill_keys`` (a device round trip a request for its sampling
key), ``prefill_build`` (the bucket and the eight host arrays),
``prefill_dispatch`` (the jitted call returning) and ``prefill_commit``
(counters, trimming and registering pages, the decode mirrors, the TTFT
stamp) of ``generation_loop_seconds_total{phase}``, over
``generation_loop_spans_total{phase="prefill_dispatch"}``.
``prefill_fetch`` is left out: that is the device's time. Admission and
commit happen once a wave and a wave is several dispatches, so this is a
mean over dispatches, not one dispatch's cost. A program's first call
(tracing, compiling or loading) is booked under the phase ``compile`` and
is in none of these. Read from the program's process-wide registry after
the server is gone: warm-up, window and drain together. Returns nothing
where the program publishes no such counter."""

HOST_PHASES = ("phase=admit", "phase=prefill_keys", "phase=prefill_build",
               "phase=prefill_dispatch", "phase=prefill_commit")


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
    dispatches = spans.get("phase=prefill_dispatch")
    if not dispatches:
        return None
    return 1e3 * sum(seconds.get(k, 0.0) for k in HOST_PHASES) / dispatches
