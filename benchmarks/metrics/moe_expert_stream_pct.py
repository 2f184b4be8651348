"""Share of the chip's memory bandwidth over the window that decoding's
touched routed experts have to read: expert calls a decode dispatch
(``generation_moe_expert_calls_total{program="decode"}``, held experts
that got at least one row, summed over layers and micro-steps, over
``generation_decode_steps_total``) x the window's decode dispatches
(``stats()`` deltas in ``facts``) x one held expert's bytes as the program
stores them (gauge ``generation_moe_expert_bytes``), over window seconds x
peak bytes/s. A floor beside ``serve_hbm_stream_pct``'s, which counts the
weights every micro-step reads and leaves the routed experts out: an
expert called is read at least once, so neither share nor their sum can
pass 100. The counters are read from the program's process-wide registry,
because the server and its own registry are gone when the readers run:
the calls a dispatch are those of warm-up, window and drain together,
where the warm-up's lone requests and the drain's emptying slots call
fewer experts than a full window does. Returns nothing where the program
publishes no such gauge or counters."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    snap = global_registry().snapshot()
    calls = snap.get("generation_moe_expert_calls_total")
    dispatches = snap.get("generation_decode_steps_total")
    expert_bytes = snap.get("generation_moe_expert_bytes")
    if not isinstance(calls, dict) or not dispatches or not expert_bytes:
        return None
    called = calls.get("program=decode")
    f = ctx.facts
    if not called or not f.get("decode_dispatches") \
            or not f.get("window_s") or not ctx.peaks:
        return None
    nbytes = float(called) / float(dispatches) * f["decode_dispatches"] \
        * float(expert_bytes)
    return 100.0 * nbytes / (f["window_s"] * ctx.run.chips
                             * ctx.peaks["hbm_bytes_s"])
