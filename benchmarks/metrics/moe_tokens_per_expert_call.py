"""Rows of the grouped product an expert gets when it is called while
decoding: (token, expert) pairs that decode dispatches routed to held
experts over held experts that got at least one token, summed over layers
and micro-steps. Read from the program's process-wide registry
(``generation_moe_assignments_total{held="yes",program="decode"}`` over
``generation_moe_expert_calls_total{program="decode"}``), because the
server and its own registry are gone when the readers run: so it covers
the decode dispatches of warm-up, window and drain together. The window's
slots are nearly all busy (``decode_slot_occupancy_pct``) and a free slot
is not routed, so what differs outside the window is the warm-up's lone
request (a row an expert) and the drain's emptying slots. Prefill rounds,
which route whole chunks, are counted under ``program="prefill"`` and left
out here. Returns nothing where the program publishes no such counters."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    snap = global_registry().snapshot()
    pairs = snap.get("generation_moe_assignments_total")
    calls = snap.get("generation_moe_expert_calls_total")
    if not isinstance(pairs, dict) or not isinstance(calls, dict):
        return None
    held = pairs.get("held=yes|program=decode")
    called = calls.get("program=decode")
    if not held or not called:
        return None
    return float(held) / float(called)
