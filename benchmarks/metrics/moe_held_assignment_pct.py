"""Share of the (token, expert) pairs that decode dispatches routed which
went to experts this chip holds: 100 x
``generation_moe_assignments_total{held="yes",program="decode"}`` over the
same counter's ``held="yes"`` and ``held="no"`` together. The router keeps
every expert's output, so this is the routing's own split: about
``experts_held / experts`` where routing is even (a half for granite's 36
of 72, a quarter for DeepSeek-V2's 40 of 160, whose kept groups move it
token by token). Read from the program's process-wide registry, because
the server and its own registry are gone when the readers run: so it
covers the decode dispatches of warm-up, window and drain together.
Returns nothing where the program publishes no such counter."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    pairs = global_registry().snapshot().get(
        "generation_moe_assignments_total")
    if not isinstance(pairs, dict):
        return None
    held = pairs.get("held=yes|program=decode")
    absent = pairs.get("held=no|program=decode")
    if held is None or absent is None or not held + absent:
        return None
    return 100.0 * float(held) / float(held + absent)
