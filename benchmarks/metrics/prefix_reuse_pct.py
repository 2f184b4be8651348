"""Share of the prompt tokens admitted that were taken from cached pages:
100 x ``generation_prefix_tokens_reused_total`` over
``generation_prompt_tokens_admitted_total`` (every token of every prompt
staged into a slot, the reused ones among them). Read from the program's
process-wide registry, because the server and its own registry are gone
when the readers run: so it covers warm-up (lone prompts, nothing shared),
the cold wave that fills the slots before the window opens (every client
prefills the document itself: no page is registered until a wave commits),
the window and the drain together. Returns nothing where the program
publishes no such counters there."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    snap = global_registry().snapshot()
    reused = snap.get("generation_prefix_tokens_reused_total")
    admitted = snap.get("generation_prompt_tokens_admitted_total")
    if not isinstance(reused, (int, float)) \
            or not isinstance(admitted, (int, float)) or not admitted:
        return None
    return 100.0 * float(reused) / float(admitted)
