"""What the page classes hold beside what one block table for every paged
layer would hold for the same slots: 100 x
``generation_kv_resident_bytes_total{layout="classes"}`` (bytes of the pages
in use over all classes: a window class's pages behind the window are
freed) over the same counter's ``layout="uniform"`` (each slot's logical
pages times the bytes a token costs in ALL paged layers), both summed over
decode dispatches by the server's loop, so the share is an average over
them weighted by what was resident. 100 where nothing is ever freed (every
context inside the window); lower is better. Read from the program's
process-wide registry, because the server and its own registry are gone
when the readers run: so it covers the decode dispatches of warm-up, window
and drain together. Returns nothing where the program publishes no such
counter (a net of one page class, or a program without page classes)."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    held = global_registry().snapshot().get(
        "generation_kv_resident_bytes_total")
    if not isinstance(held, dict):
        return None
    classes, uniform = held.get("layout=classes"), held.get("layout=uniform")
    if not classes or not uniform:
        return None
    return 100.0 * float(classes) / float(uniform)
