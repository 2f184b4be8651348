"""Share of the rows that prefill dispatches computed which served a
request: 100 x ``generation_prefill_rows_total{kind="admitted"}`` over
``{kind="computed"}``. A dispatch computes a row group of a fixed width
for its column bucket; the rows a round did not fill are padding. Read
from the program's process-wide registry, because the server and its own
registry are gone when the readers run: so it covers the dispatches of
warm-up (one request at a time), window and drain together. Returns
nothing where the program publishes no such counter (a program whose
prefill dispatch computes every slot)."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    rows = global_registry().snapshot().get("generation_prefill_rows_total")
    if not isinstance(rows, dict):
        return None
    admitted, computed = rows.get("kind=admitted"), rows.get("kind=computed")
    if not admitted or not computed:
        return None
    return 100.0 * float(admitted) / float(computed)
