"""Share of the token rows the serving programs embedded by a gather of
the table's rows: 100 x ``generation_embed_rows_total{path="gather"}`` over
``gather`` and ``one_hot`` together. Where the net's token input feeds one
plain dense layer over the vocabulary (every zoo LM), a program reads that
layer's output as rows of its table; any other net multiplies a one-hot
block into the whole table. The loop books each dispatch's rows (decode:
rows x micro-steps; prefill: row width x column bucket) under its program's
path. Read from the program's process-wide registry, because the server
and its own registry are gone when the readers run: so it covers warm-up,
window and drain together. Returns nothing where the program publishes no
such counter."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    rows = global_registry().snapshot().get("generation_embed_rows_total")
    if not isinstance(rows, dict):
        return None
    gather = rows.get("path=gather", 0.0)
    total = gather + rows.get("path=one_hot", 0.0)
    if not total:
        return None
    return 100.0 * float(gather) / float(total)
