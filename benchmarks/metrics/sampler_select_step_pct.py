"""Share of the decode micro-steps whose sampler took its selecting branch:
100 x ``generation_sampler_steps_total{path="select"}`` over ``select`` and
``greedy`` together. ``gen_decode`` picks the branch at run time from the
temperatures it is handed: with one above 0 it finds every row's top-k cut
(``kth_largest``, a selection) and samples; with none it takes the argmax
alone. The loop books the same predicate on the same array, so the share
says how often the selection ran: a load that reads 0 shows nothing of
it. Read from the program's process-wide registry, because the server and
its own registry are gone when the readers run: so it covers warm-up,
window and drain together. Returns nothing where the program publishes no
such counter (a program that sorts the vocabulary)."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    steps = global_registry().snapshot().get(
        "generation_sampler_steps_total")
    if not isinstance(steps, dict):
        return None
    select = steps.get("path=select", 0.0)
    total = select + steps.get("path=greedy", 0.0)
    if not total:
        return None
    return 100.0 * float(select) / float(total)
