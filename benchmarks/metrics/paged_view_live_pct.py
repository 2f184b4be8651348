"""Share of the keys a decode dispatch's read backend fetched that some
request owned: 100 x ``generation_kv_live_tokens_total{program="decode"}``
(the context length of each advancing row, summed over rows, micro-steps
and paged layers: what a paged read has to fetch) over
``generation_kv_viewed_tokens_total{program="decode"}`` (what the backend
fetched for them: rows x capacity a micro-step where the pool is gathered
into a dense view of every slot's whole capacity, the live keys themselves
where pages are read in place). Both are reckoned by the server's loop from
the positions it holds. Read from the program's process-wide registry,
because the server and its own registry are gone when the readers run: so
it covers the decode dispatches of warm-up, window and drain together.
Returns nothing where the program publishes no such counters."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    snap = global_registry().snapshot()
    live = snap.get("generation_kv_live_tokens_total")
    viewed = snap.get("generation_kv_viewed_tokens_total")
    if not isinstance(live, dict) or not isinstance(viewed, dict):
        return None
    live, viewed = live.get("program=decode"), viewed.get("program=decode")
    if not live or not viewed:
        return None
    return 100.0 * float(live) / float(viewed)
