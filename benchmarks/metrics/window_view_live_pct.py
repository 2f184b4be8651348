"""Share of the keys a decode dispatch fetched for the window layers that a
query could see: 100 x
``generation_cache_kv_live_tokens_total{cache="window",program="decode"}``
(``min(context, window)`` of each advancing row, summed over rows,
micro-steps and window layers) over
``generation_cache_kv_viewed_tokens_total{cache="window",program="decode"}``
(rows x the view's width a micro-step: the window layers' dense view is
``window + steps_per_dispatch + page_size`` tokens wide at most, from each
row's first live page, whatever the table's capacity). Both are reckoned
by the server's loop from the positions it holds. Read from the program's
process-wide registry, because the server and its own registry are gone
when the readers run: so it covers the decode dispatches of warm-up, window
and drain together. Returns nothing where the program publishes no such
counters (no window class)."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    snap = global_registry().snapshot()
    live = snap.get("generation_cache_kv_live_tokens_total")
    viewed = snap.get("generation_cache_kv_viewed_tokens_total")
    if not isinstance(live, dict) or not isinstance(viewed, dict):
        return None
    key = "cache=window|program=decode"
    if not live.get(key) or not viewed.get(key):
        return None
    return 100.0 * float(live[key]) / float(viewed[key])
