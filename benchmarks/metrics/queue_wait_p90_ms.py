"""90th percentile of ``generation_queue_wait_ms``: from ``submit()`` to
the loop thread taking the request off the queue (a re-queued or resumed
request observes again). It is the first part of a request's TTFT; the
rest is its prefill. In a closed loop of as many clients as slots a
request waits only for the tick in progress, so this reads a dispatch or
two; it is the open-loop cells that it is for. The quantile is the
registry's own (nearest rank over a reservoir of 1,024 observations).
Read from the program's process-wide registry after the server is gone:
warm-up, window and drain together. Returns nothing where the program
publishes no such histogram."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    hist = global_registry().snapshot().get("generation_queue_wait_ms")
    if not isinstance(hist, dict) or not hist.get("count"):
        return None
    return hist["quantiles"][0.9]
