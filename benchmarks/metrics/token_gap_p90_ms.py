"""90th percentile of ``generation_token_gap_ms``: for every request a
decode dispatch delivered tokens to, the time since that request's last
delivery (the first delivery is its first token). A delivery is the up to
``steps_per_dispatch`` tokens one fetch hands a request, so this is what
a streaming client waits between bursts: about ``steps_per_dispatch`` x
``tpot_p90_ms`` where decode is undisturbed, and more where a prefill
wave shares the tick (``tpot_p90_ms`` is a mean over a request's tokens
and cannot show that). The quantile is the registry's own (nearest rank
over a reservoir of 1,024 observations). Read from the program's
process-wide registry after the server is gone: warm-up, window and drain
together. Returns nothing where the program publishes no such
histogram."""


def read(ctx):
    try:
        from deeplearning4j_tpu.metrics.registry import global_registry
    except ImportError:
        return None
    hist = global_registry().snapshot().get("generation_token_gap_ms")
    if not isinstance(hist, dict) or not hist.get("count"):
        return None
    return hist["quantiles"][0.9]
