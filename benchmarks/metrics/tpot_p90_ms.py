"""90th percentile, over every request completed in the window with at least
two tokens, of (done stamp - first-token stamp) / (tokens - 1): a time per
output token per request, not the gap between tokens. Two sets of six runs
spread by 9% (my chip runs, PR 25), too wide for a bound, so it stands here
and not among the end-to-end metrics."""


def read(ctx):
    return ctx.facts.get("end_to_end", {}).get("tpot_p90_ms")
