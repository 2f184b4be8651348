"""90th percentile, over every request submitted in the window, of
``future._t_first`` minus the submit stamp (requests in flight at the close
are drained after it). Over some eighty requests the 90th percentile is
set by the eighth largest and jumps between clusters of prefill rounds: two
sets of six runs spread by 21-25% (my chip runs, PR 25), too wide for a
bound, so it stands here and not among the end-to-end metrics."""


def read(ctx):
    return ctx.facts.get("end_to_end", {}).get("ttft_p90_ms")
