"""The whole serving step's share of the chip's bf16 peak: operations the
algorithm needs (``ops/<config>.py``) for the prompts prefilled and the
tokens decoded inside the window, from the benchmark's own request
records, over window seconds x peak."""


def read(ctx):
    f = ctx.facts
    if ctx.ops is None or not f.get("records") or not f.get("window_s"):
        return None
    driver = ctx.run.driver
    spans = driver.work_spans(f["records"], f["t_open"], f["t_close"],
                              f["prefill_chunk"])
    flops = ctx.ops.requests_flops(ctx.state["sizes"],
                                   [(a, n) for a, n, _ in spans])
    if flops <= 0:
        return None
    return 100.0 * flops / (f["window_s"] * ctx.run.chips
                            * ctx.peaks["bf16_flop_s"])
