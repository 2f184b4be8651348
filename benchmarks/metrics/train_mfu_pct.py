"""The whole training step's share of the chip's bf16 peak: operations the
algorithm needs per sample (``ops/<config>.py``: forward once, backward
twice, nothing recomputed) times samples per second, over the peak."""


def read(ctx):
    rate = ctx.facts.get("samples_per_s")
    if rate is None or ctx.ops is None:
        return None
    sizes = ctx.state["config"]["sizes"]
    flops = ctx.ops.train_flops_per_sample(sizes)
    return 100.0 * flops * rate / (ctx.run.chips * ctx.peaks["bf16_flop_s"])
