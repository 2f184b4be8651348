"""The paged read kernel's share of its roofline over the traced slice:
the least time the chip could take, max(operations / peak FLOP/s, bytes /
peak bytes/s) for the live keys and values that the tokens prefilled and
decoded inside the slice had to read (``ops/<config>.py``, from the
benchmark's own request records), over the summed device time of the
kernel's events: the Mosaic custom calls of the trace, the only ones a
serving program holds (the program names none of them yet). Returns
nothing when the trace holds no such call."""


def read(ctx):
    f, t = ctx.facts, ctx.trace
    if not t or not t.get("mosaic_s") or not f.get("slice") \
            or ctx.ops is None:
        return None
    a, b = f["slice"]
    spans = ctx.run.driver.work_spans(f["records"], a, b, f["prefill_chunk"])
    need = ctx.ops.paged_read(ctx.state["sizes"], spans)
    by_ops = need["ops"] / ctx.peaks["bf16_flop_s"]
    by_bytes = need["bytes"] / ctx.peaks["hbm_bytes_s"]
    least = max(by_ops, by_bytes)
    if least <= 0:
        return None
    ctx.run.log(f"paged read roofline: bound by "
                f"{'bytes' if by_bytes >= by_ops else 'operations'} "
                f"(least {least:.6f} s: ops {by_ops:.6f}, bytes "
                f"{by_bytes:.6f}); kernel {t['mosaic_s']:.6f} s in "
                f"{t['mosaic_calls']} calls on {t['chips']} chip(s)")
    return 100.0 * least / t["mosaic_s"]
