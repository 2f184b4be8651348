"""Peak bytes in use on the fullest chip after the window, from
``memory_stats()``, over the chip's published HBM capacity."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return 100.0 * ctx.memory_peak_bytes / ctx.peaks["hbm_bytes"]
