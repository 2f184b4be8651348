"""Share of the chip's memory bandwidth that decoding's unavoidable weight
traffic takes up over the window: decode micro-steps of the window
(``decode_dispatches x steps_per_dispatch``, deltas of ``stats()``) times
``ops.decode_step_min_bytes(sizes)`` — the weights every micro-step has to
read whatever the routing: the layers' mixers, routers and shared experts,
and the head — over window seconds x peak bytes/s. A floor on the traffic:
the routed experts, the scan state, the KV and the prefill rounds are left
out, so the share can never pass 100; what is missing to 100 is idle
time, prefill time and traffic the floor does not count. Returns nothing
where the configuration's ``ops`` file has no such floor."""


def read(ctx):
    f = ctx.facts
    floor = getattr(ctx.ops, "decode_step_min_bytes", None)
    steps = f.get("decode_dispatches", 0) * f.get("steps_per_dispatch", 0)
    if floor is None or not steps or not f.get("window_s") \
            or not ctx.peaks:
        return None
    nbytes = steps * floor(ctx.state["sizes"])
    return 100.0 * nbytes / (f["window_s"] * ctx.run.chips
                             * ctx.peaks["hbm_bytes_s"])
