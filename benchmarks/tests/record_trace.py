#!/usr/bin/env python3
"""Records the small device trace kept beside the tests.

Run once on the chip (``python benchmarks/tests/record_trace.py``): a jitted
scan of matmuls beside a small Pallas kernel, traced for a fraction of a
second with host gaps between dispatches, so the fixture holds a ``while``
that nests ops, a ``tpu_custom_call`` and idle gaps. Writes
``chiprun_out/fixture/`` with the raw ``.xplane.pb`` and a listing of its
planes and lines; ``benchmarks/tests/data/small_trace.xplane.pb`` is a copy.
"""

import glob
import json
import os
import shutil
import sys
import time


def main():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2

    def add_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] + y_ref[...]

    @jax.jit
    def step(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        y, _ = jax.lax.scan(body, x, None, length=3)
        z = pl.pallas_call(add_kernel,
                           out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
                           name="fixture_add")(y, x)
        return z

    x = jnp.ones((512, 512), jnp.float32)
    w = jnp.ones((512, 512), jnp.float32) * 1e-3
    step(x, w).block_until_ready()
    out = "chiprun_out/fixture"
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    jax.profiler.start_trace(out)
    t0 = time.perf_counter()
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench:dispatch"):
            step(x, w).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:host_gap"):
            time.sleep(0.005)
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()
    path = glob.glob(out + "/plugins/profile/*/*.xplane.pb")[0]
    shutil.copy(path, out + "/small_trace.xplane.pb")
    shutil.rmtree(out + "/plugins")
    pd = jax.profiler.ProfileData.from_file(out + "/small_trace.xplane.pb")
    listing = {"wall_s": wall, "planes": []}
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "events": len(evs),
                          "first": [[e.name, e.start_ns, e.duration_ns]
                                    for e in evs[:12]]})
        listing["planes"].append({"name": plane.name, "lines": lines})
    with open(out + "/listing.json", "w") as f:
        json.dump(listing, f, indent=1)
    print(json.dumps({"bytes": os.path.getsize(
        out + "/small_trace.xplane.pb"), "wall_s": wall}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
