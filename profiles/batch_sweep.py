"""Round-5 batch sweep: ResNet50 + VGG16 bf16 throughput vs batch size.

VERDICT r4 weak #2/#3: batch 64 (ResNet50) and batch 32 (VGG16) were never
swept upward; the unclaimed MFU lives there. A naive A-then-B sweep on a
shared machine measures the machine's state, not batch effects. This sweep
INTERLEAVES: each round measures every config once, and configs are compared
within-round (plus median across rounds).

Usage: python profiles/batch_sweep.py [rounds]   (on the chip; one process)
Results land in profiles/chip_session_results.json under "batch_sweep_r5"
(a run-time output, not a tracked record: the round-5 file was deleted at
PR 21 with the stack it was measured on).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# round-5 corrected audit (profiles/flop_audit.py): XLA-counted train-step
# flops at multiply+add, the same convention as the peak figure
RESNET_FLOP_PER_IMG = 6.6e9
VGG16_FLOP_PER_IMG = 89.35e9
PEAK_BF16_FLOPS = 197e12       # v5e


def _prepare(model_cls, batch, seed):
    """One bench-identical timer per config (the sweep must measure with
    the SAME methodology the bench reports, or sweep-picked defaults and
    bench numbers drift apart)."""
    import bench

    timer = bench._imagenet_model_timer(
        model_cls, batch=batch, steps=10, seed=seed,
        compute_dtype="bfloat16")
    return timer.window


def main(rounds=3):
    from deeplearning4j_tpu.models import VGG16, ResNet50

    configs = []
    for b in (64, 128, 256):
        configs.append((f"resnet50_b{b}", ResNet50, b, RESNET_FLOP_PER_IMG))
    for b in (32, 64, 128, 192):
        configs.append((f"vgg16_b{b}", VGG16, b, VGG16_FLOP_PER_IMG))

    samplers = {}
    for name, cls, b, _ in configs:
        try:
            t0 = time.time()
            samplers[name] = _prepare(cls, b, seed=b)
            print(f"# prepared {name} ({time.time() - t0:.0f}s)", flush=True)
        except Exception as e:  # noqa: BLE001 — OOM at big batch is data
            print(f"# {name} PREP FAILED: {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)

    results = {name: [] for name in samplers}
    for r in range(rounds):
        for name, s in samplers.items():
            try:
                v = s()
                if v is not None:
                    results[name].append(round(v))
                print(f"# round {r} {name}: {v and round(v)} img/s",
                      flush=True)
            except Exception as e:  # noqa: BLE001
                print(f"# round {r} {name} FAILED: {e}", flush=True)

    summary = {}
    for name, _, b, flop in configs:
        if results.get(name):
            med = float(np.median(results[name]))
            summary[name] = {
                "windows_img_s": results[name],
                "median_img_s": round(med),
                "mfu_pct": round(100 * med * flop / PEAK_BF16_FLOPS, 1),
            }
    print(json.dumps(summary, indent=1), flush=True)

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chip_session_results.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as fh:
            merged = json.load(fh)
    merged["batch_sweep_r5"] = summary
    with open(path, "w") as fh:
        json.dump(merged, fh, indent=1)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 3)
