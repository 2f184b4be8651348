"""The device as jax reports it, the refusal without a chip, and the table
of peaks."""

from __future__ import annotations

import sys

#: Published peaks per chip, keyed by ``device_kind``. Source: Google Cloud
#: documentation, "TPU v5e" system architecture page (197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2e at 819 GB/s). A device that is not in the
#: table is an error, never a default. (Copied from ``bench.py``'s
#: ``PEAK_BF16_FLOP_S``, with the bandwidth and capacity added.)
PEAKS = {
    "TPU v5 lite": {"bf16_flop_s": 197e12, "int8_op_s": 393e12,
                    "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def stamp() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> dict:
    """The stamp, or ``SystemExit(2)`` with nothing on stdout when jax
    found no TPU or fewer chips than the cell asks for."""
    device = stamp()
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"benchmark: the cell needs {chips} TPU chip(s), jax found "
              f"{device}; refusing to run", file=sys.stderr)
        raise SystemExit(2)
    return device


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       "it to benchmarks/harness/device.py with its source")
    return PEAKS[kind]


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
