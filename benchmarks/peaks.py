"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` string jax reports.  A device that is not here is an
error, never a default: a share of an unknown peak means nothing.

Source for "TPU v5 lite": Google Cloud documentation, "TPU v5e"
(197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip).
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 1600e9 / 8,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            f"to benchmarks/peaks.py with its source") from None
