"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's ``roofline/analysis.py`` table (not imported,
so a program change cannot move the yardstick).  A kind missing from the
table is an error: a run on it fails rather than reading a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 394 TOP/s
    # int8, 16 GB of HBM at 819 GB/s.
    "TPU v5 lite": {
        "peak_flops": 197e12,          # bf16; the step's share uses it
        "hbm_bw": 819e9,               # bytes/s
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
