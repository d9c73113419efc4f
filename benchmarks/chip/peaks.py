"""Published peaks of each accelerator, keyed by the ``device_kind`` JAX
reports.  A kind that is not here is an error, never a default.

TPU v5e (``TPU v5 lite``): 197 TFLOP/s bf16, 819 GB/s of HBM bandwidth,
16 GB of HBM — Google Cloud documentation, "TPU v5e".
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
