"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports. A kind that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 819 GB/s of HBM bandwidth per chip.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
    },
}
SOURCE = "Google Cloud documentation, 'TPU v5e' system architecture"


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}. Add the chip to chipbench/peaks.py with its "
            "source.") from None
