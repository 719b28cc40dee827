"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  (A copy of ``repro.roofline.analysis.PEAKS``, kept here so
that no change to the program can move the yardstick.)  A device that is
not in the table is an error, not a default.
"""
from __future__ import annotations

from typing import Dict

__all__ = ["PEAKS", "peaks"]

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; add them with their source") from None
