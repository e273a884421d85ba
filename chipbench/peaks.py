"""Peak rates of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect per chip.
"""

from __future__ import annotations

from typing import Dict

__all__ = ["PEAKS", "lookup"]

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9, "ici_bytes_per_s": 1600e9 / 8}

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": _V5E,     # what JAX names a v5e chip
    "TPU v5e": _V5E,
}


def lookup(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
