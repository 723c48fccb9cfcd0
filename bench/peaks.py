"""Peak rates of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports: the yardstick's own table, so that a share of
a peak cannot move with the program.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM at 819 GB/s.
A kind that is not listed is an error, never a default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
}


def peak(device_kind: str, what: str = "bf16_flops_per_s") -> float:
    """``what`` of one chip of ``device_kind``; raises ``KeyError`` for a
    kind the table does not list."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table lists {sorted(PEAKS)}")
    return PEAKS[device_kind][what]
