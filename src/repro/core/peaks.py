"""Per-chip peaks, keyed by ``jax.Device.device_kind`` — the one table.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s and
1,600 Gbit/s of chip-to-chip interconnect per chip (four ICI links).
VMEM: 128 MiB per TensorCore (JAX Pallas TPU documentation).

The planner plans for one named target (``PLAN_TARGET``); measurement code
looks up the kind the running device reports.  A kind that is not in the
table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float              # FLOP/s, MXU
    int8_ops: float                # OP/s, MXU
    hbm_bytes: int
    hbm_bytes_per_s: float
    ici_bytes_per_s: float         # all links of one chip, one direction
    ici_links: int
    vmem_bytes: int                # per TensorCore

    @property
    def ici_bytes_per_s_per_link(self) -> float:
        return self.ici_bytes_per_s / self.ici_links


PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, int8_ops=393e12,
                             hbm_bytes=16 * 2**30, hbm_bytes_per_s=819e9,
                             ici_bytes_per_s=1600e9 / 8, ici_links=4,
                             vmem_bytes=128 * 2**20),
}

# the device kind the analytical planner (core.cost) ranks schedules for
PLAN_TARGET = "TPU v5 lite"


def peaks(device_kind: str) -> ChipPeaks:
    """The table row for ``device_kind``; raises ``KeyError`` for a kind
    the table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table lists {sorted(PEAKS)}") from None


# scoped VMEM a Pallas kernel asks the compiler for: half the target's
# VMEM, leaving the rest to the compiler's own scratch.  The planner's
# footprint filter (core.cost.conv_vmem_bytes) uses the same number.
VMEM_BUDGET = peaks(PLAN_TARGET).vmem_bytes // 2
