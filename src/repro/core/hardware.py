"""Accelerator hardware constants for roofline terms and profile synthesis."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float  # bf16 FLOP/s per chip
    hbm_bandwidth: float  # bytes/s per chip
    link_bandwidth: float  # bytes/s per ICI/NVLink link
    hbm_bytes: int
    mfu_assumption: float = 0.4  # sustained fraction for analytic time estimates


# TPU v5e — the deployment target (constants fixed by the assignment).
V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    hbm_bandwidth=819e9,
    link_bandwidth=50e9,
    hbm_bytes=16 * 1024**3,
)
