"""Per-chip hardware peak rates (jax-free; importable by report tools).

The single source of truth for device peaks, shared by the dispatch
registry's cost model (``repro.core.registry``) and the offline roofline
report (``repro.launch.roofline``).  Peaks are keyed by the
``device_kind`` JAX reports for a device; a device that is not in the
table is an error, never a default.  Deliberately dependency-free so
log-parsing scripts don't pay a JAX import to read four constants.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

__all__ = ["Hardware", "DEVICES", "PLANNING_KIND", "TPU_V5E", "peaks",
           "planning_peaks"]


@dataclasses.dataclass(frozen=True)
class Hardware:
    """Per-chip peak rates used by the cost model and the roofline."""
    name: str
    mxu_flops: float   # dense-matmul peak FLOP/s
    vpu_flops: float   # elementwise/VPU peak FLOP/s
    hbm_bw: float      # main-memory bandwidth B/s
    link_bw: float     # interconnect B/s per link


TPU_V5E = "TPU v5 lite"

DEVICES: Dict[str, Hardware] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM.  vpu_flops is an estimate that no chip run has measured yet,
    # and link_bw has not been checked against the published
    # 1,600 Gbit/s chip-to-chip interconnect.
    TPU_V5E: Hardware("tpu-v5e", mxu_flops=197e12, vpu_flops=4e12,
                      hbm_bw=819e9, link_bw=50e9),
    "NVIDIA A100-SXM4-40GB": Hardware("gpu-a100", mxu_flops=312e12,
                                      vpu_flops=19.5e12, hbm_bw=2.0e12,
                                      link_bw=300e9),
    # nominal host rates: CPU runs show correctness and counts, not speed
    "cpu": Hardware("cpu-host", mxu_flops=1.5e12, vpu_flops=0.4e12,
                    hbm_bw=100e9, link_bw=25e9),
}

# The device a plan for ``platform`` assumes when that platform is not
# the one attached (plans made ahead of time, cost-model tests).  A
# plan for the attached platform reads the attached device's kind.
PLANNING_KIND: Dict[str, str] = {
    "tpu": TPU_V5E,
    "gpu": "NVIDIA A100-SXM4-40GB",
    "cpu": "cpu",
}


def peaks(device_kind: str) -> Hardware:
    """Peak rates of one ``device_kind``; raises for an unknown device."""
    try:
        return DEVICES[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}; add its "
            f"published peaks to repro.hw.DEVICES") from None


def planning_peaks(platform: str) -> Hardware:
    """Peaks a plan for a platform that is not attached assumes."""
    try:
        return peaks(PLANNING_KIND[platform])
    except KeyError:
        raise ValueError(
            f"unknown platform {platform!r}; one of "
            f"{sorted(PLANNING_KIND)}") from None
