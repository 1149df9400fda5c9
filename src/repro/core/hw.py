"""Target-hardware constants (TPU v5e) used by the estimator, the
scheduler's TTFT projections (Algorithm 2), and the roofline analysis.

The paper's testbed is A100-80GB + NVLink; we adapt to TPU v5e per the
assignment.  All absolute latencies therefore differ from the paper —
the *relative* claims (C1–C7 in DESIGN.md) are what EXPERIMENTS.md
validates, with SLOs derived from profiled base latencies.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu-v5e"
    peak_flops: float = 197e12          # bf16 FLOP/s per chip
    hbm_bw: float = 819e9               # bytes/s per chip
    hbm_bytes: int = 16 * 1024 ** 3     # 16 GiB per chip
    ici_bw: float = 50e9                # bytes/s per ICI link
    ici_links: int = 4                  # links per chip (2D torus)
    dcn_bw: float = 25e9                # bytes/s cross-pod per host

    # achievable-efficiency derates (MFU-style), calibrated once:
    prefill_mfu: float = 0.55           # large-matmul bound
    decode_membw_eff: float = 0.75      # streaming weight/KV reads
    iteration_overhead_s: float = 2.0e-3  # launch/schedule per iteration


V5E = HardwareSpec()

#: the peaks this repo assumes, keyed by ``jax.Device.device_kind``
#: (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
#: at 819 GB/s).  JAX reports a v5e as "TPU v5 lite".
PEAKS_BY_KIND = {"TPU v5 lite": V5E}


def hardware_for(device_kind: str) -> HardwareSpec:
    """Peaks for a device kind; a kind not in the table is an error,
    never a silent v5e default."""
    try:
        return PEAKS_BY_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS_BY_KIND)}") from None


@dataclasses.dataclass(frozen=True)
class InstanceSpec:
    """One serving instance = a TP group of ``tp`` chips."""
    hw: HardwareSpec = V5E
    tp: int = 4

    @property
    def flops(self) -> float:
        return self.hw.peak_flops * self.tp

    @property
    def hbm_bw(self) -> float:
        return self.hw.hbm_bw * self.tp

    @property
    def hbm_bytes(self) -> int:
        return self.hw.hbm_bytes * self.tp

    @property
    def interconnect_bw(self) -> float:
        """Effective point-to-point bandwidth for KV migration between
        instances (ICI within a pod)."""
        return self.hw.ici_bw
