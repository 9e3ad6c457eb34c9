"""Core types: memory-reuse strategies (Table II) and hardware specs."""
from __future__ import annotations

import dataclasses
import enum
from typing import Dict, Optional, Tuple


class Strategy(enum.Enum):
    """Memory reusing strategies (paper Table II).

    Encodes how the overwritten ``T_DI`` (dispatched input) and ``T_M``
    (expert hidden) tensors are restored in the backward pass.
    """
    NONE = "none"   # keep activations on device (no reuse)
    S1 = "s1"       # T_DI: offload      T_M: offload
    S2 = "s2"       # T_DI: re-comm      T_M: offload
    S3 = "s3"       # T_DI: offload      T_M: recompute
    S4 = "s4"       # T_DI: re-comm      T_M: recompute

    @property
    def offloads(self) -> Tuple[str, ...]:
        return {"none": (), "s1": ("t_di", "t_m"), "s2": ("t_m",),
                "s3": ("t_di",), "s4": ()}[self.value]

    @property
    def saves(self) -> Tuple[str, ...]:
        return {"none": ("t_di", "t_m"), "s1": (), "s2": (),
                "s3": (), "s4": ()}[self.value]

    @property
    def needs_host(self) -> bool:
        return bool(self.offloads)


# Q-vectors from Table II: units of (v0_comp, v0_comm, v0_mem) per
# (forward, backward). q3 counts T_M copies as 4x (H = 4M convention);
# the perf model rescales for the actual H/M ratio.
Q_TABLE: Dict[Strategy, Tuple[Tuple[int, int, int], Tuple[int, int, int]]] = {
    Strategy.NONE: ((2, 2, 0), (4, 2, 0)),
    Strategy.S1:   ((2, 2, 5), (4, 2, 5)),
    Strategy.S2:   ((2, 2, 4), (4, 3, 4)),
    Strategy.S3:   ((2, 2, 1), (5, 2, 1)),
    Strategy.S4:   ((2, 2, 0), (5, 3, 0)),
}


@dataclasses.dataclass(frozen=True)
class Interference:
    """Slowdown factors (paper Fig. 3). mu: comm slowdown, eta: memcpy
    slowdown, sigma: compute slowdown (~1 on TPU: DMA-driven collectives)."""
    mu_comp: float = 0.85        # comm speed while compute runs
    mu_all: float = 0.70         # comm speed with compute + memcpy
    eta_all: float = 0.60        # memcpy speed with comm + compute
    eta_comp: float = 0.95       # memcpy speed with compute only
    sigma: float = 1.0


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """TPU v5e defaults (task brief constants)."""
    name: str = "tpu_v5e"
    flops: float = 197e12             # bf16 peak FLOP/s per chip
    hbm_bw: float = 819e9             # B/s
    ici_bw: float = 50e9              # B/s per link
    host_bw: float = 32e9             # PCIe-ish host link B/s (offload)
    hbm_bytes: float = 16e9
    has_host_offload: bool = True
    launch_overhead_s: float = 3e-6   # per fused op / collective issue
    interference: Interference = dataclasses.field(default=Interference())

    def mu(self, strategy: Strategy) -> float:
        i = self.interference
        return i.mu_all if strategy.needs_host else i.mu_comp

    def eta(self, strategy: Strategy) -> float:
        i = self.interference
        # S1/S2 copy while comm is also active -> eta_all
        return i.eta_all if strategy in (Strategy.S1, Strategy.S2) \
            else i.eta_comp


TPU_V5E = HardwareSpec()

# Rough public-datasheet numbers — the perf model only needs ratios of
# compute : interconnect : host bandwidth to rank (n, strategy) choices.
GPU_A100 = HardwareSpec(name="gpu-a100", flops=312e12, hbm_bw=2039e9,
                        ici_bw=300e9, host_bw=32e9, hbm_bytes=80e9,
                        has_host_offload=True)
CPU_HOST = HardwareSpec(name="cpu-host", flops=1e12, hbm_bw=50e9,
                        ici_bw=10e9, host_bw=10e9, hbm_bytes=16e9,
                        has_host_offload=False)

HW_SPECS: Dict[str, HardwareSpec] = {
    "tpu-v5e": TPU_V5E,
    "gpu-a100": GPU_A100,
    "cpu-host": CPU_HOST,
}


# jax ``device_kind`` prefix -> HW_SPECS name. A TPU v5e reports
# "TPU v5 lite"; kinds not listed here have no spec and are refused.
DEVICE_KINDS: Tuple[Tuple[str, str], ...] = (
    ("TPU v5 lite", "tpu-v5e"),
    ("TPU v5e", "tpu-v5e"),
    ("NVIDIA A100", "gpu-a100"),
    ("cpu", "cpu-host"),
)


def hw_for_device_kind(kind: str) -> HardwareSpec:
    """The :class:`HardwareSpec` of a jax ``device_kind``; an unknown
    kind is an error (planning a different chip as a v5e would rank
    (n, strategy) candidates with the wrong peaks)."""
    for prefix, name in DEVICE_KINDS:
        if kind.startswith(prefix):
            return HW_SPECS[name]
    raise ValueError(f"no HardwareSpec for device kind {kind!r}; known "
                     f"kinds: {[p for p, _ in DEVICE_KINDS]}")


def resolve_hw(name: str = "auto") -> HardwareSpec:
    """Named :class:`HardwareSpec`, or ``"auto"`` to detect it from the
    first attached device's ``device_kind``."""
    if name != "auto":
        try:
            return HW_SPECS[name]
        except KeyError:
            raise KeyError(f"unknown hw {name!r}; one of "
                           f"{sorted(HW_SPECS)} or 'auto'") from None
    import jax  # lazy: keep this module importable without a backend

    return hw_for_device_kind(jax.devices()[0].device_kind)
