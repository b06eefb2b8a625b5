"""Rough MFU accounting: XLA-reported step FLOPs vs hardware peak.

The reference publishes no throughput or utilization numbers (SURVEY.md §5.1);
here every run logs a model-FLOPs-utilization estimate so perf regressions
are visible in the JSONL stream. FLOPs come from the compiled executable's
own cost analysis (no hand-maintained per-model counts); peak numbers are the
public per-chip figures: dense bf16 FLOP/s, HBM bandwidth (the roofline's
second axis — obs/xla_cost.py), and HBM capacity (the preflight fit verdict —
tools/preflight.py).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """One chip's published peaks (Google Cloud TPU documentation, per chip):
    dense bf16 FLOP/s, HBM bandwidth (the roofline's second axis —
    obs/xla_cost.roofline), one-way aggregate ICI bandwidth (its comms
    floor: collective bytes of the partitioned module over this), and HBM
    capacity (the preflight fit/no-fit threshold)."""

    peak_bf16_flops: float
    hbm_bw: float
    ici_bw: float
    hbm_bytes: float


# by chip name — what `preflight --chip` takes; no device need be present
CHIPS: Dict[str, ChipSpec] = {
    "v3": ChipSpec(123e12, 900e9, 82e9, 32e9),
    "v4": ChipSpec(275e12, 1228e9, 300e9, 32e9),
    "v5e": ChipSpec(197e12, 819e9, 200e9, 16e9),
    "v5p": ChipSpec(459e12, 2765e9, 600e9, 95e9),
    "v6e": ChipSpec(918e12, 1640e9, 448e9, 32e9),  # Trillium
}

# exact ``jax.Device.device_kind`` strings → chip name. "TPU v5 lite" is
# what a v5e reports (libtpu 0.0.34; read off the chip, PR 21); the others
# are the strings jax itself matches (jax/_src/pallas/mosaic/tpu_info.py).
# A kind that is not here is not guessed at: :func:`device_chip` raises for
# it on a TPU platform.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v3": "v3",
    "TPU v4": "v4",
    "TPU v5 lite": "v5e",
    "TPU v5e": "v5e",
    "TPU v5": "v5p",
    "TPU v5p": "v5p",
    "TPU v6 lite": "v6e",
    "TPU v6e": "v6e",
}


def chip_for_kind(kind: str) -> Optional[ChipSpec]:
    """The spec for an exact ``device_kind`` string or a chip name; None for
    anything else (CPU kinds, unknown chips) — no substring matching."""
    return CHIPS.get(DEVICE_KINDS.get(kind, kind))


def _field(kind: str, name: str) -> Optional[float]:
    spec = chip_for_kind(kind)
    return getattr(spec, name) if spec is not None else None


def peak_flops_for_kind(kind: str) -> Optional[float]:
    """Per-chip bf16 peak FLOP/s by device-kind string or chip name."""
    return _field(kind, "peak_bf16_flops")


def hbm_bw_for_kind(kind: str) -> Optional[float]:
    """Per-chip HBM bandwidth (bytes/s) by device-kind string or chip name."""
    return _field(kind, "hbm_bw")


def hbm_bytes_for_kind(kind: str) -> Optional[float]:
    """Per-chip HBM capacity (bytes) by device-kind string or chip name."""
    return _field(kind, "hbm_bytes")


def ici_bw_for_kind(kind: str) -> Optional[float]:
    """Per-chip ICI bandwidth (bytes/s) by device-kind string or chip name —
    None for CPU/unknown kinds, which makes every comms-roofline consumer
    degrade to 'can't say' instead of inventing an interconnect."""
    return _field(kind, "ici_bw")


def device_chip(device: Optional[jax.Device] = None) -> Optional[ChipSpec]:
    """The live device's spec. None off the TPU (a CPU run measures no
    device metric); a TPU whose ``device_kind`` is not in
    :data:`DEVICE_KINDS` is an ERROR — a default peak would arm every gate
    built on it (MFU > 1, physical floor, HBM fit) with a wrong number, and
    None would silently disarm them."""
    d = device or jax.devices()[0]
    kind = getattr(d, "device_kind", "")
    spec = CHIPS.get(DEVICE_KINDS.get(kind))
    if spec is None and getattr(d, "platform", "") == "tpu":
        raise ValueError(
            f"unknown TPU device_kind {kind!r}: add its exact string and "
            f"published peaks to utils/mfu.py (have: {sorted(DEVICE_KINDS)})"
        )
    return spec


def device_peak_flops(device: Optional[jax.Device] = None) -> Optional[float]:
    """Per-chip bf16 peak for the device (None off the TPU)."""
    spec = device_chip(device)
    return spec.peak_bf16_flops if spec else None


def device_hbm_bandwidth(device: Optional[jax.Device] = None) -> Optional[float]:
    """Per-chip HBM bandwidth for the device (None off the TPU)."""
    spec = device_chip(device)
    return spec.hbm_bw if spec else None


def device_ici_bandwidth(device: Optional[jax.Device] = None) -> Optional[float]:
    """Per-chip ICI bandwidth for the device (None off the TPU)."""
    spec = device_chip(device)
    return spec.ici_bw if spec else None


def device_hbm_bytes(device: Optional[jax.Device] = None) -> Optional[float]:
    """Per-chip HBM capacity for the device (None off the TPU)."""
    spec = device_chip(device)
    return spec.hbm_bytes if spec else None


def executable_flops(compiled: Any) -> Optional[float]:
    """FLOPs of one call of an AOT-compiled executable (None if unavailable).

    Thin wrapper over the shared cost-analysis normalization in
    ``obs/xla_cost.py`` (one extraction, every consumer).

    NOTE on convention: for SPMD-partitioned programs some backends report
    *per-device* post-partition FLOPs, others the global total. Callers that
    divide by n_devices may understate MFU by up to n_devices on multichip;
    we keep the conservative (understating) direction so the MFU>1 honesty
    gate can only be *harder* to trip falsely, never easier.
    """
    from ..obs.xla_cost import normalize_cost_analysis

    return normalize_cost_analysis(compiled)["flops"]


def mfu(step_flops: Optional[float], step_time_s: float, n_devices: int = 1) -> Optional[float]:
    peak = device_peak_flops()
    if step_flops is None or peak is None or step_time_s <= 0:
        return None
    return step_flops / (step_time_s * peak * max(n_devices, 1))
