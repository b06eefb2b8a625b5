"""Arithmetic several readers share. A reader is ``layer_metrics/<metric>.py``
with ``LAYER``, ``UNIT``, ``SOURCE``, ``MOVES`` (as BENCHMARK.json states them)
and ``read(rec) -> float | None``; None leaves the metric out of the line."""

from __future__ import annotations

import importlib
from typing import Optional

from ..flops import kernels

# The profiler drops a few of a kernel's events (up to 2.5 % of VAR's
# decode_attention calls were missing from a five-step trace, PR 23). Further
# off than this, the step no longer makes the calls ``kernel_sites`` lists (the
# member loop was re-chunked, a site moved to another kernel): the floor would
# be that of other shapes, so no roofline share is reported.
CALL_COUNT_TOL = 0.05


def images_per_chip_in_trace(rec) -> float:
    return rec.trace.periods * rec.work_per_step / rec.chips


def kernel_time_share(rec, kernel: str) -> Optional[float]:
    """Device time of the kernel's events over the device's busy time, in %.
    A kernel with no event in the trace took no time: 0, which is a reading."""
    if rec.trace is None or rec.trace.busy_s <= 0:
        return None
    seconds, _ = rec.trace.matching(kernel)
    return 100.0 * seconds / rec.trace.busy_s


def kernel_roofline(rec, kernel: str) -> Optional[float]:
    """Roofline floor of the calls the traced steps make over the kernel's
    measured time, in %. The calls come from the configuration's
    ``kernel_sites`` and the traffic's ``images_per_kernel_call``; None where
    the trace's event count says the step makes other calls than those."""
    sites = rec.config.get("kernel_sites", {}).get(kernel)
    per_call = rec.traffic.get("images_per_kernel_call")
    if rec.trace is None or rec.peaks is None or not sites or not per_call:
        return None
    seconds, events = rec.trace.matching(kernel)
    if seconds <= 0:
        return None
    calls = getattr(kernels, kernel)(sites, per_call, rec.config["model"])
    floor = kernels.least_seconds(calls, images_per_chip_in_trace(rec),
                                  rec.peaks["bf16_flops_per_s"], rec.peaks["hbm_bytes_per_s"])
    said = (f"{kernel}: {events:.0f} events a chip in {rec.trace.periods} traced steps, "
            f"{floor['calls']:.0f} calls expected from kernel_sites")
    if abs(events - floor["calls"]) > CALL_COUNT_TOL * floor["calls"]:
        rec.notes.append(f"{said}: more than {CALL_COUNT_TOL:.0%} apart, no roofline share reported")
        return None
    rec.notes.append(f"{said}; floor {floor['seconds']:.4f} s ({floor['bound']}-bound) "
                     f"against {seconds:.4f} s measured")
    return 100.0 * floor["seconds"] / seconds


def flops_per_image(rec) -> float:
    family = importlib.import_module(f"benchmarks.flops.{rec.config['family']}")
    return family.flops_per_image(rec.config["model"])["total"]
