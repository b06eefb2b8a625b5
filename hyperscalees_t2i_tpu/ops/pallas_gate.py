"""Shared gate mechanics for every Pallas kernel in ops/.

A kernel is selected from what the code can observe — the backend
(:func:`backend_is_tpu`) and the call's shapes (each kernel's own fit check)
— plus, for the two older kernels, one tri-state environment flag each
(:func:`env_requested`): ``"0"``/``"off"`` opts a default-ON kernel out
(``gated_delta_step`` and ``ssd_step`` have no flag). Nothing is probed and nothing
falls back: a kernel its gate selected and Mosaic refuses raises at the
enclosing compile, on every path. Whether each
kernel compiles, runs and agrees with its XLA composition on a chip is
established by ``tools/kernel_check.py`` (run by ``chip_smoke.py`` for the
default-ON gates), not guessed at trace time.

- :func:`selected_kernels` — what every gate says right now, by the name each
  ``pallas_call`` carries (``name=``) and the program ledger reports
  (``obs/xla_cost`` ``pallas_kernels``), so "the gates selected X" and "the
  compiled step contains X" compare directly.
- :func:`active_pallas_flags` — the currently-set kernel env flags, stamped
  into bench/dispatch_tax artifacts and ledger geometry so a measurement
  always says which kernels were requested when it was taken.

The per-kernel gates stay in their own modules (all four kernels —
``fused_qlora``, ``decode_attention``, ``gated_delta_step``, ``ssd_step`` —
are on by default on a TPU); only the env/backend mechanics live here. Stdlib-only
at import (jax-free processes render the flag marks).
"""

from __future__ import annotations

import os
from typing import Dict, Optional

# Every Pallas-kernel env flag in ops/, with the short name artifacts render
# (tools/bench_report.py trend knob markers, tools/dispatch_tax.py stamp).
PALLAS_ENV_FLAGS = {
    "HSES_USE_PALLAS": "flash",
    "HSES_FUSED_QLORA_PALLAS": "qlora",
}


def env_requested(flag: str) -> Optional[bool]:
    """Tri-state kernel-flag read: ``"1"`` → True (explicit request),
    ``"0"``/``"off"`` → False (explicit opt-out), unset or anything else →
    None (the kernel's own default applies)."""
    v = os.environ.get(flag)
    if v == "1":
        return True
    if v is not None and v.lower() in ("0", "off"):
        return False
    return None


def backend_is_tpu() -> bool:
    """True on the backend that runs Mosaic kernels."""
    import jax

    return jax.default_backend() == "tpu"


def selected_kernels() -> Dict[str, bool]:
    """Every kernel gate's verdict on this backend under this environment,
    keyed by the kernel's ``pallas_call`` name."""
    from .attention import should_use_pallas
    from .fused_qlora import use_fused_qlora_pallas
    from .gated_delta import use_gated_delta_pallas
    from .ssd import use_ssd_pallas

    return {
        "fused_qlora": use_fused_qlora_pallas(),
        "decode_attention": should_use_pallas(),
        "gated_delta_step": use_gated_delta_pallas(),
        "ssd_step": use_ssd_pallas(),
    }


def active_pallas_flags() -> Dict[str, str]:
    """The kernel env flags currently SET in this process (value verbatim,
    including opt-outs — a ``"0"`` is provenance too). Stamped into bench
    rung records, dispatch_tax rows, and ledger geometry."""
    return {
        flag: os.environ[flag]
        for flag in PALLAS_ENV_FLAGS
        if flag in os.environ
    }


def pallas_flag_marks(flags: Dict[str, str]) -> str:
    """Compact render of :func:`active_pallas_flags` output for knob columns:
    requested kernels by short name, opt-outs suffixed ``-`` (e.g.
    ``"qlora,flash-"``). Empty string when nothing is set."""
    marks = []
    for flag in PALLAS_ENV_FLAGS:
        if flag not in flags:
            continue
        short = PALLAS_ENV_FLAGS[flag]
        v = flags[flag]
        marks.append(short if v == "1" else f"{short}-" if v.lower() in ("0", "off") else f"{short}={v}")
    return ",".join(marks)
