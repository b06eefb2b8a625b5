"""What the readers of the ``lm_ar`` generator's layers share: device seconds
by a scope's name anywhere in its path, the program's ``moe/*`` counters over
the window's rows, and the work functions of the configuration's flops file.
All return None on a program that lacks the scope or the counter (the parent
of the PR that added them)."""

from __future__ import annotations

import importlib
import statistics
from typing import Optional

from . import _scopes


def seconds_under(rec, name: str) -> Optional[float]:
    """Seconds a traced step of the ops whose scope path holds ``name``
    (``generate/lm_decode_step/lm_moe/experts`` holds ``lm_moe`` and ``experts``)."""
    by = _scopes.seconds_by_scope(rec)
    if by is None:
        return None
    found = [s for scope, s in by.items() if name in scope.split("/")]
    return sum(found) if found else None


def counter(rec, key: str, reduce=statistics.mean) -> Optional[float]:
    values = [r[key] for r in rec.window_rows if isinstance(r.get(key), (int, float))]
    return float(reduce(values)) if values else None


def work(rec):
    """The configuration's flops file, if it has the two layers' work functions."""
    family = importlib.import_module(f"benchmarks.flops.{rec.config['family']}")
    return family if hasattr(family, "experts_work") and hasattr(family, "attend_work") else None


def sequences(rec):
    """(sequences a step, sequences that advance together in one call)."""
    per_member = int(rec.flag("--prompts_per_gen")) * int(rec.flag("--batches_per_gen", "1"))
    pop, chunk = int(rec.flag("--pop_size")), int(rec.flag("--member_batch", "1"))
    return pop * per_member // rec.chips, min(chunk, pop) * per_member


def roofline_share(rec, flops: float, bytes_: float, seconds: float, what: str) -> Optional[float]:
    if rec.peaks is None or seconds <= 0:
        return None
    t_f, t_b = flops / rec.peaks["bf16_flops_per_s"], bytes_ / rec.peaks["hbm_bytes_per_s"]
    rec.notes.append(f"{what}: floor {max(t_f, t_b):.4f} s a step ({'compute' if t_f >= t_b else 'memory'}-bound: "
                     f"{flops / 1e12:.3f} TFLOP, {bytes_ / 1e9:.2f} GB) against {seconds:.4f} s measured")
    return 100.0 * max(t_f, t_b) / seconds
