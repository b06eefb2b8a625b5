"""Leaf device ops under the program's ``lm_hc`` scope over the calls of the
hyper-connection chain the traced steps make (``flops/<family>.hc_calls_per_step``:
two sub-layers a layer, the prefill and every sampled position, every chunk of
sequences): how many launches one sub-layer's residual work is — the measure of
a latency-bound chain, whose floor in bytes (``hc_work``) is a few per cent of
what it takes. The profiler drops up to a tenth of a step's events at this
event rate (PERF.md section 6), so the count is a lower bound by that much; the
note gives the seconds an op. None on a program without the scope."""
import importlib

from . import _lm, _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "count", "device_trace", "images_per_s_per_chip"


def read(rec):
    table, tr = _scopes.scope_table(rec), rec.trace
    family = importlib.import_module(f"benchmarks.flops.{rec.config['family']}")
    if tr is None or not table or not hasattr(family, "hc_calls_per_step"):
        return None
    per_chip = []
    for d in tr.devices:
        lo, hi = d.span_ns
        per_chip.append(sum(1 for e in d.leaves if lo <= e.start_ns < hi
                            and "lm_hc" in table.get(e.name, "").lstrip(_scopes.INFERRED).split("/")))
    ops = sum(per_chip) / len(per_chip) / tr.periods
    if not ops:
        return None
    per_step, per_call = _lm.sequences(rec)
    calls = family.hc_calls_per_step(rec.config["model"], per_call, per_step)
    seconds = _lm.seconds_under(rec, "lm_hc") or 0.0
    rec.notes.append(f"lm_hc: {ops:.0f} leaf ops a traced step over {calls:.0f} calls "
                     f"({seconds / ops * 1e6:.2f} us an op, {seconds / calls * 1e6:.1f} us a call)")
    return ops / calls
