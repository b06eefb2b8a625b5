"""Device time a step of the ops under the program's ``lm_moe`` scope (router,
the grouped products of the held routed experts, the shared expert), prefill
and every decode step together; the split goes to the notes."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    total = _lm.seconds_under(rec, "lm_moe")
    if total is not None:
        parts = {k: _lm.seconds_under(rec, k) or 0.0 for k in ("router", "experts", "shared")}
        rec.notes.append("lm_moe a step: " + ", ".join(f"{k} {v:.4f} s" for k, v in parts.items()))
    return total
