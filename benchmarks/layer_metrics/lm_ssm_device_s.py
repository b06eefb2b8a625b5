"""Device time a step of the ops under the program's ``lm_ssm`` scope (a
Mamba-2 mixer: its input projection, the short conv, the SSD recurrence over
the float32 state, the gated norm and output projection), prefill and every
decode step together; the three children go to the notes."""
from . import _lm, _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    total = _lm.seconds_under(rec, "lm_ssm")
    if total is not None:
        by = _scopes.seconds_by_scope(rec) or {}
        parts = {k: sum(s for scope, s in by.items() if {"lm_ssm", k} <= set(scope.split("/")))
                 for k in ("conv", "ssd", "ssm_out")}
        rec.notes.append("lm_ssm a step: " + ", ".join(f"{k} {v:.4f} s" for k, v in parts.items())
                         + f", the input projection and the rest {total - sum(parts.values()):.4f} s")
    return total
