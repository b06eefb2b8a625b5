"""Device time a step of the ops under the program's ``lm_gdn`` scope (a Gated
DeltaNet mixer: its projections, the short conv, the gated delta rule over the
recurrent state, the gated norm and output projection), prefill and every
decode step together; the three children go to the notes."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    total = _lm.seconds_under(rec, "lm_gdn")
    if total is not None:
        parts = {k: _lm.seconds_under(rec, k) or 0.0 for k in ("conv", "delta_rule", "gdn_out")}
        rec.notes.append("lm_gdn a step: " + ", ".join(f"{k} {v:.4f} s" for k, v in parts.items())
                         + f", projections and gates {total - sum(parts.values()):.4f} s")
    return total
