"""Device time a step of the ops under the program's ``lm_swa`` scope (a
sliding-window attention layer: its projections, RoPE, the ring's write and
``attend`` over the ring with the sink), prefill and every decode step
together; what ``attend`` takes of it, and the full layers' ``lm_attn`` and
the routed experts' ``lm_moe`` beside it, go to the notes."""
from . import _lm, _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    total = _lm.seconds_under(rec, "lm_swa")
    if total is not None:
        by = _scopes.seconds_by_scope(rec) or {}
        attend = {k: sum(s for scope, s in by.items() if {k, "attend"} <= set(scope.split("/")))
                  for k in ("lm_swa", "lm_attn")}
        rec.notes.append(f"lm_swa a step: attend {attend['lm_swa']:.4f} s of {total:.4f} s; lm_attn "
                         f"{_lm.seconds_under(rec, 'lm_attn') or 0.0:.4f} s (attend {attend['lm_attn']:.4f} s); "
                         f"lm_moe {_lm.seconds_under(rec, 'lm_moe') or 0.0:.4f} s")
    return total
