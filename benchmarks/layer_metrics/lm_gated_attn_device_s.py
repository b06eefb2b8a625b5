"""Device time a step of the ops under the program's ``lm_attn`` scope (a gated
softmax-attention mixer: its projections, per-head norms, rotary, the cache
write, ``attend`` and the output gate), prefill and every decode step
together; what ``attend`` takes of it, beside its floor
(``flops/<family>.attend_work``), goes to the notes."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    total = _lm.seconds_under(rec, "lm_attn")
    attend, family = _lm.seconds_under(rec, "attend"), _lm.work(rec)
    if total is not None and attend is not None and family is not None:
        per_step, per_call = _lm.sequences(rec)
        flops, bytes_ = family.attend_work(rec.config["model"], per_step, per_call)
        _lm.roofline_share(rec, flops, bytes_, attend, f"lm_attn a step: attend {attend:.4f} s of {total:.4f} s; attend")
    return total
