"""Roofline floor of the grouped expert products a step — the token-expert
pairs the program counted (``moe/local_assignments``), each held expert's int8
base read once a call (``flops/<family>.experts_work``) — over the device time
of the ``experts`` scope."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    seconds, pairs = _lm.seconds_under(rec, "experts"), _lm.counter(rec, "moe/local_assignments")
    family = _lm.work(rec)
    if seconds is None or pairs is None or family is None:
        return None
    per_step, per_call = _lm.sequences(rec)
    calls = family.expert_calls_per_step(rec.config["model"], per_call, per_step)
    flops, bytes_ = family.experts_work(rec.config["model"], pairs / rec.chips, calls)
    return _lm.roofline_share(rec, flops, bytes_, seconds, f"experts ({pairs:.0f} pairs, {calls:.0f} calls a step)")
