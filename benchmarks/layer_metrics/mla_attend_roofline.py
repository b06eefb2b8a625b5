"""Roofline floor of attention over the latent cache a step — the cache
entries' bytes and the absorbed form's FLOPs (``flops/<family>.attend_work``)
— over the device time of the ``attend`` scope."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    seconds, family = _lm.seconds_under(rec, "attend"), _lm.work(rec)
    if seconds is None or family is None:
        return None
    per_step, per_call = _lm.sequences(rec)
    flops, bytes_ = family.attend_work(rec.config["model"], per_step, per_call)
    return _lm.roofline_share(rec, flops, bytes_, seconds, "attend")
