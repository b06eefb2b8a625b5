"""Roofline floor of attention over the window layers' rings a step — at each
sampled position the ``min(sliding_window, seen)`` slots a query sees, read
once in bf16, and the prefill's band (``flops/<family>.window_attend_work``:
the same count whatever computes it) — over the device time of the
``attend`` scope under ``lm_swa``."""
from ._attend import share

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    return share(rec, "lm_swa", "window_attend_work")
