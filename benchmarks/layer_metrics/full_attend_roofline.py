"""Roofline floor of attention over the full layers' caches a step — at each
sampled position the slots a query sees, read once in bf16, and the prefill's
causal block (``flops/<family>.full_attend_work``) — over the device time of
the ``attend`` scope under ``lm_attn``."""
from ._attend import share

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    return share(rec, "lm_attn", "full_attend_work")
