"""Roofline floor of the gated delta rule a step — each sequence's float32
state read once and written once a DeltaNet layer a sampled position, the
rule's FLOPs, the prefill's (``flops/<family>.state_update_work``: the same
count whether XLA or a kernel computes it) — over the device time of the
``delta_rule`` scope."""
import importlib

from . import _lm

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    seconds = _lm.seconds_under(rec, "delta_rule")
    family = importlib.import_module(f"benchmarks.flops.{rec.config['family']}")
    if seconds is None or not hasattr(family, "state_update_work"):
        return None
    per_step, _ = _lm.sequences(rec)
    flops, bytes_ = family.state_update_work(rec.config["model"], per_step)
    return _lm.roofline_share(rec, flops, bytes_, seconds, f"delta_rule ({per_step} sequences a step)")
