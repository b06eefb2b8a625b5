"""Roofline floor of the SSD state update a step — each sequence's float32
state read once and written once a Mamba-2 layer a sampled position, the
recurrence's FLOPs, the prefill's one write (``flops/<family>.state_update_work``:
the same count whatever computes it) — over the device time of the ``ssd``
scope under ``lm_ssm``."""
import importlib

from . import _lm, _scopes

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    by = _scopes.seconds_by_scope(rec)
    found = [s for scope, s in (by or {}).items() if {"lm_ssm", "ssd"} <= set(scope.split("/"))]
    family = importlib.import_module(f"benchmarks.flops.{rec.config['family']}")
    if not found or not hasattr(family, "state_update_work"):
        return None
    per_step, _ = _lm.sequences(rec)
    flops, bytes_ = family.state_update_work(rec.config["model"], per_step)
    return _lm.roofline_share(rec, flops, bytes_, sum(found), f"ssd ({per_step} sequences a step)")
