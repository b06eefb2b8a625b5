"""Device time a step of the ops under the program's ``lm_hc`` scope (the
hyper-connection streams around every sub-layer: the norm over the streams and
the product with φ, the Sinkhorn iterations, the read ``H_pre X`` and the write
``H_res X + H_post^T y``), prefill and every decode step together; the three
children and the chain's floor (``flops/<family>.hc_work``) go to the notes.
None on a program without the scope."""
import importlib

from . import _lm

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    total = _lm.seconds_under(rec, "lm_hc")
    if total is None:
        return None
    parts = {k: _lm.seconds_under(rec, k) or 0.0 for k in ("hc_coeff", "hc_sinkhorn", "hc_mix")}
    rec.notes.append("lm_hc a step: " + ", ".join(f"{k} {v:.4f} s" for k, v in parts.items())
                     + f", own (the gauges) {total - sum(parts.values()):.4f} s")
    family = importlib.import_module(f"benchmarks.flops.{rec.config['family']}")
    if hasattr(family, "hc_work"):
        per_step, per_call = _lm.sequences(rec)
        flops, bytes_ = family.hc_work(rec.config["model"], per_step, per_call)
        _lm.roofline_share(rec, flops, bytes_, total, "lm_hc (one pass over the streams)")  # the note only
    return total
