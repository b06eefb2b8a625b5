"""Device time a step of the ops under the program's ``lm_mla`` scope (latent
attention: its low-rank projections, the cache write and ``attend``), prefill
and every decode step together, from the trace's leaf ops and the op -> scope table."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    return _lm.seconds_under(rec, "lm_mla")
