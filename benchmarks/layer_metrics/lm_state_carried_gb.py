"""The program's ``lm/state_bytes`` + ``lm/kv_cache_bytes``: what the step's
sequences carry through their decode scans — recurrent states and conv
windows, and KV caches — in GB; the two parts go to the notes."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "step program", "GB", "program_counter", "images_per_s_per_chip"


def read(rec):
    state, kv = _lm.counter(rec, "lm/state_bytes"), _lm.counter(rec, "lm/kv_cache_bytes")
    if state is None or kv is None:
        return None
    rec.notes.append(f"carried a step: recurrent state + conv windows {state / 1e9:.4f} GB, KV cache {kv / 1e9:.4f} GB")
    return (state + kv) / 1e9
