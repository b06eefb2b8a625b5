"""Device time a step of the ops under the program's ``reward`` scope
(preprocess, CLIP-B/32, CLIP-H/14, scoring); the split goes to the notes."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    _scopes.inner_note(rec, "reward")
    return _scopes.scope_seconds(rec, "reward")
