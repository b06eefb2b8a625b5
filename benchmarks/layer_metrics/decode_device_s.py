"""Device time a step of the ops under the program's ``decode`` scope (DC-AE
decode for Sana, the MSVQ decoder for VAR); its stages go to the notes."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    _scopes.inner_note(rec, "decode")
    return _scopes.scope_seconds(rec, "decode")
