"""Device time a step of the ops under the program's ``generate`` scope (the
generator: Sana's DiT, VAR's ten scales), from the trace's leaf ops and the
program's op -> scope table; the inner scopes go to the notes."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    _scopes.inner_note(rec, "generate")
    return _scopes.scope_seconds(rec, "generate")
