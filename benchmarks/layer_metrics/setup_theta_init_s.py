"""The trainer's ``setup`` span of ``trace.jsonl``: theta init (or resume), the
frozen tree, the zero previous update, their placement on the mesh."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "entry", "s", "program_span", "setup_s"


def read(rec):
    return _scopes.span_seconds(rec, "setup")
