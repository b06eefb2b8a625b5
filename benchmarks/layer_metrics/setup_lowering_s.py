"""The ``lower`` span under the step's ``compile`` span: tracing and lowering
the ES step to StableHLO, paid on every run, compile cache or not."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step builder", "s", "program_span", "setup_s"


def read(rec):
    return _scopes.span_seconds(rec, "lower", parent="compile")
