"""``to_stablehlo`` under the step's ``lower`` span: jax's own seconds for turning
the step's jaxpr into a StableHLO module (every Mosaic kernel is serialized here)."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step builder", "s", "program_span", "setup_s"


def read(rec):
    return _scopes.span_seconds(rec, "to_stablehlo", parent="lower")
