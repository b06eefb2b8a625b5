"""The ``quantize`` span of ``trace.jsonl`` (both ``quantize_frozen`` calls).
A configuration without an int8 base has none and reads 0; a program without
build spans (the parent of PR 25) reads nothing."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "entry", "s", "program_span", "setup_s"


def read(rec):
    s = _scopes.span_seconds(rec, "quantize")
    if s is None and rec.flag("--base_quant") != "int8" and rec.spans_named("build_backend"):
        return 0.0
    return s
