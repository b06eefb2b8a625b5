"""``jaxpr_trace`` under the step's ``lower`` span: jax's own seconds for running
the step's Python to a jaxpr (the outermost jitted function's). The notes split it
by the ``trace/<scope>`` spans the body opens as it is traced (a body traced twice
is counted twice; ``trace/pop_eval`` holds generate, decode and reward), largest
first, and say what ``lower`` spends beside its two halves."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step builder", "s", "program_span", "setup_s"


def read(rec):
    seconds = _scopes.span_seconds(rec, "jaxpr_trace", parent="lower")
    if seconds is None:
        return None
    by_scope = {}
    for s in rec.spans:
        if s["name"].startswith("trace/"):
            by_scope[s["name"]] = by_scope.get(s["name"], 0.0) + s["dur_s"]
    if by_scope:
        rec.notes.append("trace-time spans: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in sorted(by_scope.items(), key=lambda kv: -kv[1])))
    lower = _scopes.span_seconds(rec, "lower", parent="compile")
    to_hlo = _scopes.span_seconds(rec, "to_stablehlo", parent="lower") or 0.0
    if lower is not None:
        rec.notes.append(f"lower {lower:.3f} s = jaxpr_trace {seconds:.3f} + to_stablehlo {to_hlo:.3f} + "
                         f"{lower - seconds - to_hlo:.3f} s beside them (argument flattening, pjit's own checks)")
    return seconds
