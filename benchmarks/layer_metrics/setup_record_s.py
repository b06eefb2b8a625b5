"""The ``record`` span under ``compile``: what the program ledger costs a run
(cost and memory analysis, StableHLO stats, the donation audit) and, traced, the
op -> scope table and the whole-array op counts — the ``scope_table`` span under
it, which an untraced run does not pay: the notes give the two parts."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "step builder", "s", "program_span", "setup_s"


def read(rec):
    seconds = _scopes.span_seconds(rec, "record", parent="compile")
    traced_only = _scopes.span_seconds(rec, "scope_table", parent="record")
    if seconds is not None and traced_only is not None:
        rec.notes.append(f"record {seconds:.3f} s = {seconds - traced_only:.3f} s every run pays + "
                         f"scope_table {traced_only:.3f} s only a traced run pays")
    return seconds
