"""The ``startup`` span of ``trace.jsonl``: the operating system's stamp of the
process's start -> ``train.cli.main`` entered (interpreter, the harness's imports,
jax and the backend's bring-up). The harness's own ``t_entry - t_process_start``
goes to the notes: its clock starts when ``run.py`` does, after the interpreter."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "entry", "s", "program_span", "setup_s"


def read(rec):
    seconds = _scopes.span_seconds(rec, "startup")
    if seconds is not None:
        up = rec.spans_named("startup")[0].get("attrs", {}).get("backend_initialized")
        rec.notes.append(f"startup {seconds:.3f} s from the OS's process start (backend up at entry: {up}); "
                         f"the harness's clock reads {rec.t_entry - rec.t_process_start:.3f} s before train.cli")
    return seconds
