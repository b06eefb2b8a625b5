"""What the CLI and ``backend.setup()`` build before the step compiles:
``train.cli.main`` entered (harness clock) -> start of the first ``compile``
span of ``trace.jsonl``."""
LAYER, UNIT, SOURCE, MOVES = "entry", "s", "program_span", "setup_s"


def read(rec):
    spans = rec.spans_named("compile")
    return min(s["t0"] for s in spans) - rec.t_entry if spans else None
