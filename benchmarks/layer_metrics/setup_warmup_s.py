"""The warm-up epochs without their compile: the ``epoch`` spans of ``trace.jsonl``
that begin before the window opens, each cut at the window's opening (the harness
opens it from the hook the last warm-up epoch calls, a moment before that span
ends), less the ``compile`` spans inside them. Two steps and the first one's
program load."""
LAYER, UNIT, SOURCE, MOVES = "host loop", "s", "program_span", "setup_s"


def read(rec):
    epochs = [s for s in rec.spans_named("epoch") if s["t0"] < rec.t_open]
    if not epochs:
        return None
    total = sum(min(s["t1"], rec.t_open) - s["t0"] for s in epochs)
    compiles = sum(c["dur_s"] for c in rec.spans_named("compile")
                   if c.get("parent") == "epoch" and any(e["t0"] <= c["t0"] and c["t1"] <= e["t1"] for e in epochs))
    return total - compiles
