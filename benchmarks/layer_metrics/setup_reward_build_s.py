"""The ``build_reward`` span of ``trace.jsonl``: reward towers loaded or
seeded, tokenization, text tables; its children go to the notes."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "entry", "s", "program_span", "setup_s"


def read(rec):
    for child in ("clip_b", "clip_h", "text_tables"):
        s = _scopes.span_seconds(rec, child, parent="build_reward")
        if s is not None:
            rec.notes.append(f"build span build_reward/{child}: {s:.3f} s")
    return _scopes.span_seconds(rec, "build_reward")
