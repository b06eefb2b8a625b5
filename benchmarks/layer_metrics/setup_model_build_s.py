"""The generator's build: the ``build_backend`` and ``backend_setup`` spans of
``trace.jsonl`` (configuration, weights or seeded init, decoder, prompts)."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "entry", "s", "program_span", "setup_s"


def read(rec):
    # `setup` is the trainer's own span (theta init, resume) between the build and the first compile:
    # the part of setup_build_s that no setup_* metric holds
    for child in ("init_params", "load_prompts", "setup"):
        s = _scopes.span_seconds(rec, child)
        if s is not None:
            rec.notes.append(f"build span {child}: {s:.3f} s")
    return _scopes.span_seconds(rec, "build_backend", "backend_setup")
