"""Step programs whose ``programs.jsonl`` record says ``cache: "hit"`` over step
programs: 1 where the persistent compile cache held the step, 0 on the first run
of a changed program."""
LAYER, UNIT, SOURCE, MOVES = "step builder", "ratio", "program_counter", "setup_s"


def read(rec):
    said = [p["cache"] for p in rec.step_programs if p.get("cache") is not None]
    return sum(c == "hit" for c in said) / len(said) if said else None
