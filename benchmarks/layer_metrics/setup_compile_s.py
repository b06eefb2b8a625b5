"""``lowering_s + compile_s`` of the ``es_step_*`` record of ``programs.jsonl``."""
LAYER, UNIT, SOURCE, MOVES = "step builder", "s", "program_counter", "setup_s"


def read(rec):
    steps = rec.step_programs
    return sum(p["lowering_s"] + p["compile_s"] for p in steps) if steps else None
