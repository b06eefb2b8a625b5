"""The compiler's own peak of the step program: ``peak_bytes`` of the
``es_step_*`` record of ``programs.jsonl`` (arguments + outputs + temporaries +
code, less what is aliased), the figure a compile is refused on. A static
analysis, not a device reading: ``peak_hbm_gb`` is the runtime's counters."""
LAYER, UNIT, SOURCE, MOVES = "step builder", "GB", "program_counter", "peak_hbm_gb"


def read(rec):
    steps = rec.step_programs
    peak = steps[0].get("peak_bytes") if steps else None
    return peak / 1e9 if peak else None
