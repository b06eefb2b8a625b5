"""Median over the traced steps of: start of the step module on the first
chip to start, minus start of the program's ``enqueue`` annotation — both
events of the profiler's own trace, one clock.

Error: +-1 ms. The profiler aligns its host and its device plane only to
about a millisecond, anew in every session, so this figure and
``fetch_latency_ms`` are each off by that amount, in opposite directions
(3.19 / 4.13 ms and 2.27 / 4.98 ms in two runs of one cell, PERF.md section 6 c).
Neither half carries a claim or a comparison between runs; their sum, which
the note below prints, is exact, and is what a change of the host loop is
judged on."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "host loop", "ms", "program_span", "images_per_s_per_chip"


def read(rec):
    values = _scopes.launch_latencies_ms(rec)
    if values:
        rec.notes.append("launch latency of each traced step, ms: " + ", ".join(f"{v:.3f}" for v in values))
        _scopes.host_round_trip_note(rec)
        _scopes.clock_offset_note(rec)
    return _scopes.median_or_none(values)
