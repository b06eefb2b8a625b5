"""Median over the traced steps of: end of the program's ``fetch`` annotation
(``device_get`` of the metrics) minus end of the step module on the last chip
to end — both events of the profiler's own trace, one clock.

Error: +-1 ms, the profiler's host/device alignment, opposite in sign to
``launch_latency_ms``'s (see there): only the sum of the two, printed in that
reader's note, is exact and fit to be compared between runs."""
from . import _scopes

LAYER, UNIT, SOURCE, MOVES = "host loop", "ms", "program_span", "images_per_s_per_chip"


def read(rec):
    values = _scopes.fetch_latencies_ms(rec)
    if values:
        rec.notes.append("fetch latency of each traced step, ms: " + ", ".join(f"{v:.3f}" for v in values))
    return _scopes.median_or_none(values)
