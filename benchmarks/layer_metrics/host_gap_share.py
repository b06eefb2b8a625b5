"""Share of the window in which no ``dispatch`` span of ``trace.jsonl`` was
open: planning, fetching histograms, logging — the host loop between steps.
In a traced run the share is taken over the part of the window after the
profiler stopped, so that starting and stopping it is not read as a host gap."""
LAYER, UNIT, SOURCE, MOVES = "host loop", "%", "program_span", "images_per_s_per_chip"


def read(rec):
    lo = max(rec.t_open, rec.t_trace_done)
    spans = rec.spans_named("dispatch", lo, rec.t_close)
    if not spans or rec.t_close <= lo:
        return None
    inside = sum(min(s["t1"], rec.t_close) - max(s["t0"], lo) for s in spans)
    return 100.0 * (1.0 - inside / (rec.t_close - lo))
