"""Device busy time of one step: busy seconds of the traced span / its steps."""
LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"


def read(rec):
    return rec.trace.busy_s / rec.trace.periods if rec.trace is not None else None
