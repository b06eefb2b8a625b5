"""The part of collective time during which no other op ran on that chip,
over the traced span, mean over the chips."""
LAYER, UNIT, SOURCE, MOVES = "collectives", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    if rec.trace is None:
        return None
    return 100.0 * rec.trace.share_of_span(lambda d: d.collective_exposed_ns)
