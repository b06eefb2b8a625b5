"""1 - union of device-op intervals / traced span, on the idlest chip."""
LAYER, UNIT, SOURCE, MOVES = "device", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    return 100.0 * rec.trace.idlest.idle_share if rec.trace is not None else None
