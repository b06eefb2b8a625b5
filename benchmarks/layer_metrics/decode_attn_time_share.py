"""Device time of events named ``decode_attention`` over the device's busy time."""
from ._shared import kernel_time_share

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    return kernel_time_share(rec, "decode_attention")
