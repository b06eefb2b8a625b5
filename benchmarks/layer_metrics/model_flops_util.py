"""FLOPs an image requires (``flops/<family>.py``) x the images a chip scored
a second over the traced steps, over the chip's bf16 peak. The rate is taken
from the trace's own span (whole step periods), so starting and stopping the
profiler elsewhere in the window does not enter."""
from ._shared import flops_per_image, images_per_chip_in_trace

LAYER, UNIT, SOURCE, MOVES = "step program", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    if rec.peaks is None or rec.trace is None:
        return None
    rate = images_per_chip_in_trace(rec) / rec.trace.window_s
    return 100.0 * flops_per_image(rec) * rate / rec.peaks["bf16_flops_per_s"]
