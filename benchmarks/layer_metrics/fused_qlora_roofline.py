"""Roofline floor of the ``fused_qlora`` calls a step makes (shapes in the
configuration's ``kernel_sites``, FLOPs and bytes by ``flops/kernels.py``)
over the kernel's measured device time."""
from ._shared import kernel_roofline

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"


def read(rec):
    return kernel_roofline(rec, "fused_qlora")
