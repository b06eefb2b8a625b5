"""``mla_attend_roofline`` in the cell of the ``xing4_0`` configuration: the same reader
(over this family's work functions where it has any; the accepted entry lists
its own cell alone and may not be edited; ROADMAP R8 queues the folding)."""
from .mla_attend_roofline import read  # noqa: F401

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"
