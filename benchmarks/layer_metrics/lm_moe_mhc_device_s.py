"""``lm_moe_device_s`` in the cell of the ``xing4_0`` configuration: the same reader
(over this family's work functions where it has any; the accepted entry lists
its own cell alone and may not be edited; ROADMAP R8 queues the folding)."""
from .lm_moe_device_s import read  # noqa: F401

LAYER, UNIT, SOURCE, MOVES = "step program", "s", "device_trace", "images_per_s_per_chip"
