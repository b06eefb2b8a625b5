"""``moe_experts_roofline`` in a cell of the hybrid family: the same reader
over this family's ``experts_work`` (the accepted entry lists its own cell
alone and may not be edited; ROADMAP R8 queues the folding)."""
from .moe_experts_roofline import read  # noqa: F401

LAYER, UNIT, SOURCE, MOVES = "kernels", "%", "device_trace", "images_per_s_per_chip"
