"""The program's ``moe/max_expert_load``: the largest number of rows one held
expert saw in one call over the mean of that call, the largest of the window's steps."""
from . import _lm

LAYER, UNIT, SOURCE, MOVES = "kernels", "ratio", "program_counter", "images_per_s_per_chip"


def read(rec):
    return _lm.counter(rec, "moe/max_expert_load", max)
