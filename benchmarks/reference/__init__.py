"""Copies of the program's plain references (``hyperscalees_t2i_tpu/reference/``), kept with the
benchmark as the flops and peaks are: the comparison that decides ``correct``
uses the copy, so a change of the program's file cannot move the yardstick."""
