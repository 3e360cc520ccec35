"""Device time in ``unscoped`` (operations the program's map has under
no part: the instrument's own soundness) over the device's busy time of
the traced part, all programs together, in percent
(``benchmark/part_reduce.py``)."""
from benchmark import part_reduce


def read(facts):
    return part_reduce.share_pct(facts, "unscoped")
