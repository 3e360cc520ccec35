"""Device time in ``moe_experts`` (the expert layer beyond its kernel:
sort, gathers, moe_gmm, un-sort, combine) over the device's busy time of
the traced part, all programs together, in percent
(``benchmark/part_reduce.py``)."""
from benchmark import part_reduce


def read(facts):
    return part_reduce.share_pct(facts, "moe_experts")
