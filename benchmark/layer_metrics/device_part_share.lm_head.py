"""Device time in ``lm_head`` (the final norm and the head) over the
device's busy time of the traced part, all programs together, in percent
(``benchmark/part_reduce.py``)."""
from benchmark import part_reduce


def read(facts):
    return part_reduce.share_pct(facts, "lm_head")
