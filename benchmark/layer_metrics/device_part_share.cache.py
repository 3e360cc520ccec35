"""Device time in ``cache`` (the step's or the prompt's rows, ring rows
or recurrent state written into the slots' state) over the device's busy
time of the traced part, all programs together, in percent
(``benchmark/part_reduce.py``)."""
from benchmark import part_reduce


def read(facts):
    return part_reduce.share_pct(facts, "cache")
