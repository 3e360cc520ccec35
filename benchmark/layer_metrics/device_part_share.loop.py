"""Device time in ``loop`` (operations of a scan's body under no part:
the slicing of the scanned layer parameters, the loops' counters) over
the device's busy time of the traced part, all programs together, in
percent (``benchmark/part_reduce.py``)."""
from benchmark import part_reduce


def read(facts):
    return part_reduce.share_pct(facts, "loop")
