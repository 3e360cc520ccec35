"""Device time of the prefill program over the traced window."""
from benchmark.metric_lib import PREFILL, program_share_pct


def read(facts):
    return program_share_pct(facts, PREFILL)
