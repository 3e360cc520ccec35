"""Flash backward kernel(s) against their roofline (compute-bound)."""
from benchmark.metric_lib import FLASH_BWD, flash_roofline_pct


def read(facts):
    return flash_roofline_pct(facts, FLASH_BWD, backward=True)
