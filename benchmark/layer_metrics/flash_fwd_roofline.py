"""Flash forward kernel against its roofline (compute-bound here:
FLOPs / peak exceeds bytes / peak at 4k x 128)."""
from benchmark.metric_lib import FLASH_FWD, flash_roofline_pct


def read(facts):
    return flash_roofline_pct(facts, FLASH_FWD, backward=False)
