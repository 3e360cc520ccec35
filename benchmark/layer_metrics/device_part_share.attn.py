"""Device time in ``attn`` (attention proper: decode_attn, flash_fwd,
the warm path's products, MLA's scores, KDA's state passes) over the
device's busy time of the traced part, all programs together, in percent
(``benchmark/part_reduce.py``)."""
from benchmark import part_reduce


def read(facts):
    return part_reduce.share_pct(facts, "attn")
