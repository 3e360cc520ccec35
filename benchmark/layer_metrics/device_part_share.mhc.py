"""Device time in ``mhc`` (several residual streams: the norm over all
of them, the product with ``phi``, the Sinkhorn rounds and the three
mixes round every sublayer, ``models/glm_next.py``) over the device's
busy time of the traced part, all programs together, in percent
(``benchmark/part_reduce.py``): what the streams cost. None without a
map or without such a part in it (a parent commit, another model)."""
from benchmark import part_reduce


def read(facts):
    t = part_reduce.table(facts)
    if not t or not any("mhc" in parts
                        for parts in t.get("programs", {}).values()):
        return None
    return part_reduce.share_pct(facts, "mhc")
