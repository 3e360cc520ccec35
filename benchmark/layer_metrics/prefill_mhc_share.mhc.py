"""What the residual streams leave of a long prompt's prefill call: the
``mhc`` part's device seconds (the streams' coefficients and mixes round
every sublayer) inside the prefill programs over those programs' device
seconds, in percent, from the trace read through the replica's
``program_parts.json`` (``benchmark/part_reduce.py``), as
``prefill_linear_attn_share.hybrid`` reads its part. XLA's fusions do
this work; a kernel for it will be judged by this share. Lower is
better. None without a map, without a prefill call in the traced part or
without such a part in it (a parent commit, a CPU, a model of one
stream)."""
from benchmark import part_reduce
from benchmark.metric_lib import PREFILL

PART = "mhc"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    return 100.0 * parts[PART] / sum(parts.values())
