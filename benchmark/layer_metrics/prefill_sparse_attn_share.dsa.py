"""What attention over the chosen rows leaves of a long prompt's prefill
call: the ``attn/attn_sparse`` part's device seconds (the masked flash
kernel ``dsa_attn`` of the full layers, which walks every causal pair
and masks the unchosen) inside the prefill programs over those
programs' device seconds, in percent, from the trace read through the
replica's ``program_parts.json`` (``benchmark/part_reduce.py``). A
prefill that gathers its chosen rows will be judged by this share.
Lower is better. None without a map, without a prefill call in the
traced part or without such a part in it (a parent commit, a CPU, a
model without such a layer)."""
from benchmark import part_reduce
from benchmark.metric_lib import PREFILL

PART = "attn/attn_sparse"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    return 100.0 * parts[PART] / sum(parts.values())
