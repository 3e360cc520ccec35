"""What the indexer leaves of a long prompt's prefill call where full
layers attend over rows a learned indexer chooses: the
``attn/attn_index`` part's device seconds (the index scores' kernel
``dsa_index`` and the exact selection of the ``index_topk`` best a row)
inside the prefill programs over those programs' device seconds, in
percent, from the trace read through the replica's
``program_parts.json`` (``benchmark/part_reduce.py``). Lower is better.
None without a map, without a prefill call in the traced part or without
such a part in it (a parent commit, a CPU, a model without an
indexer)."""
from benchmark import part_reduce
from benchmark.metric_lib import PREFILL

PART = "attn/attn_index"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    return 100.0 * parts[PART] / sum(parts.values())
