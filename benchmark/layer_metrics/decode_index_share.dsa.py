"""What the indexer costs a decode step where full layers attend over
rows a learned indexer chooses: the ``attn/attn_index`` part's device
seconds (every live index key of a slot scored, the ``index_topk`` best
selected exactly and turned into positions) inside the decode chunks
over those programs' device seconds, in percent, from the trace read
through the replica's ``program_parts.json``
(``benchmark/part_reduce.py``). Lower is better. None without a map,
without a decode chunk in the traced part or without such a part in it
(a parent commit, a CPU, a model without an indexer)."""
from benchmark import part_reduce
from benchmark.metric_lib import DECODE

PART = "attn/attn_index"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(DECODE)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    return 100.0 * parts[PART] / sum(parts.values())
