"""What a long prompt's prefill call spends between its projections and
its attention: the ``qkv`` part's device seconds (the input norms, the
products with ``w_qkv`` and the decay's and beta's weights, and what a
KDA layer does to them before its delta rule: the convolution, SiLU, the
L2 norms and the decay, since PR 57 the ``kda_inputs`` kernel) inside
the prefill programs over those programs' device seconds, in percent,
from the trace read through the replica's ``program_parts.json``
(``benchmark/part_reduce.py``). Lower is better. None without a map,
without a prefill call in the traced part or without such a part in it
(a parent commit before PR 36, a CPU)."""
from benchmark import part_reduce
from benchmark.metric_lib import PREFILL

PART = "qkv"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    return 100.0 * parts[PART] / sum(parts.values())
