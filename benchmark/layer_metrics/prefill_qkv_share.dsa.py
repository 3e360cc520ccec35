"""What a long prompt's prefill call spends before its attention in the
two blocks whose layers attend over rows an indexer chooses
(``models/dots.py``, ``models/glm_dsa.py``): the ``qkv`` part's device
seconds (the input norms, the q / latent / index projections with their
norms, the rotation, and each group of heads' k and v out of the
latents: since PR 61 of the rows the segment can see alone,
``dots._live_kv``; the zeros that loop's pair starts a segment from are
a broadcast whose scope the compiler drops, and read under ``loop``)
inside the prefill programs over those programs' device seconds, in
percent, from the trace read through the replica's
``program_parts.json`` (``benchmark/part_reduce.py``). Lower is better.
None without a map, without a prefill call in the traced part or
without such a part in it (a parent commit before PR 36, a CPU)."""
from benchmark import part_reduce
from benchmark.metric_lib import PREFILL

PART = "qkv"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    return 100.0 * parts[PART] / sum(parts.values())
