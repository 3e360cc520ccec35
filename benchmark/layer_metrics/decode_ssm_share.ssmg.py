"""What the state-space blocks' recurrences leave of a decode step: the
``attn/attn_ssm`` part's device seconds (the ``ssd_step`` kernel's calls,
one an M block and step, with the little XLA work round them: the
decay's exponential, ``dt x``, the groups' B and C laid out as columns)
inside the decode chunks over those programs' device seconds, in
percent, from the trace read through the replica's
``program_parts.json`` (``benchmark/part_reduce.py``), as
``prefill_ssm_share.ssm`` reads the prefill's. Where 23 of 52 blocks are
Mamba-2 mixers beside 23 expert layers it says what the recurrences
leave of a step beside the experts. Lower is better. None without a
map, without a decode chunk in the traced part or without such a part
in it (a parent commit, a CPU, a model without such a layer)."""
from benchmark import part_reduce
from benchmark.metric_lib import DECODE

PART = "attn/attn_ssm"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(DECODE)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    return 100.0 * parts[PART] / sum(parts.values())
