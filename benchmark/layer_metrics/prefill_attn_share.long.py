"""What attention proper leaves of a long prompt's prefill call: the
``attn`` part's device seconds inside the prefill programs over those
programs' device seconds, in percent, from the trace read through the
replica's ``program_parts.json`` (``benchmark/part_reduce.py``). With
the prompt's scores formed whole this would grow with the square of the
bucket; through the flash kernel it is the kernel's time. Lower is
better. None without a map or without a prefill call in the traced part
(a parent commit, a CPU)."""
from benchmark import part_reduce
from benchmark.metric_lib import PREFILL


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()):
        return None
    return 100.0 * sum(s for p, s in parts.items()
                       if p.split("/")[0] == "attn") / sum(parts.values())
