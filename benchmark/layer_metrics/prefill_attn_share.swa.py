"""What attention proper leaves of a long prompt's prefill call in a
model of window layers beside full ones: the ``attn`` part's device
seconds inside the prefill programs (``attn/attn_window``: the banded
flash kernel; ``attn/attn_full``: the flash kernel over the rows so far)
over those programs' device seconds, in percent, from the trace read
through the replica's ``program_parts.json``
(``benchmark/part_reduce.py``). The two kinds go to stderr apart. Lower
is better. None without a map, without a prefill call in the traced part
or without either kind among its parts (a parent commit, a CPU, another
model)."""
import sys

from benchmark import part_reduce
from benchmark.metric_lib import PREFILL

NAME = "prefill_attn_share.swa"
KINDS = ("attn/attn_window", "attn/attn_full")


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()) \
            or not any(kind in parts for kind in KINDS):
        return None
    total = sum(parts.values())
    print(f"benchmark: {NAME}: of the prefill programs' {total:.4f} s "
          + ", ".join(f"{kind} {100.0 * parts.get(kind, 0.0) / total:.2f}%"
                      for kind in KINDS), file=sys.stderr, flush=True)
    return 100.0 * sum(s for p, s in parts.items()
                       if p.split("/")[0] == "attn") / total
