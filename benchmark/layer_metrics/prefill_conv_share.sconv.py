"""What the gated short convolutions leave of a prompt's prefill call:
the ``attn/attn_conv`` part's device seconds (``b * x``, the taps over a
segment's rows, the gate ``c *``: elementwise passes over ``[rows,
hidden]`` between the mixer's two products) inside the prefill programs
over those programs' device seconds, in percent, from the trace read
through the replica's ``program_parts.json``
(``benchmark/part_reduce.py``). The mixer's products lie under ``qkv``
and ``attn_out`` with the attention layers' own: their joint share goes
to stderr. Lower is better. None without a map, without a prefill call
in the traced part or without such a part in it (a parent commit, a CPU,
a model without such a layer)."""
import sys

from benchmark import part_reduce
from benchmark.metric_lib import PREFILL

NAME = "prefill_conv_share.sconv"
PART = "attn/attn_conv"


def read(facts):
    t = part_reduce.table(facts)
    parts = (t or {"programs": {}})["programs"].get(PREFILL)
    if not parts or not sum(parts.values()) or PART not in parts:
        return None
    whole = sum(parts.values())
    print(f"benchmark: {NAME}: qkv + attn_out (the mixers' products, the "
          f"attention layers' among them) "
          f"{100.0 * (parts.get('qkv', 0.0) + parts.get('attn_out', 0.0)) / whole:.1f}"
          "% of the prefill programs", file=sys.stderr, flush=True)
    return 100.0 * parts[PART] / whole
