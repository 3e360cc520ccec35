"""The prefill's masked attention kernel (``ops/dsa.py``, ``dsa_attn`` in
the trace: flash attention over every earlier key with the rows the
selection left out masked) against its roofline where EVERY layer
attends over a selection and only some make one (the family
``glm_moe_dsa``): the least time the chip could take for the work the
MODEL asks of the traced part's whole prefill calls over the time their
kernel events took.

One event a SPARSE layer (every layer: the family's ``layer_counts``),
live segment and group of heads (``PREFILL_HEAD_GROUPS``). The work is
the family's (``dsa_attn_work``): two products a CHOSEN (query, key)
pair a head, 256 and 256 wide; q read and o written once, every head's
k and v read once; the larger of matrix-unit and HBM time. The kernel
walks every CAUSAL pair, so past ``index_topk`` rows the share falls
with the chosen pairs' share of the causal ones: what a prefill that
gathers its chosen rows would win is read here. Counted as
``dsa_index_roofline.ishare`` counts (its ``roofline``). None where the
trace holds no such event (a parent commit, another model) or no whole
prefill call."""
import re

from benchmark import manifest

NAME = "dsa_attn_roofline.ishare"
KERNEL = re.compile(r"^custom-call/\d+out/dsa_attn\b")


def read(facts):
    fam, _ = manifest.model(facts["model"])
    groups = getattr(fam, "PREFILL_HEAD_GROUPS", None)
    if not groups:
        return None
    return manifest.load_python(
        "layer_metrics", "dsa_index_roofline.ishare", manifest.HERE).roofline(
            facts, NAME, KERNEL, "dsa_attn_work", "sparse", groups)
