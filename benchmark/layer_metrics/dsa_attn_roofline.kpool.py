"""The prefill's masked attention kernel (``ops/dsa.py``, ``dsa_attn`` in
the trace) against its roofline at heads WITHOUT a rotated part (256 + 0
/ 256: one product a tile) over the blocks an indexer with pooled keys
chose (the family ``glm5_next``): the least time the chip could take for
the work the MODEL asks of the traced part's whole prefill calls over
the time their kernel events took. One event a SPARSE layer, live
segment and group of heads (``PREFILL_HEAD_GROUPS``); the work is the
family's ``dsa_attn_work`` (two products a CHOSEN (query, key) pair a
head, the chosen blocks' rows and the tail's). The kernel walks every
CAUSAL pair, so past ``index_topk`` rows the share falls with the chosen
pairs' share of the causal ones. Counted as ``dsa_index_roofline.ishare``
counts. None where the trace holds no such event or no whole prefill
call."""
import re

from benchmark import manifest

NAME = "dsa_attn_roofline.kpool"
KERNEL = re.compile(r"^custom-call/\d+out/dsa_attn\b")


def read(facts):
    fam, _ = manifest.model(facts["model"])
    groups = getattr(fam, "PREFILL_HEAD_GROUPS", None)
    if not groups or not hasattr(fam, "pooled_keys"):
        return None
    return manifest.load_python(
        "layer_metrics", "dsa_index_roofline.ishare", manifest.HERE).roofline(
            facts, NAME, KERNEL, "dsa_attn_work", "sparse", groups)
