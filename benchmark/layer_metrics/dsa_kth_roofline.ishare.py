"""The selection's kernel (``ops/dsa.py``, ``dsa_kth`` in the trace: the
k-th largest index score of every row) in the PREFILL programs against
its roofline where only some layers own an indexer (the family
``glm_moe_dsa``): the least time the chip could take to read the traced
part's whole prefill calls' keys once over the time their kernel events
took.

One event an INDEX layer and segment that ran (the layers behind an
indexer layer select nothing: they read its choice); a segment's rows
hold the call's ``bucket`` int32 keys each (the family's
``dsa_kth_work``), at the HBM's peak. The 32 counting passes are the
vector unit's and are not counted, so the share reads low where they
bind. A decode chunk's events are left out. Counted as
``dsa_index_roofline.ishare`` counts (its ``roofline``). None where the
trace holds no such event (a parent commit, another model) or no whole
prefill call."""
import re

from benchmark import manifest

NAME = "dsa_kth_roofline.ishare"
KERNEL = re.compile(r"^custom-call/\d+out/dsa_kth\b")


def read(facts):
    return manifest.load_python(
        "layer_metrics", "dsa_index_roofline.ishare", manifest.HERE).roofline(
            facts, NAME, KERNEL, "dsa_kth_work", "index", 1)
