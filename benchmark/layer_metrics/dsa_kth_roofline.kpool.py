"""The selection's kernel (``ops/dsa.py``, ``dsa_kth`` in the trace) in
the PREFILL programs against its roofline where the columns are POOLED
keys, a quarter of the call's rows (the family ``glm5_next``): the least
time the chip could take to read the traced part's whole prefill calls'
keys once over the time their kernel events took. Counted as
``dsa_index_roofline.ishare`` counts (its ``roofline``), the work the
family's ``dsa_kth_work``. The 32 counting passes are the vector unit's
and are not counted, so the share reads low where they bind. None where
the trace holds no such event or no whole prefill call."""
import re

from benchmark import manifest

NAME = "dsa_kth_roofline.kpool"
KERNEL = re.compile(r"^custom-call/\d+out/dsa_kth\b")


def read(facts):
    fam, _ = manifest.model(facts["model"])
    if not hasattr(fam, "pooled_keys"):
        return None
    return manifest.load_python(
        "layer_metrics", "dsa_index_roofline.ishare", manifest.HERE).roofline(
            facts, NAME, KERNEL, "dsa_kth_work", "index", 1)
