"""The index scores' kernel (``ops/dsa.py``, ``dsa_index`` in the trace)
against its roofline where the indexer's keys are POOLED a block of rows
(the family ``glm5_next``: keys a quarter of the rows, one sparse layer
in the cell): the least time the chip could take for the traced part's
whole prefill calls' index scores over the time their kernel events
took. Counted as ``dsa_index_roofline.ishare`` counts (its ``roofline``:
one event an ``index`` layer and segment that ran), the work the
family's ``dsa_index_work`` (one product an index head a (query, WHOLE
pooled key) pair). None where the trace holds no such event (a parent
commit, another model) or no prefill call is whole."""
import re

from benchmark import manifest

NAME = "dsa_index_roofline.kpool"
KERNEL = re.compile(r"^custom-call/\d+out/dsa_index\b")


def read(facts):
    fam, _ = manifest.model(facts["model"])
    if not hasattr(fam, "pooled_keys"):
        return None
    return manifest.load_python(
        "layer_metrics", "dsa_index_roofline.ishare", manifest.HERE).roofline(
            facts, NAME, KERNEL, "dsa_index_work", "index", 1)
