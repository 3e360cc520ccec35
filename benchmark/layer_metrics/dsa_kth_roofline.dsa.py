"""The selection's kernel (``ops/dsa.py``, ``dsa_kth`` in the trace: the
k-th largest index score of every row, 32 counting passes over a block
of rows that is read once) in the PREFILL programs against its roofline:
the least time the chip could take to read the traced part's whole
prefill calls' keys once over the time their kernel events took.

One event a full layer and segment that ran; a segment's rows hold the
call's ``bucket`` int32 keys each (the family's ``dsa_kth_work``; the
bucket is the least of the engine's that holds the rows the call ran),
at the HBM's peak. The passes themselves are the vector unit's and are
not counted, so the share reads low where they bind: it says how far
the selection is from costing what reading its scores costs. A decode
chunk's events (32 rows a call) are left out. Events are put to their
executions as ``dsa_index_roofline.dsa`` puts them (its ``roofline``).
None where the trace holds no such event (a parent commit, another
model) or no whole prefill call."""
import re

from benchmark import manifest

NAME = "dsa_kth_roofline.dsa"
KERNEL = re.compile(r"^custom-call/\d+out/dsa_kth\b")


def read(facts):
    return manifest.load_python(
        "layer_metrics", "dsa_index_roofline.dsa", manifest.HERE).roofline(
            facts, NAME, KERNEL, "dsa_kth_work", 1)
