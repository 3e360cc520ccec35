"""The full layers' prefill attention kernel (``ops/dsa.py``,
``dsa_attn`` in the trace: flash attention over every earlier key with
the rows the indexer did not choose masked; one call a full layer, live
segment and group of heads) against its roofline: the least time the
chip could take for the work the MODEL asks of the traced part's whole
prefill calls over the time their kernel events took.

The work is the family's (``dsa_attn_work``): two products a CHOSEN
(query, key) pair a head, 192 and 128 wide; q read and o written once,
every head's k and v read once; the larger of matrix-unit and HBM time.
The kernel walks every CAUSAL pair, so past ``index_topk`` rows the
share falls with the chosen pairs' share of the causal ones (2,048 of up
to 32,768 a row): what a prefill that gathers its chosen rows would
win is read here. Events are put to their executions and the rows a
call ran are counted as ``dsa_index_roofline.dsa`` counts them (its
``roofline``; the groups of heads a segment are the family's
``PREFILL_HEAD_GROUPS``). None where the trace holds no such event (a
parent commit, another model) or no whole prefill call."""
import re

from benchmark import manifest

NAME = "dsa_attn_roofline.dsa"
KERNEL = re.compile(r"^custom-call/\d+out/dsa_attn\b")


def read(facts):
    fam, _ = manifest.model(facts["model"])
    groups = getattr(fam, "PREFILL_HEAD_GROUPS", None)
    if not groups:
        return None
    return manifest.load_python(
        "layer_metrics", "dsa_index_roofline.dsa", manifest.HERE).roofline(
            facts, NAME, KERNEL, "dsa_attn_work", groups)
