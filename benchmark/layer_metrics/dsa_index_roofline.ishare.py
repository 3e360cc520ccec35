"""The index scores' kernel (``ops/dsa.py``, ``dsa_index`` in the trace)
against its roofline where only SOME layers own an indexer and the
layers behind them read its choice (the family ``glm_moe_dsa``): the
least time the chip could take for the traced part's whole prefill
calls' index scores over the time their kernel events took.

``dsa_index_roofline.dsa`` as it stands, with the layers counted by
KIND: an execution of the prefill program holds one ``dsa_index`` event
an INDEX layer and segment that ran (the family's ``layer_counts``:
``index`` for the indexer's two kernels, ``sparse`` for the attention's,
which every layer calls), so the events of an execution over that count
give the rows the call ran; the events are put to their executions by
that file's ``by_execution``. The work is the family's
(``dsa_index_work``: one product ``index_head_dim`` wide an index head a
CAUSAL (query, key) pair, queries, weights and keys read once, the
causal pairs' float32 scores written), the larger of matrix-unit and HBM
time; the relu and the weighted sum ride the vector unit and are not
counted, so the share reads low where they bind. None where the trace
holds no such event (a parent commit, another model), the family counts
no such kind or no prefill call is whole."""
import re
import sys

from benchmark import manifest, model_math, span_reduce

NAME = "dsa_index_roofline.ishare"
# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNEL = re.compile(r"^custom-call/\d+out/dsa_index\b")


def roofline(facts, name: str, kernel, work: str, kind: str,
             calls_a_segment: int):
    """The share for ``kernel``, whose events number ``calls_a_segment``
    a layer of ``kind`` and segment that ran, by the family's ``work``
    (one layer's, all of a prompt's calls together)."""
    by_execution = manifest.load_python(
        "layer_metrics", "dsa_index_roofline.dsa", manifest.HERE).by_execution
    runs = [r for r in by_execution(facts.get("trace"), kernel) if r]
    if not runs:
        return None
    calls = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                               "engine.prefill")
             if {"bucket", "segments"} <= ev[3].keys()]
    span_reduce._say(name, len(calls), "engine.prefill with segments")
    fam, m = manifest.model(facts["model"])
    layers = getattr(fam, "layer_counts", lambda m: {})(m).get(kind)
    if not calls or not layers or not hasattr(fam, work):
        return None
    segment = calls[-1]["bucket"] // calls[-1]["segments"]
    peak = model_math.peaks(facts["device"]["kind"])
    most = max(c["segments"] for c in calls)
    buckets = sorted(facts.get("engine", {}).get("prompt_buckets", ())) \
        or [most * segment]
    least = measured = 0.0
    whole = 0
    for run in runs:
        segments, rest = divmod(len(run), layers * calls_a_segment)
        if rest or not 0 < segments <= most:
            continue  # (an execution the trace cut)
        whole += 1
        rows = segments * segment
        bucket = min([b for b in buckets if b >= rows] or buckets[-1:])
        least += layers * model_math.roofline_seconds(
            *getattr(fam, work)(m, rows, bucket), peak)[0]
        measured += sum(run)
    print(f"benchmark: {name}: {whole} whole prefill calls of {len(runs)} "
          f"with such events, {layers} {kind} layers, segments of {segment} "
          f"rows, {measured:.4f} s measured, least {least:.4f} s",
          file=sys.stderr, flush=True)
    return 100.0 * least / measured if measured else None


def read(facts):
    return roofline(facts, NAME, KERNEL, "dsa_index_work", "index", 1)
