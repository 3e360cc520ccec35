"""The index scores' kernel (``ops/dsa.py``, ``dsa_index`` in the trace,
one call a full layer and live segment of a prefill) against its
roofline: the least time the chip could take for the traced part's
whole prefill calls' index scores over the time their kernel events
took.

The events are put to the execution of the prefill program they lie in
(first device plane); an execution holds one event a full layer and
segment that RAN, so its count over the full layers gives the rows the
call ran (the segment's rows are ``engine.prefill``'s ``bucket`` over
``segments``). The work is the family's (``dsa_index_work``): one
product ``index_head_dim`` wide an index head a CAUSAL (query, key)
pair, the queries, weights and keys read once and the causal pairs'
float32 scores written; the larger of matrix-unit and HBM time. The
kernel's relu and weighted sum ride the vector unit and are not
counted, so the share reads low where they bind. None where the trace
holds no such event (a parent commit, another model) or no whole
prefill call."""
import bisect
import re
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce
from benchmark.metric_lib import PREFILL

NAME = "dsa_index_roofline.dsa"
# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNEL = re.compile(r"^custom-call/\d+out/dsa_index\b")
WORK = "dsa_index_work"


def by_execution(trace, kernel) -> list:
    """[[seconds of each of the kernel's events]], one entry an
    execution of the prefill program on the first device plane."""
    planes = trace_reduce.device_planes(trace) if trace else []
    if not planes:
        return []
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    runs = sorted((s, s + d) for name, s, d
                  in lines.get(trace_reduce.MODULES_LINE, [])
                  if trace_reduce.program_name(name) == PREFILL)
    starts = [s for s, _ in runs]
    out = [[] for _ in runs]
    for name, s, d in lines.get(trace_reduce.OPS_LINE, []):
        if kernel.match(name):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                out[i].append(d / 1e9)
    return out


def roofline(facts, name: str, kernel, work: str, calls_a_segment: int):
    """The share for ``kernel``, whose events number ``calls_a_segment``
    a full layer and segment that ran, by the family's ``work``."""
    runs = [r for r in by_execution(facts.get("trace"), kernel) if r]
    if not runs:
        return None
    calls = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                               "engine.prefill")
             if {"bucket", "segments"} <= ev[3].keys()]
    span_reduce._say(name, len(calls), "engine.prefill with segments")
    fam, m = manifest.model(facts["model"])
    if not calls or not hasattr(fam, work):
        return None
    segment = calls[-1]["bucket"] // calls[-1]["segments"]
    full = fam.layer_counts(m)["full"]
    peak = model_math.peaks(facts["device"]["kind"])
    most = max(c["segments"] for c in calls)
    buckets = sorted(facts.get("engine", {}).get("prompt_buckets", ())) \
        or [most * segment]
    least = measured = 0.0
    whole = 0
    for run in runs:
        segments, rest = divmod(len(run), full * calls_a_segment)
        if rest or not 0 < segments <= most:
            continue  # (an execution the trace cut)
        whole += 1
        rows = segments * segment
        bucket = min([b for b in buckets if b >= rows] or buckets[-1:])
        least += full * model_math.roofline_seconds(
            *getattr(fam, work)(m, rows, bucket), peak)[0]
        measured += sum(run)
    print(f"benchmark: {name}: {whole} whole prefill calls of {len(runs)} "
          f"with such events, segments of {segment} rows, {measured:.4f} s "
          f"measured, least {least:.4f} s", file=sys.stderr, flush=True)
    return 100.0 * least / measured if measured else None


def read(facts):
    return roofline(facts, NAME, KERNEL, WORK, 1)
