"""The prefill's flash kernels (``ops/flash_attention.py``: ``flash_fwd``
in the full layers and ``flash_fwd_window`` in the window layers, one
call a layer and segment of rows) against their roofline: the least time
the chip could take for the traced part's whole prefill calls over the
time their kernel events took.

The events are put to the execution of the prefill program they lie in
(first device plane). An execution of a P-row call holds P / segment
events a layer, so its count of ``flash_fwd`` events over the full
layers gives its segments and with them P (the segment's rows are the
program's, ``engine.prefill``'s ``bucket`` over ``segments``); an
execution the trace cut, whose counts fit no bucket, is left out with
its events. The work is the family's (``prefill_flash_work``): a full
layer's causal pairs, a window layer's BAND of pairs (never the
triangle's: a kernel that walks more blocks than the band reads low),
two products a pair 192 and 128 wide a query head; q read and o written
once, k and v read once; the larger of matrix-unit and HBM time a layer.
None where the trace holds no such event (a parent commit, another
model) or no whole prefill call."""
import bisect
import re
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce
from benchmark.metric_lib import PREFILL

NAME = "flash_fwd_roofline.swa"
# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNELS = {"full": re.compile(r"^custom-call/\d+out/flash_fwd(\.\d+)?$"),
           "window": re.compile(r"^custom-call/\d+out/flash_fwd_window\b")}


def by_execution(trace) -> list:
    """[{kind: [seconds of each of its kernel events]}], one entry an
    execution of the prefill program on the first device plane."""
    planes = trace_reduce.device_planes(trace) if trace else []
    if not planes:
        return []
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    runs = sorted((s, s + d) for name, s, d
                  in lines.get(trace_reduce.MODULES_LINE, [])
                  if trace_reduce.program_name(name) == PREFILL)
    starts = [s for s, _ in runs]
    out = [{kind: [] for kind in KERNELS} for _ in runs]
    for name, s, d in lines.get(trace_reduce.OPS_LINE, []):
        for kind, rx in KERNELS.items():
            if rx.match(name):
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < runs[i][1]:
                    out[i][kind].append(d / 1e9)
    return out


def read(facts):
    runs = [r for r in by_execution(facts.get("trace")) if any(r.values())]
    if not runs:
        return None
    calls = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                               "engine.prefill")
             if {"bucket", "segments"} <= ev[3].keys()]
    span_reduce._say(NAME, len(calls), "engine.prefill with segments")
    if not calls:
        return None
    segment = calls[-1]["bucket"] // calls[-1]["segments"]
    fam, m = manifest.model(facts["model"])
    layers = fam.layer_counts(m)
    peak = model_math.peaks(facts["device"]["kind"])
    least = measured = 0.0
    whole = 0
    for run in runs:
        segments = len(run["full"]) // max(layers["full"], 1)
        if not segments or len(run["full"]) != segments * layers["full"] \
                or len(run["window"]) != segments * layers["window"]:
            continue  # (an execution the trace cut)
        whole += 1
        for kind in KERNELS:
            flops, nbytes = fam.prefill_flash_work(m, segments * segment,
                                                   kind)
            least += layers[kind] * model_math.roofline_seconds(
                flops, nbytes, peak)[0]
            measured += sum(run[kind])
    print(f"benchmark: {NAME}: {whole} whole prefill calls of {len(runs)} "
          f"with flash events, segments of {segment} rows, {measured:.4f} s "
          f"measured, least {least:.4f} s", file=sys.stderr, flush=True)
    return 100.0 * least / measured if measured else None
