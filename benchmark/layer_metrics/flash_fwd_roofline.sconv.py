"""The prefill's flash kernel (``ops/flash_attention.py``: ``flash_fwd``,
the forward-only call at a traced offset, one a full layer and segment
of rows) at heads of 64 against its roofline: the least time the chip
could take for the traced part's whole prefill calls over the time
their kernel events took.

The events are put to the execution of the prefill program they lie in
(``flash_fwd_roofline.swa``'s reader). An execution of a P-row call
holds a ``flash_fwd`` event a full layer and LIVE segment; the P it is
charged is what ran, its live segments' rows (``engine.prefill``'s
``bucket`` over ``segments`` a segment): an execution the trace cut,
whose events do not divide by the full layers, is left out with its
events. The work is the family's ``flash_calls`` at 32 / 8 heads of 64
through ``model_math``: the causal half counted once, two products a
pair; q read and o written once, k and v read once; the larger of
matrix-unit and HBM time. None where the trace holds no such event (a
parent commit, another model) or no whole prefill call."""
import sys

from benchmark import manifest, model_math, span_reduce

NAME = "flash_fwd_roofline.sconv"


def read(facts):
    runs = [r["full"] for r in manifest.load_python(
        "layer_metrics", "flash_fwd_roofline.swa",
        manifest.HERE).by_execution(facts.get("trace")) if r["full"]]
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
    full = fam.layer_counts(m)["full"]
    peak = model_math.peaks(facts["device"]["kind"])
    least = measured = 0.0
    whole = 0
    for run in runs:
        segments = len(run) // full
        if not segments or len(run) != segments * full:
            continue  # (an execution the trace cut)
        whole += 1
        for n, b, s, h, kv, hd in fam.flash_calls(m, 1, segments * segment):
            least += n * model_math.roofline_seconds(
                model_math.flash_flops(b, s, h, hd, backward=False),
                model_math.flash_bytes(b, s, h, kv, hd, backward=False),
                peak)[0]
        measured += sum(run)
    print(f"benchmark: {NAME}: {whole} whole prefill calls of {len(runs)} "
          f"with flash events, segments of {segment} rows, {measured:.4f} s "
          f"measured, least {least:.4f} s", file=sys.stderr, flush=True)
    return 100.0 * least / measured if measured else None
