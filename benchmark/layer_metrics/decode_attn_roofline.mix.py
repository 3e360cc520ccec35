"""The decode attention kernel (``ops/decode_attention.py``,
``decode_attn`` in the trace) against its roofline for a model whose
layers keep rows of two kinds, sliding-window rings beside full stacks:
as ``decode_attn_roofline.doc`` (whose event reader it uses), the sum
over the traced part's kernel events of the least time the chip could
take for each over the sum of the events' measured times.

A step calls the kernel once a layer, on the ring in a sliding layer and
on the full stack in a full one; a call must read its layer's live k and
v rows once. The live rows by kind are the engine's own counts,
``live_rows_window`` (the sum over the occupied slots of ``min(pos,
window)``) and ``live_rows_full`` (the sum of ``pos``) of the
``engine.readback`` spans, and the calls by kind are the events shared
out as the layers are, ``window_layers`` to ``full_layers`` of the
``engine.state_init`` event. A row is 2 x kv heads x the model's own
``head_dim`` (a field here, not ``d_model // n_heads``) x the cache's
item size, at the HBM's peak. None where the trace holds no such event
(a parent commit) or the engine names no kinds of rows (a model whose
rows are of one kind)."""
import statistics
import sys

from benchmark import manifest, model_math, span_reduce

NAME = "decode_attn_roofline.mix"


def least_seconds(events: int, layers: dict, live_rows: dict,
                  row_bytes: float, peak: dict) -> float:
    """``events`` kernel calls shared out over the kinds as ``layers``
    {kind: count} is, each reading its kind's ``live_rows`` rows."""
    total = sum(layers.values())
    return sum(events * layers[kind] / total * live_rows[kind] * row_bytes
               for kind in layers) / peak["hbm_bytes_per_s"]


def read(facts):
    seconds = manifest.load_python(
        "layer_metrics", "decode_attn_roofline.doc",
        manifest.HERE).kernel_seconds(facts.get("trace"))
    if not seconds:
        return None
    init = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                              "engine.state_init")
            if {"window_layers", "full_layers"} <= ev[3].keys()]
    span_reduce._say(NAME, len(init), "engine.state_init with layer kinds")
    live = {kind: span_reduce.attr_values(
        facts, "engine.readback", f"live_rows_{kind}", metric=NAME)
        for kind in ("window", "full")}
    if not init or not all(live.values()):
        return None
    fam, m = manifest.model(facts["model"])
    layers = {kind: init[-1][f"{kind}_layers"] for kind in live}
    rows = {kind: statistics.mean(xs) for kind, xs in live.items()}
    least = least_seconds(len(seconds), layers, rows, fam.kv_row_bytes(m),
                          model_math.peaks(facts["device"]["kind"]))
    measured = sum(seconds)
    print(f"benchmark: {NAME}: {len(seconds)} decode_attn events over "
          f"{layers} layers, {measured:.4f} s measured, least "
          f"{least:.4f} s (memory; mean live rows {rows})",
          file=sys.stderr, flush=True)
    return 100.0 * least / measured
