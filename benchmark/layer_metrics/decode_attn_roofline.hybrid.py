"""The decode attention kernel (``ops/decode_attention.py``,
``decode_attn`` in the trace) against its roofline for a model whose
softmax layers stand beside recurrent ones: as ``decode_attn_roofline.doc``
(whose event reader it uses), the sum over the traced part's kernel
events of the least time the chip could take for each over the sum of
the events' measured times.

Only the full layers call the kernel (a recurrent layer keeps no rows),
so every event reads one full layer's live k and v rows once:
``live_rows_full`` of the ``engine.readback`` spans (the sum over the
occupied slots of their position at the chunk's end, up to a chunk's
steps a slot more than the mean over the chunk: about a thousandth high
at this cell's lengths), times the family's ``kv_row_bytes`` (2 x kv
heads x the model's own ``head_dim`` x the cache's item size), at the
HBM's peak. None where the trace holds no such event (a parent commit)
or the engine names no recurrent layers beside the full ones."""
import statistics
import sys

from benchmark import manifest, model_math, span_reduce

NAME = "decode_attn_roofline.hybrid"


def read(facts):
    seconds = manifest.load_python(
        "layer_metrics", "decode_attn_roofline.doc",
        manifest.HERE).kernel_seconds(facts.get("trace"))
    if not seconds:
        return None
    init = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                              "engine.state_init")
            if {"recurrent_layers", "full_layers"} <= ev[3].keys()]
    span_reduce._say(NAME, len(init), "engine.state_init with layer kinds")
    rows = span_reduce.attr_values(facts, "engine.readback",
                                   "live_rows_full", metric=NAME)
    if not init or not rows:
        return None
    fam, m = manifest.model(facts["model"])
    peak = model_math.peaks(facts["device"]["kind"])
    live = statistics.mean(rows)
    least = len(seconds) * live * fam.kv_row_bytes(m) \
        / peak["hbm_bytes_per_s"]
    measured = sum(seconds)
    print(f"benchmark: {NAME}: {len(seconds)} decode_attn events in "
          f"{init[-1]['full_layers']} full layers, {measured:.4f} s "
          f"measured, least {least:.4f} s (memory; mean live rows "
          f"{live:.1f})", file=sys.stderr, flush=True)
    return 100.0 * least / measured
