"""The decode attention kernel (``ops/decode_attention.py``,
``decode_attn`` in the trace) against its roofline for a model whose
window layers' rings and full layers' stacks hold rows of DIFFERENT
widths (kv heads by kind, keys wider than values): as
``decode_attn_roofline.doc`` (whose event reader it uses), the sum over
the traced part's kernel events of the least time the chip could take
for each over the sum of the events' measured times.

A step calls the kernel once a layer, on the rings in a window layer and
on the full stacks in a full one; a call must read its layer's live k
and v rows once. The live rows by kind are the engine's own counts,
``live_rows_window`` (the sum over the occupied slots of ``min(pos,
window)``) and ``live_rows_full`` (the sum of ``pos``) of the
``engine.readback`` spans; a row's bytes by kind are the engine's too,
``window_row_bytes`` and ``full_row_bytes`` of the ``engine.state_init``
event (one position's k and v in one layer of the kind, as stored: what
the slots' state takes over its layers, slots and rows); the calls by
kind are the events shared out as the layers are, ``window_layers`` to
``full_layers``. At the HBM's peak: the products never bind at 8 or 16
query rows a kv head. None where the trace holds no such event or the
engine names no row bytes by kind (a parent commit, another model)."""
import statistics
import sys

from benchmark import manifest, model_math, span_reduce

NAME = "decode_attn_roofline.swa"
KINDS = ("window", "full")


def least_seconds(events: int, layers: dict, live_rows: dict,
                  row_bytes: dict, peak: dict) -> float:
    """``events`` kernel calls shared out over the kinds as ``layers``
    {kind: count} is, each reading its kind's ``live_rows`` rows of its
    kind's ``row_bytes``."""
    total = sum(layers.values())
    return sum(events * layers[kind] / total * live_rows[kind]
               * row_bytes[kind] for kind in layers) \
        / peak["hbm_bytes_per_s"]


def read(facts):
    seconds = manifest.load_python(
        "layer_metrics", "decode_attn_roofline.doc",
        manifest.HERE).kernel_seconds(facts.get("trace"))
    if not seconds:
        return None
    need = {f"{kind}_{what}" for kind in KINDS
            for what in ("layers", "row_bytes")}
    init = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                              "engine.state_init")
            if need <= ev[3].keys()]
    span_reduce._say(NAME, len(init), "engine.state_init with row bytes")
    live = {kind: span_reduce.attr_values(
        facts, "engine.readback", f"live_rows_{kind}", metric=NAME)
        for kind in KINDS}
    if not init or not all(live.values()):
        return None
    layers = {kind: init[-1][f"{kind}_layers"] for kind in KINDS}
    row_bytes = {kind: init[-1][f"{kind}_row_bytes"] for kind in KINDS}
    rows = {kind: statistics.mean(xs) for kind, xs in live.items()}
    least = least_seconds(len(seconds), layers, rows, row_bytes,
                          model_math.peaks(facts["device"]["kind"]))
    measured = sum(seconds)
    print(f"benchmark: {NAME}: {len(seconds)} decode_attn events over "
          f"{layers} layers of {row_bytes} B a row, {measured:.4f} s "
          f"measured, least {least:.4f} s (memory; mean live rows {rows})",
          file=sys.stderr, flush=True)
    return 100.0 * least / measured
