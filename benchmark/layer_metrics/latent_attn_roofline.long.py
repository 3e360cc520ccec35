"""The latent decode attention kernel (``ops/decode_attention.py``,
``decode_attn_latent`` in the trace) against its roofline: the sum over
the traced part's kernel events of the least time the chip could take
for each over the sum of the events' measured times.

A call must read the live rows of its layer ONCE (a latent row is key
and value): the slots' rows that hold something, ``live_rows`` of the
``engine.readback`` spans (the sum over the occupied slots of their
position at the chunk's end: up to a chunk's steps a slot more than the
mean over the chunk, which reads the share a little high, under a
hundredth at these lengths), times the row's bytes AS STORED (the
family's ``latent_attn_bytes``: latent ‖ rotated key padded to whole
lanes, what a block's DMA brings in), at the HBM's peak. The products
(the family's ``latent_attn_flops``) are counted beside them and the
larger bound taken; at 16 query rows a block the bytes bind. Every
event takes the mean over the spans. None where the trace holds no such
event (a parent commit, a model without latent rows of this layout) or
no such span."""
import re
import statistics
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce

NAME = "latent_attn_roofline.long"
# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNEL = re.compile(r"^custom-call/\d+out/decode_attn_latent\b")


def kernel_seconds(trace) -> list:
    """Seconds of each ``decode_attn_latent`` event of the first device
    plane."""
    planes = trace_reduce.device_planes(trace) if trace else []
    return [d / 1e9 for plane in planes[:1] for line in plane["lines"]
            if line["name"] == trace_reduce.OPS_LINE
            for name, _, d in line["events"] if KERNEL.match(name)]


def read(facts):
    seconds = kernel_seconds(facts.get("trace"))
    if not seconds:
        return None
    rows = span_reduce.attr_values(facts, "engine.readback", "live_rows",
                                   metric=NAME)
    if not rows:
        return None
    fam, m = manifest.model(facts["model"])
    live = statistics.mean(rows)
    one, bound = model_math.roofline_seconds(
        fam.latent_attn_flops(live, m), fam.latent_attn_bytes(live, m),
        model_math.peaks(facts["device"]["kind"]))
    least, measured = len(seconds) * one, sum(seconds)
    print(f"benchmark: {NAME}: {len(seconds)} decode_attn_latent events, "
          f"{measured:.4f} s measured, least {least:.4f} s ({bound}; mean "
          f"live rows {live:.1f})", file=sys.stderr, flush=True)
    return 100.0 * least / measured
