"""The decode attention kernel (``ops/decode_attention.py``,
``decode_attn`` in the trace) against its roofline: the sum over the
traced part's kernel events of the least time the chip could take for
each over the sum of the events' measured times.

A call must read the live k and v rows of its layer once: the slots'
rows that hold something, ``live_rows`` of the ``engine.readback`` spans
(the sum over the occupied slots of their position at the chunk's end:
up to a chunk's steps a slot more than the mean over the chunk, which
reads the share about a hundredth high), times 2 x kv heads x head
width x the cache's item size, at the HBM's peak. FLOPs never bind at
one or two query rows a kv head. Every event takes the mean over the
spans. None where the trace holds no such event (a parent commit, a
model with a step of its own) or no such span."""
import re
import statistics
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce

# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNEL = re.compile(r"^custom-call/\d+out/decode_attn\b")


def kernel_seconds(trace) -> list:
    """Seconds of each ``decode_attn`` event of the first device plane."""
    planes = trace_reduce.device_planes(trace) if trace else []
    return [d / 1e9 for plane in planes[:1] for line in plane["lines"]
            if line["name"] == trace_reduce.OPS_LINE
            for name, _, d in line["events"] if KERNEL.match(name)]


def live_bytes(m: dict, live_rows: float, itemsize: int = 2) -> float:
    """Bytes one call must read: ``live_rows`` rows of k and of v."""
    hd = m["d_model"] // m["n_heads"]
    return live_rows * 2 * m["n_kv_heads"] * hd * itemsize


def read(facts):
    seconds = kernel_seconds(facts.get("trace"))
    if not seconds:
        return None
    rows = span_reduce.attr_values(facts, "engine.readback", "live_rows",
                                   metric="decode_attn_roofline.doc")
    if not rows:
        return None
    _, m = manifest.model(facts["model"])
    peak = model_math.peaks(facts["device"]["kind"])
    least = len(seconds) * live_bytes(m, statistics.mean(rows)) \
        / peak["hbm_bytes_per_s"]
    measured = sum(seconds)
    print(f"benchmark: decode_attn_roofline.doc: {len(seconds)} decode_attn "
          f"events, {measured:.4f} s measured, least {least:.4f} s "
          f"(memory; mean live rows {statistics.mean(rows):.1f})",
          file=sys.stderr, flush=True)
    return 100.0 * least / measured
