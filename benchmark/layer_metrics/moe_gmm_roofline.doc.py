"""The grouped matmul kernel (``ops/grouped_matmul.py``, ``moe_gmm`` in
the trace) against its roofline: the sum over the traced part's kernel
events of the least time the chip could take for each, max(FLOPs / peak,
bytes / peak), over the sum of the events' measured times.

An event's rows and output columns are read from its own HLO line
(``%moe_gmm.3 = bf16[8192,1024]{...} custom-call(...)``), the contracted
width from the model (gate and up: d_model -> d_ff; down: d_ff ->
d_model). FLOPs and bytes are the family's (``gmm_flops``,
``gmm_bytes``). The experts a call must read: for a decode step's call
(rows of at most slots x top_k) the mean ``experts_touched`` of the
``engine.readback`` spans; for a prefill's call the family's expected
count for its tokens (all 64 from a few hundred tokens on). None where
the trace holds no such event (a dense model, a parent commit)."""
import glob
import os
import re
import statistics
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce

KERNEL = re.compile(r"^%?moe_gmm[.\d]*\s*=\s*\w+\[(\d+),(\d+)\]")


def kernel_events(log_dir) -> list:
    """[(rows, output columns, seconds)]: the ``moe_gmm`` events of the
    first device plane of the newest trace under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir or "", "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        return [(int(m[1]), int(m[2]), ev.duration_ns / 1e9)
                for line in plane.lines if line.name == trace_reduce.OPS_LINE
                for ev in line.events for m in [KERNEL.match(ev.name)] if m]
    return []


def roofline_pct(events, fam, m: dict, slots: int, decode_touched, peak):
    """``events`` [(rows, columns, seconds)] -> percent, and which bound
    held most of the least time (to stderr)."""
    least = measured = 0.0
    by_bound = {"compute": 0.0, "memory": 0.0}
    decode_rows = -(-slots * m["top_k"] // 16) * 16
    for rows, n, seconds in events:
        k = m["d_model"] if n == m["d_ff"] else m["d_ff"]
        if rows <= decode_rows:
            touched = decode_touched if decode_touched is not None \
                else fam.experts_touched(m, slots)
        else:
            touched = fam.experts_touched(m, rows / m["top_k"])
        t, bound = model_math.roofline_seconds(
            fam.gmm_flops(rows, k, n), fam.gmm_bytes(rows, k, n, touched),
            peak)
        least += t
        by_bound[bound] += t
        measured += seconds
    print(f"benchmark: moe_gmm_roofline.doc: {len(events)} moe_gmm events, "
          f"{measured:.4f} s measured, least {least:.4f} s "
          f"({by_bound})", file=sys.stderr, flush=True)
    return 100.0 * least / measured if measured else None


def read(facts):
    events = facts.get("moe_gmm_events")
    if events is None:
        events = kernel_events(facts.get("log_dir"))
    if not events:
        return None
    fam, m = manifest.model(facts["model"])
    xs = span_reduce.attr_values(facts, "engine.readback", "experts_touched",
                                 metric="moe_gmm_roofline.doc")
    return roofline_pct(
        events, fam, m, facts["engine"]["slots"],
        statistics.mean(xs) if xs else None,
        model_math.peaks(facts["device"]["kind"]))
