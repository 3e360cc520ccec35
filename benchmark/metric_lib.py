"""What the per-layer readers share. A reader takes the run's facts
(client records, counter deltas, the trace as plain data, the cell's
sizes) and returns its number, or None where there is nothing to read.
"""

from __future__ import annotations

import statistics

from benchmark import model_math, stats, trace_reduce

PREFILL = "jit__prefill_batch_into_slots"
DECODE = "jit_decode_chunk"
# The four pallas_call sites of ops/flash_attention.py give no ``name=``,
# so XLA names them after their scope; they are told apart by what they
# return: the forward (out, lse), every backward kernel three arrays or,
# the legacy pair, dq alone / (dk, dv) with six operands. The only
# custom calls in the train step are these kernels.
FLASH_FWD = r"^custom-call/2out/"
FLASH_BWD = r"^custom-call/3out/"


def client_ms(facts, key: str, q: float | None = None):
    xs = [x for x in facts.get("client", {}).get(key, []) if x == x]
    if not xs:
        return None
    return 1e3 * (stats.median(xs) if q is None else stats.percentile(xs, q))


def program_share_pct(facts, program: str):
    share = trace_reduce.program_share(facts["trace"], program)
    return None if share is None else 100.0 * share


def program_median_ms(facts, program: str):
    durs = trace_reduce.program_durations(facts["trace"]).get(program)
    return 1e3 * statistics.median(durs) if durs else None


def step_program(facts):
    """The train step is the program that took most of the traced time."""
    durs = trace_reduce.program_durations(facts["trace"])
    return max(durs, key=lambda k: sum(durs[k])) if durs else None


def flash_roofline_pct(facts, pattern: str, *, backward: bool):
    """Least time the chip could take for the kernel's calls of one step
    (per device) over the time its events took, per step."""
    t = facts["train"]
    per_plane = trace_reduce.op_seconds(facts["trace"], pattern)
    prog = step_program(facts)
    if not per_plane or not sum(per_plane) or prog is None:
        return None
    planes = len(per_plane)
    steps = len(trace_reduce.program_durations(facts["trace"])[prog]) / planes
    m = t["model"]
    hd = m["d_model"] // m["n_heads"]
    peak = model_math.peaks(facts["device"]["kind"])
    # one call per layer per step; each device holds 1/chips of batch x heads
    flops = m["n_layers"] * model_math.flash_flops(
        t["batch"], t["seq"], m["n_heads"], hd, backward=backward) / t["chips"]
    nbytes = m["n_layers"] * model_math.flash_bytes(
        t["batch"], t["seq"], m["n_heads"], m["n_kv_heads"], hd,
        backward=backward) / t["chips"]
    least, _bound = model_math.roofline_seconds(flops, nbytes, peak)
    measured = statistics.mean(per_plane) / steps
    return 100.0 * least / measured
