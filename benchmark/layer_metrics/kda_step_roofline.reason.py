"""The KDA decode step's kernel (``ops/kda_step.py``, ``kda_step`` in the
trace) against its roofline: the sum over the traced part's kernel
events of the least time the chip could take for each over the sum of
the events' measured times.

A call must read the layer's state ``S`` ONCE and write it ONCE, for
every slot of the engine (the kernel visits every slot: one that is not
active gets back what was read, so its bytes move too): ``slots`` of the
engine's ``engine.state_init`` event x heads x ``dk`` x ``dv`` x 4 B
(float32) x 2, at the HBM's peak. The vectors and the output beside
them are a hundredth of that and are left out, so the share reads a
little low, never high; the products (8 a number of ``S``) are nowhere
near the peak and are not counted. The events' count is the engagement
counter (chunks x steps a chunk x KDA layers: 96 a chunk in the reason
cell) and goes to stderr with the times. None where the trace holds no
such event (a parent commit, a model without such a layer) or no such
span."""
import bisect
import re
import statistics
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce
from benchmark.metric_lib import DECODE

NAME = "kda_step_roofline.reason"
# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNEL = re.compile(r"^custom-call/\d+out/kda_step\b")


def _first_plane(trace, line_name) -> list:
    """The events of one line of the first device plane."""
    planes = trace_reduce.device_planes(trace) if trace else []
    return [event for plane in planes[:1] for line in plane["lines"]
            if line["name"] == line_name for event in line["events"]]


def kernel_events(trace) -> list:
    """(start, nanoseconds) of each ``kda_step`` event of the first
    device plane."""
    return [(s, d) for name, s, d in _first_plane(trace, trace_reduce.OPS_LINE)
            if KERNEL.match(name)]


def kernel_seconds(trace) -> list:
    """Seconds of each ``kda_step`` event of the first device plane."""
    return [d / 1e9 for _, d in kernel_events(trace)]


def calls_a_chunk(trace):
    """The median count of ``kda_step`` events inside one execution of
    the decode chunk on the first device plane (a chunk the trace cut at
    either end holds fewer), and the executions' count."""
    starts = sorted(s for s, _ in kernel_events(trace))
    chunks = [(s, s + d)
              for name, s, d in _first_plane(trace, trace_reduce.MODULES_LINE)
              if trace_reduce.program_name(name) == DECODE]
    if not chunks:
        return None, 0
    return statistics.median(
        bisect.bisect_left(starts, e) - bisect.bisect_left(starts, s)
        for s, e in chunks), len(chunks)


def state_bytes(m: dict, slots: int) -> int:
    """One layer's float32 state over ``slots`` slots, in bytes."""
    return slots * m["n_heads"] * m["kda_head_dim"] ** 2 * 4


def read(facts):
    seconds = kernel_seconds(facts.get("trace"))
    if not seconds:
        return None
    slots = span_reduce.attr_values(
        facts, "engine.state_init", "slots", metric=NAME,
        where=lambda a: "recurrent_bytes" in a)
    if not slots:
        return None
    _, m = manifest.model(facts["model"])
    one = 2 * state_bytes(m, slots[-1]) \
        / model_math.peaks(facts["device"]["kind"])["hbm_bytes_per_s"]
    least, measured = len(seconds) * one, sum(seconds)
    a_chunk, chunks = calls_a_chunk(facts["trace"])
    print(f"benchmark: {NAME}: {len(seconds)} kda_step events"
          + (f" ({a_chunk:.1f} a decode chunk of {chunks})" if chunks else "")
          + f", {measured:.4f} s measured, least {least:.4f} s "
          f"({1e6 * one:.1f} us a call: {slots[-1]} slots' state read and "
          "written)", file=sys.stderr, flush=True)
    return 100.0 * least / measured
