"""The Mamba-2 decode step's kernel (``ops/ssd_step.py``, ``ssd_step`` in
the trace) against its roofline: the sum over the traced part's kernel
events of the least time the chip could take for each over the sum of
the events' measured times.

A call must read the layer's state ``H`` ONCE and write it ONCE, for
every slot of the engine (the kernel visits every slot: one that is not
active gets back what was read, so its bytes move too): the family's
``ssd_step_bytes(m, slots)`` with ``slots`` of the engine's
``engine.state_init`` event, at the HBM's peak. The vectors and the
output beside them are under a hundredth of that and are left out, so
the share reads a little low, never high; the products (4 a number of
``H``) are nowhere near the peak and are not counted. The events' count
is the engagement counter (chunks x steps a chunk x Mamba layers: 144 a
chunk in the sessions cell) and goes to stderr with the times. None
where the trace holds no such event (a parent commit, a model without
such a layer) or no such span."""
import re
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce

NAME = "ssd_step_roofline.ssm"
# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNEL = re.compile(r"^custom-call/\d+out/ssd_step\b")


def kernel_seconds(trace) -> list:
    """Seconds of each ``ssd_step`` event of the first device plane."""
    planes = trace_reduce.device_planes(trace) if trace else []
    return [d / 1e9 for plane in planes[:1] for line in plane["lines"]
            if line["name"] == trace_reduce.OPS_LINE
            for name, _, d in line["events"] if KERNEL.match(name)]


def read(facts):
    seconds = kernel_seconds(facts.get("trace"))
    if not seconds:
        return None
    slots = span_reduce.attr_values(
        facts, "engine.state_init", "slots", metric=NAME,
        where=lambda a: "recurrent_bytes" in a)
    if not slots:
        return None
    fam, m = manifest.model(facts["model"])
    if not hasattr(fam, "ssd_step_bytes"):
        return None
    one = fam.ssd_step_bytes(m, slots[-1]) \
        / model_math.peaks(facts["device"]["kind"])["hbm_bytes_per_s"]
    least, measured = len(seconds) * one, sum(seconds)
    print(f"benchmark: {NAME}: {len(seconds)} ssd_step events, "
          f"{measured:.4f} s measured, least {least:.4f} s "
          f"({1e6 * one:.1f} us a call: {slots[-1]} slots' state read and "
          "written)", file=sys.stderr, flush=True)
    return 100.0 * least / measured
