"""The decode step's masked attention kernel (``ops/dsa.py``,
``dsa_decode_attn`` in the trace: the slots' live latent rows read in
blocks, the rows the selection left out masked) against its roofline
where EVERY layer attends over a selection and only some make one (the
family ``glm_moe_dsa``): the sum over the traced part's kernel events of
the least time the chip could take for the work the MODEL asks of each
over the sum of the events' measured times.

The model asks a call to read the rows it was handed as CHOSEN, once (a
latent row is key and value): ``attended_rows`` of the
``engine.readback`` spans (a step's sum over active slots and ALL layers
of the chosen rows their attention was handed, the mean over the chunk's
steps) over the layers that attend (``latent_layers`` of
``engine.state_init``: every layer keeps latent rows), times the row's
bytes as stored, at the HBM's peak, beside the products over those rows
(the family's ``decode_attn_work``); the larger bound is taken. The
kernel reads every LIVE row, so the share falls with the chosen rows'
share of the live ones (2,048 of about 19,000 a slot here). None where
the trace holds no such event (a parent commit, another model) or no
such span."""
import re
import statistics
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce

NAME = "dsa_decode_attn_roofline.ishare"
# (``trace_reduce.op_name`` names a custom call by what it returns)
KERNEL = re.compile(r"^custom-call/\d+out/dsa_decode_attn\b")


def read(facts):
    trace = facts.get("trace")
    planes = trace_reduce.device_planes(trace) if trace else []
    seconds = [d / 1e9 for plane in planes[:1] for line in plane["lines"]
               if line["name"] == trace_reduce.OPS_LINE
               for name, _, d in line["events"] if KERNEL.match(name)]
    if not seconds:
        return None
    sp = span_reduce.spans(facts)
    init = [ev[3] for ev in span_reduce.named(sp, "engine.state_init")
            if ev[3].get("latent_layers")]
    handed = span_reduce.attr_values(facts, "engine.readback",
                                     "attended_rows", metric=NAME)
    fam, m = manifest.model(facts["model"])
    if not init or not handed or not hasattr(fam, "decode_attn_work"):
        return None
    a_call = statistics.mean(handed) / init[-1]["latent_layers"]
    one, bound = model_math.roofline_seconds(
        *fam.decode_attn_work(m, a_call),
        model_math.peaks(facts["device"]["kind"]))
    least, measured = len(seconds) * one, sum(seconds)
    print(f"benchmark: {NAME}: {len(seconds)} dsa_decode_attn events, "
          f"{measured:.4f} s measured, least {least:.4f} s ({bound}; "
          f"{a_call:.0f} chosen rows a call)", file=sys.stderr, flush=True)
    return 100.0 * least / measured
