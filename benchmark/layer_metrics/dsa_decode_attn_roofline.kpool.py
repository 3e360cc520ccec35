"""The decode step's masked attention kernel (``ops/dsa.py``,
``dsa_decode_attn`` in the trace) against its roofline over latent rows
WITHOUT a rotated key (512 numbers a row) of which the model asks for
the chosen blocks' and the tail's (the family ``glm5_next``): the sum
over the traced part's kernel events of the least time the chip could
take for the work the MODEL asks of each over the sum of the events'
measured times. ``attended_rows`` of the ``engine.readback`` spans over
the layers that attend (``latent_layers`` of ``engine.state_init``),
times the row's bytes as stored, at the HBM's peak, beside the products
over those rows (the family's ``decode_attn_work``); the larger bound is
taken. The kernel reads every LIVE row, so the share falls with the
chosen rows' share of the live ones. None where the trace holds no such
event (a parent commit, another model) or no such span."""
import re
import statistics
import sys

from benchmark import manifest, model_math, span_reduce, trace_reduce

NAME = "dsa_decode_attn_roofline.kpool"
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
            if ev[3].get("latent_layers") and "recurrent_layers" in ev[3]]
    handed = span_reduce.attr_values(facts, "engine.readback",
                                     "attended_rows", metric=NAME)
    fam, m = manifest.model(facts["model"])
    if not init or not handed or not hasattr(fam, "pooled_keys"):
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
