"""Engine step of a mixture-of-experts model: distinct experts that got
a row from an active slot, per decode step and layer; the mean of the
``experts_touched`` attr over the ``engine.readback`` spans of the
traced part (each the mean over its chunk's steps and layers). Fewer
experts are fewer bytes a step must read. A program whose read-back
carries no such attr (a dense model, a parent commit) gives None."""
import statistics

from benchmark import span_reduce


def read(facts):
    xs = span_reduce.attr_values(facts, "engine.readback", "experts_touched",
                                 metric="moe_experts_touched.doc")
    return float(statistics.mean(xs)) if xs else None
