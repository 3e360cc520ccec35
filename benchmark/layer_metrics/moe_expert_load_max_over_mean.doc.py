"""Engine step of a mixture-of-experts model: how uneven a prefill's
routing is. Over the ``engine.readback`` spans that carry a cold
prefill's counts: ``expert_load_max`` (assignments of the prompt's real
positions on the fullest expert of a layer) over ``expert_load_mean``
(the mean over experts); the median of the spans' ratios. 1 is an even
spread; the fullest expert's group is the longest run of row tiles in
the grouped matmul. None where no span carries the attrs."""
import statistics

from benchmark import span_reduce


NAME = "moe_expert_load_max_over_mean.doc"


def read(facts):
    fullest, mean = (span_reduce.attr_values(
        facts, "engine.readback", key, metric=NAME)
        for key in ("expert_load_max", "expert_load_mean"))
    ratios = [a / b for a, b in zip(fullest, mean) if b]
    return float(statistics.median(ratios)) if ratios else None
