"""Engine programs, host side: ``trace_ms`` + ``lower_ms`` of the
``serve.setup`` mark in seconds, summed over the engine's
``engine.compiled`` marks: the Python that turns each program (the
chunk, a prefill call a bucket) into a jaxpr and the jaxpr into a
module, outermost stages only. A warm compile cache does not shorten
it: it is what a layer's body traced once, or the layers as a scan,
can take. Moves ``setup_s``; lower is better. None without the mark."""
from benchmark import setup_reduce

NAME = "setup_trace_lower_s.serve"


def read(facts):
    return setup_reduce.seconds(facts, NAME, "trace_ms", "lower_ms")
