"""Replica: what the weights cost at the start, ``weights_build_ms`` +
``weights_cast_ms`` of the ``serve.setup`` mark in seconds: the
``serve.weights_build`` span (the model's tree made or adopted, a drawn
one waited for) and ``serve.weights_cast`` (the serving cast, waited
for). Moves ``setup_s``; lower is better. None without the mark."""
from benchmark import setup_reduce

NAME = "setup_weights_s.serve"


def read(facts):
    return setup_reduce.seconds(facts, NAME, "weights_build_ms",
                                "weights_cast_ms")
