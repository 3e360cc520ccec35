"""Engine: the bytes of device memory one stream's state takes where
every layer keeps latent rows, from the engine's own
``engine.state_init`` event (left at the engine's start and again where
a trace starts): ``latent_bytes`` / ``slots``, what ``max_len`` stored
rows a layer cost a slot. It sets how many long streams a chip holds
beside the weights; lower is better. None where the trace holds no such
event or it names latent rows beside another kind of state (the hybrid
block: ``slot_state_bytes.reason``) or none (a parent commit)."""
from benchmark import span_reduce

NAME = "slot_state_bytes.long"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.state_init")
           if ev[3].get("slots") and "latent_layers" in ev[3]]
    span_reduce._say(NAME, len(evs), "engine.state_init with latent_layers")
    if not evs:
        return None
    return evs[-1]["latent_bytes"] / evs[-1]["slots"]
