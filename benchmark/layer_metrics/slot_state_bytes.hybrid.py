"""Engine: the bytes of device memory one stream's state takes where
recurrent layers stand beside layers that keep k and v rows, from the
engine's own ``engine.state_init`` event (left at the engine's start and
again where a trace starts): (``recurrent_bytes`` + ``full_bytes``) /
``slots``, what the KDA layers' float32 matrices and convolution rows
and the GQA layers' ``max_len`` rows cost a slot. It sets how many long
streams a chip holds beside the weights; lower is better. None where the
trace holds no such event or it does not name both kinds (another
block's state, a parent commit)."""
from benchmark import span_reduce

NAME = "slot_state_bytes.hybrid"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.state_init")
           if ev[3].get("slots")
           and {"recurrent_bytes", "full_bytes"} <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.state_init with both kinds")
    if not evs:
        return None
    return (evs[-1]["recurrent_bytes"] + evs[-1]["full_bytes"]) \
        / evs[-1]["slots"]
