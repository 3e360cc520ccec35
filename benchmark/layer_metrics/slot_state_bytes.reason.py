"""Engine: the bytes of device memory one stream's state takes, from the
engine's own ``engine.state_init`` event (left at the engine's start and
again where a trace starts): (``recurrent_bytes`` + ``latent_bytes``) /
``slots``, what the KDA layers' float32 matrices and convolution rows
and the MLA layer's latent rows cost a slot. It sets how many streams a
chip holds beside the weights; lower is better. None where the trace
holds no such event or it names neither kind (a model whose state is
rows of k and v, a parent commit)."""
from benchmark import span_reduce

NAME = "slot_state_bytes.reason"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.state_init")
           if ev[3].get("slots") and ("recurrent_bytes" in ev[3]
                                      or "latent_bytes" in ev[3])]
    span_reduce._say(NAME, len(evs), "engine.state_init")
    if not evs:
        return None
    a = evs[-1]
    return (a.get("recurrent_bytes", 0) + a.get("latent_bytes", 0)) \
        / a["slots"]
