"""Engine: the bytes of device memory one stream's state takes, from the
engine's own ``engine.state_init`` event (left at the engine's start and
again where a trace starts): (``window_bytes`` + ``full_bytes``) /
``slots``, what the sliding layers' rings and the full layers' rows cost
a slot. It sets how many streams a chip holds beside the weights; lower
is better. None where the trace holds no such event or it names neither
kind (a model whose rows are of one kind, a parent commit)."""
from benchmark import span_reduce

NAME = "slot_state_bytes.mix"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.state_init")
           if ev[3].get("slots") and ("window_bytes" in ev[3]
                                      or "full_bytes" in ev[3])]
    span_reduce._say(NAME, len(evs), "engine.state_init")
    if not evs:
        return None
    a = evs[-1]
    return (a.get("window_bytes", 0) + a.get("full_bytes", 0)) / a["slots"]
