"""Engine: the bytes of device memory one stream's state takes in a
model whose rows differ in width by kind, from the engine's own
``engine.state_init`` event (left at the engine's start and again where
a trace starts): (``window_bytes`` + ``full_bytes``) / ``slots``, what
the window layers' rings and the full layers' rows cost a slot, as
stored (a padded layout would count its padding). It sets how many
streams a chip holds beside the weights; lower is better. None where the
trace holds no such event or it gives no row bytes by kind (another
model, a parent commit)."""
from benchmark import span_reduce

NAME = "slot_state_bytes.swa"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.state_init")
           if ev[3].get("slots") and {"window_bytes", "full_bytes",
                                      "window_row_bytes",
                                      "full_row_bytes"} <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.state_init")
    if not evs:
        return None
    a = evs[-1]
    return (a["window_bytes"] + a["full_bytes"]) / a["slots"]
