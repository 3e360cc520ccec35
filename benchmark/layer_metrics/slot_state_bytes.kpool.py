"""Engine: the bytes of device memory one stream's state takes where a
slot holds THREE kinds side by side (the family ``glm5_next``): a
float32 recurrent state a KDA layer, the sparse layers' latent rows, and
their index keys POOLED a block of rows (a quarter of a key a position)
with the open block's raw keys, from the engine's own
``engine.state_init`` event (left at the engine's start and again where
a trace starts): (``recurrent_bytes`` + ``latent_bytes`` +
``index_bytes``) / ``slots``, as stored; each kind's share of it goes to
stderr. It sets how many streams a chip holds beside the weights; lower
is better. None where the trace holds no such event or it does not name
the three kinds (another block's state, a parent commit)."""
import sys

from benchmark import span_reduce

NAME = "slot_state_bytes.kpool"
KINDS = ("recurrent", "latent", "index")


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.state_init")
           if ev[3].get("slots")
           and {f"{k}_bytes" for k in KINDS} <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.state_init with three kinds")
    if not evs:
        return None
    last = evs[-1]
    whole = sum(last[f"{k}_bytes"] for k in KINDS)
    print(f"benchmark: {NAME}: of a slot's state " + ", ".join(
        f"{k} {100.0 * last[f'{k}_bytes'] / whole:.1f}% "
        f"({last.get(f'{k}_layers')} layers, "
        f"{last.get(f'{k}_row_bytes')} B a row)" for k in KINDS),
        file=sys.stderr, flush=True)
    return whole / last["slots"]
