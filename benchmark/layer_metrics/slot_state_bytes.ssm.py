"""Engine: the bytes of device memory one session's state takes where
state-space layers stand beside a layer that keeps k and v rows, from
the engine's own ``engine.state_init`` event (left at the engine's start
and again where a trace starts): (``recurrent_bytes`` + ``full_bytes``)
/ ``slots``, what the Mamba-2 layers' float32 states and convolution
rows and the attention layer's ``max_len`` rows cost a slot; the
recurrent share of it, which does not grow with the length, goes to
stderr. It sets how many sessions a chip holds beside the weights; lower
is better. None where the trace holds no such event or it does not name
both kinds (another block's state, a parent commit)."""
import sys

from benchmark import span_reduce

NAME = "slot_state_bytes.ssm"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.state_init")
           if ev[3].get("slots")
           and {"recurrent_bytes", "full_bytes"} <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.state_init with both kinds")
    if not evs:
        return None
    last = evs[-1]
    whole = last["recurrent_bytes"] + last["full_bytes"]
    print(f"benchmark: {NAME}: {100.0 * last['recurrent_bytes'] / whole:.1f}"
          f"% of a slot is recurrent state ({last.get('recurrent_layers')} "
          f"layers), the rest {last.get('full_layers')} layer(s) of "
          f"{last.get('max_len')} rows", file=sys.stderr, flush=True)
    return whole / last["slots"]
