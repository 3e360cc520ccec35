"""Engine step of a model that holds a part of its experts: of the
expert-layer calls of the traced part's prefills that HAD the compact
branch (``moe.moe``: the held assignments counted on the device, a
capacity C from the shapes), the share that took it, in percent. The
``engine.readback`` spans that carry a cold prefill's counts carry
``moe_expert_calls`` and ``moe_compact_calls``, their sums over the
call's layers and live segments. 100 is a router that never sent this
device over twice its uniform share; a call that did not fit ran at every
assignment's row, with the same result. None where no span carries the
attrs (a parent commit, a block whose shapes give no capacity)."""
from benchmark import span_reduce

NAME = "moe_compact_call_share.reason"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.readback")
           if {"moe_expert_calls", "moe_compact_calls"} <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.readback with compact calls")
    calls = sum(a["moe_expert_calls"] for a in evs)
    return 100.0 * sum(a["moe_compact_calls"] for a in evs) / calls \
        if calls else None
