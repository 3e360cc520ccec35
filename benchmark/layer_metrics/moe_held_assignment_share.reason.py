"""Engine step of a model that holds a part of its experts: of the
(token, expert) assignments the active slots' decode steps made, the
share that fell on experts held here, in percent: the sum of the
``held_assignments`` attr over the sum of ``assignments`` over the
``engine.readback`` spans of the traced part (each the mean over its
chunk's steps and expert layers). It is what the cut does to the expert
layer's work: two of eight groups held is 25% under uniform routing,
and a router that favours the held groups reads higher. A program whose
read-back carries no such attrs (every expert held, a dense model, a
parent commit) gives None."""
from benchmark import span_reduce

NAME = "moe_held_assignment_share.reason"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.readback")
           if {"assignments", "held_assignments"} <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.readback with assignments")
    total = sum(a["assignments"] for a in evs)
    return 100.0 * sum(a["held_assignments"] for a in evs) / total \
        if total else None
