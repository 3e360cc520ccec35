"""Engine step of a model with sliding-window layers beside full ones:
what the windows leave of an all-full cache's reads, in percent. Over
the ``engine.readback`` spans of the traced part, (``window_layers`` x
``live_rows_window`` + ``full_layers`` x ``live_rows_full``) over (all
layers x ``live_rows_full``): the k and v rows a step's attention reads
against those it would read were every layer full (``live_rows_full``
is the sum over the occupied slots of ``pos``, ``live_rows_window`` of
``min(pos, window)``; the layer counts are the ``engine.state_init``
event's). Lower is better: the rows not read are bytes a step does not
move. None where the engine names no kinds of rows (a model whose rows
are of one kind, a parent commit)."""
from benchmark import span_reduce

NAME = "attn_rows_read_share.mix"


def share_pct(layers: dict, live: dict) -> float | None:
    """``layers`` {"window", "full"} counts, ``live`` their summed live
    rows -> percent of the all-full reads."""
    all_full = (layers["window"] + layers["full"]) * live["full"]
    read = sum(layers[kind] * live[kind] for kind in layers)
    return 100.0 * read / all_full if all_full else None


def read(facts):
    sp = span_reduce.spans(facts)
    init = [ev[3] for ev in span_reduce.named(sp, "engine.state_init")
            if {"window_layers", "full_layers"} <= ev[3].keys()]
    back = [ev[3] for ev in span_reduce.named(sp, "engine.readback")
            if {"live_rows_window", "live_rows_full"} <= ev[3].keys()]
    span_reduce._say(NAME, len(back), "engine.readback with rows by kind")
    if not init or not back:
        return None
    return share_pct(
        {kind: init[-1][f"{kind}_layers"] for kind in ("window", "full")},
        {kind: sum(a[f"live_rows_{kind}"] for a in back)
         for kind in ("window", "full")})
