"""Engine step: the prompts' real tokens over the rows the prefill calls
of the traced part RAN, in percent. A segmented block's call runs the
``live_segments`` of its bucket's ``segments`` that hold a row of the
prompt (``moe.in_segments``), each of ``bucket // segments`` rows; what
is left under 100 is the padding inside a prompt's last live segment.
``prefill_token_use_share.doc`` beside it charges the whole bucket.
None where no ``engine.prefill`` span carries ``live_segments`` (a
parent commit, whose calls ran every segment)."""
from benchmark import span_reduce

NAME = "prefill_rows_run_share.doc"


def read(facts):
    evs = [ev[3] for ev in span_reduce.named(span_reduce.spans(facts),
                                             "engine.prefill")
           if {"tokens", "bucket", "segments", "live_segments"}
           <= ev[3].keys()]
    span_reduce._say(NAME, len(evs), "engine.prefill with live_segments")
    ran = sum(a["live_segments"] * (a["bucket"] // a["segments"])
              for a in evs)
    return 100.0 * sum(a["tokens"] for a in evs) / ran if ran else None
