"""Engine step of a model whose full layers attend over rows an indexer
chooses: the rows the model asked for over the latent rows the step's
attention READ, in percent. Over the ``engine.readback`` spans of the
traced part: ``selected_rows`` (a step's sum over active slots and full
layers of the chosen rows, the mean over the chunk's steps) over the
rows read, which for a step that reads every live row and masks (what
this program does; the family's ``STEP_READS`` says which) are
``index_layers`` x ``live_rows_full`` (about 11% at 19,000 live rows)
and for a step that gathers its chosen rows are those rows themselves
(100%). How far under 100 it reads is what reading the chosen rows
alone would save; the two sums go to stderr. None where the
read-back carries no such counter (a parent commit, another model)."""
import statistics
import sys

from benchmark import manifest, span_reduce

NAME = "dsa_rows_read_share.dsa"


def read(facts):
    sp = span_reduce.spans(facts)
    init = [ev[3] for ev in span_reduce.named(sp, "engine.state_init")
            if "index_layers" in ev[3]]
    back = [ev[3] for ev in span_reduce.named(sp, "engine.readback")
            if {"selected_rows", "live_rows_full"} <= ev[3].keys()]
    span_reduce._say(NAME, len(back), "engine.readback with selected_rows")
    if not init or not back:
        return None
    layers = init[-1]["index_layers"]
    selected = statistics.mean(a["selected_rows"] for a in back)
    live = statistics.mean(a["live_rows_full"] for a in back)
    fam, _ = manifest.model(facts["model"])
    gathers = getattr(fam, "STEP_READS", "chosen") == "chosen"
    read_rows = selected if gathers else layers * live
    print(f"benchmark: {NAME}: a step selects {selected:.0f} rows over "
          f"{layers} layers of {live:.0f} live rows in all (the step reads "
          f"{'the chosen rows' if gathers else 'every live row'})",
          file=sys.stderr, flush=True)
    return 100.0 * selected / read_rows if read_rows else None
