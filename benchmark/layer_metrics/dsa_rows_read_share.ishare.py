"""Engine step of a model whose EVERY layer attends over rows an indexer
chooses, the choice made in some layers and read by those behind them
(the family ``glm_moe_dsa``): the rows the model asked its attentions
to read over the latent rows the step's attention READ, in percent.
Over the ``engine.readback`` spans of the traced part: ``attended_rows``
(a step's sum over active slots and ALL layers of the chosen rows their
attention was handed, the mean over the chunk's steps: five layers'
worth for two selections) over the rows read, which for a step that
reads every live row and masks (what this program does; the family's
``STEP_READS`` says which) are ``latent_layers`` x ``live_rows_latent``
(every layer keeps latent rows and attends: the engine names a kind's
layers and live rows by the kind) and for a step that gathers its
chosen rows are those rows themselves (100%). ``selected_rows`` (the
INDEXER layers' sum) goes to stderr beside them: ``attended_rows`` over
it is how many attentions a selection serves. None where the read-back
carries no such counter (a parent commit, another model)."""
import statistics
import sys

from benchmark import manifest, span_reduce

NAME = "dsa_rows_read_share.ishare"


def read(facts):
    sp = span_reduce.spans(facts)
    init = [ev[3] for ev in span_reduce.named(sp, "engine.state_init")
            if "latent_layers" in ev[3]]
    back = [ev[3] for ev in span_reduce.named(sp, "engine.readback")
            if {"attended_rows", "selected_rows", "live_rows_latent"}
            <= ev[3].keys()]
    span_reduce._say(NAME, len(back), "engine.readback with attended_rows")
    if not init or not back:
        return None
    layers = init[-1]["latent_layers"]
    attended = statistics.mean(a["attended_rows"] for a in back)
    selected = statistics.mean(a["selected_rows"] for a in back)
    live = statistics.mean(a["live_rows_latent"] for a in back)
    fam, _ = manifest.model(facts["model"])
    gathers = getattr(fam, "STEP_READS", "chosen") == "chosen"
    read_rows = attended if gathers else layers * live
    print(f"benchmark: {NAME}: a step's {init[-1].get('index_layers')} "
          f"indexer layers select {selected:.0f} rows, its {layers} layers "
          f"attend {attended:.0f} of them over {live:.0f} live rows a layer "
          f"(the step reads "
          f"{'the chosen rows' if gathers else 'every live row'})",
          file=sys.stderr, flush=True)
    return 100.0 * attended / read_rows if read_rows else None
