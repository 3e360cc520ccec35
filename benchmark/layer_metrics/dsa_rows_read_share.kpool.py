"""Engine step of a model whose sparse layers read the BLOCKS of rows an
indexer with pooled keys chooses and the open block behind them (the
family ``glm5_next``): the rows the model asked its attentions to read
over the latent rows the step's attention READ, in percent. Over the
``engine.readback`` spans of the traced part: ``attended_rows`` (a step's
sum over active slots and sparse layers of the rows their attention was
handed, the chosen blocks' and the tail's, the mean over the chunk's
steps) over the rows read, which for a step that reads every live row
and masks (what this program does; the family's ``STEP_READS`` says
which) are ``latent_layers`` x ``live_rows_latent`` and for a step that
gathers its chosen rows are those rows themselves (100%).
``index_keys_scored`` (the pooled keys of whole blocks and the open
block's raw keys the indexers held) goes to stderr beside them: over
``live_rows_latent`` it is what pooling leaves of the indexer's reads, a
quarter. None where the read-back carries no such counter (a parent
commit, another model)."""
import statistics
import sys

from benchmark import manifest, span_reduce

NAME = "dsa_rows_read_share.kpool"


def read(facts):
    sp = span_reduce.spans(facts)
    init = [ev[3] for ev in span_reduce.named(sp, "engine.state_init")
            if "latent_layers" in ev[3] and "recurrent_layers" in ev[3]]
    back = [ev[3] for ev in span_reduce.named(sp, "engine.readback")
            if {"attended_rows", "index_keys_scored", "live_rows_latent"}
            <= ev[3].keys()]
    span_reduce._say(NAME, len(back), "engine.readback with "
                                      "index_keys_scored")
    if not init or not back:
        return None
    layers = init[-1]["latent_layers"]
    attended = statistics.mean(a["attended_rows"] for a in back)
    scored = statistics.mean(a["index_keys_scored"] for a in back)
    live = statistics.mean(a["live_rows_latent"] for a in back)
    fam, _ = manifest.model(facts["model"])
    gathers = getattr(fam, "STEP_READS", "chosen") == "chosen"
    read_rows = attended if gathers else layers * live
    print(f"benchmark: {NAME}: a step's {layers} sparse layers hold "
          f"{scored:.0f} index keys for {live:.0f} live rows a layer and "
          f"attend {attended:.0f} rows (the step reads "
          f"{'the chosen rows' if gathers else 'every live row'})",
          file=sys.stderr, flush=True)
    return 100.0 * attended / read_rows if read_rows else None
