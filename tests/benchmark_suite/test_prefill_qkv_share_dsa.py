"""``prefill_qkv_share.dsa`` on a made-up map and trace of a sparse
block's prefill call: the ``qkv`` part's seconds (the projections, the
pair's zeros and the loop that makes a group's k and v of the live rows,
``dots._live_kv``) inside the prefill programs over those programs'
seconds, by hand; the loop's product counts where its scope puts it;
nothing without a map, a prefill call or the part. No device, no
clock."""

import json

import pytest

from benchmark import manifest, part_reduce, trace_reduce

NAME = "prefill_qkv_share.dsa"
CELLS = ["dots3-note-prev-ep8-1chip.longreason-saturated-24",
         "glm-5.2-ep16-1chip.longreason-saturated-16"]
PREFILL = "jit__prefill_batch_into_slots"


def _doc(loop_part="qkv"):
    return {"engine": "decode-1", "seconds": 0.1, "programs": {
        PREFILL: [{"what": "1 x 32768", "parts": {
            "convolution_fusion.2": "qkv", "fusion.828": loop_part,
            "dsa_index.3": "attn/attn_index",
            "dsa_attn.13": "attn/attn_sparse", "fusion.4": "moe_experts"}}],
        "jit_decode_chunk": [{"what": "16 steps", "parts": {
            "fusion.8": "qkv", "fusion.9": "moe_experts"}}]}}


# (the loop's product runs once a live chunk: three events of one name)
OPS = [["convolution_fusion.2", 1_000, 20_000],
       ["fusion.828", 30_000, 10_000], ["fusion.828", 41_000, 10_000],
       ["fusion.828", 52_000, 10_000],
       ["custom-call/1out/dsa_index.3", 70_000, 30_000],
       ["custom-call/1out/dsa_attn.13", 100_000, 80_000],
       ["fusion.4", 190_000, 40_000],
       ["fusion.8", 410_000, 80_000], ["fusion.9", 500_000, 20_000]]
MODULES = [[PREFILL + "(1)", 0, 400_000],
           ["jit_decode_chunk(2)", 400_000, 200_000]]


def _facts(tmp_path, doc, ops=OPS, modules=MODULES):
    if doc is not None:
        (tmp_path / part_reduce.FILE).write_text(json.dumps(doc))
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": trace_reduce.OPS_LINE, "events": ops},
        {"name": "XLA Modules", "events": list(modules)}]}]}
    return {"spans": None, "trace": trace,
            "log_dir": str(tmp_path) if doc is not None else None}


def test_the_share_is_the_qkv_part_of_the_prefill_programs(tmp_path):
    """The projections' 20 us and three chunks' 30 of the call's 200; the
    decode chunk's ``qkv`` (80 us) is another program's."""
    read = manifest.layer_metric_reader(NAME)
    assert read(_facts(tmp_path, _doc())) == pytest.approx(100 * 50 / 200)


def test_the_loops_product_counts_where_its_scope_puts_it(tmp_path):
    """Mapped to ``loop`` (a product traced outside the ``qkv`` scope)
    the chunks' time leaves the share: what holds the loop under ``qkv``
    is the program's scope, not the reader."""
    read = manifest.layer_metric_reader(NAME)
    assert read(_facts(tmp_path, _doc("loop"))) \
        == pytest.approx(100 * 20 / 200)


@pytest.mark.parametrize("case", ["no_map", "no_prefill_call", "no_part"])
def test_nothing_to_read_is_none(tmp_path, case):
    read = manifest.layer_metric_reader(NAME)
    if case == "no_map":  # (a CPU)
        facts = _facts(tmp_path, None)
    elif case == "no_prefill_call":
        facts = _facts(tmp_path, _doc(), OPS[-2:], MODULES[1:])
    else:
        doc = _doc("loop")
        doc["programs"][PREFILL][0]["parts"]["convolution_fusion.2"] = "mlp"
        facts = _facts(tmp_path, doc)
    assert read(facts) is None


def test_the_manifest_names_it_for_the_two_sparse_cells_alone():
    m = manifest.load_manifest()
    by_name = {x["name"]: x for x in m["per_layer"]}
    beside = by_name["prefill_qkv_share.hybrid"]
    assert by_name[NAME] == {**beside, "name": NAME, "workloads": CELLS}
    assert m["per_layer"][-1]["name"] == NAME  # (appended, nothing moved)
    assert set(CELLS) <= {w["name"] for w in m["workloads"]}
    moved = {x["name"]: x for x in m["end_to_end"]}["out_tokens_per_s"]
    assert set(CELLS) <= set(moved.get("workloads", CELLS))
    # the cells' other shares of the prefill call read the same table
    for other in ("prefill_sparse_attn_share.dsa", "prefill_index_share.dsa"):
        assert set(by_name[other]["workloads"]) == set(CELLS)
