"""``prefill_qkv_share.hybrid`` on a made-up map and trace: the ``qkv``
part's seconds inside the prefill programs over those programs' seconds,
by hand; the ``kda_inputs`` kernel's call counts where its scope puts
it; nothing without a map, a prefill call or the part. No device, no
clock."""

import json

import pytest

from benchmark import manifest, part_reduce, trace_reduce

NAME = "prefill_qkv_share.hybrid"
CELL = "solar-open2-250b-ep8-1chip.longreason-saturated"
PREFILL = "jit__prefill_batch_into_slots"


def _doc(kernel_part="qkv"):
    return {"engine": "decode-1", "seconds": 0.1, "programs": {
        PREFILL: [{"what": "1 x 32768", "parts": {
            "kda_inputs.1": kernel_part, "convolution_fusion.2": "qkv",
            "kda_chunk.3": "attn/attn_linear", "fusion.4": "moe_experts"}}],
        "jit_decode_chunk": [{"what": "16 steps", "parts": {
            "fusion.8": "qkv", "fusion.9": "moe_experts"}}]}}


OPS = [["custom-call/4out/kda_inputs.1", 1_000, 30_000],
       ["convolution_fusion.2", 40_000, 50_000],
       ["custom-call/2out/kda_chunk.3", 100_000, 60_000],
       ["fusion.4", 170_000, 60_000],
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
    """The kernel's 30 us and the product's 50 of the call's 200; the
    decode chunk's ``qkv`` (80 us) is another program's."""
    read = manifest.layer_metric_reader(NAME)
    assert read(_facts(tmp_path, _doc())) == pytest.approx(100 * 80 / 200)


def test_the_kernels_call_counts_where_its_scope_puts_it(tmp_path):
    """Mapped to another part (a call traced outside the ``qkv`` scope)
    the kernel's time leaves the share: what holds the call under
    ``qkv`` is the program's scope, not the reader."""
    read = manifest.layer_metric_reader(NAME)
    assert read(_facts(tmp_path, _doc("attn/attn_linear"))) \
        == pytest.approx(100 * 50 / 200)


@pytest.mark.parametrize("case", ["no_map", "no_prefill_call", "no_part"])
def test_nothing_to_read_is_none(tmp_path, case):
    read = manifest.layer_metric_reader(NAME)
    if case == "no_map":  # (a parent before PR 36, a CPU)
        facts = _facts(tmp_path, None)
    elif case == "no_prefill_call":
        facts = _facts(tmp_path, _doc(), OPS[4:], MODULES[1:])
    else:
        doc = _doc("mlp")
        doc["programs"][PREFILL][0]["parts"]["convolution_fusion.2"] = "mlp"
        facts = _facts(tmp_path, doc)
    assert read(facts) is None


def test_the_manifest_names_it_for_solar_open2s_cell_alone():
    m = manifest.load_manifest()
    by_name = {x["name"]: x for x in m["per_layer"]}
    beside = by_name["prefill_linear_attn_share.hybrid"]
    assert by_name[NAME] == {**beside, "name": NAME, "workloads": [CELL]}
    assert CELL in {w["name"] for w in m["workloads"]}
    moved = {x["name"]: x for x in m["end_to_end"]}["out_tokens_per_s"]
    assert CELL in moved.get("workloads", [CELL])
