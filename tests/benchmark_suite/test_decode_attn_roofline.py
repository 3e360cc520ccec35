"""``decode_attn_roofline.doc`` by hand on a small hand-made trace: the
live rows' bytes of both doc cells' head layouts, what the reader does
with a program that has no such kernel or no such span, and which HLO
lines are the kernel's."""

import pytest

from benchmark import manifest

NAME = "decode_attn_roofline.doc"


def _spans(*live_rows):
    return {"lines": [{"name": "python", "events": [
        ["engine.readback", 2000 + 9000 * i, 7000,
         {"live_rows": n, "cache_rows": 8 * 1296}]
        for i, n in enumerate(live_rows)]}]}


def _trace(*micros):
    """A device plane whose operations are the kernel's calls of
    ``micros`` us each, each after a fusion that is none of its own."""
    ops = []
    for i, us in enumerate(micros):
        ops += [["fusion.201", 100_000 * i, 30_000],
                [f"custom-call/1out/decode_attn.{12 + i % 2}",
                 100_000 * i + 30_000, int(1000 * us)]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules",
             "events": [["jit_decode_chunk(1)", 0, 1_000_000]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": []}]}


def _facts(model, **more):
    return {"spans": _spans(5000, 5400), "model": model,
            "engine": {"slots": 8}, "device": {"kind": "TPU v5 lite"},
            "log_dir": None, "trace": _trace(50, 40, 60), **more}


@pytest.mark.parametrize("model,kv_heads", [
    ("internlm2-1.8b", 8), ("olmoe-1b-7b-0125-1chip", 16)])
def test_the_kernels_roofline_on_a_small_trace(model, kv_heads):
    read = manifest.layer_metric_reader(NAME)
    # three calls of 50, 40 and 60 us; a call reads 5,200 rows of k and
    # of v, bf16
    least = 5200 * 2 * kv_heads * 128 * 2 / 819e9
    want = 100 * 3 * least / 150e-6
    assert read(_facts(model)) == pytest.approx(want, rel=1e-9)
    assert (52 < want < 53) if kv_heads == 8 else (104 < want < 105)
    # no kernel event (the parent, a model with its own step), no trace
    assert read(_facts(model, trace=_trace())) is None
    assert read(_facts(model, trace=None)) is None
    # the kernel without the read-back's count: nothing to weigh it by
    bare = {"lines": [{"name": "python", "events": [
        ["engine.readback", 2000, 7000, {}]]}]}
    assert read(_facts(model, spans=bare)) is None
    assert read(_facts(model, spans=None)) is None


def test_which_events_are_the_kernels():
    module = manifest.load_python("layer_metrics", NAME, manifest.HERE)
    from benchmark import trace_reduce

    line = ("%decode_attn.12 = bf16[8,8,16,128]{3,2,1,0:T(8,128)(2,1)} "
            "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"")
    assert module.KERNEL.match(trace_reduce.op_name(line))
    assert module.KERNEL.match(trace_reduce.op_name(
        "%decode_attn = bf16[8,8,16,128]{} custom-call(%a)"))
    for other in ("%moe_gmm.7 = bf16[64,1024]{} custom-call(%a)",
                  "%decode_attn_x.1 = bf16[8]{} custom-call(%a)",
                  "%fusion.3 = bf16[8,2048]{1,0} fusion(%decode_attn.1)"):
        assert module.KERNEL.match(trace_reduce.op_name(other)) is None
    assert module.kernel_seconds(None) == []


def test_the_manifest_names_the_metric_for_both_doc_cells():
    entry = [m for m in manifest.load_manifest()["per_layer"]
             if m["name"] == NAME]
    assert len(entry) == 1 and entry[0]["moves"] == "out_tokens_per_s"
    assert entry[0]["workloads"] == [
        "internlm2-1.8b.doc-saturated",
        "olmoe-1b-7b-0125-1chip.doc-saturated"]
