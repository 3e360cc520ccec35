"""``kda_step_roofline.reason`` by hand on a small made-up trace: a call
moves the slots' state once in and once out, the share stays under 100%
while a call takes at least that long, and a program without the kernel
(the parent's) or without the engine's event reads nothing."""

import pytest

from benchmark import manifest

NAME = "kda_step_roofline.reason"
CONFIG = "ling-3.0-flash-vl-ep4-1chip"
CELL = f"{CONFIG}.reason-saturated"
# 32 slots x 32 heads x 128 x 128 x 4 B, read and written: 134.2 MB
LEAST = 2 * 32 * 32 * 128 * 128 * 4 / 819e9


def _spans(**attrs):
    return {"lines": [{"name": "python", "events": [
        ["engine.state_init", 500, 0, {"engine": "decode-1", **attrs}],
        ["engine.readback", 2000, 7000, {"experts_touched": 50.0}]]}]}


HYBRID = _spans(slots=32, max_len=3088, recurrent_bytes=32 * 13_025_280,
                latent_bytes=32 * 3_557_376)


def _trace(micros, chunks=1, name="kda_step"):
    """A device plane whose operations are the kernel's calls of
    ``micros`` us each, each after a fusion and an expert kernel that
    are none of its own, inside ``chunks`` decode chunks."""
    ops = []
    for i, us in enumerate(micros):
        ops += [["fusion.201", 1_000_000 * i, 30_000],
                ["custom-call/1out/moe_gmm.4", 1_000_000 * i + 40_000,
                 260_000],
                [f"custom-call/2out/{name}.{7 + i % 6}",
                 1_000_000 * i + 400_000, int(1000 * us)]]
    span = 1_000_000 * max(len(micros), 1)
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                [f"jit_decode_chunk({i})", i * span // chunks,
                 span // chunks] for i in range(chunks)]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": []}]}


def _facts(**more):
    return {"spans": HYBRID, "model": CONFIG, "engine": {"slots": 32},
            "device": {"kind": "TPU v5 lite"}, "log_dir": None, **more}


@pytest.mark.parametrize("micros, low, high", [
    ((212.0,) * 192, 77.0, 77.6),         # what PR 37's runs read
    ((398.0,) * 96, 41.0, 41.5),          # a kernel as slow as the XLA body
    ((1e6 * LEAST,) * 96, 99.9, 100.001), # at the roofline itself (ns)
    ((208.0, 216.0, 230.0), 75.0, 75.4)])
def test_the_share_is_the_states_bytes_over_the_measured_time(
        micros, low, high, capsys):
    read = manifest.layer_metric_reader(NAME)
    assert 163.8e-6 < LEAST < 164.0e-6
    got = read(_facts(trace=_trace(micros, chunks=2)))
    want = 100 * len(micros) * LEAST / (sum(micros) / 1e6)
    assert got == pytest.approx(want, rel=1e-6)
    assert low < got <= high <= 100.001
    said = capsys.readouterr().err
    assert f"{len(micros)} kda_step events" in said
    assert f"({len(micros) / 2:.1f} a decode chunk of 2)" in said


def test_a_chunk_the_trace_cut_does_not_lower_the_count_a_chunk():
    """The traced part starts and ends inside a decode chunk: the count
    a chunk is the median over the chunks' executions, so the two cut
    ones (1 and 2 events) leave the whole ones' 4 standing."""
    module = manifest.load_python("layer_metrics", NAME, manifest.HERE)
    trace = _trace((212.0,) * 20, chunks=5)
    assert module.calls_a_chunk(trace) == (4.0, 5)
    ops = trace["planes"][0]["lines"][1]
    kernel = [e for e in ops["events"] if "kda_step" in e[0]]
    cut = kernel[:3] + kernel[-2:]
    ops["events"] = [e for e in ops["events"]
                     if not any(e is c for c in cut)]
    assert module.calls_a_chunk(trace) == (4.0, 5)
    assert len(module.kernel_seconds(trace)) == 15
    assert module.calls_a_chunk(_trace((212.0,), chunks=0)) == (None, 0)


@pytest.mark.parametrize("facts", [
    dict(trace=_trace(())),                          # the parent: no kernel
    dict(trace=None),                                # no device trace
    dict(trace=_trace((212.0,), name="decode_attn")),  # another kernel
    dict(trace=_trace((212.0,), name="kda_step_x")),
    dict(trace=_trace((212.0,)), spans=None),        # no spans
    dict(trace=_trace((212.0,)),                     # rows of k and v
         spans=_spans(slots=8, kv_bytes=1 << 30))])
def test_nothing_to_read_reads_none_and_raises_nothing(facts):
    assert manifest.layer_metric_reader(NAME)(_facts(**facts)) is None
    assert manifest.layer_metric_reader(NAME)({
        "model": CONFIG, "device": {"kind": "TPU v5 lite"},
        "log_dir": None}) is None


def test_which_events_are_the_kernels():
    module = manifest.load_python("layer_metrics", NAME, manifest.HERE)
    from benchmark import trace_reduce

    line = ("%kda_step.9 = (f32[32,32,128,128]{3,2,1,0:T(8,128)}, "
            "f32[32,32,128]{2,1,0:T(8,128)}) custom-call(%a, %b, %c, %d), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.op_name(line) == "custom-call/2out/kda_step.9"
    assert module.KERNEL.match(trace_reduce.op_name(line))
    assert module.KERNEL.match("custom-call/2out/kda_step")
    for other in ("%moe_gmm.7 = bf16[64,1024]{} custom-call(%a)",
                  "%kda_step_x.1 = f32[8]{} custom-call(%a)",
                  "%fusion.3 = f32[32,32,128]{2,1,0} fusion(%kda_step.1)"):
        assert module.KERNEL.match(trace_reduce.op_name(other)) is None
    assert module.kernel_seconds(None) == []
    _, m = manifest.model(CONFIG)
    assert module.state_bytes(m, 32) == 67_108_864


def test_the_manifest_names_the_metric_for_the_hybrid_cell_alone():
    per_layer = manifest.load_manifest()["per_layer"]
    entry = [m for m in per_layer if m["name"] == NAME]
    assert per_layer[-1] is entry[0] and len(entry) == 1
    assert entry[0] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels (ops/kda_step.py)",
        "moves": "out_tokens_per_s", "workloads": [CELL]}
