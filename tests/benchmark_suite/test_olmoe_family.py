"""The family ``olmoe`` (``benchmark/families/olmoe.py``) by hand: its
parameter counts, a decode step's bytes at 8 slots, the grouped
matmul's FLOPs and bytes; the three ``moe_*`` readers on a small
hand-made trace; and the CPU rehearsal of the cell through
``benchmark.run`` (never a measurement)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CONFIG = "olmoe-1b-7b-0125-1chip"
CELL = CONFIG + ".doc-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def fam_and_fields():
    return manifest.model(CONFIG)


def test_the_published_keys_become_the_programs_fields(fam_and_fields):
    fam, m = fam_and_fields
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_ff"]) \
        == (2048, 16, 16, 1024)
    assert (m["n_experts"], m["top_k"], m["norm_topk_prob"]) \
        == (64, 8, False)
    assert m["qk_norm"] and m["moe_impl"] == "dropless"
    assert (m["vocab_size"], m["n_layers"], m["rope_theta"]) \
        == (50304, 4, 10000.0)
    with open(os.path.join(manifest.HERE, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    for key, value in (("clip_qkv", 8.0), ("attention_bias", True),
                       ("rope_scaling", {"type": "linear"}),
                       ("hidden_act", "gelu")):
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_parameter_counts_by_hand(fam_and_fields):
    fam, m = fam_and_fields
    attention = 4 * 2048 * 2048                      # q, k, v, o: 16 x 128
    qk_norm = 2 * 2048
    router = 2048 * 64
    experts = 64 * 3 * 2048 * 1024
    norms = 2 * 2048
    layer = attention + qk_norm + router + experts + norms
    assert (attention, router, experts) == (16_777_216, 131_072, 402_653_184)
    assert fam.layer_params(m) == layer == 419_569_664
    ends = 2 * 50304 * 2048 + 2048                   # embedding, head, norm
    assert fam.num_params(m) == 4 * layer + ends == 1_884_325_888
    assert fam.num_params({**m, "n_layers": 16}) == 6_919_161_856
    # active: attention, router, 8 of 64 experts, the head
    active_layer = attention + router + 8 * 3 * 2048 * 1024
    assert fam.matmul_params(m) == 4 * active_layer + 2048 * 50304
    # the model's "1.3 B active", embedding included
    assert fam.matmul_params({**m, "n_layers": 16}) + 50304 * 2048 \
        == 16 * 67_239_936 + 2 * 103_022_592 == 1_281_884_160
    assert fam.train_flops_per_token(m, 4096) == 3.0 * (
        2 * fam.matmul_params(m) + 4 * 4 * 2048 * 4096 * 0.5)
    assert fam.flash_calls(m, 2, 4096) == [(4, 2, 4096, 16, 16, 128)]


def test_a_decode_steps_bytes_at_8_slots_by_hand(fam_and_fields):
    fam, m = fam_and_fields
    touched = 64 * (1 - (1 - 8 / 64) ** 8)           # 42.009 of 64
    assert fam.experts_touched(m, 8) == pytest.approx(42.009, abs=1e-3)
    assert fam.experts_touched(m, 1) == pytest.approx(8.0)
    assert fam.experts_touched(m, 128) == pytest.approx(64.0, abs=1e-5)
    layer = 16_777_216 + 131_072 + touched * 6_291_456
    weights = 2 * (4 * layer + 2048 * 50304 + 8 * 2048)
    cache = 8 * 700 * 4 * 2 * 16 * 128 * 2
    assert fam.decode_step_bytes(m, 8, 700) == pytest.approx(weights + cache)
    # about 2.1 GB of experts in 2.6 GB: reading all 64 would be 3.2 GB
    assert 2.0e9 < 2 * 4 * touched * 6_291_456 < 2.2e9
    assert 2.6e9 < fam.decode_step_bytes(m, 8, 700) < 2.7e9


def test_the_grouped_matmuls_flops_and_bytes_by_hand(fam_and_fields):
    fam, _ = fam_and_fields
    # a 1024-token prefill's gate product: 8192 rows, 2048 -> 1024
    assert fam.gmm_flops(8192, 2048, 1024) == 2 * 8192 * 2048 * 1024 \
        == 34_359_738_368
    assert fam.gmm_bytes(8192, 2048, 1024, 64) \
        == 2 * (64 * 2048 * 1024 + 8192 * 2048 + 8192 * 1024)
    # a decode step's: 64 rows over 42 experts is all weights
    assert fam.gmm_bytes(64, 2048, 1024, 42) \
        == 2 * (42 * 2048 * 1024 + 64 * 2048 + 64 * 1024) == 176_553_984


# ------------------------------------------- readers on a small trace

SPANS = {"lines": [{"name": "python", "events": [
    ["serve.pump", 1000, 9000, {"active": 8, "queued": 8}],
    ["engine.readback", 2000, 7000,
     {"experts_touched": 42.5, "expert_load_max": 90.0,
      "expert_load_mean": 60.0}],
    ["serve.pump", 11000, 9000, {"active": 8, "queued": 8}],
    ["engine.readback", 12000, 7000, {"experts_touched": 41.5}],
    ["serve.pump", 21000, 9000, {"active": 8, "queued": 8}],
    ["engine.readback", 22000, 7000,
     {"experts_touched": 42.0, "expert_load_max": 40.0,
      "expert_load_mean": 32.0}],
    ["serve.pump", 31000, 9000, {"active": 8, "queued": 8}],
    ["engine.readback", 32000, 7000,
     {"experts_touched": 42.0, "expert_load_max": 256.0,
      "expert_load_mean": 128.0}],
]}]}


def _facts(**more):
    return {"spans": SPANS, "model": CONFIG, "engine": {"slots": 8},
            "device": {"kind": "TPU v5 lite"}, "log_dir": None, **more}


def test_the_span_readers_on_a_small_trace():
    touched = manifest.layer_metric_reader("moe_experts_touched.doc")
    load = manifest.layer_metric_reader("moe_expert_load_max_over_mean.doc")
    assert touched(_facts()) == pytest.approx(42.0)
    # ratios 1.5, 1.25, 2.0 over the three spans with a prefill's counts
    assert load(_facts()) == pytest.approx(1.5)
    # a program without the attrs (a dense model, a parent commit)
    bare = {"lines": [{"name": "python", "events": [
        ["engine.readback", 2000, 7000, {}]]}]}
    assert touched(_facts(spans=bare)) is None
    assert load(_facts(spans=bare)) is None
    assert touched(_facts(spans=None)) is None


def test_the_kernels_roofline_on_a_small_trace(fam_and_fields):
    fam, m = fam_and_fields
    roofline = manifest.layer_metric_reader("moe_gmm_roofline.doc")
    # one decode step's three calls and one 256-token prefill's three
    events = [(64, 1024, 300e-6), (64, 1024, 300e-6), (64, 2048, 300e-6),
              (2048, 1024, 600e-6), (2048, 1024, 600e-6),
              (2048, 2048, 600e-6)]
    decode = (42 * 2048 * 1024 + 64 * 2048 + 64 * 1024) * 2 / 819e9
    prefill_bytes = (64 * 2048 * 1024 + 2048 * 2048 + 2048 * 1024) * 2
    prefill = max(prefill_bytes / 819e9, 2 * 2048 * 2048 * 1024 / 197e12)
    assert prefill == prefill_bytes / 819e9  # 32 rows an expert: bytes
    want = 100 * 3 * (decode + prefill) / 2700e-6
    assert roofline(_facts(moe_gmm_events=events)) == pytest.approx(
        want, rel=1e-3)
    assert 60 < want < 65
    # no kernel event (the parent, a dense model), no trace at all
    assert roofline(_facts(moe_gmm_events=[])) is None
    assert roofline(_facts()) is None
    module = manifest.load_python("layer_metrics", "moe_gmm_roofline.doc",
                                  manifest.HERE)
    line = ("%moe_gmm.7 = bf16[8192,1024]{1,0:T(8,128)(2,1)} "
            "custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"")
    assert module.KERNEL.match(line).groups() == ("8192", "1024")
    assert module.KERNEL.match("%moe_tgmm.1 = bf16[64,8,8]{} x") is None
    assert module.KERNEL.match("%fusion.3 = bf16[8,2048]{1,0} fusion(") \
        is None
    # without the spans' count a decode call takes the family's expected
    assert module.roofline_pct(events[:3], fam, m, 8, None, PEAK) \
        == pytest.approx(100 * 3 * (
            fam.gmm_bytes(64, 2048, 1024, fam.experts_touched(m, 8))
            / 819e9) / 900e-6, rel=1e-6)


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:olmoe-1b-7b-0125-1chip`` through proxy, pool, replica
    pump and engine at tiny widths: served tokens agree with the plain
    reference, the routing counters reach the result line."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_CHIPS", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "3000000001", "--seconds", "4", "--trace", "1",
         "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    # tiny: 8 experts, top-2, 4 slots
    assert 2 <= metrics["moe_experts_touched.doc"]["value"] <= 8
    assert metrics["moe_expert_load_max_over_mean.doc"]["value"] >= 1
    assert "moe_gmm_roofline.doc" not in metrics  # no device, no kernel
    assert "served tokens against the reference" in proc.stderr
