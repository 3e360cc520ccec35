"""The family ``ling`` (``benchmark/families/ling.py``) by hand: the
configuration's keys against the catalog's cut, its parameter counts, a
slot's state, a decode step's bytes at 32 slots, the grouped matmul's
FLOPs and bytes OF HELD ROWS; the three ``.reason`` readers on a small
hand-made trace; the reference's blocks; and the CPU rehearsal of the
cell through ``benchmark.run`` (never a measurement)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CONFIG = "ling-3.0-flash-vl-ep4-1chip"
CELL = CONFIG + ".reason-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def fam_and_fields():
    return manifest.model(CONFIG)


def _config():
    with open(os.path.join(manifest.HERE, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_published_keys_become_the_programs_fields(fam_and_fields):
    fam, m = fam_and_fields
    assert (m["d_model"], m["n_heads"], m["kda_head_dim"]) == (2560, 32, 128)
    assert (m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"]) == (512, 128, 64, 128)
    assert (m["dense_d_ff"], m["d_ff"], m["shared_d_ff"]) == (6144, 768, 768)
    assert (m["n_experts"], m["top_k"], m["n_group"], m["topk_group"],
            m["routed_scaling_factor"]) == (512, 8, 8, 4, 2.5)
    assert m["held_experts"] == [0, 128]
    assert (m["n_layers"], m["first_k_dense"], m["layer_group_size"],
            m["vocab_size"]) == (7, 1, 6, 39296)
    assert (m["conv_kernel"], m["kda_lower_bound"], m["rope_theta"],
            m["rms_eps"]) == (4, -5.0, 6e6, 1e-6)
    # five KDA layers to one MLA layer among the six after the dense one
    assert fam.layer_counts(m) == {"kda": 6, "mla": 1, "dense": 1, "moe": 6}
    assert [i for i in range(7) if (i + 1) % 6 == 0] == [5]
    config = _config()
    for key, value in (("q_lora_rank", 1536), ("score_function", "softmax"),
                       ("use_mla_nope", True), ("kda_safe_gate", False),
                       ("use_kda_lora", True), ("norm_topk_prob", False),
                       ("gated_attention_proj_granularity_type", "elementwise"),
                       ("held_experts", [0, 100]), ("num_hidden_layers", 36)):
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_cut():
    """Every key of the catalog's row as published, but the four the cut
    changes (three sizes and, with the depth, the leading dense layers);
    what was read into the keys is under ``assumed``."""
    config = _config()
    published = {
        "hidden_size": 2560, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "num_attention_heads": 32, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts": 512, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
        "head_dim": 128, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "layer_group_size": 6,
        "short_conv_kernel_size": 4, "kda_lower_bound": -5,
        "moe_shared_expert_intermediate_size": 768,
        "max_position_embeddings": 131072, "image_patch_token": 157157}
    for key, value in published.items():
        assert config[key] == value, key
    assert len(config["expert_swiglu_limit_list"]) == 42
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["vocab_size"], config["held_experts"]) \
        == (7, 1, 39296, [0, 128])
    assert 4 * config["vocab_size"] == 157184
    assert sorted(config["reduced"]) == [
        "first_k_dense_replace", "num_experts", "num_hidden_layers",
        "vocab_size"]
    for reading in ("layer_pattern", "kda_equations", "kda_positions",
                    "use_qk_norm", "mla_gate", "router", "swiglu_limits",
                    "serving_types"):
        assert config["assumed"][reading]
    assert {"vision_tower", "mtp"} <= set(config["left_out"])
    assert "24 v5e chips" in config["deployment"]


def test_parameter_counts_by_hand(fam_and_fields):
    fam, m = fam_and_fields
    w = 32 * 128
    kda = (6 * 2560 * w          # q, k, v, decay, output gate, output
           + 2560 * 32           # beta
           + 3 * w * 4           # the convolution's taps
           + 32 + w + 128)       # A_log, dt_bias, the head norm
    assert fam.kda_params(m) == kda == 63_049_888
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
           + 4096 * 2560 + 192 + 512)
    assert fam.mla_params(m) == mla == 31_965_888
    assert fam.expert_params(m) == 3 * 2560 * 768 == 5_898_240
    assert fam.moe_fixed_params(m) == 2560 * 512 + 512 + 5_898_240 \
        == 7_209_472
    moe = 128 * 5_898_240 + 7_209_472
    assert moe == 762_184_192
    dense, ends = 3 * 2560 * 6144, 2 * 39296 * 2560
    assert (dense, ends) == (47_185_920, 201_195_520)
    norms = 7 * 2 * 2560 + 2560
    assert fam.num_params(m) == 6 * kda + mla + dense + 6 * moe + ends \
        + norms == 5_231_790_208
    # 10.46 GB in bf16; all 512 experts held would be 18.8 B parameters
    assert 10.4e9 < 2 * fam.num_params(m) < 10.5e9
    assert fam.num_params({**m, "held_experts": None}) \
        == fam.num_params(m) + 6 * 384 * 5_898_240
    # a token meets a quarter of its 8 experts here, under uniform routing
    assert fam.matmul_params(m) == 6 * kda + mla + dense + 6 * (
        7_209_472 + 2 * 5_898_240) + 2560 * 39296
    assert fam.flash_calls(m, 1, 4096) == []
    assert fam.train_flops_per_token(m, 4096) == 3.0 * (
        2 * fam.matmul_params(m) + 2 * 32 * 4096 * 0.5 * 320
        + 6 * 2 * 4 * 32 * 128 * 128)


def test_a_slots_state_and_a_decode_steps_bytes_by_hand(fam_and_fields):
    fam, m = fam_and_fields
    # a KDA layer: a float32 [32, 128, 128] and three rows of 12,288 bf16
    per_kda = 32 * 128 * 128 * 4 + 3 * 12288 * 2
    assert per_kda == 2_097_152 + 73_728
    state = fam.state_bytes_per_slot(m, 3088)
    assert state == {"recurrent": 6 * per_kda, "latent": 3088 * 576 * 2}
    # 32 slots: 0.42 GB of recurrent state, 0.11 GB of latent rows
    assert 0.41e9 < 32 * state["recurrent"] < 0.42e9
    assert 0.11e9 < 32 * state["latent"] < 0.12e9
    touched = 128 * (1 - (1 - 8 / 512) ** 32)        # 50.67 of 128 held
    assert fam.experts_touched(m, 32) == pytest.approx(50.67, abs=1e-2)
    assert fam.experts_touched(m, 1) == pytest.approx(2.0)   # 8 x 128/512
    assert fam.experts_touched(m, 4096) == pytest.approx(128, abs=1e-6)
    weights = 2 * (6 * 63_049_888 + 31_965_888 + 47_185_920
                   + 6 * (7_209_472 + touched * 5_898_240)
                   + 2560 * 39296 + 32 * 2560)
    moved = 32 * (2 * 6 * per_kda + 1500 * 576 * 2)
    assert fam.decode_step_bytes(m, 32, 1500) == pytest.approx(
        weights + moved)
    # 3.6 GB of touched experts, 1.2 GB of other weights, 0.9 GB of state
    assert 3.5e9 < 2 * 6 * touched * 5_898_240 < 3.7e9
    assert 5.6e9 < fam.decode_step_bytes(m, 32, 1500) < 5.8e9


# ------------------------------------------- readers on a small trace

SPANS = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0,
     {"engine": "decode-1", "slots": 32, "max_len": 3088,
      "recurrent_bytes": 32 * 13_025_280, "latent_bytes": 32 * 3_557_376}],
    ["serve.pump", 1000, 9000, {"active": 32, "queued": 16}],
    ["engine.readback", 2000, 7000,
     {"experts_touched": 50.0, "assignments": 256.0,
      "held_assignments": 60.0, "expert_load_max": 30.0,
      "expert_load_mean": 16.0}],
    ["serve.pump", 11000, 9000, {"active": 32, "queued": 16}],
    ["engine.readback", 12000, 7000,
     {"experts_touched": 52.0, "assignments": 256.0,
      "held_assignments": 68.0}],
    ["serve.pump", 21000, 9000, {"active": 24, "queued": 0}],
    ["engine.readback", 22000, 7000,
     {"experts_touched": 48.0, "assignments": 192.0,
      "held_assignments": 48.0}],
]}]}
BARE = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0, {"slots": 8, "kv_bytes": 1 << 30}],
    ["engine.readback", 2000, 7000, {"experts_touched": 42.0}]]}]}


def _facts(**more):
    return {"spans": SPANS, "model": CONFIG, "engine": {"slots": 32},
            "device": {"kind": "TPU v5 lite"}, "log_dir": None, **more}


def test_the_two_counters_readers_on_a_small_trace():
    share = manifest.layer_metric_reader("moe_held_assignment_share.reason")
    slot = manifest.layer_metric_reader("slot_state_bytes.reason")
    assert share(_facts()) == pytest.approx(100 * 176 / 704)   # 25%
    assert slot(_facts()) == 13_025_280 + 3_557_376 == 16_582_656
    # a program without the attrs (rows of k and v, every expert held, a
    # parent commit): nothing, and nothing raised
    for reader in (share, slot):
        assert reader(_facts(spans=BARE)) is None
        assert reader(_facts(spans=None)) is None
    # the readers the cell shares with the other saturated cells find
    # their attrs in this program's read-backs too
    touched = manifest.layer_metric_reader("moe_experts_touched.doc")
    load = manifest.layer_metric_reader("moe_expert_load_max_over_mean.doc")
    assert touched(_facts()) == pytest.approx(50.0)
    assert load(_facts()) == pytest.approx(30 / 16)


def test_the_kernels_roofline_counts_held_rows_by_hand(fam_and_fields):
    """The operand of a decode step's call has 256 rows (32 slots x
    top-8) and a 256-token prefill's 2,048, but the kernel multiplies
    the rows of held experts: 58.67 (the read-backs' mean) and a quarter
    of 2,048. Counted from the operand a share could read four times
    too high; by hand, from held rows, it cannot pass 100% while the
    kernel takes at least its bytes' time."""
    fam, m = fam_and_fields
    roofline = manifest.layer_metric_reader("moe_gmm_roofline.reason")
    events = [(256, 768, 260e-6), (256, 768, 260e-6), (256, 2560, 260e-6),
              (2048, 768, 700e-6), (2048, 768, 700e-6), (2048, 2560, 700e-6)]
    held_rows = (60 + 68 + 48) / 3
    held_share = 176 / 704
    touched = 50.0
    assert fam.gmm_flops(held_rows, 2560, 768) == 2 * held_rows * 2560 * 768
    decode_bytes = 2 * (touched * 2560 * 768 + held_rows * (2560 + 768))
    assert fam.gmm_bytes(held_rows, 2560, 768, touched) == decode_bytes
    decode = decode_bytes / 819e9
    assert decode > fam.gmm_flops(held_rows, 2560, 768) / 197e12
    rows = 2048 * held_share                         # 512 of 2,048
    prefill_touched = fam.experts_touched(m, 256)    # 125.7 of 128
    prefill_bytes = 2 * (prefill_touched * 2560 * 768 + rows * (2560 + 768))
    prefill = prefill_bytes / 819e9
    assert prefill > 2 * rows * 2560 * 768 / 197e12  # bytes bind
    want = 100 * 3 * (decode + prefill) / (3 * 260e-6 + 3 * 700e-6)
    assert roofline(_facts(moe_gmm_events=events)) == pytest.approx(
        want, rel=1e-6)
    assert 70 < want < 100
    # at the roofline itself (each event as long as its least time) the
    # share reads 100, not 400: the counts are of held rows
    at_peak = [(256, 768, decode), (2048, 768, prefill)]
    assert roofline(_facts(moe_gmm_events=at_peak)) == pytest.approx(100.0)
    from_operand = 100 * (
        2 * (touched * 2560 * 768 + 256 * (2560 + 768)) / 819e9) / decode
    assert from_operand > 100  # what the operand's rows would have read
    # no kernel event, no held counts (the parent, a dense model)
    assert roofline(_facts(moe_gmm_events=[])) is None
    assert roofline(_facts(spans=BARE, moe_gmm_events=events)) is None
    assert roofline(_facts(spans=None)) is None


# ------------------------------------------------------ the reference


def test_the_reference_computes_a_layer_at_a_time_and_shares_no_code():
    fam = manifest.family("ling")
    ref = manifest.reference(fam)
    with open(os.path.join(manifest.HERE, "families",
                           "ling.reference.py")) as f:
        source = f.read()
    assert "ray_tpu" not in source.split('"""', 2)[2]
    assert "import" not in source.split('"""', 2)[2].replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace("import jax.numpy as jnp", "").replace(
        "import jax", "").replace("import numpy as np", "")
    # every block is its own jitted call: no whole-tree float32 cast
    for block in (ref._attn_block, ref._mlp_block, ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 1 and 0 < ref.TRAIN_LOSS_TOL < 0.1


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:ling-3.0-flash-vl-ep4-1chip`` through proxy, pool,
    replica pump and engine at tiny widths: served tokens agree with the
    plain reference, the held-expert counters and the slot's state reach
    the result line."""
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
    # tiny: 32 experts of which 8 are held, top-4, 4 slots
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 8
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("ling")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS), 193, 4)
    assert metrics["slot_state_bytes.reason"]["value"] \
        == sum(per_slot.values())
    assert "moe_gmm_roofline.reason" not in metrics  # no device, no kernel
    assert "served tokens against the reference" in proc.stderr
