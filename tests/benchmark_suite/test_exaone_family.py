"""The family ``exaone_moe`` (``benchmark/families/exaone_moe.py``) by
hand: the configuration's keys against the catalog's cut, its parameter
counts against ``init_params``' shapes, a slot's state, a decode step's
bytes at 64 slots; the three ``.mix`` readers and the shared ``.reason``
ones on a small hand-made trace; the traffic file; the reference's
blocks; and the CPU rehearsal of the cell through ``benchmark.run``
(never a measurement)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CONFIG = "k-exaone-236b-a23b-ep8-1chip"
CELL = CONFIG + ".reason-long-saturated"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def fam_and_fields():
    return manifest.model(CONFIG)


def _json(kind, name):
    with open(os.path.join(manifest.HERE, kind, name + ".json")) as f:
        return json.load(f)


def test_the_published_keys_become_the_programs_fields(fam_and_fields):
    fam, m = fam_and_fields
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]) \
        == (6144, 64, 8, 128)
    assert m["n_heads"] * m["head_dim"] == 8192 != m["d_model"]
    assert (m["dense_d_ff"], m["d_ff"], m["shared_d_ff"]) \
        == (18432, 2048, 2048)
    assert (m["n_experts"], m["top_k"], m["n_group"], m["topk_group"],
            m["routed_scaling_factor"]) == (128, 8, 1, 1, 2.5)
    assert m["held_experts"] == [0, 16]
    assert (m["n_layers"], m["vocab_size"], m["sliding_window"]) \
        == (5, 19200, 128)
    assert (m["rope_theta"], m["rms_eps"], m["published_layers"]) \
        == (1e6, 1e-5, 48)
    # three sliding layers to one full layer among the four sparse ones
    assert m["layer_types"] == ["sliding_attention"] * 3 \
        + ["full_attention", "sliding_attention"]
    assert m["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert fam.layer_counts(m) == {"window": 4, "full": 1, "dense": 1,
                                   "moe": 4}
    assert m["layer_types"][1:].count("sliding_attention") == 3
    config = _json("configs", CONFIG)
    for key, value in (
            ("scoring_func", "softmax"), ("norm_topk_prob", False),
            ("n_group", 8), ("topk_group", 4), ("hidden_act", "gelu"),
            ("tie_word_embeddings", True), ("model_type", "exaone4"),
            ("num_hidden_layers", 8), ("layer_types", ["linear"] * 5),
            ("sliding_windows", [128, 128, 128, 128, 128]),
            ("sliding_windows", [64, 64, 64, 0, 64]),
            ("mlp_layer_types", ["sparse"] * 5),
            ("first_k_dense_replace", 2),
            ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"})):
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_cut():
    """Every key of the catalog's row as published, but the depth, the
    three lists that follow it, the experts held and the vocabulary;
    what was read into the keys is under ``assumed``."""
    config = _json("configs", CONFIG)
    published = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "max_position_embeddings": 262144, "model_type": "exaone_moe",
        "moe_intermediate_size": 2048, "mtp_layer_types": ["full_attention"],
        "mtp_sliding_windows": [0], "n_group": 1, "norm_topk_prob": True,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "tie_word_embeddings": False, "topk_group": 1}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["held_experts"], config["published_num_hidden_layers"]) \
        == (5, 19200, [0, 16], 48)
    assert 8 * config["vocab_size"] == 153600
    assert config["sliding_windows"] == [128, 128, 128, 0, 128]
    assert sorted(config["reduced"]) == [
        "layer_types", "mlp_layer_types", "num_experts",
        "num_hidden_layers", "sliding_windows", "vocab_size"]
    for reading in ("qk_norm", "positions", "norm_placement", "window",
                    "router", "shared_expert", "serving_types",
                    "initialisation"):
        assert config["assumed"][reading]
    assert {"mtp", "exchange"} <= set(config["left_out"])
    assert "eight" in config["deployment"]
    # BENCHMARK.json lists the same cut, and the cell under its name
    b = manifest.load_manifest()
    entry = [c for c in b["configs"] if c["name"] == CONFIG][0]
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
    cell = manifest.cell(b, CELL)
    assert (cell["chips"], cell["traffic_name"]) \
        == (1, "reason-long-saturated")
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["out_tokens_per_s", "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"decode_attn_roofline.mix", "attn_rows_read_share.mix",
            "slot_state_bytes.mix", "moe_gmm_roofline.reason",
            "decode_hbm_share.doc", "decode_chunk_ms.doc"} <= names
    assert "decode_attn_roofline.doc" not in names


def test_the_traffic_is_reason_saturated_with_every_output_doubled():
    long, short = (_json("traffic", name) for name in (
        "reason-long-saturated", "reason-saturated"))
    assert long["shapes"]["entries"] == [
        [p, 2 * o] for p, o in short["shapes"]["entries"]]
    outs = [o for _, o in long["shapes"]["entries"]]
    assert sorted(set(outs)) == [1024, 1536, 2560, 4096]
    assert sum(outs) / len(outs) == 2304
    assert (long["loop"], long["clients"]) == ("closed", 96)
    assert long["engine"] == {"slots": 64, "max_len": 1024 + 4096 + 16,
                              "chunk_tokens": 16,
                              "prompt_buckets": [256, 512, 1024]}
    assert long["window"] == {"opens_after_completed": 64}
    assert long["trace_seconds"] == 8


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import jax

    fam, m = fam_and_fields
    attn = 6144 * 8192 * 2 + 6144 * 1024 * 2 + 2 * 128
    assert fam.attn_params(m) == attn == 113_246_464
    assert fam.expert_params(m) == 3 * 6144 * 2048 == 37_748_736
    assert fam.moe_fixed_params(m) == 6144 * 128 + 128 + 37_748_736 \
        == 38_535_296
    dense, ends = 3 * 6144 * 18432, 2 * 19200 * 6144
    assert (dense, ends) == (339_738_624, 235_929_600)
    norms = 5 * 2 * 6144 + 6144
    held = 5 * attn + dense + 4 * (16 * 37_748_736 + 38_535_296) + ends \
        + norms
    assert fam.num_params(m) == held == 3_712_028_416
    # 7.42 GB in bf16; all 128 experts held would be 20.6 B parameters
    assert 7.41e9 < 2 * fam.num_params(m) < 7.43e9
    assert fam.num_params({**m, "held_experts": None}) \
        == held + 4 * 112 * 37_748_736
    # and it is what init_params allocates, leaf by leaf
    prog = fam.build(m, max_seq_len=5136, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(a.size for a in leaves) == held
    assert abs(sum(a.size * a.dtype.itemsize for a in leaves)
               - 2 * held) < 1 << 20  # (the norm vectors are float32)
    mlp = shapes["layers"][1]["mlp"]
    assert mlp["w_gate"].shape == (16, 6144, 2048)
    assert mlp["router"].shape == (6144, 128)
    assert shapes["layers"][0]["attn"]["w_qkv"].shape == (6144, 10240)
    assert shapes["lm_head"].shape == (6144, 19200)
    # a token meets an eighth of its 8 experts here, under uniform routing
    assert fam.matmul_params(m) == 5 * attn + dense + 4 * (
        38_535_296 + 37_748_736) + 6144 * 19200
    assert fam.flash_calls(m, 1, 4096) == []
    assert fam.train_flops_per_token(m, 4096) == 3.0 * (
        2 * fam.matmul_params(m)
        + 2 * 64 * (1 * 2048 + 4 * 128) * 2 * 128)


def test_a_slots_state_and_a_decode_steps_bytes_by_hand(fam_and_fields):
    import jax

    from ray_tpu.models import exaone

    fam, m = fam_and_fields
    row = 2 * 8 * 128 * 2  # a position's k and v of one layer, bf16
    assert fam.kv_row_bytes(m) == row == 4096
    state = fam.state_bytes_per_slot(m, 5136)
    assert state == {"window": 4 * 128 * row, "full": 5136 * row} \
        == {"window": 2_097_152, "full": 21_037_056}
    # 64 slots: 1.35 GB of full rows, 0.13 GB of rings; five full layers
    # would be 6.7 GB
    assert 64 * sum(state.values()) == 1_480_589_312
    assert 6.7e9 < 64 * 5 * 5136 * row < 6.8e9
    # and it is what init_state allocates
    prog = fam.build(m, max_seq_len=5136, remat=False)
    shapes = jax.eval_shape(
        lambda: exaone.SLOTS.init_state(prog.cfg, 64, 5136))
    assert exaone.SLOTS.state_bytes(shapes) \
        == {kind: 64 * n for kind, n in state.items()}
    assert shapes["k_win"].shape == (4, 64, 128, 1024)
    assert shapes["k_full"].shape == (1, 64, 5136, 1024)
    # resident: over a quarter of the chip's 16 GB
    resident = 2 * fam.num_params(m) + 64 * sum(state.values())
    assert 0.5 < resident / 16e9 < 0.6
    touched = 16 * (1 - (1 - 8 / 128) ** 64)           # 15.74 of 16 held
    assert fam.experts_touched(m, 64) == pytest.approx(15.74, abs=1e-2)
    assert fam.experts_touched(m, 1) == pytest.approx(1.0)   # 8 x 16/128
    weights = 2 * (5 * 113_246_464 + 339_738_624
                   + 4 * (38_535_296 + touched * 37_748_736)
                   + 6144 * 19200 + 64 * 6144)
    assert fam.live_row_bytes(m, 2000) == {"window": 4 * 128 * row,
                                           "full": 2000 * row}
    assert fam.live_row_bytes(m, 50)["window"] == 4 * 50 * row
    moved = 64 * (4 * 128 + 2000) * row
    assert fam.decode_step_bytes(m, 64, 2000) == pytest.approx(
        weights + moved)
    # 4.75 GB of touched experts, 2.36 GB of other weights, 0.66 GB of rows
    assert 4.7e9 < 2 * 4 * touched * 37_748_736 < 4.8e9
    assert 7.7e9 < fam.decode_step_bytes(m, 64, 2000) < 7.8e9


# ------------------------------------------- readers on a small trace

SPANS = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0,
     {"engine": "decode-1", "slots": 64, "max_len": 5136,
      "window_bytes": 64 * 2_097_152, "full_bytes": 64 * 21_037_056,
      "window_layers": 4, "full_layers": 1}],
    ["serve.pump", 1000, 9000, {"active": 64, "queued": 32}],
    ["engine.readback", 2000, 7000,
     {"live_rows": 128_000, "cache_rows": 64 * 5136,
      "live_rows_full": 128_000, "live_rows_window": 64 * 128,
      "experts_touched": 15.5, "assignments": 512.0,
      "held_assignments": 60.0, "expert_load_max": 90.0,
      "expert_load_mean": 64.0}],
    ["serve.pump", 11000, 9000, {"active": 64, "queued": 32}],
    ["engine.readback", 12000, 7000,
     {"live_rows": 136_000, "cache_rows": 64 * 5136,
      "live_rows_full": 136_000, "live_rows_window": 64 * 128,
      "experts_touched": 16.0, "assignments": 512.0,
      "held_assignments": 68.0}],
]}]}
BARE = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0, {"slots": 8, "kv_bytes": 1 << 30}],
    ["engine.readback", 2000, 7000,
     {"live_rows": 5000, "cache_rows": 10368, "experts_touched": 42.0}]]}]}
# one step's calls: four on a ring and one on the full stack
OPS = "XLA Ops"


def _trace(events):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": OPS, "events": events}]}]}


def _facts(**more):
    return {"spans": SPANS, "model": CONFIG, "engine": {"slots": 64},
            "device": {"kind": "TPU v5 lite"}, "log_dir": None, **more}


def test_the_two_counters_readers_on_a_small_trace():
    share = manifest.layer_metric_reader("attn_rows_read_share.mix")
    slot = manifest.layer_metric_reader("slot_state_bytes.mix")
    full, ring = 128_000 + 136_000, 2 * 64 * 128
    assert share(_facts()) == pytest.approx(
        100 * (4 * ring + full) / (5 * full))          # 25.0%
    assert 24.9 < share(_facts()) < 25
    assert slot(_facts()) == 2_097_152 + 21_037_056 == 23_134_208
    # a program without the attrs (rows of one kind, a parent commit):
    # nothing, and nothing raised
    for reader in (share, slot):
        assert reader(_facts(spans=BARE)) is None
        assert reader(_facts(spans=None)) is None
    # Ling's state names other kinds: its reader and this one keep apart
    assert manifest.layer_metric_reader("slot_state_bytes.reason")(
        _facts()) is None
    # the readers the cell shares with the other saturated cells find
    # their attrs in this program's read-backs too
    touched = manifest.layer_metric_reader("moe_experts_touched.doc")
    load = manifest.layer_metric_reader("moe_expert_load_max_over_mean.doc")
    held = manifest.layer_metric_reader("moe_held_assignment_share.reason")
    assert touched(_facts()) == pytest.approx(15.75)
    assert load(_facts()) == pytest.approx(90 / 64)
    assert held(_facts()) == pytest.approx(100 * 128 / 1024)   # an eighth


def test_the_attention_kernels_roofline_counts_rows_by_kind(fam_and_fields):
    """Ten ``decode_attn`` events (two steps: four ring calls and one on
    the full stack each). A ring call must read 64 x 128 rows, a full
    call the slots' mean 132,000 live rows, 4,096 B each (the row from
    the model's own ``head_dim``: 6144 / 64 = 96 would be wrong). Counted
    as ``decode_attn_roofline.doc`` counts (every call the full stack's
    live rows) the share would read 3.7 times too high."""
    fam, m = fam_and_fields
    roofline = manifest.layer_metric_reader("decode_attn_roofline.mix")
    name = "custom-call/1out/decode_attn.3"
    events = [[name, i * 2_000_000, ns]
              for i, ns in enumerate(([70_000] * 4 + [1_100_000]) * 2)]
    ring_s = 64 * 128 * 4096 / 819e9
    full_s = 132_000 * 4096 / 819e9
    want = 100 * (8 * ring_s + 2 * full_s) / (8 * 70e-6 + 2 * 1100e-6)
    got = roofline(_facts(trace=_trace(events)))
    assert got == pytest.approx(want, rel=1e-6)
    assert 55 < want < 70
    from_one_kind = 100 * 10 * full_s / (8 * 70e-6 + 2 * 1100e-6)
    assert from_one_kind > 100  # what one live_rows for all would read
    # at the roofline itself the share reads 100
    at_peak = [[name, i * 2_000_000, int(1e9 * s)] for i, s in enumerate(
        [ring_s] * 4 + [full_s])]
    assert roofline(_facts(trace=_trace(at_peak))) == pytest.approx(
        100.0, rel=1e-3)
    # no kernel event, no kinds of rows (the parent, the Llama block)
    assert roofline(_facts(trace=_trace([]))) is None
    assert roofline(_facts(spans=BARE, trace=_trace(events))) is None
    assert roofline(_facts(spans=None, trace=_trace(events))) is None
    assert roofline(_facts()) is None


def test_the_expert_kernels_roofline_at_the_widest_experts(fam_and_fields):
    """``moe_gmm_roofline.reason`` at 6144 x 2048: a decode call's
    operand has 512 rows (64 slots x top-8) of which the kernel
    multiplies the 64 held ones over ~16 experts; bytes bind (16 x
    25 MB), and the share cannot pass 100% while the kernel takes its
    bytes' time."""
    fam, m = fam_and_fields
    roofline = manifest.layer_metric_reader("moe_gmm_roofline.reason")
    held_rows, touched = 64.0, 15.75
    nbytes = 2 * (touched * 6144 * 2048 + held_rows * (6144 + 2048))
    assert fam.gmm_bytes(held_rows, 6144, 2048, touched) == nbytes
    assert fam.gmm_bytes(held_rows, 2048, 6144, touched) == nbytes
    least = nbytes / 819e9
    assert least > fam.gmm_flops(held_rows, 6144, 2048) / 197e12
    events = [(512, 2048, 650e-6), (512, 2048, 650e-6), (512, 6144, 650e-6)]
    assert roofline(_facts(moe_gmm_events=events)) == pytest.approx(
        100 * least / 650e-6, rel=1e-6)
    assert 70 < 100 * least / 650e-6 < 80
    assert roofline(_facts(moe_gmm_events=[(512, 2048, least)])) \
        == pytest.approx(100.0)


# ------------------------------------------------------ the reference


def test_the_reference_computes_a_layer_at_a_time_and_shares_no_code():
    fam = manifest.family("exaone_moe")
    ref = manifest.reference(fam)
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    with open(os.path.join(manifest.HERE, "families",
                           "exaone_moe.reference.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "ray_tpu" not in body
    assert "import" not in body.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace("import jax.numpy as jnp", "").replace(
        "import jax", "").replace("import numpy as np", "")
    # the band mask is written out, the precision is the highest
    assert 'i - j < m["sliding_window"]' in body
    assert body.count('default_matmul_precision("highest")') == 3
    # every block is its own jitted call: no whole-tree float32 cast
    for block in (ref._attn_block, ref._mlp_block, ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 1 and 0 < ref.TRAIN_LOSS_TOL < 0.1


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:k-exaone-236b-a23b-ep8-1chip`` through proxy, pool,
    replica pump and engine at tiny widths: served tokens agree with the
    plain reference, the rows by kind, the held-expert counters and the
    slot's state reach the result line."""
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
    # tiny: 16 experts of which 4 are held, top-4, 4 slots, a window of 8
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 4
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("exaone_moe")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS), 321, 4)
    assert metrics["slot_state_bytes.mix"]["value"] == sum(per_slot.values())
    # four rings of 8 rows beside one full layer of tens of rows: the
    # windows leave between a fifth and a half of an all-full cache's reads
    assert 20 < metrics["attn_rows_read_share.mix"]["value"] < 50
    assert "decode_attn_roofline.mix" not in metrics  # no device, no kernel
    assert "moe_gmm_roofline.reason" not in metrics
    assert "served tokens against the reference" in proc.stderr


def test_a_checkout_without_the_block_refuses_the_configuration(tmp_path):
    """What the parent commit does with the new cell: ``fields`` asks of
    the files whether the program has the block and raises a
    ``ManifestError``, before any process touches jax or a chip."""
    import shutil

    shutil.copytree(manifest.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.makedirs(tmp_path / "ray_tpu" / "models")   # a program, no exaone.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "exaone.py" in proc.stderr
