"""The family ``solar_open2`` (``benchmark/families/solar_open2.py``) by
hand: the configuration's keys against the catalog's row and its three
cuts, its parameter counts against ``init_params``' shapes, a slot's
state of two kinds, a decode step's bytes at 32 slots; the ``.hybrid``
readers and the shared ones on a small hand-made trace; the traffic
file; the reference's blocks; and the CPU rehearsal of the cell through
``benchmark.run`` (never a measurement)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CONFIG = "solar-open2-250b-ep8-1chip"
CELL = CONFIG + ".longreason-saturated"
NEW = ("prefill_linear_attn_share.hybrid", "decode_attn_roofline.hybrid",
       "slot_state_bytes.hybrid")


@pytest.fixture(scope="module")
def fam_and_fields():
    return manifest.model(CONFIG)


def _json(kind, name):
    with open(os.path.join(manifest.HERE, kind, name + ".json")) as f:
        return json.load(f)


def test_the_published_keys_become_the_programs_fields(fam_and_fields):
    fam, m = fam_and_fields
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["n_layers"], m["vocab_size"]) == (4096, 64, 8, 128, 4, 24576)
    assert (m["kda_head_dim"], m["kda_rank"], m["conv_kernel"]) \
        == (128, 128, 4)
    # the published list is kept whole in the file; the layers it names
    # inside the cut are the program's
    assert (m["gqa_layers"], m["gqa_interval"]) == ([0], 3)
    assert (m["d_ff"], m["shared_d_ff"]) == (1280, 1280)
    assert (m["n_experts"], m["top_k"], m["n_group"], m["topk_group"],
            m["routed_scaling_factor"]) == (320, 8, 1, 1, 1.0)
    assert m["held_experts"] == [0, 40]
    assert (m["rms_eps"], m["published_layers"], m["dtype"]) \
        == (1e-5, 48, "bfloat16")
    assert fam.layer_counts(m) == {"kda": 3, "full": 1, "moe": 4}
    config = _json("configs", CONFIG)
    lin = config["linear_attn_config"]
    for key, value in (
            ("use_rope", True), ("use_gqa_gate", False),
            ("kda_use_full_proj", True), ("kda_allow_neg_eigval", False),
            ("norm_topk_prob", False), ("first_k_dense_replace", 1),
            ("n_shared_experts", 2), ("tie_word_embeddings", True),
            ("linear_attn_config", {**lin, "num_heads": 32}),
            ("linear_attn_config", {**lin, "num_kv_heads": 8})):
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_three_cuts():
    """Every key of the catalog's row as published but the depth and the
    vocabulary (the router stays 320 wide: 40 are held); what was read
    into the keys is under ``assumed``."""
    config = _json("configs", CONFIG)
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3,
        "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_routed_experts": 320, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 8}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["held_experts"], config["published_num_hidden_layers"]) \
        == (4, 24576, [0, 40], 48)
    assert 8 * config["vocab_size"] == 196608
    assert 8 * config["held_experts"][1] == config["n_routed_experts"]
    assert list(config["reduced"]) == ["num_hidden_layers",
                                       "n_routed_experts", "vocab_size"]
    assert "3,308,353,344" in config["reduced"]["vocab_size"]
    for reading in ("router", "kda_decay", "kda_low_rank", "kda_beta",
                    "kda_inputs", "gqa_gate", "gqa_scores", "block",
                    "shared_expert", "intermediate_size", "serving_types",
                    "initialisation"):
        assert config["assumed"][reading]
    assert "softplus" in config["assumed"]["kda_decay"]
    assert "2 * sigmoid" in config["assumed"]["kda_beta"]
    assert {"exchange", "max_position_embeddings"} <= set(config["left_out"])
    assert "twelve pipeline stages of eight" in config["deployment"]
    # BENCHMARK.json lists the same cut, and the cell under its name
    b = manifest.load_manifest()
    entry = [c for c in b["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == list(config["reduced"])
    assert entry["source"] == config["source"]
    cell = manifest.cell(b, CELL)
    assert (cell["chips"], cell["traffic_name"]) \
        == (1, "longreason-saturated")
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["out_tokens_per_s", "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {*NEW, "kda_step_roofline.reason", "moe_gmm_roofline.reason",
            "moe_held_assignment_share.reason", "decode_hbm_share.doc",
            "decode_chunk_ms.doc", "prefill_device_share.doc",
            "device_part_share.attn",
            "device_part_share.moe_experts"} <= names
    # the readers whose row or state rule is another block's stay away
    assert not {"decode_attn_roofline.doc", "decode_attn_roofline.mix",
                "slot_state_bytes.reason", "slot_state_bytes.mix",
                "device_part_share.mlp", "moe_gmm_roofline.doc"} & names
    for new in NEW:
        metric = [p for p in b["per_layer"] if p["name"] == new][0]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "out_tokens_per_s"


def test_the_traffic_is_longdoc_saturated_twice_as_long_four_times_out():
    reason, long = (_json("traffic", name) for name in (
        "longreason-saturated", "longdoc-saturated"))
    assert reason["shapes"]["entries"] == [
        [2 * p, 4 * o] for p, o in long["shapes"]["entries"]]
    prompts = [p for p, _ in reason["shapes"]["entries"]]
    outs = [o for _, o in reason["shapes"]["entries"]]
    assert sorted(set(prompts)) == [8192, 9984, 12160, 14848, 18080, 22048,
                                    26880, 32768]
    assert sorted(set(outs)) == [512, 768, 1280, 2048]
    assert (sum(prompts) / 32, sum(outs) / 32) == (18120, 1152)
    assert (reason["loop"], reason["clients"]) == ("closed", 48)
    assert reason["engine"] == {
        "slots": 32, "max_len": 32768 + 2048 + 16, "chunk_tokens": 16,
        "prompt_buckets": [8192, 16384, 24576, 32768]}
    # a bucket over 1,024 rows is whole 1,024-row blocks (the flash
    # kernel's) and whole 2,048-row segments of whole 64-row chunks
    assert all(b % 2048 == 0 for b in reason["engine"]["prompt_buckets"])
    assert max(prompts) <= max(reason["engine"]["prompt_buckets"])
    assert reason["window"] == {"opens_after_completed": 32}
    assert reason["trace_seconds"] == 8
    # the mean bucket a prompt is padded to: 84.3% of its rows are real
    buckets = [min(b for b in reason["engine"]["prompt_buckets"] if b >= p)
               for p in prompts]
    assert sum(buckets) / 32 == 21504


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import jax

    fam, m = fam_and_fields
    w = 64 * 128
    kda = 4 * 4096 * w + 2 * (4096 * 128 + 128 * w) + 4096 * 64 \
        + 3 * w * 4 + 64 + w + 128
    assert fam.kda_params(m) == kda == 137_732_288
    gqa = 4096 * (64 + 2 * 8) * 128 + 2 * 4096 * w
    assert fam.gqa_params(m) == gqa == 109_051_904
    assert fam.expert_params(m) == 3 * 4096 * 1280 == 15_728_640
    assert fam.moe_fixed_params(m) == 4096 * 320 + 320 + 15_728_640 \
        == 17_039_680
    layer = 40 * 15_728_640 + 17_039_680
    assert layer == 646_185_280
    ends, norms = 2 * 24576 * 4096, 4 * 2 * 4096 + 4096
    held = 3 * kda + gqa + 4 * layer + ends + norms
    assert fam.num_params(m) == held == 3_308_353_344
    assert 6.61e9 < 2 * held < 6.63e9
    # the whole model from the same arithmetic: the row's "250B"
    whole = {**m, "n_layers": 48, "gqa_layers": list(range(0, 48, 4)),
             "held_experts": None, "vocab_size": 196608}
    assert fam.num_params(whole) == 250_287_810_304
    # and it is what init_params allocates, leaf by leaf
    prog = fam.build(m, max_seq_len=34832, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(a.size for a in leaves) == held
    assert abs(sum(a.size * a.dtype.itemsize for a in leaves)
               - 2 * held) < 1 << 20  # (the float32 vectors)
    mlp = shapes["layers"][1]["mlp"]
    assert mlp["w_gate"].shape == (40, 4096, 1280)
    assert mlp["router"].shape == (4096, 320)
    assert shapes["layers"][0]["attn"]["w_qkv"].shape == (4096, 80 * 128)
    assert shapes["layers"][1]["attn"]["w_qkv"].shape == (4096, 3 * w)
    assert shapes["layers"][1]["attn"]["w_f_down"].shape == (4096, 128)
    assert shapes["lm_head"].shape == (4096, 24576)
    # a token meets one held expert of its eight in the mean
    assert fam.matmul_params(m) == 3 * kda + gqa + 4 * (
        17_039_680 + 15_728_640) + 4096 * 24576 == 753_985_344
    assert fam.flash_calls(m, 1, 32768) == []
    assert fam.train_flops_per_token(m, 4096) == 3.0 * (
        2 * 753_985_344 + 2 * 64 * 4096 * 0.5 * 2 * 128
        + 3 * 2 * 4 * 64 * 128 ** 2)


def test_a_slots_state_and_a_decode_steps_bytes_by_hand(fam_and_fields):
    import jax

    from ray_tpu.models import solar

    fam, m = fam_and_fields
    assert fam.kv_row_bytes(m) == 2 * 8 * 128 * 2 == 4096
    assert fam.kda_state_bytes(m, 1) == 64 * 128 * 128 * 4 == 4_194_304
    state = fam.state_bytes_per_slot(m, 34832)
    assert state == {"recurrent": 3 * (4_194_304 + 3 * 3 * 8192 * 2),
                     "full": 34832 * 4096}
    assert state == {"recurrent": 13_025_280, "full": 142_671_872}
    assert 32 * sum(state.values()) == 4_982_308_864
    # four GQA layers would keep 18.3 GB for the slots
    assert 32 * 4 * 142_671_872 > 18.2e9
    prog = fam.build(m, max_seq_len=34832, remat=False)
    shapes = jax.eval_shape(
        lambda: solar.SLOTS.init_state(prog.cfg, 32, 34832))
    assert solar.SLOTS.state_bytes(shapes) == {
        kind: 32 * n for kind, n in state.items()}
    assert shapes["k_full"].shape == (1, 32, 34832, 1024)
    assert [st["s"].shape for st in shapes["kda"]] == [(32, 64, 128, 128)] * 3
    assert solar.SLOTS.row_kinds(prog.cfg) == {"recurrent": (3, 0),
                                               "full": (1, None)}
    resident = 2 * fam.num_params(m) + 32 * sum(state.values())
    assert 0.72 < resident / 16e9 < 0.73
    touched = 40 * (1 - (1 - 8 / 320) ** 32)           # 22.2 of 40
    assert fam.experts_touched(m, 32) == pytest.approx(22.21, abs=1e-2)
    assert fam.experts_touched(m, 1) == pytest.approx(1.0)
    weights = 2 * (3 * 137_732_288 + 109_051_904
                   + 4 * (17_039_680 + touched * 15_728_640)
                   + 4096 * 24576 + 32 * 4096)
    moved = 32 * (2 * 13_025_280 + 18700 * 4096)
    assert fam.decode_step_bytes(m, 32, 18700) == pytest.approx(
        weights + moved)
    # 4.2 GB of weights, 2.45 GB of live rows, 0.83 GB of state
    assert 4.1e9 < weights < 4.3e9
    assert 2.4e9 < 32 * 18700 * 4096 < 2.5e9
    assert 7.4e9 < fam.decode_step_bytes(m, 32, 18700) < 7.5e9


# ------------------------------------------- readers on a small trace

SPANS = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0,
     {"engine": "decode-1", "slots": 32, "max_len": 34832,
      "recurrent_bytes": 32 * 13_025_280, "full_bytes": 32 * 142_671_872,
      "recurrent_layers": 3, "full_layers": 1}],
    ["serve.pump", 1000, 9000, {"active": 32, "queued": 16}],
    ["engine.prefill", 1200, 500, {"bucket": 32768, "prompts": 1, "rows": 1,
                                   "tokens": 26880, "segments": 16}],
    ["engine.readback", 2000, 7000,
     {"live_rows": 590_000, "cache_rows": 32 * 34832,
      "live_rows_full": 590_000, "live_rows_recurrent": 0,
      "experts_touched": 22.0, "assignments": 256.0,
      "held_assignments": 32.0, "expert_load_max": 4000.0,
      "expert_load_mean": 3400.0}],
    ["serve.pump", 11000, 9000, {"active": 32, "queued": 16}],
    ["engine.readback", 12000, 7000,
     {"live_rows": 610_000, "cache_rows": 32 * 34832,
      "live_rows_full": 610_000, "live_rows_recurrent": 0,
      "experts_touched": 23.0, "assignments": 256.0,
      "held_assignments": 34.0}],
]}]}
# another block's event: rings beside full stacks, no recurrent state
MIX = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0,
     {"slots": 64, "window_bytes": 1 << 27, "full_bytes": 1 << 29,
      "window_layers": 4, "full_layers": 1}],
    ["engine.readback", 2000, 7000, {"live_rows": 5000, "cache_rows": 98816,
                                     "live_rows_full": 5000,
                                     "live_rows_window": 4000}]]}]}
OPS = "XLA Ops"


def _trace(events, modules=()):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": OPS, "events": events},
        {"name": "XLA Modules", "events": list(modules)}]}]}


def _facts(**more):
    return {"spans": SPANS, "model": CONFIG, "engine": {"slots": 32},
            "device": {"kind": "TPU v5 lite"}, "log_dir": None, **more}


def test_the_slots_state_reader_wants_both_kinds():
    slot = manifest.layer_metric_reader("slot_state_bytes.hybrid")
    assert slot(_facts()) == 13_025_280 + 142_671_872 == 155_697_152
    # another block's event, a parent commit: nothing, and nothing raised
    assert slot(_facts(spans=MIX)) is None
    assert slot(_facts(spans=None)) is None
    assert slot({"log_dir": None}) is None
    touched = manifest.layer_metric_reader("moe_experts_touched.doc")
    share = manifest.layer_metric_reader("moe_held_assignment_share.reason")
    assert touched(_facts()) == pytest.approx(22.5)
    assert share(_facts()) == pytest.approx(100 * 66 / 512)


def test_the_attention_kernels_roofline_counts_the_full_layers_rows():
    """Thirty-two ``decode_attn`` events (two chunks' steps of the one
    full layer). A call must read the slots' mean 600,000 live rows
    once, 4,096 B each; at that time the share reads 100; the window
    block's event and the latent kernel's are not this reader's."""
    roofline = manifest.layer_metric_reader("decode_attn_roofline.hybrid")
    name = "custom-call/1out/decode_attn.12"
    least = 600_000 * 4096 / 819e9                       # 3.0 ms
    events = [[name, i * 9_000_000, 4_500_000] for i in range(32)]
    got = roofline(_facts(trace=_trace(events)))
    assert got == pytest.approx(100 * least / 4.5e-3, rel=1e-6)
    assert 66 < got < 67
    at_peak = [[name, i * 9_000_000, int(1e9 * least)] for i in range(32)]
    assert roofline(_facts(trace=_trace(at_peak))) == pytest.approx(
        100.0, rel=1e-3)
    # the .doc reader on the same trace would take heads of 4096 / 64
    assert manifest.layer_metric_reader("decode_attn_roofline.doc")(
        _facts(trace=_trace(events))) == pytest.approx(got / 2, rel=1e-6)
    latent = [["custom-call/1out/decode_attn_latent.3", 0, 700_000]]
    assert roofline(_facts(trace=_trace(latent))) is None
    assert roofline(_facts(spans=MIX, trace=_trace(events))) is None
    assert roofline(_facts(trace=_trace([]))) is None
    assert roofline(_facts(spans=None, trace=_trace(events))) is None
    assert roofline(_facts()) is None


def test_the_state_kernels_roofline_asks_the_family_for_the_heads():
    """``kda_step_roofline.reason`` on this model's calls: 32 slots x 64
    heads x 128 x 128 x 4 B read and written, 268.4 MB, 327.7 us at the
    HBM's peak (twice Ling's)."""
    roofline = manifest.layer_metric_reader("kda_step_roofline.reason")
    least = 2 * 32 * 64 * 128 * 128 * 4 / 819e9
    assert least == pytest.approx(327.7e-6, rel=1e-3)
    events = [[f"custom-call/2out/kda_step.{7 + i % 3}", i * 1_000_000,
               425_000] for i in range(48)]
    modules = [["jit_decode_chunk(1)", 0, 48_000_000]]
    got = roofline(_facts(trace=_trace(events, modules)))
    assert got == pytest.approx(100 * least / 425e-6, rel=1e-6)
    assert 77 < got < 77.2
    assert roofline(_facts(trace=_trace([]))) is None


def test_the_linear_share_reads_the_prefill_programs_alone(tmp_path):
    """From the replica's map: the ``attn/attn_linear`` part's seconds
    inside the prefill program over that program's seconds; the decode
    chunk's ``kda_step`` does not count, nor does the full layer's
    flash call."""
    share = manifest.layer_metric_reader("prefill_linear_attn_share.hybrid")
    doc = {"engine": "decode-1", "seconds": 0.1, "programs": {
        "jit__prefill_batch_into_slots": [{"what": "1 x 32768", "parts": {
            "flash.1": "attn/attn_full", "fusion.2": "attn/attn_linear",
            "fusion.3": "qkv", "fusion.4": "moe_experts"}}],
        "jit_decode_chunk": [{"what": "16 steps", "parts": {
            "kda_step.1": "attn/attn_linear", "fusion.9": "moe_experts"}}]}}
    (tmp_path / "program_parts.json").write_text(json.dumps(doc))
    ops = [["custom-call/2out/flash.1", 1_000, 20_000],
           ["fusion.2", 30_000, 90_000], ["fusion.3", 125_000, 40_000],
           ["fusion.4", 170_000, 50_000],
           ["custom-call/2out/kda_step.1", 410_000, 80_000],
           ["fusion.9", 500_000, 20_000]]
    modules = [["jit__prefill_batch_into_slots(1)", 0, 400_000],
               ["jit_decode_chunk(2)", 400_000, 200_000]]
    facts = _facts(trace=_trace(ops, modules), log_dir=str(tmp_path))
    assert share(facts) == pytest.approx(100 * 90 / 200)
    assert manifest.layer_metric_reader("prefill_attn_share.long")(
        facts) == pytest.approx(100 * 110 / 200)
    # no map (a parent commit, a CPU), no prefill call in the trace, a
    # model without such a layer
    assert share(_facts(trace=_trace(ops, modules))) is None
    assert share(_facts(trace=_trace(ops[4:], modules[1:]),
                        log_dir=str(tmp_path))) is None
    doc["programs"]["jit__prefill_batch_into_slots"][0]["parts"][
        "fusion.2"] = "attn/attn_latent"
    (tmp_path / "program_parts.json").write_text(json.dumps(doc))
    assert share(_facts(trace=_trace(ops, modules),
                        log_dir=str(tmp_path))) is None


def test_the_expert_kernels_roofline_at_an_eighth_held(fam_and_fields):
    """``moe_gmm_roofline.reason`` on this model's calls: a decode call's
    operand has 256 rows (32 slots x top-8) of which ~33 are held rows
    over ~22.5 experts, bound by bytes; a segment's prefill call has
    16,384 operand rows of which an eighth are the kernel's."""
    fam, m = fam_and_fields
    roofline = manifest.layer_metric_reader("moe_gmm_roofline.reason")
    nbytes = 2 * (22.5 * 4096 * 1280 + 33 * (4096 + 1280))
    assert fam.gmm_bytes(33, 4096, 1280, 22.5) == nbytes
    least = nbytes / 819e9
    assert least > fam.gmm_flops(33, 4096, 1280) / 197e12
    events = [(256, 1280, 400e-6), (256, 1280, 400e-6), (256, 4096, 400e-6)]
    assert roofline(_facts(moe_gmm_events=events)) == pytest.approx(
        100 * least / 400e-6, rel=1e-6)
    # a segment of 2,048 rows: 16,384 operand rows x 66 / 512 held,
    # every held expert touched
    work = 16384 * 66 / 512
    seg = max(fam.gmm_flops(work, 4096, 1280) / 197e12,
              fam.gmm_bytes(work, 4096, 1280,
                            fam.experts_touched(m, 2048)) / 819e9)
    assert fam.experts_touched(m, 2048) == pytest.approx(40.0, abs=1e-6)
    assert roofline(_facts(moe_gmm_events=[(16384, 1280, seg)])) \
        == pytest.approx(100.0)


# ------------------------------------------------------ the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("solar_open2")
    ref = manifest.reference(fam)
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    with open(os.path.join(manifest.HERE, "families",
                           "solar_open2.reference.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "ray_tpu" not in body
    assert "import" not in body.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace(
        "import jax.numpy as jnp", "").replace("import jax", "").replace(
        "import numpy as np", "")
    # KDA a token at a time with beta doubled and the decay unbounded,
    # the mask written out, no rotary, the precision the highest
    assert "jax.lax.scan(token, s0" in body
    assert "2.0 * jax.nn.sigmoid(x @ p[\"w_beta\"])" in body
    assert "jax.nn.softplus(f)" in body
    assert "jnp.where(seen, s, -jnp.inf)" in body
    assert "rotary" not in body and "rope" not in body
    assert body.count('default_matmul_precision("highest")') == 5
    # every block is its own jitted call: no whole-tree float32 cast
    for block in (ref._kda_block, ref._gqa_project, ref._gqa_attend,
                  ref._mlp_block, ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 1 and 0 < ref.TRAIN_LOSS_TOL < 0.1


def test_blocks_of_rows_give_the_whole_sequences_forward():
    """The reference in blocks of 16 rows and 8 query rows over 50
    positions (the KDA state and the last three convolution inputs
    handed from block to block; the last blocks short) is its forward
    in one block; ``last`` gives the tail of the full logits; ``states``
    collects every KDA layer's final state."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import solar

    fam = manifest.family("solar_open2")
    ref = manifest.reference(fam)
    m = dict(fam.TINY_FIELDS)
    cfg = fam.build(m, max_seq_len=64, remat=False).cfg
    params = solar.init_params(cfg, jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 256, (2, 50)))
    whole = ref.forward(params, toks, m)
    states = []
    ref.hidden(params, toks, m, states)
    assert [s.shape for s in states] == [(2, 4, 16, 16)] * 3
    was = ref.ROWS, ref.QUERY_ROWS
    ref.ROWS, ref.QUERY_ROWS = 16, 8
    try:
        jax.clear_caches()
        blocks = ref.forward(params, toks, m)
        tail = ref.forward(params, toks, m, last=5)
        in_blocks = []
        ref.hidden(params, toks, m, in_blocks)
    finally:
        ref.ROWS, ref.QUERY_ROWS = was
        jax.clear_caches()
    np.testing.assert_allclose(blocks, whole, atol=2e-5)
    np.testing.assert_allclose(tail, whole[:, -5:], atol=2e-5)
    for a, b in zip(states, in_blocks):
        np.testing.assert_allclose(b, a, atol=2e-5)


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:solar-open2-250b-ep8-1chip`` through proxy, pool,
    replica pump and engine at tiny widths: served tokens agree with the
    plain reference; both kinds of state, the rows and the routing
    counters reach the result line."""
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
    # tiny: 16 experts of which 4 are held, top-4, 4 slots
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 4
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("solar_open2")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        34832 // 16, 4)
    assert metrics["slot_state_bytes.hybrid"]["value"] \
        == sum(per_slot.values())
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_token_use_share.doc"]["value"] <= 100
    for device_only in ("kda_step_roofline.reason",
                        "decode_attn_roofline.hybrid",
                        "prefill_linear_attn_share.hybrid",
                        "moe_gmm_roofline.reason"):
        assert device_only not in metrics  # no device, no kernel
    assert "served tokens against the reference" in proc.stderr


def test_a_checkout_without_the_block_refuses_the_configuration(tmp_path):
    """What the parent commit does with the new cell: ``fields`` asks of
    the files whether the program has the block and raises a
    ``ManifestError``, before any process touches jax or a chip."""
    import shutil

    shutil.copytree(manifest.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.makedirs(tmp_path / "ray_tpu" / "models")  # a program, no solar.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "solar.py" in proc.stderr
