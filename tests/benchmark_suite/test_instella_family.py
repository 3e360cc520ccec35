"""The family ``instella_moe`` (``benchmark/families/instella_moe.py``) by
hand: the configuration's keys against the catalog's row and its one
cut, its parameter counts against ``init_params``' shapes, a slot's
state as stored, a decode step's bytes at 32 slots; the three ``.long``
readers and the shared ``.doc`` ones on a small hand-made trace; the
traffic file; the reference's blocks; and the CPU rehearsal of the cell
through ``benchmark.run`` (never a measurement)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CONFIG = "instella-moe-16b-a3b-pp4-1chip"
CELL = CONFIG + ".longdoc-saturated"


@pytest.fixture(scope="module")
def fam_and_fields():
    return manifest.model(CONFIG)


def _json(kind, name):
    with open(os.path.join(manifest.HERE, kind, name + ".json")) as f:
        return json.load(f)


def test_the_published_keys_become_the_programs_fields(fam_and_fields):
    fam, m = fam_and_fields
    assert (m["d_model"], m["n_heads"], m["n_layers"], m["vocab_size"]) \
        == (2048, 16, 7, 128896)
    assert (m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"]) == (512, 96, 32, 128)
    assert (m["dense_d_ff"], m["d_ff"], m["shared_d_ff"]) \
        == (10944, 1408, 2816)
    assert (m["n_experts"], m["top_k"], m["n_group"], m["topk_group"],
            m["routed_scaling_factor"]) == (64, 6, 1, 1, 2.5)
    assert m["held_experts"] == [0, 64] and m["first_k_dense"] == 1
    assert (m["gated_attention"], m["farskip"]) == (True, True)
    assert (m["rope_theta"], m["rope_factor"], m["rope_original_max"],
            m["rope_beta_fast"], m["rope_beta_slow"], m["rope_mscale"],
            m["rope_mscale_all_dim"]) == (8e6, 40.0, 4096, 32.0, 1.0, 1.0,
                                          1.0)
    assert (m["rms_eps"], m["published_layers"]) == (1e-6, 27)
    assert fam.layer_counts(m) == {"latent": 7, "dense": 1, "moe": 6}
    config = _json("configs", CONFIG)
    for key, value in (
            ("scoring_func", "softmax"), ("norm_topk_prob", False),
            ("n_group", 8), ("topk_group", 4), ("hidden_act", "gelu"),
            ("tie_word_embeddings", True), ("model_type", "deepseek_v2"),
            ("q_lora_rank", 1536), ("qk_layernorm", False),
            ("rope_interleave", False), ("topk_method", "greedy"),
            ("attention_bias", True), ("num_key_value_heads", 4),
            ("qk_head_dim", 192), ("moe_layer_freq", 2), ("ep_size", 8),
            ("rope_scaling", {**config["rope_scaling"], "type": "linear"})):
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_one_cut():
    """Every key of the catalog's row as published but the depth; what
    was read into the keys is under ``assumed``, FarSkip's equation
    among them with its source."""
    config = _json("configs", CONFIG)
    published = {
        "attention_bias": False, "farskip": True, "ep_size": 1,
        "first_k_dense_replace": 1, "gated_attention": True,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10944, "kv_lora_rank": 512,
        "qk_layernorm": True, "max_position_embeddings": 65536,
        "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts_per_tok": 6,
        "num_key_value_heads": 16, "num_nextn_predict_layers": 1,
        "q_lora_rank": None, "qk_head_dim": 128, "qk_nope_head_dim": 96,
        "qk_rope_head_dim": 32, "rope_interleave": True,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 8000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128896}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["held_experts"],
            config["published_num_hidden_layers"]) == (7, [0, 64], 27)
    assert list(config["reduced"]) == ["num_hidden_layers"]
    assert "4,130,441,984" in config["reduced"]["num_hidden_layers"]
    for reading in ("farskip", "gated_attention", "qk_layernorm", "mla",
                    "rope", "norm_placement", "router", "shared_experts",
                    "serving_types", "initialisation"):
        assert config["assumed"][reading]
    far = config["assumed"]["farskip"]
    assert "arXiv:2511.11505" in far and "s_{l+1} = s_l + a_l + m_l" in far
    assert "a_l = Attn_l(N(s_l - m_{l-1}))" in far
    assert {"mtp", "exchange", "long_context"} <= set(config["left_out"])
    assert "four stages" in config["deployment"]
    # BENCHMARK.json lists the same cut, and the cell under its name
    b = manifest.load_manifest()
    entry = [c for c in b["configs"] if c["name"] == CONFIG][0]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    cell = manifest.cell(b, CELL)
    assert (cell["chips"], cell["traffic_name"]) == (1, "longdoc-saturated")
    assert [m["name"] for m in cell["end_to_end"]] \
        == ["out_tokens_per_s", "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert {"latent_attn_roofline.long", "slot_state_bytes.long",
            "prefill_attn_share.long", "moe_gmm_roofline.doc",
            "decode_hbm_share.doc", "decode_chunk_ms.doc",
            "device_part_share.attn", "device_part_share.mlp",
            "device_part_share.moe_experts"} <= names
    assert not {"decode_attn_roofline.doc", "moe_gmm_roofline.reason",
                "moe_held_assignment_share.reason"} & names
    for new in ("latent_attn_roofline.long", "slot_state_bytes.long",
                "prefill_attn_share.long"):
        metric = [p for p in b["per_layer"] if p["name"] == new][0]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "out_tokens_per_s"


def test_the_traffic_is_doc_saturated_sixteen_times_as_long():
    long, doc = (_json("traffic", name) for name in (
        "longdoc-saturated", "doc-saturated"))
    assert long["shapes"]["entries"] == [
        [16 * p, 2 * o] for p, o in doc["shapes"]["entries"]]
    prompts = [p for p, _ in long["shapes"]["entries"]]
    outs = [o for _, o in long["shapes"]["entries"]]
    assert sorted(set(prompts)) == [4096, 4992, 6080, 7424, 9040, 11024,
                                    13440, 16384]
    assert sorted(set(outs)) == [128, 192, 320, 512]
    assert (sum(prompts) / 32, sum(outs) / 32) == (9060, 288)
    assert (long["loop"], long["clients"]) == ("closed", 48)
    assert long["engine"] == {
        "slots": 32, "max_len": 16384 + 512 + 16, "chunk_tokens": 16,
        "prompt_buckets": [4096, 8192, 12288, 16384]}
    # a bucket over 1,024 rows is whole 1,024-row blocks (the flash
    # kernel's), and every prompt has one
    assert all(b % 1024 == 0 for b in long["engine"]["prompt_buckets"])
    assert max(prompts) <= max(long["engine"]["prompt_buckets"])
    assert long["window"] == {"opens_after_completed": 32}
    assert long["trace_seconds"] == 8


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import jax

    fam, m = fam_and_fields
    attn = 2048 * 2048 + 2048 * 544 + 512 * 16 * 224 + 2048 * 2048 \
        + 2048 * 2048 + 128 + 512
    assert fam.attn_params(m) == attn == 15_532_672
    assert fam.expert_params(m) == 3 * 2048 * 1408 == 8_650_752
    assert fam.moe_fixed_params(m) == 2048 * 64 + 64 + 3 * 2048 * 2816 \
        == 17_432_640
    dense, ends = 3 * 2048 * 10944, 2 * 128896 * 2048
    assert (dense, ends) == (67_239_936, 527_958_016)
    norms = 7 * 2 * 2048 + 2048
    held = 7 * attn + dense + 6 * (64 * 8_650_752 + 17_432_640) + ends \
        + norms
    assert fam.num_params(m) == held == 4_130_441_984
    # 8.26 GB in bf16; the whole 27 layers are 15.9 B parameters
    assert 8.25e9 < 2 * held < 8.27e9
    assert fam.num_params({**m, "n_layers": 27}) == 15_862_792_704
    # and it is what init_params allocates, leaf by leaf
    prog = fam.build(m, max_seq_len=16912, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(a.size for a in leaves) == held
    assert abs(sum(a.size * a.dtype.itemsize for a in leaves)
               - 2 * held) < 1 << 20  # (the norm vectors are float32)
    mlp = shapes["layers"][1]["mlp"]
    assert mlp["w_gate"].shape == (64, 2048, 1408)
    assert mlp["shared_gate"].shape == (2048, 2816)
    assert shapes["layers"][0]["mlp"]["w_up"].shape == (2048, 10944)
    assert shapes["layers"][0]["attn"]["w_kvb"].shape == (512, 16 * 224)
    assert shapes["lm_head"].shape == (2048, 128896)
    # a token meets 6 of the 64 experts, all held
    assert fam.matmul_params(m) == 7 * attn + dense + 6 * (
        17_432_640 + 6 * 8_650_752) + 2048 * 128896
    assert fam.flash_calls(m, 1, 16384) == [(7, 1, 16384, 16, 16, 128)]
    assert fam.train_flops_per_token(m, 4096) == 3.0 * (
        2 * fam.matmul_params(m) + 7 * 2 * 16 * 2048 * (128 + 128))


def test_a_slots_state_and_a_decode_steps_bytes_by_hand(fam_and_fields):
    import jax

    from ray_tpu.models import instella

    fam, m = fam_and_fields
    assert fam.latent_row_bytes(m) == (512 + 32) * 2 == 1088
    assert fam.stored_row_bytes(m) == 640 * 2 == 1280
    state = fam.state_bytes_per_slot(m, 16912)
    assert state == {"latent": 7 * 16912 * 1280} == {"latent": 151_531_520}
    assert 32 * state["latent"] == 4_849_008_640
    # GQA 16 / 16 x 128 would keep 57 KB a position: 31 GB for the slots
    assert 32 * 16912 * 7 * 2 * 16 * 128 * 2 > 31e9
    # and it is what init_state allocates
    prog = fam.build(m, max_seq_len=16912, remat=False)
    shapes = jax.eval_shape(
        lambda: instella.SLOTS.init_state(prog.cfg, 32, 16912))
    assert instella.SLOTS.state_bytes(shapes) == {"latent": 32 * state[
        "latent"]}
    assert shapes["rows"].shape == (7, 32, 16912, 640)
    assert instella.SLOTS.row_kinds(prog.cfg) == {"latent": (7, None)}
    # resident: over a quarter of the chip's 16 GB, by far
    resident = 2 * fam.num_params(m) + 32 * state["latent"]
    assert 0.8 < resident / 16e9 < 0.85
    touched = 64 * (1 - (1 - 6 / 64) ** 32)            # 61.3 of 64
    assert fam.experts_touched(m, 32) == pytest.approx(61.26, abs=1e-2)
    assert fam.experts_touched(m, 1) == pytest.approx(6.0)
    weights = 2 * (7 * 15_532_672 + 67_239_936
                   + 6 * (17_432_640 + touched * 8_650_752)
                   + 2048 * 128896 + 32 * 2048)
    moved = 32 * 9204 * 7 * 1088
    assert fam.decode_step_bytes(m, 32, 9204) == pytest.approx(
        weights + moved)
    # 6.4 GB of touched experts, 1.1 GB of other weights, 2.2 GB of rows
    assert 6.3e9 < 2 * 6 * touched * 8_650_752 < 6.4e9
    assert 2.2e9 < moved < 2.3e9
    assert 9.6e9 < fam.decode_step_bytes(m, 32, 9204) < 9.8e9
    # the kernel's call: every live row once, as stored; the bytes bind
    live = 32 * 9204
    assert fam.latent_attn_bytes(live, m) == live * 1280
    assert fam.latent_attn_flops(live, m) == 2 * 16 * live * (544 + 512)
    assert fam.latent_attn_bytes(live, m) / 819e9 \
        > fam.latent_attn_flops(live, m) / 197e12


# ------------------------------------------- readers on a small trace

SPANS = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0,
     {"engine": "decode-1", "slots": 32, "max_len": 16912,
      "latent_bytes": 32 * 151_531_520, "latent_layers": 7}],
    ["serve.pump", 1000, 9000, {"active": 32, "queued": 16}],
    ["engine.readback", 2000, 7000,
     {"live_rows": 290_000, "cache_rows": 32 * 16912,
      "live_rows_latent": 290_000, "experts_touched": 61.0,
      "assignments": 192.0, "held_assignments": 192.0,
      "expert_load_max": 900.0, "expert_load_mean": 768.0}],
    ["serve.pump", 11000, 9000, {"active": 32, "queued": 16}],
    ["engine.readback", 12000, 7000,
     {"live_rows": 300_000, "cache_rows": 32 * 16912,
      "live_rows_latent": 300_000, "experts_touched": 62.0,
      "assignments": 192.0, "held_assignments": 192.0}],
]}]}
# the hybrid block's state: latent rows beside a recurrent state, and no
# kinds of rows named
HYBRID = {"lines": [{"name": "python", "events": [
    ["engine.state_init", 500, 0,
     {"slots": 32, "recurrent_bytes": 1 << 29, "latent_bytes": 1 << 27}],
    ["engine.readback", 2000, 7000, {"live_rows": 5000,
                                     "cache_rows": 98816}]]}]}
OPS = "XLA Ops"


def _trace(events, modules=()):
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": OPS, "events": events},
        {"name": "XLA Modules", "events": list(modules)}]}]}


def _facts(**more):
    return {"spans": SPANS, "model": CONFIG, "engine": {"slots": 32},
            "device": {"kind": "TPU v5 lite"}, "log_dir": None, **more}


def test_the_slots_state_reader_and_the_shared_counters():
    slot = manifest.layer_metric_reader("slot_state_bytes.long")
    assert slot(_facts()) == 151_531_520
    # the hybrid block's event (latent rows beside a recurrent state),
    # a parent commit: nothing, and nothing raised
    assert slot(_facts(spans=HYBRID)) is None
    assert slot(_facts(spans=None)) is None
    # and the other blocks' readers keep apart from this state
    assert manifest.layer_metric_reader("slot_state_bytes.mix")(
        _facts()) is None
    touched = manifest.layer_metric_reader("moe_experts_touched.doc")
    load = manifest.layer_metric_reader("moe_expert_load_max_over_mean.doc")
    assert touched(_facts()) == pytest.approx(61.5)
    assert load(_facts()) == pytest.approx(900 / 768)


def test_the_latent_kernels_roofline_counts_stored_rows_once(fam_and_fields):
    """Fourteen ``decode_attn_latent`` events (two steps of seven
    layers). A call must read the slots' mean 295,000 live rows once,
    1,280 B each as stored; at that time the share reads 100, and an
    event of the k / v kernel is not this kernel's."""
    roofline = manifest.layer_metric_reader("latent_attn_roofline.long")
    name = "custom-call/1out/decode_attn_latent.3"
    least = 295_000 * 1280 / 819e9                       # 461 us
    events = [[name, i * 2_000_000, 700_000] for i in range(14)]
    got = roofline(_facts(trace=_trace(events)))
    assert got == pytest.approx(100 * least / 700e-6, rel=1e-6)
    assert 60 < got < 70
    at_peak = [[name, i * 2_000_000, int(1e9 * least)] for i in range(14)]
    assert roofline(_facts(trace=_trace(at_peak))) == pytest.approx(
        100.0, rel=1e-3)
    other = [["custom-call/1out/decode_attn.3", 0, 700_000]]
    assert roofline(_facts(trace=_trace(other))) is None
    assert manifest.layer_metric_reader("decode_attn_roofline.doc")(
        _facts(trace=_trace(events))) is None
    # no kernel event (a parent), no spans
    assert roofline(_facts(trace=_trace([]))) is None
    assert roofline(_facts(spans=None, trace=_trace(events))) is None
    assert roofline(_facts()) is None


def test_the_prefill_attention_share_reads_the_prefill_programs_alone(
        tmp_path):
    """From the replica's map: the ``attn`` part's seconds inside the
    prefill program over that program's seconds; the decode chunk's
    attention does not count."""
    share = manifest.layer_metric_reader("prefill_attn_share.long")
    doc = {"engine": "decode-1", "seconds": 0.1, "programs": {
        "jit__prefill_batch_into_slots": [{"what": "1 x 16384", "parts": {
            "flash.1": "attn/attn_latent", "fusion.2": "moe_experts",
            "fusion.3": "qkv"}}],
        "jit_decode_chunk": [{"what": "16 steps", "parts": {
            "latent.1": "attn/attn_latent", "fusion.9": "moe_experts"}}]}}
    (tmp_path / "program_parts.json").write_text(json.dumps(doc))
    ops = [["custom-call/2out/flash.1", 1_000, 30_000],
           ["fusion.2", 40_000, 50_000], ["fusion.3", 95_000, 20_000],
           ["custom-call/1out/latent.1", 210_000, 80_000],
           ["fusion.9", 300_000, 20_000]]
    modules = [["jit__prefill_batch_into_slots(1)", 0, 200_000],
               ["jit_decode_chunk(2)", 200_000, 200_000]]
    facts = _facts(trace=_trace(ops, modules), log_dir=str(tmp_path))
    assert share(facts) == pytest.approx(100 * 30 / (30 + 50 + 20))
    # all programs together it reads otherwise
    assert manifest.layer_metric_reader("device_part_share.attn")(
        facts) == pytest.approx(100 * 110 / 200)
    # no map (a parent commit, a CPU), no prefill call in the trace
    assert share(_facts(trace=_trace(ops, modules))) is None
    assert share(_facts(trace=_trace(ops[3:], modules[1:]),
                        log_dir=str(tmp_path))) is None


def test_the_expert_kernels_roofline_with_every_expert_held(fam_and_fields):
    """``moe_gmm_roofline.doc`` (the dropless layer's reader) on this
    model's calls: a decode call's operand has 192 rows (32 slots x
    top-6) over ~61.5 experts, all of them the kernel's; a 16,384-row
    prefill's 98,304 rows touch all 64 and compute binds. The share
    cannot pass 100% while the kernel takes its bound's time."""
    fam, m = fam_and_fields
    roofline = manifest.layer_metric_reader("moe_gmm_roofline.doc")
    nbytes = 2 * (61.5 * 2048 * 1408 + 192 * (2048 + 1408))
    assert fam.gmm_bytes(192, 2048, 1408, 61.5) == nbytes
    least = nbytes / 819e9
    assert least > fam.gmm_flops(192, 2048, 1408) / 197e12
    events = [(192, 1408, 500e-6), (192, 1408, 500e-6), (192, 2048, 500e-6)]
    assert roofline(_facts(moe_gmm_events=events)) == pytest.approx(
        100 * least / 500e-6, rel=1e-6)
    big = fam.gmm_flops(98304, 2048, 1408) / 197e12
    assert big > fam.gmm_bytes(98304, 2048, 1408, 64) / 819e9
    assert roofline(_facts(moe_gmm_events=[(98304, 1408, big)])) \
        == pytest.approx(100.0)


# ------------------------------------------------------ the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("instella_moe")
    ref = manifest.reference(fam)
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    with open(os.path.join(manifest.HERE, "families",
                           "instella_moe.reference.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "ray_tpu" not in body
    assert "import" not in body.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace("import math", "").replace(
        "import jax.numpy as jnp", "").replace("import jax", "").replace(
        "import numpy as np", "")
    # FarSkip as the configuration's sentence has it, the mask written
    # out, the precision the highest
    assert "_attn_out(s - m_prev" in body and "s = s + a + m_prev" in body
    assert "(j <= i)" in body
    assert body.count('default_matmul_precision("highest")') == 3
    # every block is its own jitted call: no whole-tree float32 cast
    for block in (ref._attn_out, ref._mlp_out, ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 1 and 0 < ref.TRAIN_LOSS_TOL < 0.1


def test_blocks_of_rows_give_the_whole_sequences_attention():
    """The reference's attention in blocks of 16 query rows over 50
    positions (the last block overlaps the one before) is its attention
    in one block; ``last`` gives the tail of the full logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import instella

    fam = manifest.family("instella_moe")
    ref = manifest.reference(fam)
    m = dict(fam.TINY_FIELDS)
    cfg = fam.build(m, max_seq_len=64, remat=False).cfg
    params = instella.init_params(cfg, jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 256, (2, 50)))
    whole = ref.forward(params, toks, m)
    ref.ROW_BLOCK, was = 16, ref.ROW_BLOCK
    try:
        jax.clear_caches()
        blocks = ref.forward(params, toks, m)
        tail = ref.forward(params, toks, m, last=5)
    finally:
        ref.ROW_BLOCK = was
        jax.clear_caches()
    np.testing.assert_allclose(blocks, whole, atol=2e-5)
    np.testing.assert_allclose(tail, whole[:, -5:], atol=2e-5)


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:instella-moe-16b-a3b-pp4-1chip`` through proxy, pool,
    replica pump and engine at tiny widths: served tokens agree with the
    plain reference; the latent state, the rows and the routing counters
    reach the result line."""
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
    # tiny: 16 experts, all held, top-4, 4 slots
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 16
    fam = manifest.family("instella_moe")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        16912 // 16, 4)
    assert metrics["slot_state_bytes.long"]["value"] == per_slot["latent"]
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_token_use_share.doc"]["value"] <= 100
    assert "latent_attn_roofline.long" not in metrics  # no device, no kernel
    assert "moe_gmm_roofline.doc" not in metrics
    assert "served tokens against the reference" in proc.stderr


def test_a_checkout_without_the_block_refuses_the_configuration(tmp_path):
    """What the parent commit does with the new cell: ``fields`` asks of
    the files whether the program has the block and raises a
    ``ManifestError``, before any process touches jax or a chip."""
    import shutil

    shutil.copytree(manifest.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    os.makedirs(tmp_path / "ray_tpu" / "models")  # a program, no instella.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "instella.py" in proc.stderr
