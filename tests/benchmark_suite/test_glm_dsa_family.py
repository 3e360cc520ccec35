"""The family ``glm_moe_dsa`` (``benchmark/families/glm_moe_dsa.py``) by
hand: the configuration's keys against the catalog's row and its cuts,
its parameter counts against ``init_params``' shapes, a slot's state of
two kinds with different layer counts, a decode step's bytes and the
kernels' work by KIND of layer; the ``.ishare`` readers on small
hand-made traces, none of which can read over 100; the reference's
duties; the guard that no older cell's program can reach the new block;
and the CPU rehearsal of the cell through ``benchmark.run`` (never a
measurement).

What these tests say of ``BENCHMARK.json`` stays true when a later PR
appends: an entry is looked up by its name and held to what it must say,
never to its place in a list or to a list's length."""

import ast
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CONFIG = "glm-5.2-ep16-1chip"
CELL = CONFIG + ".longreason-saturated-16"
SHARES = ("prefill_index_share.dsa", "decode_index_share.dsa",
          "prefill_sparse_attn_share.dsa")
ROOFLINES = ("dsa_index_roofline.ishare", "dsa_attn_roofline.ishare",
             "dsa_kth_roofline.ishare", "dsa_decode_attn_roofline.ishare")
NEW = (*ROOFLINES, "dsa_rows_read_share.ishare", "slot_state_bytes.ishare")
V5E = {"kind": "TPU v5 lite"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def fam_and_fields():
    return manifest.model(CONFIG)


def _json(kind, name):
    with open(os.path.join(manifest.HERE, kind, name + ".json")) as f:
        return json.load(f)


def _reader(name):
    return manifest.layer_metric_reader(name)


def test_the_published_keys_become_the_programs_fields(fam_and_fields):
    fam, m = fam_and_fields
    assert (m["d_model"], m["n_layers"], m["vocab_size"],
            m["indexer_layers"], m["first_k_dense"]) \
        == (6144, 5, 19360, [1, 1, 0, 0, 0], 1)
    assert (m["n_heads"], m["q_lora_rank"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["rope_theta"]) == (64, 2048, 512, 192, 64, 256, 8e6)
    assert (m["index_heads"], m["index_head_dim"], m["index_topk"]) \
        == (32, 128, 2048)
    assert (m["dense_d_ff"], m["d_ff"], m["shared_d_ff"], m["n_experts"],
            m["top_k"], m["routed_scaling_factor"], m["held_experts"]) \
        == (12288, 2048, 2048, 256, 8, 2.5, [0, 16])
    assert (m["rms_eps"], m["published_layers"], m["dtype"]) \
        == (1e-5, 78, "bfloat16")
    config = _json("configs", CONFIG)
    for key, other in (("scoring_func", "softmax"), ("rope_interleave", False),
                       ("n_group", 8), ("ep_size", 16),
                       ("model_type", "deepseek_v32")):
        with pytest.raises(manifest.ManifestError, match=key):
            fam.fields({**config, key: other})
    with pytest.raises(manifest.ManifestError, match="indexer_types"):
        fam.fields({**config, "indexer_types": ["shared"] + ["full"] * 4})
    with pytest.raises(manifest.ManifestError, match="mlp_layer_types"):
        fam.fields({**config, "mlp_layer_types": ["sparse"] * 5})
    with pytest.raises(manifest.ManifestError, match="rope_type"):
        fam.fields({**config, "rope_parameters": {
            "rope_theta": 8e6, "rope_type": "yarn"}})


def test_the_file_holds_the_catalogs_keys_and_names_its_cuts():
    """EVERY key of the catalog's row under the same key and, but for
    the six in ``reduced``, with the same value; the cut lists are the
    published lists' layers 2 and 6-9; what was read into the keys is
    under ``assumed``, one line each."""
    config = _json("configs", CONFIG)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f) if r["name"] == "GLM-5.2"][0]
        assert config["source"] == row["source_url"]
        published = row["config"]
        for key, value in published.items():
            assert key in config, key
            if key not in config["reduced"]:
                assert config[key] == value, key
        picked = [2, 6, 7, 8, 9]
        assert config["indexer_types"] == [published["indexer_types"][i]
                                           for i in picked]
        assert config["mlp_layer_types"] == [published["mlp_layer_types"][i]
                                             for i in (2, 6, 7, 8, 9)]
        assert 8 * config["vocab_size"] == published["vocab_size"]
        assert published["num_hidden_layers"] \
            == config["published_num_hidden_layers"]
    widths = {
        "hidden_size": 6144, "num_attention_heads": 64,
        "num_key_value_heads": 64, "head_dim": 192, "qk_head_dim": 256,
        "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
        "q_lora_rank": 2048, "kv_lora_rank": 512, "index_n_heads": 32,
        "index_head_dim": 128, "index_topk": 2048, "index_topk_freq": 4,
        "index_skip_topk_offset": 3, "intermediate_size": 12288,
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
        "index_share_for_mtp_iteration": True,
        "max_position_embeddings": 1048576, "rms_norm_eps": 1e-05,
        "model_type": "glm_moe_dsa", "ep_size": 1}
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["rope_parameters"] == {"rope_theta": 8000000,
                                         "rope_type": "default"}
    assert config["indexer_types"] == ["full", "full", "shared", "shared",
                                       "shared"]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["vocab_size"], config["held_experts"],
            config["published_num_hidden_layers"]) \
        == (5, 1, 19360, [0, 16], 78)
    assert 16 * config["held_experts"][1] == config["n_routed_experts"]
    assert set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "indexer_types",
        "mlp_layer_types", "n_routed_experts", "vocab_size"}
    assert "3,881,517,056" in config["reduced"]["vocab_size"]
    assert "240,758,784" in config["reduced"]["vocab_size"]
    for count in ("165,022,208", "9,371,904", "808,336,128", "817,708,032",
                  "400,898,816"):
        assert count in config["reduced"]["n_routed_experts"], count
    for reading in ("index_share", "indexer", "ties", "rotary",
                    "attention_scale", "norm_placement", "router",
                    "serving_types", "initialisation"):
        assert config["assumed"][reading]
    assert "57 x 9,371,904" in config["assumed"]["index_share"]
    assert set(config["left_out"]) == {"mtp", "exchange", "index_fp8",
                                       "long_context"}
    assert "sixteen pipeline stages of sixteen v5e chips" \
        in config["deployment"]
    assert "16 x 8 / 256 = half a row" in config["deployment"]
    # BENCHMARK.json lists the same cut once, and the cell under its name
    b = manifest.load_manifest()
    entries = [c for c in b["configs"] if c["name"] == CONFIG]
    assert len(entries) == 1
    assert entries[0]["reduced"] == list(config["reduced"])
    assert entries[0]["source"] == config["source"]
    assert entries[0]["file"] == f"benchmark/configs/{CONFIG}.json"
    assert sum(w["name"] == CELL for w in b["workloads"]) == 1
    assert {w["name"] for w in b["workloads"] if w["config"] == CONFIG} \
        == {CELL}
    cell = manifest.cell(b, CELL)
    assert cell["chips"] == 1
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {*NEW, *SHARES, "tokens_per_pump.doc", "ttft_p50_ms.doc",
            "tpot_p50_ms.doc", "prefill_device_share.doc",
            "decode_chunk_ms.doc", "decode_hbm_share.doc",
            "prefill_token_use_share.doc", "prefill_rows_run_share.doc",
            "pump_host_work_ms.doc", "moe_experts_touched.doc",
            "moe_expert_load_max_over_mean.doc",
            "moe_held_assignment_share.reason", "moe_gmm_roofline.reason",
            *("setup_" + s + ".serve" for s in (
                "process_spawn_s", "chip_claim_s", "weights_s",
                "trace_lower_s", "compile_s", "compile_cache_hit_share")),
            *("device_part_share." + p for p in (
                "attn", "mlp", "moe_experts", "lm_head", "sample", "cache",
                "loop", "unscoped"))} <= names
    # the readers whose kernel, count of layers or state rule is another
    # block's stay away: the ``.dsa`` rooflines take ONE count of layers
    assert not {"latent_attn_roofline.long", "flash_fwd_roofline.swa",
                "prefill_attn_share.swa", "decode_attn_roofline.swa",
                "slot_state_bytes.swa", "slot_state_bytes.dsa",
                "dsa_rows_read_share.dsa", "dsa_index_roofline.dsa",
                "dsa_attn_roofline.dsa", "dsa_kth_roofline.dsa",
                "dsa_decode_attn_roofline.dsa", "kda_step_roofline.reason",
                "ssd_step_roofline.ssm", "moe_gmm_roofline.doc"} & names
    for new in NEW:
        metrics = [p for p in b["per_layer"] if p["name"] == new]
        assert len(metrics) == 1, new
        assert metrics[0]["workloads"] == [CELL]
        assert metrics[0]["moves"] == "out_tokens_per_s"
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", new + ".py"))
        older = [p for p in b["per_layer"]
                 if p["name"] == new.replace(".ishare", ".dsa")][0]
        assert {k: metrics[0][k] for k in ("unit", "better", "source",
                                           "layer")} \
            == {k: older[k] for k in ("unit", "better", "source", "layer")}
    for roofline in ROOFLINES:
        assert [p for p in b["per_layer"] if p["name"] == roofline][0][
            "unit"] == "%"
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_the_traffic_is_the_three_other_hybrids_file_at_16_slots():
    """``longreason-saturated-16.json`` is dots3's file (and so Solar-
    Open2's and MiMo-V2.5's) with 16 slots for 24 and 24 callers for 36
    and every other field equal, the shapes entry for entry, so four
    hybrids stand under one set of shapes; every prompt is 4 to 16 times
    ``index_topk``."""
    b = manifest.load_manifest()
    tr = manifest.cell(b, CELL)["traffic"]
    other = _json("traffic", "longreason-saturated-24")
    assert manifest.cell(b, CELL)["traffic_name"] == "longreason-saturated-16"
    assert tr["shapes"] == other["shapes"]
    assert (tr["clients"], tr["engine"]["slots"]) == (24, 16)
    assert (other["clients"], other["engine"]["slots"]) == (36, 24)
    for t in (tr, other):
        t["clients"] = t["engine"]["slots"] = None
        t["why"] = t["why"].replace("24 callers on 16", "36 callers on 24")
    assert tr == other
    tr = manifest.cell(b, CELL)["traffic"]
    eng = tr["engine"]
    assert (eng["max_len"], eng["chunk_tokens"], eng["prompt_buckets"]) \
        == (34832, 16, [8192, 16384, 24576, 32768])
    prompts = [p for p, _ in tr["shapes"]["entries"]]
    assert 4 * 2048 <= min(prompts) and max(prompts) == 16 * 2048
    assert tr["clients"] == eng["slots"] * 3 // 2


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    """ISSUE 60's arithmetic, and what ``init_params`` allocates (by
    shape: nothing is made)."""
    import jax

    fam, m = fam_and_fields
    assert (fam.attn_params(m), fam.index_params(m)) \
        == (165_022_208, 9_371_904)
    assert fam.expert_params(m) == 37_748_736
    assert fam.moe_fixed_params(m) == 1_573_120 + 37_748_736
    shared = 165_022_208 + 37_748_736 + 1_573_120 + 12_288 \
        + 16 * 37_748_736
    assert shared == 808_336_128
    dense = 165_022_208 + 9_371_904 + 3 * 6144 * 12288 + 12_288
    assert dense == 400_898_816
    assert fam.num_params(m) == dense + (shared + 9_371_904) + 3 * shared \
        + 2 * 19360 * 6144 + 6144 == 3_881_517_056
    prog = fam.build(m, max_seq_len=64, remat=False)
    assert prog.cfg.prefill_head_groups == fam.PREFILL_HEAD_GROUPS
    assert prog.cfg.share_groups == ((0,), (1, 2, 3, 4))
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == fam.num_params(m)
    tiny = dict(fam.TINY_FIELDS)
    prog = fam.build(tiny, max_seq_len=64, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == fam.num_params(tiny)
    # a token meets every matrix but 240 of 256 routed experts' share
    assert fam.matmul_params(m) < fam.num_params(m) - 4 * 15 * 37_748_736 \
        + 10**7
    assert fam.flash_calls(m, 1, 4096) == []
    assert fam.train_flops_per_token(m, 4096) > 6 * fam.matmul_params(m)


def test_a_slots_state_a_decode_steps_bytes_and_the_kernels(fam_and_fields):
    fam, m = fam_and_fields
    assert fam.layer_counts(m) == {"index": 2, "sparse": 5, "dense": 1,
                                   "moe": 4}
    assert fam.row_bytes(m) == {"latent": 1280, "index": 256}
    per_slot = fam.state_bytes_per_slot(m, 34832)
    assert per_slot == {"latent": 5 * 34832 * 1280,
                        "index": 2 * 34832 * 256}
    assert sum(per_slot.values()) == 240_758_784
    # a step at 19,000 live rows a slot: the weights outside the experts
    # and ~6 of 16 experts a layer, every live index key in TWO layers,
    # 2,048 chosen latent rows in FIVE
    step = fam.decode_step_bytes(m, 16, 19000.0)
    rows = 16 * 2 * (2 * 19000 * 128 + 5 * 2048 * 576)
    assert 0 < step - rows < 2 * fam.num_params(m)
    assert fam.decode_step_bytes(m, 16, 100.0) < step
    assert 6 < fam.experts_touched(m, 16) < 7
    # the kernels' work: causal pairs for the indexer, CHOSEN pairs for
    # the attention, a segment's keys once for the selection
    flops, nbytes = fam.dsa_index_work(m, 4096)
    assert flops == 2.0 * 32 * 128 * (4096 * 4097 // 2)
    flops, _ = fam.dsa_attn_work(m, 4096)
    chosen = 2048 * 2049 // 2 + 2048 * 2048
    assert fam.chosen_keys(4096, 2048) == chosen
    assert flops == 2.0 * 64 * chosen * 512
    assert fam.dsa_kth_work(m, 18432, 24576) == (0.0, 4.0 * 18432 * 24576)
    flops, nbytes = fam.decode_attn_work(m, 32768.0)
    assert (flops, nbytes) == (2.0 * 64 * 32768 * 1088, 32768 * 1280)
    assert (fam.STEP_READS, fam.PREFILL_HEAD_GROUPS) == ("live", 8)


# ------------------------------------------------------- the readers


def _state_init(**kw):
    return ["engine.state_init", 0, 0, {
        "engine": "e", "slots": 16, "max_len": 34832,
        "latent_bytes": 16 * 222_924_800, "index_bytes": 16 * 17_833_984,
        "latent_layers": 5, "index_layers": 2, "latent_row_bytes": 1280,
        "index_row_bytes": 256, **kw}]


def _facts(ops=(), modules=(), spans=(), model=CONFIG, **kw):
    return {"model": model, "device": V5E,
            "engine": {"prompt_buckets": [8192, 16384, 24576, 32768]},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": list(modules)},
                {"name": "XLA Ops", "events": list(ops)}]}]},
            "spans": {"lines": [{"name": "pump", "events": list(spans)}]},
            **kw}


def test_the_slots_state_reader_wants_the_two_kinds():
    read = _reader("slot_state_bytes.ishare")
    assert read(_facts(spans=[_state_init()])) == 240_758_784
    other = _state_init()
    del other[3]["latent_bytes"]  # (another block's event: dots3's)
    assert read(_facts(spans=[other])) is None
    assert read(_facts()) is None
    # and dots3's reader finds nothing in this block's event
    assert _reader("slot_state_bytes.dsa")(
        _facts(spans=[_state_init()])) is None


def test_the_rows_read_share_is_the_attended_rows_over_the_live_ones():
    """A step of 16 slots at 19,000 rows: five layers are each handed 16
    x 2,048 chosen rows (two selections' worth five times) of 5 x
    304,000 read: 10.8%; a step that gathered would read 100; without
    the counter (a parent, dots3) None."""
    read = _reader("dsa_rows_read_share.ishare")
    back = ["engine.readback", 0, 0, {
        "selected_rows": 2 * 32768.0, "attended_rows": 5 * 32768.0,
        "live_rows": 304000, "live_rows_latent": 304000,
        "live_rows_index": 304000}]
    got = read(_facts(spans=[_state_init(), back, back]))
    assert got == pytest.approx(100.0 * 2048 / 19000)
    assert got < 100
    del back[3]["attended_rows"]
    assert read(_facts(spans=[_state_init(), back])) is None


@pytest.mark.parametrize("name, program, part", [
    ("prefill_index_share.dsa", "jit__prefill_batch_into_slots",
     "attn/attn_index"),
    ("prefill_sparse_attn_share.dsa", "jit__prefill_batch_into_slots",
     "attn/attn_sparse"),
    ("decode_index_share.dsa", "jit_decode_chunk", "attn/attn_index")])
def test_dots3s_shares_read_this_cells_parts_unchanged(name, program, part):
    """The three ``.dsa`` shares this cell was appended to read a part of
    a program by its scope's name, which this block's scopes carry."""
    read = _reader(name)
    facts = {"device_parts": {"busy_s": 2.0, "programs": {
        program: {part: 0.3, "qkv": 0.3, "moe_experts": 0.4}}}}
    assert read(facts) == pytest.approx(30.0)
    b = manifest.load_manifest()
    assert CELL in [p for p in b["per_layer"] if p["name"] == name][0][
        "workloads"]


def _prefill_run(kernel: str, events: int, each_ns: int):
    """One execution of the prefill program with ``events`` events of
    ``kernel`` in it."""
    ops = [[f"custom-call/1out/{kernel}.{i}", 1000 + i * each_ns, each_ns]
           for i in range(events)]
    return ops, [["jit__prefill_batch_into_slots(1)", 0,
                  2000 + events * each_ns]]


@pytest.mark.parametrize("name, kernel, layers, a_segment", [
    ("dsa_index_roofline.ishare", "dsa_index", 2, 1),
    ("dsa_kth_roofline.ishare", "dsa_kth", 2, 1),
    ("dsa_attn_roofline.ishare", "dsa_attn", 5, 8)])
def test_a_prefill_kernels_roofline_counts_its_own_kind_of_layer(
        name, kernel, layers, a_segment, fam_and_fields):
    """A call that ran 9 of a 24,576-row bucket's 12 segments: 2 INDEX
    layers x 9 events of the indexer's kernels, 5 SPARSE layers x 9 x 8
    groups of heads of the attention's; the least time is the family's
    work for 18,432 rows a layer of that kind at the v5e's peaks, and
    events that take twice it read 50%; events as fast as the least read
    100 and no faster exists. A cut execution (one event missing) is
    left out."""
    from benchmark import model_math

    fam, m = fam_and_fields
    read = _reader(name)
    work = {"dsa_index": fam.dsa_index_work, "dsa_kth": fam.dsa_kth_work,
            "dsa_attn": fam.dsa_attn_work}[kernel]
    least = layers * model_math.roofline_seconds(
        *work(m, 18432, 24576), model_math.peaks("TPU v5 lite"))[0]
    events = layers * 9 * a_segment
    ops, modules = _prefill_run(kernel, events,
                                int(2 * least / events * 1e9))
    span = ["engine.prefill", 0, 0, {"bucket": 24576, "segments": 12,
                                     "live_segments": 9, "tokens": 18000}]
    got = read(_facts(ops=ops, modules=modules, spans=[span]))
    assert got == pytest.approx(50.0, rel=1e-3)
    assert got < 100
    assert read(_facts(ops=ops[:-1], modules=modules, spans=[span])) is None
    assert read(_facts(ops=ops, modules=modules)) is None  # no span
    assert read(_facts(spans=[span])) is None  # no event
    assert read(_facts(ops=ops, modules=modules, spans=[span],
                       model="internlm2-1.8b")) is None
    assert read(_facts(ops=ops, modules=modules, spans=[span],
                       model="dots3-note-prev-ep8-1chip")) is None
    # (dots3's reader asks this family for its ONE count, which it has
    # not: the cell is not on that metric's list)
    with pytest.raises(KeyError, match="full"):
        _reader(name.replace(".ishare", ".dsa"))(
            _facts(ops=ops, modules=modules, spans=[span]))


def test_the_decode_kernels_roofline_is_the_attended_rows_read_once(
        fam_and_fields):
    """16 slots x 2,048 chosen rows a call, five calls a step: 41.9 MB at
    819 GB/s and 4.6e9 operations at the matrix unit's peak; the larger
    bound over events twice as long reads 50%."""
    from benchmark import model_math

    fam, m = fam_and_fields
    read = _reader("dsa_decode_attn_roofline.ishare")
    one = model_math.roofline_seconds(
        *fam.decode_attn_work(m, 32768.0), model_math.peaks("TPU v5 lite"))[0]
    ops = [[f"custom-call/1out/dsa_decode_attn.{i}", i * 10_000_000,
            int(2 * one * 1e9)] for i in range(40)]
    back = ["engine.readback", 0, 0, {
        "selected_rows": 2 * 32768.0, "attended_rows": 5 * 32768.0,
        "live_rows_latent": 304000}]
    got = read(_facts(ops=ops, spans=[_state_init(), back]))
    assert got == pytest.approx(50.0, rel=1e-3)
    assert read(_facts(ops=ops, spans=[back])) is None
    assert read(_facts(spans=[_state_init(), back])) is None
    del back[3]["attended_rows"]  # (dots3's read-back)
    assert read(_facts(ops=ops, spans=[_state_init(), back])) is None


def test_no_roofline_can_read_over_100(fam_and_fields):
    """Each kernel's least time against what the kernel cannot do
    without: ``dsa_index`` computes every causal pair the work counts
    (and the tiles astride the diagonal besides) at the matrix unit's
    peak; ``dsa_kth`` reads the keys the work counts; ``dsa_attn`` walks
    every CAUSAL pair where the work counts the chosen; the decode
    kernel reads every LIVE row where the work counts the chosen."""
    from benchmark import model_math

    fam, m = fam_and_fields
    peak = model_math.peaks("TPU v5 lite")
    rows, bucket = 18432, 24576
    flops, _ = fam.dsa_attn_work(m, rows, bucket)
    walked = 2.0 * m["n_heads"] * fam.causal_keys(rows) * 512
    assert flops < walked
    assert model_math.roofline_seconds(flops, 0.0, peak)[0] \
        < model_math.roofline_seconds(walked, 0.0, peak)[0]
    flops, _ = fam.dsa_index_work(m, rows, bucket)
    blocks = sum(2.0 * 32 * 128 * 256 * min(bucket, (q + 1) * 256 + 512)
                 for q in range(rows // 256))
    assert flops <= blocks
    _, nbytes = fam.decode_attn_work(m, 16 * 2048.0)
    assert nbytes < 16 * 19000 * 1280


def test_the_scopes_are_the_eighth_blocks_kinds_of_attention():
    from ray_tpu.models import program_parts as pp

    assert {"attn_index", "attn_sparse"} <= set(pp.ATTN_KINDS)


# ----------------------------------------------------- the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("glm_moe_dsa")
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    ref = manifest.reference(fam)
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    path = os.path.join(manifest.HERE, "families",
                        "glm_moe_dsa.reference.py")
    imported = _imports(path)
    assert not {i for i in imported if i.split(".")[0] in (
        "ray_tpu", "benchmark")}, imported
    with open(path) as f:
        text = f.read()
    assert 'default_matmul_precision("highest")' in text
    assert "argsort" in text and "top_k(" not in text
    assert "pallas" not in text and "rhd->bhr" not in text  # unabsorbed
    assert "-1e30" not in text and "NEG" not in text  # indices, no bias
    assert "left_out" in ref.__doc__ and "assumed" in ref.__doc__
    assert (ref.ROWS, ref.QUERY_ROWS) == (4096, 256)
    assert 0.01 < ref.SERVE_TOP2_GAP < 0.1


# ---------------------------------------------------------- the guard


def _imports(path: str) -> set:
    """The modules a file names in an import statement, anywhere in it."""
    with open(path) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
            found |= {f"{node.module}.{a.name}" for a in node.names}
    return found


def test_no_module_an_older_cells_program_imports_reaches_the_new_block():
    """No module of the package imports ``models/glm_dsa.py``; what it
    shares with dots3's block (``models/dots.py``, ``ops/dsa.py``) those
    two alone import; and none of the modules an OLDER cell's process
    loads (the engine, the protocol, ``moe.py``, the seven other blocks)
    imports any of the three, so no older cell loads, traces or compiles
    a line of the new block."""
    new = "ray_tpu.models.glm_dsa"
    shared = ("ray_tpu.models.dots", "ray_tpu.ops.dsa")
    package = os.path.join(ROOT, "ray_tpu")
    reach_new, reach_shared = {}, {}
    for folder, _, files in os.walk(package):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            found = _imports(path)
            if new in found:
                reach_new[os.path.relpath(path, ROOT)] = new
            if found & set(shared):
                reach_shared[os.path.relpath(path, ROOT)] = found & set(
                    shared)
    models = os.path.join("ray_tpu", "models")
    assert not reach_new, reach_new
    assert set(reach_shared) == {os.path.join(models, "dots.py"),
                                 os.path.join(models, "glm_dsa.py")}
    for older in ("decode_engine", "slots", "moe", "llama", "llama_slots",
                  "ling", "exaone", "instella", "solar", "mimo", "granite"):
        found = _imports(os.path.join(package, "models", older + ".py"))
        assert not {i for i in found if i.rsplit(".", 1)[-1] in (
            "glm_dsa", "dots", "dsa")}, (older, found)
    assert not {i for i in _imports(os.path.join(package, "models",
                                                 "glm_dsa.py"))
                if "decode_engine" in i}
    # the benchmark's own files name the block in its family file alone
    for folder, _, files in os.walk(manifest.HERE):
        for name in files:
            if name.endswith(".py") and "glm_moe_dsa" not in name:
                path = os.path.join(folder, name)
                assert new not in _imports(path), path


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:glm-5.2-ep16-1chip`` through proxy, pool, replica
    pump and engine at tiny widths: served tokens agree with the plain
    reference; the two kinds of state, ``attended_rows`` and the routing
    counters reach the result line."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_CHIPS", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", "3000000001", "--seconds", "3", "--trace", "1",
         "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    metrics = line["metrics"]
    # tiny: 16 experts of which 4 are held, top-4
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 4
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("glm_moe_dsa")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        34832 // 16, 4)
    assert metrics["slot_state_bytes.ishare"]["value"] \
        == sum(per_slot.values())
    # every stream is past index_topk rows: 8 of hundreds read
    assert 0 < metrics["dsa_rows_read_share.ishare"]["value"] < 20
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_rows_run_share.doc"]["value"] <= 100
    for device_only in (*SHARES, *ROOFLINES, "moe_gmm_roofline.reason"):
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
    os.makedirs(tmp_path / "ray_tpu" / "models")  # no glm_dsa.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "glm_dsa.py" in proc.stderr
