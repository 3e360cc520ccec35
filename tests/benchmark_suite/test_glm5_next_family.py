"""The family ``glm5_next`` (``benchmark/families/glm5_next.py``) by
hand: the configuration's keys against the catalog's row and its cuts,
its parameter counts against ``init_params``' shapes, a slot's state of
three kinds, a decode step's bytes and the kernels' work over POOLED
keys and heads without a rotated part; the ``.kpool`` / ``.mhc`` readers
on small hand-made traces, none of which can read over 100; the
reference's duties; the guard that no older cell's program can reach the
new block; and the CPU rehearsal of the cell through ``benchmark.run``
(never a measurement).

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
CONFIG = "glm-5.3-flash-ep8-1chip"
CELL = CONFIG + ".longreason-saturated-24"
ROOFLINES = ("dsa_index_roofline.kpool", "dsa_attn_roofline.kpool",
             "dsa_kth_roofline.kpool", "dsa_decode_attn_roofline.kpool")
NEW = (*ROOFLINES, "dsa_rows_read_share.kpool", "slot_state_bytes.kpool",
       "device_part_share.mhc", "prefill_mhc_share.mhc")
SHARED = ("prefill_index_share.dsa", "decode_index_share.dsa",
          "prefill_sparse_attn_share.dsa", "prefill_qkv_share.dsa",
          "kda_step_roofline.reason", "prefill_linear_attn_share.hybrid",
          "moe_compact_call_share.reason", "moe_gmm_roofline.reason",
          "device_part_share.attn", "decode_hbm_share.doc")
V5E = {"kind": "TPU v5 lite"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SLOT = 55_265_024  # a slot's bytes at max_len 34,832 (the file's sum)


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
    assert (m["d_model"], m["n_layers"], m["vocab_size"], m["layer_types"],
            m["first_k_dense"]) == (4096, 5, 19360, [0, 1, 0, 0, 0], 1)
    assert (m["n_heads"], m["q_lora_rank"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]) \
        == (64, 1536, 512, 256, 0, 256)
    assert (m["index_heads"], m["index_head_dim"], m["index_topk"],
            m["index_pool"]) == (32, 128, 2048, 4)
    assert (m["kda_head_dim"], m["conv_kernel"], m["kda_rank"],
            m["kda_lower_bound"]) == (128, 4, 128, -5.0)
    assert (m["hc_mult"], m["hc_sinkhorn_iters"], m["hc_eps"]) \
        == (4, 20, 1e-6)
    assert (m["dense_d_ff"], m["d_ff"], m["shared_d_ff"], m["n_experts"],
            m["top_k"], m["routed_scaling_factor"], m["swiglu_limit"],
            m["held_experts"], m["published_layers"]) \
        == (12288, 2048, 2048, 288, 8, 2.5, 10.0, [0, 36], 45)
    config = _json("configs", CONFIG)
    for key, bad in (("mhc", False), ("qk_rope_head_dim", 64),
                     ("index_kpool_always_select_tail", False),
                     ("model_type", "glm_moe_dsa")):
        with pytest.raises(manifest.ManifestError, match=key):
            fam.fields({**config, key: bad})
    with pytest.raises(manifest.ManifestError, match="layer_types"):
        fam.fields({**config, "layer_types": config["layer_types"][:4]})
    with pytest.raises(manifest.ManifestError, match="linear_attn_config"):
        fam.fields({**config, "linear_attn_config": {
            **config["linear_attn_config"], "full_attn_layers": [3]}})


def test_the_file_holds_the_catalogs_keys_and_names_its_cuts():
    """EVERY key of the catalog's row under the same key and, but for
    those in ``reduced``, with the same value; the cut lists are the
    published lists' layers 2-6; no width is cut, in the file or in the
    nested group; what was read into the keys is under ``assumed``."""
    config = _json("configs", CONFIG)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = [r for r in map(json.loads, f)
                   if r["name"] == "GLM-5.3-Flash"][0]
        assert config["source"] == row["source_url"]
        published = row["config"]
        for key, value in published.items():
            assert key in config, key
            if key not in config["reduced"]:
                assert config[key] == value, key
        picked = [2, 3, 4, 5, 6]
        for key in ("layer_types", "mlp_layer_types", "indexer_types"):
            assert config[key] == [published[key][i] for i in picked], key
        lin, was = config["linear_attn_config"], \
            published["linear_attn_config"]
        for key in ("num_heads", "head_dim", "short_conv_kernel_size",
                    "gate_lower_bound"):
            assert lin[key] == was[key], key
        assert 8 * config["vocab_size"] == published["vocab_size"]
        assert published["num_hidden_layers"] \
            == config["published_num_hidden_layers"] == 45
    widths = {
        "hidden_size": 4096, "num_attention_heads": 64,
        "num_key_value_heads": 64, "head_dim": 0, "qk_head_dim": 256,
        "qk_nope_head_dim": 256, "qk_rope_head_dim": 0, "v_head_dim": 256,
        "q_lora_rank": 1536, "kv_lora_rank": 512, "index_n_heads": 32,
        "index_head_dim": 128, "index_topk": 2048, "index_kpool": 4,
        "index_kpool_compress": True, "index_kpool_always_select_tail": True,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc": True,
        "mla_use_nope": True, "intermediate_size": 12288,
        "moe_intermediate_size": 2048, "n_routed_experts": 288,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "swiglu_limit": 10,
        "num_nextn_predict_layers": 1, "max_position_embeddings": 1048576,
        "rms_norm_eps": 1e-05, "model_type": "glm5_next_text", "ep_size": 1}
    for key, value in widths.items():
        assert config[key] == value, key
    assert config["linear_attn_config"] == {
        "num_heads": 64, "gate_lower_bound": -5, "head_dim": 128,
        "short_conv_kernel_size": 4, "kda_layers": [0, 2, 3, 4],
        "full_attn_layers": [1]}
    assert config["layer_types"] == [
        "linear_attention", "deepseek_sparse_attention"] \
        + ["linear_attention"] * 3
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["vocab_size"], config["held_experts"]) \
        == (5, 1, 19360, [0, 36])
    assert 8 * config["held_experts"][1] == config["n_routed_experts"]
    assert set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "layer_types",
        "mlp_layer_types", "indexer_types", "linear_attn_config",
        "n_routed_experts", "vocab_size"}
    assert {"index_kpool", "indexer_layers", "indexer_positions", "mhc",
            "kda_decay", "kda_low_rank", "kda_beta", "kda_inputs",
            "attention_scale", "norm_placement", "router", "swiglu_limit",
            "serving_types", "initialisation"} <= set(config["assumed"])
    assert set(config["left_out"]) == {"mtp", "vision_tower", "exchange",
                                       "long_context"}
    assert "nine pipeline stages of eight" in config["deployment"]
    for text in ("4,718,150,030", "55,265,024"):
        assert text in config["reduced"]["vocab_size"], text
    for text in ("1,070,842,390", "1,057,499,734", "289,521,910"):
        assert text in config["reduced"]["n_routed_experts"], text
    # the manifest's entries, by name
    b = manifest.load_manifest()
    entry = [c for c in b["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["file"] \
        == f"benchmark/configs/{CONFIG}.json"
    assert entry[0]["source"] == config["source"]
    assert set(entry[0]["reduced"]) == set(config["reduced"])
    cell = [w for w in b["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["traffic"] == "longreason-saturated-24"
    assert len(cell[0]["why"]) <= 200


def test_the_cells_metrics_are_found_by_name():
    b = manifest.load_manifest()
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "out_tokens_per_s"
        assert callable(_reader(name))
    for name in ROOFLINES:
        assert by_name[name]["unit"] == "%"
    for name in SHARED:
        assert CELL in by_name[name]["workloads"], name
    tokens = [m for m in b["end_to_end"] if m["name"] == "out_tokens_per_s"]
    assert CELL in tokens[0]["workloads"]
    found = manifest.cell(b, CELL)
    assert {m["name"] for m in found["end_to_end"]} \
        == {"out_tokens_per_s", "setup_s"}
    assert found["traffic"]["engine"]["slots"] == 24
    assert found["traffic"]["engine"]["max_len"] == 34832


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import math

    import jax

    fam, m = fam_and_fields
    assert fam.kda_params(m) == 137_732_288
    assert fam.attn_params(m) == 117_442_560
    assert fam.index_params(m) == 6_947_072
    assert fam.hc_params(m) == 393_243
    assert fam.expert_params(m) == 25_165_824
    assert fam.moe_fixed_params(m) == 25_165_824 + 1_179_936
    experts = 36 * 25_165_824 + 25_165_824 + 1_179_936 + 8_192 + 786_486
    assert 137_732_288 + experts == 1_070_842_390
    assert 117_442_560 + 6_947_072 + experts == 1_057_499_734
    dense = 137_732_288 + 3 * 4096 * 12288 + 8_192 + 786_486
    assert dense == 289_521_910
    total = dense + 1_057_499_734 + 3 * 1_070_842_390 \
        + 2 * 19360 * 4096 + 4096
    assert fam.num_params(m) == total == 4_718_150_030
    prog = fam.build(m, max_seq_len=34832, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(math.prod(a.shape)
               for a in jax.tree_util.tree_leaves(shapes)) == total
    assert fam.layer_counts(m) == {"linear": 4, "sparse": 1, "index": 1,
                                   "dense": 1, "moe": 4}
    assert 0 < fam.matmul_params(m) < total
    tiny = dict(fam.TINY_FIELDS)
    small = fam.build(tiny, max_seq_len=64, remat=False)
    shapes = jax.eval_shape(small.init_params, jax.random.PRNGKey(0))
    assert sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(
        shapes)) == fam.num_params(tiny)


def test_a_slots_state_a_decode_steps_bytes_and_the_kernels(fam_and_fields):
    fam, m = fam_and_fields
    per_slot = fam.state_bytes_per_slot(m, 34832)
    assert per_slot == {"recurrent": 4 * (4_194_304 + 147_456),
                        "latent": 34832 * 1024,
                        "index": 8708 * 256 + 768}
    assert sum(per_slot.values()) == SLOT
    assert fam.row_bytes(m) == {"latent": 1024, "index": 256}
    assert fam.pooled_keys(m, 34832) == 8708
    # row t scores its (t + 1) // 4 whole blocks
    assert fam.scored_pairs(m, 8) == 6
    assert fam.scored_pairs(m, 18432) == sum(
        (t + 1) // 4 for t in range(18432))
    # under index_topk rows every causal row is read: blocks + tail
    assert fam.chosen_keys(m, 2048) == 2048 * 2049 // 2
    assert fam.chosen_keys(m, 2051) == 2051 * 2052 // 2
    assert fam.chosen_keys(m, 2052) == 2051 * 2052 // 2 + 2048
    assert fam.chosen_keys(m, 4096) < 4096 * 4097 // 2
    flops, nbytes = fam.dsa_index_work(m, 8192)
    assert flops == 2.0 * 32 * 128 * fam.scored_pairs(m, 8192)
    assert nbytes > 2048 * 128 * 2
    assert fam.dsa_kth_work(m, 18432, 24576) == (0.0, 4.0 * 18432 * 6144)
    flops, nbytes = fam.decode_attn_work(m, 24 * 2048.0)
    assert flops == 2.0 * 64 * 24 * 2048 * 1024
    assert nbytes == 24 * 2048 * 1024
    step = fam.decode_step_bytes(m, 24, 19000.0)
    assert 6.0e9 < step < 2 * fam.num_params(m)
    assert fam.STEP_READS == "live" and fam.PREFILL_HEAD_GROUPS == 8
    assert fam.flash_calls(m, 1, 4096) == []


def _state_init(**kw):
    return ["engine.state_init", 0, 0, {
        "engine": "e", "slots": 24, "max_len": 34832,
        "recurrent_bytes": 24 * 17_367_040, "latent_bytes": 24 * 35_667_968,
        "index_bytes": 24 * 2_230_016, "recurrent_layers": 4,
        "latent_layers": 1, "index_layers": 1, "latent_row_bytes": 1024,
        "index_row_bytes": 64, **kw}]


def _facts(ops=(), modules=(), spans=(), model=CONFIG, **kw):
    return {"model": model, "device": V5E,
            "engine": {"prompt_buckets": [8192, 16384, 24576, 32768]},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": list(modules)},
                {"name": "XLA Ops", "events": list(ops)}]}]},
            "spans": {"lines": [{"name": "pump", "events": list(spans)}]},
            **kw}


def test_the_slots_state_reader_wants_the_three_kinds():
    read = _reader("slot_state_bytes.kpool")
    assert read(_facts(spans=[_state_init()])) == SLOT
    other = _state_init()
    del other[3]["recurrent_bytes"]  # (GLM-5.2's event: two kinds)
    assert read(_facts(spans=[other])) is None
    assert read(_facts()) is None
    # and GLM-5.2's reader would read this block's event: the cell is
    # not on that metric's list
    b = manifest.load_manifest()
    theirs = [m for m in b["per_layer"]
              if m["name"] == "slot_state_bytes.ishare"][0]
    assert CELL not in theirs["workloads"]


def test_the_rows_read_share_is_the_attended_rows_over_the_live_ones():
    """A step of 24 slots at 19,000 rows: the one sparse layer is handed
    24 x 2,050 rows (512 blocks and two rows of the open one) of 456,000
    read: 10.8%; without the counter (a parent, GLM-5.2) None."""
    read = _reader("dsa_rows_read_share.kpool")
    back = ["engine.readback", 0, 0, {
        "selected_rows": 24 * 2050.0, "attended_rows": 24 * 2050.0,
        "index_keys_scored": 24 * 4752.0, "live_rows": 456000,
        "live_rows_latent": 456000, "live_rows_index": 456000,
        "live_rows_recurrent": 0}]
    got = read(_facts(spans=[_state_init(), back, back]))
    assert got == pytest.approx(100.0 * 2050 / 19000)
    assert got < 100
    del back[3]["index_keys_scored"]
    assert read(_facts(spans=[_state_init(), back])) is None


def _parts_facts(folder, parts: dict, program: str):
    """Facts whose ``part_reduce.table`` is ``parts`` nanoseconds of one
    program: one operation a part end to end, the replica's map beside
    the capture (``<log_dir>/program_parts.json``)."""
    from benchmark import part_reduce

    ops, mapping, at = [], {}, 0
    for i, (part, ns) in enumerate(parts.items()):
        ops.append([f"fusion.{i}", at, ns])
        mapping[f"fusion.{i}"] = part
        at += ns
    folder.mkdir()
    (folder / part_reduce.FILE).write_text(json.dumps({
        "engine": "e", "seconds": 0.0, "programs": {program: [
            {"what": "cold, bucket 8192", "parts": mapping}]}}))
    return _facts(ops=ops, modules=[[f"{program}(1)", 0, at]],
                  log_dir=str(folder))


def test_the_stream_shares_read_the_mhc_part(tmp_path):
    """A prefill call of 200 ns of ``mhc``, 500 of the delta rule and
    300 of projections: 20% of the call and of the device's busy time;
    a capture without the part (another model, a parent) reads None."""
    from benchmark.metric_lib import PREFILL

    facts = _parts_facts(tmp_path / "a", {
        "mhc": 200, "attn/attn_linear": 500, "qkv": 300}, PREFILL)
    assert _reader("prefill_mhc_share.mhc")(facts) == pytest.approx(20.0)
    assert _reader("device_part_share.mhc")(facts) == pytest.approx(20.0)
    assert _reader("prefill_linear_attn_share.hybrid")(facts) \
        == pytest.approx(50.0)
    none = _parts_facts(tmp_path / "b", {"attn/attn_linear": 500,
                                         "qkv": 500}, PREFILL)
    assert _reader("prefill_mhc_share.mhc")(none) is None
    assert _reader("device_part_share.mhc")(none) is None
    assert _reader("device_part_share.mhc")(_facts()) is None


def _prefill_run(kernel: str, events: int, each_ns: int):
    ops = [[f"custom-call/1out/{kernel}.{i}", 1000 + i * each_ns, each_ns]
           for i in range(events)]
    return ops, [["jit__prefill_batch_into_slots(1)", 0,
                  2000 + events * each_ns]]


@pytest.mark.parametrize("name, kernel, a_segment", [
    ("dsa_index_roofline.kpool", "dsa_index", 1),
    ("dsa_kth_roofline.kpool", "dsa_kth", 1),
    ("dsa_attn_roofline.kpool", "dsa_attn", 8)])
def test_a_prefill_kernels_roofline_counts_the_one_sparse_layer(
        name, kernel, a_segment, fam_and_fields):
    """A call that ran 9 of a 24,576-row bucket's 12 segments: the ONE
    sparse layer's 9 events of the indexer's kernels, 9 x 8 groups of
    heads of the attention's; the least time is the family's work for
    18,432 rows at the v5e's peaks, and events that take twice it read
    50%. A cut execution is left out; another model's reads None."""
    from benchmark import model_math

    fam, m = fam_and_fields
    read = _reader(name)
    work = {"dsa_index": fam.dsa_index_work, "dsa_kth": fam.dsa_kth_work,
            "dsa_attn": fam.dsa_attn_work}[kernel]
    least = model_math.roofline_seconds(
        *work(m, 18432, 24576), model_math.peaks("TPU v5 lite"))[0]
    events = 9 * a_segment
    ops, modules = _prefill_run(kernel, events,
                                int(2 * least / events * 1e9))
    span = ["engine.prefill", 0, 0, {"bucket": 24576, "segments": 12,
                                     "live_segments": 9, "tokens": 18000}]
    got = read(_facts(ops=ops, modules=modules, spans=[span]))
    assert got == pytest.approx(50.0, rel=1e-3)
    if a_segment > 1:  # (71 events are no whole number of segments; one
        # event fewer of ONE layer's one call a segment is a shorter call)
        assert read(_facts(ops=ops[:-1], modules=modules,
                           spans=[span])) is None
    assert read(_facts(ops=ops, modules=modules)) is None  # no span
    assert read(_facts(spans=[span])) is None  # no event
    for other in ("internlm2-1.8b", "glm-5.2-ep16-1chip"):
        assert read(_facts(ops=ops, modules=modules, spans=[span],
                           model=other)) is None


def test_the_decode_kernels_roofline_is_the_attended_rows_read_once(
        fam_and_fields):
    from benchmark import model_math

    fam, m = fam_and_fields
    read = _reader("dsa_decode_attn_roofline.kpool")
    one = model_math.roofline_seconds(
        *fam.decode_attn_work(m, 24 * 2050.0),
        model_math.peaks("TPU v5 lite"))[0]
    ops = [[f"custom-call/1out/dsa_decode_attn.{i}", i * 10_000_000,
            int(2 * one * 1e9)] for i in range(40)]
    back = ["engine.readback", 0, 0, {
        "selected_rows": 24 * 2050.0, "attended_rows": 24 * 2050.0,
        "index_keys_scored": 1.0, "live_rows_latent": 456000}]
    got = read(_facts(ops=ops, spans=[_state_init(), back]))
    assert got == pytest.approx(50.0, rel=1e-3)
    assert read(_facts(ops=ops, spans=[back])) is None
    assert read(_facts(spans=[_state_init(), back])) is None
    two_kinds = _state_init()
    del two_kinds[3]["recurrent_layers"]  # (GLM-5.2's event)
    assert read(_facts(ops=ops, spans=[two_kinds, back])) is None


def test_no_roofline_can_read_over_100(fam_and_fields):
    """Each kernel's least time against what the kernel cannot do
    without: ``dsa_index`` computes every pair the work counts (whole
    blocks) and the tiles astride the diagonal besides; ``dsa_kth``
    reads the keys the work counts; ``dsa_attn`` walks every CAUSAL pair
    where the work counts the chosen; the decode kernel reads every LIVE
    row where the work counts the chosen."""
    from benchmark import model_math

    fam, m = fam_and_fields
    peak = model_math.peaks("TPU v5 lite")
    rows, bucket = 18432, 24576
    flops, _ = fam.dsa_attn_work(m, rows, bucket)
    walked = 2.0 * m["n_heads"] * (rows * (rows + 1) // 2) * 512
    assert flops < walked
    assert model_math.roofline_seconds(flops, 0.0, peak)[0] \
        < model_math.roofline_seconds(walked, 0.0, peak)[0]
    flops, _ = fam.dsa_index_work(m, rows, bucket)
    # q blocks of 256 rows against the 512-key tiles that begin at or
    # before the block's last row, in positions
    tiles = sum(2.0 * 32 * 128 * 256 * 512 * (
        ((q + 1) * 256 - 1) // (4 * 512) + 1) for q in range(rows // 256))
    assert flops <= tiles
    assert fam.dsa_kth_work(m, rows, bucket)[1] == 4.0 * rows * bucket / 4
    _, nbytes = fam.decode_attn_work(m, 24 * 2050.0)
    assert nbytes < 24 * 19000 * 1024


def test_the_scope_of_the_streams_is_a_part_of_its_own():
    from ray_tpu.models import program_parts as pp

    assert "mhc" in pp.VOCABULARY
    assert pp.part_of("jit(decode_chunk)/while/body/mhc/dot_general") == "mhc"
    assert pp.part_of("jit(x)/while/body/closed_call/attn/attn_index/"
                      "dot_general") == "attn/attn_index"
    for kind in ("attn_linear", "attn_index", "attn_sparse"):
        assert kind in pp.ATTN_KINDS


def test_the_reference_has_its_duties_and_shares_no_code(fam_and_fields):
    fam, _ = fam_and_fields
    ref = manifest.reference(fam)
    assert 0 < ref.SERVE_TOP2_GAP < 1
    path = os.path.join(manifest.HERE, "families", "glm5_next.reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module.split(".")[0])
    assert names <= {"__future__", "functools", "jax", "numpy"}, names
    text = open(path).read()
    for word in ("stable=True", "lax.scan", "for _ in range(iters)",
                 ".mean(2)"):
        assert word in text, word


def _imports(path: str) -> set:
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
    """No module of the package imports ``models/glm_next.py``, and none
    of the modules an older cell's process loads (the engine, the
    protocol, ``moe.py``, the nine other blocks) does: no older cell
    loads, traces or compiles a line of the new block. The benchmark's
    own files name the block in its family file alone."""
    new = "ray_tpu.models.glm_next"
    package = os.path.join(ROOT, "ray_tpu")
    reach = {}
    for folder, _, files in os.walk(package):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                if new in _imports(path):
                    reach[os.path.relpath(path, ROOT)] = new
    assert not reach, reach
    assert not {i for i in _imports(os.path.join(package, "models",
                                                 "glm_next.py"))
                if "decode_engine" in i}
    for folder, _, files in os.walk(manifest.HERE):
        for name in files:
            if name.endswith(".py") and "glm5_next" not in name:
                path = os.path.join(folder, name)
                assert new not in _imports(path), path


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:glm-5.3-flash-ep8-1chip`` through proxy, pool, replica
    pump and engine at tiny widths: served tokens agree with the plain
    reference; the three kinds of state, ``index_keys_scored`` and the
    routing counters reach the result line."""
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
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 4
    fam = manifest.family("glm5_next")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        34832 // 16, 4)
    assert metrics["slot_state_bytes.kpool"]["value"] \
        == sum(per_slot.values())
    # every stream is past index_topk rows: 8 to 11 of hundreds read
    assert 0 < metrics["dsa_rows_read_share.kpool"]["value"] < 20
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_rows_run_share.doc"]["value"] <= 100
    for device_only in (*ROOFLINES, "device_part_share.mhc",
                        "prefill_mhc_share.mhc", "kda_step_roofline.reason"):
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
    os.makedirs(tmp_path / "ray_tpu" / "models")  # no glm_next.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "glm_next.py" in proc.stderr
