"""The family ``dots3_note`` (``benchmark/families/dots3_note.py``) by
hand: the configuration's keys against the catalog's row and its cuts,
its parameter counts against ``init_params``' shapes, a slot's state of
three kinds, a decode step's bytes and the kernels' work; the ``.dsa``
readers on small hand-made traces; the reference's duties; the guard
that no older cell's program can reach the new block; and the CPU
rehearsal of the cell through ``benchmark.run`` (never a measurement).

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
CONFIG = "dots3-note-prev-ep8-1chip"
CELL = CONFIG + ".longreason-saturated-24"
SHARES = ("prefill_index_share.dsa", "decode_index_share.dsa",
          "prefill_sparse_attn_share.dsa")
ROOFLINES = ("dsa_index_roofline.dsa", "dsa_attn_roofline.dsa",
             "dsa_kth_roofline.dsa", "dsa_decode_attn_roofline.dsa")
NEW = (*SHARES, *ROOFLINES, "dsa_rows_read_share.dsa",
       "slot_state_bytes.dsa")
V5E = {"kind": "TPU v5 lite"}


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
            m["layer_pattern"]) == (5120, 5, 19008, [0, 0, 1, 1, 1])
    assert (m["n_heads"], m["q_lora_rank"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["rope_theta"]) == (128, 1024, 512, 128, 64, 128, 8e7)
    assert (m["index_heads"], m["index_head_dim"], m["index_topk"]) \
        == (64, 128, 2048)
    assert (m["window_heads"], m["window_q_lora_rank"],
            m["window_kv_lora_rank"], m["window_qk_nope_head_dim"],
            m["window_qk_rope_head_dim"], m["window_v_head_dim"],
            m["window_rope_theta"], m["sliding_window"]) \
        == (64, 1024, 1024, 192, 64, 128, 5e4, 513)
    assert (m["first_k_dense"], m["dense_d_ff"], m["d_ff"], m["shared_d_ff"],
            m["n_experts"], m["top_k"], m["routed_scaling_factor"]) \
        == (1, 13824, 1536, 1536, 256, 8, 1.0)
    assert m["held_experts"] == [0, 32] and m["lora_rescale"] is True
    assert (m["rms_eps"], m["published_layers"], m["dtype"]) \
        == (1e-5, 46, "bfloat16")
    config = _json("configs", CONFIG)
    for key, value in (("attention_gate_type", "elementwise"),
                       ("rope_scaling", {"type": "yarn"}),
                       ("apply_mla_qkv_lora_rescale", False),
                       ("n_shared_experts", 2)):
        with pytest.raises(manifest.ManifestError, match=key):
            fam.fields({**config, key: value})
    with pytest.raises(manifest.ManifestError, match="layer_types"):
        fam.fields({**config, "layer_types": ["full_attention"] * 4})
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty


def test_the_file_holds_the_catalogs_keys_and_names_its_cuts():
    """Every number of the catalog's row as published but the depth (with
    ``layer_types`` cut to its first five entries) and the vocabulary
    (the router stays 256 wide: 32 are held); what was read into the keys
    is under ``assumed``, one line each."""
    config = _json("configs", CONFIG)
    published = {
        "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
        "attention_gate_type": "headwise", "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
        "kv_lora_rank": 512, "max_position_embeddings": 524288,
        "model_type": "dots3_note", "moe_intermediate_size": 1536,
        "moe_layer_freq": 1, "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_key_value_heads": 128,
        "q_lora_rank": 1024, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 80000000, "routed_scaling_factor": 1,
        "scoring_func": "sigmoid", "sliding_window_size": 513,
        "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 1024,
        "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
        "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
        "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000,
        "swa_v_head_dim": 128, "tie_word_embeddings": False,
        "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["layer_types"] == ["full_attention"] * 2 \
        + ["sliding_attention"] * 3
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["held_experts"], config["published_num_hidden_layers"]) \
        == (5, 19008, [0, 32], 46)
    assert 8 * config["vocab_size"] == 152064
    assert 8 * config["held_experts"][1] == config["n_routed_experts"]
    assert set(config["reduced"]) == {"num_hidden_layers", "layer_types",
                                      "n_routed_experts", "vocab_size"}
    assert "first five entries" in config["reduced"]["num_hidden_layers"]
    assert "4,087,154,176" in config["reduced"]["vocab_size"]
    assert "923,938,816" in config["reduced"]["n_routed_experts"]
    for reading in ("lora_rescale", "gate", "indexer", "ties", "window",
                    "rotary", "attention_scale", "router", "serving_types",
                    "initialisation"):
        assert config["assumed"][reading]
    assert {"towers", "mtp", "exchange", "long_context"} \
        <= set(config["left_out"])
    assert "ten pipeline stages of eight v5e chips" in config["deployment"]
    assert "32 x 8 / 256 = 1 row" in config["deployment"]
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
    assert {*NEW, "tokens_per_pump.doc", "ttft_p50_ms.doc",
            "tpot_p50_ms.doc", "prefill_device_share.doc",
            "decode_chunk_ms.doc", "decode_hbm_share.doc",
            "prefill_token_use_share.doc", "prefill_rows_run_share.doc",
            "pump_host_work_ms.doc", "moe_experts_touched.doc",
            "moe_expert_load_max_over_mean.doc",
            "moe_held_assignment_share.reason", "moe_gmm_roofline.reason",
            "prefill_attn_share.swa",
            "setup_compile_s.serve",
            *("device_part_share." + p for p in (
                "attn", "mlp", "moe_experts", "lm_head", "sample", "cache",
                "loop", "unscoped"))} <= names
    # the readers whose kernel or state rule is another block's stay away
    assert not {"latent_attn_roofline.long", "flash_fwd_roofline.swa",
                "decode_attn_roofline.swa", "slot_state_bytes.swa",
                "slot_state_bytes.long", "kda_step_roofline.reason",
                "ssd_step_roofline.ssm", "moe_gmm_roofline.doc"} & names
    for new in NEW:
        metrics = [p for p in b["per_layer"] if p["name"] == new]
        assert len(metrics) == 1, new
        assert metrics[0]["workloads"] == [CELL] or CELL in metrics[0][
            "workloads"]
        assert metrics[0]["moves"] == "out_tokens_per_s"
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", new + ".py"))
    for roofline in ROOFLINES:
        assert [p for p in b["per_layer"] if p["name"] == roofline][0][
            "unit"] == "%"
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_the_traffic_is_the_two_other_hybrids_file_at_24_slots():
    """``longreason-saturated-24.json`` is the file that Solar-Open2's
    and MiMo-V2.5's cells run with 24 slots for 32 and 36 callers for 48
    (at 32 slots the 2,048-token answers pass the client's 120 s) and
    every other field equal, so three hybrids stand under one set of
    shapes; every prompt is 4 to 16 times ``index_topk``."""
    b = manifest.load_manifest()
    tr = manifest.cell(b, CELL)["traffic"]
    whole = _json("traffic", "longreason-saturated")
    assert manifest.cell(b, CELL)["traffic_name"] == "longreason-saturated-24"
    assert (tr["clients"], tr["engine"]["slots"]) == (36, 24)
    assert (whole["clients"], whole["engine"]["slots"]) == (48, 32)
    for t in (tr, whole):
        t["clients"] = t["engine"]["slots"] = None
        t["why"] = t["why"].replace("36 callers on 24", "48 callers on 32")
    assert tr == whole
    tr = manifest.cell(b, CELL)["traffic"]
    eng = tr["engine"]
    assert eng["max_len"] == 34832 and eng["prompt_buckets"][-1] == 32768
    prompts = [p for p, _ in tr["shapes"]["entries"]]
    assert 4 * 2048 <= min(prompts) and max(prompts) == 16 * 2048
    assert tr["clients"] == eng["slots"] * 3 // 2


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    """ISSUE 58's arithmetic, and what ``init_params`` allocates (by
    shape: nothing is made)."""
    import jax

    fam, m = fam_and_fields
    attn = fam.attn_params(m)
    assert (attn["full"], attn["window"], fam.index_params(m)) \
        == (134_678_016, 90_834_944, 9_371_904)
    assert fam.expert_params(m) == 23_592_960
    assert fam.moe_fixed_params(m) == 1_310_976 + 23_592_960
    assert fam.num_params(m) == 356_396_800 + 923_938_816 \
        + 3 * 870_723_840 + 194_647_040 == 4_087_154_176
    prog = fam.build(m, max_seq_len=64, remat=False)
    assert prog.cfg.prefill_head_groups == fam.PREFILL_HEAD_GROUPS
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == fam.num_params(m)
    tiny = dict(fam.TINY_FIELDS)
    prog = fam.build(tiny, max_seq_len=64, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(shapes)) \
        == fam.num_params(tiny)
    # a token meets every matrix but 224 of 256 routed experts' share
    assert fam.matmul_params(m) < fam.num_params(m) - 4 * 31 * 23_592_960 \
        + 10**7
    assert fam.flash_calls(m, 1, 4096) == []
    assert fam.train_flops_per_token(m, 4096) > 6 * fam.matmul_params(m)


def test_a_slots_state_a_decode_steps_bytes_and_the_kernels(fam_and_fields):
    fam, m = fam_and_fields
    assert fam.layer_counts(m) == {"window": 3, "full": 2, "dense": 1,
                                   "moe": 4}
    assert fam.row_bytes(m) == {"full": 1280, "index": 256, "ring": 2304}
    per_slot = fam.state_bytes_per_slot(m, 34832)
    assert per_slot == {"full": 2 * 34832 * 1280, "index": 2 * 34832 * 256,
                        "ring": 3 * 513 * 2304}
    assert sum(per_slot.values()) == 110_549_760
    # a step at 19,000 live rows a slot: the weights outside the experts
    # and ~20 of 32 experts a layer, every live index key, 2,048 chosen
    # latent rows a full layer, 513 ring rows a window layer
    step = fam.decode_step_bytes(m, 32, 19000.0)
    rows = 32 * 2 * (2 * 19000 * 128 + 2 * 2048 * 576 + 3 * 513 * 1088)
    assert 0 < step - rows < 2 * fam.num_params(m)
    assert fam.decode_step_bytes(m, 32, 100.0) < step
    assert 19 < fam.experts_touched(m, 32) < 21
    # the kernels' work: causal pairs for the indexer, CHOSEN pairs for
    # the attention, a segment's keys once for the selection
    flops, nbytes = fam.dsa_index_work(m, 4096)
    assert flops == 2.0 * 64 * 128 * (4096 * 4097 // 2)
    flops, _ = fam.dsa_attn_work(m, 4096)
    chosen = 2048 * 2049 // 2 + 2048 * 2048
    assert fam.chosen_keys(4096, 2048) == chosen
    assert flops == 2.0 * 128 * chosen * 320
    assert fam.dsa_kth_work(m, 18432, 24576) == (0.0, 4.0 * 18432 * 24576)
    flops, nbytes = fam.decode_attn_work(m, 65536.0)
    assert (flops, nbytes) == (2.0 * 128 * 65536 * 1088, 65536 * 1280)
    assert (fam.STEP_READS, fam.PREFILL_HEAD_GROUPS) == ("live", 4)


# ------------------------------------------------------- the readers


def _state_init(**kw):
    return ["engine.state_init", 0, 0, {
        "engine": "e", "slots": 32, "max_len": 34832,
        "full_bytes": 32 * 89_169_920, "index_bytes": 32 * 17_833_984,
        "ring_bytes": 32 * 3_545_856, "full_layers": 2, "index_layers": 2,
        "ring_layers": 3, "full_row_bytes": 1280, "index_row_bytes": 256,
        "ring_row_bytes": 2304, **kw}]


def _facts(ops=(), modules=(), spans=(), model=CONFIG, **kw):
    return {"model": model, "device": V5E,
            "engine": {"prompt_buckets": [8192, 16384, 24576, 32768]},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": list(modules)},
                {"name": "XLA Ops", "events": list(ops)}]}]},
            "spans": {"lines": [{"name": "pump", "events": list(spans)}]},
            **kw}


def test_the_slots_state_reader_wants_the_three_kinds():
    read = _reader("slot_state_bytes.dsa")
    assert read(_facts(spans=[_state_init()])) == 110_549_760
    other = _state_init()
    del other[3]["index_bytes"]  # (another block's event)
    assert read(_facts(spans=[other])) is None
    assert read(_facts()) is None


def test_the_rows_read_share_is_the_chosen_rows_over_the_live_ones():
    """A step of 32 slots at 19,000 rows: 2 x 32 x 2,048 chosen of 2 x
    608,000 read: 10.8%; without the counter (a parent, another block)
    None."""
    read = _reader("dsa_rows_read_share.dsa")
    back = ["engine.readback", 0, 0, {
        "selected_rows": 131072.0, "live_rows_full": 608000,
        "live_rows_index": 608000, "live_rows_ring": 16416}]
    got = read(_facts(spans=[_state_init(), back, back]))
    assert got == pytest.approx(100.0 * 131072 / (2 * 608000))
    del back[3]["selected_rows"]
    assert read(_facts(spans=[_state_init(), back])) is None


@pytest.mark.parametrize("name, program, part", [
    ("prefill_index_share.dsa", "jit__prefill_batch_into_slots",
     "attn/attn_index"),
    ("prefill_sparse_attn_share.dsa", "jit__prefill_batch_into_slots",
     "attn/attn_sparse"),
    ("decode_index_share.dsa", "jit_decode_chunk", "attn/attn_index")])
def test_a_share_reads_its_part_of_its_programs_alone(name, program, part):
    read = _reader(name)
    other = ({"jit_decode_chunk", "jit__prefill_batch_into_slots"}
             - {program}).pop()
    facts = {"device_parts": {"busy_s": 2.0, "programs": {
        program: {part: 0.3, "attn/attn_window": 0.1, "qkv": 0.2,
                  "moe_experts": 0.4},
        other: {part: 1.0}}}}
    assert read(facts) == pytest.approx(30.0)
    facts["device_parts"]["programs"][program] = {"qkv": 1.0}
    assert read(facts) is None  # (a model without such a part)
    assert read({}) is None


def _prefill_run(kernel: str, events: int, each_ns: int):
    """One execution of the prefill program with ``events`` events of
    ``kernel`` in it."""
    ops = [[f"custom-call/1out/{kernel}.{i}", 1000 + i * each_ns, each_ns]
           for i in range(events)]
    return ops, [["jit__prefill_batch_into_slots(1)", 0,
                  2000 + events * each_ns]]


@pytest.mark.parametrize("name, kernel, a_segment", [
    ("dsa_index_roofline.dsa", "dsa_index", 1),
    ("dsa_kth_roofline.dsa", "dsa_kth", 1),
    ("dsa_attn_roofline.dsa", "dsa_attn", 4)])
def test_a_prefill_kernels_roofline_counts_the_rows_the_call_ran(
        name, kernel, a_segment, fam_and_fields):
    """A call that ran 9 of a 24,576-row bucket's 12 segments: 2 layers x
    9 (x 4 groups of heads) events; the least time is the family's work
    for 18,432 rows at the v5e's peaks, and events that take twice it
    read 50%. A cut execution (one event missing) is left out."""
    from benchmark import model_math

    fam, m = fam_and_fields
    read = _reader(name)
    work = {"dsa_index": fam.dsa_index_work, "dsa_kth": fam.dsa_kth_work,
            "dsa_attn": fam.dsa_attn_work}[kernel]
    least = 2 * model_math.roofline_seconds(
        *work(m, 18432, 24576), model_math.peaks("TPU v5 lite"))[0]
    events = 2 * 9 * a_segment
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


def test_the_decode_kernels_roofline_is_the_chosen_rows_read_once(
        fam_and_fields):
    """32 slots x 2,048 chosen rows a call: 83.9 MB at 819 GB/s and 1.8e10
    operations at the matrix unit's peak; the larger bound over events
    twice as long reads 50%."""
    from benchmark import model_math

    fam, m = fam_and_fields
    read = _reader("dsa_decode_attn_roofline.dsa")
    one = model_math.roofline_seconds(
        *fam.decode_attn_work(m, 65536.0), model_math.peaks("TPU v5 lite"))[0]
    ops = [[f"custom-call/1out/dsa_decode_attn.{i}", i * 10_000_000,
            int(2 * one * 1e9)] for i in range(32)]
    back = ["engine.readback", 0, 0, {"selected_rows": 131072.0,
                                      "live_rows_full": 608000}]
    got = read(_facts(ops=ops, spans=[_state_init(), back]))
    assert got == pytest.approx(50.0, rel=1e-3)
    assert read(_facts(ops=ops, spans=[back])) is None
    assert read(_facts(spans=[_state_init(), back])) is None


def test_the_new_scopes_are_kinds_of_attention():
    from ray_tpu.models import program_parts as pp

    assert {"attn_index", "attn_sparse"} <= set(pp.ATTN_KINDS)
    assert pp.part_of("jit(decode_chunk)/while/body/attn/attn_index/sum") \
        == "attn/attn_index"
    assert pp.part_of("jit(f)/attn/attn_sparse/dsa_attn") \
        == "attn/attn_sparse"


# ----------------------------------------------------- the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("dots3_note")
    ref = manifest.reference(fam)
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    path = os.path.join(manifest.HERE, "families", "dots3_note.reference.py")
    imported = _imports(path)
    assert not {i for i in imported if i.split(".")[0] in (
        "ray_tpu", "benchmark")}, imported
    with open(path) as f:
        text = f.read()
    assert 'default_matmul_precision("highest")' in text
    assert "argsort" in text and "top_k(" not in text
    assert "pallas" not in text and "rhd->bhr" not in text  # unabsorbed
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
    """PR 49's refusal was an older cell's ``setup_s``: this block and
    its ops are imported by ``models/dots.py`` alone, so no older cell's
    process loads, traces or compiles a line of them; the engine imports
    no block, and no block imports the engine."""
    new = ("ray_tpu.models.dots", "ray_tpu.ops.dsa")
    package = os.path.join(ROOT, "ray_tpu")
    importers = {}
    for folder, _, files in os.walk(package):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            hit = _imports(path) & set(new)
            if hit:
                importers[os.path.relpath(path, ROOT)] = hit
    assert set(importers) == {os.path.join("ray_tpu", "models", "dots.py")}, \
        importers
    engine = _imports(os.path.join(package, "models", "decode_engine.py"))
    blocks = {"dots", "granite", "solar", "mimo", "ling", "exaone",
              "instella"}
    assert not {i for i in engine
                if i.rsplit(".", 1)[-1] in blocks}, engine
    assert not {i for i in _imports(os.path.join(package, "models",
                                                 "dots.py"))
                if "decode_engine" in i}
    # the benchmark's own files name the block in its family file alone
    for folder, _, files in os.walk(manifest.HERE):
        for name in files:
            if name.endswith(".py") and "dots3_note" not in name:
                path = os.path.join(folder, name)
                assert not _imports(path) & set(new), path


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:dots3-note-prev-ep8-1chip`` through proxy, pool,
    replica pump and engine at tiny widths: served tokens agree with the
    plain reference; the three kinds of state, ``selected_rows`` and the
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
    # tiny: 16 experts of which 4 are held, top-4
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 4
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("dots3_note")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        34832 // 16, 4)
    assert metrics["slot_state_bytes.dsa"]["value"] == sum(per_slot.values())
    # every stream is past index_topk rows: 8 of hundreds read
    assert 0 < metrics["dsa_rows_read_share.dsa"]["value"] < 20
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
    os.makedirs(tmp_path / "ray_tpu" / "models")  # a program, no dots.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "dots.py" in proc.stderr
