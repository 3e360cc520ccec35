"""The family ``granitemoehybrid``
(``benchmark/families/granitemoehybrid.py``) by hand: the
configuration's keys against the catalog's row and its three cuts, its
parameter counts against ``init_params``' shapes, a slot's state of two
kinds, a decode step's bytes and the step kernel's; the ``.ssm`` readers
on small hand-made traces; the reference's blocks; the guard that no
older cell's program can reach the new block; and the CPU rehearsal of
the cell through ``benchmark.run`` (never a measurement).

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
CONFIG = "granite-4.0-h-small-ep4-1chip"
CELL = CONFIG + ".sessions-saturated"
NEW = ("ssd_step_roofline.ssm", "prefill_ssm_share.ssm",
       "slot_state_bytes.ssm")
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
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["n_layers"], m["vocab_size"]) == (4096, 32, 8, 128, 10, 25088)
    assert (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
            m["conv_kernel"]) == (128, 64, 128, 4)
    assert m["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (m["d_ff"], m["shared_d_ff"], m["n_experts"], m["top_k"],
            m["n_group"], m["topk_group"], m["routed_scaling_factor"]) \
        == (768, 1536, 72, 10, 1, 1, 1.0)
    assert (m["embedding_multiplier"], m["residual_multiplier"],
            m["attention_multiplier"], m["logits_scaling"]) \
        == (12.0, 0.22, 0.0078125, 16.0)
    assert m["held_experts"] == [0, 18]
    assert (m["rms_eps"], m["published_layers"], m["dtype"]) \
        == (1e-5, 40, "bfloat16")
    assert fam.layer_counts(m) == {"ssm": 9, "full": 1, "moe": 10}
    config = _json("configs", CONFIG)
    for key, value in (
            ("position_embedding_type", "rope"), ("mamba_n_groups", 8),
            ("mamba_conv_bias", False), ("mamba_proj_bias", True),
            ("attention_bias", True), ("tie_word_embeddings", False),
            ("normalization_function", "layernorm"),
            ("mamba_expand", 4)):  # 128 x 64 is not 4 x 4,096
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_three_cuts():
    """Every number of the catalog's row as published but the depth and
    the vocabulary (the router stays 72 wide: 18 are held); what was
    read into the keys is under ``assumed``."""
    config = _json("configs", CONFIG)
    published = {
        "attention_bias": False, "attention_multiplier": 0.0078125,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 768,
        "logits_scaling": 16, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 10, "num_key_value_heads": 8,
        "num_local_experts": 72, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 1536, "tie_word_embeddings": True}
    for key, value in published.items():
        assert config[key] == value, key
    kinds = config["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds)
                                 if k == "attention"] == [5, 15, 25, 35]
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["held_experts"], config["published_num_hidden_layers"]) \
        == (10, 25088, [0, 18], 40)
    assert 4 * config["vocab_size"] == 100352
    assert 4 * config["held_experts"][1] == config["num_local_experts"]
    assert list(config["reduced"]) == [
        "num_hidden_layers", "num_local_experts", "vocab_size"]
    assert "2,955,758,208" in config["reduced"]["vocab_size"]
    assert "189,038,592" in config["reduced"]["num_local_experts"]
    for reading in ("intermediate_size", "time_step", "mamba_chunk_size",
                    "block", "attention", "router", "serving_types",
                    "initialisation"):
        assert config["assumed"][reading]
    assert {"exchange", "max_position_embeddings", "rope_theta"} \
        <= set(config["left_out"])
    assert "four pipeline stages of one period each" in config["deployment"]
    assert "96 x 10 / 72 = 13 rows" in config["deployment"]
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
    assert (cell["chips"], cell["traffic_name"]) == (1, "sessions-saturated")
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {*NEW, "tokens_per_pump.doc", "ttft_p50_ms.doc",
            "tpot_p50_ms.doc", "prefill_device_share.doc",
            "decode_chunk_ms.doc", "decode_hbm_share.doc",
            "prefill_token_use_share.doc", "pump_host_work_ms.doc",
            "moe_experts_touched.doc", "moe_expert_load_max_over_mean.doc",
            "moe_held_assignment_share.reason", "moe_gmm_roofline.reason",
            *("device_part_share." + p for p in (
                "attn", "moe_experts", "lm_head", "sample", "cache", "loop",
                "unscoped"))} <= names
    # the readers whose row or state rule is another block's stay away
    assert not {"kda_step_roofline.reason", "slot_state_bytes.hybrid",
                "prefill_linear_attn_share.hybrid", "slot_state_bytes.reason",
                "decode_attn_roofline.hybrid", "moe_gmm_roofline.doc",
                "device_part_share.mlp", "moe_compact_call_share.reason"} \
        & names
    for new in NEW:
        metrics = [p for p in b["per_layer"] if p["name"] == new]
        assert len(metrics) == 1, new
        assert CELL in metrics[0]["workloads"]
        assert metrics[0]["moves"] == "out_tokens_per_s"
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", new + ".py"))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_the_traffic_is_the_reason_cells_shapes_with_every_prompt_times_four():
    """``sessions-saturated.json``: the 32 shapes of
    ``reason-saturated.json`` in their order with every prompt x 4,
    144 callers on 96 slots of 4,096 + 2,048 + 16 rows."""
    new, old = (_json("traffic", n) for n in ("sessions-saturated",
                                              "reason-saturated"))
    assert new["shapes"]["entries"] == [
        [4 * p, o] for p, o in old["shapes"]["entries"]]
    prompts = sorted({p for p, _ in new["shapes"]["entries"]})
    assert prompts == [1024, 1248, 1520, 1856, 2260, 2756, 3360, 4096]
    assert sorted({o for _, o in new["shapes"]["entries"]}) \
        == [512, 768, 1280, 2048]
    assert len(new["shapes"]["entries"]) == 32
    assert sum(p for p, _ in new["shapes"]["entries"]) / 32 == 2265
    assert sum(o for _, o in new["shapes"]["entries"]) / 32 == 1152
    assert new["engine"] == {
        "slots": 96, "max_len": 6160, "chunk_tokens": 16,
        "prompt_buckets": [1024, 2048, 3072, 4096]}
    assert (new["kind"], new["loop"], new["clients"], new["trace_seconds"],
            new["window"]) == ("serve", "closed", 144, 8,
                               {"opens_after_completed": 96})
    assert new["engine"]["max_len"] == 4096 + 2048 + 16


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import jax

    fam, m = fam_and_fields
    assert fam.ssm_params(m) == 102_286_976 == (
        4096 * (8192 + 8192 + 128 + 128 + 128) + 8448 * 4 + 8448
        + 3 * 128 + 8192 + 8192 * 4096)
    assert fam.gqa_params(m) == 41_943_040 \
        == 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert fam.expert_params(m) == 9_437_184
    assert fam.moe_fixed_params(m) == 294_912 + 18_874_368
    experts = 18 * 9_437_184 + 18_874_368 + 294_912
    assert experts == 189_038_592
    mamba_layer = 102_286_976 + experts + 2 * 4096
    attn_layer = 41_943_040 + experts + 2 * 4096
    assert (mamba_layer, attn_layer) == (291_333_760, 230_989_824)
    total = 9 * mamba_layer + attn_layer + 25088 * 4096 + 4096
    assert fam.num_params(m) == total == 2_955_758_208
    prog = fam.build(m, max_seq_len=64, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == total
    assert "lm_head" not in shapes and "router_bias" not in \
        shapes["layers"][0]["mlp"]
    # the uncut model: every layer, expert and vocabulary row
    uncut = {**m, "n_layers": 40, "held_experts": None,
             "vocab_size": 100352,
             "layer_types": _json("configs", CONFIG)["layer_types"]}
    assert round(fam.num_params(uncut) / 1e9, 1) == 32.2
    # a token meets: a mixer's two projections or attention, the router,
    # the shared expert and its held share of ten experts, the head
    mixer = 4096 * 16768 + 8192 * 4096
    assert fam.matmul_params(m) == int(
        9 * mixer + 41_943_040 + 10 * (294_912 + 18_874_368
                                       + 10 * 18 / 72 * 9_437_184)
        + 4096 * 25088)
    assert fam.flash_calls(m, 1, 4096) == []


def test_a_slots_state_a_decode_steps_bytes_and_the_kernels(fam_and_fields):
    fam, m = fam_and_fields
    assert fam.kv_row_bytes(m) == 4096
    per_slot = fam.state_bytes_per_slot(m, 6160)
    assert per_slot == {"recurrent": 9 * (4_194_304 + 3 * 8448 * 2),
                        "full": 6160 * 4096}
    assert sum(per_slot.values()) == 63_436_288
    assert fam.ssm_state_bytes(m, 96) == 402_653_184
    assert fam.ssd_step_bytes(m, 96) == 805_306_368  # read AND written
    # a step of 96 slots at 3,000 live rows: weights outside the experts
    # once, the touched experts, the state twice, the live rows
    touched = fam.experts_touched(m, 96)
    assert 17.99 < touched <= 18
    weights = (9 * 102_286_976 + 41_943_040
               + 10 * (294_912 + 18_874_368 + touched * 9_437_184)
               + 4096 * 25088) * 2
    assert fam.decode_step_bytes(m, 96, 3000) == pytest.approx(
        weights + 96 * (2 * per_slot["recurrent"] + 3000 * 4096))
    assert 14.0e9 < fam.decode_step_bytes(m, 96, 3000) < 14.8e9
    work = fam.ssd_chunk_work(m, 2048, 128)
    assert work["flops"] == 2.0 * 2048 * (128 * 128 + 128 * 128 * 64
                                          + 2 * 128 * 64 * 128)
    assert work["bytes"] == 4.0 * (2048 * (2 * 8192 + 256 + 128)
                                   + 2 * 128 * 64 * 128)


# ---------------------------------------------------------- the readers


def _state_init(**kw):
    return ["engine.state_init", 0, 0, {
        "engine": "e", "slots": 96, "max_len": 6160,
        "recurrent_bytes": 96 * 38_204_928, "full_bytes": 96 * 25_231_360,
        "recurrent_layers": 9, "full_layers": 1, "full_row_bytes": 4096,
        **kw}]


def _facts(ops=(), modules=(), spans=(), model=CONFIG):
    return {"model": model, "device": V5E,
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": list(modules)},
                {"name": "XLA Ops", "events": list(ops)}]}]},
            "spans": {"lines": [{"name": "pump", "events": list(spans)}]}}


def test_the_slots_state_reader_wants_both_kinds():
    read = _reader("slot_state_bytes.ssm")
    assert read(_facts(spans=[_state_init()])) == 63_436_288
    other = _state_init()
    del other[3]["recurrent_bytes"]  # (another block's event)
    assert read(_facts(spans=[other])) is None
    assert read(_facts()) is None


def test_the_step_kernels_roofline_is_the_state_read_and_written():
    """Nine events a step; 96 slots: a call moves 805,306,368 B at 819
    GB/s, 983.3 us; events twice that long read 50%."""
    read = _reader("ssd_step_roofline.ssm")
    least = 805_306_368 / 819e9
    assert round(1e6 * least, 1) == 983.3
    ops = [[f"custom-call/2out/ssd_step.{i}", i * 10_000_000,
            int(2 * least * 1e9)] for i in range(18)]
    ops.append(["custom-call/1out/decode_attn.1", 1, 50_000])
    got = read(_facts(ops=ops, spans=[_state_init()]))
    assert got == pytest.approx(50.0, rel=1e-3)
    assert read(_facts(ops=ops)) is None  # no span
    assert read(_facts(ops=ops[-1:], spans=[_state_init()])) is None
    # (another family's cell, were it listed: no bytes to count by)
    assert read(_facts(ops=ops, spans=[_state_init()],
                       model="internlm2-1.8b")) is None


def test_the_ssm_share_reads_the_prefill_programs_alone():
    read = _reader("prefill_ssm_share.ssm")
    facts = {"device_parts": {"busy_s": 2.0, "programs": {
        "jit__prefill_batch_into_slots": {
            "attn/attn_ssm": 0.3, "attn/attn_full": 0.1, "qkv": 0.2,
            "moe_experts": 0.4},
        "jit_decode_chunk": {"attn/attn_ssm": 1.0}}}}
    assert read(facts) == pytest.approx(30.0)
    facts["device_parts"]["programs"]["jit__prefill_batch_into_slots"] = {
        "attn/attn_linear": 0.5, "qkv": 0.5}  # (another model)
    assert read(facts) is None
    assert read({"device_parts": None}) is None


def test_the_new_scope_is_a_kind_of_attention():
    from ray_tpu.models import program_parts as pp

    assert "attn_ssm" in pp.ATTN_KINDS
    assert pp.part_of("jit(decode_chunk)/while/body/attn/attn_ssm/"
                      "pallas_call") == "attn/attn_ssm"
    assert pp.part_of("jit(f)/attn/attn_linear/dot") == "attn/attn_linear"


# ------------------------------------------------------ the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("granitemoehybrid")
    ref = manifest.reference(fam)
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    with open(os.path.join(manifest.HERE, "families",
                           "granitemoehybrid.reference.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "ray_tpu" not in body
    assert "import" not in body.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace(
        "import jax.numpy as jnp", "").replace("import jax", "").replace(
        "import numpy as np", "")
    # the SSM a token at a time, the router in the published order, the
    # scale written out, the precision the highest
    assert "jax.lax.scan(token, h0" in body
    assert "jnp.argsort(-logits, -1, stable=True)[..., :kk]" in body
    assert 'm["attention_multiplier"]' in body
    assert "cumsum" not in body and "chunk" not in body.split(
        "def ssm_recurrence")[1].split("def _ssm_rows")[0]
    assert body.count('default_matmul_precision("highest")') == 5
    for block in (ref._ssm_block, ref._gqa_project, ref._gqa_attend,
                  ref._mlp_block, ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 1 and 0 < ref.TRAIN_LOSS_TOL < 0.1


def test_blocks_of_rows_give_the_whole_sequences_forward():
    """The reference in blocks of 16 rows and 4 query rows over 50
    positions (``H`` and the last three convolution inputs handed from
    block to block) is its forward in one block; ``last`` gives the
    tail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam = manifest.family("granitemoehybrid")
    ref = manifest.reference(fam)
    m = dict(fam.TINY_FIELDS)
    params = fam.build(m, max_seq_len=64, remat=False).init_params(
        jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 256, (2, 50)))
    was = ref.ROWS, ref.QUERY_ROWS
    ref.ROWS = ref.QUERY_ROWS = 64
    try:
        jax.clear_caches()
        whole = ref.forward(params, toks, m)
        ref.ROWS, ref.QUERY_ROWS = 16, 4
        jax.clear_caches()
        blocks = ref.forward(params, toks, m)
        tail = ref.forward(params, toks, m, last=5)
    finally:
        ref.ROWS, ref.QUERY_ROWS = was
        jax.clear_caches()
    np.testing.assert_allclose(blocks, whole, atol=1e-6)
    np.testing.assert_allclose(tail, whole[:, -5:], atol=1e-6)


# ----------------------------------- the guard for the cells left alone


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
    its two ops are imported by ``models/granite.py`` alone (and its ops
    by nothing else), so no older cell's process loads, traces or
    compiles a line of them; the engine imports no block."""
    new = ("ray_tpu.models.granite", "ray_tpu.ops.ssd_step",
           "ray_tpu.ops.ssd_chunk")
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
    assert set(importers) == {os.path.join("ray_tpu", "models",
                                           "granite.py")}, importers
    engine = _imports(os.path.join(package, "models", "decode_engine.py"))
    blocks = {"granite", "solar", "mimo", "ling", "exaone", "instella"}
    assert not {i for i in engine
                if i.rsplit(".", 1)[-1] in blocks}, engine
    # the benchmark's own files name the block in its family file alone
    for folder, _, files in os.walk(manifest.HERE):
        for name in files:
            if name.endswith(".py") and "granitemoehybrid" not in name:
                path = os.path.join(folder, name)
                assert not _imports(path) & set(new), path


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:granite-4.0-h-small-ep4-1chip`` through proxy, pool,
    replica pump and engine at tiny widths: served tokens agree with the
    plain reference; both kinds of state, their bytes and the routing
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
    # tiny: 8 experts of which 2 are held, top-2
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 2
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("granitemoehybrid")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        6160 // 16, 4)
    assert metrics["slot_state_bytes.ssm"]["value"] \
        == sum(per_slot.values())
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_token_use_share.doc"]["value"] <= 100
    for device_only in ("ssd_step_roofline.ssm", "prefill_ssm_share.ssm",
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
    os.makedirs(tmp_path / "ray_tpu" / "models")  # a program, no granite.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "granite.py" in proc.stderr
