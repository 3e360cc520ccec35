"""The family ``lfm2_moe`` (``benchmark/families/lfm2_moe.py``) by hand:
the configuration's keys against the catalog's row and its one cut, its
parameter counts against ``init_params``' shapes, a slot's state of two
kinds, a decode step's bytes and the flash call's work; the ``.sconv``
readers and the two accepted readers the cell joins on small hand-made
traces (no share over 100 at the cell's
sizes); the reference's blocks; the guard that no older cell's program
can reach the new block; and the CPU rehearsal of the cell through
``benchmark.run`` (never a measurement).

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
CONFIG = "lfm2-8b-a1b-ep2-1chip"
CELL = CONFIG + ".rag-saturated"
NEW = ("prefill_conv_share.sconv", "flash_fwd_roofline.sconv")
# accepted readers that read this cell rightly as they stand (the family
# supplies ``kv_row_bytes`` and the slots' ``row_kinds``): their lists
# are joined and no reader forwards to them
JOINED = ("slot_state_bytes.ssm", "decode_attn_roofline.hybrid")
V5E = {"kind": "TPU v5 lite"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PREFILL = "jit__prefill_batch_into_slots"


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
            m["n_layers"], m["vocab_size"]) == (2048, 32, 8, 64, 24, 65536)
    assert (m["conv_kernel"], m["n_dense_layers"], m["dense_d_ff"]) \
        == (3, 2, 7168)
    kinds = m["layer_types"]
    assert len(kinds) == 24 and [i for i, k in enumerate(kinds)
                                 if k == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]  # (not periodic to its end)
    assert (m["d_ff"], m["shared_d_ff"], m["n_experts"], m["top_k"],
            m["n_group"], m["topk_group"], m["routed_scaling_factor"]) \
        == (1792, 0, 32, 4, 1, 1, 1.0)
    assert m["held_experts"] == [0, 16] and m["norm_topk_eps"] == 1e-6
    assert (m["rms_eps"], m["rope_theta"], m["published_layers"],
            m["dtype"]) == (1e-5, 1e6, 24, "bfloat16")
    assert fam.layer_counts(m) == {"conv": 18, "full": 6, "dense": 2,
                                   "moe": 22}
    config = _json("configs", CONFIG)
    for key, value in (
            ("model_type", "lfm2"), ("conv_bias", True),
            ("norm_topk_prob", False), ("use_expert_bias", False),
            ("tie_word_embeddings", False),
            ("layer_types", config["layer_types"][:23]),
            ("layer_types", ["mamba"] * 24)):
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_cut():
    """Every key of the catalog's row letter for letter, the depth and
    the whole list of layer kinds among them (the router stays 32 wide:
    16 are held, ``num_experts`` the one cut); what was read into the
    keys is under ``assumed``."""
    config = _json("configs", CONFIG)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["held_experts"], config["vocab_size"]) \
        == (24, 32, [0, 16], 65536)
    assert 2 * config["held_experts"][1] == config["num_experts"]
    assert "published_num_hidden_layers" not in config  # (depth is whole)
    assert list(config["reduced"]) == ["num_experts"]
    for number in ("4,464,393,664", "8,339,930,560", "176,226,336",
                   "107,298,816", "DEPTH IS NOT CUT"):
        assert number in config["reduced"]["num_experts"], number
    for reading in ("tie_word_embeddings", "in_proj_order", "head_dim",
                    "block", "router", "serving_types", "initialisation"):
        assert config["assumed"][reading]
    assert "1e-6" in config["assumed"]["router"]
    # how the seeded weights are drawn is a rule of ``init_params``, said
    # here and no key of the file
    assert not [k for k in config if "damp" in k or "seeded" in k]
    assert "3.2 x sqrt(8 / hidden_size) = 0.2" \
        in config["assumed"]["initialisation"]
    assert {"exchange", "max_position_embeddings"} <= set(config["left_out"])
    assert "two v5e chips" in config["deployment"]
    assert "32 x 4 / 32 = 4 rows" in config["deployment"]
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
    assert (cell["chips"], cell["traffic_name"]) == (1, "rag-saturated")
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {*NEW, *JOINED, "tokens_per_pump.doc", "ttft_p50_ms.doc",
            "tpot_p50_ms.doc", "prefill_device_share.doc",
            "decode_chunk_ms.doc", "decode_hbm_share.doc",
            "prefill_token_use_share.doc", "pump_host_work_ms.doc",
            "moe_experts_touched.doc", "moe_expert_load_max_over_mean.doc",
            "moe_held_assignment_share.reason", "moe_gmm_roofline.reason",
            "prefill_rows_run_share.doc",
            *("device_part_share." + p for p in (
                "attn", "mlp", "moe_experts", "lm_head", "sample", "cache",
                "loop", "unscoped")),
            *(f"setup_{s}.serve" for s in (
                "process_spawn_s", "chip_claim_s", "weights_s",
                "trace_lower_s", "compile_s", "compile_cache_hit_share"))} \
        <= names
    # the readers whose row or state rule is another block's stay away
    assert not {"kda_step_roofline.reason", "slot_state_bytes.hybrid",
                "ssd_step_roofline.ssm", "moe_gmm_roofline.doc",
                "flash_fwd_roofline.swa", "moe_compact_call_share.reason",
                "device_part_share.mhc"} & names
    assert not any(n.endswith(".sconv") for n in names - set(NEW))
    for new in NEW:
        metrics = [p for p in b["per_layer"] if p["name"] == new]
        assert len(metrics) == 1, new
        assert metrics[0]["workloads"] == [CELL]
        assert metrics[0]["moves"] == "out_tokens_per_s"
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", new + ".py"))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_the_traffic_is_the_longdoc_cells_shapes_with_every_prompt_halved():
    """``rag-saturated.json``: the 32 shapes of
    ``longdoc-saturated.json`` in their order with every prompt halved,
    48 callers on 32 slots of 8,720 rows (8,192 + 512 + a chunk's 16),
    four buckets of whole 2,048-row segments."""
    rag, doc = _json("traffic", "rag-saturated"), \
        _json("traffic", "longdoc-saturated")
    assert (rag["kind"], rag["loop"], rag["clients"]) \
        == ("serve", "closed", 48)
    assert rag["engine"] == {"slots": 32, "max_len": 8720,
                             "chunk_tokens": 16,
                             "prompt_buckets": [2048, 4096, 6144, 8192]}
    assert rag["window"] == {"opens_after_completed": 32}
    assert rag["trace_seconds"] == 8
    entries = rag["shapes"]["entries"]
    assert entries == [[p // 2, o] for p, o in doc["shapes"]["entries"]]
    assert len({tuple(e) for e in entries}) == 32
    assert sorted({p for p, _ in entries}) == [
        2048, 2496, 3040, 3712, 4520, 5512, 6720, 8192]
    assert sorted({o for _, o in entries}) == [128, 192, 320, 512]
    assert sum(p for p, _ in entries) / 32 == 4530
    assert sum(o for _, o in entries) / 32 == 288
    assert max(p + o for p, o in entries) + 16 == rag["engine"]["max_len"]
    assert "temperature" not in rag and "shared_prefix" not in rag


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import jax

    fam, m = fam_and_fields
    assert fam.conv_params(m) == 16_783_360 \
        == 2048 * 6144 + 3 * 2048 + 2048 * 2048
    assert fam.gqa_params(m) == 10_485_888 \
        == 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert fam.dense_params(m) == 44_040_192
    assert fam.expert_params(m) == 11_010_048
    assert fam.moe_fixed_params(m) == 65_536 + 32
    experts = 16 * 11_010_048 + 65_536 + 32
    assert experts == 176_226_336
    outside = 18 * 16_783_360 + 6 * 10_485_888 + 24 * 4096 \
        + 2 * 44_040_192
    assert outside == 453_194_496
    total = outside + 22 * experts + 65536 * 2048 + 2048
    assert fam.num_params(m) == total == 4_464_393_664
    prog = fam.build(m, max_seq_len=64, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == total
    assert "lm_head" not in shapes
    assert "router" not in shapes["layers"][1]["mlp"]
    assert shapes["layers"][2]["mlp"]["router_bias"].shape == (32,)
    assert shapes["layers"][2]["mlp"]["w_gate"].shape == (16, 2048, 1792)
    # the uncut model: every expert
    uncut = {**m, "held_experts": None}
    assert fam.num_params(uncut) == 8_339_930_560
    # a token meets: a mixer's two products or attention, a dense MLP or
    # the router and its held share of four experts, the head
    assert fam.matmul_params(m) == int(
        18 * (2048 * 6144 + 2048 * 2048) + 6 * (10_485_888 - 128)
        + 2 * 44_040_192 + 22 * (65_536 + 4 * 16 / 32 * 11_010_048)
        + 2048 * 65536)
    assert fam.flash_calls(m, 1, 4096) == [(6, 1, 4096, 32, 8, 64)]


def test_a_slots_state_a_decode_steps_bytes_and_the_expert_calls(
        fam_and_fields):
    fam, m = fam_and_fields
    assert fam.kv_row_bytes(m) == 2048
    per_slot = fam.state_bytes_per_slot(m, 8720)
    assert per_slot == {"recurrent": 18 * 2 * 2048 * 2,
                        "full": 6 * 8720 * 2048}
    assert per_slot["recurrent"] == 147_456
    assert sum(per_slot.values()) == 107_298_816
    # a step of 32 slots at 4,700 live rows: weights outside the experts
    # once, the touched experts, the conv rows twice, the live rows
    touched = fam.experts_touched(m, 32)
    assert 15.7 < touched < 16  # (an expert untouched with 1.4%)
    weights = (453_194_496 - 24 * 4096 + 22 * (65_568
                                               + touched * 11_010_048)
               + 2048 * 65536) * 2
    assert fam.decode_step_bytes(m, 32, 4700) == pytest.approx(
        weights + 32 * (2 * 147_456 + 4700 * 12_288))
    assert 10.5e9 < fam.decode_step_bytes(m, 32, 4700) < 10.9e9
    assert fam.gmm_flops(64, 2048, 1792) == 2.0 * 64 * 2048 * 1792
    assert fam.gmm_bytes(64, 2048, 1792, 15.8) == (
        15.8 * 2048 * 1792 + 64 * 2048 + 64 * 1792) * 2


# ---------------------------------------------------------- the readers


def _state_init(**kw):
    return ["engine.state_init", 0, 0, {
        "engine": "e", "slots": 32, "max_len": 8720,
        "recurrent_bytes": 32 * 147_456, "full_bytes": 32 * 107_151_360,
        "recurrent_layers": 18, "full_layers": 6, "full_row_bytes": 2048,
        **kw}]


def _facts(ops=(), modules=(), spans=(), model=CONFIG):
    return {"model": model, "device": V5E,
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": list(modules)},
                {"name": "XLA Ops", "events": list(ops)}]}]},
            "spans": {"lines": [{"name": "pump", "events": list(spans)}]}}


def test_the_slots_state_reader_wants_both_kinds():
    read = _reader("slot_state_bytes.ssm")
    assert read(_facts(spans=[_state_init()])) == 107_298_816
    other = _state_init()
    del other[3]["recurrent_bytes"]  # (another block's event)
    assert read(_facts(spans=[other])) is None
    assert read(_facts()) is None


def test_the_decode_kernels_roofline_is_six_layers_live_rows():
    """Six events a step; 32 slots at 4,700 live rows: a call reads
    150,400 rows of 2,048 B at 819 GB/s, 376.1 us; events that long read
    100% and no more, twice that long 50%."""
    read = _reader("decode_attn_roofline.hybrid")
    live = 32 * 4700
    least = live * 2048 / 819e9
    assert round(1e6 * least, 1) == 376.1
    back = ["engine.readback", 5, 10, {"live_rows_full": live,
                                       "live_rows": live}]
    for slower, want in ((1, 100.0), (2, 50.0)):
        ops = [[f"custom-call/1out/decode_attn.{i}", i * 10_000_000,
                round(slower * least * 1e9)] for i in range(12)]
        ops.append(["custom-call/1out/moe_gmm.1", 1, 50_000])
        got = read(_facts(ops=ops, spans=[_state_init(), back]))
        assert got == pytest.approx(want, rel=1e-3) and got <= 100.001
    assert read(_facts(ops=ops, spans=[back])) is None  # no state_init
    assert read(_facts(ops=ops, spans=[_state_init()])) is None
    # (the XLA body: no kernel event, nothing to read)
    assert read(_facts(ops=ops[-1:], spans=[_state_init(), back])) is None


def test_the_flash_kernels_roofline_is_the_causal_half_at_heads_of_64():
    """A whole call of the 4,096-row bucket: two segments, six full
    layers, twelve ``flash_fwd`` events inside one execution of the
    prefill program. The least time is 6 x 2 x 32 x 4,096^2 / 2 x 64 x 2
    FLOP at 197 TFLOP/s = 2.093 ms (compute-bound: the bytes are 0.08
    ms); events that sum to it read 100%, an execution the trace cut
    (five events) is left out."""
    fam, m = manifest.model(CONFIG)
    read = _reader("flash_fwd_roofline.sconv")
    flops = 6 * 2 * 2.0 * 32 * 4096 * 4096 * 64 * 0.5
    least = flops / 197e12
    assert round(1e3 * least, 3) == 2.093
    prefill = ["engine.prefill", 0, 10, {
        "bucket": 4096, "segments": 2, "live_segments": 2, "tokens": 3040,
        "prompts": 1, "rows": 1}]
    each = round(least * 1e9 / 12)
    ops = [[f"custom-call/1out/flash_fwd.{i % 6 + 1}", 1_000 + i * 2 * each,
            each] for i in range(12)]
    modules = [[PREFILL + "(1)", 0, 12 * 2 * each + 2_000]]
    got = read(_facts(ops=ops, modules=modules, spans=[prefill]))
    assert got == pytest.approx(100.0, rel=1e-3) and got <= 100.001
    slow = [[n, s, 3 * d] for n, s, d in ops]
    assert read(_facts(ops=slow, modules=[[PREFILL + "(1)", 0, 10**9]],
                       spans=[prefill])) == pytest.approx(100 / 3, rel=1e-3)
    assert read(_facts(ops=ops[:5], modules=modules,
                       spans=[prefill])) is None  # (cut: 5 is not 6 n)
    assert read(_facts(ops=ops, modules=modules)) is None  # no span
    assert read(_facts(spans=[prefill])) is None  # no event


def test_the_conv_share_reads_the_prefill_programs_alone():
    read = _reader("prefill_conv_share.sconv")
    facts = {"device_parts": {"busy_s": 2.0, "programs": {
        PREFILL: {"attn/attn_conv": 0.05, "attn/attn_full": 0.1,
                  "qkv": 0.25, "attn_out": 0.1, "moe_experts": 0.5},
        "jit_decode_chunk": {"attn/attn_conv": 1.0}}}}
    assert read(facts) == pytest.approx(5.0)
    facts["device_parts"]["programs"][PREFILL] = {
        "attn/attn_ssm": 0.5, "qkv": 0.5}  # (another model)
    assert read(facts) is None
    assert read({"device_parts": None}) is None


def test_the_new_scope_is_a_kind_of_attention():
    from ray_tpu.models import program_parts as pp

    assert "attn_conv" in pp.ATTN_KINDS
    assert pp.part_of("jit(decode_chunk)/while/body/attn/attn_conv/"
                      "mul") == "attn/attn_conv"
    assert pp.part_of("jit(f)/attn/attn_ssm/dot") == "attn/attn_ssm"


# ------------------------------------------------------ the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("lfm2_moe")
    ref = manifest.reference(fam)
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    with open(os.path.join(manifest.HERE, "families",
                           "lfm2_moe.reference.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "ray_tpu" not in body
    assert "import" not in body.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace(
        "import jax.numpy as jnp", "").replace("import jax", "").replace(
        "import numpy as np", "")
    # the convolution as shifted sums over whole rows, the router in the
    # published order with its 1e-6, the scores written out, the
    # precision the highest
    assert "sum(taps[i] * padded[:, i:i + t] for i in range(kk))" in body
    assert "jnp.argsort(-(scores + bias), -1, stable=True)[..., :kk]" in body
    assert 'm.get("norm_topk_eps")' in body
    assert "/ jnp.sqrt(jnp.float32(hd))" in body
    assert body.count('default_matmul_precision("highest")') == 6
    for block in (ref._conv_gates_block, ref._conv_out_block,
                  ref._gqa_project, ref._gqa_attend, ref._mlp_block,
                  ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 3 and 0 < ref.TRAIN_LOSS_TOL < 0.1
    assert 0 < ref.SERVE_MEAN_REGRET < ref.SERVE_TOP2_GAP / 4


def test_the_check_holds_served_tokens_to_two_limits(monkeypatch):
    """``check_served_tokens``: the reference's own greedy tokens pass
    with nothing given up; the last of them replaced by the runner-up
    fails by the gap's limit where that stands under the position's gap,
    by the mean regret's where that stands under a 24th of it, and
    passes between the two."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam = manifest.family("lfm2_moe")
    ref = manifest.reference(fam)
    m = dict(fam.TINY_FIELDS)
    params = fam.build(m, max_seq_len=64, remat=False).init_params(
        jax.random.PRNGKey(5))
    prompt = [int(t) for t in np.random.RandomState(5).randint(1, 256, 20)]
    tokens = []
    for _ in range(24):
        z = ref.forward(params, jnp.asarray([prompt + tokens]), m, last=1)
        tokens.append(int(z[0, -1].argmax()))
    own = ref.check_served_tokens(params, prompt, tokens, m)
    assert own["ok"] and own["agree"] == 24
    assert (own["mean_regret"], own["parted_up_to"]) == (0, 0)
    last = np.asarray(ref.forward(
        params, jnp.asarray([prompt + tokens[:-1]]), m, last=1)[0, -1])
    runner_up = int(np.argsort(last)[-2])
    gap = float(np.sort(last)[-1] - np.sort(last)[-2])
    other = tokens[:-1] + [runner_up]
    for gap_limit, regret_limit, ok in ((gap / 2, 1.0, False),
                                        (gap * 2, gap / 48, False),
                                        (gap * 2, gap / 12, True)):
        monkeypatch.setattr(ref, "SERVE_TOP2_GAP", gap_limit)
        monkeypatch.setattr(ref, "SERVE_MEAN_REGRET", regret_limit)
        got = ref.check_served_tokens(params, prompt, other, m)
        assert got["ok"] is ok, (gap_limit, regret_limit, got)
        assert got["agree"] == 23
        assert got["parted_up_to"] == pytest.approx(gap, abs=1e-3)
        assert got["mean_regret"] == pytest.approx(gap / 24, abs=1e-3)


def test_blocks_of_rows_give_the_whole_sequences_forward():
    """The reference in blocks of 16 rows and 4 query rows over 50
    positions (the convolution over whole rows between two passes in
    blocks, the rotation at a block's own positions) is its forward in
    one block; ``last`` gives the tail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam = manifest.family("lfm2_moe")
    ref = manifest.reference(fam)
    m = dict(fam.TINY_FIELDS)
    params = fam.build(m, max_seq_len=64, remat=False).init_params(
        jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.RandomState(0).randint(1, 256, (2, 50)))
    was = ref.ROWS, ref.QUERY_ROWS
    ref.ROWS = ref.QUERY_ROWS = 64
    try:
        whole = ref.forward(params, toks, m)
        ref.ROWS, ref.QUERY_ROWS = 16, 4
        blocks = ref.forward(params, toks, m)
        tail = ref.forward(params, toks, m, last=5)
    finally:
        ref.ROWS, ref.QUERY_ROWS = was
    np.testing.assert_allclose(blocks, whole, atol=2e-5)
    np.testing.assert_allclose(tail, whole[:, -5:], atol=2e-5)


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
    """PR 49's refusal was an older cell's ``setup_s``: no file of the
    package imports ``models/lfm2.py`` (the block is found through its
    configuration's ``slot_model``, built by its family file alone), so
    no older cell's process loads, traces or compiles a line of it; the
    engine imports no block."""
    new = {"ray_tpu.models.lfm2"}
    package = os.path.join(ROOT, "ray_tpu")
    importers = {}
    for folder, _, files in os.walk(package):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                if _imports(path) & new:
                    importers[os.path.relpath(path, ROOT)] = True
    assert not importers, importers
    engine = _imports(os.path.join(package, "models", "decode_engine.py"))
    blocks = {"lfm2", "granite", "solar", "mimo", "ling", "exaone",
              "instella", "dots", "glm_dsa", "glm_next"}
    assert not {i for i in engine
                if i.rsplit(".", 1)[-1] in blocks}, engine
    # the benchmark's own files name the block in its family file alone
    for folder, _, files in os.walk(manifest.HERE):
        for name in files:
            if name.endswith(".py") and "lfm2_moe" not in name:
                path = os.path.join(folder, name)
                assert not _imports(path) & new, path


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:lfm2-8b-a1b-ep2-1chip`` through proxy, pool, replica
    pump and engine at tiny widths: served tokens agree with the plain
    reference; both kinds of state, their bytes and the routing counters
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
    # tiny: 8 experts of which 4 are held, top-2
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 4
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("lfm2_moe")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        8720 // 16, 4)
    assert metrics["slot_state_bytes.ssm"]["value"] \
        == sum(per_slot.values())
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_token_use_share.doc"]["value"] <= 100
    assert 0 < metrics["prefill_rows_run_share.doc"]["value"] <= 100
    for device_only in ("decode_attn_roofline.hybrid",
                        "flash_fwd_roofline.sconv",
                        "prefill_conv_share.sconv",
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
    os.makedirs(tmp_path / "ray_tpu" / "models")  # a program, no lfm2.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "lfm2.py" in proc.stderr
