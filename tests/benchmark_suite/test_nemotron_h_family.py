"""The family ``nemotron_h`` (``benchmark/families/nemotron_h.py``) by
hand: the configuration's keys against the catalog's row and its two
cuts, its parameter counts against ``init_params``' shapes and the
issue's table, a slot's state of two kinds, a decode step's bytes; the
new ``.ssmg`` reader and the accepted readers the cell joins on small
hand-made traces (no share over 100 at the cell's sizes); the
reference's blocks; the guard that no older cell's program can reach the
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
CONFIG = "nemotron-3-nano-30b-a3b-ep8-1chip"
CELL = CONFIG + ".reason-saturated"
NEW = ("decode_ssm_share.ssmg",)
# accepted readers that read this cell rightly as they stand (they call
# the family): their lists are joined and no reader forwards to them
JOINED = ("ssd_step_roofline.ssm", "prefill_ssm_share.ssm",
          "slot_state_bytes.ssm", "moe_gmm_roofline.reason",
          "moe_compact_call_share.reason")
V5E = {"kind": "TPU v5 lite"}
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PREFILL, DECODE = "jit__prefill_batch_into_slots", "jit_decode_chunk"
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


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
            m["vocab_size"]) == (2688, 32, 2, 128, 16384)
    assert m["pattern"] == PATTERN and len(PATTERN) == 52
    assert [i for i, k in enumerate(PATTERN) if k == "*"] \
        == [5, 12, 19, 26, 33, 42]  # (runs of 5, 6, 6, 6, 6, 8, 9)
    assert (m["ssm_heads"], m["ssm_head_dim"], m["ssm_state"],
            m["ssm_groups"], m["conv_kernel"], m["ssm_chunk"]) \
        == (64, 64, 128, 8, 4, 256)
    assert (m["d_ff"], m["shared_d_ff"], m["n_experts"], m["top_k"],
            m["n_group"], m["topk_group"], m["routed_scaling_factor"]) \
        == (1856, 3712, 128, 6, 1, 1, 2.5)
    assert m["held_experts"] == [0, 16] and "norm_topk_eps" not in m
    assert (m["rms_eps"], m["published_layers"], m["dtype"]) \
        == (1e-5, 52, "bfloat16")
    assert fam.layer_counts(m) == {"ssm": 23, "full": 6, "moe": 23}
    config = _json("configs", CONFIG)
    for key, value in (
            ("model_type", "nemotron"), ("mlp_hidden_act", "silu"),
            ("tie_word_embeddings", True), ("use_conv_bias", False),
            ("mamba_proj_bias", True), ("attention_bias", True),
            ("n_group", 8), ("n_groups", 3),
            ("hybrid_override_pattern", PATTERN[:-1] + "-"),
            ("hybrid_override_pattern", PATTERN[:-1])):
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_two_cuts():
    """Every key of the catalog's row letter for letter but the sliced
    vocabulary (the router stays 128 wide: 16 are held); depth is whole;
    what was read into the keys is under ``assumed``."""
    config = _json("configs", CONFIG)
    if os.path.isfile(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert config["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key != "vocab_size":
                assert config[key] == value, key
        assert row["config"]["vocab_size"] == 8 * config["vocab_size"]
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["held_experts"], config["vocab_size"]) \
        == (52, 128, [0, 16], 16384)
    assert 8 * config["held_experts"][1] == config["n_routed_experts"]
    assert "published_num_hidden_layers" not in config  # (depth is whole)
    assert list(config["reduced"]) == ["n_routed_experts", "vocab_size"]
    for number in ("9,977,856", "179,948,288", "DEPTH IS NOT CUT"):
        assert number in config["reduced"]["n_routed_experts"], number
    for number in ("5,258,420,544", "31,577,940,288", "68,055,040",
                   "131,072 -> 16,384"):
        assert number in config["reduced"]["vocab_size"], number
    for reading in ("mamba_inner", "attention", "router", "time_step",
                    "chunk_size", "block", "serving_types",
                    "initialisation"):
        assert config["assumed"][reading]
    assert "NO rotary" in config["assumed"]["attention"]
    assert "4,096" in config["assumed"]["mamba_inner"]
    assert "n_groups 8 is the MIXER's" in config["assumed"]["router"]
    assert {"exchange", "max_position_embeddings", "num_logits_to_keep",
            "use_mamba_kernels"} <= set(config["left_out"])
    assert "eight chips of one v5e-8 host" in config["deployment"]
    assert "32 x 6 / 128 = 1.5 rows" in config["deployment"]
    # BENCHMARK.json lists the same cuts once, and the cell under its name
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
    assert (cell["chips"], cell["traffic_name"]) == (1, "reason-saturated")
    assert {m["name"] for m in cell["end_to_end"]} \
        == {"out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell["per_layer"]}
    assert {*NEW, *JOINED, "tokens_per_pump.doc", "ttft_p50_ms.doc",
            "tpot_p50_ms.doc", "prefill_device_share.doc",
            "decode_chunk_ms.doc", "decode_hbm_share.doc",
            "prefill_token_use_share.doc", "pump_host_work_ms.doc",
            "moe_experts_touched.doc", "moe_expert_load_max_over_mean.doc",
            "moe_held_assignment_share.reason", "prefill_rows_run_share.doc",
            *("device_part_share." + p for p in (
                "attn", "moe_experts", "lm_head", "sample", "cache",
                "loop", "unscoped")),
            *(f"setup_{s}.serve" for s in (
                "process_spawn_s", "chip_claim_s", "weights_s",
                "trace_lower_s", "compile_s", "compile_cache_hit_share"))} \
        <= names
    # the readers whose row or state rule is another block's stay away
    assert not {"kda_step_roofline.reason", "slot_state_bytes.hybrid",
                "slot_state_bytes.reason", "moe_gmm_roofline.doc",
                "decode_attn_roofline.hybrid", "device_part_share.mlp",
                "device_part_share.mhc"} & names
    assert not any(n.endswith(".ssmg") for n in names - set(NEW))
    for new in NEW:
        metrics = [p for p in b["per_layer"] if p["name"] == new]
        assert len(metrics) == 1, new
        assert metrics[0]["workloads"] == [CELL]
        assert (metrics[0]["moves"], metrics[0]["unit"],
                metrics[0]["better"]) == ("out_tokens_per_s", "%", "lower")
        assert os.path.isfile(os.path.join(
            manifest.HERE, "layer_metrics", new + ".py"))
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 4)


def test_the_traffic_is_lings_file_as_it_stands():
    """``reason-saturated.json`` is shared with Ling's cell: 48 callers
    on 32 slots of 3,088 rows, three buckets, its 32 shapes."""
    t = _json("traffic", "reason-saturated")
    assert (t["kind"], t["loop"], t["clients"]) == ("serve", "closed", 48)
    assert t["engine"] == {"slots": 32, "max_len": 3088, "chunk_tokens": 16,
                           "prompt_buckets": [256, 512, 1024]}
    entries = t["shapes"]["entries"]
    assert len(entries) == 32
    assert sum(p for p, _ in entries) / 32 == 566.25
    assert sum(o for _, o in entries) / 32 == 1152
    assert max(p + o for p, o in entries) + 16 == t["engine"]["max_len"]
    b = manifest.load_manifest()
    assert {w["name"] for w in b["workloads"]
            if w["traffic"] == "reason-saturated"} \
        == {"ling-3.0-flash-vl-ep4-1chip.reason-saturated", CELL}


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import jax

    fam, m = fam_and_fields
    # the issue's table, a block's norm counted with it
    assert fam.ssm_params(m) + 2688 == 38_744_896 \
        == 2688 * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * 2688 \
        + 2688
    assert fam.gqa_params(m) + 2688 == 23_399_040 \
        == 2688 * 4096 + 2 * 2688 * 256 + 4096 * 2688 + 2688
    assert fam.expert_params(m) == 9_977_856 == 2 * 2688 * 1856
    assert fam.moe_fixed_params(m) + 2688 == 20_302_592 \
        == 2 * 2688 * 3712 + 2688 * 128 + 128 + 2688
    total = 23 * 38_744_896 + 6 * 23_399_040 \
        + 23 * (16 * 9_977_856 + 20_302_592) + 2 * 16384 * 2688 + 2688
    assert fam.num_params(m) == total == 5_258_420_544
    prog = fam.build(m, max_seq_len=64, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == total
    assert shapes["lm_head"].shape == (2688, 16384)  # (untied)
    experts = shapes["layers"][1]["mix"]
    assert "w_gate" not in experts and "shared_gate" not in experts
    # (an ungated expert's w_up lies [count, F, D])
    assert experts["w_up"].shape == experts["w_down"].shape \
        == (16, 1856, 2688)
    assert experts["shared_up"].shape == (2688, 3712)
    assert shapes["layers"][0]["mix"]["w_in"].shape == (2688, 10304)
    assert shapes["layers"][5]["mix"]["w_qkv"].shape == (2688, 4096 + 512)
    # the uncut model: every expert, the whole vocabulary
    uncut = {**m, "held_experts": None, "vocab_size": 131072}
    assert fam.num_params(uncut) == 31_577_940_288
    assert fam.matmul_params(m) == int(
        23 * (2688 * 10304 + 4096 * 2688) + 6 * fam.gqa_params(m)
        + 23 * (2688 * 128 + 2 * 2688 * 3712 + 6 * 16 / 128 * 9_977_856)
        + 2688 * 16384)
    assert fam.flash_calls(m, 1, 1024) == []


def test_a_slots_state_a_decode_steps_bytes_and_the_kernels_calls(
        fam_and_fields):
    fam, m = fam_and_fields
    assert fam.kv_row_bytes(m) == 1024  # 2 x 2 x 128 bf16
    per_slot = fam.state_bytes_per_slot(m, 3088)
    assert per_slot == {"recurrent": 23 * (2_097_152 + 3 * 6144 * 2),
                        "full": 6 * 3088 * 1024}
    assert per_slot["recurrent"] == 49_082_368
    assert sum(per_slot.values()) == 68_055_040
    assert fam.ssm_state_bytes(m, 32) == 32 * 2_097_152
    assert fam.ssd_step_bytes(m, 32) == 134_217_728  # read AND written
    assert round(1e6 * fam.ssd_step_bytes(m, 32) / 819e9, 1) == 163.9
    touched = fam.experts_touched(m, 32)
    assert 12.5 < touched < 12.7  # (16 x (1 - (1 - 6/128)^32))
    step = fam.decode_step_bytes(m, 32, 1500)
    assert 12.0e9 < step < 12.6e9
    experts = 23 * touched * 9_977_856 * 2
    state = 32 * 2 * 49_082_368
    assert 0.75 < (experts + 23 * 2 * 2688 * 3712 * 2 + state) / step < 0.85
    assert fam.gmm_flops(9, 2688, 1856) == 2.0 * 9 * 2688 * 1856
    assert fam.gmm_bytes(9, 1856, 2688, 6.5) == (
        6.5 * 1856 * 2688 + 9 * 1856 + 9 * 2688) * 2


# ---------------------------------------------------------- the readers


def _state_init(**kw):
    return ["engine.state_init", 0, 0, {
        "engine": "e", "slots": 32, "max_len": 3088,
        "recurrent_bytes": 32 * 49_082_368, "full_bytes": 32 * 18_972_672,
        "recurrent_layers": 23, "full_layers": 6, "full_row_bytes": 1024,
        **kw}]


def _facts(ops=(), modules=(), spans=(), model=CONFIG):
    return {"model": model, "device": V5E, "engine": {"slots": 32},
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": list(modules)},
                {"name": "XLA Ops", "events": list(ops)}]}]},
            "spans": {"lines": [{"name": "pump", "events": list(spans)}]}}


def test_the_slots_state_reader_reads_both_kinds():
    read = _reader("slot_state_bytes.ssm")
    assert read(_facts(spans=[_state_init()])) == 68_055_040
    assert read(_facts()) is None


def test_the_step_kernels_roofline_is_23_grouped_calls_a_step():
    """23 events a step; a call reads and writes 32 slots' 2 MB: 163.9
    us at the HBM's peak; events that long read 100% and no more, 1.3
    times that long 77%."""
    read = _reader("ssd_step_roofline.ssm")
    least = 134_217_728 / 819e9
    for slower, want in ((1.0, 100.0), (1.3, 100 / 1.3)):
        ops = [[f"custom-call/2out/ssd_step.{i % 23 + 1}", i * 1_000_000,
                round(slower * least * 1e9)] for i in range(46)]
        ops.append(["custom-call/1out/moe_gmm.1", 1, 50_000])
        got = read(_facts(ops=ops, spans=[_state_init()]))
        assert got == pytest.approx(want, rel=1e-3) and got <= 100.001
    assert read(_facts(ops=ops)) is None  # no state_init
    assert read(_facts(ops=ops[-1:], spans=[_state_init()])) is None


def test_the_grouped_matmuls_roofline_tells_up_from_down_by_the_columns():
    """Two events an expert block and step, [rows, 1856] (up: contracts
    the hidden size) and [rows, 2688] (down: contracts 1,856): the
    accepted reader takes k from the columns, so both are charged one
    expert matrix a touched expert: 9 held rows over 6.5 experts read
    6.5 x 9.98 MB, 79.3 us at the HBM's peak, each."""
    fam, m = manifest.model(CONFIG)
    read = _reader("moe_gmm_roofline.reason")
    back = ["engine.readback", 5, 10, {
        "assignments": 192, "held_assignments": 9, "experts_touched": 6.5}]
    least = fam.gmm_bytes(9, 2688, 1856, 6.5) / 819e9
    assert round(1e6 * least, 1) == 79.3
    assert fam.gmm_bytes(9, 1856, 2688, 6.5) == fam.gmm_bytes(
        9, 2688, 1856, 6.5)
    events = [(192, n, slower * least) for n in (1856, 2688)
              for slower in (1.0, 1.0)]
    facts = _facts(spans=[back])
    facts["moe_gmm_events"] = events
    got = read(facts)
    assert got == pytest.approx(100.0, rel=1e-3) and got <= 100.001
    facts["moe_gmm_events"] = [(r, n, 2 * s) for r, n, s in events]
    assert read(facts) == pytest.approx(50.0, rel=1e-3)
    facts["moe_gmm_events"] = []
    assert read(facts) is None


def test_the_ssm_shares_read_their_own_programs():
    """``decode_ssm_share.ssmg`` reads the decode chunks' parts alone,
    ``prefill_ssm_share.ssm`` the prefill's; neither finds anything in
    another model's table or on a parent's."""
    decode, prefill = _reader(NEW[0]), _reader("prefill_ssm_share.ssm")
    facts = {"device_parts": {"busy_s": 2.0, "programs": {
        PREFILL: {"attn/attn_ssm": 0.2, "attn/attn_full": 0.1, "qkv": 0.3,
                  "moe_experts": 0.4},
        DECODE: {"attn/attn_ssm": 0.25, "moe_experts": 0.5, "qkv": 0.15,
                 "lm_head": 0.1}}}}
    assert decode(facts) == pytest.approx(25.0)
    assert prefill(facts) == pytest.approx(20.0)
    facts["device_parts"]["programs"][DECODE] = {
        "attn/attn_linear": 0.5, "qkv": 0.5}  # (another model)
    assert decode(facts) is None
    del facts["device_parts"]["programs"][DECODE]
    assert decode(facts) is None
    assert decode({"device_parts": None}) is None


def test_every_scope_of_the_block_is_in_the_vocabulary():
    """The block opens the names the readers know and no other, so that
    no part of it lands in ``unscoped``."""
    import re

    from ray_tpu.models import program_parts as pp

    with open(os.path.join(ROOT, "ray_tpu", "models", "nemotron.py")) as f:
        source = f.read()
    scopes = set(re.findall(r'named_scope\("([^"]+)"\)', source))
    assert scopes == {"qkv", "attn_out", "attn", "attn/attn_full", "cache",
                      "moe_router", "moe_shared", "embed"}
    for scope in scopes:
        head, _, kind = scope.partition("/")
        assert head in pp.VOCABULARY, scope
        assert not kind or kind in pp.ATTN_KINDS, scope
    assert pp.part_of("jit(decode_chunk)/while/body/attn/attn_ssm/"
                      "mul") == "attn/attn_ssm"


# ------------------------------------------------------ the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("nemotron_h")
    ref = manifest.reference(fam)
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    for called in ("layer_counts", "experts_touched", "gmm_flops",
                   "gmm_bytes", "ssd_step_bytes", "ssm_state_bytes",
                   "state_bytes_per_slot", "kv_row_bytes"):
        assert hasattr(fam, called), called
    with open(os.path.join(manifest.HERE, "families",
                           "nemotron_h.reference.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "ray_tpu" not in body
    assert "import" not in body.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace(
        "import jax.numpy as jnp", "").replace("import jax", "").replace(
        "import numpy as np", "")
    # the recurrence a token at a time with a head's group, the norm by
    # group, the squared relu, the router in the published order with
    # its 2.5, the scores written out, the precision the highest
    assert "jax.lax.scan(token, h0" in body
    assert "of_head = jnp.arange(h) // (h // b.shape[2])" in body
    assert "w.reshape(g, inner // g)" in body
    assert "jnp.square(jax.nn.relu(x @ w_up)) @ w_down" in body
    assert "jnp.argsort(-(scores + bias), -1, stable=True)[..., :kk]" in body
    assert '* m["routed_scaling_factor"]' in body
    assert "/ jnp.sqrt(jnp.float32(hd))" in body
    assert body.count('default_matmul_precision("highest")') == 5
    for block in (ref._ssm_block, ref._gqa_project, ref._gqa_attend,
                  ref._moe_block, ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 3 and 0 < ref.TRAIN_LOSS_TOL < 0.1
    assert 0 < ref.SERVE_MEAN_REGRET < ref.SERVE_TOP2_GAP / 4


def test_blocks_of_rows_give_the_whole_sequences_forward():
    """The reference in blocks of 16 rows and 4 query rows over 50
    positions (``H`` and the three ``xBC`` rows handed from block to
    block) is its forward in one block; ``last`` gives the tail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    fam = manifest.family("nemotron_h")
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
    package imports ``models/nemotron.py`` (the block is found through
    its configuration's ``slot_model``, built by its family file alone),
    so no older cell's process loads, traces or compiles a line of it;
    the engine imports no block. The new block itself imports the
    seventh (``models/granite.py``: the mixer's functions and the slots'
    state are one copy) and the two ``ops/ssd_*.py``: this guard asks
    only that nothing OLDER reach the new block."""
    new = {"ray_tpu.models.nemotron"}
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
    blocks = {"nemotron", "lfm2", "granite", "solar", "mimo", "ling",
              "exaone", "instella", "dots", "glm_dsa", "glm_next"}
    assert not {i for i in engine
                if i.rsplit(".", 1)[-1] in blocks}, engine
    assert "ray_tpu.models.granite" in _imports(
        os.path.join(package, "models", "nemotron.py"))
    # the benchmark's own files name the block in its family file alone
    for folder, _, files in os.walk(manifest.HERE):
        for name in files:
            if name.endswith(".py") and "nemotron_h" not in name:
                path = os.path.join(folder, name)
                assert not _imports(path) & new, path


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:nemotron-3-nano-30b-a3b-ep8-1chip`` through proxy,
    pool, replica pump and engine at tiny widths: served tokens agree
    with the plain reference; both kinds of state, their bytes and the
    routing counters reach the result line."""
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
    fam = manifest.family("nemotron_h")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        3088 // 16, 4)
    assert metrics["slot_state_bytes.ssm"]["value"] \
        == sum(per_slot.values())
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_token_use_share.doc"]["value"] <= 100
    assert 0 < metrics["prefill_rows_run_share.doc"]["value"] <= 100
    for device_only in ("ssd_step_roofline.ssm", "prefill_ssm_share.ssm",
                        "decode_ssm_share.ssmg", "moe_gmm_roofline.reason"):
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
    os.makedirs(tmp_path / "ray_tpu" / "models")  # no nemotron.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "nemotron.py" in proc.stderr
