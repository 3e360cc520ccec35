"""The family ``mimo_v2`` (``benchmark/families/mimo_v2.py``) by hand: the
configuration's keys against the catalog's row and its five cuts, its
parameter counts against ``init_params``' shapes, a slot's state of two
kinds at their own row widths, a decode step's bytes and a prefill's
flash work (a band's work the band's); the ``.swa`` readers on small
hand-made traces; the reference's blocks; and the CPU rehearsal of the
cell through ``benchmark.run`` (never a measurement)."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

from benchmark import manifest

ROOT = manifest.ROOT
CONFIG = "mimo-v2.5-ep16-1chip"
CELL = CONFIG + ".longreason-saturated"
NEW = ("decode_attn_roofline.swa", "flash_fwd_roofline.swa",
       "prefill_attn_share.swa", "slot_state_bytes.swa")
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
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"],
            m["window_kv_heads"], m["head_dim"], m["v_head_dim"],
            m["n_layers"], m["vocab_size"]) \
        == (4096, 64, 4, 8, 192, 128, 7, 19072)
    assert m["rotary_dim"] == 64  # floor(0.334 x 192)
    assert (m["layer_pattern"], m["moe_pattern"]) \
        == ([0, 1, 1, 1, 1, 0, 1], [0, 1, 1, 1, 1, 1, 1])
    assert (m["rope_theta"], m["window_rope_theta"], m["value_scale"],
            m["sliding_window"]) == (1e7, 1e4, 0.707, 128)
    assert (m["dense_d_ff"], m["d_ff"], m["shared_d_ff"]) == (16384, 2048, 0)
    assert (m["n_experts"], m["top_k"], m["n_group"], m["topk_group"],
            m["routed_scaling_factor"]) == (256, 8, 1, 1, 1.0)
    assert m["held_experts"] == [0, 16]
    assert (m["rms_eps"], m["published_layers"], m["dtype"]) \
        == (1e-5, 48, "bfloat16")
    assert fam.layer_counts(m) == {"window": 5, "full": 2, "dense": 1,
                                   "moe": 6}
    config = _json("configs", CONFIG)
    for key, value in (
            ("model_type", "mimo"), ("scoring_func", "softmax"),
            ("norm_topk_prob", False), ("n_shared_experts", 1),
            ("add_swa_attention_sink_bias", False),
            ("add_full_attention_sink_bias", True),
            ("attention_projection_layout", "split"),
            ("swa_head_dim", 128), ("swa_v_head_dim", 64),
            ("swa_num_attention_heads", 32), ("tie_word_embeddings", True),
            ("hybrid_layer_pattern", [0, 1]),
            ("partial_rotary_factor", 0.33)):  # 63 numbers: no pairs
        with pytest.raises(manifest.ManifestError):
            fam.fields({**config, key: value})


def test_the_file_holds_the_catalogs_keys_and_names_its_five_cuts():
    """Every number of the catalog's row as published but the depth and
    the vocabulary (the router stays 256 wide: 16 are held); what was
    read into the keys is under ``assumed``."""
    config = _json("configs", CONFIG)
    published = {
        "attention_bias": False, "attention_chunk_size": 128,
        "attention_value_scale": 0.707,
        "attention_projection_layout": "fused_qkv",
        "add_full_attention_sink_bias": False,
        "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
        "swa_num_attention_heads": 64, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384,
        "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
        "model_type": "mimo_v2", "moe_intermediate_size": 2048,
        "n_group": 1, "n_routed_experts": 256, "n_shared_experts": None,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "partial_rotary_factor": 0.334,
        "rope_scaling": {"rope_type": "default", "type": "default"},
        "rope_theta": 10000000, "routed_scaling_factor": None,
        "scoring_func": "sigmoid", "sliding_window": 128,
        "sliding_window_size": 128, "swa_rope_theta": 10000,
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128}
    for key, value in published.items():
        assert config[key] == value, key
    whole = [0] + ([1, 1, 1, 1, 0] + [1] * 5 + [0] + [1] * 5)[:6]
    assert config["hybrid_layer_pattern"] == whole == [0, 1, 1, 1, 1, 0, 1]
    assert config["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["held_experts"], config["published_num_hidden_layers"]) \
        == (7, 19072, [0, 16], 48)
    assert 8 * config["vocab_size"] == 152576
    assert 16 * config["held_experts"][1] == config["n_routed_experts"]
    assert list(config["reduced"]) == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size"]
    assert "3,429,955,392" in config["reduced"]["vocab_size"]
    assert "181,616,640" in config["reduced"]["vocab_size"]
    for reading in ("sink", "rotation", "projection", "norm_placement",
                    "window", "value_scale", "router", "attention_scale",
                    "serving_types", "initialisation"):
        assert config["assumed"][reading]
    assert {"mtp", "towers", "exchange", "attention_chunk_size",
            "long_context"} <= set(config["left_out"])
    assert "seven pipeline stages of sixteen" in config["deployment"]
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
    assert {*NEW, "moe_gmm_roofline.reason", "device_part_share.mlp",
            "moe_held_assignment_share.reason", "decode_hbm_share.doc",
            "decode_chunk_ms.doc", "prefill_device_share.doc",
            "device_part_share.attn",
            "device_part_share.moe_experts"} <= names
    # the readers whose row or state rule is another block's stay away
    assert not {"decode_attn_roofline.doc", "decode_attn_roofline.mix",
                "decode_attn_roofline.hybrid", "slot_state_bytes.mix",
                "slot_state_bytes.hybrid", "attn_rows_read_share.mix",
                "kda_step_roofline.reason", "moe_gmm_roofline.doc"} & names
    for new in NEW:
        metric = [p for p in b["per_layer"] if p["name"] == new][0]
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "out_tokens_per_s"
    assert len(b["workloads"]) == 11
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1


def test_the_traffic_file_is_solars_as_it_stands():
    """The cell runs ``longreason-saturated.json`` as the parent has it:
    the control is the same traffic on the other hybrid."""
    with open(os.path.join(manifest.HERE, "traffic",
                           "longreason-saturated.json"), "rb") as f:
        raw = f.read()
    assert hashlib.sha256(raw).hexdigest() == (
        "aed21ab18824fd4c79f7f88b460848f2787c899c13f0d9775d4ad31ef1c49e4a")
    assert json.loads(raw)["engine"] == {
        "slots": 32, "max_len": 34832, "chunk_tokens": 16,
        "prompt_buckets": [8192, 16384, 24576, 32768]}
    b = manifest.load_manifest()
    assert {w["name"] for w in b["workloads"]
            if w["traffic"] == "longreason-saturated"} == {
        CELL, "solar-open2-250b-ep8-1chip.longreason-saturated"}


def test_parameter_counts_by_hand_and_by_init_params(fam_and_fields):
    import jax

    fam, m = fam_and_fields
    a = fam.attn_params(m)
    assert a == {"window": 94_371_904, "full": 89_128_960}
    assert a["full"] == 4096 * (64 * 192 + 4 * 192 + 4 * 128) \
        + 64 * 128 * 4096
    assert fam.expert_params(m) == 25_165_824
    assert fam.moe_fixed_params(m) == 1_048_832
    dense = 3 * 4096 * 16384
    layer0 = a["full"] + dense + 2 * 4096
    sparse_window = a["window"] + 16 * 25_165_824 + 1_048_832 + 2 * 4096
    sparse_full = a["full"] + 16 * 25_165_824 + 1_048_832 + 2 * 4096
    assert (layer0, sparse_window, sparse_full) \
        == (290_463_744, 498_082_112, 492_839_168)
    total = layer0 + 5 * sparse_window + sparse_full \
        + 2 * 19072 * 4096 + 4096
    assert fam.num_params(m) == total == 3_429_955_392
    prog = fam.build(m, max_seq_len=64, remat=False)
    shapes = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) == total
    # the uncut model: every layer, expert and vocabulary row
    uncut = {**m, "n_layers": 48, "held_experts": None,
             "vocab_size": 152576,
             "layer_pattern": [int(i > 0 and (i + 1) % 6 != 0)
                               for i in range(48)],
             "moe_pattern": [0] + [1] * 47}
    assert round(fam.num_params(uncut) / 1e9, 2) == 308.78
    # a token meets: attention, the dense MLP or the router and its
    # held share of eight experts, the head
    assert fam.matmul_params(m) == int(
        5 * (a["window"] - 64) + 2 * a["full"] + dense
        + 6 * (4096 * 256 + 8 * 16 / 256 * 25_165_824) + 4096 * 19072)


def test_a_slots_state_a_decode_steps_bytes_and_a_bands_work(
        fam_and_fields):
    fam, m = fam_and_fields
    assert fam.kv_row_bytes(m) == {"window": 5120, "full": 2560}
    per_slot = fam.state_bytes_per_slot(m, 34832)
    assert per_slot == {"window": 5 * 128 * 5120, "full": 2 * 34832 * 2560}
    assert sum(per_slot.values()) == 181_616_640
    live = fam.live_row_bytes(m, 19000)
    assert live == {"window": 5 * 128 * 5120, "full": 2 * 19000 * 2560}
    # a step of 32 slots at 19,000 live rows: weights outside the
    # experts once, the touched experts, the live rows by kind
    touched = fam.experts_touched(m, 32)
    assert 10 < touched < 11
    weights = (5 * 94_371_904 + 2 * 89_128_960 + 3 * 4096 * 16384
               + 6 * (1_048_832 + touched * 25_165_824)
               + 4096 * 19072 + 32 * 4096) * 2
    assert fam.decode_step_bytes(m, 32, 19000) == pytest.approx(
        weights + 32 * sum(live.values()))
    # a band's pairs: row p sees min(p + 1, 128) keys
    assert fam.band_keys(5, 128) == 15
    assert fam.band_keys(300, 128) == 128 * 129 // 2 + 172 * 128
    assert fam.band_keys(100, 128, first=200) == 100 * 128
    assert fam.causal_keys(4) == 10 and fam.causal_keys(2, first=3) == 9
    rows = 32768
    flops, nbytes = fam.prefill_flash_work(m, rows, "window")
    assert flops == 2.0 * 64 * fam.band_keys(rows, 128) * 320
    assert nbytes == rows * (64 * 320 + 8 * 320) * 2
    full_flops, _ = fam.prefill_flash_work(m, rows, "full")
    assert full_flops == 2.0 * 64 * (rows * (rows + 1) // 2) * 320
    # the band's work is the band's: a 128th of the triangle's and less
    assert flops < full_flops / 100
    assert fam.flash_calls(m, 1, 4096) == []


# ---------------------------------------------------------- the readers


def _state_init(**kw):
    return ["engine.state_init", 0, 0, {
        "engine": "e", "slots": 32, "max_len": 34832,
        "window_bytes": 32 * 3_276_800, "full_bytes": 32 * 178_339_840,
        "window_layers": 5, "full_layers": 2, "window_row_bytes": 5120,
        "full_row_bytes": 2560, **kw}]


def _facts(ops=(), modules=(), spans=()):
    return {"model": CONFIG, "device": V5E,
            "trace": {"planes": [{"name": "/device:TPU:0", "lines": [
                {"name": "XLA Modules", "events": list(modules)},
                {"name": "XLA Ops", "events": list(ops)}]}]},
            "spans": {"lines": [{"name": "pump", "events": list(spans)}]}}


def test_the_slots_state_reader_wants_the_row_bytes():
    read = _reader("slot_state_bytes.swa")
    assert read(_facts(spans=[_state_init()])) == 181_616_640
    other = _state_init()
    del other[3]["window_row_bytes"]  # (a parent's event: no such attr)
    assert read(_facts(spans=[other])) is None
    assert read(_facts()) is None


def test_the_decode_kernels_roofline_prices_each_kinds_rows_apart():
    """Seven events a step (five rings, two full stacks); 32 slots at
    19,000 rows: a ring call reads 32 x 128 rows of 5,120 B, a full call
    32 x 19,000 rows of 2,560 B, at 819 GB/s."""
    read = _reader("decode_attn_roofline.swa")
    back = ["engine.readback", 0, 0, {"live_rows_window": 32 * 128,
                                      "live_rows_full": 32 * 19000}]
    least = (5 * 32 * 128 * 5120 + 2 * 32 * 19000 * 2560) / 819e9
    ops = [[f"custom-call/1out/decode_attn.{i}", i * 10_000_000,
            int(2 * least / 7 * 1e9)] for i in range(7)]
    got = read(_facts(ops=ops, spans=[_state_init(), back]))
    assert got == pytest.approx(50.0, rel=1e-3)
    plain = _state_init()
    del plain[3]["full_row_bytes"]
    assert read(_facts(ops=ops, spans=[plain, back])) is None
    assert read(_facts(spans=[_state_init(), back])) is None


def test_the_flash_kernels_roofline_counts_whole_calls_and_the_band(
        fam_and_fields):
    """One whole 8,192-row prefill (4 segments: 8 ``flash_fwd`` and 20
    ``flash_fwd_window`` events) and one the trace cut (3 events): the
    cut one is left out with its events; the least time is the family's
    band and triangle."""
    from benchmark import model_math

    fam, m = fam_and_fields
    read = _reader("flash_fwd_roofline.swa")
    peak = model_math.peaks(V5E["kind"])
    least = sum(n * model_math.roofline_seconds(
        *fam.prefill_flash_work(m, 8192, kind), peak)[0]
        for kind, n in (("full", 2), ("window", 5)))
    each = int(2 * least / 28 * 1e9)  # twice the least, over 28 events
    ops, t = [], 1_000
    for i in range(28):
        name = "flash_fwd_window" if i % 7 >= 2 else "flash_fwd"
        ops.append([f"custom-call/2out/{name}.{i % 7 + 1}", t, each])
        t += each + 10
    whole = ["jit__prefill_batch_into_slots(7)", 0, t]
    cut_at = t + 1_000
    ops += [[f"custom-call/2out/flash_fwd.{i}", cut_at + i * 100, 50]
            for i in (1, 2, 3)]
    cut = ["jit__prefill_batch_into_slots(7)", cut_at, 10_000]
    call = ["engine.prefill", 0, 0, {"bucket": 8192, "segments": 4,
                                     "tokens": 8000, "rows": 1}]
    got = read(_facts(ops=ops, modules=[whole, cut], spans=[call]))
    assert got == pytest.approx(50.0, rel=1e-3)
    assert read(_facts(ops=ops, modules=[whole, cut])) is None  # no span
    assert read(_facts(modules=[whole], spans=[call])) is None  # no event


def test_the_attention_share_reads_the_prefill_programs_alone():
    read = _reader("prefill_attn_share.swa")
    facts = {"device_parts": {"busy_s": 2.0, "programs": {
        "jit__prefill_batch_into_slots": {
            "attn/attn_window": 0.1, "attn/attn_full": 0.3, "qkv": 0.2,
            "moe_experts": 0.4},
        "jit_decode_chunk": {"attn/attn_full": 1.0}}}}
    assert read(facts) == pytest.approx(40.0)
    facts["device_parts"]["programs"]["jit__prefill_batch_into_slots"] = {
        "attn": 0.5, "qkv": 0.5}  # (another model: neither kind)
    assert read(facts) is None
    assert read({"device_parts": None}) is None


# ------------------------------------------------------ the reference


def test_the_reference_computes_in_blocks_and_shares_no_code():
    fam = manifest.family("mimo_v2")
    ref = manifest.reference(fam)
    for duty in manifest.FAMILY_DUTIES:
        assert hasattr(fam, duty), duty
    for duty in manifest.REFERENCE_DUTIES:
        assert hasattr(ref, duty), duty
    with open(os.path.join(manifest.HERE, "families",
                           "mimo_v2.reference.py")) as f:
        source = f.read()
    body = source.split('"""', 2)[2]
    assert "ray_tpu" not in body
    assert "import" not in body.replace(
        "from __future__ import annotations", "").replace(
        "import functools", "").replace(
        "import jax.numpy as jnp", "").replace("import jax", "").replace(
        "import numpy as np", "")
    # the mask written out, the sink one more logit whose column is
    # dropped, the value scale on the output, the precision the highest
    assert "jnp.where(seen[None, None], s, -jnp.inf)" in body
    assert "jnp.concatenate([s, col], -1), -1)[..., :-1]" in body
    assert 'm["value_scale"] * o' in body
    assert body.count('default_matmul_precision("highest")') == 4
    for block in (ref._project, ref._attend, ref._mlp_block, ref._head):
        assert hasattr(block, "lower")
    assert 0 < ref.SERVE_TOP2_GAP < 1 and 0 < ref.TRAIN_LOSS_TOL < 0.1


def test_blocks_of_rows_give_the_whole_sequences_forward():
    """The reference in blocks of 16 rows and 4 query rows over 50
    positions (a window layer's block reading its band alone: 4 + 7
    keys) is its forward in one block; ``last`` gives the tail."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import mimo

    fam = manifest.family("mimo_v2")
    ref = manifest.reference(fam)
    m = dict(fam.TINY_FIELDS)
    cfg = fam.build(m, max_seq_len=64, remat=False).cfg
    params = mimo.init_params(cfg, jax.random.PRNGKey(3))
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
    np.testing.assert_allclose(blocks, whole, atol=2e-5)
    np.testing.assert_allclose(tail, whole[:, -5:], atol=2e-5)


# ------------------------------------------------------ the rehearsal


def test_the_cell_rehearses_on_the_cpu():
    """``rehearsal:mimo-v2.5-ep16-1chip`` through proxy, pool, replica
    pump and engine at tiny widths: served tokens agree with the plain
    reference; both kinds of rows, their bytes and the routing counters
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
    # tiny: 16 experts of which 4 are held, top-4, 4 slots
    assert 0 < metrics["moe_experts_touched.doc"]["value"] <= 4
    assert 0 < metrics["moe_held_assignment_share.reason"]["value"] < 100
    fam = manifest.family("mimo_v2")
    per_slot = fam.state_bytes_per_slot(dict(fam.TINY_FIELDS),
                                        34832 // 16, 4)
    assert metrics["slot_state_bytes.swa"]["value"] \
        == sum(per_slot.values())
    assert metrics["tokens_per_pump.doc"]["value"] > 0
    assert 0 < metrics["prefill_token_use_share.doc"]["value"] <= 100
    for device_only in ("decode_attn_roofline.swa", "flash_fwd_roofline.swa",
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
    os.makedirs(tmp_path / "ray_tpu" / "models")  # a program, no mimo.py
    (tmp_path / "ray_tpu" / "__init__.py").write_text("")
    (tmp_path / "ray_tpu" / "_private").mkdir()
    proc = subprocess.run(
        [sys.executable, "-c",
         "from benchmark import manifest; "
         f"manifest.model({CONFIG!r})"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert "ManifestError" in proc.stderr and "mimo.py" in proc.stderr
