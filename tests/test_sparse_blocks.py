"""The two blocks whose latent attention reads the rows a learned indexer
chooses, each against its plain float32 reference on seeded weights at a
tiny size, on the CPU, and both through ONE sparse layer
(``models/dots.py``: ``sparse_segment``, ``sparse_step_layer``,
``SparseSlots``):

- ``dots``, the eighth block (dots3-note-prev's language model: full
  layers that select beside window layers with latents of their own and
  a ring of latent rows, a gate a head;
  ``benchmark/families/dots3_note.reference.py``): ``index_topk`` 8 of
  up to 240 rows, a window of 9;
- ``glm_dsa``, the ninth (GLM-5.2's: MLA in every layer, the choice made
  in the layers that own an indexer and read by the layers behind them;
  ``benchmark/families/glm_moe_dsa.reference.py``, which hands a
  selection on as a set of INDICES): the cell's five-layer pattern,
  ``index_topk`` 8 of up to 100 rows, keys 24 + 8 wide beside values of
  32.

What both hold, one case a block:

- the engine's path (the segmented prefill into a slot's stacks, then
  the ragged steps: dots3's rings wrap twenty times in 200, GLM-5.2's 60
  hand one bias to four layers) gives the reference's logits, which
  attends UNABSORBED and selects by a sort of its own;
- a reused slot shows nothing of its last stream; a sparse layer's k and
  v are made for the live rows alone; ``RaggedDecoder`` serves the
  reference's tokens in bf16 and its spans carry the block's kinds of
  rows and its counters.

What one block has alone stands behind them, under its name. Every test
runs the bucket of 128 rows in eight segments of 16 and one shape of
state, so that the file compiles each program once a block; what each
mechanism is worth is in ``test_dots_mechanisms.py`` and
``test_glm_dsa_mechanisms.py``, the kernels in ``test_dsa_ops.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import (
    decode_from, forget_programs, live_kv_case, prefill_slot)
from _sparse import only, sparse_block, tokens
from ray_tpu.models import dots, glm_dsa, moe
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import dsa
from ray_tpu.ops.norms import rms_norm

# float32 on both sides, the same products in another order (absorbed
# against unabsorbed, a head at a time against all at once): readings of
# 2e-6 to 3e-5 on logits that spread by 0.8; a mechanism left out moves
# them by 6e-3 and more (test_dots_mechanisms.py, test_glm_dsa_mechanisms.py)
F32_TOL = 1e-4
PROMPT = 40  # the engine test's prompt; the block's steps behind it


# steps: the ragged steps behind the prompt; kind / sparse_layers: the
# sparse layers' widths and count; kinds: (kind of rows, its stack, its
# layers); refusal: what the engine says to speculative decoding
BLOCKS = {
    "dots": sparse_block(
        "dots", steps=200,
        kind=lambda cfg: cfg.kind(False),
        sparse_layers=lambda cfg: cfg.full_layers,
        kinds=(("full", "lat", 2), ("index", "idx", 2), ("ring", "ring", 3)),
        refusal="ring of rows"),
    "glm_dsa": sparse_block(
        "glm_dsa", steps=60,
        kind=lambda cfg: cfg.mla, sparse_layers=lambda cfg: cfg.n_layers,
        kinds=(("latent", "lat", 5), ("index", "idx", 2)),
        refusal="GlmDsaConfig"),
}

@pytest.fixture(scope="module", autouse=True)
def segments_of_16():
    """Every bucket of this file in segments of 16 rows (the engine's
    programs are cached by cfg alone: set once, cleared once)."""
    was, moe.SEGMENT_ROWS = moe.SEGMENT_ROWS, 16
    forget_programs()
    yield
    moe.SEGMENT_ROWS = was
    forget_programs()


@pytest.fixture(scope="module", params=list(BLOCKS))
def block(request):
    return BLOCKS[request.param]


@functools.cache
def _model(name: str):
    cfg = BLOCKS[name].cfg()
    return cfg, BLOCKS[name].mod.init_params(cfg, jax.random.PRNGKey(7))


@pytest.fixture(scope="module")
def model(block):
    """(drawn once a block and a process, in whatever order the cases of
    one block and of both come)"""
    return _model(block.name)


def _empty(block, cfg):
    """(the prefill program is donated its state and tokens)"""
    return block.slots.init_state(cfg, 2, 256), jnp.zeros((2,), jnp.int32)


@pytest.fixture(scope="module")
def served(block):
    return _served(block.name)


@functools.cache
def _served(name: str):
    """The 40-token prompt through the engine's prefill program (a
    128-row bucket, three of eight segments live; GLM-5.2's second
    group's four layers in one scan) and the block's greedy steps of the
    ragged step -> (prompt + tokens fed, float32 logits of the steps,
    the reference's logits [prompt + steps, V], what the reference's
    sparse layers read: dots3's full layers' masks, the sets GLM-5.2's
    five layers read)."""
    block, (cfg, params) = BLOCKS[name], _model(name)
    assert block.slots.prefill_segments(cfg, 128) == 8
    state, cur = prefill_slot(cfg, params, *_empty(block, cfg), 1,
                              tokens(5, PROMPT))
    assert int(state["pos"][1]) == PROMPT
    fed, got = decode_from(block.slots, cfg, params, state, cur, 1,
                           block.steps)
    seq = list(tokens(5, PROMPT)) + fed
    read = []
    h = block.ref.hidden(params, jnp.asarray([seq]), block.m, read)
    want = block.ref._head(h, params["final_norm"], params["lm_head"],
                           block.m["rms_eps"])
    return seq, got, np.asarray(want[0]), read


# ------------------------------------- the model, through the engine


def test_prefill_then_decode_steps_are_the_references_forward(block, served):
    """From 41 rows on every step's indexers select 8 of the slot's rows
    (the prefill's rows past the 8th chose theirs across segment
    boundaries). dots3: every full layer's step gathers and attends
    them, and the rings of 9 rows wrap twenty times. GLM-5.2: five
    layers attend them, three over a bias they did not make, a segment's
    bias crossing four layers. Every step's logits are the reference's
    full forward over prompt + tokens."""
    seq, got, want, _ = served
    assert seq[PROMPT] == int(want[PROMPT - 1].argmax())  # (the prefill's)
    assert np.abs(got - want[PROMPT:PROMPT + block.steps]).max() < F32_TOL


def test_a_reused_slot_shows_nothing_of_its_last_stream(block, model, served):
    """The served prompt and 12 steps in a slot that a 100-token prompt
    filled before (every stack of the block, every layer of its latent
    rows; seven segments live for the short one's three): the logits
    are the fresh slot's bit for bit, the rows behind the short prompt's
    segments are zeros, and the inactive slot beside it keeps its
    position."""
    cfg, params = model
    _, fresh, _, _ = served
    used, cur = prefill_slot(cfg, params, *_empty(block, cfg), 1,
                             tokens(9, 100))
    for _, name, layers in block.kinds:
        assert all(np.asarray(used[name][i, 1]).any() for i in range(layers))
    state, cur = prefill_slot(cfg, params, used, cur, 1, tokens(5, PROMPT))
    assert not np.asarray(state["lat"][:, 1, 48:128]).any()
    assert not np.asarray(state["idx"][:, 1, 48:128]).any()
    assert int(state["pos"][0]) == 0
    _, reused = decode_from(block.slots, cfg, params, state, cur, 1, 12)
    np.testing.assert_array_equal(reused, fresh[:12])


@pytest.mark.parametrize("case", ["whole_bucket", "stale_1e4", "lowered"])
def test_a_sparse_layers_k_and_v_are_made_for_the_live_rows_alone(
        block, model, monkeypatch, case):
    """``dots._live_kv`` in the prefill of dots3's two full layers and of
    all five of GLM-5.2's, four of them in one scan body
    (``_segments.live_kv_case`` says what each case holds)."""
    cfg, params = model
    live_kv_case(case, monkeypatch, block.mod, block.kind(cfg),
                 block.sparse_layers(cfg), cfg, params, tokens(11, 1, 64))


def test_submit_and_pump_serve_the_references_tokens_in_bf16(block):
    """``RaggedDecoder`` (submit -> pump) on the model in bfloat16: three
    streams of 64 positions over two slots, so a slot is reused and the
    streams sit at ragged positions, each decoded past ``index_topk``
    rows (and two wraps of dots3's rings); every stream's tokens pass
    the reference's ``check_served_tokens``. The spans carry the block's
    kinds of rows, their bytes a row and ``selected_rows`` (two
    indexers'), GLM-5.2's ``attended_rows`` too (five layers' for those
    two selections)."""
    from ray_tpu._private import flight_recorder as fr

    # (32 rows chosen of up to 64: where bf16 activations flip a set at
    # the threshold, a thirty-second of a row's attention moves, not the
    # eighth that the float32 tests' 8 rows would)
    m = {**block.m, "index_topk": 32}
    cfg = block.cfg(dtype="bfloat16", index_topk=32)
    params = block.mod.init_params(cfg, jax.random.PRNGKey(8))
    seen = fr._get().recorded  # (the ring is bounded: count, not place)
    name = f"{block.name}-test"
    eng = RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                        prompt_buckets=(64,), name=name)
    asked = [(tokens(20 + n, n), 64 - n) for n in (13, 40, 24)]
    sids = [eng.submit(p, out) for p, out in asked]
    eng.drain()
    for sid, (p, out) in zip(sids, asked):
        toks = list(eng.finished[sid].tokens)
        assert len(toks) == out
        check = block.ref.check_served_tokens(params, list(p), toks, m)
        assert check["wrong"] == 0 and check["agree"] > out // 2, check
    st = eng.stats()
    per_slot = block.fam.state_bytes_per_slot(block.m, 96, 2)
    assert st["state_bytes"] == {kind: 2 * n for kind, n in per_slot.items()}
    by_kind = st["attn_live_rows_by_kind"]
    assert 0 < by_kind[block.kinds[0][0]] == by_kind["index"]
    with pytest.raises(ValueError, match=block.refusal):
        RaggedDecoder(params, cfg, slots=2, max_len=64, spec_depth=2)
    spans = list(fr._get().ring)[seen - fr._get().recorded:]
    init = [s["attrs"] for s in spans if s["name"] == "engine.state_init"
            and s["attrs"].get("engine") == name][-1]
    rows = block.fam.row_bytes(block.m, 2)
    assert init["slots"] == 2
    for kind, _, layers in block.kinds:
        assert init[f"{kind}_bytes"] == 2 * per_slot[kind]
        assert init[f"{kind}_layers"] == layers
        assert init[f"{kind}_row_bytes"] == rows[kind]
    last = block.slots.step_counters[-1]
    back = [s["attrs"] for s in spans if s["name"] == "engine.readback"
            and last in s["attrs"]]
    # two streams past index_topk rows: 32 rows each in two indexers
    assert back and max(a["selected_rows"] for a in back) == 2 * 2 * 32
    assert {"held_assignments", *(f"live_rows_{kind}"
                                  for kind, _, _ in block.kinds)} \
        <= back[-1].keys()
    if block.mod is dots:
        assert 0 < by_kind["ring"] < by_kind["full"]
        assert back[-1]["live_rows_ring"] <= block.m["sliding_window"] \
            < back[-1]["live_rows_full"]
    else:  # (handed to five attentions)
        assert max(a["attended_rows"] for a in back) == 2 * 5 * 32
        assert all(2 * a["attended_rows"] == 5 * a["selected_rows"]
                   for a in back)
        assert back[-1]["live_rows_latent"] == back[-1]["live_rows"]
    pre = [s["attrs"] for s in spans if s["name"] == "engine.prefill"]
    assert {(a["segments"], a["live_segments"]) for a in pre} \
        == {(4, 1), (4, 3), (4, 2)}


# ------------------------------------------------- dots3's block alone


def test_dots_configuration_carries_both_kinds_widths_and_the_pattern():
    cfg = BLOCKS["dots"].cfg()
    assert (cfg.window_layers, cfg.full_layers, cfg.moe_layers) == (3, 2, 4)
    assert [cfg.stack_index(i) for i in range(5)] == [0, 1, 0, 1, 2]
    whole = dots.DotsConfig()
    assert whole.layer_pattern[:10] == (0, 0, 1, 1, 1, 0, 1, 1, 1, 0)
    assert (whole.window_layers, whole.full_layers) == (33, 13)
    full, win = whole.kind(False), whole.kind(True)
    assert (full.heads, full.kv_lora, full.dn, full.dr, full.row_width) \
        == (128, 512, 128, 64, 640)
    assert (win.heads, win.kv_lora, win.dn, win.dr, win.row_width) \
        == (64, 1024, 192, 64, 1152)
    assert (full.rescale, full.gated, win.rescale, win.gated) == (True,) * 4
    assert dots.SLOTS.row_kinds(whole) == {
        "full": (13, None), "index": (13, None), "ring": (33, 513)}
    assert dots.SLOTS.rows_state is False
    assert dots.SLOTS.step_counters[-1] == "selected_rows"
    with pytest.raises(ValueError, match="layer_pattern"):
        dots.DotsConfig(n_layers=3, layer_pattern=(0, 1))


@only("dots")
def test_dots_init_params_draws_this_blocks_leaves(block, model):
    """The indexer's leaves in the full layers alone, a gate a head, the
    shared expert, and the family's count of parameters."""
    cfg, params = model
    full, win = params["layers"][1]["attn"], params["layers"][2]["attn"]
    index = {"w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw"}
    assert index <= set(full) and not index & set(win)
    assert full["w_iq"].shape == (32, 4 * 16) and full["w_iw"].shape == (64, 4)
    assert full["w_gate"].shape == (64, 4) and win["w_gate"].shape == (64, 2)
    assert full["w_kvb"].shape == (16, 4 * 32)
    assert win["w_kvb"].shape == (32, 2 * 40)
    assert "shared_gate" in params["layers"][1]["mlp"]
    assert set(params["layers"][0]["mlp"]) == {"w_gate", "w_up", "w_down"}
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == block.fam.num_params(block.m)


def _program_sets(cfg, params, tokens, prompt: int):
    """Layer 0's selected sets as the PROGRAM makes them for ``tokens``
    [T]: the first ``prompt`` rows by the prefill's way (two segments:
    ``dsa.index_scores`` at an offset over the rows so far, ``select``),
    the rest by the step's way (one row against the stack, ``select``).
    -> [T, T] bool."""
    p = params["layers"][0]
    t = len(tokens)
    x = rms_norm(params["embed"][jnp.asarray(tokens)][None], p["attn_norm"],
                 cfg.rms_eps)
    at = jnp.arange(t, dtype=jnp.int32)[None]
    k = cfg.kind(False)
    rotation = k.rotation(at)
    *_, c_q = dots._mla_inputs(cfg, k, p["attn"], x, rotation)
    q_i, k_i, w = dots._index_inputs(cfg, p["attn"], x, c_q, rotation)
    out = np.zeros((t, t), bool)
    seg = prompt // 2
    for start in (0, seg):
        rows = slice(start, start + seg)
        keys = k_i.at[:, start + seg:].set(0)  # (not written yet)
        scores = dsa.index_scores(q_i[:, rows], w[:, rows], keys, start)
        valid = jnp.arange(t)[None, :] <= at[0, rows, None]
        out[rows] = np.asarray(dsa.select(scores, valid[None],
                                          cfg.index_topk)[0])
    for pos in range(prompt, t):
        scores = dsa.index_scores_xla(q_i[:, pos:pos + 1], w[:, pos:pos + 1],
                                      k_i)[:, 0]
        valid = jnp.arange(t)[None, :] <= pos
        out[pos] = np.asarray(dsa.select(scores, valid, cfg.index_topk)[0])
    return out


@only("dots")
def test_dots_program_and_reference_select_the_same_sets(block, model,
                                                         served):
    """Layer 0 (its input is the embedding on both sides) over the served
    sequence's first 64 rows: the prefill's way across a segment boundary
    at row 16, the step's way from row 32 on, across the row where a
    stream first holds more than ``index_topk`` rows (row 8): the sets
    are the reference's (a stable full argsort of its own scores), row
    for row; a row with no more than ``index_topk`` earlier rows reads
    them all, which makes the layer plain causal MLA there."""
    cfg, params = model
    seq, _, _, masks = served
    assert len(masks) == cfg.full_layers
    want = np.asarray(masks[0][0])[:64, :64]
    got = _program_sets(cfg, params, np.asarray(seq[:64]), prompt=32)
    np.testing.assert_array_equal(got, want)
    causal = np.tril(np.ones((64, 64), bool))
    np.testing.assert_array_equal(want[:block.topk], causal[:block.topk])
    assert (want.sum(1) == np.minimum(np.arange(64) + 1, block.topk)).all()
    assert not (want & ~causal).any()


# ----------------------------------------------- GLM-5.2's block alone


def test_glm_configuration_carries_the_pattern_and_the_share_groups():
    cfg = BLOCKS["glm_dsa"].cfg()
    assert (cfg.index_layers, cfg.moe_layers) == (2, 4)
    assert cfg.share_groups == ((0,), (1, 2, 3, 4))
    assert [cfg.index_stack(i) for i in range(5)] == [0, 1, None, None, None]
    whole = glm_dsa.GlmDsaConfig()
    # the published list: three leading indexer layers, then one in four
    assert whole.indexer_layers[:11] == (1, 1, 1, 0, 0, 0, 1, 0, 0, 0, 1)
    assert (whole.index_layers, whole.n_layers) == (21, 78)
    assert whole.share_groups[:4] == ((0,), (1,), (2, 3, 4, 5), (6, 7, 8, 9))
    assert whole.share_groups[-1] == (74, 75, 76, 77)
    k = whole.mla
    assert (k.heads, k.q_lora, k.kv_lora, k.dn, k.dr, k.dv, k.row_width) \
        == (64, 2048, 512, 192, 64, 256, 640)
    # the kind says so; the configuration states neither as a field
    assert (k.rescale, k.gated) == (False, False)
    assert not hasattr(whole, "lora_rescale")
    assert not hasattr(whole, "gated_attention")
    assert glm_dsa.SLOTS.row_kinds(whole) == {
        "latent": (78, None), "index": (21, None)}
    assert glm_dsa.SLOTS.rows_state is False
    assert glm_dsa.SLOTS.step_counters[-2:] == ("selected_rows",
                                                "attended_rows")
    with pytest.raises(ValueError, match="EARLIER"):
        glm_dsa.GlmDsaConfig(n_layers=3, indexer_layers=(0, 1, 0))
    with pytest.raises(ValueError, match="indexer_layers"):
        glm_dsa.GlmDsaConfig(n_layers=3, indexer_layers=(1, 0))


@only("glm_dsa")
def test_glm_shared_layer_owns_no_indexer_leaf_and_no_index_rows(block,
                                                                 model):
    """The indexer's leaves in the indexer layers alone, no gate, the
    shared expert, the family's count of parameters; the slot's index
    stack has a layer an INDEXER layer, its latent stack one a layer."""
    cfg, params = model
    index = {"w_iq", "w_ik", "ik_norm", "ik_bias", "w_iw"}
    for i, p in enumerate(params["layers"]):
        assert bool(index & set(p["attn"])) == cfg.indexes(i), i
        assert index <= set(p["attn"]) or not index & set(p["attn"])
        assert "w_gate" not in p["attn"]
    own = params["layers"][1]["attn"]
    assert own["w_iq"].shape == (32, 2 * 16) and own["w_iw"].shape == (64, 2)
    assert own["w_qb"].shape == (32, 4 * 32)
    assert own["w_kvb"].shape == (16, 4 * 56) and own["wo"].shape == (128, 64)
    assert "shared_gate" in params["layers"][1]["mlp"]
    assert set(params["layers"][0]["mlp"]) == {"w_gate", "w_up", "w_down"}
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == block.fam.num_params(block.m)
    state = glm_dsa.SLOTS.init_state(cfg, 3, 64)
    assert state["lat"].shape == (5, 3, 64, 128)
    assert state["idx"].shape == (2, 3, 64, 16)
    assert glm_dsa.SLOTS.state_bytes(state) == {
        kind: 3 * n for kind, n in
        block.fam.state_bytes_per_slot(block.m, 64, 4).items()}


@only("glm_dsa")
def test_glm_forward_is_the_references_logits_and_short_rows_read_every_row(
        block, model, served):
    """Whole sequences (seven segments of 16, ``live=None``): the
    reference's logits. The sets the reference's five layers read: a
    shared layer's is THE ARRAY its indexer layer made; a row with no
    more than ``index_topk`` earlier rows reads them all, which makes
    every layer plain causal MLA there; and a sequence no longer than
    ``index_topk`` is plain causal MLA in every layer, bit for bit in
    the reference (the indexer asked for every row gives the same
    logits)."""
    cfg, params = model
    seq, _, want, sets = served
    ref, m, topk = block.ref, block.m, block.topk
    toks = jnp.asarray([seq[:96]])
    got = jax.jit(lambda p, t: glm_dsa.forward(p, t, cfg))(params, toks)
    assert float(jnp.abs(got[0] - want[:96]).max()) < F32_TOL
    assert len(sets) == 5
    assert sets[2] is sets[1] and sets[3] is sets[1] and sets[4] is sets[1]
    assert sets[0] is not sets[1]
    assert not np.array_equal(np.asarray(sets[0]), np.asarray(sets[1]))
    n = len(seq)
    for s in (sets[0], sets[1]):
        s = np.asarray(s[0])
        assert s.shape == (n, topk)
        for t in range(topk):  # (n: an empty place)
            assert sorted(s[t]) == list(range(t + 1)) + [n] * (topk - 1 - t)
        assert (s[topk:] < n).all() and (s <= np.arange(n)[:, None]).sum() \
            == sum(min(t + 1, topk) for t in range(n))
        assert all(len(set(row)) == topk for row in s[topk:])
    short = toks[:, :topk]
    np.testing.assert_array_equal(
        np.asarray(ref.forward(params, short, m)),
        np.asarray(ref.forward(params, short, {**m, "index_topk": 10**6})))
