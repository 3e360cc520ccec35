"""The ninth block, ``models/glm_dsa.py`` (GLM-5.2's language model: MLA
in every layer over the rows a learned indexer chooses, the choice made
in the layers that own an indexer and read by the layers behind them),
against its plain float32 reference
(``benchmark/families/glm_moe_dsa.reference.py``, which hands a
selection on as a set of INDICES) on seeded weights at a tiny size, on
the CPU: the cell's five-layer pattern, ``index_topk`` 8 of up to 100
rows, keys 24 + 8 wide beside values of 32.

- the engine's path (the grouped, segmented prefill into a slot's two
  stacks, then 60 ragged steps that hand one bias to four layers) gives
  the reference's logits, and so do whole sequences;
- a shared layer owns no indexer leaf and the slot no index stack for
  it; the sets every layer read are the reference's, a shared layer's
  the array its indexer layer made;
- a reused slot shows nothing of its last stream; ``RaggedDecoder``
  serves the reference's tokens in bf16 and its spans carry the two
  kinds of rows, ``selected_rows`` and ``attended_rows``.

What each mechanism is worth, who reads whose selection and the shares
of an expert layer are in ``test_glm_dsa_mechanisms.py``, the kernels at
this block's widths in ``test_dsa_ops_glm.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import decode_from, live_kv_case, prefill_slot
from benchmark import manifest
from ray_tpu.models import glm_dsa, moe
from ray_tpu.models.decode_engine import RaggedDecoder

# float32 on both sides, the same products in another order (absorbed
# against unabsorbed, a head at a time against all at once): readings of
# 2e-6 to 3e-5 on logits that spread by 0.8; a mechanism left out moves
# them by 1e-2 and more (test_glm_dsa_mechanisms.py)
F32_TOL = 1e-4

FAM = manifest.family("glm_moe_dsa")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
TOPK = M["index_topk"]
PROMPT, STEPS = 40, 60


def _cfg(**kw):
    """The family's own way to the program's configuration."""
    return FAM.build({**M, **kw}, max_seq_len=256, remat=False).cfg


@pytest.fixture(scope="module", autouse=True)
def segments_of_16():
    """Every bucket of this file in segments of 16 rows (the engine's
    programs are cached by cfg alone: set once, cleared once)."""
    was, moe.SEGMENT_ROWS = moe.SEGMENT_ROWS, 16
    jax.clear_caches()
    yield
    moe.SEGMENT_ROWS = was
    jax.clear_caches()


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, glm_dsa.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(seed: int, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


def _empty(cfg):
    """(the prefill program is donated its state and tokens)"""
    return glm_dsa.SLOTS.init_state(cfg, 2, 256), jnp.zeros((2,), jnp.int32)


@pytest.fixture(scope="module")
def served(model):
    """The 40-token prompt through the engine's prefill program (a
    128-row bucket, three of eight segments live, the second group's
    four layers in one scan) and 60 greedy steps of the ragged step ->
    (prompt + tokens fed [100], float32 logits of the 60 steps, the
    reference's logits [100, V], the sets its five layers read)."""
    cfg, params = model
    assert glm_dsa.SLOTS.prefill_segments(cfg, 128) == 8
    state, cur = prefill_slot(cfg, params, *_empty(cfg), 1,
                              _tokens(5, PROMPT))
    assert int(state["pos"][1]) == PROMPT
    fed, got = decode_from(glm_dsa.SLOTS, cfg, params, state, cur, 1, STEPS)
    seq = list(_tokens(5, PROMPT)) + fed
    sets = []
    h = REF.hidden(params, jnp.asarray([seq]), M, sets)
    want = REF._head(h, params["final_norm"], params["lm_head"],
                     M["rms_eps"])
    return seq, got, np.asarray(want[0]), sets


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_pattern_and_the_share_groups():
    cfg = _cfg()
    assert (cfg.index_layers, cfg.moe_layers) == (2, 4)
    assert cfg.share_groups == ((0,), (1, 2, 3, 4))
    assert [cfg.index_stack(i) for i in (0, 1)] == [0, 1]
    whole = glm_dsa.GlmDsaConfig()
    # the published list: three leading indexer layers, then one in four
    assert whole.indexer_layers[:11] == (1, 1, 1, 0, 0, 0, 1, 0, 0, 0, 1)
    assert (whole.index_layers, whole.n_layers) == (21, 78)
    assert whole.share_groups[:4] == ((0,), (1,), (2, 3, 4, 5), (6, 7, 8, 9))
    assert whole.share_groups[-1] == (74, 75, 76, 77)
    k = whole.mla
    assert (k.heads, k.q_lora, k.kv_lora, k.dn, k.dr, k.dv, k.row_width) \
        == (64, 2048, 512, 192, 64, 256, 640)
    assert glm_dsa.SLOTS.row_kinds(whole) == {
        "latent": (78, None), "index": (21, None)}
    assert glm_dsa.SLOTS.rows_state is False
    assert glm_dsa.SLOTS.step_counters[-2:] == ("selected_rows",
                                                "attended_rows")
    with pytest.raises(ValueError, match="EARLIER"):
        glm_dsa.GlmDsaConfig(n_layers=3, indexer_layers=(0, 1, 0))
    with pytest.raises(ValueError, match="indexer_layers"):
        glm_dsa.GlmDsaConfig(n_layers=3, indexer_layers=(1, 0))


def test_a_shared_layer_owns_no_indexer_leaf_and_no_index_rows(model):
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
        == FAM.num_params(M)
    state = glm_dsa.SLOTS.init_state(cfg, 3, 64)
    assert state["lat"].shape == (5, 3, 64, 128)
    assert state["idx"].shape == (2, 3, 64, 16)
    assert glm_dsa.SLOTS.state_bytes(state) == {
        kind: 3 * n for kind, n in
        FAM.state_bytes_per_slot(M, 64, 4).items()}


# ------------------------------------- the model, through the engine


def test_prefill_then_60_decode_steps_are_the_references_forward(served):
    """From 41 rows on every step's two indexer layers select 8 of the
    slot's rows and five layers attend them, three over a bias they did
    not make (the prefill's rows past the 8th chose theirs across
    segment boundaries, a segment's bias crossing four layers). Every
    step's logits are the reference's full forward over prompt +
    tokens."""
    seq, got, want, _ = served
    assert seq[PROMPT] == int(want[PROMPT - 1].argmax())  # (the prefill's)
    assert np.abs(got - want[PROMPT:PROMPT + STEPS]).max() < F32_TOL


def test_forward_is_the_references_logits_and_short_rows_read_every_row(
        model, served):
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
        assert s.shape == (n, TOPK)
        for t in range(TOPK):  # (n: an empty place)
            assert sorted(s[t]) == list(range(t + 1)) + [n] * (TOPK - 1 - t)
        assert (s[TOPK:] < n).all() and (s <= np.arange(n)[:, None]).sum() \
            == sum(min(t + 1, TOPK) for t in range(n))
        assert all(len(set(row)) == TOPK for row in s[TOPK:])
    short = toks[:, :TOPK]
    np.testing.assert_array_equal(
        np.asarray(REF.forward(params, short, M)),
        np.asarray(REF.forward(params, short, {**M, "index_topk": 10**6})))


def test_a_reused_slot_shows_nothing_of_its_last_stream(model, served):
    """The served prompt and 12 steps in a slot that a 100-token prompt
    filled before (latent rows of five layers and index keys of two;
    seven segments live for the short one's three): the logits are the
    fresh slot's bit for bit, the rows behind the short prompt's
    segments are zeros, and the inactive slot beside it keeps its
    position."""
    cfg, params = model
    _, fresh, _, _ = served
    used, cur = prefill_slot(cfg, params, *_empty(cfg), 1, _tokens(9, 100))
    assert all(np.asarray(used[name][:, 1]).any() for name in ("lat", "idx"))
    assert all(np.asarray(used["lat"][i, 1]).any() for i in range(5))
    state, cur = prefill_slot(cfg, params, used, cur, 1, _tokens(5, PROMPT))
    assert not np.asarray(state["lat"][:, 1, 48:128]).any()
    assert not np.asarray(state["idx"][:, 1, 48:128]).any()
    assert int(state["pos"][0]) == 0
    _, reused = decode_from(glm_dsa.SLOTS, cfg, params, state, cur, 1, 12)
    np.testing.assert_array_equal(reused, fresh[:12])


@pytest.mark.parametrize("case", ["whole_bucket", "stale_1e4", "lowered"])
def test_a_layers_k_and_v_are_made_for_the_live_rows_alone(
        model, monkeypatch, case):
    """``dots._live_kv`` in all five layers' prefill, four of them in one
    scan body (``_segments.live_kv_case`` says what each case holds)."""
    cfg, params = model
    live_kv_case(case, monkeypatch, glm_dsa, cfg.mla, cfg.n_layers, cfg,
                 params, _tokens(11, 1, 64))


def test_submit_and_pump_serve_the_references_tokens_in_bf16(model):
    """``RaggedDecoder`` (submit -> pump) on the model in bfloat16: three
    streams of 64 positions over two slots, so a slot is reused and the
    streams sit at ragged positions, each decoded past ``index_topk``
    rows; every stream's tokens pass the reference's
    ``check_served_tokens``. The spans carry the two kinds of rows,
    their bytes a row, ``selected_rows`` (two layers') and
    ``attended_rows`` (five layers' for those two selections)."""
    from ray_tpu._private import flight_recorder as fr

    # (32 rows chosen of up to 64: where bf16 activations flip a set at
    # the threshold, a thirty-second of a row's attention moves, not the
    # eighth that the float32 tests' 8 rows would)
    m = {**M, "index_topk": 32}
    cfg = _cfg(dtype="bfloat16", index_topk=32)
    params = glm_dsa.init_params(cfg, jax.random.PRNGKey(8))
    seen = fr._get().recorded  # (the ring is bounded: count, not place)
    eng = RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                        prompt_buckets=(64,), name="glm-test")
    asked = [(_tokens(20 + n, n), 64 - n) for n in (13, 40, 24)]
    sids = [eng.submit(p, out) for p, out in asked]
    eng.drain()
    for sid, (p, out) in zip(sids, asked):
        toks = list(eng.finished[sid].tokens)
        assert len(toks) == out
        check = REF.check_served_tokens(params, list(p), toks, m)
        assert check["wrong"] == 0 and check["agree"] > out // 2, check
    st = eng.stats()
    per_slot = FAM.state_bytes_per_slot(M, 96, 2)
    assert st["state_bytes"] == {kind: 2 * n for kind, n in per_slot.items()}
    by_kind = st["attn_live_rows_by_kind"]
    assert 0 < by_kind["latent"] == by_kind["index"]
    with pytest.raises(ValueError, match="GlmDsaConfig"):
        RaggedDecoder(params, cfg, slots=2, max_len=64, spec_depth=2)
    spans = list(fr._get().ring)[seen - fr._get().recorded:]
    init = [s["attrs"] for s in spans if s["name"] == "engine.state_init"
            and s["attrs"].get("engine") == "glm-test"][-1]
    rows = FAM.row_bytes(M, 2)
    assert init["slots"] == 2
    for kind, layers in (("latent", 5), ("index", 2)):
        assert init[f"{kind}_bytes"] == 2 * per_slot[kind]
        assert init[f"{kind}_layers"] == layers
        assert init[f"{kind}_row_bytes"] == rows[kind]
    back = [s["attrs"] for s in spans if s["name"] == "engine.readback"
            and "attended_rows" in s["attrs"]]
    # two streams past index_topk rows: 32 rows each in two indexer
    # layers, handed to five attentions
    assert back and max(a["selected_rows"] for a in back) == 2 * 2 * 32
    assert max(a["attended_rows"] for a in back) == 2 * 5 * 32
    assert all(2 * a["attended_rows"] == 5 * a["selected_rows"]
               for a in back)
    assert {"live_rows", "live_rows_latent", "live_rows_index",
            "held_assignments"} <= back[-1].keys()
    assert back[-1]["live_rows_latent"] == back[-1]["live_rows"]
    pre = [s["attrs"] for s in spans if s["name"] == "engine.prefill"]
    assert {(a["segments"], a["live_segments"]) for a in pre} \
        == {(4, 1), (4, 3), (4, 2)}
