"""The eighth block, ``models/dots.py`` (dots3-note-prev's language model:
full layers whose MLA reads the rows a learned indexer chooses beside
window layers with latents of their own and a ring of latent rows, a
gate a head, a shared expert), against its plain float32 reference
(``benchmark/families/dots3_note.reference.py``) on seeded weights at a
tiny size, on the CPU: ``index_topk`` 8 of up to 240 rows, a window of 9.

- the engine's path (the segmented prefill into a slot's three stacks,
  then 200 ragged steps: the chosen rows gathered, the rings wrapped
  twenty times) gives the reference's logits, which attends UNABSORBED
  and selects by a sort of its own;
- the selected SETS of program and reference are equal, row for row;
- a reused slot shows nothing of its last stream; ``RaggedDecoder``
  serves the reference's tokens in bf16 and its spans carry the three
  kinds of rows and ``selected_rows``.

Every test runs the bucket of 128 rows in eight segments of 16 and one
shape of state, so that the file compiles each program once; what each
mechanism is worth, the ring and the shares are in
``test_dots_mechanisms.py``, the kernels in ``test_dsa_ops.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import decode_from, live_kv_case, prefill_slot
from benchmark import manifest
from ray_tpu.models import dots, moe
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import dsa
from ray_tpu.ops.norms import rms_norm

# float32 on both sides, the same products in another order (absorbed
# against unabsorbed, a head at a time against all at once): readings of
# 2e-6 to 2e-5 on logits that spread by 0.8; a mechanism left out moves
# them by 6e-3 and more (test_dots_mechanisms.py)
F32_TOL = 1e-4

FAM = manifest.family("dots3_note")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
W, TOPK = M["sliding_window"], M["index_topk"]
PROMPT = 40  # the engine test's prompt; 200 steps behind it


def _cfg(**kw):
    m = {**M, **kw}
    held = m.pop("held_experts")
    return dots.DotsConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_pattern": tuple(m["layer_pattern"])}, max_seq_len=256,
        prefill_head_groups=2)


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
    return cfg, dots.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(seed: int, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


def _empty(cfg):
    """(the prefill program is donated its state and tokens)"""
    return dots.SLOTS.init_state(cfg, 2, 256), jnp.zeros((2,), jnp.int32)


@pytest.fixture(scope="module")
def served(model):
    """The 40-token prompt through the engine's prefill program (a
    128-row bucket, three of eight segments live) and 200 greedy steps
    of the ragged step -> (prompt + tokens fed [240], float32 logits of
    the 200 steps, the reference's logits [240, V], its full layers'
    masks)."""
    cfg, params = model
    assert dots.SLOTS.prefill_segments(cfg, 128) == 8
    state, cur = prefill_slot(cfg, params, *_empty(cfg), 1,
                              _tokens(5, PROMPT))
    assert int(state["pos"][1]) == PROMPT
    fed, got = decode_from(dots.SLOTS, cfg, params, state, cur, 1, 200)
    seq = list(_tokens(5, PROMPT)) + fed
    masks = []
    h = REF.hidden(params, jnp.asarray([seq]), M, masks)
    want = REF._head(h, params["final_norm"], params["lm_head"],
                     M["rms_eps"])
    return seq, got, np.asarray(want[0]), masks


# ------------------------------------------------------- configuration


def test_the_configuration_carries_both_kinds_widths_and_the_pattern():
    cfg = _cfg()
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
    assert dots.SLOTS.row_kinds(whole) == {
        "full": (13, None), "index": (13, None), "ring": (33, 513)}
    assert dots.SLOTS.rows_state is False
    assert dots.SLOTS.step_counters[-1] == "selected_rows"
    with pytest.raises(ValueError, match="layer_pattern"):
        dots.DotsConfig(n_layers=3, layer_pattern=(0, 1))


def test_init_params_draws_this_blocks_leaves(model):
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
        == FAM.num_params(M)


# ------------------------------------- the model, through the engine


def test_prefill_then_200_decode_steps_are_the_references_forward(served):
    """From 41 rows on every full layer's step selects 8 of its rows,
    gathers and attends them (the prefill's rows past the 8th chose
    theirs across segment boundaries), and the rings of 9 rows wrap
    twenty times. Every step's logits are the reference's full forward
    over prompt + tokens."""
    seq, got, want, _ = served
    assert seq[PROMPT] == int(want[PROMPT - 1].argmax())  # (the prefill's)
    assert np.abs(got - want[PROMPT:PROMPT + 200]).max() < F32_TOL


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
    rotation = dots._rotation(cfg, at, False)
    *_, c_q = dots._mla_inputs(cfg, cfg.kind(False), p["attn"], x, rotation)
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

def test_program_and_reference_select_the_same_sets(model, served):
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
    np.testing.assert_array_equal(want[:TOPK], causal[:TOPK])
    assert (want.sum(1) == np.minimum(np.arange(64) + 1, TOPK)).all()
    assert not (want & ~causal).any()


def test_a_reused_slot_shows_nothing_of_its_last_stream(model, served):
    """The served prompt and 12 steps in a slot that a 100-token prompt
    filled before (latent rows, index keys and rings; seven segments
    live for the short one's three): the logits are the fresh slot's bit
    for bit, the rows behind the short prompt's segments are zeros, and
    the inactive slot beside it keeps its position."""
    cfg, params = model
    _, fresh, _, _ = served
    used, cur = prefill_slot(cfg, params, *_empty(cfg), 1, _tokens(9, 100))
    assert all(np.asarray(used[name][:, 1]).any()
               for name in ("lat", "idx", "ring"))
    state, cur = prefill_slot(cfg, params, used, cur, 1, _tokens(5, PROMPT))
    assert not np.asarray(state["lat"][:, 1, 48:128]).any()
    assert not np.asarray(state["idx"][:, 1, 48:128]).any()
    assert int(state["pos"][0]) == 0
    _, reused = decode_from(dots.SLOTS, cfg, params, state, cur, 1, 12)
    np.testing.assert_array_equal(reused, fresh[:12])


@pytest.mark.parametrize("case", ["whole_bucket", "stale_1e4", "lowered"])
def test_a_full_layers_k_and_v_are_made_for_the_live_rows_alone(
        model, monkeypatch, case):
    """``dots._live_kv`` in the two full layers' prefill
    (``_segments.live_kv_case`` says what each case holds)."""
    cfg, params = model
    live_kv_case(case, monkeypatch, dots, cfg.kind(False), cfg.full_layers,
                 cfg, params, _tokens(11, 1, 64))


def test_submit_and_pump_serve_the_references_tokens_in_bf16(model):
    """``RaggedDecoder`` (submit -> pump) on the model in bfloat16: three
    streams of 64 positions over two slots, so a slot is reused and the
    streams sit at ragged positions, each decoded past ``index_topk``
    rows and two wraps of its rings; every stream's tokens pass the
    reference's ``check_served_tokens``. The spans carry the three
    kinds of rows, their bytes a row and ``selected_rows``."""
    from ray_tpu._private import flight_recorder as fr

    # (32 rows chosen of up to 64: where bf16 activations flip a set at
    # the threshold, a thirty-second of a row's attention moves, not the
    # eighth that the float32 tests' 8 rows would)
    m = {**M, "index_topk": 32}
    cfg = _cfg(dtype="bfloat16", index_topk=32)
    params = dots.init_params(cfg, jax.random.PRNGKey(8))
    seen = fr._get().recorded  # (the ring is bounded: count, not place)
    eng = RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                        prompt_buckets=(64,), name="dots-test")
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
    assert 0 < by_kind["ring"] < by_kind["full"] == by_kind["index"]
    with pytest.raises(ValueError, match="ring of rows"):
        RaggedDecoder(params, cfg, slots=2, max_len=64, spec_depth=2)
    spans = list(fr._get().ring)[seen - fr._get().recorded:]
    init = [s["attrs"] for s in spans if s["name"] == "engine.state_init"
            and s["attrs"].get("engine") == "dots-test"][-1]
    rows = FAM.row_bytes(M, 2)
    for kind, layers in (("full", 2), ("index", 2), ("ring", 3)):
        assert init[f"{kind}_bytes"] == 2 * per_slot[kind]
        assert init[f"{kind}_layers"] == layers
        assert init[f"{kind}_row_bytes"] == rows[kind]
    back = [s["attrs"] for s in spans if s["name"] == "engine.readback"
            and "selected_rows" in s["attrs"]]
    # two streams past index_topk rows: two full layers x 32 rows each
    assert back and max(a["selected_rows"] for a in back) == 2 * 2 * 32
    assert {"live_rows_full", "live_rows_index", "live_rows_ring",
            "held_assignments"} <= back[-1].keys()
    assert back[-1]["live_rows_ring"] <= W < back[-1]["live_rows_full"]
    pre = [s["attrs"] for s in spans if s["name"] == "engine.prefill"]
    assert {(a["segments"], a["live_segments"]) for a in pre} \
        == {(4, 1), (4, 3), (4, 2)}
