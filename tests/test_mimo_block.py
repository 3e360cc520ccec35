"""The sixth block, ``models/mimo.py`` (MiMo-V2.5's language model: window
layers with a learned sink beside full layers, kv heads by kind, keys
wider than values, a part of a head rotated, no shared expert), against
its plain float32 reference (``benchmark/families/mimo_v2.reference.py``)
on seeded weights at a tiny size, on the CPU:

- whole sequences (``forward``) and the engine's path (the segmented
  prefill into a slot's four stacks, then ragged steps past a wrapped
  ring and across a segment boundary) give the reference's logits;
- each mechanism the configuration names is load-bearing: the same
  comparison FAILS with the sink, the value scale, the partial rotation,
  the window kind's own theta or its own kv heads left out;
- sixteen shares of an expert layer add up to the uncut layer (there is
  no shared expert to count once);
- both attention kernels in the Pallas interpreter at the published head
  widths (keys 192, values 128) with a sink, against their XLA bodies.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import (  # noqa: F401 (segments_of_16: a fixture)
    pallas_calls, segments_of_16, short_prompt_in_a_reused_slot)
from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import mimo, moe
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops.attention import attend_rows
from ray_tpu.ops.flash_attention import flash_attention, flash_fwd

F32_TOL = 1e-4

FAM = manifest.family("mimo_v2")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
W = M["sliding_window"]


def _cfg(**kw):
    m = {**M, **kw}
    held = m.pop("held_experts")
    return mimo.MimoConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_pattern": tuple(m["layer_pattern"]),
        "moe_pattern": tuple(m["moe_pattern"])}, max_seq_len=256)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, mimo.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_pattern_as_data():
    cfg = _cfg()
    assert (cfg.window_layers, cfg.full_layers, cfg.moe_layers) == (3, 2, 4)
    assert [cfg.stack_index(i) for i in range(5)] == [0, 0, 1, 1, 2]
    assert (cfg.row_widths(False), cfg.row_widths(True)) \
        == ((2 * 24, 2 * 16), (4 * 24, 4 * 16))
    # the published pattern is the default: layer 0 full, four window
    # layers, a full one, then five window layers to one full
    whole = mimo.MimoConfig()
    assert whole.layer_pattern[:12] == (0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0)
    assert (whole.window_layers, whole.full_layers) == (39, 9)
    assert whole.moe_pattern == (0,) + (1,) * 47
    assert (whole.row_widths(False), whole.row_widths(True)) \
        == ((768, 512), (1536, 1024))
    with pytest.raises(ValueError, match="layer_pattern"):
        mimo.MimoConfig(n_layers=3, layer_pattern=(0, 1))
    with pytest.raises(ValueError, match="layer_pattern"):
        mimo.MimoConfig(n_layers=1, layer_pattern=(2,))


def test_init_params_draws_this_blocks_leaves(model):
    """The two kinds' fused projections at their own widths, a sink a
    head in the window layers alone, no shared expert's leaves, and the
    family's count of parameters."""
    cfg, params = model
    full, win = params["layers"][0]["attn"], params["layers"][1]["attn"]
    assert full["w_qkv"].shape == (48, 8 * 24 + 2 * 24 + 2 * 16)
    assert win["w_qkv"].shape == (48, 8 * 24 + 4 * 24 + 4 * 16)
    assert full["wo"].shape == win["wo"].shape == (8 * 16, 48)
    assert "sink" not in full and win["sink"].shape == (8,)
    assert win["sink"].dtype == jnp.float32
    assert set(params["layers"][1]["mlp"]) == {
        "router", "router_bias", "w_gate", "w_up", "w_down"}
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == FAM.num_params(M)


# ------------------------------------------- the model, whole sequences


def test_forward_is_the_references_logits(model):
    cfg, params = model
    toks = np.random.RandomState(3).randint(1, 256, (2, 40)).astype(np.int32)
    got = mimo.forward(params, jnp.asarray(toks), cfg)
    want = REF.forward(params, jnp.asarray(toks), M)
    assert float(jnp.abs(got - want).max()) < F32_TOL


def _without(what: str, cfg, params):
    """The program with one of the configuration's mechanisms left out."""
    if what == "sink":
        layers = [{**p, "attn": {k: v for k, v in p["attn"].items()
                                 if k != "sink"}} for p in params["layers"]]
        return cfg, {**params, "layers": layers}
    if what == "value_scale":
        return dataclasses.replace(cfg, value_scale=1.0), params
    if what == "partial_rotation":  # the whole head rotated
        return dataclasses.replace(cfg, rotary_dim=cfg.head_dim), params
    if what == "window_theta":  # the full layers' theta everywhere
        return dataclasses.replace(
            cfg, window_rope_theta=cfg.rope_theta), params
    assert what == "window_kv_heads"
    # the window layers read as if they had the full layers' kv heads:
    # their first n_kv_heads key and value heads, shared by twice as
    # many query heads each
    hq, hkv, hw = cfg.n_heads, cfg.n_kv_heads, cfg.window_kv_heads
    dk, dv = cfg.head_dim, cfg.v_head_dim
    cols = np.r_[0:hq * dk + hkv * dk,
                 (hq + hw) * dk:(hq + hw) * dk + hkv * dv]
    layers = [{**p, "attn": {**p["attn"], "w_qkv": p["attn"]["w_qkv"][
        :, cols]}} if cfg.windowed(i) else p
        for i, p in enumerate(params["layers"])]
    return dataclasses.replace(cfg, window_kv_heads=hkv), \
        {**params, "layers": layers}


@pytest.mark.parametrize("what", ["sink", "value_scale", "partial_rotation",
                                  "window_theta", "window_kv_heads"])
def test_the_comparison_fails_without(what, model):
    """Each mechanism moves the logits by far more than the tolerance
    that the whole program meets."""
    cfg, params = model
    toks = jnp.asarray(np.random.RandomState(4).randint(
        1, 256, (1, 40)).astype(np.int32))
    want = REF.forward(params, toks, M)
    assert float(jnp.abs(mimo.forward(params, toks, cfg) - want).max()) \
        < F32_TOL
    cfg_off, params_off = _without(what, cfg, params)
    off = mimo.forward(params_off, toks, cfg_off)
    assert float(jnp.abs(off - want).max()) > 100 * F32_TOL, what


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps, spare_slot: int = 1):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state (``spare_slot`` stays empty and
    inactive), then ``steps`` greedy steps of the model's ragged step
    with every slot at its own position. -> for each prompt (its tokens
    followed by the generated ones, float32 logits [steps, V] from the
    last prompt position on)."""
    slots, max_len = len(prompts) + 1, 96
    state = mimo.SLOTS.init_state(cfg, slots, max_len)
    cur = jnp.zeros((slots,), jnp.int32)
    seqs, rows = {}, {}
    free = [s for s in range(slots) if s != spare_slot]
    for slot, p in zip(free[::-1], prompts):
        bucket = 16 if len(p) <= 16 else 64
        row = np.zeros((1, bucket), np.int32)
        row[0, :len(p)] = p
        state, cur, *_ = de._prefill_batch_into_slots(
            params, row, np.array([len(p)], np.int32),
            np.array([slot], np.int32), np.array([0], np.uint32),
            np.array([0.0], np.float32), np.array([1.0], np.float32),
            state, cur, cfg)
        seqs[slot], rows[slot] = list(p), []
    active = jnp.asarray([s in seqs for s in range(slots)])
    step = jax.jit(functools.partial(mimo.SLOTS.step, cfg, params, None))
    tok = cur
    for _ in range(steps):
        for slot in seqs:
            seqs[slot].append(int(tok[slot]))
        rest = {k: v for k, v in state.items() if k != "pos"}
        logits, rest, *_ = step(tok, rest, state["pos"], active)
        state = {**rest, "pos": state["pos"] + active}
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for slot in seqs:
            rows[slot].append(np.asarray(logits[slot]))
    assert int(state["pos"][spare_slot]) == 0
    return [(seqs[s], np.stack(rows[s])) for s in seqs]


# prompts shorter than the window (5), equal to it (8), longer (23, 41)
PROMPTS = (5, 8, 23, 41)


@pytest.mark.parametrize("segment", [2048, 16])
def test_prefill_then_ragged_decode_is_the_references_forward(
        segment, monkeypatch):
    """Four slots at different positions and a fifth inactive among
    them, 20 decoded positions each (a window of 8: two wraps of every
    ring and more); with ``segment`` 16 the 64-row bucket's prefill runs
    in four segments, so the 23- and 41-token prompts' attention crosses
    segment boundaries (a window layer's band and a full layer's earlier
    rows both reach into the segment before). The logits of every
    decoded position are the reference's full forward over prompt +
    tokens."""
    monkeypatch.setattr(moe, "SEGMENT_ROWS", segment)
    cfg = _cfg()
    assert mimo.SLOTS.prefill_segments(cfg, 64) == 64 // min(segment, 64)
    params = mimo.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, n).astype(np.int32) for n in PROMPTS]
    steps = 20
    assert steps > 2 * W
    for (seq, got), p in zip(_ragged_logits(cfg, params, prompts, steps),
                             prompts):
        want = np.asarray(REF.forward(params, jnp.asarray([seq]), M)[0])
        # step j's logits are the position's after len(p) + j tokens
        assert np.abs(got - want[len(p):len(p) + len(got)]).max() < F32_TOL


@pytest.mark.parametrize("lens, live_segments", [((40, 70), 5), ((64,), 4),
                                                 ((128, 3), 8)])
def test_a_call_without_its_dead_segments_leaves_what_is_read_bit_for_bit(
        lens, live_segments, segments_of_16, model):
    """A 128-row bucket in eight segments of 16 with ``live`` = the
    longest prompt's rows (traced, as ``SLOTS.prefill`` passes it)
    against the same call with every segment run: the first tokens and
    their logprobs, every window layer's ring, each prompt's own rows of
    the full layers, the stream's real rows and the loads are the same
    bits; a full layer's rows and the stream's in the segments not run
    are zeros."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (len(lens), 128), 1, 256)
    lens = jnp.array(lens, jnp.int32)
    f = len(lens)

    def call(live):
        h, rows, (loads, _) = mimo.prefill(params, toks, lens, cfg,
                                           loads=True, live=live)
        return h, rows, loads, mimo.SLOTS.first_token(
            functools.partial(moe.logits, cfg), params, h, lens,
            jnp.zeros((f,), jnp.uint32), jnp.zeros((f,), jnp.float32),
            jnp.ones((f,), jnp.float32))

    h0, rows0, loads0, first0 = jax.jit(lambda: call(None))()
    h1, rows1, loads1, first1 = jax.jit(call)(jnp.max(lens))
    np.testing.assert_array_equal(first1[0], first0[0])
    np.testing.assert_array_equal(first1[1], first0[1])
    np.testing.assert_array_equal(loads1, loads0)
    assert int(loads0.sum()) > 0
    run = live_segments * 16
    for i, (kv0, kv1) in enumerate(zip(rows0, rows1)):
        for a, b in zip(kv0, kv1):
            if cfg.windowed(i):
                assert a.shape[1] == W
                np.testing.assert_array_equal(b, a)
                continue
            for j, n in enumerate(np.asarray(lens)):
                np.testing.assert_array_equal(b[j, :n], a[j, :n])
            assert not b[:, run:].any() and b[:, :run].any()
    for j, n in enumerate(np.asarray(lens)):
        np.testing.assert_array_equal(h1[j, :n], h0[j, :n])
    assert not h1[:, run:].any() and h0[:, 112:].any()


def test_a_short_prompt_in_a_long_bucket_is_the_reference_in_a_reused_slot(
        segments_of_16, model):
    cfg, params = model
    short_prompt_in_a_reused_slot(
        mimo.SLOTS, cfg, params, lambda tokens: REF.forward(
            params, jnp.asarray([tokens]), M)[0], F32_TOL)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_submit_and_pump_serve_the_references_tokens(dtype):
    """``RaggedDecoder`` (submit -> pump) on the model: five streams
    over three slots, so slots are reused and streams sit at ragged
    positions, every one decoded past two wraps of its rings; every
    stream's tokens pass the reference's ``check_served_tokens`` and, in
    float32, are its argmax outright."""
    cfg = _cfg(dtype=dtype)
    params = mimo.init_params(cfg, jax.random.PRNGKey(8))
    eng = RaggedDecoder(params, cfg, slots=3, max_len=96, chunk_tokens=4,
                        prompt_buckets=(8, 16, 64))
    rng = np.random.RandomState(1)
    asked = [(rng.randint(1, 256, n).astype(np.int32), out)
             for n, out in ((13, 19), (7, 22), (40, 18), (3, 24), (8, 20))]
    sids = [eng.submit(p, out) for p, out in asked]
    eng.drain()
    for sid, (p, out) in zip(sids, asked):
        toks = list(eng.finished[sid].tokens)
        assert len(toks) == out
        check = REF.check_served_tokens(params, list(p), toks, M)
        assert check["wrong"] == 0, check
        if dtype == "float32":
            assert check["agree"] == out, check
    st = eng.stats()
    assert st["state_bytes"] == {
        kind: 3 * n for kind, n in
        FAM.state_bytes_per_slot(M, 96, jnp.dtype(dtype).itemsize).items()}
    assert st["moe_assignments"] > 0 and st["moe_touched_expert_steps"] > 0
    by_kind = st["attn_live_rows_by_kind"]
    assert 0 < by_kind["window"] < by_kind["full"]
    with pytest.raises(ValueError, match="ring of rows"):
        RaggedDecoder(params, cfg, slots=2, max_len=64, spec_depth=2)


def test_spans_carry_the_state_the_row_bytes_and_the_rows_by_kind(
        model, monkeypatch):
    from ray_tpu._private import flight_recorder as fr

    monkeypatch.setattr(moe, "SEGMENT_ROWS", 8)
    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=64, chunk_tokens=4,
                        prompt_buckets=(16,), name="mimo-test")
    eng.submit(np.arange(1, 13, dtype=np.int32), 8)
    eng.drain()
    spans = [s for s in fr._get().ring if s["attrs"].get("engine")
             == "mimo-test" or s["name"] in ("engine.readback",
                                             "engine.prefill")]
    init = [s for s in spans if s["name"] == "engine.state_init"][-1]["attrs"]
    assert (init["slots"], init["max_len"]) == (2, 64)
    assert (init["window_layers"], init["full_layers"]) == (3, 2)
    per_slot = FAM.state_bytes_per_slot(M, 64, 4)
    assert init["window_bytes"] == 2 * per_slot["window"]
    assert init["full_bytes"] == 2 * per_slot["full"]
    # a row's bytes by kind: kv heads x (24 + 16) x 4 B
    row = FAM.kv_row_bytes(M, 4)
    assert (init["window_row_bytes"], init["full_row_bytes"]) \
        == (row["window"], row["full"]) == (640, 320)
    pre = [s["attrs"] for s in spans if s["name"] == "engine.prefill"][-1]
    assert (pre["segments"], pre["live_segments"]) == (2, 2)
    backs = [s["attrs"] for s in spans if s["name"] == "engine.readback"
             and "live_rows_window" in s["attrs"]][-2:]
    assert [b["live_rows_full"] for b in backs] == [16, 20]
    assert [b["live_rows_window"] for b in backs] == [W, W]
    assert backs[-1]["assignments"] == M["top_k"]


# ------------------------------------------------------- the shares


def test_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the layer cut over sixteen chips by index
    of expert. Each share routes over all 16 experts and computes its
    own one; the sixteen partial results add up to the reference's layer
    with every expert held. There is no shared expert to count once."""
    whole = _cfg(held_experts=None)
    p = mimo.init_params(whole, jax.random.PRNGKey(5))["layers"][1]["mlp"]
    assert p["w_gate"].shape[0] == 16 and "shared_gate" not in p
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = REF.moe_layer(M, p, x, held=(0, 16))
    total = jnp.zeros_like(x)
    for first in range(16):
        share = {**p, **{w: p[w][first:first + 1]
                         for w in ("w_gate", "w_up", "w_down")}}
        part = moe.moe(_cfg(held_experts=(first, 1)), share, x)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                part, REF.moe_layer(M, share, x, held=(first, 1)),
                atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(moe.moe(whole, p, x), want, atol=5e-5)


# ------------------------------------------------------- the kernels


def test_packed_rows_unpack_to_the_heads():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4, 192))
    rows = da.pack_heads(x)
    assert rows.shape == (3, 5, 768)
    # every head's first 128 end to end, then every head's last 64
    np.testing.assert_array_equal(rows[..., 128:256], x[..., 1, :128])
    np.testing.assert_array_equal(rows[..., 512 + 64:512 + 128],
                                  x[..., 1, 128:])
    np.testing.assert_array_equal(da.unpack_heads(rows, 4), x)
    whole = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 128))
    np.testing.assert_array_equal(da.pack_heads(whole),
                                  whole.reshape(2, 1024))


@pytest.mark.parametrize("hkv, s, lengths", [
    # a ring of 128 rows, 8 query rows a kv head: under, at the window
    (8, 128, (0, 1, 77, 128)),
    # a full stack, 16 query rows a kv head: under, at and over it
    (4, 400, (5, 128, 0, 393))])
def test_decode_attn_at_the_published_head_widths_with_a_sink(
        hkv, s, lengths):
    """The kernel in the interpreter against its XLA body: keys 192 wide
    (packed rows) and values 128, 64 query heads, a sink a head; a slot
    of length 0 gives zeros. ``s`` 400 ends inside the last block."""
    ks = jax.random.split(jax.random.PRNGKey(hkv), 4)
    b, layers = len(lengths), 2
    q = jax.random.normal(ks[0], (b, 1, 64, 192))
    k = da.pack_heads(jax.random.normal(ks[1], (layers, b, s, hkv, 192)))
    v = jax.random.normal(ks[2], (layers, b, s, hkv * 128))
    sink = 2.0 + jax.random.normal(ks[3], (64,))
    n = jnp.asarray(lengths, jnp.int32)
    for snk in (sink, None):
        want = da.decode_attention(q, k, v, 1, n, use_kernel=False, sink=snk)
        got = da.decode_attention(q, k, v, 1, n, interpret=True, rows=128,
                                  sink=snk)
        assert got.shape == (b, 1, 64, 128)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert not np.asarray(got)[np.asarray(lengths) == 0].any()
    # the sink takes its share: with it the output is smaller
    sunk = da.decode_attention(q, k, v, 1, n, use_kernel=False, sink=sink)
    assert float(jnp.abs(sunk - want).max()) > 1e-3


# (heads, kv heads, T, offset, window, a sink, blocks or the kernel's own)
_BAND_CASES = [
    # the tests' tiny shapes: explicit 64 x 64 blocks
    (8, 2, 64, 0, 128, True, 64), (8, 2, 128, 0, 128, True, 64),
    (8, 2, 256, 0, 128, True, 64), (8, 2, 128, 256, 128, True, 64),
    (8, 2, 128, 256, 16, False, 64), (8, 2, 128, 200, 16, True, 64),
    # the published heads, the blocks the window gives: rows with fewer
    # than ``window`` keys behind them, an offset of whole blocks, and
    # one that is not (the band then touches three blocks)
    (64, 8, 256, 0, 128, True, None), (64, 8, 256, 0, 128, False, None),
    (64, 8, 256, 256, 128, True, None), (64, 8, 256, 256, 128, False, None),
    (64, 8, 256, 200, 128, True, None), (64, 8, 256, 200, 128, False, None),
    # T smaller than a block, at the start and behind other rows
    (64, 8, 64, 0, 128, True, None), (64, 8, 64, 64, 128, False, None),
    # a band over MHA (a group of one), aligned and not
    (4, 4, 512, 512, 128, True, None), (4, 4, 512, 72, 128, False, None),
]


@pytest.mark.parametrize("hq, hkv, t, offset, window, sunk, block",
                         _BAND_CASES)
def test_banded_flash_fwd_at_the_published_head_widths_with_a_sink(
        hq, hkv, t, offset, window, sunk, block):
    """``flash_fwd`` in the interpreter against ``attend_rows``' XLA
    body: d_qk 192 beside d_v 128, a band with a sink and without,
    prompts under, at and over the window, a segment at a traced offset
    behind earlier rows (whole blocks of them, or not), a kv head's
    group of 8, 4 and 1 query heads in a cell; the full layers' call (no
    band, no sink) at the tiny shapes."""
    ks = jax.random.split(jax.random.PRNGKey(t + offset), 4)
    s = -(-(offset + t) // 128) * 128  # (the keys are whole blocks)
    q = jax.random.normal(ks[0], (1, hq, t, 192))
    k = jax.random.normal(ks[1], (1, hkv, s, 192))
    v = jax.random.normal(ks[2], (1, hkv, s, 128))
    sink = 2.0 + jax.random.normal(ks[3], (hq,)) if sunk else None
    for window, snk in [(window, sink)] + [(None, None)] * bool(block):
        want = attend_rows(q, k, v, offset=offset, window=window, sink=snk,
                           use_flash=False)
        got = jax.jit(lambda q, k, v, o: flash_fwd(
            q, k, v, offset=o, window=window, sink=snk, block_q=block,
            block_k=block, interpret=True))(q, k, v, jnp.int32(offset))
        assert got.shape == (1, hq, t, 128)
        np.testing.assert_allclose(got, want, atol=2e-5)


# (heads, kv heads, d_qk, T, S, offset, a sink, blocks or the rule's own),
# and for a whole bucket what its rows hold: a "padded" tail (the last
# rows of q, k and v zeros: a dead segment's) or a "spiked" k tile
_FULL_CASES = [
    # the published heads, 16 a cell over 128-row q blocks: the first
    # rows, an offset of whole blocks, one that is not
    (64, 4, 192, 256, 512, 0, False, 128),
    (64, 4, 192, 256, 512, 0, True, 128),
    (64, 4, 192, 256, 512, 256, True, 128),
    (64, 4, 192, 256, 512, 256, False, 128),
    (64, 4, 192, 256, 512, 200, True, 128),
    (64, 4, 192, 256, 512, 200, False, 128),
    # rows before every key (T > S): half of them, and a whole q block
    (64, 4, 192, 256, 256, -128, True, 128),
    (64, 4, 192, 256, 256, -128, False, 128),
    (64, 4, 192, 256, 384, -200, False, 64),
    # the rule's own blocks: two 1,024-key tiles behind 1,792 rows
    (64, 4, 192, 256, 2048, 1792, True, None),
    # 64 / 8 heads of 128 over a whole bucket (offset None: S - T = 0)
    (64, 8, 128, 512, 512, None, False, None),
    (64, 8, 128, 512, 512, None, False, 128),
    # a group of one: a head's rows alone in a cell
    (4, 4, 192, 256, 512, 256, True, 128), (4, 4, 192, 512, 512, 0, False, 64),
    # a SERVING prefill's whole bucket (PR 69: Solar-Open2's 64 / 8,
    # Granite's 32 / 8, Instella-MoE's 16 / 16 heads of 128), T = S with
    # the offset left out and given as a traced 0, several tiles a q
    # block and the rule's own blocks, a right-padded tail
    (64, 8, 128, 512, 512, 0, False, 128),
    (64, 8, 128, 512, 512, None, False, 128, "padded"),
    (32, 8, 128, 512, 512, None, False, 128),
    (32, 8, 128, 512, 512, 0, False, None, "padded"),
    (16, 16, 128, 512, 512, None, False, 128, "padded"),
    (16, 16, 128, 512, 512, 0, False, 128),
    (16, 16, 128, 512, 512, 0, False, None),
    # a k tile forty times as large behind a q block's first: the lagged
    # update's room is passed and the tile is done again max first
    (64, 8, 128, 512, 512, 0, False, 128, "spiked"),
    (16, 16, 128, 512, 512, None, False, 128, "spiked"),
]


@pytest.mark.parametrize(
    "hq, hkv, d, t, s, offset, sunk, block, holds",
    [(*c, None)[:9] for c in _FULL_CASES])
def test_full_flash_fwd_forms_its_scores_transposed_and_lags_a_tile(
        hq, hkv, d, t, s, offset, sunk, block, holds):
    """The forward-only full causal body (``_fwd_kernel_t``) in the
    interpreter against ``attend_rows``' XLA body, the offset traced: a
    kv head's 16, 8, 4 and 1 query heads in a cell, keys 192 and 128
    wide, with a sink and without; a whole bucket from position 0 (the
    three serving prefills that reach it through ``attend_bucket``)
    against ``attention_reference`` too. A row that lies before every
    key gives zeros (the XLA body's plain softmax gives the mean of v
    there; with a sink it gives zeros too)."""
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import attention_reference

    ks = jax.random.split(jax.random.PRNGKey(t + s), 4)
    q = jax.random.normal(ks[0], (1, hq, t, d))
    k = jax.random.normal(ks[1], (1, hkv, s, d))
    v = jax.random.normal(ks[2], (1, hkv, s, 128))
    if holds == "padded":
        q, k, v = (a.at[:, :, 3 * t // 4 - 19:].set(0.0) for a in (q, k, v))
    if holds == "spiked":  # (every row behind it: over _LAG_MAX above)
        k = k.at[:, :, 128:256].multiply(40.0)
        scores = jnp.einsum("bhtd,bhsd->bhts", q[:, ::hq // hkv, 256:],
                            k) * d ** -0.5
        gap = scores[..., 128:256].max(-1) - scores[..., :128].max(-1)
        assert float(gap.min()) * 1.4427 > fa._LAG_MAX
    sink = 2.0 + jax.random.normal(ks[3], (hq,)) if sunk else None
    at = s - t if offset is None else offset
    want = attend_rows(q, k, v, offset=at, sink=sink, use_flash=False)
    if holds is not None or (t == s and at == 0 and d == 128 and not sunk):
        def rows_first(a):
            return a.transpose(0, 2, 1, 3)

        np.testing.assert_allclose(want, rows_first(attention_reference(
            rows_first(q), rows_first(k), rows_first(v))), atol=2e-5)
    traced = {} if offset is None else {"offset": jnp.int32(offset)}
    got = jax.jit(lambda q, k, v, kw: flash_fwd(
        q, k, v, sink=sink, block_q=block, block_k=block, interpret=True,
        **kw))(q, k, v, traced)
    assert got.shape == (1, hq, t, 128)
    blind = max(0, -at)  # rows that lie before every key
    np.testing.assert_allclose(got[:, :, blind:], want[:, :, blind:],
                               atol=2e-5)
    assert not np.asarray(got[:, :, :blind]).any()
    assert np.isfinite(np.asarray(got)).all()
    if sunk:
        np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("sunk", [True, False])
def test_a_tile_far_above_the_max_so_far_is_done_again_max_first(sunk):
    """Keys behind the first tile forty times as large: their scores
    stand over ``_LAG_MAX`` above the rows' max so far, the lagged
    update would overflow, and the tile is done again with its max
    first; keys of ordinary size behind those join lagged again."""
    from ray_tpu.ops import flash_attention as fa

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (1, 8, 128, 192))
    k = jax.random.normal(ks[1], (1, 2, 512, 192))
    k = k.at[:, :, 128:256].multiply(40.0)
    v = jax.random.normal(ks[2], (1, 2, 512, 128))
    sink = 2.0 + jax.random.normal(ks[3], (8,)) if sunk else None
    scores = jnp.einsum("bhtd,bhsd->bhts", q[:, ::4], k) * 192 ** -0.5
    gap = scores[..., 128:256].max(-1) - scores[..., :128].max(-1)
    assert float(gap.min()) * 1.4427 > fa._LAG_MAX  # (every row's tile)
    want = attend_rows(q, k, v, offset=384, sink=sink, use_flash=False)
    got = jax.jit(lambda q, k, v, o: flash_fwd(
        q, k, v, offset=o, sink=sink, block_q=64, block_k=128,
        interpret=True))(q, k, v, jnp.int32(384))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_full_calls_cell_and_blocks_come_from_its_shapes():
    """``_fwd_blocks``: 2,048 rows a cell, as many of a kv head's query
    heads as divide its group over a q block of at least 128 rows, and
    1,024 keys; a short segment or few keys are taken whole."""
    from ray_tpu.ops.flash_attention import _fwd_blocks

    assert _fwd_blocks(16, 2048, 32768) == (16, 128, 1024)  # MiMo-V2.5
    assert _fwd_blocks(8, 32768, 32768) == (8, 256, 1024)  # 64 / 8 heads
    assert _fwd_blocks(4, 2048, 8192) == (4, 512, 1024)
    assert _fwd_blocks(2, 2048, 8192) == (2, 1024, 1024)
    assert _fwd_blocks(1, 4096, 4096) == (1, 2048, 1024)
    assert _fwd_blocks(32, 2048, 4096) == (16, 128, 1024)
    assert _fwd_blocks(12, 2048, 4096) == (12, 128, 1024)
    assert _fwd_blocks(3, 2048, 4096) == (3, 512, 1024)
    assert _fwd_blocks(16, 64, 256) == (16, 64, 256)


def test_the_differentiable_call_keeps_its_two_results_and_the_forward_one():
    """``flash_attention`` makes the lse whether it is differentiated or
    not (nothing there can see which): its ``flash_fwd`` has two
    results, under ``jax.grad`` and without, one k tile or several. The
    forward-only call has one, and differentiating it raises by name."""
    q = jnp.ones((1, 128, 2, 64))

    def loss(q, **kw):
        return flash_attention(q, q, q, interpret=True, **kw).sum()

    for kw in ({}, {"block_q": 64, "block_k": 64}):
        plain = pallas_calls(jax.make_jaxpr(
            lambda q: loss(q, **kw))(q).jaxpr)
        assert plain == [("flash_fwd", 2)], plain
        under_grad = pallas_calls(jax.make_jaxpr(jax.grad(
            lambda q: loss(q, **kw)))(q).jaxpr)
        assert ("flash_fwd", 2) in under_grad and not [
            c for c in under_grad if c[0] == "flash_fwd" and c[1] != 2]
        assert any(name.startswith("flash_bwd") for name, _ in under_grad)
    qh = q.transpose(0, 2, 1, 3)
    alone = pallas_calls(jax.make_jaxpr(lambda q, o: flash_fwd(
        q, q, q, offset=o, interpret=True))(qh, jnp.int32(0)).jaxpr)
    assert alone == [("flash_fwd", 1)], alone
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_fwd(
            q, qh, qh, offset=jnp.int32(0), interpret=True).sum())(qh)


def test_the_bands_blocks_come_from_the_window_and_the_group():
    """``_window_blocks``: a k block is the window in whole lanes, a q
    block as long where the group's rows fill a cell (512 of them) and a
    multiple where they do not."""
    from ray_tpu.ops.flash_attention import _window_blocks

    assert _window_blocks(128, 8) == _window_blocks(128, 4) == (128, 128)
    assert _window_blocks(128, 2) == (256, 128)
    assert _window_blocks(128, 1) == (512, 128)
    assert _window_blocks(100, 8) == (128, 128)
    assert _window_blocks(200, 8) == (256, 256)


def test_a_differentiated_band_or_sink_raises_by_name():
    q = jnp.ones((1, 2, 64, 192))
    k, v = jnp.ones((1, 1, 64, 192)), jnp.ones((1, 1, 64, 128))
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_fwd(q, k, v, window=16, block_q=64,
                                     block_k=64, interpret=True).sum())(q)
    with pytest.raises(ValueError, match="values as wide as keys"):
        flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), interpret=True)


# ------------------------------------- who knows the block is there


def test_nothing_of_the_program_imports_or_names_the_block():
    """The block is found through a configuration that names it
    (``cfg.slot_model``; ``benchmark/families/mimo_v2.py: build``): no
    module of the program imports it, so a process that serves another
    configuration never loads it, and neither the engine, the serving
    tier nor a kernel carries its name."""
    import ast
    import glob
    import os

    import ray_tpu

    root = ray_tpu.__path__[0]
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        if path.endswith(os.path.join("models", "mimo.py")):
            continue
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            words = []
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                words = [a.name for a in node.names] + [
                    getattr(node, "module", None) or ""]
            elif isinstance(node, ast.Name):
                words = [node.id]
            elif isinstance(node, ast.Attribute):
                words = [node.attr]
            assert not any("mimo" in w.lower() for w in words), path
