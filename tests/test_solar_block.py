"""The hybrid block without positions (``ray_tpu/models/solar.py``: KDA
with negative eigenvalues three layers in four beside gated NoPE GQA,
experts in every layer) against its plain reference
(``benchmark/families/solar_open2.reference.py``) at tiny sizes on the
CPU, seeded: the chunkwise KDA in segments = stepping = the reference's
recurrence a token at a time, with beta up to 2 and a decay that
underflows inside one chunk; the step kernel in the interpreter = the
XLA body at 64 heads; prefill in one segment = prefill in eight; the
whole model through the engine's slots at ragged positions, logits, each
prompt in a bucket longer than itself; beta undoubled, the gate left
out, a rotary put in and a full-rank gate FAIL the same comparison; the
eight shares of an expert layer; ``RaggedDecoder``; a reused slot; the
spans; the refusals.

The tiny size keeps a period and a layer of the next (GQA, KDA x 3,
GQA), 4 heads of 16 on a hidden size of 48, a low rank of 8, 16 experts
of which 4 are held, chunks of 8 rows.

Tolerances (readings of ``test_prefill_then_ragged_decode...``'s own
comparison, logits that spread by 1.07, this CPU). In float32 both sides
round nothing but their sums, in another order (the chunkwise form
against the recurrence): the LARGEST difference reads 7.2e-7 to 1.2e-6
over the three prompts, and the control, the same program with its
matrices rounded to bf16 (8 mantissa bits), 4.3e-3; ``F32_TOL`` = 1e-4
is about their geometric mean, and every structural departure reads
over a hundred times it (``test_a_part_left_out_fails_the_comparison``:
beta undoubled 0.047, the gate left out 0.21, a rotary 0.12, a full-rank
gate 0.26). In bf16 a router near-tie that flips an expert moves single
logits by more than rounding does, so bf16 is judged on the MEDIAN
difference of a prompt's logits: the program reads 0.0035-0.0036 over
the three prompts, the control (matrices cut to 3 mantissa bits, the
nearest precision below) 0.026; ``BF16_TOL`` = 0.01 is about their
geometric mean.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import (  # noqa: F401 (segments_of_16: a fixture)
    forget_programs, segments_of_16, short_prompt_in_a_reused_slot)
from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import ling, moe, solar
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import kda_step as ks

F32_TOL = 1e-4
BF16_TOL = 0.01

FAM = manifest.family("solar_open2")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
PROMPTS = (5, 23, 41)


def _cfg(**kw):
    m = {**M, "kda_chunk": 8, **kw}
    held = m.pop("held_experts")
    return solar.SolarConfig(**{**m, "gqa_layers": tuple(m["gqa_layers"])},
                             held_experts=held and tuple(held),
                             max_seq_len=256)


def _cut(params, bits: int):
    """Every matrix rounded to ``bits`` mantissa bits (8: bf16)."""
    drop = 23 - bits

    def cut(path, a):
        if getattr(path[-1], "key", None) in solar.SLOTS.F32_LEAVES:
            return a
        raw = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        raw = (raw + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
            ~((1 << drop) - 1) & 0xFFFFFFFF)
        return jax.lax.bitcast_convert_type(raw, jnp.float32).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, solar.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_published_sizes():
    """``SolarConfig()`` is the published model: one GQA layer in four
    from layer 0, 64 / 8 heads of 128, KDA of 64 x 128 at rank 128, 320
    experts of 1,280; and the tiny one keeps every kind of layer."""
    cfg = solar.SolarConfig()
    assert cfg.gqa_layers == tuple(range(0, 48, 4))
    assert (cfg.full_layers, cfg.kda_layers, cfg.moe_layers) == (12, 36, 48)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.kv_width) \
        == (64, 8, 128, 1024)
    assert (cfg.kda_head_dim, cfg.kda_rank, cfg.conv_kernel) == (128, 128, 4)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.n_group) \
        == (320, 8, 1280, 1)
    assert cfg.routed_scaling_factor == 1.0 and cfg.rms_eps == 1e-5
    tiny = _cfg()
    assert [tiny.full(i) for i in range(5)] == [True, False, False, False,
                                                True]
    assert [tiny.stack_index(i) for i in range(5)] == [0, 0, 1, 2, 1]
    with pytest.raises(ValueError, match="gqa_layers"):
        _cfg(gqa_layers=[0, 9])


def test_a_bucket_is_cut_into_equal_segments_of_whole_chunks(monkeypatch):
    cfg = _cfg(kda_chunk=64)
    assert moe.SEGMENT_ROWS == 2048
    assert [moe.segment_rows(t, cfg.kda_chunk) for t in (7, 1024, 2048, 8192, 32768)] \
        == [7, 1024, 2048, 2048, 2048]
    assert solar.SLOTS.prefill_segments(cfg, 32768) == 16
    assert solar.SLOTS.prefill_segments(cfg, 1024) == 1
    with pytest.raises(ValueError, match="segments"):
        moe.segment_rows(2049, cfg.kda_chunk)  # two segments of 1024.5 rows
    with pytest.raises(ValueError, match="chunks"):
        moe.segment_rows(4160, cfg.kda_chunk)  # three of 1386.67; 4,160 = 65 x 64


# ------------------------------------------------------------------ KDA


def _kda_layer(cfg, seed):
    return solar.init_params(cfg, jax.random.PRNGKey(seed))["layers"][1][
        "attn"]


@pytest.mark.parametrize("t", [1, 63, 64, 65, 200])
def test_kda_chunkwise_prefill_is_stepping_is_the_recurrence(t):
    """One KDA layer over ``t`` tokens, chunks of 64: the chunkwise form,
    ``t`` single steps and the reference's token-by-token recurrence
    give the same outputs, leave the same S and the same last three
    convolution inputs; beta reaches past 1 on the way."""
    cfg = _cfg(kda_chunk=64)
    p = _kda_layer(cfg, t)
    x = jax.random.normal(jax.random.PRNGKey(100 + t), (2, t, cfg.d_model))
    y, st = solar.kda_segment(cfg, p, x, solar.kda_empty(cfg, 2), 0,
                              jnp.array([t, t]))
    on = jnp.ones((2,), bool)
    st_step, ys = jax.lax.scan(
        lambda s, x_t: solar.kda_step(cfg, p, x_t, s, on)[::-1],
        solar.kda_empty(cfg, 2), jnp.moveaxis(x, 1, 0)[:, :, None])
    y_step = jnp.moveaxis(ys[:, :, 0], 0, 1)
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, u = REF.kda_inputs(M, p, x)
        _, s_ref = REF.kda_recurrence(q, k, v, g, beta)
        y_ref = REF._kda(M, p, x)
    if t > 1:
        assert float(beta.max()) > 1.2 and float(beta.min()) < 0.8
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(y_step, y_ref, atol=2e-5)
    np.testing.assert_allclose(st["s"], s_ref, atol=2e-5)
    np.testing.assert_allclose(st_step["s"], s_ref, atol=2e-5)
    rows = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))[:, -3:]
    np.testing.assert_array_equal(st["conv"], rows)
    np.testing.assert_array_equal(st_step["conv"], rows)


def test_kda_padding_and_later_segments_leave_the_real_tokens_state():
    """A prompt right-padded to its bucket, its rows in four segments:
    S and the convolution rows after the last segment are those after
    the last REAL token, wherever in a segment it lies (its first row,
    its last, the middle; a whole segment of padding behind it)."""
    cfg = _cfg()
    p = _kda_layer(cfg, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, cfg.d_model))
    lens = jnp.array([13, 16, 17, 32])
    state, ys = solar.kda_empty(cfg, 4), []
    for start in range(0, 32, 8):
        y, state = solar.kda_segment(cfg, p, x[:, start:start + 8], state,
                                     start, lens)
        ys.append(y)
    for b, n in enumerate(np.asarray(lens)):
        y1, exact = solar.kda_segment(cfg, p, x[b:b + 1, :n],
                                      solar.kda_empty(cfg, 1), 0,
                                      lens[b:b + 1])
        np.testing.assert_allclose(state["s"][b], exact["s"][0], atol=1e-6)
        np.testing.assert_array_equal(state["conv"][b], exact["conv"][0])
        np.testing.assert_allclose(jnp.concatenate(ys, 1)[b, :n], y1[0],
                                   atol=1e-5)


def test_the_chunkwise_form_survives_a_decay_that_underflows_in_a_chunk():
    """Kimi Linear's decay has no lower bound (Ling's has: its
    ``kda_lower_bound``): with g near -20 a token a chunk of 8 sums to
    -160, far under float32's e^-87. The chunkwise form takes decays
    pairwise, e^(G_i - G_j) <= 1, so nothing overflows and nothing is
    NaN, and with beta up to 2 it is still the recurrence."""
    cfg = _cfg()
    key = jax.random.split(jax.random.PRNGKey(3), 6)
    shape = (2, 32, cfg.n_heads, cfg.kda_head_dim)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q, k = (unit(jax.random.normal(kk, shape)) for kk in key[:2])
    v = jax.random.normal(key[2], shape)
    g = -20.0 - 5.0 * jax.random.uniform(key[3], shape)
    # (some channels hardly decay, so that the state is not all noise)
    g = jnp.where(jax.random.uniform(key[4], shape) < 0.3, -0.01, g)
    beta = jax.random.uniform(key[5], shape[:3], minval=1.5, maxval=2.0)
    s0 = jax.random.normal(key[0], (2, cfg.n_heads, 16, 16))
    assert float(jnp.cumsum(g, 1)[:, 7].min()) < -80
    o, s = ling.kda_chunked(q, k, v, g, beta, s0, chunk=cfg.kda_chunk)
    o_ref, s_ref = REF.kda_recurrence(q, k, v, g, beta, s0)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s)).all()
    np.testing.assert_allclose(o, o_ref, atol=2e-5)
    np.testing.assert_allclose(s, s_ref, atol=2e-5)


def test_the_step_kernel_is_the_xla_body_at_64_heads():
    """``ops.kda_step`` in the Pallas interpreter at this model's shape
    (64 heads of 128 x 128: four blocks of 16 a slot) with beta in (0,
    2) and a decay without a lower bound, against the XLA body; the
    inactive slot's S comes back bit for bit."""
    key = jax.random.split(jax.random.PRNGKey(4), 6)
    b, h, d = 3, 64, 128
    assert ks.block_heads(h) == ks.BLOCK_HEADS == 16
    s = jax.random.normal(key[0], (b, h, d, d))
    q, k, v = (jax.random.normal(kk, (b, h, d)) * d ** -0.5
               for kk in key[1:4])
    g = -8.0 * jax.random.uniform(key[4], (b, h, d)) ** 4
    beta = 2.0 * jax.random.uniform(key[5], (b, h))
    active = jnp.array([True, False, True])
    s_k, o_k = ks.kda_step(s, q, k, v, g, beta, active, interpret=True)
    s_x, o_x = ks.kda_step(s, q, k, v, g, beta, active, use_kernel=False)
    np.testing.assert_array_equal(s_k[1], s[1])
    np.testing.assert_allclose(s_k, s_x, atol=1e-5)
    np.testing.assert_allclose(o_k, o_x, atol=1e-5)


# -------------------------------------------------------------- segments


def test_prefill_in_eight_segments_is_prefill_in_one(monkeypatch, model):
    """Segmenting is arithmetic-neutral but for the order of the
    chunkwise scan's float32 sums: a 128-row bucket whole against the
    same in eight segments of 16, a prompt that ends inside the fifth:
    the stream, every KDA layer's S and convolution rows, the GQA
    layers' rows and the loads agree to 1e-5."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 1, 256)
    lens = jnp.array([128, 77])
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 128)
    assert solar.SLOTS.prefill_segments(cfg, 128) == 1
    h1, st1, (loads1, _) = solar.prefill(params, toks, lens, cfg,
                                           loads=True)
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 16)
    assert solar.SLOTS.prefill_segments(cfg, 128) == 8
    h8, st8, (loads8, _) = solar.prefill(params, toks, lens, cfg,
                                           loads=True)
    np.testing.assert_allclose(h8[0], h1[0], atol=1e-5)
    np.testing.assert_allclose(h8[1, :77], h1[1, :77], atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(st1["kda"]),
                    jax.tree_util.tree_leaves(st8["kda"])):
        np.testing.assert_allclose(b, a, atol=1e-5)
    for name in ("k_full", "v_full"):
        assert st1[name].shape == (2, 2, 128, cfg.kv_width)
        np.testing.assert_allclose(st8[name][:, 0], st1[name][:, 0],
                                   atol=1e-5)
        np.testing.assert_allclose(st8[name][:, 1, :77],
                                   st1[name][:, 1, :77], atol=1e-5)
    np.testing.assert_array_equal(loads8, loads1)
    assert loads1.shape == (5, 4) and int(loads1.sum()) > 0


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state, each in a bucket longer than
    itself, then ``steps`` greedy steps of the model's ragged step with
    every slot at its own position and one slot inactive. -> for each
    prompt (its tokens followed by the generated ones, float32 logits
    [steps, V] from the last prompt position on)."""
    slots, max_len = 4, 96
    state = solar.SLOTS.init_state(cfg, slots, max_len)
    cur = jnp.zeros((slots,), jnp.int32)
    seqs, rows = {}, {}
    for slot, p in zip((2, 0, 3), prompts):
        bucket = 16 if len(p) < 16 else 64
        row = np.zeros((1, bucket), np.int32)
        row[0, :len(p)] = p
        state, cur, *_ = de._prefill_batch_into_slots(
            params, row, np.array([len(p)], np.int32),
            np.array([slot], np.int32), np.array([0], np.uint32),
            np.array([0.0], np.float32), np.array([1.0], np.float32),
            state, cur, cfg)
        seqs[slot], rows[slot] = list(p), []
    active = jnp.asarray([s in seqs for s in range(slots)])
    step = jax.jit(functools.partial(solar.SLOTS.step, cfg, params, None))
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
    return [(seqs[s], np.stack(rows[s])) for s in seqs]


def _worst(cfg, params, prompts, steps, off, want_params=None):
    worst = 0.0
    for (seq, got), p in zip(_ragged_logits(cfg, params, prompts, steps),
                             prompts):
        want = np.asarray(REF.forward(
            want_params or params, jnp.asarray([seq]), M)[0])
        # step j's logits are the position's after len(p) + j tokens
        worst = max(worst, off(np.abs(
            got - want[len(p):len(p) + len(got)])))
    return worst


def _prompts(seed, lengths=PROMPTS):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 256, n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("dtype, tol, control_bits, off", [
    ("float32", F32_TOL, 8, np.max), ("bfloat16", BF16_TOL, 3, np.median)])
def test_prefill_then_ragged_decode_is_the_references_forward(
        dtype, tol, control_bits, off):
    """Five layers of both kinds, a quarter of the experts held, three
    slots at different positions and one inactive (prompts of 5, 23 and
    41 tokens in buckets of 16 and 64, in segments of 16 rows: the last
    two cross segment boundaries): the logits of every decoded position
    (``kda_step`` on the carried state, the GQA step over the slot's
    rows) against the reference's full forward over prompt + tokens,
    inside ``tol`` (``off``: the largest difference in float32, a
    prompt's median in bf16; module docstring); the control (matrices
    cut to ``control_bits`` mantissa bits) is outside it."""
    cfg = _cfg(dtype=dtype)
    params = solar.init_params(cfg, jax.random.PRNGKey(7))
    worst = _worst(cfg, params, _prompts(0), 12, off)
    assert worst < tol, worst
    control = _worst(cfg, _cut(params, control_bits), _prompts(0)[1:2], 6,
                     off, want_params=params)
    assert control > tol, (control, tol)


def test_the_engines_prefill_in_segments_is_the_references_forward(
        segments_of_16, model):
    """The same comparison with the 64-row buckets run in four segments
    of 16 rows: the state a decode step starts from was carried across
    segment boundaries."""
    cfg, params = model
    assert solar.SLOTS.prefill_segments(cfg, 64) == 4
    assert _worst(cfg, params, _prompts(1), 8, np.max) < F32_TOL


def _first_tokens(cfg, params, h, lens):
    f = len(lens)
    return solar.SLOTS.first_token(
        functools.partial(moe.logits, cfg), params, h, lens,
        jnp.zeros((f,), jnp.uint32), jnp.zeros((f,), jnp.float32),
        jnp.ones((f,), jnp.float32))


@pytest.mark.parametrize("lens, live_segments", [((40, 70), 5), ((64,), 4),
                                                 ((128, 3), 8)])
def test_a_call_without_its_dead_segments_leaves_what_is_read_bit_for_bit(
        lens, live_segments, segments_of_16, model):
    """A 128-row bucket in eight segments of 16 with ``live`` = the
    longest prompt's rows (traced, as ``SLOTS.prefill`` passes it)
    against the same call with every segment run: the first tokens and
    their logprobs, every KDA layer's ``S`` and convolution rows, each
    prompt's own k / v rows, the stream's real rows and the loads are
    the same bits; the rows of the segments not run are zeros."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (len(lens), 128), 1, 256)
    lens = jnp.array(lens, jnp.int32)

    def call(live):
        h, st, (loads, _) = solar.prefill(params, toks, lens, cfg,
                                          loads=True, live=live)
        return h, st, loads, _first_tokens(cfg, params, h, lens)

    h0, st0, loads0, first0 = jax.jit(lambda: call(None))()
    h1, st1, loads1, first1 = jax.jit(call)(jnp.max(lens))
    np.testing.assert_array_equal(first1[0], first0[0])
    np.testing.assert_array_equal(first1[1], first0[1])
    np.testing.assert_array_equal(loads1, loads0)
    assert len(st1["kda"]) == 3
    for a, b in zip(jax.tree_util.tree_leaves(st0["kda"]),
                    jax.tree_util.tree_leaves(st1["kda"])):
        np.testing.assert_array_equal(b, a)
    run = live_segments * 16
    for i, n in enumerate(np.asarray(lens)):
        np.testing.assert_array_equal(h1[i, :n], h0[i, :n])
        for name in ("k_full", "v_full"):
            np.testing.assert_array_equal(st1[name][:, i, :n],
                                          st0[name][:, i, :n])
    for a in (h1, st1["k_full"][0], st1["v_full"][1]):
        assert not a[:, run:].any() and a[:, :run].any()
    assert h0[:, 112:].any()  # (run whole, the padding's rows are not)


def test_a_short_prompt_in_a_long_bucket_is_the_reference_in_a_reused_slot(
        segments_of_16, model):
    cfg, params = model
    short_prompt_in_a_reused_slot(
        solar.SLOTS, cfg, params, lambda tokens: REF.forward(
            params, jnp.asarray([tokens]), M)[0], F32_TOL)


@pytest.fixture(scope="module")
def as_it_is(model):
    """The comparison of ``test_a_part_left_out...`` on the program as
    it is, made once for its four cases."""
    return _worst(*model, _prompts(4), 6, np.max)


@pytest.mark.parametrize("left_out", ["beta_doubled", "gqa_gate", "no_rotary",
                                      "low_rank_gate"])
def test_a_part_left_out_fails_the_comparison(left_out, monkeypatch, model,
                                              as_it_is):
    """The float32 comparison catches each reading the configuration
    file had to choose: beta = sigmoid (not doubled:
    ``kda_allow_neg_eigval`` ignored), the GQA layers' output ungated
    (``use_gqa_gate`` ignored), a rotary on q and k (``use_rope`` false
    ignored), and a full-rank output gate in the KDA layers where the
    low rank is stated (``kda_use_full_proj`` false ignored)."""
    cfg, params = model
    assert as_it_is < F32_TOL
    if left_out == "beta_doubled":
        inputs = solar._kda_inputs

        def undoubled(*a):
            q, k, v, g, beta, u = inputs(*a)
            return q, k, v, g, beta / 2, u

        monkeypatch.setattr(solar, "_kda_inputs", undoubled)
    elif left_out == "gqa_gate":
        monkeypatch.setattr(
            solar, "_gqa_out", lambda cfg, p, x, o: o.reshape(
                *o.shape[:2], -1).astype(cfg.compute_dtype) @ p["wo"])
    elif left_out == "no_rotary":
        from ray_tpu.ops.rope import apply_rotary, rotary_embedding

        qkv = solar._qkv

        def rotated(cfg, p, x):  # (positions 0..T-1: the prefill's)
            q, k, v = qkv(cfg, p, x)
            sin, cos = rotary_embedding(
                jnp.arange(x.shape[1])[None], cfg.head_dim, 1e4)
            return apply_rotary(q, sin, cos), apply_rotary(k, sin, cos), v

        monkeypatch.setattr(solar, "_qkv", rotated)
    else:
        full = jax.random.normal(jax.random.PRNGKey(9), (
            cfg.d_model, cfg.n_heads * cfg.kda_head_dim)) * cfg.d_model ** -.5

        def full_rank(cfg, p, x, o):
            gate = jax.nn.sigmoid(x @ full).reshape(o.shape)
            return ling._kda_out(cfg, p, o, gate)

        monkeypatch.setattr(solar, "_kda_out", full_rank)
    forget_programs()
    got = _worst(cfg, params, _prompts(4), 6, np.max)
    monkeypatch.undo()
    forget_programs()
    print(f"{left_out}: {got}")
    assert got > 100 * F32_TOL, (left_out, got)


# --------------------------------------------------------------- router


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_against_the_reference_on_seeded_scores(seed):
    """320-wide in the cell, 16 here: one group, the ``top_k`` largest
    biased scores, weights s / sum s times 1."""
    cfg = _cfg()
    scores = jax.nn.sigmoid(jax.random.normal(
        jax.random.PRNGKey(seed), (64, M["n_experts"])))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 10),
                                   (M["n_experts"],))
    weights, ids = moe.route(cfg, scores, bias)
    gates, chosen = REF.router(M, scores, bias)
    got = jnp.sum(jax.nn.one_hot(ids, cfg.n_experts) * weights[..., None], -2)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(got, gates, atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the layer cut over eight chips. Each share
    routes over all 16 experts and computes its own 2; the eight partial
    results, the shared expert counted once, add up to the reference's
    layer with every expert held."""
    whole = _cfg(held_experts=None)
    p = solar.init_params(whole, jax.random.PRNGKey(5))["layers"][1]["mlp"]
    assert p["w_gate"].shape[0] == 16
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = REF.moe_layer(M, p, x, held=(0, 16))
        shared = REF._swiglu(x, p["shared_gate"], p["shared_up"],
                             p["shared_down"])
    total = jnp.zeros_like(x)
    for first in range(0, 16, 2):
        share = {**p, **{w: p[w][first:first + 2]
                         for w in ("w_gate", "w_up", "w_down")}}
        part = moe.moe(_cfg(held_experts=(first, 2)), share, x)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                part, REF.moe_layer(M, share, x, held=(first, 2)),
                atol=2e-5)
        total = total + (part - shared)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    np.testing.assert_allclose(moe.moe(whole, p, x), want, atol=5e-5)


# ------------------------------------------------------ RaggedDecoder


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_submit_and_pump_serve_the_references_tokens(dtype):
    """``RaggedDecoder`` (submit -> pump) on the hybrid model: five
    streams over three slots, so slots are reused and streams sit at
    ragged positions; every stream's tokens pass the reference's
    ``check_served_tokens`` and, in float32, are its argmax outright."""
    cfg = _cfg(dtype=dtype)
    params = solar.init_params(cfg, jax.random.PRNGKey(8))
    eng = RaggedDecoder(params, cfg, slots=3, max_len=96, chunk_tokens=4,
                        prompt_buckets=(8, 16, 64))
    rng = np.random.RandomState(1)
    asked = [(rng.randint(1, 256, n).astype(np.int32), out)
             for n, out in ((13, 9), (7, 12), (40, 5), (3, 14), (21, 8))]
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


def test_the_slots_do_not_fall_into_one_cycle(model):
    """A seeded model under greedy traffic: four streams of different
    prompts still emit different tokens after 40 steps (the next token
    depends on the context, not on the last token alone)."""
    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=4, max_len=96, chunk_tokens=4,
                        prompt_buckets=(16,))
    sids = [eng.submit(p, 40) for p in _prompts(3, (9, 12, 15, 11))]
    eng.drain()
    tails = {tuple(eng.finished[s].tokens[-8:]) for s in sids}
    assert len(tails) == 4, tails


def test_spans_carry_both_kinds_of_state_the_segments_and_the_routing(
        segments_of_16, model):
    from ray_tpu._private import flight_recorder as fr

    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                        prompt_buckets=(8, 64), name="solar-test")
    assert eng.row_kinds == {"recurrent": (3, 0), "full": (2, None)}
    sid = eng.submit(np.arange(1, 40, dtype=np.int32), 8)
    eng.drain()
    assert len(eng.finished[sid].tokens) == 8
    ring = list(fr._get().ring)
    init = [s["attrs"] for s in ring if s["name"] == "engine.state_init"
            and s["attrs"].get("engine") == "solar-test"][-1]
    per_slot = FAM.state_bytes_per_slot(M, 96, 4)
    assert init["recurrent_bytes"] == 2 * per_slot["recurrent"]
    assert init["full_bytes"] == 2 * per_slot["full"]
    assert (init["recurrent_layers"], init["full_layers"]) == (3, 2)
    assert (init["slots"], init["max_len"]) == (2, 96)
    pre = [s["attrs"] for s in ring if s["name"] == "engine.prefill"][-1]
    assert pre == {"bucket": 64, "prompts": 1, "rows": 1, "tokens": 39,
                   "segments": 4, "live_segments": 3}
    back = [s["attrs"] for s in ring if s["name"] == "engine.readback"
            and "held_assignments" in s["attrs"]][-1]
    # one occupied slot, 39 + 8 positions at the last chunk's end
    assert back["live_rows"] == back["live_rows_full"] == 47
    assert back["live_rows_recurrent"] == 0 and back["cache_rows"] == 192
    assert back["assignments"] == M["top_k"]
    assert 0 <= back["experts_touched"] <= back["held_assignments"] \
        <= M["top_k"]


@pytest.mark.parametrize("tokens, live", [(78, 5), (64, 4), (128, 8)])
def test_the_prefill_span_counts_the_live_segments_beside_the_buckets(
        tokens, live, segments_of_16, model):
    """The 16-row rehearsal of a 9,984-token prompt in the 16,384-row
    bucket (78 in 128: 4.875 segments): ``segments`` stays the bucket's
    8, ``live_segments`` is the 5 that hold a row of the prompt."""
    from ray_tpu._private import flight_recorder as fr

    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=160, chunk_tokens=4,
                        prompt_buckets=(128,), name="solar-live")
    sid = eng.submit(np.arange(1, tokens + 1, dtype=np.int32) % 255 + 1, 4)
    eng.drain()
    assert len(eng.finished[sid].tokens) == 4
    pre = [s["attrs"] for s in fr._get().ring
           if s["name"] == "engine.prefill"][-1]
    assert pre == {"bucket": 128, "prompts": 1, "rows": 1, "tokens": tokens,
                   "segments": 8, "live_segments": live}


def test_both_kda_blocks_call_the_one_step_kernel():
    assert solar._kda_step is ling._kda_step is ks.kda_step


def test_init_params_draws_this_blocks_leaves_in_blocks(monkeypatch):
    """Both kinds of layer's leaves and shapes; a leaf larger than a
    block drawn block by block (a block is 4,096 numbers here, the leaf
    read 6,144: two blocks); the matrices that write into the stream
    scaled for the published depth (the types:
    ``tests/test_slot_protocol.py``)."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    cfg = _cfg(dtype="bfloat16")
    params = solar.init_params(cfg, jax.random.PRNGKey(0))
    kda, gqa = params["layers"][1]["attn"], params["layers"][0]["attn"]
    assert set(kda) == {"w_qkv", "conv", "w_f_down", "w_f_up", "dt_bias",
                        "a_log", "w_beta", "w_g_down", "w_g_up", "o_norm",
                        "wo"}
    assert kda["w_f_down"].shape == (48, 8) and kda["w_g_up"].shape == (8, 64)
    assert set(gqa) == {"w_qkv", "w_gate", "wo"}
    assert gqa["w_qkv"].shape == (48, (4 + 2 * 2) * 16)
    w = np.asarray(params["layers"][1]["mlp"]["w_down"], np.float32)
    assert w.shape == (4, 32, 48)
    assert abs(w.std() * 32 ** 0.5 * (2 * 48) ** 0.5 - 1) < 0.1
