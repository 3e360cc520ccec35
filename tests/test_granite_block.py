"""The hybrid block of state-space layers (``ray_tpu/models/granite.py``:
Mamba-2 mixers beside NoPE GQA, a softmax router with a shared expert in
every layer, the model's four multipliers) against its plain reference
(``benchmark/families/granitemoehybrid.reference.py``) at tiny sizes on
the CPU, seeded: the chunked scan in segments = stepping = the
reference's recurrence a token at a time, with a decay that underflows
inside one chunk; the step kernel in the interpreter = the XLA body at
the published head; the whole model through the engine's slots at ragged
positions for 300 steps, logits; a state or a router kept in bf16 and
each multiplier left out FAIL the same comparison; ``route`` fed
softmax scores = the published top-k then softmax; the four shares of
an expert layer; ``RaggedDecoder``; a reused slot; the spans.

The tiny size (``TINY_FIELDS``) keeps two Mamba layers, an attention
layer and a Mamba layer, 4 heads of 8 with a state of 16 on a hidden
size of 32, 8 experts of which 2 are held beside a shared expert twice
as wide, chunks of 8 rows.

In the tests the weights are drawn for a depth of 1
(``published_layers``), so that a layer moves the stream by a sixth of
itself and not by a fortieth: a part left out then shows in the logits.

Tolerances (readings of ``test_prefill_then_300_steps...``'s own
comparison, logits that spread by 0.030, this CPU). In float32 both
sides round nothing but their sums, in another order (the chunked scan
against the recurrence): the LARGEST difference over the three prompts'
300 positions reads 1.7e-7 to 3.2e-7, and the controls read: the
program with its matrices rounded to bf16 (8 mantissa bits) 1.8e-3, the
state ``H`` rounded to bf16 after every step and prefill 5.4e-3, the
router's scores rounded to bf16 before the choice 3.1e-4 (a weight
moves by 2^-9 of itself). ``F32_TOL`` = 1e-5 is the geometric mean of
3.2e-7 and the nearest control's 3.1e-4: a bf16 state misses it 540
times over, a bf16 router 31 times, and every structural departure
reads over fifty times it
(``test_a_part_left_out_fails_the_comparison``). In bf16 a router
near-tie that flips an expert moves single logits by more than rounding
does, so bf16 is judged on the MEDIAN difference of a prompt's logits:
the program reads 2.6e-4 to 2.8e-4 over the three prompts, the control
(matrices cut to 3 mantissa bits, the nearest precision below) 3.1e-3;
``BF16_TOL`` = 9e-4 is about their geometric mean.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import (  # noqa: F401 (segments_of_16: a fixture)
    forget_programs, segments_of_16, short_prompt_in_a_reused_slot)
from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import granite, moe
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import ssd_chunk as sc
from ray_tpu.ops import ssd_step as ss

F32_TOL = 1e-5
BF16_TOL = 9e-4

FAM = manifest.family("granitemoehybrid")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
PROMPTS = (5, 23, 41)
STEPS = 300


def _cfg(**kw):
    m = {**M, "published_layers": 1, **kw}
    held = m.pop("held_experts")
    return granite.GraniteConfig(
        **{**m, "layer_types": tuple(m["layer_types"])},
        held_experts=held and tuple(held), max_seq_len=512)


def _cut(params, bits: int):
    """Every matrix rounded to ``bits`` mantissa bits (8: bf16)."""
    drop = 23 - bits

    def cut(path, a):
        if getattr(path[-1], "key", None) in granite.SLOTS.F32_LEAVES:
            return a
        raw = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        raw = (raw + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
            ~((1 << drop) - 1) & 0xFFFFFFFF)
        return jax.lax.bitcast_convert_type(raw, jnp.float32).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(cut, params)


def _as_bf16(a):
    return a.astype(jnp.bfloat16).astype(jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, granite.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_published_sizes():
    """``GraniteConfig()`` is the published model: an attention layer at
    5, 15, 25 and 35 of 40, 32 / 8 heads of 128, mixers of 128 heads x
    64 with a state of 128, 72 experts of 768 beside a shared one of
    1,536, the four multipliers; and the tiny one keeps both kinds."""
    cfg = granite.GraniteConfig()
    assert [i for i in range(40) if cfg.full(i)] == [5, 15, 25, 35]
    assert (cfg.full_layers, cfg.ssm_layers, cfg.moe_layers) == (4, 36, 40)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.kv_width) \
        == (32, 8, 128, 1024)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.conv_kernel,
            cfg.inner, cfg.conv_width) == (128, 64, 128, 4, 8192, 8448)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff, cfg.shared_d_ff,
            cfg.n_group) == (72, 10, 768, 1536, 1)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.attention_multiplier, cfg.logits_scaling) \
        == (12.0, 0.22, 1 / 128, 16.0)
    assert cfg.router_softmax and cfg.rms_eps == 1e-5
    assert "router_softmax" not in {
        f.name for f in dataclasses.fields(cfg)}
    tiny = _cfg()
    assert [tiny.full(i) for i in range(4)] == [False, False, True, False]
    assert [tiny.stack_index(i) for i in range(4)] == [0, 1, 0, 2]
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(layer_types=["mamba", "window", "attention", "mamba"])
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(layer_types=["mamba"])


def test_a_bucket_is_cut_into_equal_segments_of_whole_chunks():
    cfg = granite.GraniteConfig()
    assert cfg.ssm_chunk == 256
    assert [moe.segment_rows(t, cfg.ssm_chunk)
            for t in (7, 1024, 2048, 3072, 4096)] \
        == [7, 1024, 2048, 1536, 2048]
    assert [granite.SLOTS.prefill_segments(cfg, b)
            for b in (1024, 2048, 3072, 4096)] == [1, 1, 2, 2]


# -------------------------------------------------------------- Mamba-2


def _ssm_layer(cfg, seed):
    return granite.init_params(cfg, jax.random.PRNGKey(seed))["layers"][0][
        "attn"]


@pytest.mark.parametrize("t", [1, 7, 8, 9, 40])
def test_the_chunked_scan_is_stepping_is_the_recurrence(t):
    """One Mamba layer over ``t`` rows (less than a chunk, a chunk, a
    chunk and a row, five chunks) as one segment, as ``t`` decode steps
    and as the reference's layer: outputs, ``H`` and the convolution
    rows."""
    cfg = _cfg()
    p = _ssm_layer(cfg, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, t, cfg.d_model))
    lens = jnp.full((2,), t)
    y, st = granite.ssm_segment(cfg, p, x, granite.ssm_empty(cfg, 2), 0,
                                lens)
    st_step, ys = granite.ssm_empty(cfg, 2), []
    for i in range(t):
        y_i, st_step = granite.ssm_step(cfg, p, x[:, i:i + 1], st_step,
                                        jnp.array([True, True]))
        ys.append(y_i)
    with jax.default_matmul_precision("highest"):
        want, h_ref, tail = REF._ssm_rows(M, p, x, *REF._ssm_empty(M, 2))
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), want, atol=1e-5)
    # (the slot's state lies in the step kernel's layout)
    assert st["h"].shape == (2, 4, 16, 8)
    np.testing.assert_allclose(ss.unpack(st["h"], 8), h_ref, atol=1e-5)
    np.testing.assert_allclose(ss.unpack(st_step["h"], 8), h_ref,
                               atol=1e-5)
    np.testing.assert_array_equal(ss.pack(ss.unpack(st["h"], 8)), st["h"])
    np.testing.assert_allclose(st["conv"], tail, atol=1e-6)
    np.testing.assert_allclose(st_step["conv"], tail, atol=1e-6)


def test_padding_and_later_segments_leave_the_real_tokens_state():
    """A prompt right-padded to its bucket, its rows in four segments:
    ``H`` and the convolution rows after the last segment are those
    after the last REAL token, wherever in a segment it lies (its first
    row, its last, the middle; a whole segment of padding behind it)."""
    cfg = _cfg()
    p = _ssm_layer(cfg, 1)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, cfg.d_model))
    lens = jnp.array([13, 16, 17, 32])
    state, ys = granite.ssm_empty(cfg, 4), []
    for start in range(0, 32, 8):
        y, state = granite.ssm_segment(cfg, p, x[:, start:start + 8], state,
                                       start, lens)
        ys.append(y)
    for b, n in enumerate(np.asarray(lens)):
        y1, exact = granite.ssm_segment(cfg, p, x[b:b + 1, :n],
                                        granite.ssm_empty(cfg, 1), 0,
                                        lens[b:b + 1])
        np.testing.assert_allclose(state["h"][b], exact["h"][0], atol=1e-6)
        np.testing.assert_array_equal(state["conv"][b], exact["conv"][0])
        np.testing.assert_allclose(jnp.concatenate(ys, 1)[b, :n], y1[0],
                                   atol=1e-5)


def test_the_chunked_scan_survives_a_decay_that_underflows_in_a_chunk():
    """``dt`` has no upper clamp and A reaches -16: with dt A near -20 a
    token a chunk of 8 sums to -160, far under float32's e^-87, and the
    quotient e^(L_t) / e^(L_s) would be 0 / 0. The chunked scan takes
    decays pairwise, e^(L_t - L_s) <= 1 masked before the exponential,
    so nothing overflows and nothing is NaN, and across four chunks with
    a carried state it is still the recurrence."""
    key = jax.random.split(jax.random.PRNGKey(3), 7)
    b, t, h, p, n = 2, 32, 4, 8, 16
    x = jax.random.normal(key[0], (b, t, h, p))
    a = -jnp.array([1.0, 4.0, 16.0, 16.0])
    dt = 1.25 + 0.3 * jax.random.uniform(key[1], (b, t, h))
    # (some steps hardly decay, so that the state is not all noise)
    dt = jnp.where(jax.random.uniform(key[2], (b, t, h)) < 0.3, 1e-3, dt)
    bb, cc = (jax.random.normal(k, (b, t, n)) for k in key[3:5])
    h0 = jax.random.normal(key[5], (b, h, p, n))
    assert float(jnp.cumsum(dt * a, 1)[:, 7].min()) < -100
    # (the ops take a group axis: the block's one group)
    y, last = sc.ssd_chunked(x, dt, a, bb[:, :, None], cc[:, :, None], h0,
                             chunk=8)
    y_ref, last_ref = REF.ssm_recurrence(x, dt, a, bb, cc, h0)
    assert np.isfinite(np.asarray(y)).all() and np.isfinite(
        np.asarray(last)).all()
    np.testing.assert_allclose(y, y_ref, atol=3e-5, rtol=2e-5)
    np.testing.assert_allclose(last, last_ref, atol=3e-5, rtol=2e-5)
    # a chunk of padding (dt 0) hands the state on bit for bit
    _, kept = sc.ssd_chunked(x[:, :8], jnp.zeros((b, 8, h)), a,
                             bb[:, :8, None], cc[:, :8, None], h0, chunk=8)
    np.testing.assert_array_equal(kept, h0)


@pytest.mark.parametrize("heads, p, rows", [(128, 64, None), (32, 64, 8),
                                            (6, 128, None), (4, 8, None)])
def test_the_step_kernel_is_the_xla_body_is_the_recurrence(heads, p, rows):
    """``ops.ssd_step`` in the Pallas interpreter at the published head
    (128 heads of 64 x 128: 64 lane rows of two heads, four blocks of 16
    a slot), in blocks of 8 rows, at a head that fills the lanes alone
    and at the tests' (4 heads of 8: a head a row, no kernel on a chip)
    against the XLA body and the reference's recurrence on the unpacked
    state; the inactive slot's ``H`` comes back bit for bit."""
    key = jax.random.split(jax.random.PRNGKey(4), 5)
    b, n = 3, 128
    g = ss.lane_heads(heads, p)
    assert g == {64: 2, 128: 1, 8: 1}[p]
    if heads == 128:
        assert ss.block_rows(heads // g) == ss.BLOCK_ROWS == 16
    assert ss.block_rows(6) == 6 and ss.block_rows(24, 16) == 8
    plain = jax.random.normal(key[0], (b, heads, p, n))
    h = ss.pack(plain)
    assert h.shape == (b, heads // g, n, g * p)
    np.testing.assert_array_equal(ss.unpack(h, p), plain)
    dtx = jax.random.normal(key[1], (b, heads, p)) * 0.1
    da = jax.random.uniform(key[2], (b, heads)) ** 4
    bb, cc = (jax.random.normal(k, (b, n)) for k in key[3:5])
    active = jnp.array([True, False, True])
    # (the ops take a group axis: the block's one group)
    h_k, y_k = ss.ssd_step(h, dtx, da, bb[:, None], cc[:, None], active,
                           interpret=True, rows=rows)
    h_x, y_x = ss.ssd_step(h, dtx, da, bb[:, None], cc[:, None], active,
                           use_kernel=False)
    np.testing.assert_array_equal(h_k[1], h[1])
    np.testing.assert_array_equal(h_x[1], h[1])
    np.testing.assert_allclose(h_k, h_x, atol=1e-5)
    np.testing.assert_allclose(y_k, y_x, atol=1e-4)
    # one token of the reference's recurrence: x dt = dtx, e^(dt a) = da
    y_ref, h_ref = REF.ssm_recurrence(
        dtx[:, None], jnp.ones((b, 1, heads)), jnp.zeros((heads,)),
        bb[:, None], cc[:, None], plain * da[..., None, None])
    on = np.asarray(active)
    np.testing.assert_allclose(ss.unpack(h_k, p)[on], h_ref[on], atol=1e-5)
    np.testing.assert_allclose(y_k, y_ref[:, 0], atol=1e-4)


# -------------------------------------------------------------- segments


def test_prefill_in_eight_segments_is_prefill_in_one(monkeypatch, model):
    """Segmenting is arithmetic-neutral but for the order of the chunked
    scan's float32 sums: a 128-row bucket whole against the same in
    eight segments of 16, a prompt that ends inside the fifth: the
    stream, every Mamba layer's ``H`` and convolution rows, the
    attention layer's rows and the loads agree to 1e-5."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 128), 1, 256)
    lens = jnp.array([128, 77])
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 128)
    assert granite.SLOTS.prefill_segments(cfg, 128) == 1
    h1, st1, (loads1, calls) = granite.prefill(params, toks, lens, cfg,
                                               loads=True)
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 16)
    assert granite.SLOTS.prefill_segments(cfg, 128) == 8
    h8, st8, (loads8, _) = granite.prefill(params, toks, lens, cfg,
                                           loads=True)
    np.testing.assert_allclose(h8[0], h1[0], atol=1e-5)
    np.testing.assert_allclose(h8[1, :77], h1[1, :77], atol=1e-5)
    assert len(st1["ssm"]) == 3
    for a, b in zip(jax.tree_util.tree_leaves(st1["ssm"]),
                    jax.tree_util.tree_leaves(st8["ssm"])):
        np.testing.assert_allclose(b, a, atol=1e-5)
    for name in ("k_full", "v_full"):
        assert st1[name].shape == (1, 2, 128, cfg.kv_width)
        np.testing.assert_allclose(st8[name][:, 0], st1[name][:, 0],
                                   atol=1e-5)
        np.testing.assert_allclose(st8[name][:, 1, :77],
                                   st1[name][:, 1, :77], atol=1e-5)
    np.testing.assert_array_equal(loads8, loads1)
    assert loads1.shape == (4, 2) and int(loads1.sum()) > 0
    # a quarter of the experts held: the expert layer has no compact branch
    np.testing.assert_array_equal(calls, [0, 0])


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state, each in a bucket longer than
    itself, then ``steps`` greedy steps of the model's ragged step with
    every slot at its own position and one slot inactive. -> for each
    prompt (its tokens followed by the generated ones, float32 logits
    [steps, V] from the last prompt position on)."""
    slots, max_len = 4, 384
    state = granite.SLOTS.init_state(cfg, slots, max_len)
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
    step = jax.jit(functools.partial(granite.SLOTS.step, cfg, params, None))
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
def test_prefill_then_300_steps_through_the_slots_is_the_references_forward(
        dtype, tol, control_bits, off, segments_of_16):
    """Four layers of both kinds, a quarter of the experts held, three
    slots at different positions and one inactive (prompts of 5, 23 and
    41 tokens in buckets of 16 and 64, in segments of 16 rows: the last
    two cross chunk and segment boundaries): the logits of 300 decoded
    positions (``ssd_step`` on the carried state, the attention step
    over the slot's rows) against the reference's full forward over
    prompt + tokens, whose SSM is the recurrence a token at a time,
    inside ``tol`` (``off``: the largest difference in float32, a
    prompt's median in bf16; module docstring); the control (matrices
    cut to ``control_bits`` mantissa bits) is outside it."""
    cfg = _cfg(dtype=dtype)
    params = granite.init_params(cfg, jax.random.PRNGKey(7))
    worst = _worst(cfg, params, _prompts(0), STEPS, off)
    assert worst < tol, worst
    control = _worst(cfg, _cut(params, control_bits), _prompts(0)[1:2], 40,
                     off, want_params=params)
    assert control > tol, (control, tol)


@pytest.mark.parametrize("kept_in_bf16", ["state", "router"])
def test_a_state_or_a_router_in_bf16_misses_the_float32_tolerance(
        kept_in_bf16, monkeypatch, model):
    """What the types of the module docstring are for: ``H`` rounded to
    bf16 after every step and every prefill, or the router's scores
    rounded to bf16 before the choice, move the logits of 300 positions
    by ten times ``F32_TOL`` and more (readings: the state 5.4e-3, the
    router 3.1e-4, the program as it is 3.2e-7)."""
    cfg, params = model
    if kept_in_bf16 == "state":
        def rounded(fn):
            def call(*args, **kw):
                first, second = fn(*args, **kw)
                return ((_as_bf16(first), second) if fn is step
                        else (first, _as_bf16(second)))
            return call

        step, chunk = granite._ssd_step, granite._ssd_chunk
        monkeypatch.setattr(granite, "_ssd_step", rounded(step))
        monkeypatch.setattr(granite, "_ssd_chunk", rounded(chunk))
    else:
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda cfg, scores, bias: route(
            cfg, _as_bf16(scores), bias))
    forget_programs()
    try:
        worst = _worst(cfg, params, _prompts(0)[1:2], STEPS, np.max)
    finally:
        monkeypatch.undo()
        forget_programs()
    assert worst > 10 * F32_TOL, worst


def test_a_short_prompt_in_a_long_bucket_is_the_reference_in_a_reused_slot(
        segments_of_16, model):
    """A reused slot shows nothing of its last stream: ``H`` and the
    convolution rows are replaced whole by ``scatter``, the attention
    layer's rows behind the prompt are never read."""
    cfg, params = model
    short_prompt_in_a_reused_slot(
        granite.SLOTS, cfg, params, lambda tokens: REF.forward(
            params, jnp.asarray([tokens]), M)[0], F32_TOL)


@pytest.mark.parametrize("left_out", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling", "gate_before_norm", "skip", "conv_bias"])
def test_a_part_left_out_fails_the_comparison(left_out, monkeypatch, model):
    """The float32 comparison catches each of the model's own scalars
    read as 1 (``attention_multiplier`` as ``head_dim^-1/2``, what every
    other block has) and each reading of the mixer: the gate applied
    AFTER the norm (Mamba-2's other variant), the skip ``D x`` left out,
    the convolution's bias left out."""
    cfg, params = model
    if left_out == "gate_before_norm":
        def out(cfg, p, y, xs, z):
            b, t = y.shape[:2]
            y = (y + p["d_skip"][:, None] * xs).reshape(b, t, -1)
            y = granite.rms_norm(y, p["y_norm"], cfg.rms_eps)
            return (y * jax.nn.silu(z)).astype(cfg.compute_dtype) @ p["w_out"]

        monkeypatch.setattr(granite, "_ssm_out", out)
    elif left_out in ("skip", "conv_bias"):
        name = {"skip": "d_skip", "conv_bias": "conv_bias"}[left_out]
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.zeros_like(a)
            if getattr(path[-1], "key", None) == name else a, params)
    else:
        plain = {"attention_multiplier": M["head_dim"] ** -0.5}.get(
            left_out, 1.0)
        cfg = _cfg(**{left_out: plain})
    forget_programs()
    try:
        got = _ragged_logits(cfg, params, _prompts(0)[1:2], 6)[0]
    finally:
        monkeypatch.undo()
        forget_programs()
    seq, rows = got
    want = np.asarray(REF.forward(model[1], jnp.asarray([seq]), M)[0])
    off = np.abs(rows - want[23:23 + len(rows)]).max()
    assert off > 50 * F32_TOL, (left_out, off)


# --------------------------------------------------------------- router


@pytest.mark.parametrize("seed", [0, 1, 2, "ties"])
def test_route_fed_softmax_scores_is_the_published_top_k_then_softmax(seed):
    """72 wide in the cell, 8 here (and 72 with ten chosen): ``route``
    over ``softmax(logits)`` with a zero bias, one group and a scaling
    of 1 gives the ids and weights of the published order, the
    ``top_k`` largest logits and a softmax over those alone; on a tie
    (logits drawn from five values) both take the lower index."""
    for m in (M, {**M, "n_experts": 72, "top_k": 10}):
        cfg = _cfg(n_experts=m["n_experts"], top_k=m["top_k"],
                   held_experts=None)
        if seed == "ties":
            logits = jax.random.randint(
                jax.random.PRNGKey(9), (64, m["n_experts"]), 0, 5
            ).astype(jnp.float32) * 0.5
        else:
            logits = 2.0 * jax.random.normal(
                jax.random.PRNGKey(seed), (64, m["n_experts"]))
        weights, ids = moe.route(cfg, jax.nn.softmax(logits), 0.0)
        gates, chosen = REF.router(m, logits)
        np.testing.assert_array_equal(ids, chosen)
        got = jnp.sum(jax.nn.one_hot(ids, cfg.n_experts)
                      * weights[..., None], -2)
        np.testing.assert_allclose(got, gates, atol=1e-6)
        np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-5)
        picked = np.take_along_axis(np.asarray(logits), np.asarray(ids), -1)
        np.testing.assert_allclose(weights, jax.nn.softmax(picked),
                                   rtol=2e-5)


def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the layer cut over four chips. Each share
    routes over all 8 experts and computes its own 2; the four partial
    results, the shared expert counted once, add up to the reference's
    layer with every expert held."""
    whole = _cfg(held_experts=None)
    p = granite.init_params(whole, jax.random.PRNGKey(5))["layers"][1]["mlp"]
    assert p["w_gate"].shape[0] == 8 and "router_bias" not in p
    assert p["shared_gate"].shape == (32, 32) and p["w_gate"].shape[2] == 16
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = REF.moe_layer(M, p, x, held=(0, 8))
        shared = REF._swiglu(x, p["shared_gate"], p["shared_up"],
                             p["shared_down"])
    total = jnp.zeros_like(x)
    for first in range(0, 8, 2):
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


@pytest.mark.parametrize("dtype, gap", [("float32", F32_TOL),
                                        ("bfloat16", 10 * BF16_TOL)])
def test_submit_and_pump_serve_the_references_tokens(dtype, gap):
    """``RaggedDecoder`` (submit -> pump) on the hybrid model: five
    streams over three slots, so slots are reused and streams sit at
    ragged positions; every stream's tokens are the reference's argmax
    wherever its top two logits lie further apart than ``gap``: the
    comparison's own tolerance in float32, ten times the median's in
    bf16, where single logits move by more than the median does (the
    one token of 48 that parts does so at a gap of 0.0056; the
    reference's ``SERVE_TOP2_GAP`` is a reading of the published widths
    and is held to by the cell's rehearsal, not here)."""
    cfg = _cfg(dtype=dtype)
    params = granite.init_params(cfg, jax.random.PRNGKey(8))
    eng = RaggedDecoder(params, cfg, slots=3, max_len=96, chunk_tokens=4,
                        prompt_buckets=(8, 16, 64))
    rng = np.random.RandomState(1)
    asked = [(rng.randint(1, 256, n).astype(np.int32), out)
             for n, out in ((13, 9), (7, 12), (40, 5), (3, 14), (21, 8))]
    sids = [eng.submit(p, out) for p, out in asked]
    eng.drain()
    clear_all = 0
    for sid, (p, out) in zip(sids, asked):
        toks = list(eng.finished[sid].tokens)
        assert len(toks) == out
        rows = np.asarray(REF.forward(
            params, jnp.asarray([list(p) + toks]), M)[0])[
                len(p) - 1:len(p) - 1 + out]
        top2 = np.sort(rows, -1)[:, -2:]
        clear = top2[:, 1] - top2[:, 0] > gap
        assert (rows.argmax(-1) == np.asarray(toks))[clear].all()
        clear_all += int(clear.sum())
    assert clear_all >= (47 if dtype == "float32" else 12), clear_all
    st = eng.stats()
    assert st["state_bytes"] == {
        kind: 3 * n for kind, n in
        FAM.state_bytes_per_slot(M, 96, jnp.dtype(dtype).itemsize).items()}
    assert st["moe_assignments"] > 0 and st["moe_touched_expert_steps"] > 0


def test_the_block_refuses_what_needs_rows(model):
    cfg, params = model
    assert granite.SLOTS.rows_state is False
    with pytest.raises(ValueError, match="GraniteConfig"):
        RaggedDecoder(params, cfg, slots=2, max_len=64, chunk_tokens=4,
                      prompt_buckets=(16,), spec_depth=2)
    with pytest.raises(ValueError, match="prefix of cached rows"):
        granite.SLOTS.prefill(params, None, None, None, None, None, cfg, 64,
                              prefix=object())


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
                        prompt_buckets=(8, 64), name="granite-test")
    assert eng.row_kinds == {"recurrent": (3, 0), "full": (1, None)}
    sid = eng.submit(np.arange(1, 40, dtype=np.int32), 8)
    eng.drain()
    assert len(eng.finished[sid].tokens) == 8
    ring = list(fr._get().ring)
    init = [s["attrs"] for s in ring if s["name"] == "engine.state_init"
            and s["attrs"].get("engine") == "granite-test"][-1]
    per_slot = FAM.state_bytes_per_slot(M, 96, 4)
    assert init["recurrent_bytes"] == 2 * per_slot["recurrent"]
    assert init["full_bytes"] == 2 * per_slot["full"]
    assert (init["recurrent_layers"], init["full_layers"]) == (3, 1)
    assert init["full_row_bytes"] == FAM.kv_row_bytes(M, 4)
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
    loads = [s["attrs"] for s in ring if s["name"] == "engine.readback"
             and "expert_load_max" in s["attrs"]][-1]
    assert loads["expert_load_max"] >= loads["expert_load_mean"] > 0


def test_init_params_draws_this_blocks_leaves_in_blocks(monkeypatch):
    """Both kinds of layer's leaves and shapes; a leaf larger than a
    block drawn block by block (a block is 4,096 numbers here, the
    embedding read 8,192: two blocks); the matrices that write into the stream
    scaled for the published depth; Mamba-2's own initialisation of the
    decay, the step size and the skip; one array for embedding and
    head."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    cfg = _cfg(dtype="bfloat16")
    params = granite.init_params(cfg, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "layers", "final_norm"}
    ssm, gqa = params["layers"][0]["attn"], params["layers"][2]["attn"]
    assert set(ssm) == {"w_in", "conv", "conv_bias", "a_log", "dt_bias",
                        "d_skip", "y_norm", "w_out"}
    assert ssm["w_in"].shape == (32, 32 + 32 + 2 * 16 + 4)
    assert ssm["conv"].shape == (4, 64) and ssm["w_out"].shape == (32, 32)
    assert set(gqa) == {"w_qkv", "wo"}
    assert gqa["w_qkv"].shape == (32, (4 + 2 * 2) * 16)
    a = np.exp(np.asarray(ssm["a_log"]))
    assert ((a >= 1) & (a <= 16)).all()
    dt = np.log1p(np.exp(np.asarray(ssm["dt_bias"])))
    assert ((dt > 9e-4) & (dt < 0.11)).all()
    np.testing.assert_array_equal(ssm["d_skip"], 1.0)
    for leaf in ("a_log", "dt_bias", "d_skip", "conv_bias", "y_norm"):
        assert ssm[leaf].dtype == jnp.float32
    assert ssm["w_in"].dtype == params["embed"].dtype == jnp.bfloat16
    w = np.asarray(params["layers"][1]["mlp"]["w_down"], np.float32)
    assert w.shape == (2, 16, 32)
    assert abs(w.std() * 16 ** 0.5 * 2 ** 0.5 - 1) < 0.15
    e = np.asarray(params["embed"], np.float32)
    assert abs(e.std() * 6 * 16 - 1) < 0.05
    assert (np.asarray(params["final_norm"]) < 0).any()
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == FAM.num_params(M)
