"""The block of gated short convolutions (``ray_tpu/models/lfm2.py``:
conv mixers beside GQA layers with a norm a head, a dense layer and
then a sigmoid router with a selection bias and the published ``1e-6``
in its renormalisation, a tied head) against its plain reference
(``benchmark/families/lfm2_moe.reference.py``) at tiny sizes on the
CPU, seeded: whole sequences; a bucket in four segments = in one; the
whole model through the engine's slots at ragged positions for 300
steps, logits; a bf16 router, a tap, the gate ``c``, a head norm, the
selection's bias and the renormalisation left out FAIL the same
comparison; the two shares of an expert layer; ``RaggedDecoder``; a
reused slot; the spans.

The tiny size (``TINY_FIELDS``) keeps the published list's head and an
uneven tail (conv, conv, full, conv, full, conv), 4 heads of 16 over 2
kv heads on a hidden size of 32, one dense layer, 8 experts of which 4
are held.

In the tests the weights are drawn for a depth of 1
(``published_layers``), so that a layer moves the stream by about its
own size and not by a seventh: a part left out then shows in the logits.

Tolerances (readings of ``test_prefill_then_300_steps...``'s own
comparison, logits that spread by 2.9, this CPU). In float32 both sides
round nothing but their sums, in another order: the LARGEST difference
over the three prompts' 300 positions reads 1.8e-5 to 2.0e-5, and the
controls read: the program with its matrices rounded to bf16 (8
mantissa bits) 0.079, the router's scores rounded to bf16 before the
choice 3.6 (an expert flips and the logit moves by its spread).
``F32_TOL`` = 1e-3 is about the geometric mean of 2.0e-5 and the
nearest control's 0.079: bf16 matrices miss it 79 times over, and every
structural departure reads over twenty times it
(``test_a_part_left_out_fails_the_comparison``). In bf16 a router
near-tie that flips an expert moves single logits by more than rounding
does (the largest difference reads 2.3 to 3.8), so bf16 is judged on the
MEDIAN difference of a prompt's logits: the program reads 0.028 to 0.029
over the three prompts, the control (matrices cut to 3 mantissa bits,
the nearest precision below) 0.34; ``BF16_TOL`` = 0.1 is their
geometric mean.
"""

import functools

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import (  # noqa: F401 (segments_of_16: a fixture)
    forget_programs, segments_of_16, short_prompt_in_a_reused_slot)
from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import lfm2, moe
from ray_tpu.models.decode_engine import RaggedDecoder

F32_TOL = 1e-3
BF16_TOL = 0.1

FAM = manifest.family("lfm2_moe")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
PROMPTS = (5, 23, 41)
STEPS = 300


def _cfg(**kw):
    m = {**M, "published_layers": 1, **kw}
    held = m.pop("held_experts")
    return lfm2.Lfm2Config(
        **{**m, "layer_types": tuple(m["layer_types"])},
        held_experts=held and tuple(held), max_seq_len=512)


def _cut(params, bits: int):
    """Every matrix rounded to ``bits`` mantissa bits (8: bf16)."""
    drop = 23 - bits

    def cut(path, a):
        if getattr(path[-1], "key", None) in lfm2.SLOTS.F32_LEAVES:
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
    return cfg, lfm2.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_published_sizes():
    """The cell's configuration through the family's ``fields`` and
    ``build``: the published widths, the whole list of 24 layer kinds
    (18 conv, 6 full, the uneven tail), two dense layers, 16 of 32
    experts held, the ``1e-6`` stated."""
    fam, m = manifest.model("lfm2-8b-a1b-ep2-1chip")
    cfg = fam.build(m, max_seq_len=8720, remat=False).cfg
    assert (cfg.d_model, cfg.n_layers, cfg.vocab_size) == (2048, 24, 65536)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.conv_layers, cfg.full_layers, cfg.moe_layers) == (18, 6, 22)
    assert [i for i in range(24) if cfg.full(i)] == [2, 6, 10, 14, 18, 21]
    assert (cfg.dense_d_ff, cfg.d_ff, cfg.conv_kernel) == (7168, 1792, 3)
    assert (cfg.n_experts, cfg.top_k, cfg.held) == (32, 4, (0, 16))
    assert cfg.norm_topk_eps == 1e-6 and cfg.rope_theta == 1e6
    assert cfg.kv_width == 512 and cfg.slot_model is lfm2.SLOTS
    assert [cfg.stack_index(i) for i in (0, 1, 2, 3, 6, 23)] \
        == [0, 1, 0, 2, 1, 17]
    assert moe.compact_rows(cfg, 2048 * 4) is None  # (a half is held)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2Config(n_layers=4, layer_types=("conv",) * 3)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2Config()  # (no period is derived)


def test_forward_is_the_references_logits(model):
    """Whole sequences, two of them, in one segment."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 48), 1, 256)
    got = lfm2.forward(params, toks, cfg)
    want = REF.forward(params, toks, M)
    assert float(jnp.std(want)) > 1.0
    np.testing.assert_allclose(got, want, atol=F32_TOL)


def test_prefill_in_four_segments_is_prefill_in_one(monkeypatch, model):
    """Segmenting is arithmetic-neutral but for the order of float32
    sums: a 64-row bucket whole against the same in four segments of
    16, a prompt that ends inside the third: the stream, every conv
    layer's rows of ``u``, the full layers' rows and the loads agree;
    the conv rows kept are the last two REAL rows' whatever the padding
    behind them holds."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 1, 256)
    lens = jnp.array([64, 37])
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 64)
    assert lfm2.SLOTS.prefill_segments(cfg, 64) == 1
    h1, st1, (loads1, calls) = lfm2.prefill(params, toks, lens, cfg,
                                            loads=True)
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 16)
    assert lfm2.SLOTS.prefill_segments(cfg, 64) == 4
    h4, st4, (loads4, _) = lfm2.prefill(params, toks, lens, cfg, loads=True)
    np.testing.assert_allclose(h4[0], h1[0], atol=1e-4)
    np.testing.assert_allclose(h4[1, :37], h1[1, :37], atol=1e-4)
    assert st1["conv"].shape == (4, 2, 2, cfg.d_model)
    np.testing.assert_allclose(st4["conv"], st1["conv"], atol=1e-4)
    for name in ("k_full", "v_full"):
        assert len(st1[name]) == 2
        assert st1[name][0].shape == (2, 64, cfg.kv_width)
        for a, b in zip(st1[name], st4[name]):
            np.testing.assert_allclose(b[0], a[0], atol=1e-4)
            np.testing.assert_allclose(b[1, :37], a[1, :37], atol=1e-4)
    np.testing.assert_array_equal(loads4, loads1)
    assert loads1.shape == (5, 4) and int(loads1.sum()) > 0
    # half of the experts held: the expert layer has no compact branch
    np.testing.assert_array_equal(calls, [0, 0])
    # other padding, the same state: prompt 1's rows behind 37 replaced
    other = toks.at[1, 37:].set(7)
    _, st, _ = lfm2.prefill(params, other, lens, cfg)
    np.testing.assert_array_equal(st["conv"][:, 1], st4["conv"][:, 1])
    # with ``live`` the dead segment (rows 48 ..) is left out
    h_live, st_live, _ = lfm2.prefill(params, toks[1:], lens[1:], cfg,
                                      live=jnp.int32(37))
    np.testing.assert_array_equal(h_live[0, :37], h4[1, :37])
    assert not np.asarray(h_live[0, 48:]).any()
    np.testing.assert_array_equal(st_live["conv"][:, 0], st4["conv"][:, 1])


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state, each in a bucket longer than
    itself, then ``steps`` greedy steps of the model's ragged step with
    every slot at its own position and one slot inactive. -> for each
    prompt (its tokens followed by the generated ones, float32 logits
    [steps, V] from the last prompt position on)."""
    slots, max_len = 4, 384
    state = lfm2.SLOTS.init_state(cfg, slots, max_len)
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
    step = jax.jit(functools.partial(lfm2.SLOTS.step, cfg, params, None))
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
    """Six layers of both kinds, half of the experts held, three slots
    at different positions and one inactive (prompts of 5, 23 and 41
    tokens in buckets of 16 and 64, in segments of 16 rows: the last two
    cross segment boundaries): the logits of 300 decoded positions (the
    slot's two rows of ``u``, the attention step over the slot's rows)
    against the reference's full forward over prompt + tokens, whose
    convolution is three shifted sums over whole rows, inside ``tol``
    (``off``: the largest difference in float32, a prompt's median in
    bf16; module docstring); the control (matrices cut to
    ``control_bits`` mantissa bits) is outside it."""
    cfg = _cfg(dtype=dtype)
    params = lfm2.init_params(cfg, jax.random.PRNGKey(7))
    worst = _worst(cfg, params, _prompts(0), STEPS, off)
    assert worst < tol, worst
    control = _worst(cfg, _cut(params, control_bits), _prompts(0)[1:2], 40,
                     off, want_params=params)
    assert control > tol, (control, tol)


def test_a_short_prompt_in_a_long_bucket_is_the_reference_in_a_reused_slot(
        segments_of_16, model):
    """A reused slot shows nothing of its last stream: the convolution
    rows are replaced whole by ``scatter``, the full layers' rows behind
    the prompt are never read; padding reaches neither the convolution
    rows nor the logits."""
    cfg, params = model
    short_prompt_in_a_reused_slot(
        lfm2.SLOTS, cfg, params, lambda tokens: REF.forward(
            params, jnp.asarray([tokens]), M)[0], F32_TOL)


LEFT_OUT = ("bf16_router", "tap", "gate_c", "head_norm", "selection_bias",
            "renormalisation", "thirds_swapped", "rope")


@pytest.mark.parametrize("left_out", LEFT_OUT)
def test_a_part_left_out_fails_the_comparison(left_out, monkeypatch, model):
    """The float32 comparison catches the router's scores rounded to
    bf16 before the choice and each part of the block read another way:
    the oldest tap dropped, the gate ``c`` left out, the head norms left
    out, the bias left out of the selection, the chosen scores not
    renormalised, the input product's thirds read as C, x, B for B, C,
    x (B and x alone commute), the rotation left out."""
    cfg, params = model
    if left_out == "bf16_router":
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda cfg, scores, bias: route(
            cfg, _as_bf16(scores), bias))
    elif left_out == "tap":
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: a.at[0].set(0.0)
            if getattr(path[-1], "key", None) == "conv" else a, params)
    elif left_out == "gate_c":
        conv = lfm2._short_conv
        monkeypatch.setattr(lfm2, "_short_conv", lambda p, b, c, x, rows:
                            conv(p, b, jnp.ones_like(c), x, rows))
    elif left_out == "head_norm":
        norm = lfm2.rms_norm
        monkeypatch.setattr(lfm2, "rms_norm", lambda x, w, eps: x
                            if w.shape == (cfg.head_dim,)
                            else norm(x, w, eps))
    elif left_out == "selection_bias":
        params = jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.zeros_like(a)
            if getattr(path[-1], "key", None) == "router_bias" else a,
            params)
    elif left_out == "renormalisation":
        def route(cfg, scores, bias):
            _, ids = jax.lax.top_k(scores + bias, cfg.top_k)
            return jnp.take_along_axis(scores, ids, axis=-1), ids

        monkeypatch.setattr(moe, "route", route)
    elif left_out == "thirds_swapped":
        inputs = lfm2._conv_inputs

        def rotated(cfg, p, h):  # (B and x alone commute: u = b * x)
            b, c, x = inputs(cfg, p, h)
            return c, x, b

        monkeypatch.setattr(lfm2, "_conv_inputs", rotated)
    elif left_out == "rope":
        monkeypatch.setattr(lfm2, "apply_rotary", lambda x, sin, cos: x)
    forget_programs()
    try:
        got = _ragged_logits(cfg, params, _prompts(0)[1:2], 6)[0]
    finally:
        monkeypatch.undo()
        forget_programs()
    seq, rows = got
    want = np.asarray(REF.forward(model[1], jnp.asarray([seq]), M)[0])
    off = np.abs(rows - want[23:23 + len(rows)]).max()
    # (a score's eighth bit moves a weight by 0.2% of a feed-forward that
    # the seeded rule writes at 0.4 here: over the limit, not far over)
    margin = 1.5 if left_out == "bf16_router" else 20
    assert off > margin * F32_TOL, (left_out, off)


# --------------------------------------------------------------- router


@pytest.mark.parametrize("seed", [0, 1, "ties", "near_zero"])
def test_route_is_the_published_order_with_its_1e_6(seed):
    """32 wide and four chosen as in the cell, 8 and two as here:
    ``route`` over sigmoid scores with the selection bias gives the ids
    and weights of the published order; on a tie (scores drawn from five
    values, no bias) both take the lower index; where every score is
    near zero (logits of -16: scores of 1e-7) the weights sum to well
    under 1, as the published ``sum + 1e-6`` makes them, and a
    configuration without the field (the older blocks') renormalises
    them to 1."""
    for m in (M, {**M, "n_experts": 32, "top_k": 4}):
        cfg = _cfg(n_experts=m["n_experts"], top_k=m["top_k"],
                   held_experts=None)
        bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3),
                                        (m["n_experts"],))
        if seed == "ties":
            logits = jax.random.randint(
                jax.random.PRNGKey(9), (64, m["n_experts"]), 0, 5
            ).astype(jnp.float32) * 0.5
            bias = jnp.zeros_like(bias)
        elif seed == "near_zero":
            logits = -16.0 + jax.random.normal(
                jax.random.PRNGKey(4), (64, m["n_experts"]))
        else:
            logits = 2.0 * jax.random.normal(
                jax.random.PRNGKey(seed), (64, m["n_experts"]))
        weights, ids = moe.route(cfg, jax.nn.sigmoid(logits), bias)
        gates, chosen = REF.router(m, logits, bias)
        np.testing.assert_array_equal(ids, chosen)
        got = jnp.sum(jax.nn.one_hot(ids, cfg.n_experts)
                      * weights[..., None], -2)
        np.testing.assert_allclose(got, gates, rtol=1e-5, atol=1e-9)
        if seed == "near_zero":
            assert float(weights.sum(-1).max()) < 0.9
            plain, _ = moe.route(_NoEps(cfg), jax.nn.sigmoid(logits), bias)
            np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-5)
        else:
            np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-4)


class _NoEps:
    """A configuration's fields without ``norm_topk_eps``: what every
    older block hands ``moe.route``."""

    def __init__(self, cfg):
        self._cfg = cfg

    def __getattr__(self, name):
        if name == "norm_topk_eps":
            raise AttributeError(name)
        return getattr(self._cfg, name)


def test_two_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the layer cut over two chips. Each share
    routes over all 8 experts and computes its own 4; the two partial
    results (no shared expert to count once) add up to the reference's
    layer with every expert held."""
    whole = _cfg(held_experts=None)
    p = lfm2.init_params(whole, jax.random.PRNGKey(5))["layers"][1]["mlp"]
    assert p["w_gate"].shape == (8, 32, 16) and "shared_gate" not in p
    assert p["router_bias"].shape == (8,)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = REF.moe_layer(M, p, x, held=(0, 8))
    total = jnp.zeros_like(x)
    for first in (0, 4):
        share = {**p, **{w: p[w][first:first + 4]
                         for w in ("w_gate", "w_up", "w_down")}}
        part = moe.moe(_cfg(held_experts=(first, 4)), share, x)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                part, REF.moe_layer(M, share, x, held=(first, 4)),
                atol=2e-5)
        total = total + part
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, atol=5e-5)
    np.testing.assert_allclose(moe.moe(whole, p, x), want, atol=5e-5)


# ------------------------------------------------------ RaggedDecoder


@pytest.mark.parametrize("dtype, gap", [("float32", F32_TOL),
                                        ("bfloat16", 10 * BF16_TOL)])
def test_submit_and_pump_serve_the_references_tokens(dtype, gap):
    """``RaggedDecoder`` (submit -> pump) on the block: five streams
    over three slots, so slots are reused and streams sit at ragged
    positions; every stream's tokens are the reference's argmax wherever
    its top two logits lie further apart than ``gap``: the comparison's
    own tolerance in float32, ten times the median's in bf16, where
    single logits move by more than the median does."""
    cfg = _cfg(dtype=dtype)
    params = lfm2.init_params(cfg, jax.random.PRNGKey(8))
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
    assert lfm2.SLOTS.rows_state is False
    with pytest.raises(ValueError, match="Lfm2Config"):
        RaggedDecoder(params, cfg, slots=2, max_len=64, chunk_tokens=4,
                      prompt_buckets=(16,), spec_depth=2)
    with pytest.raises(ValueError, match="prefix of cached rows"):
        lfm2.SLOTS.prefill(params, None, None, None, None, None, cfg, 64,
                           prefix=object())


def test_the_32_slots_do_not_fall_into_one_cycle(model):
    """A seeded model with a tied head under greedy traffic: 32 streams
    of different prompts on 32 slots still emit different tokens after
    40 steps, and no stream's last 24 tokens are a short cycle (the next
    token depends on the context, not on the last token alone:
    ``lfm2.init_params`` says by which two choices; readings 17 to 24
    distinct tokens of 24 in every stream, two seeds)."""
    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=32, max_len=96, chunk_tokens=8,
                        prompt_buckets=(16,))
    rng = np.random.RandomState(3)
    sids = [eng.submit(rng.randint(1, 256, 9 + i % 7).astype(np.int32), 40)
            for i in range(32)]
    eng.drain()
    tails = [tuple(eng.finished[s].tokens[-24:]) for s in sids]
    assert len(set(tails)) == 32
    assert min(len(set(t)) for t in tails) >= 12, tails


def test_spans_carry_both_kinds_of_state_the_segments_and_the_routing(
        segments_of_16, model):
    from ray_tpu._private import flight_recorder as fr

    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                        prompt_buckets=(8, 64), name="lfm2-test")
    assert eng.row_kinds == {"recurrent": (4, 0), "full": (2, None)}
    sid = eng.submit(np.arange(1, 40, dtype=np.int32), 8)
    eng.drain()
    assert len(eng.finished[sid].tokens) == 8
    ring = list(fr._get().ring)
    init = [s["attrs"] for s in ring if s["name"] == "engine.state_init"
            and s["attrs"].get("engine") == "lfm2-test"][-1]
    per_slot = FAM.state_bytes_per_slot(M, 96, 4)
    assert init["recurrent_bytes"] == 2 * per_slot["recurrent"]
    assert init["full_bytes"] == 2 * per_slot["full"]
    assert (init["recurrent_layers"], init["full_layers"]) == (4, 2)
    assert init["full_row_bytes"] == FAM.kv_row_bytes(M, 4)
    assert "recurrent_row_bytes" not in init
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
    """Both kinds of layer's leaves and shapes, both kinds of
    feed-forward; a leaf larger than a block drawn block by block (a
    block is 4,096 numbers here, the embedding 8,192: two blocks); the
    matrices that write into the stream scaled for the published depth
    and against the embedding's scale (the rule of ``init_params``);
    one array for embedding and head, drawn so that the logits spread by
    sqrt(8); a final norm with both signs."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    cfg = _cfg(dtype="bfloat16", published_layers=24)
    params = lfm2.init_params(cfg, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "layers", "final_norm"}
    conv, gqa = params["layers"][0]["attn"], params["layers"][2]["attn"]
    assert set(conv) == {"w_in", "conv", "w_out"}
    assert conv["w_in"].shape == (32, 96) and conv["conv"].shape == (3, 32)
    assert conv["w_out"].shape == (32, 32)
    assert set(gqa) == {"w_qkv", "q_norm", "k_norm", "wo"}
    assert gqa["w_qkv"].shape == (32, (4 + 2 * 2) * 16)
    assert gqa["q_norm"].shape == gqa["k_norm"].shape == (16,)
    assert set(params["layers"][0]["mlp"]) == {"w_gate", "w_up", "w_down"}
    assert set(params["layers"][1]["mlp"]) == {
        "router", "router_bias", "w_gate", "w_up", "w_down"}
    for leaf in ("q_norm", "k_norm"):
        assert gqa[leaf].dtype == jnp.float32
    assert params["layers"][1]["mlp"]["router_bias"].dtype == jnp.float32
    assert conv["w_in"].dtype == conv["conv"].dtype == jnp.bfloat16
    assert params["embed"].dtype == jnp.bfloat16
    assert lfm2.SLOTS.serving_params(cfg, params) is not None
    # what writes into the stream: (2 x 24)^-1/2 from ``makers`` and a
    # multiple of the embedding's scale besides, never above 1: at this
    # width (sqrt(8 / 32) = 0.5) the mixers' 1, the feed-forwards' 0.4;
    # at the published width 0.2 and 0.05
    w = np.asarray(params["layers"][1]["mlp"]["w_down"], np.float32)
    assert w.shape == (4, 16, 32)
    assert abs(w.std() * 16 ** 0.5 * 48 ** 0.5 - 0.4) < 0.06
    for name, a, fan_in, by in (("w_out", conv, 32, 1.0), ("wo", gqa, 64, 1.0),
                                ("w_in", conv, 32, 48 ** 0.5)):
        std = float(np.asarray(a[name], np.float32).std())
        assert abs(std * fan_in ** 0.5 * 48 ** 0.5 / by - 1) < 0.1, name
    assert (lfm2.MIXER_TO_START * (8 / 2048) ** 0.5,
            lfm2.FF_TO_START * (8 / 2048) ** 0.5) == (0.2, 0.05)
    assert "out_damp" not in {f.name for f in dataclasses.fields(cfg)}
    e = np.asarray(params["embed"], np.float32)
    assert abs(e.std() * 2 - 1) < 0.05  # sqrt(8 / 32)
    assert (np.asarray(params["final_norm"]) < 0).any()
    assert (np.asarray(params["final_norm"]) > 0).any()
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == FAM.num_params(M)
