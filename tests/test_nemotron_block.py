"""The block of one sublayer a layer (``ray_tpu/models/nemotron.py``:
Mamba-2 mixers whose B and C come in groups of heads, two-matrix relu^2
experts beside a shared one behind a sigmoid router with a selection
bias and a scaling of 2.5, NoPE GQA layers, an untied head) against its
plain reference (``benchmark/families/nemotron_h.reference.py``) at tiny
sizes on the CPU, seeded: whole sequences; a bucket in four segments =
in one; the whole model through the engine's slots at ragged positions
for 120 steps, logits; each reading of the block left out FAILS the same
comparison by name; the eight shares of an expert layer;
``RaggedDecoder``; a reused slot; the spans.

The tiny size (``TINY_FIELDS``) keeps the published string's head and an
uneven tail (``MEM*EMEME``), 4 Mamba heads of 8 in TWO groups with a
state of 16, 4 heads of 16 over 2 kv heads on a hidden size of 32, 8
experts of which 2 are held, top-2, a scaling of 2.5.

In the tests the weights are drawn for a depth of 1
(``published_layers``), so that a block moves the stream by about its
own size: a part left out then shows in the logits.

Tolerances (readings of ``test_prefill_then_120_steps...``'s own
comparison, logits that spread by 1.0, this CPU). In float32 both sides
round nothing but their sums, in another order: the LARGEST difference
over the three prompts' 120 positions reads 2e-5 to 6e-5, and the
program with its matrices rounded to bf16 (8 mantissa bits) reads 0.05
and more. ``F32_TOL`` = 1e-3 stands between the two, and every
structural departure reads over ten times it
(``test_a_part_left_out_fails_the_comparison``). In bf16 a router
near-tie that flips an expert moves single logits by more than rounding
does, so bf16 is judged on the MEDIAN difference of a prompt's logits
against ``BF16_TOL``, and its control is the program with matrices of 3
mantissa bits (the nearest precision below).
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
from ray_tpu.models import granite, moe, nemotron
from ray_tpu.models.decode_engine import RaggedDecoder

F32_TOL = 1e-3
BF16_TOL = 0.06

FAM = manifest.family("nemotron_h")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
PROMPTS = (5, 23, 41)
STEPS = 120


def _cfg(**kw):
    m = {**M, "published_layers": 1, **kw}
    held = m.pop("held_experts")
    return nemotron.NemotronConfig(
        **m, held_experts=held and tuple(held), max_seq_len=512)


def _cut(params, bits: int):
    """Every matrix rounded to ``bits`` mantissa bits (8: bf16)."""
    drop = 23 - bits

    def cut(path, a):
        if getattr(path[-1], "key", None) in nemotron.SLOTS.F32_LEAVES:
            return a
        raw = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        raw = (raw + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
            ~((1 << drop) - 1) & 0xFFFFFFFF)
        return jax.lax.bitcast_convert_type(raw, jnp.float32).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, nemotron.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_published_sizes():
    """The cell's configuration through the family's ``fields`` and
    ``build``: the published widths, the whole string of 52 blocks (23
    M, 23 E, 6 attention at 5, 12, 19, 26, 33, 42: no period), eight
    groups of eight Mamba heads, 16 of 128 ungated experts held."""
    fam, m = manifest.model("nemotron-3-nano-30b-a3b-ep8-1chip")
    cfg = fam.build(m, max_seq_len=3088, remat=False).cfg
    assert (cfg.d_model, cfg.n_layers, cfg.vocab_size) == (2688, 52, 16384)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.ssm_layers, cfg.moe_layers, cfg.full_layers) == (23, 23, 6)
    assert [i for i, k in enumerate(cfg.pattern) if k == "*"] \
        == [5, 12, 19, 26, 33, 42]
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.conv_kernel) == (64, 64, 128, 8, 4)
    assert (cfg.inner, cfg.conv_width) == (4096, 6144)
    assert (cfg.d_ff, cfg.shared_d_ff) == (1856, 3712)
    assert (cfg.n_experts, cfg.top_k, cfg.held, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling_factor) == (128, 6, (0, 16), 1, 1, 2.5)
    assert cfg.router_softmax is False
    assert not hasattr(cfg, "norm_topk_eps")
    assert cfg.kv_width == 256 and cfg.slot_model is nemotron.SLOTS
    # a block's place among its kind: what the states' list, the k / v
    # stack and the routing counters index by
    assert [cfg.stack_index(i) for i in (0, 1, 2, 5, 6, 12, 51)] \
        == [0, 0, 1, 0, 2, 1, 22]
    # the compact branch stands at the two wider buckets, a quarter of
    # the assignments each, and not at 256 rows nor at a decode step
    assert [moe.compact_rows(cfg, rows * 6) for rows in (32, 256, 512, 1024)] \
        == [None, None, 768, 1536]
    assert nemotron.SLOTS.row_kinds(cfg) == {"recurrent": (23, 0),
                                             "full": (6, None)}
    with pytest.raises(ValueError, match="pattern"):
        nemotron.NemotronConfig(pattern="ME-M")
    with pytest.raises(ValueError, match="groups"):
        nemotron.NemotronConfig(ssm_heads=12, ssm_groups=8)


def test_the_mixers_groups_and_the_routers_are_read_by_their_own_keys():
    """``n_groups`` 8 is the mixer's, ``n_group`` 1 and ``topk_group`` 1
    the router's: swapped in the file, ``fields`` refuses (a router of
    eight groups is unproven here) and never reads one for the other;
    and the block's other refusals."""
    import json
    import os

    with open(os.path.join(manifest.HERE, "configs",
                           "nemotron-3-nano-30b-a3b-ep8-1chip.json")) as f:
        config = json.load(f)
    m = FAM.fields(config)
    assert (m["ssm_groups"], m["n_group"], m["topk_group"]) == (8, 1, 1)
    swapped = {**config, "n_groups": config["n_group"],
               "n_group": config["n_groups"]}
    with pytest.raises(manifest.ManifestError, match="one group"):
        FAM.fields(swapped)
    one = FAM.fields({**config, "n_groups": 1})
    assert (one["ssm_groups"], one["n_group"]) == (1, 1)
    for key, value in (
            ("hybrid_override_pattern", config["hybrid_override_pattern"][
                :-1] + "-"), ("mlp_hidden_act", "silu"),
            ("attention_bias", True), ("mlp_bias", True),
            ("mamba_proj_bias", True), ("use_conv_bias", False),
            ("tie_word_embeddings", True), ("topk_group", 2),
            ("n_groups", 5), ("norm_topk_prob", False),
            ("num_hidden_layers", 51), ("model_type", "granitemoehybrid")):
        with pytest.raises(manifest.ManifestError):
            FAM.fields({**config, key: value})


def test_forward_is_the_references_logits(model):
    """Whole sequences, two of them, in one segment."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 48), 1, 256)
    got = nemotron.forward(params, toks, cfg)
    want = REF.forward(params, toks, M)
    assert float(jnp.std(want)) > 0.5
    np.testing.assert_allclose(got, want, atol=F32_TOL)


def test_prefill_in_four_segments_is_prefill_in_one(monkeypatch, model):
    """Segmenting is arithmetic-neutral but for the order of float32
    sums: a 64-row bucket whole against the same in four segments of
    16, a prompt that ends inside the third: the stream, every M block's
    ``H`` and convolution rows, the attention block's rows and the loads
    agree; with ``live`` the dead segment is left out and the state is
    the same."""
    cfg, params = model
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 64), 1, 256)
    lens = jnp.array([64, 37])
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 64)
    assert nemotron.SLOTS.prefill_segments(cfg, 64) == 1
    h1, st1, (loads1, calls) = nemotron.prefill(params, toks, lens, cfg,
                                                loads=True)
    monkeypatch.setattr(moe, "SEGMENT_ROWS", 16)
    assert nemotron.SLOTS.prefill_segments(cfg, 64) == 4
    h4, st4, (loads4, _) = nemotron.prefill(params, toks, lens, cfg,
                                            loads=True)
    np.testing.assert_allclose(h4[0], h1[0], atol=1e-4)
    np.testing.assert_allclose(h4[1, :37], h1[1, :37], atol=1e-4)
    assert len(st1["ssm"]) == 4
    assert st1["ssm"][0]["h"].shape == (2, 4, 16, 8)  # (a head a lane row)
    assert st1["ssm"][0]["conv"].shape == (2, 3, 32 + 2 * 2 * 16)
    for a, b in zip(st1["ssm"], st4["ssm"]):
        np.testing.assert_allclose(b["h"], a["h"], atol=1e-4)
        np.testing.assert_allclose(b["conv"], a["conv"], atol=1e-4)
    for name in ("k_full", "v_full"):
        assert st1[name].shape == (1, 2, 64, cfg.kv_width)
        np.testing.assert_allclose(st4[name][:, 0], st1[name][:, 0],
                                   atol=1e-4)
        np.testing.assert_allclose(st4[name][:, 1, :37],
                                   st1[name][:, 1, :37], atol=1e-4)
    np.testing.assert_array_equal(loads4, loads1)
    assert loads1.shape == (4, 2) and int(loads1.sum()) > 0
    np.testing.assert_array_equal(calls, [0, 0])  # (a quarter is held)
    h_live, st_live, _ = nemotron.prefill(params, toks[1:], lens[1:], cfg,
                                          live=jnp.int32(37))
    # (a batch of one against a batch of two: float32 sums in another
    # order on this CPU)
    np.testing.assert_allclose(h_live[0, :37], h4[1, :37], atol=1e-4)
    assert not np.asarray(h_live[0, 48:]).any()
    for a, b in zip(st_live["ssm"], st4["ssm"]):
        np.testing.assert_allclose(a["h"][0], b["h"][1], atol=1e-4)
        np.testing.assert_allclose(a["conv"][0], b["conv"][1], atol=1e-4)


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state, each in a bucket longer than
    itself, then ``steps`` greedy steps of the model's ragged step with
    every slot at its own position and one slot inactive. -> for each
    prompt (its tokens followed by the generated ones, float32 logits
    [steps, V] from the last prompt position on)."""
    slots, max_len = 4, 288
    state = nemotron.SLOTS.init_state(cfg, slots, max_len)
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
    step = jax.jit(functools.partial(nemotron.SLOTS.step, cfg, params, None))
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
def test_prefill_then_120_steps_through_the_slots_is_the_references_forward(
        dtype, tol, control_bits, off, segments_of_16):
    """Nine blocks of the three kinds, a quarter of the experts held,
    three slots at different positions and one inactive (prompts of 5,
    23 and 41 tokens in buckets of 16 and 64, in segments of 16 rows:
    the last two cross segment boundaries): the logits of 120 decoded
    positions (``ssd_step`` on the carried grouped state, the attention
    step over the slot's rows) against the reference's full forward over
    prompt + tokens, whose SSM is the recurrence a token at a time,
    inside ``tol`` (``off``: the largest difference in float32, a
    prompt's median in bf16; module docstring); the control (matrices
    cut to ``control_bits`` mantissa bits) is outside it."""
    cfg = _cfg(dtype=dtype)
    params = nemotron.init_params(cfg, jax.random.PRNGKey(7))
    worst = _worst(cfg, params, _prompts(0), STEPS, off)
    assert worst < tol, worst
    control = _worst(cfg, _cut(params, control_bits), _prompts(0)[1:2], 40,
                     off, want_params=params)
    assert control > tol, (control, tol)


def test_a_short_prompt_in_a_long_bucket_is_the_reference_in_a_reused_slot(
        segments_of_16, model):
    """A reused slot shows nothing of its last stream: ``H`` and the
    convolution rows are replaced whole by ``scatter``, the attention
    block's rows behind the prompt are never read; padding reaches
    neither the state nor the logits."""
    cfg, params = model
    short_prompt_in_a_reused_slot(
        nemotron.SLOTS, cfg, params, lambda tokens: REF.forward(
            params, jnp.asarray([tokens]), M)[0], F32_TOL)


LEFT_OUT = ("silu_for_relu2", "a_gate", "one_group_for_the_mixers",
            "norm_over_all_channels", "no_2.5", "selection_bias",
            "renormalisation", "conv_bias", "d_skip", "bf16_state",
            "gate_after_the_norm")


@pytest.mark.parametrize("left_out", LEFT_OUT)
def test_a_part_left_out_fails_the_comparison(left_out, monkeypatch, model):
    """The float32 comparison catches each reading of the block taken
    another way: a silu for the squared relu, a gate on the experts
    (``silu(a) * a`` for ``relu(a)^2``), group 0's B and C for every
    group's, the gated norm over all channels, the scaling of 2.5 left
    out, the bias left out of the selection, the chosen scores not
    renormalised, the convolution's bias and the skip ``D x`` left out,
    the state ``H`` rounded to bf16 between steps, the gate after the
    norm."""
    cfg, params = model

    def without(leaf, value=0.0):
        return jax.tree_util.tree_map_with_path(
            lambda path, a: jnp.full_like(a, value)
            if getattr(path[-1], "key", None) == leaf else a, params)

    if left_out == "silu_for_relu2":
        monkeypatch.setattr(moe, "relu2", jax.nn.silu)
    elif left_out == "a_gate":
        monkeypatch.setattr(moe, "relu2", lambda up: jax.nn.silu(up) * up)
    elif left_out == "one_group_for_the_mixers":
        inputs = granite._ssm_inputs

        def one_group(cfg, p, x, conv_rows):
            z, xs, dt, b, c, u = inputs(cfg, p, x, conv_rows)
            return (z, xs, dt, jnp.broadcast_to(b[:, :, :1], b.shape),
                    jnp.broadcast_to(c[:, :, :1], c.shape), u)

        monkeypatch.setattr(granite, "_ssm_inputs", one_group)
    elif left_out == "norm_over_all_channels":
        monkeypatch.setattr(granite, "_ssm_out", _ssm_out_one_group)
    elif left_out == "no_2.5":
        route = moe.route
        monkeypatch.setattr(moe, "route", lambda c, scores, bias: route(
            _With(c, routed_scaling_factor=1.0), scores, bias))
    elif left_out == "selection_bias":
        params = without("router_bias")
    elif left_out == "renormalisation":
        def route(cfg, scores, bias):
            _, ids = jax.lax.top_k(scores + bias, cfg.top_k)
            return (jnp.take_along_axis(scores, ids, axis=-1)
                    * cfg.routed_scaling_factor, ids)

        monkeypatch.setattr(moe, "route", route)
    elif left_out == "conv_bias":
        params = without("conv_bias")
    elif left_out == "d_skip":
        params = without("d_skip")
    elif left_out == "bf16_state":
        step = granite._ssd_step

        def rounded(h, *a, **kw):
            new, y = step(h, *a, **kw)
            return new.astype(jnp.bfloat16).astype(jnp.float32), y

        monkeypatch.setattr(granite, "_ssd_step", rounded)
    elif left_out == "gate_after_the_norm":
        def gate_last(c, p, y, xs, z):
            b, t = y.shape[:2]
            g = c.ssm_groups
            y = (y + p["d_skip"][:, None] * xs).reshape(b, t, g, -1)
            y = granite.rms_norm(y, p["y_norm"].reshape(g, -1), c.rms_eps)
            y = y.reshape(b, t, -1) * jax.nn.silu(z)
            return y.astype(c.compute_dtype) @ p["w_out"]

        monkeypatch.setattr(granite, "_ssm_out", gate_last)
    forget_programs()
    try:
        got = _ragged_logits(cfg, params, _prompts(0)[1:2], 12)[0]
    finally:
        monkeypatch.undo()
        forget_programs()
    seq, rows = got
    want = np.asarray(REF.forward(model[1], jnp.asarray([seq]), M)[0])
    off = np.abs(rows - want[23:23 + len(rows)]).max()
    # (a state's eighth bit over twelve steps of a decay near 1 moves a
    # logit by 0.003: over the limit, not far over)
    margin = 2 if left_out == "bf16_state" else 10
    assert off > margin * F32_TOL, (left_out, off)


class _With:
    """A configuration's fields with some of them replaced."""

    def __init__(self, cfg, **fields):
        self._cfg = cfg
        self.__dict__.update(fields)

    def __getattr__(self, name):
        return getattr(self._cfg, name)


_SSM_OUT = granite._ssm_out


def _ssm_out_one_group(cfg, p, y, xs, z):
    return _SSM_OUT(_With(cfg, ssm_groups=1), p, y, xs, z)


# --------------------------------------------------------------- router


@pytest.mark.parametrize("seed", [0, 1, "ties"])
def test_route_is_the_published_order_scaled_by_2_5(seed):
    """128 wide and six chosen as in the cell, 8 and two as here:
    ``route`` over sigmoid scores with the selection bias gives the ids
    and weights of the published order, the weights summing to 2.5; on a
    tie (scores drawn from five values, no bias) both take the lower
    index."""
    for m in (M, {**M, "n_experts": 128, "top_k": 6}):
        cfg = _cfg(n_experts=m["n_experts"], top_k=m["top_k"],
                   held_experts=None)
        bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3),
                                        (m["n_experts"],))
        if seed == "ties":
            logits = jax.random.randint(
                jax.random.PRNGKey(9), (64, m["n_experts"]), 0, 5
            ).astype(jnp.float32) * 0.5
            bias = jnp.zeros_like(bias)
        else:
            logits = 2.0 * jax.random.normal(
                jax.random.PRNGKey(seed), (64, m["n_experts"]))
        weights, ids = moe.route(cfg, jax.nn.sigmoid(logits), bias)
        gates, chosen = REF.router(m, logits, bias)
        np.testing.assert_array_equal(ids, chosen)
        got = jnp.sum(jax.nn.one_hot(ids, cfg.n_experts)
                      * weights[..., None], -2)
        np.testing.assert_allclose(got, gates, rtol=1e-5, atol=1e-9)
        np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-4)


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the layer cut over eight chips, 16 experts
    of 128 each at a width that is not the hidden size. Each share
    routes over all 128 experts and computes its own 16 as two grouped
    products round a squared relu; the eight routed parts, with the
    shared expert (which every chip computes alike) counted ONCE, add up
    to the reference's layer with every expert held."""
    whole = _cfg(n_experts=128, top_k=6, held_experts=None)
    m = {**M, "n_experts": 128, "top_k": 6}
    p = nemotron.init_params(whole, jax.random.PRNGKey(5))["layers"][1][
        "mix"]
    assert set(p) == {"router", "router_bias", "w_up", "w_down",
                      "shared_up", "shared_down"}
    # (an ungated expert's w_up lies [count, F, D], as w_down does)
    assert p["w_up"].shape == p["w_down"].shape == (128, 24, 32)
    assert p["shared_up"].shape == (32, 48)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = REF.moe_layer(m, p, x, held=(0, 128))
        shared = REF.relu2_mlp(x, p["shared_up"], p["shared_down"])
    routed = {k: v for k, v in p.items() if not k.startswith("shared")}
    total = jnp.zeros_like(x)
    for first in range(0, 128, 16):
        share = {**routed, **{w: p[w][first:first + 16]
                              for w in ("w_up", "w_down")}}
        part = moe.moe(_cfg(n_experts=128, top_k=6,
                            held_experts=(first, 16)), share, x)
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                part, REF.moe_layer(m, share, x, held=(first, 16),
                                    shared=False), atol=2e-5)
        total = total + part
    assert float(jnp.abs(want - shared).max()) > 0.1
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    np.testing.assert_allclose(moe.moe(whole, p, x), want, atol=5e-5)


# ------------------------------------------------------ RaggedDecoder


@pytest.mark.parametrize("dtype, gap", [("float32", F32_TOL),
                                        ("bfloat16", 10 * BF16_TOL)])
def test_submit_and_pump_serve_the_references_tokens(dtype, gap):
    """``RaggedDecoder`` (submit -> pump) on the block: five streams
    over three slots, so slots are reused and streams sit at ragged
    positions; every stream's tokens are the reference's argmax wherever
    its top two logits lie further apart than ``gap``: the comparison's
    own tolerance in float32, ten times the median's in bf16, where a
    moved assignment of a top-2 of 8 scaled by 2.5 moves single logits
    by far more than the median does (few positions are that clear)."""
    cfg = _cfg(dtype=dtype)
    params = nemotron.init_params(cfg, jax.random.PRNGKey(8))
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
    assert clear_all >= (44 if dtype == "float32" else 2), clear_all
    st = eng.stats()
    assert st["state_bytes"] == {
        kind: 3 * n for kind, n in
        FAM.state_bytes_per_slot(M, 96, jnp.dtype(dtype).itemsize).items()}
    assert st["moe_assignments"] > 0 and st["moe_touched_expert_steps"] > 0


def test_the_block_refuses_what_needs_rows(model):
    cfg, params = model
    assert nemotron.SLOTS.rows_state is False
    with pytest.raises(ValueError, match="NemotronConfig"):
        RaggedDecoder(params, cfg, slots=2, max_len=64, chunk_tokens=4,
                      prompt_buckets=(16,), spec_depth=2)
    with pytest.raises(ValueError, match="prefix of cached rows"):
        nemotron.SLOTS.prefill(params, None, None, None, None, None, cfg,
                               64, prefix=object())


def test_the_32_slots_do_not_fall_into_one_cycle(model):
    """A seeded model under greedy traffic: 32 streams of different
    prompts on 32 slots still emit different tokens after 40 steps, and
    no stream's last 24 tokens are a short cycle (the next token depends
    on the context, not on the last token alone)."""
    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=32, max_len=96, chunk_tokens=8,
                        prompt_buckets=(16,))
    rng = np.random.RandomState(3)
    sids = [eng.submit(rng.randint(1, 256, 9 + i % 7).astype(np.int32), 40)
            for i in range(32)]
    eng.drain()
    tails = [tuple(eng.finished[s].tokens[-24:]) for s in sids]
    assert len(set(tails)) == 32
    assert min(len(set(t)) for t in tails) >= 10, tails


def test_spans_carry_both_kinds_of_state_and_the_routing_by_kind(
        segments_of_16, model):
    """``engine.state_init`` names both kinds with their layer counts (4
    M blocks, 1 attention block), ``engine.prefill`` the segments,
    ``engine.readback`` the routing of the step's FOUR expert blocks
    (counted by kind: the block has nine layers)."""
    from ray_tpu._private import flight_recorder as fr

    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                        prompt_buckets=(8, 64), name="nemotron-test")
    assert eng.row_kinds == {"recurrent": (4, 0), "full": (1, None)}
    sid = eng.submit(np.arange(1, 40, dtype=np.int32), 8)
    eng.drain()
    assert len(eng.finished[sid].tokens) == 8
    ring = list(fr._get().ring)
    init = [s["attrs"] for s in ring if s["name"] == "engine.state_init"
            and s["attrs"].get("engine") == "nemotron-test"][-1]
    per_slot = FAM.state_bytes_per_slot(M, 96, 4)
    assert init["recurrent_bytes"] == 2 * per_slot["recurrent"]
    assert init["full_bytes"] == 2 * per_slot["full"]
    assert (init["recurrent_layers"], init["full_layers"]) == (4, 1)
    assert init["full_row_bytes"] == FAM.kv_row_bytes(M, 4)
    assert (init["slots"], init["max_len"]) == (2, 96)
    pre = [s["attrs"] for s in ring if s["name"] == "engine.prefill"][-1]
    assert pre == {"bucket": 64, "prompts": 1, "rows": 1, "tokens": 39,
                   "segments": 4, "live_segments": 3}
    back = [s["attrs"] for s in ring if s["name"] == "engine.readback"
            and "held_assignments" in s["attrs"]][-1]
    assert back["live_rows"] == back["live_rows_full"] == 47
    assert back["live_rows_recurrent"] == 0
    assert back["assignments"] == M["top_k"]
    assert 0 <= back["experts_touched"] <= back["held_assignments"] \
        <= M["top_k"]
    loads = [s["attrs"] for s in ring if s["name"] == "engine.readback"
             and "expert_load_max" in s["attrs"]][-1]
    assert loads["expert_load_max"] >= loads["expert_load_mean"] > 0


def test_init_params_draws_this_blocks_leaves_in_blocks(monkeypatch):
    """The three kinds' leaves and shapes; an expert block has NO
    ``w_gate`` and no ``shared_gate``; a leaf larger than a block drawn
    block by block; the matrices that write into the stream scaled for
    the published depth; Mamba-2's own leaves in float32, ``dt_bias``
    the inverse softplus of a step between the floor and 0.1; two
    matrices for embedding and head."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    cfg = _cfg(dtype="bfloat16", published_layers=52)
    params = nemotron.init_params(cfg, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "layers", "final_norm", "lm_head"}
    assert [set(p) for p in params["layers"]] == [{"norm", "mix"}] * 9
    mamba, experts, gqa = (params["layers"][i]["mix"] for i in (0, 1, 3))
    assert set(mamba) == {"w_in", "conv", "conv_bias", "a_log", "dt_bias",
                          "d_skip", "y_norm", "w_out"}
    assert mamba["w_in"].shape == (32, 32 + 96 + 4)
    assert mamba["conv"].shape == (4, 96) and mamba["y_norm"].shape == (32,)
    assert set(gqa) == {"w_qkv", "wo"}
    assert gqa["w_qkv"].shape == (32, (4 + 2 * 2) * 16)
    assert set(experts) == {"router", "router_bias", "w_up", "w_down",
                            "shared_up", "shared_down"}
    assert experts["w_up"].shape == experts["w_down"].shape == (2, 24, 32)
    for leaf in ("a_log", "dt_bias", "d_skip", "conv_bias", "y_norm"):
        assert mamba[leaf].dtype == jnp.float32, leaf
    assert experts["router_bias"].dtype == jnp.float32
    assert mamba["w_in"].dtype == mamba["conv"].dtype == jnp.bfloat16
    dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))
    assert (dt >= 1e-4 - 1e-7).all() and (dt <= 0.1 + 1e-6).all()
    assert nemotron.SLOTS.serving_params(cfg, params) is not None
    # what writes into the stream: (2 x 52)^-1/2 from ``makers`` and the
    # rule of ``init_params`` besides (the mixers' x 5, the expert
    # blocks' x 0.25); what does not: its fan-in alone (w_up's is the
    # hidden size, its LAST axis: the matrix lies [F, D])
    assert (nemotron.MIXER_WRITES, nemotron.EXPERT_WRITES) == (5.0, 0.25)
    for name, a, fan_in, by in (
            ("w_out", mamba, 32, 104 ** 0.5 / 5),
            ("wo", gqa, 64, 104 ** 0.5 / 5),
            ("w_down", experts, 24, 104 ** 0.5 * 4),
            ("w_up", experts, 32, 1.0),
            ("shared_down", experts, 48, 104 ** 0.5 * 4),
            ("w_in", mamba, 32, 1.0)):
        std = float(np.asarray(a[name], np.float32).std())
        assert abs(std * fan_in ** 0.5 * by - 1) < 0.12, (name, std)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert n == FAM.num_params(M)
