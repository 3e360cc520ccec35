"""The hybrid block (``ray_tpu/models/ling.py``) against its plain
reference (``benchmark/families/ling.reference.py``) at tiny sizes on the
CPU, seeded: the chunkwise KDA prefill = stepping = the reference's
recurrence; the absorbed MLA decode through the latent cache = the
reference's unabsorbed forward; the router; the four shares of one expert
layer add up to the uncut layer; the whole model through
``RaggedDecoder`` at ragged positions, logits. (A reused slot, the three
mechanisms that refuse a state that is not rows and the serving types:
``tests/test_slot_protocol.py``, every block's.)

Tolerances (readings of ``test_prefill_then_ragged_decode...``'s own
comparison, logits that spread by 1, this CPU). In float32 both sides
round nothing but their sums, in another order: the LARGEST difference
reads 1.2e-6, and the control, the same program with its matrices
rounded to bf16 (8 mantissa bits), 6.1e-3; ``F32_TOL`` = 1e-4 is about
their geometric mean. In bf16 a router near-tie that flips an expert
moves single logits by more than rounding does (largest 0.058, 99th
percentile 0.022), so bf16 is judged on the MEDIAN difference of a
prompt's logits: the program reads 0.0046-0.0049 over the three prompts,
the control (matrices cut to 3 mantissa bits, the nearest precision
below) 0.030-0.032; ``BF16_TOL`` = 0.012 is about their geometric mean.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import ling, moe
from ray_tpu.models.decode_engine import RaggedDecoder

F32_TOL = 1e-4
BF16_TOL = 0.012

FAM = manifest.family("ling")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)


def _cfg(**kw):
    held = kw.pop("held_experts", M["held_experts"])
    return ling.LingConfig(**{**M, **kw, "held_experts": held and tuple(held)},
                           max_seq_len=256)


def _cut(params, bits: int):
    """Every matrix rounded to ``bits`` mantissa bits (8: bf16)."""
    drop = 23 - bits

    def cut(path, a):
        if getattr(path[-1], "key", None) in ling.SLOTS.F32_LEAVES:
            return a
        raw = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        raw = (raw + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
            ~((1 << drop) - 1) & 0xFFFFFFFF)
        return jax.lax.bitcast_convert_type(raw, jnp.float32).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, ling.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------------------ KDA


@pytest.mark.parametrize("t", [1, 63, 64, 65, 200])
def test_kda_chunkwise_prefill_is_stepping_is_the_recurrence(t):
    """One KDA layer over ``t`` tokens, chunks of 64: the chunkwise form,
    ``t`` single steps and the reference's token-by-token recurrence
    give the same outputs, leave the same S and the same last three
    convolution inputs."""
    cfg = _cfg(kda_chunk=64)
    p = ling.init_params(cfg, jax.random.PRNGKey(t))["layers"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(100 + t), (2, t, cfg.d_model))
    y, st = ling.kda_prefill(cfg, p, x, jnp.array([t, t]))
    on = jnp.ones((2,), bool)
    zero = jax.tree_util.tree_map(jnp.zeros_like, st)
    st_step, ys = jax.lax.scan(
        lambda s, x_t: ling.kda_step(cfg, p, x_t, s, on)[::-1], zero,
        jnp.moveaxis(x, 1, 0)[:, :, None])
    y_step = jnp.moveaxis(ys[:, :, 0], 0, 1)
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta, u = REF.kda_inputs(M, p, x)
        _, s_ref = REF.kda_recurrence(q, k, v, g, beta)
        y_ref = REF._kda(M, p, x)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(y_step, y_ref, atol=2e-5)
    np.testing.assert_allclose(st["s"], s_ref, atol=2e-5)
    np.testing.assert_allclose(st_step["s"], s_ref, atol=2e-5)
    rows = jnp.pad(u, ((0, 0), (3, 0), (0, 0)))[:, -3:]
    np.testing.assert_array_equal(st["conv"], rows)
    np.testing.assert_array_equal(st_step["conv"], rows)


def test_kda_padding_leaves_the_state_of_the_real_tokens():
    """A prompt right-padded to its bucket: S and the convolution rows
    are those after the last REAL token."""
    cfg = _cfg()
    p = ling.init_params(cfg, jax.random.PRNGKey(1))["layers"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, cfg.d_model))
    _, padded = ling.kda_prefill(cfg, p, x, jnp.array([13]))
    _, exact = ling.kda_prefill(cfg, p, x[:, :13], jnp.array([13]))
    np.testing.assert_allclose(padded["s"], exact["s"], atol=1e-6)
    np.testing.assert_array_equal(padded["conv"], exact["conv"])


# ------------------------------------------------------------------ MLA


def test_mla_absorbed_decode_over_the_latent_cache_is_the_reference():
    """Positions 0..T-1 one at a time through ``mla_step`` (absorbed,
    over rows of latent and one rotated key) against the reference's
    unabsorbed forward; the prefill's rows are the cache's rows."""
    cfg = _cfg()
    p = ling.init_params(cfg, jax.random.PRNGKey(3))["layers"][5]["attn"]
    t = 19
    x = jax.random.normal(jax.random.PRNGKey(4), (2, t, cfg.d_model))
    y_pre, rows = ling.mla_prefill(cfg, p, x)
    assert rows["latent"].shape == (2, t, cfg.kv_lora_rank)
    assert rows["k_rope"].shape == (2, t, cfg.qk_rope_head_dim)
    cache, ys = jax.lax.scan(
        lambda c, xp: ling.mla_step(cfg, p, xp[0], c, xp[1])[::-1],
        {k: jnp.zeros((2, t + 5, a.shape[-1])) for k, a in rows.items()},
        (jnp.moveaxis(x, 1, 0)[:, :, None],
         jnp.broadcast_to(jnp.arange(t)[:, None], (t, 2))))
    with jax.default_matmul_precision("highest"):
        y_ref = REF._mla(M, p, x)
    np.testing.assert_allclose(jnp.moveaxis(ys[:, :, 0], 0, 1), y_ref,
                               atol=2e-5)
    np.testing.assert_allclose(y_pre, y_ref, atol=2e-5)
    for k in rows:
        np.testing.assert_allclose(cache[k][:, :t], rows[k], atol=1e-6)
        assert not np.asarray(cache[k][:, t:]).any()


# --------------------------------------------------------------- router


def _route_both(scores, bias, **kw):
    cfg, m = _cfg(**kw), {**M, **kw}
    weights, ids = moe.route(cfg, scores, bias)
    gates, chosen = REF.router(m, scores, bias)
    got = jnp.sum(jax.nn.one_hot(ids, cfg.n_experts) * weights[..., None], -2)
    return np.asarray(weights), np.asarray(ids), np.asarray(got), \
        np.asarray(gates), np.asarray(chosen)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_against_the_reference_on_seeded_scores(seed):
    scores = jax.nn.sigmoid(jax.random.normal(
        jax.random.PRNGKey(seed), (64, M["n_experts"])))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(seed + 10),
                                   (M["n_experts"],))
    weights, ids, got, gates, chosen = _route_both(scores, bias)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(got, gates, atol=1e-6)
    # renormalised and scaled; the bias is not in the weights
    np.testing.assert_allclose(weights.sum(-1), 2.5, rtol=1e-5)
    picked = np.take_along_axis(np.asarray(scores), ids, -1)
    np.testing.assert_allclose(weights, 2.5 * picked
                               / picked.sum(-1, keepdims=True), rtol=1e-5)
    # the group limit: the chosen lie in topk_group of the n_group groups
    per_group = M["n_experts"] // M["n_group"]
    assert all(len(set(row // per_group)) <= M["topk_group"] for row in ids)


def test_the_bias_moves_the_selection_only():
    scores = jnp.full((1, M["n_experts"]), 0.5).at[0, :4].set(0.9)
    none = jnp.zeros((M["n_experts"],))
    _, ids, *_ = _route_both(scores, none)
    assert sorted(ids[0]) == [0, 1, 2, 3]
    # a bias that lifts group 3's experts over everything: they are
    # chosen, and weighed by their UNBIASED scores (all 0.5: a quarter
    # of 2.5 each)
    bias = none.at[24:28].set(1.0)
    weights, ids, *_ = _route_both(scores, bias)
    assert sorted(ids[0]) == [24, 25, 26, 27]
    np.testing.assert_allclose(weights[0], 2.5 / 4, rtol=1e-6)


def test_exactly_top_k_are_chosen_on_ties():
    """All scores equal: groups 0 and 1 win the tie, and of their 16
    experts exactly top_k, the lowest ids, in the program and in the
    reference alike."""
    scores = jnp.full((3, M["n_experts"]), 0.5)
    weights, ids, got, gates, chosen = _route_both(
        scores, jnp.zeros((M["n_experts"],)))
    assert ids.shape == (3, M["top_k"])
    assert sorted(ids[0]) == sorted(chosen[0]) == [0, 1, 2, 3]
    assert (np.count_nonzero(got, -1) == M["top_k"]).all()
    np.testing.assert_allclose(got, gates, atol=1e-6)


# ------------------------------------------------------ the share test


def test_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the layer cut over four chips by groups of
    experts. Each share routes over all 32 experts and computes its own
    8; the four partial results, the shared expert counted once, add up
    to the reference's layer with every expert held. A share whose
    experts nobody chose adds the shared expert alone."""
    whole = _cfg(held_experts=None)
    p = ling.init_params(whole, jax.random.PRNGKey(5))["layers"][1]["mlp"]
    assert p["w_gate"].shape[0] == 32
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 9, whole.d_model))
    with jax.default_matmul_precision("highest"):
        want = REF.moe_layer(M, p, x, held=(0, 32))
        shared = REF._swiglu(x, p["shared_gate"], p["shared_up"],
                             p["shared_down"])
    total = jnp.zeros_like(x)
    for first in (0, 8, 16, 24):
        share = {**p, **{w: p[w][first:first + 8]
                         for w in ("w_gate", "w_up", "w_down")}}
        aux = {}
        part = moe.moe(_cfg(held_experts=(first, 8)), share, x, aux)
        assert aux["expert_ids"].shape == (2, 9, M["top_k"])
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                part, REF.moe_layer(M, share, x, held=(first, 8)),
                atol=2e-5)
        total = total + (part - shared)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    # the uncut program layer is the uncut reference layer too
    np.testing.assert_allclose(moe.moe(whole, p, x), want, atol=5e-5)


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state, then ``steps`` greedy steps of the
    model's ragged step with every slot at its own position. -> for
    each prompt (its tokens followed by the generated ones, float32
    logits [1 + steps, V] from the last prompt position on)."""
    slots, max_len = 4, 96
    state = ling.SLOTS.init_state(cfg, slots, max_len)
    cur = jnp.zeros((slots,), jnp.int32)
    seqs, rows = {}, {}
    for slot, p in zip((2, 0, 3), prompts):
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
    step = jax.jit(functools.partial(ling.SLOTS.step, cfg, params, None))
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


@pytest.mark.parametrize("dtype, tol, control_bits, off", [
    ("float32", F32_TOL, 8, np.max), ("bfloat16", BF16_TOL, 3, np.median)])
def test_prefill_then_ragged_decode_is_the_references_forward(
        dtype, tol, control_bits, off):
    """Seven layers of every kind, a quarter of the experts held, three
    slots at different positions (prompts of 5, 23 and 41 tokens: the
    last two cross chunk boundaries of the KDA prefill): the logits of
    every decoded position against the reference's full forward over
    prompt + tokens, inside ``tol`` (``off``: the largest difference
    in float32, a prompt's median in bf16; module docstring); the
    control (matrices cut to ``control_bits`` mantissa bits) is outside
    it."""
    cfg = _cfg(dtype=dtype)
    params = ling.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, n).astype(np.int32) for n in (5, 23, 41)]
    worst = 0.0
    for (seq, got), p in zip(_ragged_logits(cfg, params, prompts, 10),
                             prompts):
        want = np.asarray(REF.forward(params, jnp.asarray([seq]), M)[0])
        # step j's logits are the position's after len(p) + j tokens
        worst = max(worst, off(np.abs(
            got - want[len(p):len(p) + len(got)])))
    assert worst < tol, worst
    cut = _cut(params, control_bits)
    seq, got = _ragged_logits(cfg, cut, prompts[1:2], 6)[0]
    want = np.asarray(REF.forward(params, jnp.asarray([seq]), M)[0])
    control = off(np.abs(got - want[23:23 + len(got)]))
    assert control > tol, (control, tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_submit_and_pump_serve_the_references_tokens(dtype):
    """``RaggedDecoder`` (submit -> pump) on the hybrid model: five
    streams over three slots, so slots are reused and streams sit at
    ragged positions; every stream's tokens pass the reference's
    ``check_served_tokens`` and, in float32, are its argmax outright."""
    cfg = _cfg(dtype=dtype)
    params = ling.init_params(cfg, jax.random.PRNGKey(8))
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


def test_readback_counts_the_held_share_and_weights_can_be_swapped(model):
    from ray_tpu._private import flight_recorder as fr

    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=64, chunk_tokens=4,
                        prompt_buckets=(8,), name="ling-test")
    sid = eng.submit(np.arange(1, 8, dtype=np.int32), 8)
    eng.drain()
    first = list(eng.finished[sid].tokens)
    spans = [s for s in fr._get().ring if s["attrs"].get("engine")
             == "ling-test" or s["name"] == "engine.readback"]
    init = [s for s in spans if s["name"] == "engine.state_init"][-1]
    assert init["attrs"]["slots"] == 2 and init["attrs"]["max_len"] == 64
    assert init["attrs"]["recurrent_bytes"] + init["attrs"]["latent_bytes"] \
        == sum(eng.state_bytes.values())
    back = [s["attrs"] for s in spans if s["name"] == "engine.readback"
            and "held_assignments" in s["attrs"]][-1]
    # one active slot: top_k assignments a step and layer, some held
    assert back["assignments"] == M["top_k"]
    assert 0 <= back["held_assignments"] <= M["top_k"]
    assert back["experts_touched"] <= back["held_assignments"]
    # set_params: another tree, other tokens; the first again, the first
    eng.set_params(ling.init_params(cfg, jax.random.PRNGKey(99)), 1)
    sid2 = eng.submit(np.arange(1, 8, dtype=np.int32), 8)
    eng.drain()
    assert list(eng.finished[sid2].tokens) != first
    eng.set_params(params, 2)
    sid3 = eng.submit(np.arange(1, 8, dtype=np.int32), 8)
    eng.drain()
    assert list(eng.finished[sid3].tokens) == first


def test_init_params_draws_a_large_leaf_in_blocks(monkeypatch):
    """A leaf larger than a block drawn block by block (the same values
    whatever the block size would be a different draw: only shapes and
    statistics are held; the types: ``tests/test_slot_protocol.py``).
    A block is 4,096 numbers here: the leaf read, 16,384, is drawn in
    four, and the small leaves whole (blocks of 1,024 put 75 of the
    129 leaves through a loop of their own, 37 s of compiling)."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    cfg = _cfg(dtype="bfloat16")
    params = ling.init_params(cfg, jax.random.PRNGKey(0))
    w = np.asarray(params["layers"][1]["mlp"]["w_gate"], np.float32)
    assert w.shape == (8, 64, 32) and abs(w.std() * 8 - 1) < 0.1
    assert len(np.unique(w[0])) > 100 and not np.array_equal(w[0], w[1])
