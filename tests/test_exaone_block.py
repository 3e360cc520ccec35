"""The block with sliding-window layers beside full ones
(``ray_tpu/models/exaone.py``) against its plain reference
(``benchmark/families/exaone_moe.reference.py``) at tiny sizes on the
CPU, seeded: what a prompt leaves in a ring; prefill and then decoding
through the rings and the full stack = the reference's full forward with
its band mask written out, logits, with prompts shorter than, equal to
and longer than the window, decoded past two wraps of the ring, slots at
different positions and an inactive slot among them; the whole model
through ``RaggedDecoder``; a reused slot; the router without a group
limit; the eight shares of one expert layer add up to the uncut layer;
and the three mechanisms that refuse a ring.

The tiny size keeps both kinds of layer (three sliding to one full), a
dense and four sparse MLPs, a window of 8 (smaller than every sequence
but one prompt), and 8 heads x 16 = 128 unequal to the hidden 48.

Tolerances (readings of ``test_prefill_then_ragged_decode...``'s own
comparison, logits that spread by 1.03, this CPU). In float32 both sides
round nothing but their sums, in another order: the LARGEST difference
reads 1.4e-6 to 1.9e-6 over the four prompts, and the control, the same
program with its matrices rounded to bf16 (8 mantissa bits), 6.8e-3 to
3.6e-2; ``F32_TOL`` = 1e-4 is about their geometric mean. That limit
also fails float32 statistics computed in bf16: the router's scores
rounded to bf16 read 3.8e-2 (experts flip), the softmax's 8.4e-3
(``test_bf16_where_float32_is_stated_fails``). In bf16 a router
near-tie that flips an expert moves single logits by more than rounding
does (largest 0.046, 99th percentile 0.022), so bf16 is judged on the
MEDIAN difference of a prompt's logits: the program reads 0.0042-0.0047
over the four prompts, the control (matrices cut to 3 mantissa bits, the
nearest precision below) 0.041-0.046; ``BF16_TOL`` = 0.014 is their
geometric mean.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import exaone, moe
from ray_tpu.models.decode_engine import RaggedDecoder

F32_TOL = 1e-4
BF16_TOL = 0.014

FAM = manifest.family("exaone_moe")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
W = M["sliding_window"]


def _cfg(**kw):
    m = {**M, **kw}
    held = m.pop("held_experts")
    return exaone.ExaoneConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_types": tuple(m["layer_types"]),
        "mlp_layer_types": tuple(m["mlp_layer_types"])}, max_seq_len=256)


def _cut(params, bits: int):
    """Every matrix rounded to ``bits`` mantissa bits (8: bf16)."""
    drop = 23 - bits

    def cut(path, a):
        if getattr(path[-1], "key", None) in exaone.SLOTS.F32_LEAVES:
            return a
        raw = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        raw = (raw + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
            ~((1 << drop) - 1) & 0xFFFFFFFF)
        return jax.lax.bitcast_convert_type(raw, jnp.float32).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, exaone.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_pattern_as_data():
    cfg = _cfg()
    assert cfg.n_heads * cfg.head_dim != cfg.d_model
    assert (cfg.window_layers, cfg.full_layers, cfg.moe_layers) == (4, 1, 4)
    assert [cfg.stack_index(i) for i in range(5)] == [0, 1, 2, 0, 3]
    assert [cfg.windowed(i) for i in range(5)] == [1, 1, 1, 0, 1]
    # the published pattern is the default: three sliding, one full
    whole = exaone.ExaoneConfig()
    assert whole.layer_types[:8] == (exaone.SLIDING,) * 3 + (exaone.FULL,) \
        + (exaone.SLIDING,) * 3 + (exaone.FULL,)
    assert (whole.window_layers, whole.full_layers) == (36, 12)
    assert whole.mlp_layer_types.count(exaone.DENSE) == 1
    assert (whole.n_heads * whole.head_dim, whole.kv_width) == (8192, 1024)
    with pytest.raises(ValueError, match="layer_types"):
        exaone.ExaoneConfig(n_layers=3, layer_types=("full_attention",))
    with pytest.raises(ValueError, match="layer_types"):
        exaone.ExaoneConfig(n_layers=1, layer_types=("linear",))


# ------------------------------------------------------------ the ring


@pytest.mark.parametrize("n", [1, 5, 8, 9, 16, 21, 40])
def test_a_prompt_leaves_its_last_window_in_the_ring_at_its_offsets(n):
    """Of ``n`` real rows (in a bucket of 40) ring row r holds the last
    position p < n with p % 8 == r, and zeros where no position landed:
    shorter than, equal to and longer than the window."""
    rows = jnp.arange(1, 41, dtype=jnp.float32)[None, :, None] \
        * jnp.ones((2, 1, 3))
    got = np.asarray(exaone.ring_rows(rows, jnp.array([n, n]), W))
    assert got.shape == (2, W, 3)
    want = np.zeros((W,))
    for p in range(n):
        want[p % W] = p + 1  # a later position overwrites an earlier one
    np.testing.assert_array_equal(got[0, :, 0], want)
    np.testing.assert_array_equal(got[1], got[0])
    if n >= W:
        assert sorted(got[0, :, 0]) == list(range(n - W + 1, n + 1))


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps, spare_slot: int = 1):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state (``spare_slot`` stays empty and
    inactive), then ``steps`` greedy steps of the model's ragged step
    with every slot at its own position. -> for each prompt (its tokens
    followed by the generated ones, float32 logits [steps, V] from the
    last prompt position on)."""
    slots, max_len = len(prompts) + 1, 96
    state = exaone.SLOTS.init_state(cfg, slots, max_len)
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
    step = jax.jit(functools.partial(exaone.SLOTS.step, cfg, params, None))
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


@pytest.mark.parametrize("dtype, tol, control_bits, off", [
    ("float32", F32_TOL, 8, np.max), ("bfloat16", BF16_TOL, 3, np.median)])
def test_prefill_then_ragged_decode_is_the_references_forward(
        dtype, tol, control_bits, off):
    """Five layers, a quarter of the experts held, four slots at
    different positions and a fifth inactive among them, 20 decoded
    positions each (a window of 8: two wraps of every ring and more):
    the logits of every decoded position against the reference's full
    forward over prompt + tokens, inside ``tol`` (``off``: the largest
    difference in float32, a prompt's median in bf16; module docstring);
    the control (matrices cut to ``control_bits`` mantissa bits) is
    outside it."""
    cfg = _cfg(dtype=dtype)
    params = exaone.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, n).astype(np.int32) for n in PROMPTS]
    steps = 20
    assert steps > 2 * W
    worst = 0.0
    for (seq, got), p in zip(_ragged_logits(cfg, params, prompts, steps),
                             prompts):
        want = np.asarray(REF.forward(params, jnp.asarray([seq]), M)[0])
        # step j's logits are the position's after len(p) + j tokens
        worst = max(worst, off(np.abs(
            got - want[len(p):len(p) + len(got)])))
    assert worst < tol, worst
    cut = _cut(params, control_bits)
    seq, got = _ragged_logits(cfg, cut, prompts[2:3], 6)[0]
    want = np.asarray(REF.forward(params, jnp.asarray([seq]), M)[0])
    control = off(np.abs(got - want[23:23 + len(got)]))
    assert control > tol, (control, tol)


@pytest.mark.parametrize("what", ["router_scores", "softmax"])
def test_bf16_where_float32_is_stated_fails(what, monkeypatch):
    """The float32 tolerance is tight enough for the statistics the
    configuration states in float32: with the router's scores, or the
    attention's probabilities' statistics, rounded to bf16 in an
    otherwise float32 program the comparison fails."""
    cfg = _cfg()
    params = exaone.init_params(cfg, jax.random.PRNGKey(7))
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if what == "router_scores":
        sigmoid = jax.nn.sigmoid
        monkeypatch.setattr(moe.jax.nn, "sigmoid",
                            lambda a: bf16(sigmoid(bf16(a))))
    else:
        softmax = jax.nn.softmax
        monkeypatch.setattr(exaone.jax.nn, "softmax",
                            lambda a, axis=-1: bf16(softmax(bf16(a), axis)))
    toks = np.random.RandomState(3).randint(1, 256, (1, 30)).astype(np.int32)
    got = exaone.forward(params, jnp.asarray(toks), cfg)
    monkeypatch.undo()
    want = REF.forward(params, jnp.asarray(toks), M)
    assert float(jnp.abs(got - want).max()) > F32_TOL
    clean = exaone.forward(params, jnp.asarray(toks), cfg)
    assert float(jnp.abs(clean - want).max()) < F32_TOL


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_submit_and_pump_serve_the_references_tokens(dtype):
    """``RaggedDecoder`` (submit -> pump) on the model: five streams
    over three slots, so slots are reused and streams sit at ragged
    positions, every one decoded past two wraps of its rings; every
    stream's tokens pass the reference's ``check_served_tokens`` and, in
    float32, are its argmax outright."""
    cfg = _cfg(dtype=dtype)
    params = exaone.init_params(cfg, jax.random.PRNGKey(8))
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
    assert by_kind["full"] == st["attn_live_rows"]
    assert 0 < by_kind["window"] < by_kind["full"]


def test_spans_carry_the_state_and_the_rows_by_kind(model):
    from ray_tpu._private import flight_recorder as fr

    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=64, chunk_tokens=4,
                        prompt_buckets=(16,), name="exaone-test")
    eng.submit(np.arange(1, 13, dtype=np.int32), 8)
    eng.drain()
    spans = [s for s in fr._get().ring if s["attrs"].get("engine")
             == "exaone-test" or s["name"] == "engine.readback"]
    init = [s for s in spans if s["name"] == "engine.state_init"][-1]["attrs"]
    assert (init["slots"], init["max_len"]) == (2, 64)
    assert (init["window_layers"], init["full_layers"]) == (4, 1)
    per_slot = FAM.state_bytes_per_slot(M, 64, 4)
    assert init["window_bytes"] == 2 * per_slot["window"]
    assert init["full_bytes"] == 2 * per_slot["full"]
    backs = [s["attrs"] for s in spans if s["name"] == "engine.readback"
             and "live_rows_window" in s["attrs"]][-2:]
    # one occupied slot: 12 prompt rows, then 4 more a chunk; the full
    # layer holds them all, a ring its window
    assert [b["live_rows_full"] for b in backs] == [16, 20]
    assert [b["live_rows_window"] for b in backs] == [W, W]
    assert backs[-1]["live_rows"] == backs[-1]["live_rows_full"]
    # one active slot: top_k assignments a step and layer, some held
    assert backs[-1]["assignments"] == M["top_k"]
    assert 0 <= backs[-1]["held_assignments"] <= M["top_k"]
    assert backs[-1]["experts_touched"] <= backs[-1]["held_assignments"]
    # the Llama block's engine names no kinds of rows
    from ray_tpu.models import llama

    lcfg = llama.LlamaConfig.tiny()
    plain = RaggedDecoder(llama.init_params(lcfg, jax.random.PRNGKey(0)),
                          lcfg, slots=2, max_len=32, name="llama-plain")
    assert plain.row_kinds == {}
    assert "attn_live_rows_by_kind" not in plain.stats()


# ------------------------------------- the router and the shares


def test_without_groups_the_router_picks_the_plain_top_k_of_the_bias():
    """``n_group`` 1 / ``topk_group`` 1: the ``top_k`` largest BIASED
    scores, weighed by their unbiased scores renormalised and scaled;
    the program's router is the reference's."""
    cfg = _cfg()
    scores = jax.nn.sigmoid(jax.random.normal(
        jax.random.PRNGKey(0), (64, M["n_experts"])))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (M["n_experts"],))
    weights, ids = moe.route(cfg, scores, bias)
    plain = np.argsort(-np.asarray(scores + bias), -1,
                       kind="stable")[:, :M["top_k"]]
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(plain, -1))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    gates, chosen = REF.router(M, scores, bias)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(chosen, -1))
    got = jnp.sum(jax.nn.one_hot(ids, M["n_experts"]) * weights[..., None],
                  -2)
    np.testing.assert_allclose(got, gates, atol=1e-6)
    # all equal: exactly top_k, the lowest ids, in both
    _, ids = moe.route(cfg, jnp.full((2, M["n_experts"]), 0.5),
                       jnp.zeros((M["n_experts"],)))
    assert sorted(np.asarray(ids[0])) == [0, 1, 2, 3]


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The guide's section 4: the layer cut over eight chips by index of
    expert. Each share routes over all 16 experts and computes its own
    2; the eight partial results, the shared expert counted once, add up
    to the reference's layer with every expert held."""
    whole = _cfg(held_experts=None)
    p = exaone.init_params(whole, jax.random.PRNGKey(5))["layers"][1]["mlp"]
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
    # the uncut program layer is the uncut reference layer too
    np.testing.assert_allclose(moe.moe(whole, p, x), want, atol=5e-5)


def test_init_params_draws_this_blocks_leaves_in_blocks(monkeypatch):
    """The attention's and the experts' shapes, a leaf larger than a
    block drawn block by block (a block is 4,096 numbers here, the
    leaf read 6,144: two blocks), the published count of parameters
    (the types: ``tests/test_slot_protocol.py``)."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    cfg = _cfg(dtype="bfloat16")
    params = exaone.init_params(cfg, jax.random.PRNGKey(0))
    attn = params["layers"][3]["attn"]
    assert attn["w_qkv"].shape == (48, (8 + 2 * 2) * 16)
    assert attn["wo"].shape == (128, 48)
    assert attn["q_norm"].shape == attn["k_norm"].shape == (16,)
    w = np.asarray(params["layers"][1]["mlp"]["w_gate"], np.float32)
    assert w.shape == (4, 48, 32) and abs(w.std() * 48 ** 0.5 - 1) < 0.1
    assert not np.array_equal(w[0], w[1])
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == FAM.num_params(M)
