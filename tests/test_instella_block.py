"""The block with gated latent attention in every layer, YaRN's rotary
and the FarSkip residual path (``ray_tpu/models/instella.py``) against
its plain reference (``benchmark/families/instella_moe.reference.py``)
at tiny sizes on the CPU, seeded: prefill (unabsorbed) and then decoding
(absorbed, over the slots' rows) = the reference's full forward, logits,
with prompts in buckets longer than themselves, slots at different
positions and an inactive slot among them, every sequence past the
trained range of the rotary; the latent kernel in interpret mode = the
XLA body; FarSkip, the gate or YaRN's blend left out FAIL the same
comparison; the whole model through ``RaggedDecoder``; a reused slot;
the spans; and the three mechanisms that refuse rows that are no k / v.

The tiny size keeps a dense and three sparse MLPs with two shared
experts side by side and every expert held, 4 heads of 24 + 8 (the
rotated part) and values of 32 on a latent of 32, rows of 40 stored as
128, and a trained range of 16 positions stretched eightfold, shorter
than every sequence decoded here.

Tolerances (readings of ``test_prefill_then_ragged_decode...``'s own
comparison, logits that spread by 1.08, this CPU). In float32 both sides
round nothing but their sums, in another order (and the absorbed step
multiplies in another order than the reference's unabsorbed scores):
the LARGEST difference reads 1.4e-6 to 1.7e-6 over the four prompts, and
the control, the same program with its matrices rounded to bf16 (8
mantissa bits), 7.3e-3; ``F32_TOL`` = 1e-4 is about their geometric
mean. That limit also fails float32 statistics computed in bf16 (the
router's scores and the gate rounded to bf16 read 3.4e-3) and every
structural departure: FarSkip off reads 0.58, the gate left out 1.85,
the plain rotary's frequencies past the trained range 0.42
(``test_a_part_left_out_fails_the_comparison``). In bf16 a router
near-tie that flips an expert moves single logits by more than rounding
does, so bf16 is judged on the MEDIAN difference of a prompt's logits:
the program reads 0.0043-0.0044 over the four prompts, the control
(matrices cut to 3 mantissa bits, the nearest precision below) 0.042;
``BF16_TOL`` = 0.014 is their geometric mean.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _segments import forget_programs
from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import instella, moe
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import decode_attention as da
from ray_tpu.ops import rope

F32_TOL = 1e-4
BF16_TOL = 0.014

FAM = manifest.family("instella_moe")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
TRAINED = M["rope_original_max"]


def _cfg(**kw):
    m = {**M, **kw}
    held = m.pop("held_experts")
    return instella.InstellaConfig(
        **m, held_experts=held and tuple(held), max_seq_len=256)


def _cut(params, bits: int):
    """Every matrix rounded to ``bits`` mantissa bits (8: bf16)."""
    drop = 23 - bits

    def cut(path, a):
        if getattr(path[-1], "key", None) in instella.SLOTS.F32_LEAVES:
            return a
        raw = jax.lax.bitcast_convert_type(a.astype(jnp.float32), jnp.uint32)
        raw = (raw + jnp.uint32(1 << (drop - 1))) & jnp.uint32(
            ~((1 << drop) - 1) & 0xFFFFFFFF)
        return jax.lax.bitcast_convert_type(raw, jnp.float32).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, instella.init_params(cfg, jax.random.PRNGKey(7))


# ------------------------------------------------------- configuration


def test_the_configuration_carries_the_published_sizes():
    whole = instella.InstellaConfig()
    assert (whole.n_layers, whole.moe_layers, whole.n_heads) == (27, 26, 16)
    assert (whole.qk_head_dim, whole.v_head_dim, whole.kv_lora_rank) \
        == (128, 128, 512)
    # a row: 512 + 32 = 544 numbers, stored as five whole lanes
    assert whole.row_width == 640
    assert whole.shared_d_ff == 2 * whole.d_ff == 2816
    assert whole.held == (0, 64) and whole.slot_model is instella.SLOTS
    tiny = _cfg()
    assert tiny.row_width == 128 and [tiny.sparse(i) for i in range(4)] \
        == [False, True, True, True]


# ------------------------------------- the model, through the engine


def _ragged_logits(cfg, params, prompts, steps, spare_slot: int = 1):
    """Prompts of different lengths prefilled by the engine's own
    program into slots of one state, each in a bucket longer than itself
    (``spare_slot`` stays empty and inactive), then ``steps`` greedy
    steps of the model's ragged step with every slot at its own
    position. -> for each prompt (its tokens followed by the generated
    ones, float32 logits [steps, V] from the last prompt position on)."""
    slots, max_len = len(prompts) + 1, 96
    state = instella.SLOTS.init_state(cfg, slots, max_len)
    cur = jnp.zeros((slots,), jnp.int32)
    seqs, rows = {}, {}
    free = [s for s in range(slots) if s != spare_slot]
    for slot, p in zip(free[::-1], prompts):
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
    step = jax.jit(functools.partial(instella.SLOTS.step, cfg, params, None))
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


# shorter than the trained range (5), a whole bucket less one (15), past
# it (23, 41); every one decoded past it
PROMPTS = (5, 15, 23, 41)


def _worst(cfg, params, prompts, steps, off, want_params=None, ragged=None):
    """The comparison every test here makes: the ragged program's logits
    of every decoded position (``ragged``, where the caller made them
    already) against the reference's full forward over prompt + tokens
    (on ``want_params``, default the program's), reduced by ``off`` a
    prompt, the largest over the prompts."""
    worst = 0.0
    for (seq, got), p in zip(
            ragged or _ragged_logits(cfg, params, prompts, steps), prompts):
        want = np.asarray(REF.forward(
            params if want_params is None else want_params,
            jnp.asarray([seq]), M)[0])
        # step j's logits are the position's after len(p) + j tokens
        worst = max(worst, off(np.abs(
            got - want[len(p):len(p) + len(got)])))
    return worst


@pytest.mark.parametrize("dtype, tol, control_bits, off", [
    ("float32", F32_TOL, 8, np.max), ("bfloat16", BF16_TOL, 3, np.median)])
def test_prefill_then_ragged_decode_is_the_references_forward(
        dtype, tol, control_bits, off):
    """Four layers, four slots at different positions and a fifth
    inactive among them, each prompt in a bucket longer than itself, 20
    decoded positions each: the unabsorbed prefill's rows and then the
    absorbed step over them against the reference's full forward over
    prompt + tokens, inside ``tol`` (``off``: the largest difference in
    float32, a prompt's median in bf16; module docstring); the control
    (matrices cut to ``control_bits`` mantissa bits) is outside it."""
    cfg = _cfg(dtype=dtype)
    params = instella.init_params(cfg, jax.random.PRNGKey(7))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, n).astype(np.int32) for n in PROMPTS]
    worst = _worst(cfg, params, prompts, 20, off)
    assert worst < tol, worst
    control = _worst(cfg, _cut(params, control_bits), prompts[2:3], 6, off,
                     want_params=params)
    assert control > tol, (control, tol)


def _past_the_trained_range():
    rng = np.random.RandomState(4)
    return [rng.randint(1, 256, TRAINED + 7).astype(np.int32)]


@pytest.fixture(scope="module")
def as_it_is(model):
    """The comparison of ``test_a_part_left_out...`` on the program as
    it is, made once for its four cases."""
    return _worst(*model, _past_the_trained_range(), 6, np.max)


@pytest.mark.parametrize("left_out", ["farskip", "gate", "yarn_blend",
                                      "float32_sigmoids_in_bf16"])
def test_a_part_left_out_fails_the_comparison(left_out, monkeypatch, model,
                                              as_it_is):
    """The float32 comparison catches a dropped term: the plain pre-norm
    residual path for FarSkip's, the attention's output ungated, the
    plain rotary's frequencies under YaRN's softmax scale (a prompt past
    the trained range, every decoded position beyond it), and the
    float32 sigmoids (the router's scores, the gate) rounded to bf16."""
    cfg, params = model
    prompts = _past_the_trained_range()
    assert as_it_is < F32_TOL
    if left_out == "farskip":
        cfg = _cfg(farskip=False)
    elif left_out == "gate":
        cfg = _cfg(gated_attention=False)
    elif left_out == "yarn_blend":
        monkeypatch.setattr(
            instella, "yarn_inv_freq",
            lambda dim, theta, *a, **kw: rope.yarn_inv_freq(
                dim, theta, 1.0, TRAINED))
    else:
        bf16 = lambda a: a.astype(jnp.bfloat16).astype(  # noqa: E731
            jnp.float32)
        sigmoid = jax.nn.sigmoid
        monkeypatch.setattr(jax.nn, "sigmoid",
                            lambda a: bf16(sigmoid(bf16(a))))
    forget_programs()
    ragged = _ragged_logits(cfg, params, prompts, 6)
    monkeypatch.undo()  # (the reference computes as it is written)
    forget_programs()
    got = _worst(cfg, params, prompts, 6, np.max, ragged=ragged)
    print(f"{left_out}: {got}")
    assert got > 10 * F32_TOL, (left_out, got)


def test_the_absorbed_step_is_the_unabsorbed_forward(model):
    """The program against itself, no reference between: a decode step's
    logits at position n (q carried into the latent's space, the
    probabilities weighing latents) are the whole-sequence forward's
    (k and v made from the latent) at n."""
    cfg, params = model
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, 256, 30).astype(np.int32)
    (seq, got), = _ragged_logits(cfg, params, [prompt], 8)
    whole = np.asarray(instella.forward(params, jnp.asarray([seq]), cfg)[0])
    np.testing.assert_allclose(got, whole[30:38], atol=F32_TOL)


# --------------------------------------------------------- the kernel


@pytest.mark.parametrize("rows, block", [(96, 32), (100, 48), (64, 64)])
def test_the_latent_kernel_is_the_xla_body_at_ragged_lengths(rows, block):
    """``decode_attn_latent`` in the Pallas interpreter against
    ``attend_latent``: eight slots at lengths from one row to every row,
    an inactive one among them (its output zeros), a block read once as
    key and as value; a cache that ends inside its last block (100 rows
    in blocks of 48); the layer taken from the stack in place."""
    layers, slots, heads, w, dv = 3, 8, 4, 256, 128
    k1, k2 = jax.random.split(jax.random.PRNGKey(rows))
    stack = jax.random.normal(k1, (layers, slots, rows, w), jnp.float32)
    q = jax.random.normal(k2, (slots, heads, w), jnp.float32)
    lengths = jnp.asarray([1, rows, 0, 33, block, block + 1, rows - 1, 17],
                          jnp.int32)
    for layer in (0, 2):
        want = da.attend_latent(q, stack[layer], lengths, dv, 0.125)
        got = da.decode_attention_latent(
            q, stack, layer, lengths, dv=dv, scale=0.125, interpret=True,
            block=block)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert not np.asarray(got[2]).any()
        # a row's value is its first dv numbers: one row, itself
        np.testing.assert_allclose(got[0], jnp.broadcast_to(
            stack[layer, 0, 0, :dv], (heads, dv)), atol=1e-6)
    # nothing behind a slot's length decides the result: large numbers
    # there, as a reused slot's stale rows may be, the same output
    behind = jnp.arange(rows)[None, :, None] >= lengths[:, None, None]
    got = da.decode_attention_latent(
        q, jnp.where(behind[None], 1e4, stack), 1, lengths, dv=dv,
        scale=0.125, interpret=True, block=block)
    want = da.attend_latent(q, stack[1], lengths, dv, 0.125)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_dispatch_takes_the_xla_body_off_a_tpu():
    q = jnp.ones((2, 4, 128)), jnp.ones((1, 2, 8, 128))
    got = da.decode_attention_latent(
        *q, 0, jnp.asarray([3, 0]), dv=128, scale=1.0)
    np.testing.assert_allclose(got[0], 1.0, atol=1e-6)
    assert not np.asarray(got[1]).any()
    assert da.block_rows(16912, da.LATENT_BLOCK_ROWS) == 1008


# --------------------------------------------------- through the engine


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_submit_and_pump_serve_the_references_tokens(dtype):
    """``RaggedDecoder`` (submit -> pump) on the model: five streams
    over three slots, so slots are reused and streams sit at ragged
    positions; every stream's tokens pass the reference's
    ``check_served_tokens`` and, in float32, are its argmax outright."""
    cfg = _cfg(dtype=dtype)
    params = instella.init_params(cfg, jax.random.PRNGKey(8))
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
    assert st["state_bytes"] == {"latent": 3 * FAM.state_bytes_per_slot(
        M, 96, jnp.dtype(dtype).itemsize)["latent"]}
    assert st["moe_assignments"] > 0 and st["moe_touched_expert_steps"] > 0
    assert st["attn_live_rows_by_kind"] == {"latent": st["attn_live_rows"]}


def test_spans_carry_the_latent_state_and_the_routing(model):
    from ray_tpu._private import flight_recorder as fr

    cfg, params = model
    eng = RaggedDecoder(params, cfg, slots=2, max_len=64, chunk_tokens=4,
                        prompt_buckets=(16,), name="instella-test")
    eng.submit(np.arange(1, 13, dtype=np.int32), 8)
    eng.drain()
    spans = [s for s in fr._get().ring if s["attrs"].get("engine")
             == "instella-test" or s["name"] in ("engine.readback",
                                                 "engine.prefill")]
    init = [s for s in spans if s["name"] == "engine.state_init"][-1]["attrs"]
    assert (init["slots"], init["max_len"]) == (2, 64)
    assert init["latent_layers"] == M["n_layers"]
    assert init["latent_bytes"] == 2 * FAM.state_bytes_per_slot(
        M, 64, 4)["latent"] == 2 * 4 * 64 * 128 * 4
    fill = [s["attrs"] for s in spans if s["name"] == "engine.prefill"][-1]
    assert (fill["bucket"], fill["tokens"]) == (16, 12)
    backs = [s["attrs"] for s in spans if s["name"] == "engine.readback"
             and "live_rows_latent" in s["attrs"]][-2:]
    # one occupied slot: 12 prompt rows, then 4 more a chunk
    assert [b["live_rows"] for b in backs] == [16, 20]
    assert [b["live_rows_latent"] for b in backs] == [16, 20]
    assert backs[-1]["cache_rows"] == 2 * 64
    # every expert is held: all of the one active slot's assignments
    assert backs[-1]["assignments"] == backs[-1]["held_assignments"] \
        == M["top_k"]
    assert backs[-1]["experts_touched"] == M["top_k"]


def test_init_params_draws_this_blocks_leaves_in_blocks(monkeypatch):
    """The attention's and the experts' shapes, a leaf larger than a
    block drawn block by block (a block is 4,096 numbers here, the leaf
    read 32,768: eight blocks), ``w_down`` scaled for the published
    depth and ``wo`` not (the types: ``tests/test_slot_protocol.py``)."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    cfg = _cfg(dtype="bfloat16")
    params = instella.init_params(cfg, jax.random.PRNGKey(0))
    attn = params["layers"][2]["attn"]
    assert attn["wq"].shape == (64, 4 * 32)
    assert attn["w_kva"].shape == (64, 32 + 8)
    assert attn["w_kvb"].shape == (32, 4 * (24 + 32))
    assert attn["w_gate"].shape == (64, 4 * 32) == attn["wo"].shape[::-1]
    assert attn["q_norm"].shape == (32,) and attn["kv_norm"].shape == (32,)
    mlp = params["layers"][1]["mlp"]
    w = np.asarray(mlp["w_gate"], np.float32)
    assert w.shape == (16, 64, 32) and abs(w.std() * 64 ** 0.5 - 1) < 0.1
    assert not np.array_equal(w[0], w[1])
    assert mlp["shared_gate"].shape == (64, 64)
    down = np.asarray(mlp["w_down"], np.float32).std() * 32 ** 0.5
    assert abs(down - (2 * 27) ** -0.5) < 0.02
    assert abs(np.asarray(attn["wo"], np.float32).std() * 128 ** 0.5
               - 1) < 0.1
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) \
        == FAM.num_params(M)
