"""The tenth block (``models/glm_next.py``) on the CPU at a tiny size
against the plain reference (``benchmark/families/glm5_next.reference.
py``): whole sequences of the cell's five-layer pattern (lengths that
are and are not whole blocks of 4), prefill then decode through the slot
past ``index_topk`` and across a block that a prompt left open, the
streams' coefficients (doubly stochastic; forced to the identity the
block is its one-stream form), what each mechanism is worth (the
comparison FAILS with bf16 index scores, the tail not read, 5 Sinkhorn
rounds for 20, ``H_res`` left out of the state's update, the clamp left
out), the eight shares of an expert layer, and the clamp's reach (the
older blocks' SwiGLU bit for bit).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import dots, glm_next, moe
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import dsa

# float32 on both sides, the same products in another order: readings of
# 1e-6 to 2e-6 on logits that spread by 1.3
F32_TOL = 1e-4

FAM = manifest.family("glm5_next")
REF = manifest.reference(FAM)
M = dict(FAM.TINY_FIELDS)
ROWS = 72  # nine times ``index_topk``: 18 blocks, 2 chosen


def _cfg(**kw):
    """The family's own way to the program's configuration."""
    return FAM.build({**M, **kw}, max_seq_len=256, remat=False).cfg


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, glm_next.init_params(cfg, jax.random.PRNGKey(7))


def _tokens(seed: int, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)


@pytest.fixture(scope="module")
def reference(model):
    """(tokens [1, 72], the reference's logits over them)."""
    toks = jnp.asarray(_tokens(4, 1, ROWS))
    return toks, REF.forward(model[1], toks, M)


@pytest.fixture(scope="module")
def long_reference(model):
    """(tokens [1, 160], the reference's logits over them): 40 blocks,
    enough for a bfloat16 index score to change a choice."""
    toks = jnp.asarray(_tokens(4, 1, 160))
    return toks, REF.forward(model[1], toks, M)


def _forward(cfg, params, toks):
    # (a function of its own a call: a patched operation must be traced)
    return jax.jit(lambda p, t: glm_next.forward(p, t, cfg))(params, toks)


@pytest.mark.parametrize("rows", [ROWS, 43])
def test_forward_is_the_references_logits(rows, model):
    """Whole sequences, a length of whole blocks and one that leaves a
    block of three rows open: the logits and the sparse layer's chosen
    blocks (the reference's are indices out of a stable sort)."""
    cfg, params = model
    toks = jnp.asarray(_tokens(4, 2, rows))
    sets = []
    want = REF.forward(params, toks, M, sets=sets)
    assert float(jnp.abs(_forward(cfg, params, toks) - want).max()) < F32_TOL
    assert len(sets) == cfg.sparse_layers == 1
    assert sets[0].shape == (2, rows, M["index_topk"] // M["index_pool"])


@pytest.mark.parametrize("prompt", [22, 19, 40])
def test_prefill_then_steps_through_the_slot_are_the_references_forward(
        prompt, model):
    """A prompt of 22 (two rows of an open block), 19 (three) or 40
    (none) tokens through the engine's prefill into a slot, then decode
    steps past ``index_topk`` rows that close the open block and open
    the next: every step's logits are the reference's full forward's at
    that position, and the greedy tokens the engine serves are its
    argmax."""
    cfg, params = model
    toks = _tokens(11, prompt + 30)
    want = np.asarray(REF.forward(params, jnp.asarray(toks[None]), M)[0])
    slots = glm_next.SLOTS
    state = slots.init_state(cfg, 2, 96)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :prompt] = toks[:prompt]
    lens = jnp.array([prompt], jnp.int32)
    streams, full, *_ = slots.prefill(
        params, jnp.asarray(padded), lens, jnp.zeros(1, jnp.uint32),
        jnp.zeros(1), jnp.ones(1), cfg, 96)
    state = slots.scatter(state, jnp.array([1]), streams, full)
    pos = state.pop("pos")
    step = jax.jit(lambda st, tok, pos: slots.step(
        cfg, params, None, tok, st, pos, jnp.array([False, True])))
    for j in range(30):
        out = step(state, jnp.array([0, toks[prompt + j]]), pos)
        state = out[1]
        err = np.abs(np.asarray(out[0][1]) - want[prompt + j]).max()
        assert err < F32_TOL, (j, err)
        n = prompt + j + 1  # the rows the slot holds now
        pool, blocks = cfg.index_pool, cfg.index_topk // cfg.index_pool
        assert int(out[5][0]) == pool * min(blocks, n // pool) + n % pool
        assert int(out[7][0]) == n // pool + n % pool
        pos = pos + jnp.array([0, 1])


def test_the_engine_serves_the_references_tokens(model):
    """Through ``RaggedDecoder`` (submit, pump, read-back): the greedy
    tokens are the reference's argmax wherever its top two are apart,
    and the read-back carries the block's three counters."""
    cfg, params = model
    prompt = _tokens(5, 27)
    eng = RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                        prompt_buckets=(32,))
    sid = eng.submit(prompt, 20)
    eng.drain()
    served = eng.finished[sid].tokens
    seq = jnp.asarray(np.concatenate([prompt, served])[None])
    rows = np.asarray(REF.forward(params, seq, M)[0, 26:-1])
    top2 = np.sort(rows, -1)[:, -2:]
    apart = top2[:, 1] - top2[:, 0] > 1e-3
    assert apart.sum() > 10
    assert (rows.argmax(-1) == np.asarray(served))[apart].all()
    assert eng.row_kinds == {"recurrent": (4, 0), "latent": (1, None),
                             "index": (1, None)}
    assert set(eng.state_bytes) == {"recurrent", "latent", "index"}


def test_with_index_topk_over_the_length_the_sparse_layer_is_causal_mla(
        model, reference, monkeypatch):
    """``index_topk`` past the sequence's length: every whole block is
    chosen and the tail is read, so the bias is the causal one; the
    program's logits are the reference's, and they are the program's own
    with the layer's bias replaced by a causal one."""
    cfg, params = model
    toks, plain = reference
    every = dataclasses.replace(cfg, index_topk=4096)
    got = _forward(every, params, toks)
    want = REF.forward(params, toks, {**M, "index_topk": 4096})
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert float(jnp.abs(got - plain).max()) > 30 * F32_TOL
    real = dsa.masked_attention

    def causal(q_n, q_r, k_n, k_r, v, bias, offset, **kw):
        assert q_r is None and k_r is None  # (no rotated part)
        t, s = bias.shape[1:]
        seen = jnp.arange(s)[None, :] <= jnp.arange(t)[:, None]
        return real(q_n, q_r, k_n, k_r, v, jnp.where(
            seen, 0.0, dsa.NEG).astype(bias.dtype)[None], offset, **kw)

    monkeypatch.setattr(dsa, "masked_attention", causal)
    np.testing.assert_array_equal(np.asarray(_forward(every, params, toks)),
                                  np.asarray(got))


def test_h_res_is_doubly_stochastic(model):
    """Twenty Sinkhorn rounds on the seeded coefficients of real streams:
    every row and every column of ``H_res`` sums to 1 within 1e-4,
    ``H_pre`` lies in (0, 1) and ``H_post`` in (0, 2); five rounds leave
    a row further off than that."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.hc_mult,
                                                  cfg.d_model))
    for p in (params["layers"][0]["hc_attn"], params["layers"][3]["hc_mlp"]):
        pre, post, res = glm_next.hc_coefficients(cfg, p, x)
        assert res.shape == (4, 4, 2, 24)
        assert float(jnp.abs(res.sum(0) - 1).max()) < 1e-4
        assert float(jnp.abs(res.sum(1) - 1).max()) < 1e-4
        assert 0 < float(pre.min()) and float(pre.max()) < 1
        assert 0 < float(post.min()) and float(post.max()) < 2
    few = glm_next.hc_coefficients(
        dataclasses.replace(cfg, hc_sinkhorn_iters=5), p, x)[2]
    assert float(jnp.abs(few.sum(1) - 1).max()) > 1e-4


def _one_stream(params, toks, m):
    """The model with ONE residual stream, written with the reference's
    own layer functions: ``h <- h + F(N(h))`` round every sublayer."""
    f32 = REF._f32
    h = params["embed"][toks].astype(jnp.float32)
    for i, p in enumerate(params["layers"]):
        a = f32(p["attn"])
        x = REF._rms_norm(h, p["attn_norm"], m["rms_eps"])
        if m["layer_types"][i]:
            c, k_i = REF.latents(m, a, x)
            q, q_i, w = REF.queries(m, a, x)
            pool = m["index_pool"]
            sets = REF.chosen_blocks(
                REF.index_scores(q_i, w, REF.pooled(k_i, pool)), 0, pool,
                m["index_topk"] // pool)
            o = REF.attend(m, a, q, c, REF.seen_rows(sets, 0, pool,
                                                     c.shape[1]))
            h = h + o.reshape(*o.shape[:2], -1) @ a["wo"]
        else:
            h = h + REF.kda_rows(m, a, x, *REF.kda_empty(m, h.shape[0]))[0]
        x = REF._rms_norm(h, p["mlp_norm"], m["rms_eps"])
        if i >= m["first_k_dense"]:
            h = h + REF.moe_layer(m, p["mlp"], x)
        else:
            q = f32(p["mlp"])
            h = h + REF._swiglu(m, x, q["w_gate"], q["w_up"], q["w_down"])
    return h


def test_with_its_coefficients_forced_the_block_is_its_one_stream_form(
        model):
    """``H_pre = e_0``, ``H_post = e_0``, ``H_res = I`` forced through
    the leaves (``alpha`` 0, the biases at their limits): stream 0 is
    the one-stream model's residual stream, the three others stay the
    embedding, and the head reads their sum."""
    cfg, params = model
    n = cfg.hc_mult
    b = np.full(2 * n + n * n, -60.0, np.float32)
    b[0] = 60.0  # sigmoid -> 1, the others 0
    b[n] = 0.0  # 2 sigmoid(0) = 1, the others 0
    b[2 * n::n + 1] = 0.0  # exp(0) on the diagonal, e^-60 off it
    forced = {"hc_phi": None, "hc_b": jnp.asarray(b),
              "hc_alpha": jnp.zeros(3, jnp.float32)}
    layers = [{**p, **{hc: {**forced, "hc_phi": p[hc]["hc_phi"]}
                       for hc in ("hc_attn", "hc_mlp")}}
              for p in params["layers"]]
    one = {**params, "layers": layers}
    toks = jnp.asarray(_tokens(9, 1, 40))
    with jax.default_matmul_precision("highest"):
        h = _one_stream(one, toks, M) \
            + (n - 1) * one["embed"][toks].astype(jnp.float32)
        want = REF._rms_norm(h, one["final_norm"], M["rms_eps"]) \
            @ one["lm_head"].astype(jnp.float32)
    got = _forward(cfg, one, toks)
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert float(jnp.abs(got - _forward(cfg, params, toks)).max()) \
        > 30 * F32_TOL


def _rounded(fn):
    return lambda *a, **kw: fn(*a, **kw).astype(jnp.bfloat16).astype(
        jnp.float32)


def _without(what: str, cfg, monkeypatch):
    """The program with one mechanism left out or weakened."""
    if what == "bf16_index_scores":
        monkeypatch.setattr(dsa, "index_scores", _rounded(dsa.index_scores))
        return cfg
    if what == "tail_not_read":
        real = dots.pooled_bias

        def no_tail(chosen, at, pool, rows):
            reads = jnp.repeat(chosen, pool, axis=-1)[..., :rows]
            return jnp.where(reads, 0.0, dsa.NEG).astype(jnp.bfloat16), reads

        assert real is not None
        monkeypatch.setattr(dots, "pooled_bias", no_tail)
        return cfg
    if what == "five_sinkhorn_rounds":
        return dataclasses.replace(cfg, hc_sinkhorn_iters=5)
    if what == "h_res_left_out":
        real = glm_next.hc_coefficients

        def identity(cfg, p, x):
            pre, post, res = real(cfg, p, x)
            eye = jnp.eye(cfg.hc_mult)[:, :, None, None]
            return pre, post, jnp.broadcast_to(eye, res.shape)

        monkeypatch.setattr(glm_next, "hc_coefficients", identity)
        return cfg
    if what == "one_block_fewer":
        return dataclasses.replace(
            cfg, index_topk=cfg.index_topk - cfg.index_pool)
    assert what == "clamp_left_out"
    return dataclasses.replace(cfg, swiglu_limit=None)


@pytest.mark.parametrize("what", [
    "bf16_index_scores", "tail_not_read", "five_sinkhorn_rounds",
    "h_res_left_out", "one_block_fewer"])
def test_the_comparison_fails_without(what, model, long_reference,
                                      monkeypatch):
    """Each moves the logits of a 160-token sequence by far more than
    the tolerance the whole program meets: index scores rounded to
    bfloat16 before the selection, the open block not read, five
    Sinkhorn rounds for twenty, ``H_res`` left out of the state's
    update, one block fewer chosen. And the reference's own controls
    (what the chip comparison runs) move it as far."""
    cfg, params = model
    toks, want = long_reference
    off = float(jnp.abs(_forward(_without(what, cfg, monkeypatch), params,
                                 toks) - want).max())
    print(f"\n{what}: logits off by {off:.3g}")
    assert off > 30 * F32_TOL, what
    controls = {"bf16_index_scores": {"bf16_index": True},
                "tail_not_read": {"tail": False},
                "five_sinkhorn_rounds": {"iters": 5},
                "h_res_left_out": {"keep_res": False}}.get(what)
    if controls:
        ref_off = float(jnp.abs(
            REF.forward(params, toks, M, controls=controls) - want).max())
        assert ref_off > 30 * F32_TOL, (what, ref_off)


def test_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(model):
    """Layer 1's expert layer by the reference: eight shares of two
    experts each, the shared expert counted once, sum to the layer with
    every expert held; and the program's share is the reference's."""
    cfg, params = model
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, cfg.d_model))
    whole = dataclasses.replace(cfg, held_experts=None)
    p = glm_next.init_params(whole, jax.random.PRNGKey(7))["layers"][1]["mlp"]
    assert p["w_gate"].shape[0] == 16
    names = ("w_gate", "w_up", "w_down")
    with jax.default_matmul_precision("highest"):
        uncut = REF.moe_layer({**M, "held_experts": None}, p, x)
        parts = 0
        for first in range(0, 16, 2):
            held = {**p, **{n: p[n][first:first + 2] for n in names}}
            parts = parts + REF.moe_layer(M, held, x, held=(first, 2),
                                          shared=first == 0)
        assert float(jnp.abs(parts - uncut).max()) < 1e-5
        share = dataclasses.replace(cfg, held_experts=(2, 2))
        held = {**p, **{n: p[n][2:4] for n in names}}
        got = moe.moe(share, held, x)
        want = REF.moe_layer(M, held, x, held=(2, 2))
    assert float(jnp.abs(got - want).max()) < 1e-5


def test_the_clamp_changes_what_a_large_draw_makes_and_nothing_older(model):
    """``swiglu_limit`` 10: a large input's expert layer and dense MLP
    differ from the unclamped ones and are the reference's; an input of
    the model's own spread never reaches the limit; a configuration that
    states no limit (every older block's) runs the old lines: the same
    bits as the formula written out."""
    cfg, params = model
    free = dataclasses.replace(cfg, swiglu_limit=None)
    small = jax.random.normal(jax.random.PRNGKey(3), (1, 16, cfg.d_model))
    large = small  # (the norm undoes an input's scale: the draw is large)
    for layer, sparse in ((0, False), (1, True)):
        p = params["layers"][layer]
        big = {name: 200.0 * w for name, w in p["mlp"].items()
               if name.endswith(("gate", "up"))}

        def mlp(c, x, p=p):
            return moe.mlp_layer(c, sparse, p, x, None, residual=False)

        drawn = {**p, "mlp": {**p["mlp"], **big}}
        assert float(jnp.abs(mlp(cfg, large, drawn)
                             - mlp(free, large, drawn)).max()) > 1.0
        np.testing.assert_array_equal(np.asarray(mlp(cfg, small)),
                                      np.asarray(mlp(free, small)))
        with jax.default_matmul_precision("highest"):
            x = REF._rms_norm(large, p["mlp_norm"], M["rms_eps"])
            q = drawn["mlp"]
            want = REF.moe_layer(M, q, x) if sparse else REF._swiglu(
                M, x, q["w_gate"], q["w_up"], q["w_down"])
            got = mlp(cfg, large, drawn)
        assert float(jnp.abs(got - want).max()) < 1e-3 * float(
            jnp.abs(want).max())
        # with its residual the layer is what the older blocks call
        np.testing.assert_array_equal(
            np.asarray(moe.mlp_layer(free, sparse, p, small)),
            np.asarray(small + mlp(free, small)))
    q = params["layers"][0]["mlp"]
    x = 200.0 * small[0]
    np.testing.assert_array_equal(
        np.asarray(moe.swiglu(x, q["w_gate"], q["w_up"], q["w_down"])),
        np.asarray((jax.nn.silu(x @ q["w_gate"]) * (x @ q["w_up"]))
                   @ q["w_down"]))
    assert moe.swiglu_limit(free) is None and moe.swiglu_limit(cfg) == 10.0
    assert not hasattr(dots.DotsConfig.tiny(), "swiglu_limit")
