"""OLMoE's block through the repo's one block (``models/llama.py`` with
``qk_norm``, ``norm_topk_prob=False``, ``moe_impl="dropless"``) against
its plain reference (``benchmark/families/olmoe.reference.py``), at a
small size on the CPU, seeded random weights, LOGITS and not tokens.

Tolerance 1e-4 (absolute, on logits of spread ~1 and a loss near
ln 128 = 4.9): both sides compute in float32 and differ by the order of
their sums alone (the program sorts assignments and sums top_k rows, the
reference sums over all experts; a cache against a full forward), which
reads 1e-6 to 2e-5 here. Anything left out of the block moves a logit
by hundredths or more: the controls (no q/k norm, renormalised gates,
one expert too few) must each fail the same tolerance.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _oracle import greedy_tokens
from benchmark import manifest
from ray_tpu.models import decode_engine as de
from ray_tpu.models import llama
from ray_tpu.models import llama_slots

TOL = 1e-4
FAMILY = manifest.family("olmoe")
M = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
         d_ff=32, rope_theta=1e4, rms_eps=1e-5, tie_embeddings=False,
         n_experts=8, top_k=2, norm_topk_prob=False, qk_norm=True,
         moe_impl="dropless", dtype="float32")


@pytest.fixture(scope="module")
def ref():
    return manifest.reference(FAMILY)


@pytest.fixture(scope="module")
def cfg():
    return FAMILY.build(M, max_seq_len=64, remat=False).cfg


@pytest.fixture(scope="module")
def params(cfg):
    p = llama.init_params(cfg, jax.random.PRNGKey(0))
    # norm scales away from 1, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for name in ("q_norm", "k_norm", "attn_norm", "mlp_norm"):
        p["layers"][name] = 1.0 + 0.3 * jax.random.normal(
            next(keys), p["layers"][name].shape)
    return p


def _tokens(seed, shape):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 1,
                              M["vocab_size"])


def test_forward_matches_the_reference(ref, cfg, params):
    toks = _tokens(2, (2, 24))
    want = ref.forward(params, toks, M)
    got = llama.forward(params, toks, dataclasses.replace(
        cfg, use_flash=False))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


def test_the_router_chooses_the_references_experts(ref, cfg, params):
    """In float32 the program's routing is the reference's, set for set,
    in every layer (on the chip a bf16 router flips near-ties: PERF.md
    gives the share that agrees)."""
    toks = _tokens(2, (2, 24))
    _, chosen = ref.forward_and_routing(params, toks, M)
    aux = {}
    llama.prefill(params, toks, np.full((2,), 23, np.int32), cfg, None, aux)
    assert aux["expert_ids"].shape == chosen.shape == (2, 2, 24, 2)
    np.testing.assert_array_equal(np.sort(aux["expert_ids"], -1),
                                  np.sort(chosen, -1))


@pytest.mark.parametrize("control", [
    dict(qk_norm=False), dict(norm_topk_prob=True), dict(top_k=1)])
def test_a_block_that_leaves_something_out_fails_the_tolerance(
        ref, cfg, params, control):
    toks = _tokens(2, (2, 24))
    want = ref.forward(params, toks, M)
    got = llama.forward(params, toks, dataclasses.replace(
        cfg, use_flash=False, **control))
    assert float(jnp.abs(got - want).max()) > 100 * TOL


def test_loss_and_gradients_match_the_reference(ref, cfg, params):
    toks = _tokens(3, (2, 25))
    inputs, targets = toks[:, :-1], toks[:, 1:]
    cfg = dataclasses.replace(cfg, use_flash=False)
    want, want_g = jax.value_and_grad(
        lambda p: ref.loss(p, inputs, targets, M))(params)
    got, got_g = jax.value_and_grad(lambda p: llama.loss_fn(
        p, {"inputs": inputs, "targets": targets}, cfg)[0])(params)
    assert abs(float(got) - float(want)) < TOL
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = jax.tree_util.tree_leaves(got_g)
    for (path, w), g in zip(flat_w, flat_g):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=TOL,
            err_msg=jax.tree_util.keystr(path))
    # every leaf is trained, the router and both new norms among them
    for name in ("router", "q_norm", "k_norm", "w_gate", "w_down"):
        assert float(jnp.abs(got_g["layers"][name]).max()) > 0, name


def _prefill(cfg, params, prompts, bucket, slots, max_len):
    """Each prompt through the engine's own one-row prefill call into
    its slot -> (cache, cur_tok, first tokens, first logprobs, loads)."""
    cache = llama_slots.init_ragged_cache(cfg, slots, max_len)
    cur = jnp.zeros((slots,), jnp.int32)
    toks0, lps0, loads = [], [], []
    for slot, prompt in enumerate(prompts):
        row = np.zeros((1, bucket), np.int32)
        row[0, :len(prompt)] = prompt
        cache, cur, t0, lp0, load = de._prefill_batch_into_slots(
            params, row, np.array([len(prompt)], np.int32),
            np.array([slot], np.int32), np.zeros(1, np.uint32),
            np.zeros(1, np.float32), np.ones(1, np.float32), cache, cur,
            cfg)
        toks0.append(int(t0[0]))
        lps0.append(float(lp0[0]))
        loads.append(np.asarray(load))
    return cache, cur, toks0, lps0, loads


def test_prefill_then_decode_through_the_cache_matches_the_reference(
        ref, cfg, params):
    """Two prompts of 5 and 11 tokens in one bucket of 16 (so 11 and 5
    padded positions), each prefilled by the engine's program, then 8
    greedy steps of ``decode_chunk`` without and with the sampling lanes
    (at temperature 0 it reports the chosen token's log-probability: a
    logit-level reading). Against the reference's full forward over
    prompt + tokens: the same tokens, the same log-probabilities."""
    prompts = [list(map(int, _tokens(4, (5,)))),
               list(map(int, _tokens(5, (11,))))]
    chunk, slots, max_len = 8, 2, 40
    active = np.ones(slots, bool)
    cache, cur, toks0, lps0, loads = _prefill(cfg, params, prompts, 16,
                                              slots, max_len)
    toks, _, _, _, touched = de.decode_chunk(params, cache, cur, active,
                                             None, cfg, chunk)
    cache, cur, _, _, _ = _prefill(cfg, params, prompts, 16, slots, max_len)
    toks_s, lps, _, _, _ = de.decode_chunk(
        params, cache, cur, active, (
            np.zeros(slots, np.uint32), np.zeros(slots, np.float32),
            np.ones(slots, np.float32)), cfg, chunk)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks_s))
    for slot, prompt in enumerate(prompts):
        served = [toks0[slot]] + [int(t) for t in toks[slot]]
        served_lp = [lps0[slot]] + [float(x) for x in lps[slot]]
        seq = jnp.asarray([prompt + served[:-1]], jnp.int32)
        logp = np.asarray(jax.nn.log_softmax(ref.forward(params, seq, M)[0]))
        rows = logp[len(prompt) - 1:]
        assert [int(r.argmax()) for r in rows] == served
        np.testing.assert_allclose(
            served_lp, [r.max() for r in rows], atol=TOL)
        # the padded positions are not counted: top_k assignments a real
        # position and layer
        assert loads[slot].shape == (M["n_layers"], M["n_experts"])
        assert loads[slot].sum() == len(prompt) * M["top_k"] * M["n_layers"]
    # experts touched by 2 slots x top-2: between 2 and 4 a step and layer
    touched = np.asarray(touched)
    assert touched.shape == (chunk, M["n_layers"])
    assert touched.min() >= 2 and touched.max() <= 4


def test_the_serving_programs_serve_a_dropless_model(cfg, params):
    """Plain, sampled and speculative chunks and the prefix cache's
    suffix prefill all give the tokens of ``greedy_generate`` under the
    every-expert oracle (``moe_impl="dense"``), and the engine's stats
    carry the routing counters."""
    from ray_tpu.models.kv_prefix_cache import PrefixCache

    dense = dataclasses.replace(cfg, moe_impl="dense")
    shared = list(map(int, _tokens(6, (8,))))
    prompts = [shared + [3, 4, 5], shared + [9, 8], [7, 8, 9]]
    want = [greedy_tokens(params, p, dense, 10).tolist() for p in prompts]
    for kw in (dict(), dict(spec_depth=2, spec_draft_layers=1),
               dict(prefix_cache=PrefixCache(block=4))):
        eng = de.RaggedDecoder(params, cfg, slots=2, max_len=40,
                               chunk_tokens=4, prompt_buckets=(4, 16), **kw)
        got = []
        for p in prompts:  # one at a time: the second finds a prefix
            sid = eng.submit(p, 10)
            sampled = eng.submit(p, 4, temperature=1.0, seed=7)
            eng.drain()
            got.append(eng.pop_finished(sid).tokens)
            assert len(eng.pop_finished(sampled).tokens) == 4
        assert got == want, kw
        st = eng.stats()
        assert st["moe_touched_expert_steps"] > 0
        if "prefix_cache" in kw:
            assert st["prefix_cache"]["hits"] >= 1
        else:  # every prompt was prefilled cold, twice
            assert st["moe_assignments"] == 2 * sum(
                len(p) for p in prompts) * M["top_k"] * M["n_layers"]


def test_a_dense_model_is_the_program_it_was(cfg):
    """``qk_norm=False, n_experts=0`` builds the parameter tree of the
    parent commit (leaves and shapes pinned here), its ``forward`` traces
    to as many equations as at the parent (122, counted there), and its
    serving programs return what they returned: the dense cells'
    programs cannot change unnoticed."""
    dense = llama.LlamaConfig.tiny(use_flash=False)
    shapes = jax.eval_shape(
        lambda: llama.init_params(dense, jax.random.PRNGKey(0)))
    leaves = {jax.tree_util.keystr(k): v.shape for k, v in
              jax.tree_util.tree_leaves_with_path(shapes)}
    assert leaves == {
        "['embed']": (256, 64), "['final_norm']": (64,),
        "['lm_head']": (64, 256),
        "['layers']['attn_norm']": (2, 64),
        "['layers']['mlp_norm']": (2, 64),
        "['layers']['wq']": (2, 64, 64), "['layers']['wk']": (2, 64, 32),
        "['layers']['wv']": (2, 64, 32), "['layers']['wo']": (2, 64, 64),
        "['layers']['w_gate']": (2, 64, 128),
        "['layers']['w_up']": (2, 64, 128),
        "['layers']['w_down']": (2, 128, 64)}
    for c in (dense, cfg):  # axis names for every leaf, the new two too
        axes = jax.tree_util.tree_leaves_with_path(
            llama.param_logical_axes(c),
            is_leaf=lambda x: isinstance(x, tuple))
        want = jax.tree_util.tree_leaves_with_path(jax.eval_shape(
            lambda c=c: llama.init_params(c, jax.random.PRNGKey(0))))
        assert [(k, len(a)) for k, a in axes] \
            == [(k, len(v.shape)) for k, v in want]

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        n += count(inner)
        return n

    traced = jax.make_jaxpr(lambda p, t: llama.forward(p, t, dense))(
        shapes, jnp.zeros((2, 16), jnp.int32))
    assert count(traced.jaxpr) == 122
    cache = jax.eval_shape(lambda: llama_slots.init_ragged_cache(dense, 2, 32))
    out = jax.eval_shape(
        lambda p, c: de.decode_chunk(p, c, jnp.zeros(2, jnp.int32),
                                     jnp.ones(2, bool), None, dense, 4),
        shapes, cache)
    # tokens, no logprobs, cache, last token: no routing counter
    assert len(out) == 4 and out[1] is None
    assert dense.num_params() == sum(
        int(np.prod(s)) for s in leaves.values())
    assert cfg.num_params() == FAMILY.num_params(M)


def test_dropless_on_an_ep_mesh_is_refused():
    from ray_tpu.parallel import MeshConfig, build_mesh, use_mesh

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 virtual devices")
    cfg = llama.LlamaConfig.tiny(n_experts=4, top_k=2, moe_impl="dropless",
                                 use_flash=False)
    p = jax.tree_util.tree_map(lambda a: a[0], llama.init_params(
        cfg, jax.random.PRNGKey(0))["layers"])
    with use_mesh(build_mesh(MeshConfig(ep=2), devs[:2])):
        with pytest.raises(ValueError, match="ep axis"):
            jax.jit(lambda x: llama._moe_mlp(cfg, p, x))(
                jnp.zeros((2, 8, 64)))
