"""A replica casts its weights once, when it adopts them (ISSUE 31).

``llama.serving_params`` rounds the matrices of the f32 masters to the
compute dtype; the serving programs used to do that themselves, in front
of every product. The contract under test:

- every serving program returns BIT-identical outputs from the serving
  tree and from the masters (same operand values into every product), so
  the cast is held by a comparison that shares nothing with it;
- the engine's tokens still follow the uncached ``llama.forward`` run
  in float32 from the MASTERS (``tests/_oracle.py`` where f32 serves);
- the adoption points (``RaggedDecoder.__init__`` / ``set_params``,
  ``LLMServer.update_weights``, ``PrefillWorker``) leave the process
  holding the serving tree alone, record one ``serve.weights_cast`` span
  an adoption and none a pump, and still clear the prefix cache.
"""

import dataclasses
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _oracle import greedy_tokens
from ray_tpu._private import flight_recorder as fr
from ray_tpu.models import decode_engine as de
from ray_tpu.models import llama, mlp
from ray_tpu.models import llama_slots
from ray_tpu.models.decode_engine import RaggedDecoder

_DENSE = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
              n_kv_heads=4, d_ff=128, max_seq_len=48, remat=False)
CONFIGS = {
    "dense-bf16": dict(_DENSE, dtype="bfloat16"),
    "dense-f32": dict(_DENSE, dtype="float32"),
    "gqa-bf16": dict(_DENSE, n_layers=3, n_kv_heads=2, dtype="bfloat16"),
    # OLMoE's shape: q/k norms, a dropless top-k expert layer whose
    # stack rides beside the layer scan (llama.split_layers)
    "olmoe-bf16": dict(_DENSE, d_ff=32, n_experts=8, top_k=2,
                       norm_topk_prob=False, qk_norm=True,
                       moe_impl="dropless", dtype="bfloat16"),
}
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "router")
SLOTS, MAX_LEN, BUCKET, CHUNK = 2, 48, 16, 6
LENS = [7, 13]


@pytest.fixture(scope="module", params=list(CONFIGS))
def model(request):
    """(cfg, f32 masters, their serving cast); norm scales away from 1,
    so that a norm vector that lost its float32 would show."""
    cfg = llama.LlamaConfig(**CONFIGS[request.param])
    masters = llama.init_params(cfg, jax.random.PRNGKey(3))
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 8))
    for name, leaf in masters["layers"].items():
        if name.endswith("_norm"):
            masters["layers"][name] = 1.0 + 0.3 * jax.random.normal(
                next(keys), leaf.shape)
    return cfg, masters, llama.serving_params(cfg, masters)


def _nbytes(tree):
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def _lanes(n, seed=0, temp=0.0):
    return (np.full(n, seed, np.uint32), np.full(n, temp, np.float32),
            np.ones(n, np.float32))


def _prompts():
    rng = np.random.RandomState(31)
    rows = np.zeros((SLOTS, BUCKET), np.int32)
    for i, n in enumerate(LENS):
        rows[i, :n] = rng.randint(1, 250, n)
    return rows


def _prefilled(cfg, tree, lanes=None):
    return de._prefill_batch_into_slots(
        tree, _prompts(), np.array(LENS, np.int32),
        np.arange(SLOTS, dtype=np.int32), *(lanes or _lanes(SLOTS)),
        llama_slots.init_ragged_cache(cfg, SLOTS, MAX_LEN),
        jnp.zeros((SLOTS,), jnp.int32), cfg)


def _chunk(cfg, tree, lanes):
    cache, tok, *_ = _prefilled(cfg, tree)
    return de.decode_chunk(tree, cache, tok, np.ones(SLOTS, bool), lanes,
                           cfg, CHUNK)


def _chunk_spec(cfg, tree):
    cache, tok, *_ = _prefilled(cfg, tree)
    # the draft's adapter head is engine-local and stays float32
    head = mlp.init_draft_head(cfg.d_model, jax.random.PRNGKey(5))
    head = jax.tree_util.tree_map(lambda a: a + 0.01, head)
    return de.decode_chunk_spec(
        tree, head, cache, tok, np.ones(SLOTS, bool),
        *_lanes(SLOTS, seed=9, temp=0.7), cfg, 2, 2, 1)


def _prefill_prefix(cfg, tree):
    """The prefix cache's warm path: the first 4 rows of both prompts
    seed the temporary cache, the suffixes are prefilled behind them."""
    n_pref = 4
    cold = _prefilled(cfg, tree)[0]
    # (a prefix is rows as a prefill makes them, [L, F, S, Hkv, hd]; the
    # slots hold a row's kv heads end to end)
    heads = (*cold["k"].shape[:3], cfg.n_kv_heads, cfg.head_dim)
    pref = {kv: jnp.zeros(heads, cold[kv].dtype).at[:, :, :n_pref].set(
        cold[kv][:, :, :n_pref].reshape(*heads[:2], n_pref, *heads[3:]))
        for kv in "kv"}
    suffix = np.zeros((SLOTS, BUCKET), np.int32)
    suffix[:, :BUCKET - n_pref] = _prompts()[:, n_pref:]
    return de._prefill_batch_into_slots(
        tree, suffix, np.array(LENS, np.int32) - n_pref,
        np.arange(SLOTS, dtype=np.int32), *_lanes(SLOTS, seed=7, temp=0.5),
        llama_slots.init_ragged_cache(cfg, SLOTS, MAX_LEN),
        jnp.zeros((SLOTS,), jnp.int32), cfg,
        (pref["k"], pref["v"], np.int32(n_pref)))


PROGRAMS = {
    "chunk_greedy": lambda cfg, tree: _chunk(cfg, tree, None),
    "chunk_lanes": lambda cfg, tree: _chunk(
        cfg, tree, _lanes(SLOTS, seed=11, temp=0.8)),
    "chunk_spec": _chunk_spec,
    "prefill_cold": lambda cfg, tree: _prefilled(
        cfg, tree, _lanes(SLOTS, seed=7, temp=0.5)),
    "prefill_prefix": _prefill_prefix,
    "prefill_kv": lambda cfg, tree: de.prefill_kv(
        tree, _prompts(), np.array(LENS, np.int32),
        *_lanes(SLOTS, seed=7, temp=0.5), cfg, MAX_LEN),
    # the logits behind every first token, and every layer's rows
    "first_token_logits": lambda cfg, tree: llama.prefill(
        tree, jnp.asarray(_prompts()), np.array(LENS, np.int32) - 1, cfg),
}


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_serving_tree_and_masters_give_the_same_bits(model, program):
    """Tokens, logprobs, logits and cache rows, leaf for leaf."""
    cfg, masters, serving = model
    want = jax.tree_util.tree_leaves(PROGRAMS[program](cfg, masters))
    got = jax.tree_util.tree_leaves(PROGRAMS[program](cfg, serving))
    assert len(got) == len(want) >= 2
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_serving_tree_types_and_idempotence(model):
    cfg, masters, serving = model
    cdt = cfg.compute_dtype
    assert serving["embed"].dtype == cdt
    assert serving["lm_head"].dtype == cdt
    assert serving["final_norm"].dtype == jnp.float32
    for name, leaf in serving["layers"].items():
        want = cdt if name in MATRICES else jnp.float32
        assert leaf.dtype == want, name
        # rounded once, to the nearest: what .astype gave the products
        np.testing.assert_array_equal(
            np.asarray(leaf.astype(jnp.float32)),
            np.asarray(masters["layers"][name].astype(want)
                       .astype(jnp.float32)))
    assert set(serving["layers"]) == set(masters["layers"])
    # a tree already in those types comes back itself: no copy
    assert llama.serving_params(cfg, serving) is serving
    if cdt == jnp.float32:
        assert serving is masters
    # the masters are not touched
    assert all(a.dtype == jnp.float32
               for a in jax.tree_util.tree_leaves(masters))


def _serve(eng, prompt, n):
    sid = eng.submit(np.asarray(prompt, np.int32), n)
    eng.drain()
    return np.asarray(eng.pop_finished(sid).tokens[:n])


def test_engine_holds_the_serving_tree_and_follows_the_masters(model):
    """An engine built from the masters holds their serving cast alone
    and reports its bytes; its greedy tokens are the uncached forward's
    from the MASTERS: in f32 token for token (``_oracle``), in bf16
    wherever the f32 logits of the masters decide: top two further
    apart than 0.25, where bf16 moves a logit of these models by up to
    0.04 (0.22 when a near-tied router picks another expert)."""
    cfg, masters, serving = model
    n0 = len(_ring())
    eng = RaggedDecoder(masters, cfg, slots=SLOTS, max_len=MAX_LEN,
                        chunk_tokens=CHUNK, prompt_buckets=(BUCKET,))
    for a, b in zip(jax.tree_util.tree_leaves(eng.params),
                    jax.tree_util.tree_leaves(serving)):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    nbytes = _nbytes(serving)
    assert eng.stats()["weights_bytes"] == nbytes
    span, = _ring()[n0:]
    assert span["attrs"] == {
        "version": 0, "bytes_out": nbytes, "bytes_in": _nbytes(masters)}
    if cfg.compute_dtype != jnp.float32:
        assert nbytes < 0.51 * span["attrs"]["bytes_in"]

    prompt, n = _prompts()[1, :LENS[1]], 16
    toks = _serve(eng, prompt, n)
    assert len(_ring()) == n0 + 1  # pumps record none
    if cfg.compute_dtype == jnp.float32:
        np.testing.assert_array_equal(
            toks, greedy_tokens(masters, prompt, cfg, n))
        return
    f32 = dataclasses.replace(cfg, dtype="float32")
    seq = jnp.asarray(np.concatenate([prompt, toks])[None])
    rows = np.asarray(llama.forward(masters, seq, f32))[
        0, len(prompt) - 1:len(prompt) - 1 + n]
    top2 = np.sort(rows, -1)[:, -2:]
    decided = top2[:, 1] - top2[:, 0] > 0.25
    assert decided.sum() >= n // 4
    np.testing.assert_array_equal(toks[decided], rows.argmax(-1)[decided])


# ---- the adoption points ----

BF16 = llama.LlamaConfig(**CONFIGS["gqa-bf16"])


def _ring():
    return [s for s in fr._get().ring if s["name"] == "serve.weights_cast"]


def _masters(seed):
    return llama.init_params(BF16, jax.random.PRNGKey(seed))


def _assert_serving_types(tree):
    assert llama.serving_params(BF16, tree) is tree
    assert tree["layers"]["wq"].dtype == BF16.compute_dtype
    assert tree["layers"]["attn_norm"].dtype == jnp.float32


def test_set_params_adopts_the_serving_cast_of_a_published_tree():
    from ray_tpu.models.kv_prefix_cache import PrefixCache

    cache = PrefixCache(block=4, max_bytes=2**20)
    eng = RaggedDecoder(_masters(0), BF16, slots=SLOTS, max_len=MAX_LEN,
                        chunk_tokens=CHUNK, prompt_buckets=(BUCKET,),
                        prefix_cache=cache)
    prompt = _prompts()[1, :LENS[1]]
    before = _serve(eng, prompt, 10)
    assert cache.stats()["entries"] > 0
    n0 = len(_ring())
    published = jax.tree_util.tree_map(np.asarray, _masters(1))  # host f32
    eng.set_params(published, 4)
    _assert_serving_types(eng.params)
    assert cache.stats()["entries"] == 0  # rows of the old weights
    st = eng.stats()
    assert st["weights_version"] == 4
    assert st["weights_bytes"] == _nbytes(eng.params)
    after = _serve(eng, prompt, 10)
    fresh = RaggedDecoder(_masters(1), BF16, slots=SLOTS, max_len=MAX_LEN,
                          chunk_tokens=CHUNK, prompt_buckets=(BUCKET,))
    np.testing.assert_array_equal(after, _serve(fresh, prompt, 10))
    assert not np.array_equal(after, before)
    casts = _ring()[n0:]
    # the publish and the fresh engine: one span an adoption
    assert [s["attrs"]["version"] for s in casts] == [4, 0]
    assert casts[0]["attrs"]["bytes_in"] == _nbytes(published)
    assert casts[0]["attrs"]["bytes_out"] == st["weights_bytes"]


def _tiny_bf16_model(model_size="tiny", *, params_blob=None, seed=0, **_):
    """``serve.llm.build_model`` for a bf16 model small enough for a CPU
    (its own "tiny" computes in f32, where the cast is the identity)."""
    if params_blob is not None:
        return jax.tree_util.tree_map(jnp.asarray, params_blob), BF16
    return _masters(seed), BF16


def test_llm_server_update_weights_publishes_into_serving_types():
    from ray_tpu.serve import llm

    with mock.patch.object(llm, "build_model", _tiny_bf16_model):
        srv = llm.LLMServer("tiny", slots=SLOTS, max_len=MAX_LEN,
                            chunk_tokens=CHUNK, prompt_buckets=(BUCKET,))
    try:
        _assert_serving_types(srv.engine.params)
        prompt = _prompts()[1, :LENS[1]].tolist()
        srv.generate(prompt, 6)
        n0 = len(_ring())
        published = jax.tree_util.tree_map(np.asarray, _masters(1))
        assert srv.update_weights(published, 3) == 3
        deadline = time.time() + 60
        while srv.weights_version() != 3 and time.time() < deadline:
            time.sleep(0.01)  # adopted by the pump thread, between pumps
        assert srv.weights_version() == 3
        _assert_serving_types(srv.engine.params)
        got = np.asarray(srv.generate(prompt, 10)["tokens"])
        fresh = RaggedDecoder(_masters(1), BF16, slots=SLOTS,
                              max_len=MAX_LEN, chunk_tokens=CHUNK,
                              prompt_buckets=(BUCKET,))
        np.testing.assert_array_equal(got, _serve(fresh, prompt, 10))
        # one for the publish, one for ``fresh``; the pumps added none
        assert [s["attrs"]["version"] for s in _ring()[n0:]] == [3, 0]
        assert srv.stats()["weights_bytes"] == _nbytes(srv.engine.params)
    finally:
        srv.shutdown(drain_s=10.0)


def test_prefill_worker_adopts_the_serving_cast():
    from ray_tpu.serve import llm_pool

    n0 = len(_ring())
    with mock.patch.object(llm_pool, "build_model", _tiny_bf16_model):
        worker = llm_pool.PrefillWorker(
            "tiny", max_len=MAX_LEN, prompt_buckets=(BUCKET,))
    _assert_serving_types(worker.params)
    prompt = _prompts()[1, :LENS[1]]
    first = worker.prefill(prompt.tolist())
    assert worker.update_weights(
        jax.tree_util.tree_map(np.asarray, _masters(1)), 2) == 2
    _assert_serving_types(worker.params)
    out = worker.prefill(prompt.tolist())
    assert out["version"] == 2
    assert [s["attrs"]["version"] for s in _ring()[n0:]] == [0, 2]
    # the rows and first token are those of the engine's own prefill
    # from the masters: a decode replica adopts them as its own
    row = np.zeros((1, BUCKET), np.int32)
    row[0, :len(prompt)] = prompt
    k, v, tok0, _ = de.prefill_kv(
        _masters(1), row, np.array([len(prompt)], np.int32), *_lanes(1),
        BF16, MAX_LEN)
    assert out["first_token"] == int(tok0[0])
    assert out["first_token"] != first["first_token"] \
        or not np.array_equal(out["k"], first["k"])
    assert np.asarray(out["k"]).tobytes() == np.asarray(k[:, 0]).tobytes()
    assert np.asarray(out["v"]).tobytes() == np.asarray(v[:, 0]).tobytes()
