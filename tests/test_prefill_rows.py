"""A cold prefill does its prompt's work, not its slot's (ISSUE 35).

``llama_slots._prefill_core`` is sized by the prompts' bucket P: each
layer attends over the prompt's own P rows (``ops.attention``: the flash
kernel on a TPU, here the reference product or the kernel interpreted),
the final norm and the head see the last real position alone, and P rows
go into the slot, whatever lay behind them. Held against the uncached
forward (``tests/_oracle.py``) at every bucket with a padded prompt, for
a GQA 16 / 8 model and for an MHA one with ``qk_norm`` and experts; and
the two other ways into a slot, the prefix cache's warm path and
``prefill_kv`` -> ``submit_prefilled``, give the cold tokens in a slot
another stream has used.
"""

import dataclasses
import functools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from _oracle import greedy_tokens  # noqa: E402
from ray_tpu.models import decode_engine as de  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models import llama_slots  # noqa: E402
from ray_tpu.models.kv_prefix_cache import PrefixCache  # noqa: E402

SLOTS, MAX_LEN = 3, 48
BUCKETS = {8: 5, 16: 11, 32: 21}  # bucket: the padded prompt's length
MODELS = {
    "gqa_16_8": dict(n_heads=16, n_kv_heads=8),
    "mha_qk_norm_experts": dict(
        n_heads=4, n_kv_heads=4, qk_norm=True, n_experts=4, top_k=2,
        norm_topk_prob=False, moe_impl="dropless"),
}


@functools.lru_cache(maxsize=None)
def _model(name: str):
    cfg = llama.LlamaConfig(
        vocab_size=251, d_model=128, n_layers=2, d_ff=256, max_seq_len=64,
        dtype="float32", remat=False, **MODELS[name])
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(35))


def _prompt(n: int, seed: int = 35):
    return np.random.RandomState(seed + n).randint(1, 250, n).astype(np.int32)


def _lanes(temp: float = 0.0):
    return (np.array([9], np.uint32), np.array([temp], np.float32),
            np.ones(1, np.float32))


def _prefill(cfg, params, prompt, width, slot, cache=None):
    row = np.zeros((1, width), np.int32)
    row[0, :len(prompt)] = prompt
    if cache is None:
        cache = llama_slots.init_ragged_cache(cfg, SLOTS, MAX_LEN)
    return de._prefill_batch_into_slots(
        params, row, np.array([len(prompt)], np.int32),
        np.array([slot], np.int32), *_lanes(), cache,
        jnp.zeros((SLOTS,), jnp.int32), cfg)


@pytest.mark.parametrize("attn", ["product", "flash"])
@pytest.mark.parametrize("bucket", list(BUCKETS))
@pytest.mark.parametrize("name", list(MODELS))
def test_cold_prefill_is_the_uncached_forwards(name, bucket, attn,
                                               monkeypatch):
    """First token, logprob and the slot's rows of a padded prompt: the
    token is the argmax of the uncached forward at the last real
    position and carries its log-probability; the slot's first P rows
    are written (the real ones those of an unpadded call) and its
    ``pos``, every other row of the stack is left as it lay; and the
    chunk program decodes the oracle's tokens from those rows."""
    cfg, params = _model(name)
    if attn == "flash":  # no chip here: the interpreter, by choice
        from ray_tpu.ops import flash_attention as fa

        monkeypatch.setattr(fa, "flash_attention", functools.partial(
            fa.flash_attention, interpret=True))
        cfg = dataclasses.replace(cfg, use_flash=True)
    n, slot = BUCKETS[bucket], 1
    prompt = _prompt(n)
    lay = llama_slots.init_ragged_cache(cfg, SLOTS, MAX_LEN)
    lay = {**lay, "k": lay["k"] + 7.0, "v": lay["v"] - 7.0}
    was = {kv: np.asarray(lay[kv]) for kv in "kv"}  # (lay is donated)
    cache, cur, tok0, lp0, *loads = _prefill(
        cfg, params, prompt, bucket, slot, lay)

    logits = llama.forward(params, jnp.asarray(prompt[None]), dataclasses
                           .replace(cfg, use_flash=False))[0, -1]
    assert int(tok0[0]) == int(jnp.argmax(logits)) == int(cur[slot])
    np.testing.assert_allclose(
        float(lp0[0]), float(jax.nn.log_softmax(logits)[int(tok0[0])]),
        atol=1e-4)
    assert list(np.asarray(cache["pos"])) == [0, n, 0]

    exact, *_ = _prefill(dataclasses.replace(cfg, use_flash=False), params,
                         prompt, n, slot)
    for kv in "kv":
        got = np.asarray(cache[kv])
        np.testing.assert_allclose(
            got[:, slot, :n], np.asarray(exact[kv])[:, slot, :n], atol=1e-5)
        np.testing.assert_array_equal(got[:, slot, bucket:],
                                      was[kv][:, slot, bucket:])
        np.testing.assert_array_equal(got[:, [0, 2]], was[kv][:, [0, 2]])
        assert not (got[:, slot, :bucket] == was[kv][:, slot, :bucket]).any()
    if cfg.n_experts:  # the real positions' assignments, no pad row's
        assert loads[0].shape == (cfg.n_layers, cfg.n_experts)
        assert list(np.asarray(loads[0]).sum(axis=1)) == [n * cfg.top_k] * 2
    else:
        assert loads == []

    toks, *_ = de.decode_chunk(
        params, cache, cur, np.arange(SLOTS) == slot, None, cfg, 6)
    np.testing.assert_array_equal(
        [int(tok0[0]), *np.asarray(toks)[slot]],
        greedy_tokens(params, prompt, cfg, 7))


@pytest.mark.parametrize("bucket", list(BUCKETS))
@pytest.mark.parametrize("name", list(MODELS))
def test_cold_prefill_is_sized_by_its_bucket(name, bucket):
    """The program as traced: but for the stack itself (and the one
    slot's P rows taken out of and put into it) nothing has an extent of
    ``max_len`` rows, no score is wider than P x P, and the only
    vocabulary-wide values are a row a stream, not the bucket's P."""
    cfg, params = _model(name)
    vec = lambda dt: jax.ShapeDtypeStruct((1,), dt)  # noqa: E731
    text = de._prefill_batch_into_slots.lower(
        params, jax.ShapeDtypeStruct((1, bucket), jnp.int32),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.uint32), vec(jnp.float32),
        vec(jnp.float32), llama_slots.init_ragged_cache(cfg, SLOTS, MAX_LEN),
        jnp.zeros((SLOTS,), jnp.int32), cfg=cfg).as_text()
    shapes = {tuple(int(d) for d in dims[:-1].split("x"))
              for dims in re.findall(r"tensor<((?:\d+x)+)", text)}
    stack = (cfg.n_layers, SLOTS, MAX_LEN, cfg.n_kv_heads * cfg.head_dim)
    assert stack in shapes
    assert {s for s in shapes if MAX_LEN in s} == {stack}
    assert (1, cfg.n_heads, bucket, bucket) in shapes  # the scores
    wide = {s for s in shapes if cfg.vocab_size in s}
    assert wide and all(bucket not in s for s in wide), wide


def _held_a_long_stream(eng):
    """Run one stream through every slot to the cache's edge."""
    sids = [eng.submit(_prompt(30, seed=i), 100) for i in range(eng.slots)]
    eng.drain()
    for sid in sids:
        assert len(eng.pop_finished(sid).tokens) == MAX_LEN - 30 - 1


@pytest.mark.parametrize("entry", ["warm", "prefilled"])
@pytest.mark.parametrize("name", list(MODELS))
def test_other_entries_give_the_cold_tokens_in_a_used_slot(name, entry):
    """The prefix cache's warm path (cached rows, then a suffix prefill
    behind them) and ``prefill_kv`` -> ``submit_prefilled`` (a prefill
    worker's rows adopted) into slots whose last streams ran to the
    cache's edge: the oracle's tokens, which are the cold path's."""
    cfg, params = _model(name)
    prompt = _prompt(21)
    want = greedy_tokens(params, prompt, cfg, 12)
    kw = dict(slots=2, max_len=MAX_LEN, chunk_tokens=4,
              prompt_buckets=(8, 16, 32))
    if entry == "warm":
        pc = PrefixCache(block=8)
        eng = de.RaggedDecoder(params, cfg, prefix_cache=pc, **kw)
        _held_a_long_stream(eng)
        cold = eng.submit(prompt, 12)  # leaves rows 0..15 in the cache
        eng.drain()
        np.testing.assert_array_equal(eng.pop_finished(cold).tokens, want)
        sid = eng.submit(prompt, 12)
        eng.drain()
        assert pc.stats()["hits"] == 1
    else:
        eng = de.RaggedDecoder(params, cfg, **kw)
        _held_a_long_stream(eng)
        row = np.zeros((1, 32), np.int32)
        row[0, :21] = prompt
        k, v, tok0, lp0 = de.prefill_kv(
            eng.params, row, np.array([21], np.int32), *_lanes(), cfg,
            MAX_LEN)
        assert k.shape == (cfg.n_layers, 1, MAX_LEN, cfg.n_kv_heads,
                           cfg.head_dim)
        assert not np.asarray(k[:, :, 32:]).any()
        sid = eng.submit_prefilled(prompt, 12, {
            "k": np.asarray(k[:, 0]), "v": np.asarray(v[:, 0]),
            "first_token": int(tok0[0]), "first_logprob": float(lp0[0]),
            "true_len": 21})
        eng.drain()
    np.testing.assert_array_equal(eng.pop_finished(sid).tokens, want)
