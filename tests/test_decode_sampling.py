"""Sampled decoding: temperature/top-p lanes, seed replay, logprobs.

The invariants the RL rollout path leans on (ISSUE 12 satellite):

- temperature 0 through the sampled kernel is BIT-IDENTICAL to greedy
  decode (the serving default cannot regress);
- a stream's tokens are a pure function of (weights, prompt, seed) —
  independent of slot index, batch composition, and which engine decodes
  it (seed-replay: what makes replica-death failover dedup exact under
  sampling);
- per-token logprobs match teacher-forced `llama.forward` log-softmax
  (what the learner computes its importance ratios against);
- the disaggregated-prefill path samples the same first token as inline.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from _oracle import greedy_tokens  # noqa: E402
from ray_tpu.models import decode_engine as de  # noqa: E402
from ray_tpu.models import llama  # noqa: E402
from ray_tpu.models import llama_slots  # noqa: E402
from ray_tpu.models.decode_engine import (  # noqa: E402
    RaggedDecoder,
    prefill_kv,
)

TINY = llama.LlamaConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=64, dtype="float32", remat=False)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY, jax.random.PRNGKey(0))


def _greedy(params, prompt, n):
    return greedy_tokens(params, prompt, TINY, n)


def _teacher_forced_logprobs(params, cfg, prompt, toks):
    """log-softmax of the uncached forward over prompt + toks, at each
    of ``toks`` from the position before it."""
    seq = np.concatenate([prompt, toks]).astype(np.int32)
    logp = np.asarray(jax.nn.log_softmax(llama.forward(
        params, jnp.asarray(seq[None]), cfg)[0].astype(jnp.float32)))
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    return logp[at, toks]


def _run_stream(params, prompt, n, *, temperature, seed, top_p=1.0,
                extra_streams=0, slots=2, chunk=4, rng_seed=99):
    """Decode one stream (optionally amid unrelated concurrent
    streams) and return (tokens, logprobs)."""
    eng = RaggedDecoder(params, TINY, slots=slots, max_len=64,
                        chunk_tokens=chunk, prompt_buckets=(8, 16))
    rng = np.random.RandomState(rng_seed)
    others = [eng.submit(rng.randint(1, 250, 6).astype(np.int32), n,
                         temperature=0.7, seed=int(rng.randint(2**31)))
              for _ in range(extra_streams)]
    sid = eng.submit(np.asarray(prompt, np.int32), n,
                     temperature=temperature, top_p=top_p, seed=seed)
    eng.drain()
    s = eng.pop_finished(sid)
    for o in others:
        eng.purge(o)
    return (np.asarray(s.tokens[:n]),
            np.asarray(s.logprobs[:n], np.float32))


def test_temperature_zero_is_bit_identical_to_greedy(params):
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    toks, lps = _run_stream(params, prompt, 12, temperature=0.0, seed=5)
    np.testing.assert_array_equal(toks, _greedy(params, prompt, 12))
    assert len(lps) == len(toks)
    assert np.all(lps <= 0.0)


def test_sampling_is_deterministic_and_seed_sensitive(params):
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    a = _run_stream(params, prompt, 10, temperature=1.0, seed=123)
    b = _run_stream(params, prompt, 10, temperature=1.0, seed=123)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    c = _run_stream(params, prompt, 10, temperature=1.0, seed=124)
    assert not np.array_equal(a[0], c[0])
    # and sampling at high temperature actually deviates from greedy
    assert not np.array_equal(a[0], _greedy(params, prompt, 10))


def test_seed_replay_independent_of_batch_composition(params):
    """The failover contract: the SAME (prompt, seed) decoded alone on
    one engine and amid 3 unrelated sampled streams on another yields
    identical tokens AND logprobs — RNG lanes are (seed, position),
    never slot- or batch-dependent."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    alone = _run_stream(params, prompt, 10, temperature=0.9, seed=777,
                        slots=2, extra_streams=0)
    crowded = _run_stream(params, prompt, 10, temperature=0.9, seed=777,
                          slots=4, extra_streams=3, rng_seed=41)
    np.testing.assert_array_equal(alone[0], crowded[0])
    np.testing.assert_allclose(alone[1], crowded[1], atol=1e-5)


def test_tiny_top_p_recovers_greedy(params):
    """top_p small enough keeps only the top token — sampling must
    reduce to greedy exactly (temperature rescaling preserves argmax)."""
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    toks, _ = _run_stream(params, prompt, 10, temperature=1.3,
                          top_p=1e-6, seed=9)
    np.testing.assert_array_equal(toks, _greedy(params, prompt, 10))


def test_logprobs_match_teacher_forced_forward(params):
    """Engine behavior logprobs == log_softmax of the full forward at
    the sampled tokens (temperature 1, top_p 1): the exact consistency
    the learner's importance ratio depends on."""
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, 250, 8).astype(np.int32)
    toks, lps = _run_stream(params, prompt, 8, temperature=1.0,
                            seed=1234)
    np.testing.assert_allclose(
        lps, _teacher_forced_logprobs(params, TINY, prompt, toks), atol=1e-4)


def test_disaggregated_prefill_samples_same_first_token(params):
    """prefill_kv on a 'prefill worker' must sample the SAME
    first token/logprob as an inline sampled admission (same (seed,
    true_len-1) lane), and the adopted stream continues identically."""
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, 250, 7).astype(np.int32)
    inline_toks, inline_lps = _run_stream(
        params, prompt, 10, temperature=1.0, seed=4321)
    padded = np.zeros((1, 8), np.int32)
    padded[0, :len(prompt)] = prompt
    k, v, tok0, lp0 = prefill_kv(
        params, jnp.asarray(padded),
        jnp.asarray([len(prompt)], jnp.int32),
        jnp.asarray([4321], jnp.uint32), jnp.asarray([1.0], jnp.float32),
        jnp.asarray([1.0], jnp.float32), TINY, 64)
    assert int(tok0[0]) == int(inline_toks[0])
    np.testing.assert_allclose(float(lp0[0]), inline_lps[0], atol=1e-5)
    kv = {"k": np.asarray(k[:, 0]), "v": np.asarray(v[:, 0]),
          "first_token": int(tok0[0]), "first_logprob": float(lp0[0]),
          "true_len": len(prompt)}
    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=4, prompt_buckets=(8,))
    sid = eng.submit_prefilled(prompt, 10, kv, temperature=1.0,
                               seed=4321)
    eng.drain()
    s = eng.pop_finished(sid)
    np.testing.assert_array_equal(np.asarray(s.tokens[:10]), inline_toks)
    np.testing.assert_allclose(np.asarray(s.logprobs[:10], np.float32),
                               inline_lps, atol=1e-5)


def test_take_tokens_streams_logprobs_in_lockstep(params):
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, 250, 6).astype(np.int32)
    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=4, prompt_buckets=(8,))
    sid = eng.submit(prompt, 9, temperature=0.8, seed=55)
    got_t, got_l, done = [], [], False
    while not done:
        eng.pump()
        new, lps, done = eng.take_tokens(sid, with_logprobs=True)
        assert len(new) == len(lps)
        got_t.extend(new)
        got_l.extend(lps)
    ref_t, ref_l = _run_stream(params, prompt, 9, temperature=0.8,
                               seed=55)
    np.testing.assert_array_equal(np.asarray(got_t[:9]), ref_t)
    np.testing.assert_allclose(np.asarray(got_l[:9], np.float32), ref_l,
                               atol=1e-5)
    # drained + finished → purged, with the 3-tuple shape
    assert eng.take_tokens(sid, with_logprobs=True) == ([], [], True)
    # legacy 2-tuple shape unchanged
    assert eng.take_tokens(sid) == ([], True)


@pytest.mark.parametrize("entry", ["submit", "submit_prefilled"])
def test_submit_validates_top_p(params, entry):
    """Both ways in refuse, at the submitter, a ``top_p`` outside (0, 1]
    and a prompt that leaves no room to decode."""
    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=4, prompt_buckets=(8, 64))

    def enqueue(prompt, **kw):
        if entry == "submit":
            return eng.submit(prompt, 4, **kw)
        rows = np.zeros((TINY.n_layers, 64, TINY.n_kv_heads, TINY.head_dim),
                        np.float32)
        return eng.submit_prefilled(prompt, 4, {
            "k": rows, "v": rows, "first_token": 1,
            "true_len": len(prompt)}, **kw)

    with pytest.raises(ValueError, match="top_p"):
        enqueue([1, 2, 3], temperature=1.0, top_p=0.0)
    with pytest.raises(ValueError, match="top_p"):
        enqueue([1, 2, 3], temperature=1.0, top_p=1.5)
    with pytest.raises(ValueError, match="no decode room"):
        enqueue([1] * 63)
    assert not eng.queue
    enqueue([1, 2, 3], temperature=1.0, top_p=1.0)
    assert len(eng.queue) == 1


def _gqa(group):
    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=4 // group, d_ff=128, max_seq_len=48, dtype="float32",
        remat=False)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(10 + group))


def _lanes(n, seed=0, temp=0.0):
    return (np.full(n, seed, np.uint32), np.full(n, temp, np.float32),
            np.ones(n, np.float32))


@pytest.mark.parametrize("group", [1, 2])
def test_chunk_program_with_lanes_at_temperature_zero_is_the_greedy_one(
        group):
    """The one chunk program, traced without lanes and with lanes at
    temperature 0, on the same prefilled slots: the same tokens; the
    second also returns each token's logprob, which is the uncached
    forward's log-softmax at that token."""
    cfg, params = _gqa(group)
    slots, max_len, bucket, chunk = 2, 48, 16, 8
    rng = np.random.RandomState(29)
    lens = [7, 13]
    prompts = np.zeros((slots, bucket), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.randint(1, 250, n)

    def prefilled():
        return de._prefill_batch_into_slots(
            params, prompts, np.array(lens, np.int32),
            np.arange(slots, dtype=np.int32), *_lanes(slots),
            llama_slots.init_ragged_cache(cfg, slots, max_len),
            jnp.zeros((slots,), jnp.int32), cfg)

    active = np.ones(slots, bool)
    cache, tok, toks0, lps0 = prefilled()
    greedy, none, _, _ = de.decode_chunk(
        params, cache, tok, active, None, cfg, chunk)
    assert none is None
    cache, tok, _, _ = prefilled()
    toks, lps, _, _ = de.decode_chunk(
        params, cache, tok, active, _lanes(slots), cfg, chunk)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(greedy))
    for i, n in enumerate(lens):
        served = np.concatenate([np.asarray(toks0)[i:i + 1],
                                 np.asarray(toks)[i]])
        want = _teacher_forced_logprobs(params, cfg, prompts[i, :n], served)
        np.testing.assert_allclose(
            np.concatenate([np.asarray(lps0)[i:i + 1], np.asarray(lps)[i]]),
            want, atol=1e-4)


@pytest.mark.parametrize("temp", [0.0, 0.8])
def test_the_prefill_entries_agree(temp):
    """One GQA prompt of 11 tokens through the three ways into a slot —
    cold, the prefix cache's rows + a suffix prefill, rows out of
    ``prefill_kv`` and adopted — leaves the same k/v rows in the slot,
    the same pos, and gives the same first token and logprob on the
    (seed, position 10) lane, greedy and sampled."""
    cfg, params = _gqa(2)
    slots, max_len, n, n_pref = 2, 48, 11, 8
    prompt = np.random.RandomState(30).randint(1, 250, n).astype(np.int32)
    lane = _lanes(1, seed=77, temp=temp)
    slot = np.array([1], np.int32)

    def into_slot(tokens, width, prefix=None):
        row = np.zeros((1, width), np.int32)
        row[0, :len(tokens)] = tokens
        cache, cur, tok0, lp0 = de._prefill_batch_into_slots(
            params, row, np.array([len(tokens)], np.int32), slot, *lane,
            llama_slots.init_ragged_cache(cfg, slots, max_len),
            jnp.zeros((slots,), jnp.int32), cfg, prefix)
        assert int(cur[1]) == int(tok0[0])
        return cache, int(tok0[0]), float(lp0[0])

    cold, tok0, lp0 = into_slot(prompt, 16)
    assert list(np.asarray(cold["pos"])) == [0, n]
    assert not np.asarray(cold["k"][:, 0]).any()  # the other slot
    assert not np.asarray(cold["k"][:, 1, 16:]).any()  # full-slot write

    pref = {kv: np.zeros((cfg.n_layers, 1, max_len, cfg.n_kv_heads,
                          cfg.head_dim), np.float32) for kv in "kv"}
    for kv in "kv":  # (the slots hold a row's kv heads end to end)
        pref[kv][:, 0, :n_pref] = np.asarray(
            cold[kv][:, 1, :n_pref]).reshape(pref[kv][:, 0, :n_pref].shape)
    warm, tok_w, lp_w = into_slot(
        prompt[n_pref:], 4, (pref["k"], pref["v"], np.int32(n_pref)))

    row = np.zeros((1, 16), np.int32)
    row[0, :n] = prompt
    k, v, tok_r, lp_r = de.prefill_kv(
        params, row, np.array([n], np.int32), *lane, cfg, max_len)
    adopted, cur = de._adopt_kv_into_slot(
        k[:, 0], v[:, 0], np.int32(n), tok_r[0], np.int32(1),
        llama_slots.init_ragged_cache(cfg, slots, max_len),
        jnp.zeros((slots,), jnp.int32), cfg)
    assert int(cur[1]) == int(tok_r[0])

    assert tok_w == tok0 and int(tok_r[0]) == tok0
    np.testing.assert_allclose([lp_w, float(lp_r[0])], lp0, atol=1e-5)
    for other in (warm, adopted):
        assert list(np.asarray(other["pos"])) == [0, n]
        for kv in "kv":  # the rows a stream can ever see: its prompt's
            np.testing.assert_allclose(
                np.asarray(other[kv][:, 1, :n]),
                np.asarray(cold[kv][:, 1, :n]), atol=1e-5)
    # and against the uncached forward: the logprob under the sampling
    # distribution (temperature-scaled; top_p is 1), greedy the argmax
    logits = llama.forward(params, jnp.asarray(prompt[None]), cfg)[0, -1]
    logits = logits.astype(jnp.float32)
    if not temp:
        assert tok0 == int(jnp.argmax(logits))
    np.testing.assert_allclose(
        lp0, jax.nn.log_softmax(logits / temp if temp else logits)[tok0],
        atol=1e-4)


def test_stats_carry_version_and_pumps(params):
    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=4, prompt_buckets=(8,),
                        weights_version=7)
    st = eng.stats()
    assert st["weights_version"] == 7
    assert st["pumps"] == 0
    eng.submit([1, 2, 3], 2)
    eng.pump()
    assert eng.stats()["pumps"] == 1
    # set_params bumps the version and drops nothing else
    eng.set_params(eng.params, 9)
    assert eng.stats()["weights_version"] == 9
