"""Continuous-batching LLM serving (serve/llm.py + decode_engine.py):
greedy-parity of the ragged engine under slot churn, and the Serve
deployment path end-to-end with concurrent requests sharing one slot
batch (reference anchor: OPT-30B inference release test)."""

import dataclasses
import functools
import time

import numpy as np
import pytest

import jax

import ray_tpu
from _oracle import greedy_tokens
from ray_tpu import serve
from ray_tpu.cluster_utils import Cluster
from ray_tpu.models import llama
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.ops import decode_attention as da
from ray_tpu.serve.api import Deployment
from ray_tpu.serve.llm import LLMServer

TINY = llama.LlamaConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=128, dtype="float32", remat=False)


def _greedy(params, prompt, max_new):
    return greedy_tokens(params, prompt, TINY, max_new)


def test_ragged_engine_matches_greedy_generate():
    """Every stream decoded by the continuous-batching engine — under
    queueing, staggered admission, and slot reuse — must match the
    per-stream greedy_generate reference exactly."""
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 256, size=n).astype(np.int32)
               for n in (5, 9, 17, 26, 31)]
    max_new = 10

    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=3, prompt_buckets=(8, 16, 32))
    sids = [eng.submit(p, max_new) for p in prompts]
    eng.drain()
    for sid, p in zip(sids, prompts):
        got = np.asarray(eng.pop_finished(sid).tokens[:max_new])
        np.testing.assert_array_equal(got, _greedy(params, p, max_new))


def test_engine_interleaves_new_streams_into_free_slots():
    """Continuous batching proper: a LATER-submitted stream must start
    decoding before an earlier long stream finishes (static batching
    would serialize them)."""
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    eng = RaggedDecoder(params, TINY, slots=2, max_len=96,
                        chunk_tokens=4, prompt_buckets=(8,))
    long_sid = eng.submit(rng.randint(1, 256, 6).astype(np.int32), 40)
    short_sid = eng.submit(rng.randint(1, 256, 6).astype(np.int32), 4)
    eng.pump()  # both admitted (2 slots); short finishes first
    while short_sid not in eng.finished:
        eng.pump()
    assert long_sid not in eng.finished  # long still running
    late_sid = eng.submit(rng.randint(1, 256, 6).astype(np.int32), 4)
    eng.pump()  # late stream admitted into the freed slot
    got_service = (late_sid in eng.finished or any(
        s is not None and s.sid == late_sid for s in eng.slot_stream))
    assert got_service, "late stream not admitted while long one runs"
    assert long_sid not in eng.finished  # interleaved, not serialized
    eng.drain()
    assert late_sid in eng.finished and long_sid in eng.finished


# ---- cold prefill: one prompt a call, one row (ISSUE-25) ----


def _prefill_spans():
    from ray_tpu._private import flight_recorder as fr

    return [s["attrs"] for s in fr._get().ring
            if s["name"] == "engine.prefill"]


@pytest.mark.parametrize("n_prompts", [1, 2, 4], ids=["one", "two", "slots"])
def test_one_pump_prefills_each_prompt_in_a_call_of_its_own(n_prompts):
    """Prompts of two buckets admitted by ONE pump: a prefill call and an
    ``engine.prefill`` span for each, one row wide, and every stream's
    tokens are those of the prompt served alone and of the reference."""
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.RandomState(25)
    lens = [6, 13, 16, 3][:n_prompts]  # buckets 8, 16, 16, 8
    prompts = [rng.randint(1, 256, size=n).astype(np.int32) for n in lens]
    kw = dict(slots=4, max_len=64, chunk_tokens=3, prompt_buckets=(8, 16))
    eng = RaggedDecoder(params, TINY, **kw)
    sids = [eng.submit(p, 7) for p in prompts]
    n_spans = len(_prefill_spans())
    assert eng.pump() == n_prompts  # all admitted at once
    assert eng.stats()["prefill_calls"] == n_prompts
    assert _prefill_spans()[n_spans:] == [  # in queue order
        {"bucket": 8 if n <= 8 else 16, "prompts": 1, "rows": 1,
         "tokens": n, "segments": 1, "live_segments": 1} for n in lens]
    eng.drain()
    alone = RaggedDecoder(params, TINY, **kw)
    for sid, p in zip(sids, prompts):
        got = np.asarray(eng.pop_finished(sid).tokens)
        a = alone.submit(p, 7)
        alone.drain()
        np.testing.assert_array_equal(got, alone.pop_finished(a).tokens)
        np.testing.assert_array_equal(got, _greedy(params, p, 7))


def test_a_burst_compiles_no_prefill_program_beyond_one_per_bucket():
    """What the benchmark's warm-up rests on: one request per bucket
    compiles every prefill shape; a burst that fills every slot at once
    adds none."""
    from ray_tpu.models.decode_engine import _prefill_batch_into_slots

    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.RandomState(26)
    # max_len 72: shapes no other test of this process compiles
    eng = RaggedDecoder(params, TINY, slots=8, max_len=72, chunk_tokens=4,
                        prompt_buckets=(8, 16, 32))
    n0 = _prefill_batch_into_slots._cache_size()
    for n in (5, 12, 20):
        eng.submit(rng.randint(1, 256, size=n).astype(np.int32), 2)
        eng.drain()
    assert _prefill_batch_into_slots._cache_size() - n0 == 3
    for n in (3, 8, 9, 16, 17, 32, 7, 30):
        eng.submit(rng.randint(1, 256, size=n).astype(np.int32), 2)
    assert eng.pump() == 8
    assert eng.stats()["prefill_calls"] == 3 + 8
    assert _prefill_batch_into_slots._cache_size() - n0 == 3


@pytest.mark.parametrize("first_ran_to", ["the_edge", "row_40"])
@pytest.mark.parametrize("body", ["xla", "kernel"])
def test_reused_slot_shows_nothing_of_its_previous_occupant(
        body, first_ran_to, monkeypatch):
    """No reader looks past a slot's own length. A long stream decodes
    to the cache's edge (the clamped write at row max_len - 1) or stops
    short of it; a short prompt then takes the slot, and its one-row
    prefill writes its bucket's 8 rows and the slot's ``pos``, nothing
    else: the long stream's rows still lie behind them. The short stream
    decodes over them to the edge itself and emits the reference's
    tokens, as a fresh engine does, through the XLA body and through the
    ``decode_attn`` kernel (interpreted), which reads a slot's blocks up
    to its length and masks inside the last."""
    cfg = TINY
    if body == "kernel":  # (a size of its own: decode_chunk is cached)
        monkeypatch.setattr(da, "decode_attention", functools.partial(
            da.decode_attention, interpret=True))
        cfg = dataclasses.replace(
            TINY, vocab_size=253 - (first_ran_to == "row_40"))
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(27)
    kw = dict(slots=1, max_len=48, chunk_tokens=4, prompt_buckets=(8, 32))
    eng = RaggedDecoder(params, cfg, **kw)
    long_p = rng.randint(1, 250, size=30).astype(np.int32)
    short_p = rng.randint(1, 250, size=5).astype(np.int32)
    first_new = 100 if first_ran_to == "the_edge" else 10
    sid = eng.submit(long_p, first_new)  # (clamped to the slot's room)
    eng.drain()
    assert len(eng.pop_finished(sid).tokens) == min(first_new, 48 - 30 - 1)
    # (a row of the stack is the position's kv heads end to end)
    before = {kv: np.asarray(eng.cache[kv]) for kv in "kv"}
    assert np.abs(before["k"][:, 0, 9:40]).min(axis=(0, 2)).min() > 0
    sid = eng.submit(short_p, 100)
    eng.pump()  # prefill at bucket 8, then 4 decode steps: rows 5..8
    assert int(eng.cache["pos"][0]) == 9
    for kv in "kv":  # the long stream's rows, where they were
        np.testing.assert_array_equal(
            np.asarray(eng.cache[kv])[:, 0, 9:], before[kv][:, 0, 9:])
        assert not np.array_equal(
            np.asarray(eng.cache[kv])[:, 0, :9], before[kv][:, 0, :9])
    eng.drain()
    got = np.asarray(eng.pop_finished(sid).tokens)
    assert len(got) == 48 - 5 - 1
    np.testing.assert_array_equal(
        got, greedy_tokens(params, short_p, cfg, 42))
    fresh = RaggedDecoder(params, cfg, **kw)
    sid = fresh.submit(short_p, 100)
    fresh.drain()
    np.testing.assert_array_equal(got, fresh.pop_finished(sid).tokens)


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(head_resources={"CPU": 4, "memory": 4 * 2**30})
    c.connect()
    yield c
    serve.shutdown()
    c.shutdown()


def test_llm_deployment_concurrent_requests(cluster):
    """Concurrent generate() calls through a Serve replica share ONE
    slot batch; every request returns its exact greedy continuation."""
    dep = Deployment(LLMServer, max_concurrent_queries=8,
                     resources={"CPU": 0}, route_prefix="/llm")
    handle = serve.run(dep, name="llm", init_kwargs={
        "model_size": "tiny", "slots": 2, "max_len": 96,
        "chunk_tokens": 4, "prompt_buckets": (8, 16)})

    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, 256, size=7).astype(np.int32)
               for _ in range(5)]
    max_new = 8
    t0 = time.perf_counter()
    refs = [handle.remote({"prompt_ids": p.tolist(),
                           "max_tokens": max_new}) for p in prompts]
    outs = ray_tpu.get(refs, timeout=300)
    assert time.perf_counter() - t0 < 300
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(
            np.asarray(out["tokens"]), _greedy(params, p, max_new))
        assert len(out["token_times_s"]) == max_new
        assert out["token_times_s"][0] >= out["submitted_s"]
