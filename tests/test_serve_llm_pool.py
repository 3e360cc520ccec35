"""Production serving tier (serve/llm_pool.py + models/kv_prefix_cache.py).

Covers the ISSUE-10 acceptance surface: multi-replica pool behind one
admission queue, prefill/decode disaggregation through the object
store, prefix/KV-cache reuse with BIT-IDENTICAL outputs vs cold
prefill, SLO-keyed replica demand (autoscaler hook), graceful replica
shutdown, chaos failover with no client-visible error, and token
streaming through pool + HTTP proxy chunked responses."""

import threading
import time

import numpy as np
import pytest

import jax

import ray_tpu
from _oracle import greedy_tokens
from ray_tpu import serve
from ray_tpu.cluster_utils import Cluster
from ray_tpu.models import llama
from ray_tpu.models.decode_engine import RaggedDecoder, prefill_kv
from ray_tpu.models.kv_prefix_cache import PrefixCache, chain_keys
from ray_tpu.serve.llm import LLMServer
from ray_tpu.serve.llm_pool import LLMPool

TINY = llama.LlamaConfig(
    vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
    d_ff=128, max_seq_len=96, dtype="float32", remat=False)


def _greedy(params, prompt, max_new):
    return greedy_tokens(params, prompt, TINY, max_new)


# ---------------- pure units (no cluster) ----------------

def test_serve_replica_demand_policy():
    from ray_tpu.autoscaler.demand_scheduler import serve_replica_demand

    kw = dict(min_replicas=1, max_replicas=8,
              target_queue_per_replica=4.0)
    # load-driven sizing
    assert serve_replica_demand(queue_depth=0, inflight=0,
                                n_replicas=1, **kw) == 1
    assert serve_replica_demand(queue_depth=10, inflight=6,
                                n_replicas=1, **kw) == 4
    # clamped to max
    assert serve_replica_demand(queue_depth=100, inflight=0,
                                n_replicas=2, **kw) == 8
    # SLO breach asks for one more than current even at low load
    assert serve_replica_demand(
        queue_depth=0, inflight=2, n_replicas=3, ttft_p99_s=1.0,
        target_ttft_s=0.5, **kw) == 4
    # scale-down held while ttft sits inside the headroom band
    assert serve_replica_demand(
        queue_depth=0, inflight=1, n_replicas=3, ttft_p99_s=0.4,
        target_ttft_s=0.5, **kw) == 3
    # scale-down allowed with real SLO headroom
    assert serve_replica_demand(
        queue_depth=0, inflight=1, n_replicas=3, ttft_p99_s=0.1,
        target_ttft_s=0.5, **kw) == 1


def test_replica_resource_demands_feed_bin_packer():
    from ray_tpu.autoscaler.demand_scheduler import (
        get_nodes_to_launch,
        replica_resource_demands,
    )

    demands = replica_resource_demands(3, {"TPU": 1.0})
    assert demands == [{"TPU": 1.0}] * 3
    launch = get_nodes_to_launch(
        demands,
        {"tpu": {"resources": {"TPU": 1.0, "CPU": 4.0},
                 "max_workers": 10}},
        free_capacities=[])
    assert launch == {"tpu": 3}


def test_prefix_cache_lru_and_match():
    pc = PrefixCache(block=4, max_bytes=10_000)
    toks = np.arange(1, 17, dtype=np.int32)  # 16 tokens, 4 blocks
    k = np.zeros((2, 12, 2, 8), np.float32)  # rows for 3 blocks
    v = np.ones_like(k)
    assert pc.insert(toks, k, v) == 3  # only 3 blocks have rows
    # deepest cached depth wins; capped at len(prompt)-1
    n, e = pc.match(toks[:13])
    assert n == 12 and e is not None
    n, e = pc.match(toks[:9])
    assert n == 8 and e is not None
    # diverging block breaks the chain
    other = toks.copy()
    other[5] = 99
    n, _ = pc.match(other)
    assert n == 4
    # byte-budget eviction is LRU
    small = PrefixCache(block=4, max_bytes=k[:, :4].nbytes * 2 + 1)
    small.insert(toks[:5], k[:, :4], v[:, :4])
    assert small.stats()["entries"] == 1
    small.insert(np.asarray([7, 7, 7, 7, 7], np.int32),
                 k[:, :4] + 1, v[:, :4])
    small.insert(np.asarray([9, 9, 9, 9, 9], np.int32),
                 k[:, :4] + 2, v[:, :4])
    st = small.stats()
    assert st["evictions"] >= 1 and st["bytes"] <= small.max_bytes
    assert chain_keys(toks, 4)[0] == chain_keys(toks[:7], 4)[0]


# ---------------- engine-level numerics (no cluster) ----------------

def test_prefix_cache_decode_bit_identical_to_cold_prefill():
    """THE prefix-cache acceptance numerics: a repeated-system-prompt
    workload must serve cached-prefix requests with tokens bit-identical
    to a cold full prefill."""
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    pc = PrefixCache(block=8, max_bytes=1 << 26)
    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=3, prompt_buckets=(8, 16, 32),
                        prefix_cache=pc)
    rng = np.random.RandomState(1)
    head = rng.randint(1, 256, size=16).astype(np.int32)  # system prompt
    tails = [rng.randint(1, 256, size=n).astype(np.int32)
             for n in (4, 6, 3, 7)]
    prompts = [np.concatenate([head, t]) for t in tails]
    # first prompt populates the cache (cold); the rest hit it
    for i, p in enumerate(prompts):
        sid = eng.submit(p, 10)
        eng.drain()
        got = np.asarray(eng.pop_finished(sid).tokens[:10])
        np.testing.assert_array_equal(got, _greedy(params, p, 10))
    st = pc.stats()
    assert st["hits"] >= len(prompts) - 1, st
    assert st["hit_rate"] > 0.5


def test_disaggregated_prefill_adopt_bit_identical():
    """prefill_kv on a 'prefill worker' + submit_prefilled adoption on
    a 'decode replica' must reproduce inline-prefill decode exactly."""
    import jax.numpy as jnp

    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, 256, size=20).astype(np.int32)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :len(prompt)] = prompt
    k, v, toks0, _ = prefill_kv(
        params, jnp.asarray(padded), jnp.asarray([len(prompt)], jnp.int32),
        jnp.zeros(1, jnp.uint32), jnp.zeros(1, jnp.float32),
        jnp.ones(1, jnp.float32), TINY, 64)
    kv = {"k": np.asarray(k[:, 0]), "v": np.asarray(v[:, 0]),
          "first_token": int(toks0[0]), "true_len": len(prompt)}
    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=3, prompt_buckets=(8, 16, 32))
    sid = eng.submit_prefilled(prompt, 10, kv)
    eng.drain()
    got = np.asarray(eng.pop_finished(sid).tokens[:10])
    np.testing.assert_array_equal(got, _greedy(params, prompt, 10))
    # wrong-shape KV is rejected at submit, not inside the pump
    with pytest.raises(ValueError):
        eng.submit_prefilled(prompt, 10, {**kv, "k": kv["k"][:, :32]})


def test_engine_stats_and_streaming_take():
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    eng = RaggedDecoder(params, TINY, slots=2, max_len=64,
                        chunk_tokens=4, prompt_buckets=(8,))
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 256, size=6).astype(np.int32)
    sid = eng.submit(prompt, 9)
    st = eng.stats()
    assert st["slots"] == 2 and st["queued"] == 1 and st["active"] == 0
    got, done = [], False
    while not done:
        eng.pump()
        new, done = eng.take_tokens(sid)
        got.extend(new)
    np.testing.assert_array_equal(np.asarray(got[:9]),
                                  _greedy(params, prompt, 9))
    st = eng.stats()
    assert st["total_tokens"] >= 9
    assert "tokens_per_sec" in st
    # one prompt went through one call of the one-row prefill program
    assert st["prefill_calls"] == 1
    assert "prefill_rows" not in st and "prefill_prompts" not in st
    # fully-taken finished stream is purged
    assert eng.take_tokens(sid) == ([], True)


def test_deployment_serving_options_fold_into_autoscaling():
    from ray_tpu.serve.api import Deployment

    d = Deployment(LLMServer, min_replicas=2, max_replicas=5,
                   target_ttft_s=0.25)
    assert d.autoscaling_config == {
        "min_replicas": 2, "max_replicas": 5, "target_ttft_s": 0.25}
    # survives .options() round-trips
    d2 = d.options(num_replicas=3)
    assert d2.autoscaling_config == d.autoscaling_config


# ---------------- pool end-to-end (cluster) ----------------

@pytest.fixture(scope="module")
def cluster():
    c = Cluster(head_resources={"CPU": 4, "memory": 4 * 2**30})
    c.connect()
    yield c
    try:
        serve.shutdown()
    except Exception:  # noqa: BLE001
        pass
    c.shutdown()


def _drain_stream(pool, rid, deadline_s=120.0):
    toks = []
    t0 = time.time()
    while time.time() - t0 < deadline_s:
        out = pool.poll_stream(rid)
        toks.extend(out["tokens"])
        if out["done"]:
            return toks
        time.sleep(0.01)
    raise TimeoutError("stream did not finish")


def test_pool_generate_stream_and_disagg_parity(cluster):
    """2 decode replicas + 1 prefill worker + prefix cache, one shared
    weight publish: short prompts (inline prefill), long prompts
    (disaggregated through the object store), and streaming all return
    the exact greedy continuation."""
    pool = LLMPool(model_size="tiny", slots=2, max_len=96,
                   chunk_tokens=4, prompt_buckets=(8, 16),
                   min_replicas=2, max_replicas=2, prefill_workers=1,
                   prefill_threshold=12, prefix_cache_block=4,
                   autoscale=False)
    try:
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        rng = np.random.RandomState(3)
        for n in (7, 14):  # inline vs disaggregated prefill
            p = rng.randint(1, 256, size=n).astype(np.int32)
            out = pool.generate(p.tolist(), 8)
            np.testing.assert_array_equal(
                np.asarray(out["tokens"]), _greedy(params, p, 8))
        # streaming: short (inline) AND long (disaggregated — the KV
        # ref rides submit_stream_prefilled as a top-level arg)
        for n in (7, 14):
            p = rng.randint(1, 256, size=n).astype(np.int32)
            rid = pool.submit_stream(
                {"prompt_ids": p.tolist(), "max_tokens": 8})["rid"]
            np.testing.assert_array_equal(
                np.asarray(_drain_stream(pool, rid)),
                _greedy(params, p, 8))
        st = pool.stats()
        assert st["replicas"] == 2
        assert st["ttft_p99_s"] is not None
        assert set(st["per_replica"]) == {"decode-1", "decode-2"}
    finally:
        pool.shutdown()


def test_pool_consumer_tags_ride_fetch_path(cluster):
    """The pool's two big transfers declare their consumer identity:
    weight broadcast submits with {owner: weights, qos: bulk} and the
    executor's param fetch carries those tags to fetch_object; the
    prefill→decode KV handoff submits with {owner: kv-handoff, qos: kv}.
    (Cross-node, these tags select the pull's pacer class and owner
    attribution — test_data_plane asserts that half.)"""
    w = cluster._driver
    submits = []
    orig_submit = w.submit_actor_task

    def rec_submit(*a, **k):
        if k.get("fetch_tags"):
            submits.append(dict(k["fetch_tags"]))
        return orig_submit(*a, **k)

    w.submit_actor_task = rec_submit
    pool = LLMPool(model_size="tiny", slots=2, max_len=96,
                   chunk_tokens=4, prompt_buckets=(8, 16),
                   min_replicas=2, max_replicas=2, prefill_workers=1,
                   prefill_threshold=12, autoscale=False)
    try:
        rng = np.random.RandomState(5)
        p = rng.randint(1, 256, size=14).astype(np.int32)  # disagg path
        pool.generate(p.tolist(), 4)
        params = llama.init_params(TINY, jax.random.PRNGKey(1))
        v = pool.publish_weights(params)
        assert pool.wait_version(v, timeout=60)
        assert {"qos": "bulk", "owner": "weights"} in submits, submits
        assert {"qos": "kv", "owner": "kv-handoff"} in submits, submits
    finally:
        w.submit_actor_task = orig_submit
        pool.shutdown()


def test_pool_chaos_replica_kill_no_client_visible_error(cluster):
    """THE chaos acceptance: kill a decode replica mid-stream and
    mid-generate; the pool re-queues in-flight work to survivors and
    clients see exact tokens, never an error."""
    pool = LLMPool(model_size="tiny", slots=2, max_len=96,
                   chunk_tokens=2, prompt_buckets=(8,),
                   min_replicas=3, max_replicas=3, autoscale=False,
                   chunk_delay_s=0.02)
    try:
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        rng = np.random.RandomState(5)
        p = rng.randint(1, 256, size=6).astype(np.int32)
        rid = pool.submit_stream(
            {"prompt_ids": p.tolist(), "max_tokens": 40})["rid"]
        toks = []
        while len(toks) < 6:
            out = pool.poll_stream(rid)
            toks.extend(out["tokens"])
            assert not out["done"]
            time.sleep(0.01)
        victim = pool._streams[rid]["rep"]
        ray_tpu.kill(victim.handle)  # mid-stream kill
        t0 = time.time()
        while time.time() - t0 < 120:
            out = pool.poll_stream(rid)
            toks.extend(out["tokens"])
            if out["done"]:
                break
            time.sleep(0.01)
        np.testing.assert_array_equal(np.asarray(toks),
                                      _greedy(params, p, 40))

        # blocking path: kill one of the survivors with calls in flight
        outs = [None] * 4
        prompts = [rng.randint(1, 256, size=6).astype(np.int32)
                   for _ in range(4)]

        def one(i):
            outs[i] = pool.generate(prompts[i].tolist(), 30)

        ths = [threading.Thread(target=one, args=(i,)) for i in range(4)]
        for t in ths:
            t.start()
        time.sleep(0.3)
        ray_tpu.kill(pool._alive()[0].handle)
        for t in ths:
            t.join(120)
        for pp, out in zip(prompts, outs):
            assert out is not None, "client saw an error"
            np.testing.assert_array_equal(
                np.asarray(out["tokens"]), _greedy(params, pp, 30))
    finally:
        pool.shutdown()


def _sampled_ref(params, prompt, n, *, temperature, seed):
    """Reference sampled decode on a plain (non-speculative) engine —
    the sequence any replica must reproduce for this (prompt, seed)."""
    eng = RaggedDecoder(params, TINY, slots=2, max_len=96,
                        chunk_tokens=4, prompt_buckets=(8,))
    sid = eng.submit(np.asarray(prompt, np.int32), n,
                     temperature=temperature, seed=seed)
    eng.drain()
    return np.asarray(eng.pop_finished(sid).tokens[:n])


@pytest.mark.slow  # heaviest failover soak; replica-kill failover stays
# tier-1 via test_pool_chaos_replica_kill_no_client_visible_error and
# the spec-on greedy path via the decode-spec unit tests
def test_pool_replica_kill_failover_spec_sampled_exact(cluster):
    """ISSUE-19 acceptance: kill a decode replica mid-stream with
    speculative decoding ON and sampling ON; the re-queued stream must
    reproduce the EXACT token sequence of a plain non-speculative
    engine — acceptance is judged against the target's own
    (seed, position) RNG-lane token, so seed-replay is exact no matter
    how many draft tokens each pump accepted before or after the
    kill."""
    pool = LLMPool(model_size="tiny", slots=2, max_len=96,
                   chunk_tokens=2, prompt_buckets=(8,),
                   min_replicas=2, max_replicas=2, autoscale=False,
                   chunk_delay_s=0.02,
                   spec_depth=4, spec_draft_layers=1)
    try:
        params = llama.init_params(TINY, jax.random.PRNGKey(0))
        rng = np.random.RandomState(7)
        p = rng.randint(1, 256, size=6).astype(np.int32)
        ref = _sampled_ref(params, p, 32, temperature=0.8, seed=12345)
        rid = pool.submit_stream(
            {"prompt_ids": p.tolist(), "max_tokens": 32,
             "temperature": 0.8, "seed": 12345})["rid"]
        toks = []
        while len(toks) < 6:
            out = pool.poll_stream(rid)
            toks.extend(out["tokens"])
            assert not out["done"]
            time.sleep(0.01)
        ray_tpu.kill(pool._streams[rid]["rep"].handle)  # mid-stream
        t0 = time.time()
        while time.time() - t0 < 120:
            out = pool.poll_stream(rid)
            toks.extend(out["tokens"])
            if out["done"]:
                break
            time.sleep(0.01)
        np.testing.assert_array_equal(np.asarray(toks), ref)
        # speculation actually ran on the decoding replicas
        st = pool.stats()
        specs = [s.get("spec") for s in st["per_replica"].values()
                 if isinstance(s, dict)]
        assert any(sp and sp["pumps"] > 0 for sp in specs)
    finally:
        pool.shutdown()


def test_pool_multiplex_routes_by_model_id(cluster):
    """Model multiplexing (serve/multiplex.py wired to real weight
    swaps): requests routed by model_id decode under THAT model's
    weights — each compared against its own reference greedy decode —
    with the construction model addressable as "" and unregistered ids
    rejected.  The LRU keeps swapped-in models resident as object-store
    refs."""
    pool = LLMPool(model_size="tiny", slots=2, max_len=96,
                   chunk_tokens=4, prompt_buckets=(8,),
                   min_replicas=1, max_replicas=1, autoscale=False)
    try:
        base = llama.init_params(TINY, jax.random.PRNGKey(0))
        alt = llama.init_params(TINY, jax.random.PRNGKey(42))
        pool.register_model("alt", alt)
        rng = np.random.RandomState(8)
        p = rng.randint(1, 256, size=6).astype(np.int32)
        a1 = pool.generate(p.tolist(), 12)
        np.testing.assert_array_equal(a1["tokens"],
                                      _greedy(base, p, 12))
        b = pool.generate(p.tolist(), 12, model_id="alt")
        np.testing.assert_array_equal(b["tokens"], _greedy(alt, p, 12))
        assert not np.array_equal(b["tokens"], a1["tokens"])
        # back to the construction model by its reserved id
        a2 = pool.generate(p.tolist(), 12, model_id="")
        np.testing.assert_array_equal(a2["tokens"], a1["tokens"])
        st = pool.stats()
        assert st["active_model"] == ""
        assert st["registered_models"] == ["alt"]
        assert "alt" in st["resident_models"]
        with pytest.raises(KeyError):
            pool.generate(p.tolist(), 4, model_id="nope")
    finally:
        pool.shutdown()


def test_pool_autoscales_up_and_drains_down(cluster):
    """Queue pressure scales the pool toward max_replicas via the
    demand hook; idleness drains it back to min (draining replicas get
    an explicit LLMServer.shutdown before the kill)."""
    pool = LLMPool(model_size="tiny", slots=1, max_len=96,
                   chunk_tokens=2, prompt_buckets=(8,),
                   min_replicas=1, max_replicas=2,
                   target_queue_per_replica=1.0, autoscale=True,
                   chunk_delay_s=0.05)
    pool.AUTOSCALE_PERIOD_S = 0.2
    try:
        rng = np.random.RandomState(7)
        prompts = [rng.randint(1, 256, size=6).astype(np.int32)
                   for _ in range(6)]
        ths = [threading.Thread(
            target=lambda p=p: pool.generate(p.tolist(), 30))
            for p in prompts]
        for t in ths:
            t.start()
        deadline = time.time() + 60
        while len(pool._alive()) < 2 and time.time() < deadline:
            time.sleep(0.1)
        assert len(pool._alive()) == 2, "pool never scaled up"
        for t in ths:
            t.join(120)
        deadline = time.time() + 90
        while time.time() < deadline:
            with pool._lock:
                live = [r for r in pool._replicas if not r.draining]
            if len(live) <= 1:
                break
            time.sleep(0.2)
        assert len([r for r in pool._replicas
                    if not r.draining]) <= 1, "pool never drained down"
    finally:
        pool.shutdown()


def test_llm_server_shutdown_drains_deterministically():
    """Satellite: explicit shutdown() replaces the nondeterministic
    __del__ teardown — in-flight streams finish, new admits are
    rejected, and the pump thread is joined."""
    srv = LLMServer(model_size="tiny", slots=2, max_len=96,
                    chunk_tokens=4, prompt_buckets=(8,))
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    p = np.asarray([3, 5, 7, 9], np.int32)
    done = {}

    def gen():
        done["out"] = srv.generate(p.tolist(), 12)

    th = threading.Thread(target=gen)
    th.start()
    time.sleep(0.05)  # let it admit
    assert srv.shutdown(drain_s=60.0) is True
    th.join(30)
    np.testing.assert_array_equal(
        np.asarray(done["out"]["tokens"]), _greedy(params, p, 12))
    assert not srv._pump_thread.is_alive()
    with pytest.raises(RuntimeError):
        srv.generate(p.tolist(), 4)


def test_http_proxy_streams_chunked_tokens(cluster):
    """Streaming satellite: {"stream": true} through the HTTP proxy
    returns chunked NDJSON token batches that concatenate to the exact
    greedy continuation."""
    import http.client
    import json as _json

    from ray_tpu.serve.api import Deployment

    dep = Deployment(LLMServer, max_concurrent_queries=8,
                     resources={"CPU": 0}, route_prefix="/sllm")
    serve.run(dep, name="sllm", init_kwargs={
        "model_size": "tiny", "slots": 2, "max_len": 96,
        "chunk_tokens": 4, "prompt_buckets": (8,),
        "chunk_delay_s": 0.05})
    host, port = serve.start_http_proxy()
    params = llama.init_params(TINY, jax.random.PRNGKey(0))
    p = np.asarray([2, 4, 6, 8, 10], np.int32)
    body = _json.dumps({"prompt_ids": p.tolist(), "max_tokens": 24,
                        "stream": True})
    deadline = time.time() + 120
    toks, chunks = [], 0
    while time.time() < deadline:
        conn = http.client.HTTPConnection(host, port, timeout=60)
        try:
            conn.request("POST", "/sllm", body,
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            if r.status != 200:  # route still propagating
                time.sleep(0.5)
                continue
            assert r.getheader("Transfer-Encoding") == "chunked"
            toks, chunks = [], 0
            for line in r:  # http.client de-chunks line by line
                if not line.strip():
                    continue
                msg = _json.loads(line)
                assert "error" not in msg, msg
                if msg.get("tokens"):
                    toks.extend(msg["tokens"])
                    chunks += 1
                if msg.get("done"):
                    break
            break
        finally:
            conn.close()
    np.testing.assert_array_equal(np.asarray(toks),
                                  _greedy(params, p, 24))
    assert chunks >= 2, "tokens arrived in one burst — not streamed"


def test_job_submission_log_tailing(cluster):
    """Streaming satellite (job side): tail_job_logs yields increments
    as the job prints, finishing when the job does."""
    import sys

    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient()
    code = ("import time\n"
            "for i in range(5):\n"
            "    print('tok', i, flush=True)\n"
            "    time.sleep(0.2)\n")
    sid = client.submit_job(
        entrypoint=f'{sys.executable} -c "{code}"')
    chunks = list(client.tail_job_logs(sid, poll_s=0.1, timeout=120))
    text = "".join(chunks)
    assert [f"tok {i}" in text for i in range(5)] == [True] * 5
    assert len(chunks) >= 2, "logs arrived in one burst — not tailed"
    client.delete_job(sid)
