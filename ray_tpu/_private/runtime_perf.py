"""Runtime microbenchmarks — the framework's `ray microbenchmark` analog.

Reference: python/ray/_private/ray_perf.py:93 (benchmark list) +
ray_microbenchmark_helpers.py:14 (timeit harness). Same workload families,
sized for an in-process test cluster: task submit+get (1:1 sync, batched
async, multi-client), actor calls (sync / async batch / async actors /
n:n), put/get at 1 KB / 1 MB / 1 GB, wait over 1k refs, and a
10k-queued-task drain.

Run:  python -m ray_tpu._private.runtime_perf [--out RUNTIME_BENCH.json]
Each result is one JSON line: {"name", "per_s", "unit"}.
"""

from __future__ import annotations

import json
import time

import numpy as np

import ray_tpu


def timeit(name: str, fn, multiplier: int = 1, *, windows: int = 3,
           window_s: float = 1.0):
    """Best-of-N-windows ops/sec (min wall time per op over windows)."""
    fn()  # warmup / compile / worker spinup
    # calibrate: how many calls fit one window
    start = time.perf_counter()
    count = 0
    while time.perf_counter() - start < 0.3:
        fn()
        count += 1
    per_window = max(1, int(count * window_s / 0.3))
    best = 0.0
    for _ in range(windows):
        start = time.perf_counter()
        for _ in range(per_window):
            fn()
        dt = time.perf_counter() - start
        best = max(best, multiplier * per_window / dt)
    return {"name": name, "per_s": round(best, 1), "unit": "ops/s"}


@ray_tpu.remote(num_cpus=0)
def _small_value():
    return b"ok"


@ray_tpu.remote(num_cpus=0)
def _small_value_batch(n):
    ray_tpu.get([_small_value.remote() for _ in range(n)], timeout=120)
    return 0


@ray_tpu.remote(num_cpus=0)
def _noop(*_args):
    return None


@ray_tpu.remote(num_cpus=0)
class _Actor:
    def small_value(self):
        return b"ok"

    def small_value_arg(self, _x):
        return b"ok"


@ray_tpu.remote(num_cpus=0, max_concurrency=8)
class _AsyncActor:
    async def small_value(self):
        return b"ok"


@ray_tpu.remote(num_cpus=0)
class _CollRank:
    """One collective rank for the DCN star/ring/ring+int8 comparison."""

    def init(self, world, rank, name):
        from ray_tpu.collective import init_collective_group

        init_collective_group(world, rank, group_name=name)
        self.group = name
        return rank

    def allreduce_loop(self, nbytes, iters, transport, codec):
        """Lockstep allreduce timing; returns (s/op, wire bytes/op)."""
        from ray_tpu.collective import collective as col
        from ray_tpu.collective import ring

        arr = np.ones(nbytes // 4, dtype=np.float32)
        col.allreduce(arr, self.group, transport=transport, codec=codec)
        t0 = time.perf_counter()
        for _ in range(iters):
            col.allreduce(arr, self.group, transport=transport,
                          codec=codec)
        dt = time.perf_counter() - t0
        st = ring.last_op_stats(self.group)
        return dt / iters, st.bytes_sent


def run_collective_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `collective` family: star vs ring vs ring+int8 allreduce across
    4 ranks at 1 MB / 16 MB — wall time plus per-rank wire bytes, the
    numbers the ring engine exists to move (2·(N−1)/N per rank vs
    O(N·bytes) at the star root; int8 ≤ ~26% of the f32 bytes)."""
    import uuid

    results = []
    world = 4
    ranks = [_CollRank.remote() for _ in range(world)]
    try:
        name = f"perf-{uuid.uuid4().hex[:8]}"
        ray_tpu.get([a.init.remote(world, r, name)
                     for r, a in enumerate(ranks)], timeout=120)
        sizes = [(1, 5)] if quick else [(1, 8), (16, 3)]
        for mb, iters in sizes:
            nbytes = mb * 1024 * 1024
            for transport, codec, label in (
                ("star", None, "star"),
                ("ring", None, "ring"),
                ("ring", "int8", "ring+int8"),
            ):
                outs = ray_tpu.get(
                    [a.allreduce_loop.remote(nbytes, iters, transport,
                                             codec)
                     for a in ranks],
                    timeout=600,
                )
                per_op = max(dt for dt, _ in outs)
                wire = max(b for _, b in outs)
                r = {
                    "name":
                        f"collective allreduce {label} {mb}MB (4 ranks)",
                    "per_s": round(1.0 / per_op, 1),
                    "unit": "ops/s",
                    "wire_bytes_per_rank": int(wire),
                    "tensor_bytes": nbytes,
                }
                results.append(r)
                print(json.dumps(r), flush=True)
    finally:
        for a in ranks:
            ray_tpu.kill(a)
    return results


def run_transfer_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `transfer` family: the object data plane under the zero-copy
    discipline — single-copy put at 1MB/64MB, and cross-node pull of a
    64MB object with 1 vs 2 source locations (pipelined chunk window,
    striped across holders) vs a sequential depth=1 pull. The pull tier
    runs on a dedicated in-process mini-cluster (control plane + 3
    agents, no driver) so it measures the agent-to-agent chunk path."""
    import os as _os
    import uuid

    from ray_tpu._private import config as _cfg
    from ray_tpu._private.rpc import EventLoopThread
    from ray_tpu.core.control_plane import ControlPlane
    from ray_tpu.core.node_agent import NodeAgent

    results = []

    def record(name, per_s, **extra):
        r = {"name": name, "per_s": round(per_s, 2), "unit": "ops/s",
             **extra}
        results.append(r)
        print(json.dumps(r), flush=True)

    # -- put tier (driver-attached store; requires ray_tpu.init'd) --
    mb = np.zeros(1024 * 1024, dtype=np.uint8)
    results.append(timeit("transfer put 1MB (zero-copy)",
                          lambda: ray_tpu.put(mb),
                          windows=1 if quick else 3))
    print(json.dumps(results[-1]), flush=True)
    big = np.zeros(64 * 1024 * 1024, dtype=np.uint8)

    def put64():
        r = ray_tpu.put(big)
        ray_tpu.free([r])

    results.append(timeit("transfer put 64MB", put64,
                          windows=1 if quick else 3))
    print(json.dumps(results[-1]), flush=True)

    # -- cross-node pull tier (dedicated mini-cluster) --
    io = EventLoopThread("ray_tpu-transfer-bench")
    cp = ControlPlane()
    head_port = io.run(cp.start())
    sid = uuid.uuid4().hex[:8]
    agents = [
        NodeAgent("127.0.0.1", head_port,
                  resources={"CPU": 1.0, "memory": 2.0 * 2**30},
                  # full mode adds a 1GB pull tier; quick keeps it lean
                  store_capacity=(512 if quick else 1536) * 1024 * 1024,
                  session_id=f"xfer{sid}{i}")
        for i in range(3)
    ]
    for a in agents:
        io.run(a.start())
    nbytes = 64 * 1024 * 1024
    blob = _os.urandom(nbytes)

    def seed(agent):
        oid = _os.urandom(16)
        agent.store.put_bytes(oid, blob, metadata=b"")
        io.run(agent.rpc_object_sealed(None,
                                       {"object_id": oid, "size": nbytes}))
        return oid

    def pull(dst, oid):
        t0 = time.perf_counter()
        ok = io.run(dst.rpc_fetch_object(
            None, {"object_id": oid, "timeout": 120}))
        dt = time.perf_counter() - t0
        assert ok, "bench pull failed"
        return dt

    try:
        iters = 2 if quick else 3
        depth = _cfg.get("transfer_pull_pipeline_depth")
        # sequential baseline: one chunk request in flight at a time
        _cfg.set_system_config({"transfer_pull_pipeline_depth": 1})
        seq = []
        for _ in range(iters):
            oid = seed(agents[0])
            seq.append(pull(agents[1], oid))
            agents[1].store.delete(oid)
            agents[0].store.pin(oid, False)
            agents[0].store.delete(oid)
        _cfg.set_system_config({"transfer_pull_pipeline_depth": depth})
        record("cross-node pull 64MB (sequential depth=1)",
               1.0 / min(seq), gb_per_s=round(nbytes / min(seq) / 1e9, 3))
        # pipelined, 1 source
        one = []
        for _ in range(iters):
            oid = seed(agents[0])
            one.append(pull(agents[1], oid))
            agents[1].store.delete(oid)
            agents[0].store.pin(oid, False)
            agents[0].store.delete(oid)
        record("cross-node pull 64MB (1 source)", 1.0 / min(one),
               gb_per_s=round(nbytes / min(one) / 1e9, 3),
               max_inflight=(agents[1].transfer_stats["last_pull"] or
                             {}).get("max_inflight"))
        # pipelined, 2 sources (striped)
        two = []
        for _ in range(iters):
            oid = seed(agents[0])
            pull(agents[1], oid)  # second holder
            two.append(pull(agents[2], oid))
            for a in agents[1:]:
                a.store.delete(oid)
            agents[0].store.pin(oid, False)
            agents[0].store.delete(oid)
        record("cross-node pull 64MB (2 sources)", 1.0 / min(two),
               gb_per_s=round(nbytes / min(two) / 1e9, 3),
               sources=(agents[2].transfer_stats["last_pull"] or
                        {}).get("sources"))
        # scatter A/B at 64MB: the pipelined tiers above run with
        # transfer_scatter_read ON (the default); this is the same
        # 1-source pull with the receive fast path disabled — the
        # reader-side copy cost in isolation
        _cfg.set_system_config({"transfer_scatter_read": False})
        off = []
        for _ in range(iters):
            oid = seed(agents[0])
            off.append(pull(agents[1], oid))
            agents[1].store.delete(oid)
            agents[0].store.pin(oid, False)
            agents[0].store.delete(oid)
        _cfg.set_system_config({"transfer_scatter_read": True})
        record("cross-node pull 64MB (scatter off)", 1.0 / min(off),
               gb_per_s=round(nbytes / min(off) / 1e9, 3))
        if not quick:
            # 1GB tier, scatter on vs off (needs the 1.5GB stores)
            gbytes = 1024 * 1024 * 1024
            gblob = _os.urandom(gbytes)

            def seed_big(agent):
                oid = _os.urandom(16)
                agent.store.put_bytes(oid, gblob, metadata=b"")
                io.run(agent.rpc_object_sealed(
                    None, {"object_id": oid, "size": gbytes}))
                return oid

            for flag, tag in ((True, "scatter on"),
                              (False, "scatter off")):
                _cfg.set_system_config({"transfer_scatter_read": flag})
                times = []
                for _ in range(2):
                    oid = seed_big(agents[0])
                    times.append(pull(agents[1], oid))
                    agents[1].store.delete(oid)
                    agents[0].store.pin(oid, False)
                    agents[0].store.delete(oid)
                record(f"cross-node pull 1GB ({tag})", 1.0 / min(times),
                       gb_per_s=round(gbytes / min(times) / 1e9, 3))
            del gblob
            _cfg.set_system_config({"transfer_scatter_read": True})
    finally:
        for a in agents:
            try:
                io.run(a.stop(), timeout=10)
            except Exception:
                pass
        try:
            io.run(cp.stop(), timeout=10)
        except Exception:
            pass
        io.stop()

    # -- consumer tier (driver-attached pool): the serve-side transfers
    #    that ride the pull fast path with declared fetch tags --
    import jax

    from ray_tpu.serve.llm import build_model
    from ray_tpu.serve.llm_pool import LLMPool

    pool = LLMPool(model_size="tiny", slots=4, max_len=96, chunk_tokens=8,
                   prompt_buckets=(8, 16), min_replicas=2, max_replicas=2,
                   prefill_workers=1, prefill_threshold=12,
                   autoscale=False)
    try:
        params, _ = build_model("tiny", max_len=96, seed=1)
        host = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), params)
        lats = []
        for _ in range(2 if quick else 4):
            t0 = time.perf_counter()
            v = pool.publish_weights(host)
            assert pool.wait_version(v, timeout=60.0), "adoption timeout"
            lats.append(time.perf_counter() - t0)
        record("transfer weight publish-to-adoption (2 replicas)",
               1.0 / min(lats), latency_s=round(min(lats), 4),
               weight_bytes=int(sum(
                   a.nbytes for a in jax.tree_util.tree_leaves(host))))
        # prefill-to-decode kv handoff: a disaggregated 1-token generate
        # (prompt over prefill_threshold) — prefill on the worker, kv
        # adoption on the decode replica, one decode chunk
        rng = np.random.RandomState(11)
        pool.generate([int(x) for x in rng.randint(1, 250, 14)], 1)  # warm
        lats = []
        for i in range(3 if quick else 6):
            p2 = [int(x) for x in np.random.RandomState(20 + i)
                  .randint(1, 250, 14)]
            t0 = time.perf_counter()
            pool.generate(p2, 1)
            lats.append(time.perf_counter() - t0)
        record("transfer kv handoff (prefill to decode, 1 token)",
               1.0 / min(lats), latency_s=round(min(lats), 4))
    finally:
        pool.shutdown()
    return results


def run_serve_benchmarks(*, quick: bool = False) -> list[dict]:
    """Serving-tier floors: LLMPool aggregate decode throughput at 1 vs
    2 replicas on ONE host, plus the prefix-cache configuration.

    Decode compute rides a tiny model with an EMULATED per-chunk device
    dispatch latency (decode_engine chunk_delay_s — same idiom as the
    injected per-chunk latency in the pipelined-pull floor: loopback
    CPU cannot exhibit the device wait that dominates a real TPU
    replica's chunk cadence and overlaps perfectly across replicas).
    What these numbers measure is the SERVING tier — admission,
    routing, multi-replica overlap, prefix reuse — not matmul speed."""
    import threading

    from ray_tpu.serve.llm_pool import LLMPool

    prompt_len, new_tokens, chunk_delay = 16, 96, 0.05
    n_requests = 16 if quick else 32
    concurrency = 32
    results = []

    def prompt_for(i, shared_head):
        rng = np.random.RandomState(1000 + i)
        if shared_head is not None:
            return list(shared_head) + [
                int(x) for x in rng.randint(1, 250, 7)]
        return [int(x) for x in rng.randint(1, 250, prompt_len)]

    def run_pool(n_replicas, *, prefix=False):
        pool = LLMPool(
            model_size="tiny", slots=8, max_len=128, chunk_tokens=8,
            prompt_buckets=(prompt_len,), min_replicas=n_replicas,
            max_replicas=n_replicas, chunk_delay_s=chunk_delay,
            prefix_cache_block=8 if prefix else 0, autoscale=False)
        head = ([int(x) for x in np.random.RandomState(7)
                 .randint(1, 250, 8)] if prefix else None)
        try:
            # warm EVERY replica through BOTH prefill paths (the cold
            # prefill, then the prefix-cache suffix path) so
            # jit compiles stay out of the timed window
            warm = prompt_for(0, head)
            ray_tpu.get([r.handle.generate.remote(warm, 8)
                         for r in pool._alive()], timeout=600)
            if prefix:
                warm2 = prompt_for(1, head)
                ray_tpu.get([r.handle.generate.remote(warm2, 8)
                             for r in pool._alive()], timeout=600)
            outs = [None] * n_requests
            errs: list[str] = []
            sem = threading.Semaphore(concurrency)

            def one(i):
                with sem:
                    try:
                        outs[i] = pool.generate(
                            prompt_for(100 + i, head), new_tokens)
                    except Exception as e:  # noqa: BLE001 — surface
                        # the real failure, not a len(None) TypeError
                        errs.append(f"req {i}: {type(e).__name__}: {e}")

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n_requests)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            dt = time.perf_counter() - t0
            if errs:
                raise RuntimeError(
                    f"{len(errs)}/{n_requests} pool requests failed; "
                    f"first: {errs[0][:300]}")
            total = sum(len(o["tokens"]) for o in outs)
            ttfts = sorted(o["token_times_s"][0] - o["submitted_s"]
                           for o in outs)
            st = pool.stats()
            return {
                "per_s": round(total / dt, 1),
                "unit": "tokens/s",
                "replicas": n_replicas,
                "concurrency": concurrency,
                "n_requests": n_requests,
                "new_tokens": new_tokens,
                "chunk_delay_s": chunk_delay,
                "ttft_p50_s": round(ttfts[len(ttfts) // 2], 3),
                "ttft_p99_s": round(ttfts[min(len(ttfts) - 1,
                                              int(0.99 * len(ttfts)))],
                                    3),
                "prefix_hit_rate": st["prefix_cache_hit_rate"],
            }
        finally:
            pool.shutdown()

    for name, kw in [
        ("serve pool decode (1 replica)", dict(n_replicas=1)),
        ("serve pool decode (2 replicas)", dict(n_replicas=2)),
        ("serve pool decode (2 replicas + prefix cache)",
         dict(n_replicas=2, prefix=True)),
    ]:
        r = {"name": name, **run_pool(**kw)}
        results.append(r)
        print(json.dumps(r), flush=True)
    return results


def run_serve_spec_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `serve_spec` family: speculative decoding's pump-rate win.

    Same workload shape as the serve family (tiny model, emulated
    chunk dispatch latency) with speculation off vs draft depth 2/4,
    greedy and sampled. What speculation buys is PUMPS: each verify
    round emits 1..K+1 tokens, so a stream finishes in fewer chunk
    dispatches — under a real device's per-dispatch latency (the
    chunk_delay_s stand-in) that is the whole win. Every spec record
    also proves the correctness contract en passant: its token
    sequences are compared bit-for-bit against the spec-off baseline
    of the same seeds (``match_baseline``)."""
    import threading

    from ray_tpu.serve.llm_pool import LLMPool

    prompt_len, new_tokens, chunk_delay = 16, 96, 0.05
    chunk_tokens = 4  # short pumps: dispatch cadence dominates, as on device
    n_requests = 16 if quick else 32
    concurrency = 32
    results = []

    def prompt_for(i):
        rng = np.random.RandomState(1000 + i)
        return [int(x) for x in rng.randint(1, 250, prompt_len)]

    def run_pool(spec_depth, temperature):
        pool = LLMPool(
            model_size="tiny", slots=8, max_len=128,
            chunk_tokens=chunk_tokens,
            prompt_buckets=(prompt_len,), min_replicas=1,
            max_replicas=1, chunk_delay_s=chunk_delay,
            spec_depth=spec_depth, spec_draft_layers=1,
            autoscale=False)
        try:
            # warm: compiles prefill + the (spec or plain) decode kernel
            pool.generate(prompt_for(0), 8, temperature=temperature,
                          seed=1)
            outs = [None] * n_requests
            errs: list[str] = []
            sem = threading.Semaphore(concurrency)

            def one(i):
                with sem:
                    try:
                        outs[i] = pool.generate(
                            prompt_for(100 + i), new_tokens,
                            temperature=temperature,
                            seed=(100 + i) * 7 + 1)
                    except Exception as e:  # noqa: BLE001
                        errs.append(
                            f"req {i}: {type(e).__name__}: {e}")

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n_requests)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            dt = time.perf_counter() - t0
            if errs:
                raise RuntimeError(
                    f"{len(errs)}/{n_requests} spec pool requests "
                    f"failed; first: {errs[0][:300]}")
            total = sum(len(o["tokens"]) for o in outs)
            st = pool.stats()
            spec_st = next(
                (s.get("spec") for s in st["per_replica"].values()
                 if isinstance(s, dict) and s.get("spec")), None)
            return {
                "per_s": round(total / dt, 1),
                "unit": "tokens/s",
                "replicas": 1,
                "concurrency": concurrency,
                "n_requests": n_requests,
                "new_tokens": new_tokens,
                "chunk_delay_s": chunk_delay,
                "chunk_tokens": chunk_tokens,
                "spec_depth": spec_depth,
                "temperature": temperature,
                "acceptance_rate": (spec_st or {}).get(
                    "acceptance_rate"),
            }, [o["tokens"] for o in outs]
        finally:
            pool.shutdown()

    for temperature, label in [(0.0, "greedy"), (0.8, "sampled")]:
        baseline = None
        for depth in (0, 2, 4):
            r, toks = run_pool(depth, temperature)
            if depth == 0:
                baseline = toks
            else:
                # the correctness contract, measured on the bench
                # workload itself: speculation must emit the exact
                # sequences the plain path emits
                r["match_baseline"] = (toks == baseline)
            tag = "off" if depth == 0 else f"depth {depth}"
            r = {"name": f"serve spec decode {tag} ({label})", **r}
            results.append(r)
            print(json.dumps(r), flush=True)
    return results


def run_rl_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `rl` family: the actor–learner loop's three data paths.

    - rollout tokens/s: sampled streaming decode (temperature/top-p +
      per-token logprobs) through the pool's experience surface
      (submit_stream/poll_stream) — the Podracer rollout rate;
    - experience bytes/s: trajectory handoff through the object store
      (forced-plasma put → versioned buffer add → claim → learner-side
      get), the zero-copy path the learner gang feeds from;
    - publish-to-adoption: one-put weight broadcast → every replica's
      engine has SWAPPED (not merely staged) the new version — the
      staleness window the off-policy correction is sized against."""
    import threading

    import ray_tpu
    from ray_tpu.rl.experience import ExperienceBuffer
    from ray_tpu.serve.llm import build_model
    from ray_tpu.serve.llm_pool import LLMPool

    results = []
    prompt_len, new_tokens, chunk_delay = 16, 96, 0.05
    n_requests = 12 if quick else 24
    pool = LLMPool(
        model_size="tiny", slots=8, max_len=128, chunk_tokens=8,
        prompt_buckets=(prompt_len,), min_replicas=2, max_replicas=2,
        chunk_delay_s=chunk_delay, autoscale=False)
    try:
        # --- rollout tokens/s (sampled streaming + logprobs) ---
        def stream_one(i, out):
            rng = np.random.RandomState(2000 + i)
            prompt = [int(x) for x in rng.randint(1, 250, prompt_len)]
            sub = pool.submit_stream({
                "prompt_ids": prompt, "max_tokens": new_tokens,
                "temperature": 1.0, "top_p": 0.95,
                "seed": 1000 + i})
            toks, lps = [], []
            while True:
                r = pool.poll_stream(sub["rid"])
                toks += r["tokens"]
                lps += r["logprobs"]
                if r["done"]:
                    break
                time.sleep(0.004)
            assert len(toks) == len(lps)
            out[i] = len(toks)

        # warm BOTH replicas' compile caches (sampled kernel): two
        # concurrent streams — least-loaded routing lands one on each
        warm = [0, 0]
        wts = [threading.Thread(target=stream_one, args=(i, warm))
               for i in range(2)]
        for t in wts:
            t.start()
        for t in wts:
            t.join()
        counts = [0] * n_requests
        threads = [threading.Thread(target=stream_one, args=(i, counts))
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        r = {"name": "rl rollout sampled stream (2 replicas)",
             "per_s": round(sum(counts) / dt, 1), "unit": "tokens/s",
             "replicas": 2, "n_requests": n_requests,
             "new_tokens": new_tokens, "chunk_delay_s": chunk_delay}
        results.append(r)
        print(json.dumps(r), flush=True)

        # --- experience bytes/s through the store ---
        buf = ray_tpu.remote(num_cpus=0)(ExperienceBuffer).remote()
        ray_tpu.get(buf.size.remote(), timeout=120)
        traj_tokens = 4096  # a long-generation trajectory's arrays
        traj = {
            "prompt": np.arange(512, dtype=np.int32),
            "tokens": np.zeros(traj_tokens, np.int32),
            "logprobs": np.zeros(traj_tokens, np.float32),
            "rewards": np.zeros(traj_tokens, np.float32),
            "version": 0,
        }
        nbytes = sum(v.nbytes for v in traj.values()
                     if isinstance(v, np.ndarray))
        iters = 30 if quick else 100

        def xfer_once(i):
            ref = ray_tpu.put(traj, _inline=False)
            ray_tpu.get(buf.add.remote(
                {"key": (0, i), "version": 0, "traj": {"ref": ref}}),
                timeout=60)
            out = ray_tpu.get(buf.claim.remote("bench", 1, i + 1),
                              timeout=60)
            got = ray_tpu.get(out["entries"][0]["traj"]["ref"],
                              timeout=60)
            assert got["tokens"].nbytes == traj["tokens"].nbytes

        xfer_once(-1)  # warm
        t0 = time.perf_counter()
        for i in range(iters):
            xfer_once(i)
        dt = time.perf_counter() - t0
        r = {"name": "rl experience handoff (put+add+claim+get)",
             "per_s": round(iters / dt, 1), "unit": "ops/s",
             "traj_bytes": nbytes,
             "mb_per_s": round(iters * nbytes / dt / 1e6, 1)}
        results.append(r)
        print(json.dumps(r), flush=True)
        ray_tpu.kill(buf)

        # --- publish-to-adoption latency ---
        import jax

        params, _ = build_model("tiny", max_len=128, seed=1)
        host = jax.tree_util.tree_map(
            lambda a: np.asarray(jax.device_get(a)), params)
        lats = []
        for i in range(3 if quick else 5):
            t0 = time.perf_counter()
            v = pool.publish_weights(host)
            assert pool.wait_version(v, timeout=60.0), "adoption timed out"
            lats.append(time.perf_counter() - t0)
        lat = min(lats)
        r = {"name": "rl weight publish-to-adoption (2 replicas)",
             "per_s": round(1.0 / lat, 1), "unit": "ops/s",
             "latency_s": round(lat, 4),
             "weight_bytes": int(sum(
                 a.nbytes for a in jax.tree_util.tree_leaves(host)))}
        results.append(r)
        print(json.dumps(r), flush=True)
    finally:
        pool.shutdown()
    return results


def run_qos_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `qos` family: multi-tenant pacing under contention.

    - pacer grant fast path: ops/s of the unlimited-rate tally path —
      what EVERY tagged send pays when enforcement is off (rate=0);
    - serve contention floors: a tenant's pool decode tokens/s and TTFT
      p99 while a learner gang (paced collective sends) and a bulk
      object spill (paced chunk pulls) saturate the same host, vs the
      same workload uncontended. The committed floors: per-tenant
      tokens/s >= 0.7x uncontended, TTFT p99 <= 2x uncontended, the
      bulk transfer still completes byte-identical, and byte
      attribution stays within 1% with the pacer ON;
    - batched stream fanout: aggregate sampled-stream tokens/s across
      concurrent rollouts with the per-REPLICA batched poll surface,
      plus the replica-side poll-RPC count it amortizes."""
    import os as _os
    import threading
    import uuid

    from ray_tpu._private import config as _cfg
    from ray_tpu._private import net_accounting as _net
    from ray_tpu._private import net_qos as _qos
    from ray_tpu._private.rpc import EventLoopThread
    from ray_tpu.core.control_plane import ControlPlane
    from ray_tpu.core.node_agent import NodeAgent
    from ray_tpu.serve.llm_pool import LLMPool

    results = []

    # ---- pacer grant fast path (enforcement off: pure tally) ----
    _qos.reset()
    results.append(timeit(
        "qos pacer grant (unlimited fast path)",
        lambda: _qos.try_acquire("bench-peer", "bulk", 65536,
                                 owner="bench"),
        windows=1 if quick else 3))
    print(json.dumps(results[-1]), flush=True)
    _qos.reset()

    # ---- serve contention floors (tenant vs gang + bulk spill) ----
    prompt_len, new_tokens, chunk_delay = 16, 96, 0.05
    n_requests = 8 if quick else 16
    concurrency = 8
    pool = LLMPool(
        model_size="tiny", slots=8, max_len=128, chunk_tokens=8,
        prompt_buckets=(prompt_len,), min_replicas=2, max_replicas=2,
        chunk_delay_s=chunk_delay, autoscale=False)

    def serve_round():
        outs = [None] * n_requests
        errs: list[str] = []
        sem = threading.Semaphore(concurrency)

        def one(i):
            rng = np.random.RandomState(4000 + i)
            prompt = [int(x) for x in rng.randint(1, 250, prompt_len)]
            with sem:
                try:
                    outs[i] = pool.generate(prompt, new_tokens,
                                            tenant="tenant-a")
                except Exception as e:  # noqa: BLE001
                    errs.append(f"req {i}: {type(e).__name__}: {e}")

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0
        if errs:
            raise RuntimeError(f"{len(errs)} serve requests failed; "
                               f"first: {errs[0][:300]}")
        total = sum(len(o["tokens"]) for o in outs)
        ttfts = sorted(o["token_times_s"][0] - o["submitted_s"]
                       for o in outs)
        p99 = ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]
        return total / dt, p99

    io = EventLoopThread("ray_tpu-qos-bench")
    cp = ControlPlane()
    head_port = io.run(cp.start())
    sid = uuid.uuid4().hex[:8]
    agents = [
        NodeAgent("127.0.0.1", head_port,
                  resources={"CPU": 1.0, "memory": 2.0 * 2**30},
                  store_capacity=128 * 1024 * 1024,
                  session_id=f"qos{sid}{i}")
        for i in range(2)
    ]
    for a in agents:
        io.run(a.start())
    nbytes = 8 * 1024 * 1024
    blob = _os.urandom(nbytes)

    def seed_blob():
        o = _os.urandom(16)
        agents[0].store.put_bytes(o, blob, metadata=b"")
        io.run(agents[0].rpc_object_sealed(
            None, {"object_id": o, "size": nbytes}))
        return o

    def drop_blob(o):
        agents[1].store.delete(o)
        agents[0].store.pin(o, False)
        agents[0].store.delete(o)

    ranks = []
    try:
        # warm both replicas, then the uncontended baseline
        warm = [int(x) for x in np.random.RandomState(9)
                .randint(1, 250, prompt_len)]
        ray_tpu.get([r.handle.generate.remote(warm, 8)
                     for r in pool._alive()], timeout=600)
        base_rate, base_p99 = serve_round()

        # contended: finite per-peer pacing ON, gang + bulk in the
        # background (ranks spawned AFTER the config flip so their
        # processes inherit the paced rate through the env)
        _qos.reset()
        _net.reset_local()
        _cfg.set_system_config({"net_qos_rate_mbps": 200.0})
        world = 2
        ranks = [_CollRank.remote() for _ in range(world)]
        gname = f"qos-{uuid.uuid4().hex[:8]}"
        ray_tpu.get([a.init.remote(world, r, gname)
                     for r, a in enumerate(ranks)], timeout=120)
        stop = threading.Event()
        pulls = [0]
        bulk_err: list[str] = []

        def bulk_loop():
            try:
                while not stop.is_set():
                    o = seed_blob()
                    ok = io.run(agents[1].rpc_fetch_object(
                        None, {"object_id": o, "timeout": 120}))
                    assert ok, "bulk pull failed under pacing"
                    pulls[0] += 1
                    drop_blob(o)
            except Exception as e:  # noqa: BLE001
                bulk_err.append(f"{type(e).__name__}: {e}")

        def gang_loop():
            mb2 = 2 * 1024 * 1024
            while not stop.is_set():
                try:
                    ray_tpu.get(
                        [a.allreduce_loop.remote(mb2, 2, "ring", None)
                         for a in ranks], timeout=120)
                except Exception:
                    return

        bt = threading.Thread(target=bulk_loop)
        gt = threading.Thread(target=gang_loop)
        bt.start()
        gt.start()
        try:
            cont_rate, cont_p99 = serve_round()
        finally:
            stop.set()
            bt.join(timeout=120)
            gt.join(timeout=120)
        if bulk_err:
            raise RuntimeError(bulk_err[0])
        # byte-identical completion under pacing/preemption
        o = seed_blob()
        ok = io.run(agents[1].rpc_fetch_object(
            None, {"object_id": o, "timeout": 120}))
        buf = agents[1].store.get(o)
        identical = bool(ok) and buf is not None and (
            bytes(buf.data) == blob)
        if buf is not None:
            buf.release()
        drop_blob(o)
        pulls[0] += 1
        # attribution: the driver-process rx tally (pull side) must
        # match the wire bytes the bulk loop actually moved
        rx = _net.total("rx", qos_class="bulk")
        expect = pulls[0] * nbytes
        attrib_err = abs(rx - expect) / expect
        qst = _qos.stats()
        parks = sum(s["parks"]["bulk"] + s["parks"]["collective"]
                    for s in qst.values())
        r = {
            "name": "qos serve contention (gang + bulk spill, paced)",
            "per_s": round(cont_rate, 1),
            "unit": "tokens/s",
            "uncontended_per_s": round(base_rate, 1),
            "ratio_tokens": round(cont_rate / base_rate, 3),
            "ttft_p99_s": round(cont_p99, 3),
            "uncontended_ttft_p99_s": round(base_p99, 3),
            "ratio_ttft": round(cont_p99 / max(base_p99, 1e-9), 3),
            "bulk_pulls": pulls[0],
            "bulk_completed": bool(identical),
            "attribution_err": round(attrib_err, 5),
            "pacer_parks": parks,
            "rate_mbps": 200.0,
        }
        results.append(r)
        print(json.dumps(r), flush=True)

        # ---- batched stream fanout (per-replica poll batching) ----
        _cfg.set_system_config({"net_qos_rate_mbps": 0.0})
        _qos.reset()
        n_streams = 8
        counts = [0] * n_streams

        def stream_one(i):
            rng = np.random.RandomState(5000 + i)
            prompt = [int(x) for x in rng.randint(1, 250, prompt_len)]
            sub = pool.submit_stream({
                "prompt_ids": prompt, "max_tokens": new_tokens,
                "temperature": 1.0, "top_p": 0.95, "seed": 100 + i,
                "tenant": "tenant-a"})
            toks = []
            while True:
                out = pool.poll_stream(sub["rid"])
                toks += out["tokens"]
                if out["done"]:
                    break
                time.sleep(0.004)
            counts[i] = len(toks)

        # warm the sampled kernel on both replicas
        wts = [threading.Thread(target=stream_one, args=(i,))
               for i in range(2)]
        for t in wts:
            t.start()
        for t in wts:
            t.join()
        polls0 = sum(ray_tpu.get(rep.handle.stats.remote(), timeout=60)
                     .get("stream_polls", 0) for rep in pool._alive())
        threads = [threading.Thread(target=stream_one, args=(i,))
                   for i in range(n_streams)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        polls1 = sum(ray_tpu.get(rep.handle.stats.remote(), timeout=60)
                     .get("stream_polls", 0) for rep in pool._alive())
        r = {"name": "qos batched stream fanout (8 streams)",
             "per_s": round(sum(counts) / dt, 1), "unit": "tokens/s",
             "streams": n_streams, "tokens": sum(counts),
             "replica_poll_rpcs": polls1 - polls0,
             "polls_per_token":
                 round((polls1 - polls0) / max(1, sum(counts)), 3)}
        results.append(r)
        print(json.dumps(r), flush=True)
    finally:
        _cfg.set_system_config({"net_qos_rate_mbps": 0.0})
        _qos.reset()
        for a in ranks:
            try:
                ray_tpu.kill(a)
            except Exception:
                pass
        pool.shutdown()
        for a in agents:
            try:
                io.run(a.stop(), timeout=10)
            except Exception:
                pass
        try:
            io.run(cp.stop(), timeout=10)
        except Exception:
            pass
        io.stop()
    return results


def run_colocate_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `colocate` family: train+serve on one cluster, with the
    overload guardian's survival numbers.

    - train step-time ratio: a 2-rank gang's allreduce step solo vs
      with a two-tenant serving pool decoding on the same host — the
      colocation tax on the collective class;
    - per-tenant TTFT p99 under that colocated load (kv class floor);
    - shed rate at 2x overcommit: the fraction of submissions a
      single-replica pool refuses TYPED at ladder level L3 when
      flooded past its admission capacity, plus the seconds the
      guardian takes to walk back to L0 once the flood stops (the
      no-flap recovery number)."""
    import threading
    import uuid

    from ray_tpu._private import config as _cfg
    from ray_tpu.serve.llm_pool import LLMPool
    from ray_tpu.serve.overload import PoolOverloadedError

    results = []
    prompt_len, new_tokens = 16, 64

    # ---- train step-time ratio + per-tenant TTFT under colocation ----
    world = 2
    mb2 = 2 * 1024 * 1024
    iters = 2 if quick else 4
    pool = LLMPool(
        model_size="tiny", slots=8, max_len=128, chunk_tokens=8,
        prompt_buckets=(prompt_len,), min_replicas=2, max_replicas=2,
        chunk_delay_s=0.05, autoscale=False,
        tenant_weights={"tenant-a": 2.0, "tenant-b": 1.0})
    ranks = [_CollRank.remote() for _ in range(world)]
    try:
        gname = f"colo-{uuid.uuid4().hex[:8]}"
        ray_tpu.get([a.init.remote(world, r, gname)
                     for r, a in enumerate(ranks)], timeout=120)
        warm = [int(x) for x in np.random.RandomState(9)
                .randint(1, 250, prompt_len)]
        ray_tpu.get([r.handle.generate.remote(warm, 8)
                     for r in pool._alive()], timeout=600)

        def gang_step_s():
            outs = ray_tpu.get(
                [a.allreduce_loop.remote(mb2, iters, "ring", None)
                 for a in ranks], timeout=300)
            return max(s for s, _ in outs)

        solo_step = gang_step_s()

        stop = threading.Event()
        ttfts: dict[str, list[float]] = {"tenant-a": [],
                                         "tenant-b": []}
        errs: list[str] = []
        lock = threading.Lock()

        def serve_loop(tenant, k):
            rng = np.random.RandomState(6000 + k)
            while not stop.is_set():
                prompt = [int(x) for x in
                          rng.randint(1, 250, prompt_len)]
                try:
                    o = pool.generate(prompt, new_tokens,
                                      tenant=tenant)
                    with lock:
                        ttfts[tenant].append(
                            o["token_times_s"][0] - o["submitted_s"])
                except Exception as e:  # noqa: BLE001
                    with lock:
                        errs.append(f"{tenant}: "
                                    f"{type(e).__name__}: {e}")
                    return

        threads = [threading.Thread(target=serve_loop,
                                    args=(tn, 10 * i + j))
                   for i, tn in enumerate(("tenant-a", "tenant-b"))
                   for j in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.5 if quick else 1.0)  # serve load in flight
        steps = []
        rounds = 2 if quick else 3
        for _ in range(rounds):
            steps.append(gang_step_s())
        # keep sampling TTFT past the gang window so the per-tenant
        # p99 rests on more than a handful of requests
        time.sleep(1.0 if quick else 3.0)
        stop.set()
        for t in threads:
            t.join(timeout=120)
        if errs:
            raise RuntimeError(errs[0])
        colo_step = min(steps)  # best-of: box noise, not contention

        def p99(vals):
            v = sorted(vals)
            return v[min(len(v) - 1, int(0.99 * len(v)))] if v else None

        r = {
            "name": "colocate train step (gang + 2-tenant pool)",
            "per_s": round(1.0 / colo_step, 2),
            "unit": "steps/s",
            "solo_step_s": round(solo_step, 4),
            "colocated_step_s": round(colo_step, 4),
            "step_ratio": round(colo_step / max(solo_step, 1e-9), 3),
            "ttft_p99_a_s": round(p99(ttfts["tenant-a"]) or 0.0, 3),
            "ttft_p99_b_s": round(p99(ttfts["tenant-b"]) or 0.0, 3),
            "served": sum(len(v) for v in ttfts.values()),
        }
        results.append(r)
        print(json.dumps(r), flush=True)
    finally:
        for a in ranks:
            try:
                ray_tpu.kill(a)
            except Exception:  # noqa: BLE001
                pass
        pool.shutdown()

    # ---- shed rate at 2x overcommit + L0 recovery time ----
    _cfg.set_system_config({
        "overload_escalate_dwell_s": 0.2,
        "overload_recover_dwell_s": 0.3,
        "overload_queue_per_replica_high": 2.0,
        "overload_shed_queue_bound": 8,
    })
    pool = LLMPool(
        model_size="tiny", slots=2, max_len=128, chunk_tokens=8,
        prompt_buckets=(prompt_len,), min_replicas=1, max_replicas=1,
        chunk_delay_s=0.05, max_inflight_per_replica=2,
        autoscale=True,
        tenant_weights={"gold": 4.0, "bronze": 1.0})
    try:
        warm = [int(x) for x in np.random.RandomState(9)
                .randint(1, 250, prompt_len)]
        ray_tpu.get([r.handle.generate.remote(warm, 8)
                     for r in pool._alive()], timeout=600)
        stop = threading.Event()
        counts = {"submitted": 0, "shed": 0, "ok": 0}
        lock = threading.Lock()
        errs: list[str] = []

        def flood(tenant, k):
            rng = np.random.RandomState(7000 + k)
            while not stop.is_set():
                prompt = [int(x) for x in
                          rng.randint(1, 250, prompt_len)]
                with lock:
                    counts["submitted"] += 1
                try:
                    pool.generate(prompt, 24, tenant=tenant)
                    with lock:
                        counts["ok"] += 1
                except PoolOverloadedError:
                    with lock:
                        counts["shed"] += 1
                    time.sleep(0.2)
                except Exception as e:  # noqa: BLE001
                    errs.append(f"{tenant}: {type(e).__name__}: {e}")
                    return

        threads = ([threading.Thread(target=flood, args=("bronze", k))
                    for k in range(6)]
                   + [threading.Thread(target=flood,
                                       args=("gold", 10 + k))
                      for k in range(2)])
        for t in threads:
            t.start()
        flood_s = 6.0 if quick else 10.0
        time.sleep(flood_s)
        peak_level = pool._guardian.level
        stop.set()
        for t in threads:
            t.join(timeout=120)
        if errs:
            raise RuntimeError(errs[0])
        t0 = time.perf_counter()
        recovered = None
        while time.perf_counter() - t0 < 60:
            if pool._guardian.level == 0:
                recovered = time.perf_counter() - t0
                break
            time.sleep(0.25)
        r = {
            "name": "colocate shed rate (2x overcommit, 1 replica)",
            "per_s": round(counts["submitted"] / flood_s, 1),
            "unit": "submissions/s",
            "shed_rate": round(counts["shed"]
                               / max(1, counts["submitted"]), 3),
            "served": counts["ok"],
            "shed": counts["shed"],
            "peak_level": peak_level,
            "recovery_to_l0_s":
                round(recovered, 1) if recovered is not None else None,
            "transitions": len(pool._guardian.transitions),
        }
        results.append(r)
        print(json.dumps(r), flush=True)
    finally:
        pool.shutdown()
        _cfg.set_system_config({
            "overload_escalate_dwell_s": 1.0,
            "overload_recover_dwell_s": 3.0,
            "overload_queue_per_replica_high": 8.0,
            "overload_shed_queue_bound": 64,
        })
    return results


def run_obs_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `obs` family: what the always-on flight recorder costs.

    - span record throughput: ring-only ``record()`` rate in one
      process — the ceiling any per-op span can ever cost;
    - allreduce overhead: ring 16MB allreduce instrumented vs the
      suppressed baseline (workers spawned under
      ``flight_recorder_enabled=False`` start with recording AND byte
      accounting off — the honest uninstrumented comparison);
    - serve overhead: pool decode tokens/s instrumented vs suppressed.

    The committed floors hold both overheads to <=3%: observability
    that taxes the hot path more than that does not ship."""
    import threading
    import uuid

    from ray_tpu._private import config as _cfg
    from ray_tpu._private import flight_recorder as _fr

    results = []

    # ---- raw span record throughput (ring only, no flush traffic) ----
    n = 50_000 if quick else 200_000
    t = time.monotonic()
    _fr.record("bench", "obs.warm", t, t, flush=False)
    t0 = time.perf_counter()
    for _ in range(n):
        _fr.record("bench", "obs.span", t, t, flush=False)
    dt = time.perf_counter() - t0
    r = {"name": "obs span record throughput (ring only)",
         "per_s": round(n / dt, 1), "unit": "spans/s", "n": n}
    results.append(r)
    print(json.dumps(r), flush=True)

    # ---- ring allreduce overhead (worker-side spans + byte tags) ----
    def allreduce_rate(enabled: bool) -> float:
        _cfg.set_system_config({"flight_recorder_enabled": enabled})
        world = 4
        ranks = [_CollRank.remote() for _ in range(world)]
        try:
            name = f"obs-{uuid.uuid4().hex[:8]}"
            ray_tpu.get([a.init.remote(world, rk, name)
                         for rk, a in enumerate(ranks)], timeout=120)
            nbytes = 16 * 1024 * 1024
            iters = 3 if quick else 6
            best = None
            for _ in range(2 if quick else 3):
                outs = ray_tpu.get(
                    [a.allreduce_loop.remote(nbytes, iters, "ring", None)
                     for a in ranks], timeout=600)
                per_op = max(d for d, _ in outs)
                best = per_op if best is None else min(best, per_op)
            return 1.0 / best
        finally:
            for a in ranks:
                ray_tpu.kill(a)

    base = allreduce_rate(False)
    inst = allreduce_rate(True)
    _cfg.set_system_config({"flight_recorder_enabled": True})
    r = {"name": "obs overhead: ring allreduce 16MB (4 ranks)",
         "per_s": round(inst, 2), "unit": "ops/s",
         "baseline_per_s": round(base, 2),
         "overhead_pct": round(max(0.0, (base - inst) / base) * 100, 2)}
    results.append(r)
    print(json.dumps(r), flush=True)

    # ---- serve decode overhead (pool + replica + engine spans) ----
    def serve_rate(enabled: bool) -> float:
        import contextlib as _ctx

        from ray_tpu.serve.llm_pool import LLMPool

        _cfg.set_system_config({"flight_recorder_enabled": enabled})
        # the pool itself runs in THIS process: suppress driver-side
        # spans too for the baseline (workers read the config flag)
        with _ctx.ExitStack() as stack:
            if not enabled:
                stack.enter_context(_fr._suppressed())
            pool = LLMPool(
                model_size="tiny", slots=8, max_len=128, chunk_tokens=8,
                prompt_buckets=(16,), min_replicas=1, max_replicas=1,
                chunk_delay_s=0.01, autoscale=False)
            try:
                warm = [int(x) for x in
                        np.random.RandomState(3).randint(1, 250, 16)]
                ray_tpu.get([rep.handle.generate.remote(warm, 8)
                             for rep in pool._alive()], timeout=600)
                n_req, new_tokens = (8 if quick else 16), 64
                outs = [None] * n_req

                def one(i):
                    rng = np.random.RandomState(2000 + i)
                    outs[i] = pool.generate(
                        [int(x) for x in rng.randint(1, 250, 16)],
                        new_tokens)

                threads = [threading.Thread(target=one, args=(i,))
                           for i in range(n_req)]
                t0 = time.perf_counter()
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                dt = time.perf_counter() - t0
                return sum(len(o["tokens"]) for o in outs) / dt
            finally:
                pool.shutdown()

    sbase = serve_rate(False)
    sinst = serve_rate(True)
    _cfg.set_system_config({"flight_recorder_enabled": True})
    r = {"name": "obs overhead: serve pool decode (1 replica)",
         "per_s": round(sinst, 1), "unit": "tokens/s",
         "baseline_per_s": round(sbase, 1),
         "overhead_pct":
             round(max(0.0, (sbase - sinst) / sbase) * 100, 2)}
    results.append(r)
    print(json.dumps(r), flush=True)
    return results


def run_pipeline_benchmarks(*, quick: bool = False) -> list[dict]:
    """The `pipeline` family: cross-slice MPMD pipeline parallelism.

    A 2-stage matmul pipeline — one WorkerGroup gang per stage, 1F1B
    schedule, activations/activation-grads streamed stage-to-stage over
    the paced collective p2p lanes — driven end-to-end through
    `MpmdPipeline.fit` (gang spawn + p2p rendezvous included in the
    wall, the honest cold-start number). Records optimizer steps/s,
    stage-boundary microbatch hops/s, and the measured bubble fraction
    (p2p-wait + allreduce-wait over wall, the flight-recorder span
    decomposition) next to the analytic (S-1)/(M+S-1) floor."""
    from ray_tpu.parallel import MpmdPipeline, StageSpec

    results = []
    bsz, dim = 256, 256
    steps = 4 if quick else 10
    mbs = 8

    def data_fn(step, m):
        rng = np.random.RandomState(1000 + step * 100 + m)
        return (rng.standard_normal((bsz, dim)),
                rng.standard_normal((bsz, dim)))

    def init_fn(cfg):
        return {"w": np.random.RandomState(7).standard_normal((dim, dim))}

    def fwd(params, x):
        return x @ params["w"], x

    def bwd(params, x, dy):
        return dy @ params["w"].T, {"w": x.T @ dy}

    def loss_fn(params, y, t):
        d = y - t
        return 0.5 * float(np.mean(d * d)), d / d.size

    pipe = MpmdPipeline(
        [StageSpec(1, init_fn, fwd, bwd),
         StageSpec(1, init_fn, fwd, bwd, loss_fn)],
        data_fn=data_fn, num_steps=steps, microbatches=mbs,
        name="bench-pipe")
    start = time.perf_counter()
    res = pipe.fit()
    wall = time.perf_counter() - start
    assert res.steps_completed == steps, res
    assert res.heals == 0 and res.gang_restarts == 0, res
    num_stages = 2
    analytic = (num_stages - 1) / (mbs + num_stages - 1)
    r = {"name": "pipeline 2-stage 1f1b (steps/s)",
         "per_s": round(steps / wall, 3), "unit": "steps/s",
         "steps": steps, "microbatches": mbs,
         "bubble_measured": round(res.bubble_fraction, 4),
         "bubble_analytic": round(analytic, 4),
         "heals": res.heals, "gang_restarts": res.gang_restarts}
    results.append(r)
    print(json.dumps(r), flush=True)
    # each microbatch makes one activation hop down and one grad hop up
    # per stage boundary: 2 * mbs paced p2p round-trips per step
    r = {"name": "pipeline stage-boundary hops (microbatches/s)",
         "per_s": round(2 * mbs * steps / wall, 1), "unit": "hops/s"}
    results.append(r)
    print(json.dumps(r), flush=True)
    return results


def run_benchmarks(*, quick: bool = False) -> list[dict]:
    results = []
    windows = 1 if quick else 3

    def bench(name, fn, multiplier=1):
        r = timeit(name, fn, multiplier, windows=windows)
        results.append(r)
        print(json.dumps(r), flush=True)

    # ---- put/get ----
    kb = np.zeros(1024, dtype=np.uint8)
    mb = np.zeros(1024 * 1024, dtype=np.uint8)

    ref_small = ray_tpu.put(b"ok")
    bench("single client get small", lambda: ray_tpu.get(ref_small))
    bench("single client put small", lambda: ray_tpu.put(b"ok"))
    bench("put 1KB", lambda: ray_tpu.put(kb))
    bench("put 1MB", lambda: ray_tpu.put(mb))
    ref_mb = ray_tpu.put(mb)
    bench("get 1MB", lambda: ray_tpu.get(ref_mb))

    gb = np.zeros(1024 * 1024 * 1024, dtype=np.uint8)

    def put_get_gb():
        r = ray_tpu.put(gb)
        out = ray_tpu.get(r, timeout=120)
        assert out.nbytes == gb.nbytes
        del out
        ray_tpu.free([r])

    bench("put+get 1GB (GB/s)", put_get_gb, multiplier=1)

    # ---- tasks ----
    bench("single client tasks sync",
          lambda: ray_tpu.get(_small_value.remote(), timeout=60))
    bench("single client tasks async (batch 1000)",
          lambda: ray_tpu.get(
              [_small_value.remote() for _ in range(1000)], timeout=120),
          multiplier=1000)
    bench("multi client tasks async (4 clients x 250)",
          lambda: ray_tpu.get(
              [_small_value_batch.remote(250) for _ in range(4)],
              timeout=120),
          multiplier=1000)

    # ---- wait ----
    refs_1k = [ray_tpu.put(i) for i in range(1000)]
    bench("wait on 1k refs",
          lambda: ray_tpu.wait(refs_1k, num_returns=1000, timeout=60))

    # ---- actors ----
    a = _Actor.remote()
    ray_tpu.get(a.small_value.remote(), timeout=60)
    bench("1:1 actor calls sync",
          lambda: ray_tpu.get(a.small_value.remote(), timeout=60))
    bench("1:1 actor calls async (batch 1000)",
          lambda: ray_tpu.get(
              [a.small_value.remote() for _ in range(1000)], timeout=120),
          multiplier=1000)
    arg_ref = ray_tpu.put(0)
    bench("1:1 actor calls with arg async (batch 1000)",
          lambda: ray_tpu.get(
              [a.small_value_arg.remote(arg_ref) for _ in range(1000)],
              timeout=120),
          multiplier=1000)

    aa = _AsyncActor.remote()
    ray_tpu.get(aa.small_value.remote(), timeout=60)
    bench("1:1 async-actor calls async (batch 1000)",
          lambda: ray_tpu.get(
              [aa.small_value.remote() for _ in range(1000)], timeout=120),
          multiplier=1000)

    n_actors = 4
    actors = [_Actor.remote() for _ in range(n_actors)]
    ray_tpu.get([b.small_value.remote() for b in actors], timeout=60)
    bench(f"1:n actor calls async (n={n_actors}, batch 250 each)",
          lambda: ray_tpu.get(
              [b.small_value.remote() for b in actors for _ in range(250)],
              timeout=120),
          multiplier=1000)

    # ---- queued-task drain (reference 'tasks queued on a node') ----
    def drain_10k():
        refs = [_noop.remote() for _ in range(10_000)]
        ray_tpu.get(refs, timeout=300)

    t0 = time.perf_counter()
    drain_10k()
    dt = time.perf_counter() - t0
    r = {"name": "10k queued task drain", "per_s": round(10_000 / dt, 1),
         "unit": "tasks/s"}
    results.append(r)
    print(json.dumps(r), flush=True)

    # ---- serving tier (LLM pool replica scaling + prefix cache) ----
    results.extend(run_serve_benchmarks(quick=quick))

    # ---- speculative decoding (draft/verify pump-rate win) ----
    results.extend(run_serve_spec_benchmarks(quick=quick))

    # ---- rl (actor-learner rollout / experience / publish paths) ----
    results.extend(run_rl_benchmarks(quick=quick))

    # ---- qos (pacing under contention + batched stream fanout) ----
    results.extend(run_qos_benchmarks(quick=quick))

    # ---- colocate (train+serve tax + overload guardian survival) ----
    results.extend(run_colocate_benchmarks(quick=quick))

    # ---- transfer (zero-copy put + pipelined cross-node pull) ----
    results.extend(run_transfer_benchmarks(quick=quick))

    # ---- collective (DCN star vs ring vs ring+int8) ----
    results.extend(run_collective_benchmarks(quick=quick))

    # ---- obs (flight-recorder overhead + span throughput) ----
    results.extend(run_obs_benchmarks(quick=quick))

    return results


def _start_head_proc(store_capacity: int):
    """Run the head (control plane + node agent) as a REAL subprocess via
    the CLI, like the reference's `ray microbenchmark` measures against a
    separate raylet/GCS — an in-process head shares the driver's GIL and
    measures contention, not the runtime."""
    import re
    import subprocess
    import sys

    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.scripts", "start", "--head",
         "--resources", '{"CPU": 8, "memory": 8589934592}',
         "--store-capacity", str(store_capacity)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    assert proc.stdout is not None
    deadline = time.time() + 30
    line = ""
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                break  # head died before printing its address
            time.sleep(0.05)
            continue
        m = re.search(r"--address (\S+:\d+)", line)
        if m:
            # keep draining the merged pipe or the head blocks on its
            # next log write once the ~64KB buffer fills
            import threading

            def _drain(stream=proc.stdout):
                for _ in stream:
                    pass

            threading.Thread(target=_drain, daemon=True).start()
            return proc, m.group(1)
    proc.kill()
    raise RuntimeError(f"head failed to start: {line!r}")


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--out", default=None, help="write results JSON here")
    p.add_argument("--quick", action="store_true")
    p.add_argument("--family", default="all",
                   choices=["all", "collective", "transfer", "serve",
                            "serve_spec", "rl", "obs", "qos",
                            "pipeline", "colocate"],
                   help="run one workload family only")
    p.add_argument("--in-process", action="store_true",
                   help="head in the driver process (debug only)")
    p.add_argument("--store-capacity", type=int,
                   default=3 * 1024 * 1024 * 1024)  # fits the 1 GB put
    args = p.parse_args(argv)

    proc = None
    if args.in_process:
        ray_tpu.init(num_cpus=8, object_store_memory=args.store_capacity)
    else:
        proc, address = _start_head_proc(args.store_capacity)
        ray_tpu.init(address=address)
    try:
        if args.family == "collective":
            results = run_collective_benchmarks(quick=args.quick)
        elif args.family == "transfer":
            results = run_transfer_benchmarks(quick=args.quick)
        elif args.family == "serve":
            results = run_serve_benchmarks(quick=args.quick)
        elif args.family == "serve_spec":
            results = run_serve_spec_benchmarks(quick=args.quick)
        elif args.family == "rl":
            results = run_rl_benchmarks(quick=args.quick)
        elif args.family == "obs":
            results = run_obs_benchmarks(quick=args.quick)
        elif args.family == "qos":
            results = run_qos_benchmarks(quick=args.quick)
        elif args.family == "pipeline":
            results = run_pipeline_benchmarks(quick=args.quick)
        elif args.family == "colocate":
            results = run_colocate_benchmarks(quick=args.quick)
        else:
            results = run_benchmarks(quick=args.quick)
    finally:
        ray_tpu.shutdown()
        if proc is not None:
            proc.terminate()
            try:
                proc.wait(timeout=5)
            except Exception:
                proc.kill()
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results,
                       "ts": time.strftime("%Y-%m-%d")}, f, indent=2)
    return results


if __name__ == "__main__":
    main()
