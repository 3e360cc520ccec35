"""Runtime performance floors (reference release/microbenchmark analog).

Conservative floors (~5-10x below measured-on-dev-box, see
RUNTIME_BENCH.json) so load/CI noise doesn't flake, but a pathological
regression — a serialization bug, an accidental sync point, a fork storm —
fails loudly. VERDICT r2 item 3.
"""

import pickle
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(head_resources={"CPU": 8, "memory": 8 * 2**30})
    c.connect()
    yield c
    c.shutdown()


def _best(fn, n, repeats=5):
    """Seconds a call of ``fn`` takes at its best of ``repeats`` batches
    of ``n``: the batch that another process did not interrupt."""
    fn()  # warmup
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n)
    return best


def _op_seconds():
    """This process's speed now, as the floors below count it: one
    pickle round trip of a small tuple into a dict, at its best of five
    batches (1 us on the dev box, PR 66). A floor is a multiple of it
    timed in the same test, as ``test_live_span_record_throughput_floor``
    has been since PR 56: a box that five other workers load moves both
    sides, where a constant rate read 724 of 1,000 /s and 432 of 650 /s
    in PR 65's run with nothing wrong."""
    store = {}

    def op():
        store["k"] = pickle.loads(pickle.dumps((1, "k", b"ok")))

    return _best(op, 2000)


def test_put_get_floors(cluster):
    """A ``get`` of a small sealed object, a 1 KiB ``put`` and a 1 MiB
    ``put``, each at its best of five batches, against what they are
    made of timed beside them: the first two in pickle round trips
    (measured 2-7 and 30-95 on the dev box; the constant floors they
    replace, 60k/s and 3k/s, were 17 and 330), the third in plain
    copies of the same MiB (measured 17-22: one copy, the seal and its
    asynchronous announce; the double-copy, synchronous-announce path
    it replaced cost 1.9 times that and fails the 32)."""
    kb = np.zeros(1024, dtype=np.uint8)
    mb = np.zeros(1024 * 1024, dtype=np.uint8)
    ref = ray_tpu.put(b"ok")
    op = _op_seconds()
    get = _best(lambda: ray_tpu.get(ref), 200) / op
    put_kb = _best(lambda: ray_tpu.put(kb), 100) / op
    dst = np.empty_like(mb)
    copy = _best(lambda: np.copyto(dst, mb), 100)
    put_mb = _best(lambda: ray_tpu.put(mb), 100) / copy
    print(f"get {get:.1f} op, put 1 KiB {put_kb:.1f} op, "
          f"put 1 MiB {put_mb:.1f} copies")
    assert get < 40, f"get costs {get:.0f} pickle round trips"
    assert put_kb < 400, f"a 1 KiB put costs {put_kb:.0f} round trips"
    assert put_mb < 32, f"a 1 MiB put costs {put_mb:.0f} copies of it"


def test_put_get_bandwidth_floor(cluster):
    """Large-object put+get, the weight-publishing path: one memcpy into
    the shm segment on put, zero-copy view on get. Measured ~6.5 GB/s
    warm in this fixture (the old path: ~1.3-3 GB/s)."""
    big = np.zeros(192 * 1024 * 1024, dtype=np.uint8)

    def put_get():
        r = ray_tpu.put(big)
        out = ray_tpu.get(r, timeout=60)
        assert out.nbytes == big.nbytes
        del out
        ray_tpu.free([r])

    put_get()  # warm the segment pages
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        put_get()
        best = max(best, big.nbytes / (time.perf_counter() - t0))
    assert best > 3.0e9, f"put+get bandwidth {best/1e9:.2f} GB/s"


def _recorded_bench():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..",
                        "RUNTIME_BENCH.json")
    with open(path) as f:
        return {r["name"]: r for r in json.load(f)["results"]}


def test_recorded_bench_meets_2x_baseline():
    """The committed RUNTIME_BENCH.json must hold the ISSUE-9 acceptance
    ratios over the pre-zero-copy baseline: put 1MB >= 2x 790 ops/s and
    put+get 1GB >= 2x 1.2 GB/s."""
    by_name = {n: r["per_s"] for n, r in _recorded_bench().items()}
    assert by_name["put 1MB"] >= 2 * 790
    assert by_name["put+get 1GB (GB/s)"] >= 2 * 1.2


def test_recorded_serve_pool_scaling_floors():
    """ISSUE-10 acceptance: the committed 2-replica LLM pool bench must
    hold >= 1.6x the single-replica aggregate tokens/s on the same
    host, with TTFT p99 recorded and bounded under concurrency 32, and
    the prefix-cache configuration must show real hits."""
    rec = _recorded_bench()
    r1 = rec["serve pool decode (1 replica)"]
    r2 = rec["serve pool decode (2 replicas)"]
    rp = rec["serve pool decode (2 replicas + prefix cache)"]
    assert r2["per_s"] >= 1.6 * r1["per_s"], (
        f"2-replica aggregate {r2['per_s']} < 1.6x single "
        f"{r1['per_s']}")
    for r in (r1, r2, rp):
        assert r["concurrency"] >= 32
        assert r["ttft_p99_s"] is not None
        # bounded: a p99 blowup (queue collapse) is the failure this
        # floor exists to catch; generous vs the ~0.8s recorded
        assert r["ttft_p99_s"] < 10.0
    assert rp["prefix_hit_rate"] is not None
    assert rp["prefix_hit_rate"] >= 0.5


def test_recorded_serve_spec_family_floors():
    """ISSUE-19 acceptance: the committed `serve_spec` family must show
    speculative decoding paying off under the emulated 50ms chunk
    dispatch — depth-4 spec-on >= 1.5x spec-off tokens/s on the
    sampled arm (acceptance ~0.45 with the random-weight tiny model's
    1-layer draft; greedy acceptance is too low on random weights to
    carry the throughput floor, so it carries the correctness floor
    instead) — and every spec record must be bit-identical to its
    spec-off baseline (``match_baseline``), which is the whole
    draft/verify contract: speculation changes latency, never tokens."""
    rec = _recorded_bench()
    off_s = rec["serve spec decode off (sampled)"]
    d2_s = rec["serve spec decode depth 2 (sampled)"]
    d4_s = rec["serve spec decode depth 4 (sampled)"]
    assert d4_s["per_s"] >= 1.5 * off_s["per_s"], (
        f"depth-4 sampled {d4_s['per_s']} < 1.5x spec-off "
        f"{off_s['per_s']}")
    assert d2_s["per_s"] >= 1.2 * off_s["per_s"], (d2_s, off_s)
    for tag in ("depth 2", "depth 4"):
        for arm in ("greedy", "sampled"):
            r = rec[f"serve spec decode {tag} ({arm})"]
            assert r["match_baseline"] is True, r
            assert r["acceptance_rate"] is not None, r
            assert r["chunk_delay_s"] == 0.05, r


def test_recorded_rl_family_floors():
    """ISSUE-12 satellite: the committed `rl` runtime_perf family must
    exist with sane floors — rollout tokens/s through the sampled
    streaming surface, experience bytes/s through the store, and a
    bounded publish-to-adoption latency (the weight staleness window)."""
    rec = _recorded_bench()
    roll = rec["rl rollout sampled stream (2 replicas)"]
    assert roll["unit"] == "tokens/s"
    # measured ~105 tok/s on the dev box (per-request polling surface,
    # emulated 50ms chunk dispatch); floor well under
    assert roll["per_s"] >= 40, roll
    xfer = rec["rl experience handoff (put+add+claim+get)"]
    # measured ~125 ops/s (~6.4 MB/s of trajectory arrays)
    assert xfer["per_s"] >= 40, xfer
    assert xfer["mb_per_s"] >= 1.0, xfer
    pub = rec["rl weight publish-to-adoption (2 replicas)"]
    # measured ~40ms for a tiny-model tree across 2 replicas; the
    # bound is what keeps "bounded staleness" an enforceable claim
    assert pub["latency_s"] <= 2.0, pub


def test_recorded_transfer_family_floors():
    """ISSUE-17 acceptance: the committed `transfer` family must show
    the receive-side zero-copy data plane paying off — cross-node 64MB
    pull >= 0.9 GB/s (>= 2x the 0.34 recorded before scatter-read +
    pre-faulted segments), scatter-on beating scatter-off in every
    tier, the 1GB tier completing (the serve-pin leak once stranded
    7x64MB and OOM'd it), and the real consumers (weight broadcast,
    prefill->decode KV handoff) recorded with bounded latency."""
    rec = _recorded_bench()
    seq = rec["cross-node pull 64MB (sequential depth=1)"]
    assert seq["gb_per_s"] >= 0.9, seq
    pipe = rec["cross-node pull 64MB (1 source)"]
    off = rec["cross-node pull 64MB (scatter off)"]
    assert pipe["gb_per_s"] >= off["gb_per_s"], (pipe, off)
    g_on = rec["cross-node pull 1GB (scatter on)"]
    g_off = rec["cross-node pull 1GB (scatter off)"]
    assert g_on["gb_per_s"] >= g_off["gb_per_s"], (g_on, g_off)
    assert g_on["gb_per_s"] >= 0.3, g_on
    # consumer adoption latencies: generous bounds (the recorded
    # numbers are ~16ms and ~113ms) — the floor pins that both paths
    # exist and stay interactive, not the exact figure
    pub = rec["transfer weight publish-to-adoption (2 replicas)"]
    assert pub["latency_s"] <= 2.0, pub
    assert pub["weight_bytes"] > 0, pub
    kv = rec["transfer kv handoff (prefill to decode, 1 token)"]
    assert kv["latency_s"] <= 2.0, kv


def test_recorded_qos_family_floors():
    """ISSUE-16 acceptance: the committed `qos` runtime_perf family must
    hold the multi-tenant contention floors — with the pacer ON and a
    learner gang + bulk spill saturating the host, the serving tenant
    keeps >= 0.7x its uncontended decode tokens/s and TTFT p99 within
    2x uncontended, the bulk transfer still completes byte-identical,
    and byte attribution stays within 1%. The batched stream fanout
    must beat the old per-request poll ceiling (~106 tok/s) with well
    under one replica poll RPC per emitted token."""
    rec = _recorded_bench()
    grant = rec["qos pacer grant (unlimited fast path)"]
    # measured ~500k grants/s on the dev box: the tally fast path every
    # tagged send pays when enforcement is off costs ~2us
    assert grant["per_s"] >= 50_000, grant
    cont = rec["qos serve contention (gang + bulk spill, paced)"]
    assert cont["ratio_tokens"] >= 0.7, cont
    assert cont["ratio_ttft"] <= 2.0, cont
    assert cont["bulk_completed"] is True, cont
    assert cont["attribution_err"] <= 0.01, cont
    assert cont["pacer_parks"] > 0, cont  # pacing actually engaged
    assert cont["rate_mbps"] > 0, cont
    fan = rec["qos batched stream fanout (8 streams)"]
    # measured ~640 tok/s aggregate (dev box); the pre-batching surface
    # capped each stream near ~106 tok/s and cost ~3 RPCs/token
    assert fan["per_s"] >= 150, fan
    assert fan["polls_per_token"] <= 1.0, fan


def test_pipelined_pull_2x_sequential_under_latency():
    """Cross-node pull with the chunk window vs one-request-at-a-time,
    under a deterministic injected per-chunk serve latency (the
    fault-injection site standing in for real cross-host RTT, which
    loopback cannot exhibit): the pipeline must hide >= half of it."""
    import os as _os

    from ray_tpu._private import config as cfg
    from ray_tpu._private import fault_injection
    from ray_tpu.cluster_utils import Cluster

    # agents only, no driver (a connect() would clobber the module
    # cluster fixture's global worker)
    c = Cluster(head_resources={"CPU": 2, "memory": 2 * 2**30},
                store_capacity=256 * 2**20)
    c.add_node(resources={"CPU": 2, "memory": 2 * 2**30})
    old_chunk = cfg.get("object_transfer_chunk_bytes")
    try:
        cfg.set_system_config({"object_transfer_chunk_bytes": 256 * 1024})
        src, dst = c.agents[0], c.agents[1]
        data = _os.urandom(4 * 2**20)  # 16 chunks
        fault_injection.configure([
            {"site": "object.read_chunk", "action": "delay",
             "delay_s": 0.01, "count": 0},  # every chunk, 10ms "RTT"
        ])

        def timed_pull(depth):
            cfg.set_system_config({"transfer_pull_pipeline_depth": depth})
            oid = _os.urandom(16)
            src.store.put_bytes(oid, data, metadata=b"")
            c.io.run(src.rpc_object_sealed(
                None, {"object_id": oid, "size": len(data)}))
            t0 = time.perf_counter()
            ok = c.io.run(dst.rpc_fetch_object(
                None, {"object_id": oid, "timeout": 60}))
            dt = time.perf_counter() - t0
            assert ok
            buf = dst.store.get(oid)
            assert bytes(buf.data) == data
            buf.release()
            return dt

        seq = min(timed_pull(1) for _ in range(2))
        pipe = min(timed_pull(8) for _ in range(2))
        assert seq / pipe >= 2.0, (
            f"pipelined pull only {seq/pipe:.2f}x sequential "
            f"({pipe:.3f}s vs {seq:.3f}s)")
    finally:
        fault_injection.clear()
        cfg.set_system_config({
            "object_transfer_chunk_bytes": old_chunk,
            "transfer_pull_pipeline_depth": 8,
        })
        c.shutdown()


def test_recorded_pipeline_family_floors():
    """ISSUE-18 acceptance: the committed `pipeline` runtime_perf family
    must hold the MPMD pipeline floors — a 2-stage 1F1B pipeline makes
    real forward progress through the paced p2p lanes (steps/s and
    boundary hops/s floors ~5x under the dev-box numbers) and its
    measured bubble fraction (p2p-wait + allreduce-wait over wall) stays
    bounded: above the analytic (S-1)/(M+S-1) lower bound, and well
    under the no-overlap ceiling a sequential send-wait-compute loop
    would show."""
    rec = _recorded_bench()
    pipe = rec["pipeline 2-stage 1f1b (steps/s)"]
    # measured ~3.4 steps/s on the dev box (10 steps, 8 microbatches,
    # 256x256 matmul stages, gang spawn + rendezvous included)
    assert pipe["per_s"] >= 0.5, pipe
    assert pipe["heals"] == 0 and pipe["gang_restarts"] == 0, pipe
    analytic = pipe["bubble_analytic"]
    assert abs(analytic - 1 / 9) < 1e-3, pipe
    # measured 0.39 on the dev box: transport overhead rides on top of
    # the analytic schedule bubble, but overlap keeps it far from the
    # ~1.0 a fully-serialized pipeline would record
    assert analytic <= pipe["bubble_measured"] <= 0.75, pipe
    hops = rec["pipeline stage-boundary hops (microbatches/s)"]
    # measured ~54 hops/s (2 x 8 mbs x 10 steps over the same wall)
    assert hops["per_s"] >= 8, hops


def test_recorded_colocate_family_floors():
    """ISSUE-20 acceptance: the committed `colocate` runtime_perf family
    must hold the train+serve-on-one-cluster floors — the gang's
    allreduce step stays within a bounded colocation tax while a
    two-tenant pool decodes on the same host (both tenants keeping a
    live TTFT), and at 2x overcommit the guardian actually walks the
    ladder to L3, sheds typed without starving the pool, and recovers
    to L0 once the flood stops (no parked degradation)."""
    rec = _recorded_bench()
    colo = rec["colocate train step (gang + 2-tenant pool)"]
    # measured 1.18x on the dev box: the serve pool costs the gang
    # under 20% step time; 2.5x is the "colocation is broken" line
    assert colo["step_ratio"] <= 2.5, colo
    assert colo["ttft_p99_a_s"] <= 5.0, colo
    assert colo["ttft_p99_b_s"] <= 5.0, colo
    assert colo["served"] >= 8, colo
    shed = rec["colocate shed rate (2x overcommit, 1 replica)"]
    # measured 0.53 shed rate: the flood is genuinely past capacity
    # (sheds happen) but admission keeps the pool serving (oks happen)
    assert shed["shed"] > 0 and shed["served"] > 0, shed
    assert 0.05 <= shed["shed_rate"] <= 0.95, shed
    assert shed["peak_level"] == 3, shed
    # measured 3.8s back to L0 (fast-dwell knobs): recovery must not
    # park — 30s is the flap/stuck line
    assert shed["recovery_to_l0_s"] is not None, shed
    assert shed["recovery_to_l0_s"] <= 30.0, shed
    assert shed["transitions"] >= 6, shed  # full up AND down ladder


def test_recorded_obs_family_floors():
    """ISSUE-14 acceptance: the committed `obs` runtime_perf family must
    show the always-on flight recorder costing <= 3% on ring allreduce
    and serve decode throughput, with a healthy span-record rate."""
    rec = _recorded_bench()
    spans = rec["obs span record throughput (ring only)"]
    # measured ~820k spans/s on the dev box; even a 5x-slower CI box
    # clears this with room — per-op spans cost microseconds
    assert spans["per_s"] >= 100_000, spans
    for name in ("obs overhead: ring allreduce 16MB (4 ranks)",
                 "obs overhead: serve pool decode (1 replica)"):
        r = rec[name]
        assert r["overhead_pct"] <= 3.0, r
        assert r["baseline_per_s"] > 0, r


def test_live_span_record_throughput_floor():
    """Ring-only record() (the per-chunk hot-path form) must stay
    cheap: at most RECORD_OVER_BARE times what it is made of, the dict
    build and the ``deque.append`` under a lock, both timed in this
    process as the best of five repeats, so that a loaded box (six
    xdist workers) moves both and not their ratio. Measured 2.5 on the
    dev box and 2.5 on the chip machine's host (PR 56); a trace-context
    lookup or a second lock on the path would show as 4 and more."""
    import collections
    import threading
    import time as _time

    from ray_tpu._private import flight_recorder as fr

    RECORD_OVER_BARE = 8.0
    n = 20_000
    t = _time.monotonic()
    fr.record("bench", "warm", t, t, flush=False)

    def recorded():
        for _ in range(n):
            fr.record("bench", "floor", t, t, flush=False)

    ring, lock = collections.deque(maxlen=fr.stats()["ring_cap"]), \
        threading.Lock()

    def bare():
        for _ in range(n):
            span = {"kind": "bench", "name": "floor", "start_s": t + 1.0,
                    "end_s": t + 1.0, "trace": None, "attrs": {}}
            with lock:
                ring.append(span)

    def best_of_five(fn):
        best = float("inf")
        for _ in range(5):
            t0 = _time.perf_counter()
            fn()
            best = min(best, _time.perf_counter() - t0)
        return best

    ratio = best_of_five(recorded) / best_of_five(bare)
    print(f"record() over its bare parts: {ratio:.2f}")
    assert ratio <= RECORD_OVER_BARE, f"record() costs {ratio:.1f}x"
    # ring stays bounded regardless of volume
    st = fr.stats()
    assert st["ring_len"] <= st["ring_cap"]


def test_task_throughput_floors(cluster):
    @ray_tpu.remote(num_cpus=0)
    def noop():
        return 1

    # spin the pool up before measuring
    ray_tpu.get([noop.remote() for _ in range(32)], timeout=60)
    op = _op_seconds()

    out = []

    def batch():
        out[:] = ray_tpu.get([noop.remote() for _ in range(500)],
                             timeout=120)

    # pipelined submission + lease refill + coalesced wire writes: a
    # task of a batch of 500 costs 120-240 pickle round trips on the
    # dev box at its best of three batches (4.9-5.9k/s; the head shares
    # the driver's GIL here); the r4 path cost 1.4 times the constant
    # floor this replaces (1.8k/s: 560)
    task = _best(batch, 1, repeats=3) / 500 / op
    assert sum(out) == 500
    # one task at a time, three processes deep: 1,200-2,300 round trips
    # (500-680/s on the dev box, idle); a synchronous point added to the
    # path costs a scheduler's wake-up, thousands more
    sync = _best(lambda: ray_tpu.get(noop.remote(), timeout=60), 20,
                 repeats=3) / op
    print(f"a batched task {task:.0f} op, a task alone {sync:.0f} op")
    assert task < 600, f"a batched task costs {task:.0f} round trips"
    assert sync < 6000, f"a task alone costs {sync:.0f} round trips"


def test_multi_client_throughput_floor(cluster):
    """Aggregate throughput of concurrent worker-owners (each a nested
    driver submitting its own children). r4 shipped a silent regression
    here (509/s aggregate vs 1.9k/s single-client) because no floor
    existed: lease grants + background spawns monopolized the pool and
    queued tasks starved behind lease traffic for seconds."""
    @ray_tpu.remote(num_cpus=0)
    def child():
        return 1

    @ray_tpu.remote(num_cpus=0)
    def owner_batch(n):
        return sum(ray_tpu.get(
            [child.remote() for _ in range(n)], timeout=120))

    ray_tpu.get([owner_batch.remote(50) for _ in range(4)], timeout=120)
    best = 0.0
    for _ in range(3):  # best-of-3: shared-box noise must not flake CI
        t0 = time.perf_counter()
        out = ray_tpu.get([owner_batch.remote(250) for _ in range(4)],
                          timeout=180)
        best = max(best, 1000 / (time.perf_counter() - t0))
        assert sum(out) == 1000
    # measured ~3.2-4.3k/s (r5); r4's starved path was ~0.5k/s
    assert best > 2_200, f"multi-client aggregate {best:.0f}/s"


def test_no_worker_fork_storm(cluster):
    """A flood of zero-cpu tasks must reuse a bounded worker pool, not
    spawn a process per in-flight task (the bug this test pins: 1000
    concurrent num_cpus=0 tasks once spawned 375 workers)."""
    @ray_tpu.remote(num_cpus=0)
    def noop():
        return 1

    agent = cluster.head_agent

    def n_pool():
        return sum(1 for w in agent.workers.values()
                   if w.actor_id is None)

    # nested-owner tests earlier in this shared fixture legitimately
    # leave the pool above cap (blocked-worker backfills linger until
    # the idle cull); the fork-storm invariant is that a flood does not
    # GROW the pool past max(current, cap)
    before = n_pool()
    out = ray_tpu.get([noop.remote() for _ in range(600)], timeout=120)
    assert sum(out) == 600
    assert n_pool() <= max(before, agent._pool_worker_cap())


def test_actor_call_floors(cluster):
    @ray_tpu.remote(num_cpus=0)
    class A:
        def ping(self):
            return b"ok"

    a = A.remote()
    ray_tpu.get(a.ping.remote(), timeout=60)
    t0 = time.perf_counter()
    out = ray_tpu.get([a.ping.remote() for _ in range(500)], timeout=120)
    rate = 500 / (time.perf_counter() - t0)
    assert len(out) == 500
    # fired (non-blocking) actor calls: measured ~8.5k/s (r4)
    assert rate > 2_000, f"actor async call throughput {rate:.0f}/s"


def test_wait_1k_refs_floor(cluster):
    refs = [ray_tpu.put(i) for i in range(1000)]
    t0 = time.perf_counter()
    ready, _ = ray_tpu.wait(refs, num_returns=1000, timeout=60)
    dt = time.perf_counter() - t0
    assert len(ready) == 1000
    assert dt < 2.0, f"wait on 1k local refs took {dt:.2f}s"


def test_collective_family_floors(cluster):
    """The `collective` runtime_perf family's committed invariants, run
    small (4 ranks, 1 MB): per-rank wire bytes for ring allreduce are
    exactly 2·(N−1)/N of the tensor (vs ≥(N−1)·tensor at the star root),
    ring+int8 moves ≤30% of the f32 ring bytes, and throughput floors
    ~5-10x under dev-box measurements (RUNTIME_BENCH.json) so only a
    pathological regression — a per-chunk sync point, a serialization
    storm — trips them."""
    import uuid

    from ray_tpu._private.runtime_perf import _CollRank

    world = 4
    nbytes = 1024 * 1024
    ranks = [_CollRank.remote() for _ in range(world)]
    name = f"floor-{uuid.uuid4().hex[:8]}"

    def run(transport, codec, iters=3):
        outs = ray_tpu.get(
            [a.allreduce_loop.remote(nbytes, iters, transport, codec)
             for a in ranks],
            timeout=300,
        )
        per_op = max(dt for dt, _ in outs)
        return 1.0 / per_op, [b for _, b in outs]

    try:
        ray_tpu.get([a.init.remote(world, r, name)
                     for r, a in enumerate(ranks)], timeout=120)
        star_rate, star_bytes = run("star", None)
        ring_rate, ring_bytes = run("ring", None)
        int8_rate, int8_bytes = run("ring", "int8")

        ring_limit = 2 * (world - 1) / world * nbytes
        for b in ring_bytes:
            assert b <= ring_limit, f"ring rank moved {b} > {ring_limit}"
        # star root re-sends the full reduction to every other rank
        assert max(star_bytes) >= (world - 1) * nbytes
        for b8, bf in zip(int8_bytes, ring_bytes):
            assert b8 <= 0.30 * bf, f"int8 wire {b8} > 30% of f32 {bf}"
        # measured ~30-60/s (ring) and ~25-50/s (star) on the dev box for
        # 1 MB x 4 ranks in this in-process fixture
        assert ring_rate > 3, f"ring 1MB allreduce {ring_rate:.1f}/s"
        assert star_rate > 3, f"star 1MB allreduce {star_rate:.1f}/s"
        assert int8_rate > 3, f"ring+int8 1MB allreduce {int8_rate:.1f}/s"
    finally:
        for a in ranks:
            ray_tpu.kill(a)
