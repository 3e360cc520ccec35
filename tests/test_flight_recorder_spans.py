"""The flight recorder on the profiler's clock (ISSUE-24).

- ``span()`` / ``mark()`` are ring entries always and profiler events
  under a live ``jax.profiler`` session, with their attrs (those the
  body attached too); a span never imports jax;
- the engine records ``serve.first_token`` once per request, split into
  engine queue wait and prefill-to-token, plus the way to the engine
  from the request's birth stamps when it carried any;
- the pool's stamps survive a failover re-assignment.
"""

import glob
import os
import subprocess
import sys
import time

import pytest

from ray_tpu._private import flight_recorder as fr


def _ring(name):
    return [s for s in fr._get().ring if s["name"] == name]


def _profiled_events(log_dir, prefix="t24."):
    """{name: (duration_ns, stats)} of the capture's events whose name
    starts with ``prefix`` (a string or a tuple of them)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return {ev.name: (ev.duration_ns, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)}


def test_a_span_never_imports_jax():
    code = (
        "import sys\n"
        "from ray_tpu._private import flight_recorder as fr\n"
        "with fr.span('serve', 't24.plain', attrs={'a': 1}) as a:\n"
        "    a['b'] = 2\n"
        "fr.mark('serve', 't24.mark', attrs={'a': 1})\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
        "ring = [(s['name'], s['attrs']) for s in fr._get().ring]\n"
        "assert ring == [('t24.plain', {'a': 1, 'b': 2}),\n"
        "                ('t24.mark', {'a': 1})], ring\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


@pytest.mark.parametrize("session", [False, True],
                         ids=["no-session", "live-session"])
def test_span_and_mark_with_and_without_a_profiler_session(
        session, tmp_path):
    import jax

    if session:
        jax.profiler.start_trace(str(tmp_path))
    try:
        with fr.span("serve", "t24.span", attrs={"rows": 8, "ok": True},
                     flush=False) as a:
            time.sleep(0.002)
            a["late"] = 7  # learnt in the body
            a["blob"] = [1, 2]  # no profiler metadata: ring only
        fr.mark("serve", "t24.mark", attrs={"pickup_ms": 1.5},
                flush=False)
    finally:
        if session:
            jax.profiler.stop_trace()
    # the ring holds both either way
    sp, mk = _ring("t24.span")[-1], _ring("t24.mark")[-1]
    assert sp["attrs"] == {"rows": 8, "ok": True, "late": 7,
                           "blob": [1, 2]}
    assert sp["end_s"] - sp["start_s"] >= 0.002
    assert mk["start_s"] == mk["end_s"]
    assert mk["attrs"] == {"pickup_ms": 1.5}
    if not session:
        assert not glob.glob(str(tmp_path / "plugins" / "*"))
        return
    # ... and the profiler's host plane holds them with their attrs as
    # stats, timed by the profiler
    evs = _profiled_events(str(tmp_path))
    dur, stats = evs["t24.span"]
    assert stats == {"rows": 8, "ok": 1, "late": 7} and dur >= 2e6
    assert evs["t24.mark"][1] == {"pickup_ms": 1.5}


def test_suppressed_recorder_leaves_no_annotation(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with fr._suppressed():
            with fr.span("serve", "t24.off"):
                pass
            fr.mark("serve", "t24.off_mark")
        with fr.span("serve", "t24.on"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert set(_profiled_events(str(tmp_path))) == {"t24.on"}
    assert not _ring("t24.off") and not _ring("t24.off_mark")


# ---------------------------------------------------------------------------
# the engine records serve.first_token, once, with its split
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine():
    import jax

    from ray_tpu.models import llama
    from ray_tpu.models.decode_engine import RaggedDecoder

    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=96, dtype="float32", remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return RaggedDecoder(params, cfg, slots=2, max_len=96, chunk_tokens=4,
                         prompt_buckets=(8, 16), name="t24-engine")


def _first_tokens(engine_name):
    return [s for s in _ring("serve.first_token")
            if s["attrs"].get("engine") == engine_name]


def test_one_first_token_span_per_request_with_its_split(engine):
    before = len(_first_tokens("t24-engine"))
    stats0 = engine.stats()
    now = fr.wall(time.monotonic())
    stamps = {"proxy_recv": now - 0.030, "pool_enqueue": now - 0.020,
              "pool_admitted": now - 0.005}
    # three requests on two slots: the third waits a whole request
    sids = [engine.submit(list(range(1, 6 + i)), 6,
                          stamps=stamps if i == 0 else None)
            for i in range(3)]
    engine.drain()
    spans = _first_tokens("t24-engine")[before:]
    assert sorted(s["attrs"]["sid"] for s in spans) == sorted(sids)
    for s in spans:
        a = s["attrs"]
        dur_ms = 1e3 * (s["end_s"] - s["start_s"])
        assert a["queue_wait_ms"] + a["prefill_to_token_ms"] \
            == pytest.approx(dur_ms, abs=0.01)
        assert a["queue_wait_ms"] >= 0 and a["prefill_to_token_ms"] > 0
        assert a["bucket"] == 8 and a["prompt_len"] in (5, 6, 7)
    by_sid = {s["attrs"]["sid"]: s["attrs"] for s in spans}
    # the third request waited for a slot: its queue wait holds at
    # least the first two's whole decode
    assert by_sid[sids[2]]["queue_wait_ms"] \
        > by_sid[sids[0]]["queue_wait_ms"]
    # birth stamps: the parts and their sum, to a millisecond
    a = by_sid[sids[0]]
    assert a["proxy_to_pool_ms"] == pytest.approx(10.0, abs=0.01)
    assert a["admission_wait_ms"] == pytest.approx(15.0, abs=0.01)
    assert a["upstream_ms"] == pytest.approx(
        a["proxy_to_pool_ms"] + a["admission_wait_ms"]
        + a["pool_to_replica_ms"], abs=0.01)
    assert 5.0 <= a["pool_to_replica_ms"] < 1000.0
    # a request without stamps gets the attrs it can have, no others
    assert not {"upstream_ms", "proxy_to_pool_ms", "admission_wait_ms",
                "pool_to_replica_ms"} & set(by_sid[sids[1]])
    # serve.decode closes each stream; the counters count the calls
    assert len([s for s in _ring("serve.decode")
                if s["attrs"]["sid"] in sids
                and s["start_s"] >= now]) == 3  # not older engines' sids
    # one prefill call a prompt, whatever number of slots was free
    assert engine.stats()["prefill_calls"] - stats0["prefill_calls"] == 3


@pytest.mark.parametrize("stamps, want", [
    ({"pool_enqueue": 99.0, "pool_admitted": 99.5},
     {"admission_wait_ms": 500.0, "pool_to_replica_ms": 500.0,
      "upstream_ms": 1000.0}),
    ({"proxy_recv": 99.9}, {"upstream_ms": 100.0}),
    ({"proxy_recv": "soon", "pool_admitted": None}, {}),
    ("not a dict", {}),
    (None, {}),
], ids=["pool-direct", "proxy-only", "garbage-values", "garbage", "none"])
def test_upstream_split_takes_what_the_request_carried(stamps, want):
    from ray_tpu.models.decode_engine import _upstream_ms

    assert _upstream_ms(stamps, 100.0) == pytest.approx(want)


# ---------------------------------------------------------------------------
# through the pool: stamps on the wire, and across a failover
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(head_resources={"CPU": 8, "memory": 4 * 2**30})
    c.connect()
    yield c
    c.shutdown()


def _drain_stream(pool, rid, deadline_s=120):
    toks, deadline = [], time.time() + deadline_s
    while time.time() < deadline:
        out = pool.poll_stream(rid)
        toks.extend(out["tokens"])
        if out["done"]:
            return toks
        time.sleep(0.02)
    raise AssertionError("stream did not finish")


def _head_first_tokens(trace_id=None, n=1, timeout=30):
    """serve.first_token SPAN events at the head (the replicas' flushers
    ship them every half second), optionally of one trace."""
    import ray_tpu

    deadline = time.time() + timeout
    while True:
        evs = [e for e in ray_tpu.list_tasks(limit=5000)
               if e.get("state") == "SPAN"
               and e.get("name") == "serve.first_token"
               and (trace_id is None or (e.get("trace") or {}).get(
                   "trace_id") == trace_id)]
        if len(evs) >= n or time.time() > deadline:
            return evs
        time.sleep(0.25)


def test_pool_stamps_reach_the_replica_and_survive_failover(cluster):
    import ray_tpu
    from ray_tpu._private import trace as _trace
    from ray_tpu.serve.llm_pool import LLMPool

    # (a chunk takes 30 ms: the poll that finds the first tokens must
    # find the stream unfinished, or there is nothing to fail over; six
    # bare chunks of the tiny model fit between two polls one time in
    # five)
    pool = LLMPool(model_size="tiny", slots=2, max_len=96, chunk_tokens=4,
                   prompt_buckets=(8, 16), min_replicas=2, max_replicas=2,
                   prefill_workers=0, autoscale=False, chunk_delay_s=0.03)
    try:
        t_proxy = fr.wall(time.monotonic()) - 0.050
        sub = pool.submit_stream({
            "prompt_ids": list(range(1, 8)), "max_tokens": 24,
            "stamps": {"proxy_recv": t_proxy}})
        rec = pool._streams[sub["rid"]]
        first = dict(rec["stamps"])
        assert first["proxy_recv"] == t_proxy
        assert first["proxy_recv"] < first["pool_enqueue"] \
            <= first["pool_admitted"]
        # take the first tokens, then kill the replica mid-stream
        victim = rec["rep"]
        got = []
        while not got:
            got.extend(pool.poll_stream(sub["rid"])["tokens"])
            time.sleep(0.02)
        # (the victim's first-token span is at the head before it dies)
        assert len(_head_first_tokens(rec["trace"][0])) == 1
        ray_tpu.kill(victim.handle)
        got.extend(_drain_stream(pool, sub["rid"]))
        assert len(got) == 24
        assert rec["rep"] is not victim
        # the re-assignment kept the stream's birth, renewed its grant
        assert rec["stamps"]["proxy_recv"] == t_proxy
        assert rec["stamps"]["pool_enqueue"] == first["pool_enqueue"]
        assert rec["stamps"]["pool_admitted"] > first["pool_admitted"]
        # each engine that served the stream recorded its first token
        # once, under the stream's trace, with the whole way from the
        # proxy: the survivor's counts the failover too
        spans = _head_first_tokens(rec["trace"][0], n=2)
        assert len(spans) == 2
        for a in (s["attrs"] for s in spans):
            assert a["upstream_ms"] >= 50.0
            assert a["upstream_ms"] == pytest.approx(
                a["proxy_to_pool_ms"] + a["admission_wait_ms"]
                + a["pool_to_replica_ms"], abs=0.01)
            assert a["queue_wait_ms"] >= 0 and a["bucket"] == 8
        assert len({a["engine"] for a in
                    (s["attrs"] for s in spans)}) == 2

        # the blocking path: a request without stamps gets the pool's
        # own two and no proxy part
        with _trace.root_scope() as (tid, _):
            out = pool.generate(list(range(1, 6)), 5)
        assert len(out["tokens"]) == 5
        (span,) = _head_first_tokens(tid)
        assert {"admission_wait_ms", "pool_to_replica_ms",
                "upstream_ms"} <= set(span["attrs"])
        assert "proxy_to_pool_ms" not in span["attrs"]
    finally:
        pool.shutdown()


def test_a_compile_after_the_warm_up_is_a_named_mark_in_the_timeline(cluster):
    """What a benchmark's window calls "N compilation(s)": a prompt of a
    bucket the warm-up left out compiles that bucket's prefill program,
    and the replica's ``engine.compiled`` mark names it in
    ``ray_tpu.timeline()`` beside the bring-up's ``serve.replica_start``
    (ISSUE-56); with no new shape, no mark."""
    import ray_tpu
    from ray_tpu.serve.llm_pool import LLMPool

    def compiled():
        return [e["args"] for e in ray_tpu.timeline()
                if e.get("name") == "engine.compiled"
                and e["args"].get("engine") == "decode-1"
                and e["ts"] >= t_start_us]

    def wait_for(n, timeout=30.0):
        deadline = time.time() + timeout
        while len(compiled()) < n and time.time() < deadline:
            time.sleep(0.25)
        return compiled()

    t_start_us = 1e6 * fr.wall(time.monotonic())
    # (shapes of its own: a reused worker process that had run the other
    # tests' programs would compile nothing)
    pool = LLMPool(model_size="tiny", slots=2, max_len=88, chunk_tokens=3,
                   prompt_buckets=(8, 24), min_replicas=1, max_replicas=1,
                   prefill_workers=0, autoscale=False)
    try:
        pool.generate(list(range(1, 6)), 5)  # the warm-up: bucket 8
        warm = wait_for(2)
        assert sorted((a["program"], a["bucket"]) for a in warm) == [
            ("jit__prefill_batch_into_slots", 8), ("jit_decode_chunk", 0)]
        pool.generate(list(range(1, 7)), 5)  # the same shapes: nothing
        pool.generate(list(range(1, 12)), 5)  # "inside the window"
        marks = wait_for(3)
        assert len(marks) == 3, marks
        late = [a for a in marks if a not in warm]
        assert [(a["program"], a["bucket"]) for a in late] == [
            ("jit__prefill_batch_into_slots", 24)]
        assert late[0]["since_ready_ms"] > max(
            a["since_ready_ms"] for a in warm)
        # the mark count is the number of programs the replica ran
        rec = next(iter(pool.stats()["per_replica"].values()))["setup"]
        assert rec["first_calls"] == 3
        starts = [e for e in ray_tpu.timeline()
                  if e.get("name") == "serve.replica_start"
                  and e["args"].get("engine") == "decode-1"
                  and e["ts"] >= t_start_us]
        assert len(starts) == 1
        assert starts[0]["dur"] == pytest.approx(
            1e3 * rec["replica_start_ms"], rel=0.05)
    finally:
        pool.shutdown()


def test_replica_pump_spans_and_poll_pickup_marks(tmp_path):
    """One replica in this process: the pump thread's ring-only spans,
    the poll marks, and the product's own trace hook."""
    from ray_tpu.serve.llm import LLMServer

    srv = LLMServer("tiny", slots=2, max_len=96, chunk_tokens=4,
                    prompt_buckets=(8, 16), engine_name="t24-replica")
    try:
        srv.generate(list(range(1, 6)), 5)  # compile outside the capture
        n0 = {k: len(_ring(k)) for k in (
            "serve.pump", "serve.pump_bookkeeping", "engine.admit",
            "engine.prefill", "engine.decode_dispatch",
            "engine.readback", "engine.deliver", "serve.poll_pickup")}
        assert srv.start_trace(str(tmp_path)) is True
        sid = srv.submit_stream({"prompt_ids": list(range(1, 8)),
                                 "max_tokens": 9})["sid"]
        toks, deadline = [], time.time() + 60
        while time.time() < deadline:
            out = srv.poll_stream(sid)
            toks.extend(out["tokens"])
            if out["done"]:
                break
            time.sleep(0.005)
        assert srv.stop_trace() is True
        assert len(toks) == 9
        new = {k: _ring(k)[n:] for k, n in n0.items()}
        # 9 tokens at 4 a chunk: the first token and four with the first
        # chunk, then a second chunk: two working pumps, one admission
        assert len(new["serve.pump"]) == len(new["engine.readback"]) \
            == len(new["engine.deliver"]) == 2
        assert len(new["engine.admit"]) == len(new["engine.prefill"]) == 1
        assert new["engine.prefill"][0]["attrs"] == {
            "bucket": 8, "prompts": 1, "rows": 1, "tokens": 7, "segments": 1,
            "live_segments": 1}
        assert new["engine.admit"][0]["attrs"] == {
            "admitted": 1, "prefilled": 0, "cold": 1, "warm": 0}
        assert [s["attrs"]["delivered"] for s in new["engine.deliver"]] \
            == [5, 4]
        assert new["engine.deliver"][1]["attrs"]["finished"] == 1
        pump = new["serve.pump"][0]
        assert pump["attrs"]["queued"] == 1
        # mono_ns maps the span's clock to time.monotonic
        assert fr.wall(pump["attrs"]["mono_ns"] / 1e9) \
            == pytest.approx(pump["start_s"], abs=0.005)
        for child in ("engine.admit", "engine.readback",
                      "serve.pump_bookkeeping"):
            c = new[child][0]
            assert pump["start_s"] <= c["start_s"] \
                and c["end_s"] <= pump["end_s"] + 1e-6, child
        picks = [s["attrs"] for s in new["serve.poll_pickup"]]
        assert sum(p["tokens"] for p in picks) == 9
        assert [p["first"] for p in picks] == [True] + [False] * (
            len(picks) - 1)
        assert all(0 <= p["pickup_ms"] < 5000 for p in picks)
        # an idle replica leaves the ring alone
        time.sleep(0.1)
        assert len(_ring("serve.pump")) == n0["serve.pump"] + 2
        # the capture holds the same spans on the profiler's clock
        evs = set(_profiled_events(str(tmp_path), ("serve.", "engine.")))
        assert {"serve.pump", "engine.prefill", "engine.readback",
                "serve.first_token", "serve.poll_pickup"} <= evs
    finally:
        srv.shutdown()


def test_trace_replicas_captures_device_and_spans_in_one_file(
        cluster, tmp_path):
    from ray_tpu.serve.llm_pool import LLMPool

    # (shapes of its own: a reused worker process that had run the other
    # tests' programs would compile nothing)
    pool = LLMPool(model_size="tiny", slots=2, max_len=88, chunk_tokens=3,
                   prompt_buckets=(8, 24), min_replicas=1, max_replicas=1,
                   prefill_workers=0, autoscale=False)
    try:
        pool.generate(list(range(1, 6)), 5)  # compile outside the capture
        import threading

        t = threading.Thread(
            target=lambda: [pool.generate(list(range(1, 7)), 9)
                            for _ in range(3)])
        t.start()
        dirs = pool.trace_replicas(str(tmp_path), 1.5)
        t.join(timeout=120)
        assert not t.is_alive() and len(dirs) == 1
        names = set(_profiled_events(dirs[0], ("serve.", "engine.")))
        assert {"serve.pump", "engine.readback", "engine.deliver",
                "serve.first_token"} <= names
    finally:
        pool.shutdown()
