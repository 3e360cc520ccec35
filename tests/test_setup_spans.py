"""A replica's bring-up in spans (ISSUE-56).

- ``accelerator``'s compile tally is a thread's own: a caller reads
  ``compile_mark()`` before a call and owns what ``compile_since`` finds
  after it, nested stages counted once, a cache read apart from a
  compile;
- ``serve.replica_start`` holds the claim, the weights' build, the cast
  and the engine's state on one thread;
- the engine leaves one ``engine.compiled`` a program's first call and
  none after it;
- ``stats()["setup"]`` and the ``serve.setup`` mark of a capture are one
  record.

One tiny ``LLMServer`` on the CPU, in this process.
"""

import glob
import os
import threading
import time

import pytest

from ray_tpu._private import accelerator
from ray_tpu._private import flight_recorder as fr

SIX = {"requests", "hits", "trace_ms", "lower_ms", "compile_ms",
       "cache_read_ms"}
STAGES = ("trace_ms", "lower_ms", "compile_ms", "cache_read_ms")
CHILDREN = ("serve.claim_device", "serve.weights_build",
            "serve.weights_cast", "engine.state_init")


def _ring(name, engine=None):
    return [s for s in fr._get().ring if s["name"] == name
            and (engine is None or s["attrs"].get("engine") == engine)]


def _lines(log_dir):
    """[[(name, start_ns, dur_ns, stats)] a host thread] of a capture."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [[(ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
             for ev in line.events
             if ev.name.startswith(("serve.", "engine."))]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines]


# ---------------------------------------------------------------------------
# the compile tally
# ---------------------------------------------------------------------------


def _fresh_jit(sleep_s=0.0, inner=None):
    """A jitted function nobody has called: its first call traces,
    lowers and compiles (the sleep is trace time)."""
    import jax

    def body(x):
        time.sleep(sleep_s)
        return (inner(x) if inner else x) * 2 + 1

    return jax.jit(body)


@pytest.fixture(scope="module")
def x():
    import jax.numpy as jnp

    accelerator.claim_device()  # the listeners
    return jnp.ones((5,)).block_until_ready()


def _tally_own_call(x):
    mark = accelerator.compile_mark()
    t0 = time.time()
    _fresh_jit(0.05)(x)
    call_ms = 1e3 * (time.time() - t0)
    found = accelerator.compile_since(mark)
    assert set(found) == SIX
    assert found["trace_ms"] >= 50 and found["compile_ms"] > 0
    assert sum(found[k] for k in STAGES) <= call_ms
    began = accelerator.compile_began(mark)
    assert t0 <= began <= t0 + 0.5  # the trace began with the call
    # a second call of a function that is compiled moves nothing
    f = _fresh_jit()
    f(x)
    mark = accelerator.compile_mark()
    f(x)
    assert accelerator.compile_mark() == mark
    assert accelerator.compile_since(mark) is None
    assert accelerator.compile_began(mark) is None


def _tally_nested_once(x):
    # a jitted body traced inside its caller's trace fires its own
    # event there: 50 ms of the outer's 100 are the inner's, and the
    # sum must not hold them twice
    mark = accelerator.compile_mark()
    t0 = time.time()
    _fresh_jit(0.05, inner=_fresh_jit(0.05))(x)
    call_ms = 1e3 * (time.time() - t0)
    found = accelerator.compile_since(mark)
    assert 100 <= found["trace_ms"] <= call_ms
    assert sum(found[k] for k in STAGES) <= call_ms


def _tally_other_thread(x):
    # another thread compiles while this one is in a "call": nothing of
    # it lands here, all of it there
    theirs = {}

    def other():
        m = accelerator.compile_mark()
        _fresh_jit(0.02)(x)
        theirs.update(accelerator.compile_since(m))

    mark = accelerator.compile_mark()
    th = threading.Thread(target=other)
    th.start()
    th.join()
    assert accelerator.compile_mark() == mark
    assert accelerator.compile_since(mark) is None
    assert theirs["trace_ms"] >= 20 and theirs["compile_ms"] > 0


def _tally_old_keys(x):
    # device_report()["compile"]: the four old keys keep their meaning
    # (every cache request and hit, every backend stage's seconds), the
    # three new ones join them
    before = accelerator.device_report()["compile"]
    mark = accelerator.compile_mark()
    _fresh_jit(0.02)(x)
    found = accelerator.compile_since(mark)
    after = accelerator.device_report()["compile"]
    assert set(after) == {"dir", "requests", "hits", "seconds",
                          "trace_seconds", "lower_seconds",
                          "cache_read_seconds"}
    assert after["dir"] == before["dir"]
    assert after["requests"] - before["requests"] == found["requests"]
    assert after["hits"] - before["hits"] == found["hits"]
    grew = 1e3 * (after["seconds"] - before["seconds"])
    assert grew == pytest.approx(
        found["compile_ms"] + found["cache_read_ms"], abs=2.0)
    assert 1e3 * (after["trace_seconds"] - before["trace_seconds"]) \
        == pytest.approx(found["trace_ms"], abs=2.0)


def _tally_cache_read(x, tmp_path):
    # a persistent-cache hit: its read is ``cache_read_ms`` and no part
    # of ``compile_ms``, though jax's backend stage holds both
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        for k, v in zip(keys, (True, str(tmp_path), 0, -1)):
            jax.config.update(k, v)
        cc.reset_cache()
        runs = []
        for _ in range(2):
            before = accelerator.compile_report()["seconds"]
            mark = accelerator.compile_mark()
            # (one text, so one cache key; a new function object, so a
            # first call each time)
            jax.jit(lambda v: v * 7 + 3)(x)
            runs.append((accelerator.compile_since(mark),
                         accelerator.compile_report()["seconds"] - before))
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()
    (cold, _), (warm, backend_s) = runs
    assert (cold["requests"], cold["hits"]) == (1, 0)
    assert cold["cache_read_ms"] == 0 and cold["compile_ms"] > 0
    assert (warm["requests"], warm["hits"]) == (1, 1)
    assert warm["cache_read_ms"] > 0 and warm["compile_ms"] >= 0
    assert warm["compile_ms"] + warm["cache_read_ms"] \
        == pytest.approx(1e3 * backend_s, abs=1.5)
    assert warm["trace_ms"] > 0  # Python does not cache


@pytest.mark.parametrize("fact", [
    _tally_own_call, _tally_nested_once, _tally_other_thread,
    _tally_old_keys, _tally_cache_read], ids=lambda f: f.__name__[7:])
def test_compile_tally(fact, x, tmp_path):
    if fact is _tally_cache_read:
        fact(x, tmp_path)
    else:
        fact(x)


def test_a_tally_outlives_its_trimmed_log(x):
    """The log is bounded: a mark from before a trim finds what is
    left, a mark from after it what it owns."""
    t = accelerator.tally
    old = accelerator.compile_mark()
    for _ in range(2 * accelerator._TALLY_KEEP + 10):
        t.add(accelerator._TRACE, 0.001)
    assert len(t.log) <= 2 * accelerator._TALLY_KEEP
    mark = accelerator.compile_mark()
    t.add(accelerator._TRACE, 0.5)
    assert accelerator.compile_since(mark)["trace_ms"] == 500.0
    assert accelerator.compile_since(old)["trace_ms"] >= 500.0


# ---------------------------------------------------------------------------
# the replica: bring-up, first calls, the record
# ---------------------------------------------------------------------------

ENGINE = "t56-replica"


@pytest.fixture(scope="module")
def replica(tmp_path_factory):
    """A tiny replica brought up under a capture, then three requests
    (a bucket's first, its second, a new bucket's first) and a capture
    through the product's own hook; what each left in the ring."""
    import jax

    from ray_tpu.serve.llm import LLMServer

    flushed, record = {}, fr.record

    def spy(kind, name, *args, flush=True, **kw):
        flushed[name] = flush
        return record(kind, name, *args, flush=flush, **kw)

    fr.record = spy  # (span() and mark() end in it)
    boot_dir = str(tmp_path_factory.mktemp("boot"))
    jax.profiler.start_trace(boot_dir)
    try:
        # (shapes no other test file runs: a worker process that had
        # these programs already would compile nothing)
        srv = LLMServer("tiny", slots=3, max_len=88, chunk_tokens=3,
                        prompt_buckets=(8, 24), engine_name=ENGINE)
    finally:
        jax.profiler.stop_trace()
    out = {"srv": srv, "boot_dir": boot_dir, "marks": [],
           "flushed": flushed}
    try:
        for prompt in (range(1, 6), range(1, 6), range(1, 12)):
            n = len(_ring("engine.compiled", ENGINE))
            srv.generate(list(prompt), 5)
            out["marks"].append(
                [s["attrs"] for s in _ring("engine.compiled", ENGINE)[n:]])
        cap_dir = str(tmp_path_factory.mktemp("capture"))
        srv.start_trace(cap_dir)
        out["stats"] = srv.stats()
        srv.stop_trace()
        out["cap_dir"] = cap_dir
        fr.record = record
        yield out
    finally:
        fr.record = record
        srv.shutdown(drain_s=5.0)


def _start_children_in_order(rep):
    # in the capture of the bring-up: parent and children on ONE
    # thread's line, each child inside the parent, in this order
    (line,) = [ln for ln in _lines(rep["boot_dir"])
               if any(ev[0] == "serve.replica_start" for ev in ln)]
    (parent,) = [ev for ev in line if ev[0] == "serve.replica_start"]
    kids = sorted((ev for ev in line if ev[0] in CHILDREN),
                  key=lambda ev: ev[1])
    assert tuple(ev[0] for ev in kids) == CHILDREN
    for _, start, dur, _ in kids:
        assert parent[1] <= start and start + dur <= parent[1] + parent[2]
    for a, b in zip(kids, kids[1:]):
        assert a[1] + a[2] <= b[1]  # one after the other


def _start_parts_sum(rep):
    ring = {n: _ring(n)[-1] if n != "serve.replica_start"
            else _ring(n, ENGINE)[-1]
            for n in CHILDREN[:3] + ("serve.replica_start",)}
    dur = {n: s["end_s"] - s["start_s"] for n, s in ring.items()}
    assert sum(dur[n] for n in CHILDREN[:3]) <= dur["serve.replica_start"]
    rec = rep["stats"]["setup"]
    assert rec["claim_ms"] + rec["weights_build_ms"] \
        + rec["weights_cast_ms"] <= rec["replica_start_ms"]
    assert rec["replica_start_ms"] == pytest.approx(
        1e3 * dur["serve.replica_start"], abs=5.0)
    assert rec["ready_mono_ns"] - rec["init_mono_ns"] == pytest.approx(
        1e6 * rec["replica_start_ms"], rel=1e-3)


def _start_attrs(rep):
    claim = _ring("serve.claim_device")[-1]["attrs"]
    assert claim == {"waited_ms": 0.0, "platform": "cpu",
                     "count": claim["count"]} and claim["count"] >= 1
    build = _ring("serve.weights_build")[-1]["attrs"]
    assert build["bytes"] == rep["stats"]["weights_bytes"] > 0  # f32 tiny
    # the tally's difference where the draw compiled on this thread
    # (all six), nothing where an earlier test's process had its programs
    assert set(build) - {"bytes"} in (SIX, set())
    start = _ring("serve.replica_start", ENGINE)[-1]["attrs"]
    # the process is older than the replica: imports, this test's own
    assert start["process_age_ms"] > 100.0


def _start_flushed(rep):
    # once a replica's life: for ray_tpu.timeline(), not ring-only
    flushed = rep["flushed"]
    assert all(flushed[n] for n in (
        "serve.replica_start", "serve.claim_device", "serve.weights_build",
        "engine.compiled"))
    assert not flushed["serve.setup"] and not flushed["serve.weights_cast"]


@pytest.mark.parametrize("fact", [
    _start_children_in_order, _start_parts_sum, _start_attrs,
    _start_flushed], ids=lambda f: f.__name__[7:])
def test_replica_start(fact, replica):
    fact(replica)


def _marks_first_request(marks):
    # a bucket's first request: the prefill program's and the chunk's
    assert sorted((a["program"], a["bucket"]) for a in marks[0]) == [
        ("jit__prefill_batch_into_slots", 8), ("jit_decode_chunk", 0)]


def _marks_second_request(marks):
    assert marks[1] == []  # nothing compiled: no mark


def _marks_new_bucket(marks):
    # a bucket the replica has not run (what a compile inside a
    # benchmark's window is): one mark, naming it
    (a,) = marks[2]
    assert (a["program"], a["bucket"]) == (
        "jit__prefill_batch_into_slots", 24)


def _marks_nothing_twice(marks):
    for a in marks[0] + marks[2]:
        assert SIX | {"engine", "program", "bucket", "call_ms",
                      "since_ready_ms"} == set(a)
        parts = sum(a[k] for k in STAGES)
        # nothing counted twice, nothing large left out
        assert 0.8 * a["call_ms"] <= parts <= a["call_ms"]
        assert a["since_ready_ms"] > 0 and a["engine"] == ENGINE
    ready = [a["since_ready_ms"] for a in marks[0] + marks[2]]
    assert ready == sorted(ready)


@pytest.mark.parametrize("fact", [
    _marks_first_request, _marks_second_request, _marks_new_bucket,
    _marks_nothing_twice], ids=lambda f: f.__name__[7:])
def test_engine_compiled_marks(fact, replica):
    fact(replica["marks"])


def _record_sums_the_marks(rep):
    rec, marks = rep["stats"]["setup"], sum(rep["marks"], [])
    assert rec["first_calls"] == len(marks) == 3
    for k in STAGES:
        assert rec[k] == pytest.approx(sum(a[k] for a in marks), abs=0.01)
    assert rec["first_call_ms"] == pytest.approx(
        sum(a["call_ms"] for a in marks), abs=0.01)
    assert rec["compile_requests"] == sum(a["requests"] for a in marks)
    assert rec["cache_hits"] == sum(a["hits"] for a in marks)
    assert rec["last_compile_mono_ns"] > rec["ready_mono_ns"]
    proc = rep["stats"]["device"]["compile"]
    assert rec["proc_compile_requests"] == proc["requests"]
    assert rec["proc_cache_hits"] == proc["hits"]


def _record_is_the_mark(rep):
    # under the capture: engine.state_init's neighbour, ring-only, the
    # same keys as stats()["setup"], every value a plain number
    rec = rep["stats"]["setup"]
    assert set(rec) == {
        "process_age_ms", "claim_ms", "chip_wait_ms", "weights_build_ms",
        "weights_cast_ms", "replica_start_ms", "first_calls", "trace_ms",
        "lower_ms", "compile_ms", "cache_read_ms", "first_call_ms",
        "compile_requests", "cache_hits", "proc_compile_requests",
        "proc_cache_hits", "init_mono_ns", "ready_mono_ns",
        "last_compile_mono_ns"}
    assert all(type(v) in (int, float) for v in rec.values())
    evs = [ev for ln in _lines(rep["cap_dir"]) for ev in ln
           if ev[0] in ("serve.setup", "engine.state_init")]
    assert sorted(ev[0] for ev in evs) == ["engine.state_init",
                                           "serve.setup"]
    (stats,) = [ev[3] for ev in evs if ev[0] == "serve.setup"]
    assert stats == pytest.approx(rec)
    assert _ring("serve.setup")[-1]["attrs"] == pytest.approx(rec)


@pytest.mark.parametrize("fact", [
    _record_sums_the_marks, _record_is_the_mark],
    ids=lambda f: f.__name__[8:])
def test_setup_record(fact, replica):
    fact(replica)


def test_a_weight_publish_keeps_the_starts_cast(replica):
    """``weights_cast_ms`` of the record is the bring-up's: a publish
    moves the engine's own figure and not the record's."""
    import jax

    from ray_tpu.models import llama

    srv = replica["srv"]
    was = srv.setup_record()["weights_cast_ms"]
    tree = jax.device_get(llama.init_params(srv.engine.cfg,
                                            jax.random.PRNGKey(3)))
    srv.update_weights(tree, 1)
    srv.generate(list(range(1, 6)), 3)  # the pump adopts it first
    assert srv.weights_version() == 1
    assert srv.setup_record()["weights_cast_ms"] == was
