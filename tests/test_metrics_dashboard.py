"""Metrics API + dashboard head.

Reference test models: python/ray/tests/test_metrics_agent.py,
dashboard/tests — user metrics flow process -> head -> Prometheus text;
dashboard endpoints serve live cluster state.
"""

import http.client
import json
import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util.metrics import Counter, Gauge, Histogram, flush_once


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(head_resources={"CPU": 4, "memory": 4 * 2**30})
    c.connect()
    yield c
    c.shutdown()


def _get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=60)
    conn.request("GET", path)
    r = conn.getresponse()
    body = r.read()
    conn.close()
    return r.status, body


def test_metric_types_validate():
    with pytest.raises(ValueError):
        Histogram("h_bad", boundaries=[])
    with pytest.raises(ValueError):
        Histogram("h_bad2", boundaries=[5, 1])
    c = Counter("c_neg")
    with pytest.raises(ValueError):
        c.inc(-1)


def test_metrics_flow_to_head(cluster):
    c = Counter("test_requests_total", description="reqs",
                tag_keys=("route",))
    g = Gauge("test_queue_depth")
    h = Histogram("test_latency_s", boundaries=[0.1, 1.0])
    c.inc(3, tags={"route": "/a"})
    c.inc(2, tags={"route": "/b"})
    g.set(7)
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    flush_once()
    w = ray_tpu._private.api._get_worker()
    rows = w.head.call("get_metrics", {})
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    assert sum(r["value"] for r in by_name["test_requests_total"]) == 5
    assert any(r["value"] == 7 for r in by_name["test_queue_depth"])
    lat = {tuple(map(tuple, r["tags"])): r["value"]
           for r in by_name["test_latency_s"]}
    assert lat[(("le", "0.1"),)] == 1
    assert lat[(("le", "1.0"),)] == 2
    assert lat[(("le", "+Inf"),)] == 3


def test_metrics_from_remote_task(cluster):
    @ray_tpu.remote
    def emit():
        from ray_tpu.util.metrics import Counter, flush_once

        Counter("task_side_metric").inc(11)
        flush_once()
        return True

    assert ray_tpu.get(emit.remote())
    w = ray_tpu._private.api._get_worker()
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        rows = w.head.call("get_metrics", {})
        vals = [r["value"] for r in rows if r["name"] == "task_side_metric"]
        if vals == [11]:
            return
        time.sleep(0.2)
    raise AssertionError(f"task metric never arrived: {rows}")


def test_stop_dashboard_ends_the_heads_threads(cluster):
    """A head that is stopped leaves no thread behind (its loop's, and
    the pool's that served its requests) and answers nobody."""
    import threading

    from ray_tpu.dashboard import start_dashboard, stop_dashboard

    def loops_and_pools():  # (the cluster's own loops have theirs too)
        return {t for t in threading.enumerate()
                if t.name == "ray_tpu-dashboard"
                or t.name.startswith("asyncio_")}

    before = loops_and_pools()
    addr = start_dashboard()
    assert _get(addr, "/api/cluster")[0] == 200
    started = loops_and_pools() - before
    assert len(started) >= 2, started  # the loop and one of its pool
    stop_dashboard()
    assert not [t for t in started if t.is_alive()]
    with pytest.raises(OSError):
        _get(addr, "/api/cluster")


def test_dashboard_endpoints(cluster):
    from ray_tpu.dashboard import start_dashboard

    Counter("dash_metric").inc(4)
    flush_once()
    addr = start_dashboard()

    status, body = _get(addr, "/api/cluster")
    assert status == 200
    summary = json.loads(body)
    assert summary["nodes_alive"] >= 1
    assert summary["cpus_total"] >= 4

    status, body = _get(addr, "/api/nodes")
    nodes = json.loads(body)
    assert status == 200 and len(nodes) >= 1
    # reporter stats ride heartbeats; wait for one carrying psutil stats
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        nodes = json.loads(_get(addr, "/api/nodes")[1])
        if any("mem_total" in (n.get("stats") or {}) for n in nodes):
            break
        time.sleep(0.5)
    assert any("mem_total" in (n.get("stats") or {}) for n in nodes)

    status, body = _get(addr, "/api/actors")
    assert status == 200

    status, body = _get(addr, "/metrics")
    text = body.decode()
    assert status == 200
    assert "ray_tpu_cluster_nodes_alive" in text
    assert "dash_metric 4" in text or "dash_metric" in text

    status, body = _get(addr, "/api/nope")
    assert status == 404


def test_slo_speculation_acceptance_block(cluster):
    """ISSUE-19 satellite: /api/slo aggregates the speculative-decode
    counter pair into a per-engine acceptance block, so an operator can
    see whether the draft model is earning its verify cost."""
    from ray_tpu.dashboard import start_dashboard

    Counter("decode_engine_spec_proposed_total",
            tag_keys=("engine",)).inc(40, {"engine": "decode-9"})
    Counter("decode_engine_spec_accepted_total",
            tag_keys=("engine",)).inc(25, {"engine": "decode-9"})
    flush_once()
    addr = start_dashboard()
    deadline = time.monotonic() + 15
    spec = {}
    while time.monotonic() < deadline:
        status, body = _get(addr, "/api/slo")
        assert status == 200
        spec = json.loads(body).get("speculation", {})
        if "decode-9" in spec:
            break
        time.sleep(0.2)
    ent = spec["decode-9"]
    assert ent["proposed"] >= 40 and ent["accepted"] >= 25
    assert 0.0 < ent["acceptance_rate"] <= 1.0


def test_dashboard_stacks(cluster):
    from ray_tpu.dashboard import start_dashboard

    @ray_tpu.remote
    def parked():
        time.sleep(8)
        return True

    ref = parked.remote()
    time.sleep(1.0)  # let it start
    addr = start_dashboard()
    status, body = _get(addr, "/api/stacks")
    assert status == 200
    dumps = json.loads(body)
    text = json.dumps(dumps)
    assert "parked" in text or "time.sleep" in text
    ray_tpu.get(ref, timeout=30)


def test_dashboard_logs(cluster):
    """Per-worker log files + /api/logs listing and tailing (reference
    log_monitor + dashboard/modules/log)."""
    from ray_tpu.dashboard import start_dashboard

    @ray_tpu.remote
    def chatty():
        print("hello-from-worker-stdout")
        import sys

        print("warn-on-stderr", file=sys.stderr)
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=60) == 1
    addr = start_dashboard()

    # listing: at least one node exposes worker-*.out files
    deadline = time.monotonic() + 20
    listing = []
    while time.monotonic() < deadline:
        status, body = _get(addr, "/api/logs")
        assert status == 200
        listing = json.loads(body)
        files = [f for n in listing for f in n["files"]
                 if isinstance(f, dict)]
        if any(f["file"].endswith(".out") and f["bytes"] > 0
               for f in files):
            break
        time.sleep(0.3)
    node = next(n for n in listing
                if any(isinstance(f, dict) and f["file"].endswith(".out")
                       and f["bytes"] > 0 for f in n["files"]))
    # find the file containing our line (several pool workers may exist)
    found = False
    for f in node["files"]:
        if not f["file"].endswith(".out"):
            continue
        status, body = _get(
            addr, f"/api/logs?node_id={node['node_id']}&file={f['file']}")
        assert status == 200
        tail = json.loads(body)
        if "hello-from-worker-stdout" in tail["data"]:
            found = True
            break
    assert found, "worker stdout line not served via /api/logs"


def test_dashboard_profile(cluster):
    """On-demand statistical CPU profiling across workers (reference
    reporter_agent CpuProfiling / py-spy analog)."""
    from ray_tpu.dashboard import start_dashboard

    @ray_tpu.remote
    def started():
        return True

    @ray_tpu.remote
    def burn():
        import time as t

        end = t.time() + 8.0
        x = 0
        while t.time() < end:
            x += 1
        return x

    # readiness: a task completing means a worker exists and the queue
    # has drained to `burn` — the sample window then overlaps it
    ray_tpu.get(started.remote(), timeout=60)
    ref = burn.remote()
    time.sleep(0.5)  # let burn dispatch
    addr = start_dashboard()
    status, body = _get(addr, "/api/profile?duration=1.5")
    assert status == 200
    nodes = json.loads(body)
    samples = {}
    for n in nodes:
        for w in n.get("workers", []):
            samples.update(w.get("samples", {}))
    assert samples, "no profile samples collected"
    # the busy loop shows up in some collapsed stack
    assert any("burn" in k for k in samples), list(samples)[:3]
    assert ray_tpu.get(ref, timeout=60) > 0
