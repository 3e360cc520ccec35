"""Direct-task lease caching (reference direct_task_transport.h:110:
lease a granted worker per SchedulingKey, push repeat tasks straight to
it, return on idle TTL; worker death falls back to queued retry)."""

import os
import time

import pytest

import ray_tpu
from ray_tpu._private import api as _api
from ray_tpu._private import config as cfg
from ray_tpu.cluster_utils import Cluster


_cluster_ref = None


@pytest.fixture(scope="module")
def cluster():
    global _cluster_ref
    c = Cluster(head_resources={"CPU": 4, "memory": 4 * 2**30})
    c.connect()
    _cluster_ref = c
    yield c
    c.shutdown()


def _agent():
    return _cluster_ref.head_agent


@ray_tpu.remote
def _pid():
    return os.getpid()


def _warm():
    """Tasks until the agent has granted the lease. The first ask is
    refused while the pool's worker starts, and the owner asks again
    only 0.2 s later: a worker that starts from cached bytecode answers
    the first task in 0.19 s, and the tasks behind it in a millisecond
    each, all of them queued (PR 66; from source a start took 2 s and
    the second task always found the lease)."""
    deadline = time.time() + 30
    while True:
        ray_tpu.get(_pid.remote(), timeout=60)
        if _agent().leases or time.time() > deadline:
            return
        time.sleep(0.05)


def test_repeat_tasks_ride_one_lease(cluster):
    _warm()
    pids = [ray_tpu.get(_pid.remote(), timeout=60) for _ in range(10)]
    # sequential same-shape tasks ride the cached lease; a rare re-grant
    # (e.g. a renew racing the TTL) may switch workers once
    dominant = max(pids.count(p) for p in set(pids))
    assert dominant >= 9, f"lease reuse broken: {pids}"
    assert len(_agent().leases) >= 1
    w = _api._get_worker()
    assert len(w._lease_cache) >= 1


def test_lease_expires_and_frees_resources(cluster):
    _warm()
    agent = _agent()
    assert agent.leases
    deadline = time.time() + cfg.get("worker_lease_ttl_s") + 10
    while time.time() < deadline and agent.leases:
        time.sleep(0.5)
    assert not agent.leases, "lease never expired"
    # resources back in the pool
    assert agent.resources_available.get("CPU") == \
        agent.resources_total.get("CPU")


def test_parallel_burst_mixes_lease_and_queue(cluster):
    @ray_tpu.remote
    def slow(i):
        time.sleep(0.2)
        return i

    out = ray_tpu.get([slow.remote(i) for i in range(8)], timeout=120)
    assert out == list(range(8))


def test_leased_worker_death_retries(cluster, tmp_path):
    marker = tmp_path / "died_once"

    @ray_tpu.remote(max_retries=2)
    def fragile():
        import os as _os

        if not marker.exists():
            marker.write_text("x")
            _os._exit(1)  # die mid-task on the leased worker
        return "recovered"

    ray_tpu.get(_pid.remote(), timeout=60)  # warm a lease
    assert ray_tpu.get(fragile.remote(), timeout=120) == "recovered"


def test_lease_skips_pg_and_strategy_tasks(cluster):
    w = _api._get_worker()
    spec = {"pg_id": b"x", "resources": {"CPU": 1}}
    assert w._lease_key(spec) is None
    assert w._lease_key({"scheduling_strategy": "SPREAD"}) is None
    assert w._lease_key({"resources": {"CPU": 1}, "deps": []}) is not None
