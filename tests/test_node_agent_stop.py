"""``NodeAgent.stop`` returns once the workers it gave chips to are GONE.

A dead worker keeps its chips while the kernel closes them (13-21 s for
four on the chip's host: ``PERF.md`` section 7(e)); a job started on the
host straight after would die of a busy device node. No chip is needed:
the workers are fakes in the agent's own tables.
"""

import subprocess
import threading
import time

import pytest

from ray_tpu._private import accelerator, flight_recorder
from ray_tpu._private.api import LocalCluster
from ray_tpu.core.node_agent import WorkerHandle


class DyingWorker:
    """A ``Popen`` whose process outlives its first signal (SIGKILL
    too: it is in exit, closing its files) by ``linger`` seconds."""

    def __init__(self, pid: int, linger: float):
        self.pid, self.linger = pid, linger
        self.returncode = None
        self.signals = []
        self._gone = threading.Event()

    def terminate(self):
        self.signals.append("TERM")
        timer = threading.Timer(self.linger, self._gone.set)
        timer.daemon = True
        timer.start()

    def kill(self):
        self.signals.append("KILL")

    def poll(self):
        if self._gone.is_set():
            self.returncode = -9
        return self.returncode

    def wait(self, timeout=None):
        if not self._gone.wait(timeout):
            raise subprocess.TimeoutExpired("worker", timeout)
        return self.poll()


@pytest.fixture
def cluster(monkeypatch):
    marks = []
    monkeypatch.setattr(
        flight_recorder, "mark",
        lambda kind, name, attrs=None, **kw: marks.append(
            (kind, name, attrs)))
    cluster = LocalCluster(resources={"CPU": 1.0, "TPU": 2.0},
                           store_capacity=16 * 2**20)
    stopped = []

    def stop():
        if not stopped:
            stopped.append(cluster.stop())

    try:
        yield cluster, marks, stop
    finally:
        stop()


def _adopt(agent, proc, chips=()):
    handle = WorkerHandle(bytes([proc.pid % 256]) * 16, proc)
    handle.chips = tuple(chips)
    agent.workers[handle.worker_id] = handle
    for c in chips:
        agent._chip_procs[c] = proc


def test_stop_returns_only_after_a_chip_granted_worker_is_reaped(
        cluster, capsys):
    """A worker that holds chip 1 outlives its SIGTERM by 1 s: ``stop``
    signals it, then waits until it is reaped, and says so; the
    grantless worker beside it, which lingers for 30 s, is signalled
    and not waited for."""
    cluster, marks, stop = cluster
    granted, grantless = DyingWorker(101, 1.0), DyingWorker(102, 30.0)
    _adopt(cluster.agent, granted, chips=(1,))
    _adopt(cluster.agent, grantless)
    t0 = time.monotonic()
    stop()
    took = time.monotonic() - t0
    assert 1.0 <= took < 3.0
    assert granted.returncode == -9 and granted.signals == ["TERM"]
    assert grantless.returncode is None and grantless.signals == ["TERM"]
    (kind, name, attrs), = marks
    assert (kind, name) == ("accel", "chip_wait")
    assert attrs["leaving"] is True and attrs["waited_ms"] >= 900
    assert attrs["nodes"] == "chip1"  # (this box has no device node)
    assert "leaving=True" in capsys.readouterr().err


def test_stop_waits_for_nobody_where_no_worker_holds_a_chip(cluster):
    """Chips nobody was given, a worker that was reaped long ago and a
    grantless one that lingers: nothing to wait for, nothing said."""
    cluster, marks, stop = cluster
    reaped = DyingWorker(103, 0.0)
    reaped._gone.set()
    reaped.poll()
    cluster.agent._chip_procs[0] = reaped
    _adopt(cluster.agent, DyingWorker(104, 30.0))
    t0 = time.monotonic()
    stop()
    assert time.monotonic() - t0 < 1.0
    assert marks == []


def test_a_worker_that_never_lets_go_bounds_the_stop(cluster, monkeypatch):
    """SIGTERM was not enough: SIGKILL after the grace, and past the
    bound ``stop`` gives up (and warns) instead of hanging the
    shutdown."""
    cluster, marks, stop = cluster
    monkeypatch.setattr(accelerator, "CHIP_WAIT_S", 0.5)
    worker = DyingWorker(105, 30.0)
    _adopt(cluster.agent, worker, chips=(0, 1))
    t0 = time.monotonic()
    stop()
    assert 0.5 <= time.monotonic() - t0 < 2.5
    assert worker.signals == ["TERM", "KILL"] and worker.returncode is None
    assert marks[0][2]["nodes"] == "chip0,chip1"
