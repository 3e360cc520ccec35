"""Unit regressions for the robustness satellites:

- AsyncRpcClient.fire's 32MB transport-buffer backstop (awaited drain);
- node agent read_object_chunk retryable {"busy"} refusal + the pull
  side's bounded backoff on it;
- autoscaler monitor exit-code contract (head-unreachable restartable);
- decode_chunk per-slot position clamp at the cache edge.
"""

import asyncio
import socket
import threading
import time
from types import SimpleNamespace

import numpy as np
import pytest

from ray_tpu._private import rpc


# ---------------------------------------------------------------------------
# rpc fire backstop
# ---------------------------------------------------------------------------


class _FakeTransport:
    def __init__(self):
        self.buffered = 0

    def get_write_buffer_size(self):
        return self.buffered


class _FakeWriter:
    def __init__(self, drain_gate: asyncio.Event):
        self.transport = _FakeTransport()
        self.writes = []
        self._gate = drain_gate

    def write(self, b):
        self.writes.append(b)

    async def drain(self):
        await self._gate.wait()

    def is_closing(self):
        return False


def test_async_fire_backstop_pauses_past_threshold():
    """Past FIRE_BUFFER_BACKSTOP buffered bytes the async fire path must
    stop writing to the transport and await a drain; queued fires flush
    once the buffer recedes — the wedged-peer buffer stays bounded."""

    async def run():
        cli = rpc.AsyncRpcClient("127.0.0.1", 1)
        gate = asyncio.Event()
        w = _FakeWriter(gate)
        cli._writer = w

        cli.fire("m", b"a")
        await asyncio.sleep(0)  # let the call_soon flush run
        assert len(w.writes) == 1

        # buffer jumps past the backstop: the next flush notices and
        # parks an awaited drain
        w.transport.buffered = rpc.FIRE_BUFFER_BACKSTOP + 1
        cli.fire("m", b"b")
        await asyncio.sleep(0)
        assert len(w.writes) == 2
        assert cli._fire_drain_task is not None

        # while draining, fires queue instead of hitting the transport
        cli.fire("m", b"c")
        cli.fire("m", b"d")
        await asyncio.sleep(0.05)
        assert len(w.writes) == 2
        assert len(cli._fire_out) == 2

        # buffer recedes -> drain completes -> backlog flushes (one
        # coalesced write)
        w.transport.buffered = 0
        gate.set()
        await asyncio.sleep(0.05)
        assert len(w.writes) == 3
        assert cli._fire_out == []

    asyncio.run(run())


def test_async_fire_backstop_writes_through_when_wedged(monkeypatch):
    """A peer wedged past the drain deadline still gets the queued
    frames (mirroring SyncRpcClient.fire's bounded WAIT): collective
    chunks must never be silently dropped to a slow-but-alive peer —
    and the next flush re-arms pacing while the buffer stays high."""

    async def run():
        cli = rpc.AsyncRpcClient("127.0.0.1", 1)
        gate = asyncio.Event()  # never set: wedged peer
        w = _FakeWriter(gate)
        cli._writer = w
        w.transport.buffered = rpc.FIRE_BUFFER_BACKSTOP + 1

        monkeypatch.setattr(rpc, "FIRE_DRAIN_TIMEOUT_S", 0.1)
        cli.fire("m", b"a")
        await asyncio.sleep(0)
        assert cli._fire_drain_task is not None
        cli.fire("m", b"backlogged")
        await asyncio.sleep(0.3)
        # backlog written through after the bounded wait, not dropped
        assert cli._fire_out == []
        assert len(w.writes) == 2
        # later fires keep making paced progress while the buffer stays
        # high: one write-through per drain window, never a drop
        cli.fire("m", b"c")
        await asyncio.sleep(0.3)
        assert cli._fire_out == []
        assert len(w.writes) == 3

    asyncio.run(run())


def test_peer_lost_evicts_cached_client():
    """A dead peer connection must be EVICTED from the worker's client
    cache when on_close fires: a reformed collective incarnation reusing
    the same (addr, port) must redial, not receive the closed client —
    keeping it would re-abort every fresh incarnation (livelock)."""
    from ray_tpu._private.worker import CoreWorker

    w = CoreWorker.__new__(CoreWorker)
    closed = []
    stale = SimpleNamespace(close=lambda: closed.append(True))
    key = ("10.0.0.7", 4321)
    w._peer_clients = {key: stale}
    seen = []
    w._peer_lost_listeners = [seen.append]
    w._notify_peer_lost(key)
    assert key not in w._peer_clients  # evicted before listeners ran
    assert closed == [True]
    assert seen == [key]


# ---------------------------------------------------------------------------
# read_object_chunk busy refusal + pull backoff
# ---------------------------------------------------------------------------


def _agent_shell():
    """A NodeAgent shell with only what the tested methods touch."""
    from ray_tpu.core.node_agent import NodeAgent

    return NodeAgent.__new__(NodeAgent)


def test_read_object_chunk_refuses_retryably_on_pacing_deadline():
    from ray_tpu.core import node_agent as na

    agent = _agent_shell()
    window = int(na.cfg.get("transfer_outbound_window_bytes"))

    class _Conn:
        state = {}

        class writer:
            class transport:
                @staticmethod
                def get_write_buffer_size():
                    return window + 1

                @staticmethod
                def set_write_buffer_limits(high=None, low=None):
                    _Conn.state["limits"] = (high, low)

            @staticmethod
            def is_closing():
                return False

        @staticmethod
        async def drain():
            raise asyncio.TimeoutError  # pacing deadline expired

    out = asyncio.run(na.NodeAgent.rpc_read_object_chunk(
        agent, _Conn, {"object_id": b"x", "offset": 0}))
    assert out == {"busy": True, "retry_after_s": 0.5}
    # the per-peer wakeup is transport-level: water marks set once per
    # connection (no 5ms poll loops) to the serve gate — ~2 chunks, so
    # responses stream from a small buffer instead of memmoving a
    # window-sized bytearray on every partial send
    gate = min(window, 2 * na._chunk_size())
    assert _Conn.state["limits"] == (gate, gate // 2)
    assert _Conn.state["paced"] is True


def test_read_object_chunk_serves_when_under_window():
    from ray_tpu.core import node_agent as na

    agent = _agent_shell()
    sentinel = {"total": 3, "meta": b"", "chunk": b"abc"}
    agent._read_object_chunk = lambda p, conn=None: sentinel

    class _Conn:
        state = {}

        class writer:
            class transport:
                @staticmethod
                def get_write_buffer_size():
                    return 0

    out = asyncio.run(na.NodeAgent.rpc_read_object_chunk(
        agent, _Conn, {"object_id": b"x", "offset": 0}))
    assert out is sentinel


def test_pull_backs_off_on_busy_then_succeeds():
    from ray_tpu.core import node_agent as na

    agent = _agent_shell()
    calls = []

    class _Cli:
        async def call(self, method, p):
            calls.append(p["offset"])
            if len(calls) < 3:
                return {"busy": True, "retry_after_s": 0.01}
            return {"total": 3, "meta": b"", "chunk": b"abc"}

    out = asyncio.run(na.NodeAgent._read_chunk_backoff(
        agent, _Cli(), b"oid", 0))
    assert out["chunk"] == b"abc"
    assert len(calls) == 3


def test_pull_gives_up_after_wall_clock_budget():
    from ray_tpu.core import node_agent as na

    agent = _agent_shell()
    n = [0]

    class _Cli:
        async def call(self, method, p):
            n[0] += 1
            return {"busy": True}

    t0 = time.monotonic()
    out = asyncio.run(na.NodeAgent._read_chunk_backoff(
        agent, _Cli(), b"oid", 0, budget_s=1.0))
    elapsed = time.monotonic() - t0
    assert out is None
    assert n[0] > 1           # it retried...
    assert elapsed < 10       # ...but gave up once the budget elapsed


# ---------------------------------------------------------------------------
# monitor exit-code contract
# ---------------------------------------------------------------------------


def test_run_monitor_head_unreachable_is_distinct_restartable_rc():
    from ray_tpu.autoscaler import monitor as mon

    # nothing listens on a fresh ephemeral port → connect fails fast
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    rc = mon.run_monitor(f"127.0.0.1:{port}", "no.such.module:Provider")
    assert rc == mon.RC_HEAD_UNREACHABLE
    assert rc not in (0, mon.RC_WIRING)


def test_run_monitor_broken_wiring_is_terminal_rc():
    from ray_tpu.autoscaler import monitor as mon

    # a bare listener accepts the head connection; the bogus provider
    # spec then fails construction → terminal wiring code
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(5)
    try:
        rc = mon.run_monitor(
            f"127.0.0.1:{srv.getsockname()[1]}",
            "no.such.module:Provider")
        assert rc == mon.RC_WIRING
    finally:
        srv.close()


def test_monitor_supervisor_restarts_head_unreachable(monkeypatch):
    """rc=RC_HEAD_UNREACHABLE must be restarted (with backoff) — a
    transient head outage can't permanently disable autoscaling."""
    from ray_tpu.autoscaler.monitor import (
        MonitorProcess,
        RC_HEAD_UNREACHABLE,
    )

    spawned = []

    class _Proc:
        def __init__(self):
            self.returncode = RC_HEAD_UNREACHABLE

        def poll(self):
            return self.returncode

    mon = MonitorProcess("127.0.0.1:1", "x:y")
    mon.RESTART_BACKOFF_S = 0.05
    monkeypatch.setattr(
        mon, "_spawn", lambda: spawned.append(1) or _Proc())
    mon.start()
    try:
        deadline = time.monotonic() + 10
        while mon.restarts < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert mon.restarts >= 2, "head-unreachable exits were not restarted"
    finally:
        mon.stop()


def test_monitor_supervisor_leaves_wiring_failures_dead(monkeypatch):
    from ray_tpu.autoscaler.monitor import MonitorProcess, RC_WIRING

    class _Proc:
        returncode = RC_WIRING

        def poll(self):
            return self.returncode

    mon = MonitorProcess("127.0.0.1:1", "x:y")
    monkeypatch.setattr(mon, "_spawn", lambda: _Proc())
    mon.start()
    try:
        mon._sup.join(timeout=10)
        assert not mon._sup.is_alive()  # supervisor gave up by design
        assert mon.restarts == 0
    finally:
        mon._stop.set()


# ---------------------------------------------------------------------------
# decode_chunk position clamp
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [4])
def test_decode_chunk_clamps_pos_at_cache_edge(chunk):
    """Slots that hit the cache edge mid-chunk keep pos pinned at
    max_len-1 (in-range scatters, exact finish check) instead of
    running past the cache."""
    jax = pytest.importorskip("jax")
    from ray_tpu.models import llama
    from ray_tpu.models.decode_engine import decode_chunk
    from ray_tpu.models.llama_slots import init_ragged_cache

    cfg = llama.LlamaConfig(
        vocab_size=64, d_model=16, n_layers=1, n_heads=2, n_kv_heads=2,
        d_ff=32, max_seq_len=8, dtype="float32", use_flash=False,
        remat=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    max_len = 8
    cache = init_ragged_cache(cfg, slots=2, max_len=max_len)
    # slot 0 is 2 rows from the edge; slot 1 frozen mid-cache
    cache["pos"] = jax.numpy.asarray(np.array([max_len - 2, 3], np.int32))
    tok = jax.numpy.zeros((2,), jax.numpy.int32)
    active = np.array([True, False])
    toks, _, cache, last = decode_chunk(params, cache, tok, active, None,
                                        cfg, chunk)
    pos = np.asarray(cache["pos"])
    assert pos[0] == max_len - 1, f"pos ran past the cache edge: {pos}"
    assert pos[1] == 3  # frozen slot untouched
    assert np.asarray(toks).shape == (2, chunk)
