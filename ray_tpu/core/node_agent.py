"""Node agent: per-node data plane (raylet equivalent, SURVEY.md §2.3).

Composes, like the reference NodeManager (`node_manager.h:115`):
- WorkerPool        — worker process lifecycle, reuse, idle cull
                      (worker_pool.h:156); hands out TPU chips at spawn
                      (_private/accelerator.py: a granted worker sees
                      its chips, every other worker is pinned to the
                      CPU); chip holders are never idle-culled (device
                      init + compiled programs are expensive to recreate).
- ClusterTaskManager— local-vs-spill decision from the synced cluster view
                      (cluster_task_manager.h:42); hybrid policy: prefer
                      local while resources fit, else best remote node.
- LocalTaskManager  — dependency staging → resource grant → dispatch to a
                      leased worker (local_task_manager.h:58).
- ObjectManager     — owns the node's shm store segment; chunked pulls from
                      peer agents (object_manager.h:117 push/pull).
- PlacementGroupResourceManager — 2-phase bundle PREPARE/COMMIT
                      (placement_group_resource_manager.h).
- MemoryMonitor     — node OOM watcher killing newest worker
                      (memory_monitor.h:52).

Resources are a flat {name: float} map; TPU chips appear as "TPU" plus
slice-topology labels ("tpu-slice:v5e-8": 1) so gang placement can target
whole ICI domains.
"""

from __future__ import annotations

import asyncio
import logging
import os
import subprocess
import sys
import tempfile
import time
from collections import deque
from typing import Any

from ray_tpu._private import accelerator
from ray_tpu._private import config as cfg
from ray_tpu._private import fault_injection, rpc, task_spec
from ray_tpu._private.rpc import AsyncRpcClient, OobReply, RpcServer
from ray_tpu.core import pull_manager
from ray_tpu.core.object_store import (
    ObjectExistsError,
    ObjectStoreClient,
    StoreFullError,
)

logger = logging.getLogger(__name__)

# Tunables ride the central flag system (ray_config_def.h analog); env
# RAY_TPU_<NAME> overrides each.
IDLE_CULL_S = cfg.get("idle_worker_cull_s")
SPILL_MAX = cfg.get("task_spill_max_forwards")
DEP_LOST_S = cfg.get("dep_lost_reconstruct_s")

# Cached serve-side object pins idle longer than this are dropped (an
# abandoned mid-transfer puller must not pin store memory forever; a
# striped pull's non-tail sources also land here, so the TTL is short —
# a live transfer re-requests within milliseconds, never seconds).
SERVE_PIN_TTL_S = 10.0


def _chunk_size() -> int:
    """Transfer chunk size, read per use (not import time) so tests and
    `set_system_config` can resize it on a live process."""
    return int(cfg.get("object_transfer_chunk_bytes"))


def _part_chunk(part: dict):
    """Chunk bytes of a read_object_chunk reply: out-of-band framed
    ("oob", the zero-copy path) or inline ("chunk", legacy/local)."""
    oob = part.get("oob")
    if oob:
        return oob[0]
    return part.get("chunk", b"")


def _owner_label(owner) -> str:
    """Byte-attribution owner tag from a directory entry's
    owner_address dict (the tenant that created the object)."""
    if isinstance(owner, dict) and owner.get("worker_id"):
        try:
            return owner["worker_id"].hex()[:12]
        except (AttributeError, TypeError):
            pass
    return "unknown"


_xfer_metrics: dict | None = None


def _transfer_metrics() -> dict:
    global _xfer_metrics
    if _xfer_metrics is None:
        from ray_tpu.util import metrics as M

        _xfer_metrics = {
            "bytes": M.Counter(
                "object_transfer_pull_bytes_total",
                "bytes pulled from peer object stores"),
            "inflight_peak": M.Gauge(
                "object_transfer_pull_inflight_peak",
                "peak concurrent chunk requests of the latest pull"),
        }
    return _xfer_metrics


def detect_resources() -> dict:
    import psutil

    res = {"CPU": float(os.cpu_count() or 1),
           "memory": float(psutil.virtual_memory().total)}
    chips = accelerator.detect_tpu_chips()
    if chips:
        res["TPU"] = float(chips)
        topo = os.environ.get("RAY_TPU_TOPOLOGY")
        if topo:
            res[f"tpu-slice:{topo}"] = 1.0
    return res


def _env_hash(runtime_env: dict | None):
    if not runtime_env:
        return None
    import hashlib
    import json

    return hashlib.blake2b(
        json.dumps(runtime_env, sort_keys=True, default=str).encode(),
        digest_size=8,
    ).hexdigest()


# what a worker gets between SIGTERM and SIGKILL
_TERM_GRACE_S = 2.0


class WorkerHandle:
    def __init__(self, worker_id: bytes, proc: subprocess.Popen):
        self.worker_id = worker_id
        self.proc = proc
        self.addr: str | None = None
        self.port: int | None = None
        self.client: AsyncRpcClient | None = None
        self.ready = asyncio.Event()
        self.busy_task: bytes | None = None  # lease/reservation marker
        self.blocked = 0  # depth of in-get parks (worker_blocked fires)
        self._parked_tid = b""  # task id of the most recent in-get park
        # Queued-path tasks pushed to this worker's exec queue and not
        # yet done: dispatch pipelines up to pool_dispatch_depth of them
        # (reference pipelines lease pushes, direct_task_transport.h:211
        # — without this, every pool task pays a full dispatch→execute→
        # done round trip before the next one starts on that worker).
        self.pool_inflight: set[bytes] = set()
        self.actor_id: bytes | None = None
        self.job_id: bytes | None = None
        # chip indices this process was spawned with (accelerator.py):
        # fixed for its lifetime, so reuse matches on the count
        self.chips: tuple[int, ...] = ()
        self.idle_since = time.monotonic()
        self.started_at = time.monotonic()
        self.actor_resources: dict | None = None
        self.actor_bundle = None

    @property
    def idle(self) -> bool:
        return (self.busy_task is None and self.actor_id is None
                and not self.pool_inflight)


class NodeAgent:
    def __init__(self, head_addr: str, head_port: int, *,
                 host: str = "127.0.0.1", port: int = 0,
                 resources: dict | None = None,
                 store_capacity: int = 512 * 1024 * 1024,
                 session_id: str | None = None,
                 node_id: bytes | None = None,
                 labels: dict | None = None):
        self.head_addr = head_addr
        self.head_port = head_port
        self.node_id = node_id or os.urandom(16)
        self.resources_total = dict(resources or detect_resources())
        self.resources_available = dict(self.resources_total)
        self.labels = labels or {}
        self.server = RpcServer(host, port)
        self.host = host
        self.session_id = session_id or os.urandom(4).hex()
        self.store_name = (
            f"/rtstore_{self.session_id}_{self.node_id.hex()[:8]}"
        )
        self.store = ObjectStoreClient.create(
            self.store_name, store_capacity
        )
        if (cfg.get("object_store_prefault")
                and store_capacity
                >= int(cfg.get("object_store_prefault_min_capacity"))):
            # pay the first-touch page faults HERE, off the data path:
            # pull-destination writes then land on warm pages (~10 GB/s
            # vs ~0.4 GB/s faulting). First-fit allocates from the heap
            # head, so the warmed prefix is the pull-buffer pool. Gated
            # on capacity: production stores (multi-GB) amortize the
            # ~0.6s/512MB touch over a long life; the small short-lived
            # stores test clusters spin up by the hundred do not.
            self.store.prewarm(
                int(cfg.get("object_store_prewarm_bytes")),
                hugepage=bool(cfg.get("object_store_hugepages")))
        self.head: AsyncRpcClient | None = None
        self.workers: dict[bytes, WorkerHandle] = {}
        # chip index -> the process it was handed to. A chip is free
        # once that process has EXITED (not merely been told to): the
        # device is released by the process dying, not by its grant
        self._chip_procs: dict[int, subprocess.Popen | None] = {
            c: None
            for c in range(int(self.resources_total.get("TPU", 0)))}
        self.task_queue: deque[dict] = deque()
        self.running: dict[bytes, dict] = {}  # task_id → spec
        self.cluster_view: dict[bytes, dict] = {}
        # delta-heartbeat protocol state (ray_syncer.h:86 analog)
        self._hb_sent: dict = {}
        self._hb_pending: dict = {}
        self._hb_n = 0
        self._view_since: int | None = None
        self.bundles: dict[tuple[bytes, int], dict] = {}  # prepared/committed
        self.bundle_available: dict[tuple[bytes, int], dict] = {}
        self._peer_clients: dict[bytes, AsyncRpcClient] = {}
        self._pull_sched: pull_manager.PullScheduler | None = None
        # oid -> {"qos", "owner"} declared by the fetch_object caller
        # (consumer attribution: weights broadcast, kv handoff,
        # checkpoint restore); consumed by _pull_object. The scheduler
        # dedups concurrent requests per oid, so first declarer wins.
        self._fetch_tags: dict[bytes, dict] = {}
        # cross-host pull instrumentation (the OpStats complement: proves
        # the pipeline actually overlaps chunk requests; tests and the
        # perf harness read it, /metrics exports it)
        self.transfer_stats: dict = {
            "pulls": 0, "pull_bytes": 0, "pull_chunks": 0,
            "pull_max_inflight": 0, "last_pull": None,
        }
        # worker leases for owner-direct task pushes (lease caching,
        # reference direct_task_transport.h:110): lease_id -> grant
        self.leases: dict[bytes, dict] = {}
        # task_done that beat its lease_task_started fire (both async)
        self._done_before_started: set[bytes] = set()
        self._done_order: deque[bytes] = deque()
        # actors waiting for resources reserve ahead of queued tasks
        self._actor_reservations: list[dict] = []
        # Spilling state (reference local_object_manager.h:110 SpillObjects
        # + external_storage.py:246 FileSystemStorage): pinned primaries in
        # seal order (the spill queue) and oid -> spill file for restores.
        self.primaries: dict[bytes, int] = {}  # oid -> size, insert-ordered
        self.spilled_files: dict[bytes, str] = {}
        self.spill_dir = os.path.join(
            tempfile.gettempdir(),
            f"ray_tpu_spill_{self.session_id}_{self.node_id.hex()[:8]}",
        )
        # session log dir (reference session_latest/logs): per-worker
        # stdout/err files served via rpc_list_logs / rpc_read_log
        self.log_dir = os.path.join(
            tempfile.gettempdir(),
            f"ray_tpu_logs_{self.session_id}_{self.node_id.hex()[:8]}",
        )
        # runtime_env package cache (pkg:// URIs -> extracted dirs with
        # worker refcounts + GC; _private/runtime_env.py)
        from ray_tpu._private.runtime_env import PackageCache

        self.pkg_cache = PackageCache(os.path.join(
            tempfile.gettempdir(),
            f"ray_tpu_pkgs_{self.session_id}_{self.node_id.hex()[:8]}",
        ))
        self._spilling = False
        self._bg: list[asyncio.Task] = []
        # SIGKILL-escalation tasks spawned by _kill_worker. Tracked so
        # stop() can cancel+await them — a fire-and-forget coro still
        # pending at loop teardown logs "Task was destroyed but it is
        # pending!" and skips the kill.
        self._escalations: set[asyncio.Task] = set()
        # Native (C++) hybrid placement core; None falls back to the pure-
        # Python policy in _choose_node (no g++ on the host, or a failed
        # build) — said out loud, so nobody times the wrong scheduler.
        self._native_sched = None
        if cfg.get("scheduler_use_native"):
            try:
                from ray_tpu._native.scheduler import NativeScheduler

                self._native_sched = NativeScheduler()
            except (OSError, subprocess.CalledProcessError) as e:
                logger.warning("native scheduler unavailable (%s); using "
                               "the pure-Python placement policy", e)
        self._install_routes()
        self._dead = False

    SPILL_HIGH = cfg.get("spill_high_fraction")
    SPILL_LOW = cfg.get("spill_low_fraction")

    # ---------------- lifecycle ----------------

    def _install_routes(self):
        for name in dir(self):
            if name.startswith("rpc_"):
                self.server.handlers[name[4:]] = getattr(self, name)

    async def start(self) -> int:
        port = await self.server.start()
        self.port = port
        self.head = AsyncRpcClient(self.head_addr, self.head_port)
        await self.head.connect()
        self.head.on_push("node_dead", self._on_node_dead_push)
        self.head.on_push("node_added", self._on_node_added_push)
        reply = await self.head.call("register_node", {
            "node_id": self.node_id, "addr": self.host, "port": port,
            "resources": self.resources_total, "labels": self.labels,
        })
        for view in reply["nodes"]:
            self.cluster_view[view["node_id"]] = view
        self.head.on_push("job_finished", self._on_job_finished_push)
        await self.head.call("subscribe", {"channel": "node_dead"})
        await self.head.call("subscribe", {"channel": "node_added"})
        await self.head.call("subscribe", {"channel": "job_finished"})
        self._bg.append(asyncio.ensure_future(self._heartbeat_loop()))
        self._bg.append(asyncio.ensure_future(self._reap_loop()))
        self._bg.append(asyncio.ensure_future(self._dispatch_loop()))
        self._bg.append(asyncio.ensure_future(self._memory_monitor_loop()))
        self._bg.append(asyncio.ensure_future(self._serve_pin_sweep_loop()))
        self.server.on_disconnect = self._on_server_disconnect
        logger.info("node agent %s up on %s:%s", self.node_id.hex()[:8],
                    self.host, port)
        return port

    async def stop(self):
        """Kills every worker and returns once those that were given a
        chip are GONE (reaped: a dead worker keeps its chips while the
        kernel closes them, 13-21 s for four, and the next job on this
        host would die of a busy device node), bounded by
        ``accelerator.CHIP_WAIT_S``. Workers without chips are killed
        and not waited for."""
        self._dead = True
        for t in self._bg:
            t.cancel()
        for w in list(self.workers.values()):
            self._kill_worker(w)
        # settle escalation tasks before the loop dies: cancelling runs
        # each one's ``finally`` (immediate SIGKILL for stragglers) and
        # keeps teardown free of destroyed-pending-task warnings
        if self._escalations:
            pending = list(self._escalations)
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
        await self._chip_workers_gone()
        if self.head is not None:
            await self.head.close()
        for c in self._peer_clients.values():
            await c.close()
        await self.server.stop()
        self.store.close()

    async def _chip_workers_gone(self) -> None:
        """Reaps the processes in ``_chip_procs`` off the loop: a zombie
        leader (``Zl``) is reaped only when its last thread has closed
        its files, the chips' nodes among them."""
        held = {}  # process -> its chips
        for c, proc in self._chip_procs.items():
            if proc is not None and proc.returncode is None:
                held.setdefault(proc, []).append(c)
        if not held:
            return

        t0 = time.monotonic()
        bound = accelerator.CHIP_WAIT_S

        def reap(proc):
            # (stop() cancels the escalation of a worker it has only just
            # signalled before that task ran at all: the SIGKILL is here)
            try:
                return proc.wait(timeout=min(_TERM_GRACE_S, bound))
            except subprocess.TimeoutExpired:
                proc.kill()
            try:
                proc.wait(timeout=max(0.0, t0 + bound - time.monotonic()))
            except subprocess.TimeoutExpired:
                logger.warning("worker %s still holds its chips %.0f s after "
                               "it was killed", proc.pid, bound)

        loop = asyncio.get_running_loop()
        await asyncio.gather(*(loop.run_in_executor(None, reap, proc)
                               for proc in held))
        nodes = accelerator.chip_device_paths()
        accelerator.report_chip_wait(
            t0, [nodes[c] if c < len(nodes) else f"chip{c}"
                 for chips in held.values() for c in sorted(chips)],
            leaving=True)

    def _on_node_dead_push(self, payload):
        nid = payload["node_id"]
        view = self.cluster_view.get(nid)
        if view is not None:
            view["alive"] = False
        cli = self._peer_clients.pop(nid, None)
        if cli is not None:
            asyncio.ensure_future(cli.close())
        # purge the dead peer's pacer window (PR 1 purge discipline): an
        # exhausted bucket must not throttle a reused address forever
        try:
            from ray_tpu._private import net_qos as _qos

            _qos.purge_peer(nid.hex()[:8])
        except Exception:  # noqa: BLE001 — purge is best-effort
            pass

    def _on_node_added_push(self, payload):
        self.cluster_view[payload["node_id"]] = payload

    def _on_job_finished_push(self, payload):
        """Reap this job's workers (reference: raylet kills job workers on
        driver exit)."""
        job_id = payload["job_id"]
        for w in list(self.workers.values()):
            if w.job_id == job_id and w.actor_id is None:
                self._kill_worker(w)

    async def _reconnect_head(self) -> bool:
        """Head restarted (GCS FT): dial it again, re-register, re-subscribe
        (reference raylet NotifyGCSRestart reconnect flow)."""
        cli = AsyncRpcClient(self.head_addr, self.head_port)
        try:
            await cli.connect(retries=10, delay=0.3)
        except rpc.ConnectionLost:
            return False
        old, self.head = self.head, cli
        if old is not None:
            await old.close()
        cli.on_push("node_dead", self._on_node_dead_push)
        cli.on_push("node_added", self._on_node_added_push)
        cli.on_push("job_finished", self._on_job_finished_push)
        try:
            await cli.call("register_node", {
                "node_id": self.node_id, "addr": self.host,
                "port": self.port, "resources": self.resources_total,
                "labels": self.labels,
            })
            for ch in ("node_dead", "node_added", "job_finished"):
                await cli.call("subscribe", {"channel": ch})
            # re-announce local primaries so the rebuilt directory knows us
            for oid, size in list(self.primaries.items()):
                await cli.call("object_add_location", {
                    "object_id": oid, "node_id": self.node_id, "size": size,
                })
            # spilled primaries live on this node's disk: re-announce the
            # spill urls too so restores keep working after a head restart
            for oid, path in list(self.spilled_files.items()):
                await cli.call("object_spilled", {
                    "object_id": oid, "url": self._spill_url(path),
                })
        except (rpc.ConnectionLost, rpc.RpcError):
            return False
        logger.info("reconnected to restarted head")
        return True

    def _hb_snapshot(self) -> dict:
        """Everything a FULL heartbeat would carry (reference load
        report). Stats are quantized so jitter (cpu %, free memory)
        doesn't defeat the delta encoding."""
        stats = self._node_stats()
        q = dict(stats)
        if "cpu_percent" in q:
            q["cpu_percent"] = round(q["cpu_percent"] / 10) * 10
        if "mem_available" in q:
            gran = 256 * 1024 * 1024
            q["mem_available"] = (q["mem_available"] // gran) * gran
        return {
            "resources_available": dict(self.resources_available),
            # demand signal = WAITING work only (running tasks don't
            # need more nodes); primaries gate scale-down
            "queued": len(self.task_queue),
            # demand SHAPES so the autoscaler can bin-pack against
            # provider node types (resource_demand_scheduler.py analog)
            "queued_shapes": [
                spec.get("resources", {"CPU": 1.0})
                for spec in list(self.task_queue)[:50]
            ],
            "running": len(self.running),
            "store_primaries": len(self.primaries),
            # reporter-agent analog (reporter_agent.py:266)
            "stats": q,
        }

    # every Nth beat resends the full snapshot: self-healing against any
    # head/agent state divergence the delta protocol can't see
    _HB_FULL_EVERY = 10

    def _build_heartbeat(self) -> dict:
        """Delta heartbeat (reference ray_syncer.h:86: versioned deltas,
        not per-beat snapshots): only fields that changed since the last
        ACCEPTED beat ride the wire; an idle node sends just its id."""
        snap = self._hb_snapshot()
        self._hb_n = getattr(self, "_hb_n", 0) + 1
        if self._hb_n % self._HB_FULL_EVERY == 0:
            self._hb_pending = snap
            return {"node_id": self.node_id, **snap}
        payload = {"node_id": self.node_id}
        for k, v in snap.items():
            if self._hb_sent.get(k) != v:
                payload[k] = v
        self._hb_pending = snap
        return payload

    async def _heartbeat_loop(self):
        while not self._dead:
            try:
                if self.head.closed:
                    if not await self._reconnect_head():
                        await asyncio.sleep(1.0)
                        continue
                if fault_injection.enabled():
                    # chaos site: "stall" sleeps past the head's timeout
                    # (node marked dead while the process lives), "drop"
                    # skips one beat — both deterministic per occurrence
                    act = fault_injection.fire(
                        "agent.heartbeat", node=self.node_id.hex())
                    if act == "drop":
                        await asyncio.sleep(1.0)
                        continue
                reply = await self.head.call(
                    "heartbeat", self._build_heartbeat())
                if reply.get("unknown"):
                    await self.head.call("register_node", {
                        "node_id": self.node_id, "addr": self.host,
                        "port": self.port,
                        "resources": self.resources_total,
                        "labels": self.labels,
                    })
                    # force a FULL beat + full view after (re)register
                    self._hb_sent = {}
                    self._view_since = None
                else:
                    self._hb_sent = self._hb_pending
                view = await self.head.call(
                    "get_cluster_view",
                    {} if self._view_since is None
                    else {"since": self._view_since})
                for v in view["nodes"]:
                    self.cluster_view[v["node_id"]] = v
                self._view_since = view.get("ver")
            except (rpc.ConnectionLost, rpc.RpcError):
                # the head may have restarted with empty state: next
                # round re-registers; send full state again
                self._hb_sent = {}
                self._view_since = None
            await asyncio.sleep(1.0)

    def _node_stats(self) -> dict:
        """psutil node stats (reference reporter_agent.py:266 — cpu/mem
        plus this framework's store occupancy)."""
        try:
            import psutil

            vm = psutil.virtual_memory()
            return {
                "cpu_percent": psutil.cpu_percent(interval=None),
                "mem_total": vm.total,
                "mem_available": vm.available,
                "num_workers": len(self.workers),
            }
        except Exception:  # noqa: BLE001 — stats are best-effort
            return {"num_workers": len(self.workers)}

    # ---------------- worker pool ----------------

    @property
    def _spawn_gate(self) -> asyncio.Semaphore:
        """Bounds concurrent worker startups (fork → registered) —
        reference worker_pool.h maximum_startup_concurrency. Unbounded
        concurrent interpreter starts thrash the host until every spawn
        misses its register timeout (observed: 50 concurrent actor
        creations on a 1-core box all timed out at 60s)."""
        gate = getattr(self, "_spawn_gate_sem", None)
        if gate is None:
            n = cfg.get("worker_startup_concurrency") or max(
                2, os.cpu_count() or 1)
            gate = self._spawn_gate_sem = asyncio.Semaphore(int(n))
        return gate

    async def _spawn_worker_registered(
            self, job_id: bytes | None, n_chips: int = 0,
            runtime_env: dict | None = None, *,
            reserve: bool = False, recheck_pool_cap: bool = False,
            gate_deadline: float | None = None) -> WorkerHandle | None:
        """Spawn AND wait for registration, holding a startup slot from
        fork to registered. Env materialization (package fetch, pip
        plugin installs — possibly minutes) runs BEFORE acquiring the
        gate so slow installs never serialize unrelated startups.

        n_chips: whole TPU chips the work this worker is for was granted.
        The worker's environment shows it exactly those chips, or pins it
        to the CPU platform when there are none (accelerator.worker_env)
        — set last, so neither the inherited environment nor a
        runtime_env can put a grantless process on a chip.

        recheck_pool_cap: re-evaluate the pool cap AFTER acquiring the
        gate — spawns parked at the gate are invisible to callers' cap
        checks, so a burst would otherwise overshoot; returns None when
        the cap filled while waiting. gate_deadline (monotonic): bound
        on slot acquisition — past it PoolSaturated propagates so a
        caller's granted resources don't sit pinned behind a wedged
        gate. On register timeout the worker is reaped (a dead handle
        would pin a cap slot forever) and TimeoutError propagates."""
        worker_id = os.urandom(16)
        env = dict(os.environ)
        env.update({
            "RAY_TPU_HEAD": f"{self.head_addr}:{self.head_port}",
            "RAY_TPU_AGENT": f"{self.host}:{self.port}",
            "RAY_TPU_STORE": self.store_name,
            "RAY_TPU_NODE_ID": self.node_id.hex(),
            "RAY_TPU_WORKER_ID": worker_id.hex(),
            "RAY_TPU_SESSION": self.session_id,
        })
        pkg_uris: list[str] = []

        def _release_uris():
            # a failed spawn must release the URI refcounts already
            # acquired, or the cache dirs are pinned forever (once the
            # handle exists, _kill_worker/_on_worker_death own this)
            for uri in pkg_uris:
                self.pkg_cache.release(uri)

        try:
            py_exe, cwd = await self._materialize_env(
                env, pkg_uris, runtime_env)
            if gate_deadline is not None:
                try:
                    await asyncio.wait_for(
                        self._spawn_gate.acquire(),
                        timeout=max(0.05,
                                    gate_deadline - time.monotonic()))
                except asyncio.TimeoutError:
                    raise self.PoolSaturated(
                        "worker startup gate saturated") from None
            else:
                await self._spawn_gate.acquire()
        except BaseException:
            _release_uris()
            raise
        try:
            if recheck_pool_cap:
                pool_ws = [x for x in self.workers.values()
                           if x.actor_id is None]
                n = sum(1 for x in pool_ws if not x.blocked)
                if (n >= self._pool_worker_cap()
                        or len(pool_ws) >= 4 * self._pool_worker_cap()):
                    _release_uris()
                    return None
            try:
                # claim -> fork with no await between: the claim is only
                # recorded by the fork (chip -> process)
                chips = await self._claim_chips(n_chips)
                env.update(accelerator.worker_env(
                    chips, len(self._chip_procs)))
                w = self._fork_worker(worker_id, py_exe, env, cwd,
                                      pkg_uris, job_id, chips,
                                      runtime_env)
            except BaseException:
                _release_uris()
                raise
            if reserve:
                # an unreserved idle worker would be claimed by another
                # waiter the moment `ready` fires
                w.busy_task = self._RESERVED
            try:
                await asyncio.wait_for(
                    w.ready.wait(),
                    timeout=cfg.get("worker_register_timeout_s"),
                )
            except (asyncio.TimeoutError, asyncio.CancelledError):
                self._kill_worker(w)
                raise
            return w
        finally:
            self._spawn_gate.release()

    async def _claim_chips(self, n: int) -> tuple[int, ...]:
        """n chip indices that no live process holds. Resource accounting
        already admitted the work, so a missing chip is held either by an
        idle pool worker left over from a finished TPU task (evicted
        here) or by a process still exiting (waited for). Returns with
        no await after the final check — the caller forks at once."""
        if n == 0:
            return ()
        deadline = time.monotonic() + cfg.get("worker_register_timeout_s")
        while True:
            free = [c for c, proc in self._chip_procs.items()
                    if proc is None or proc.poll() is not None]
            if len(free) >= n:
                return tuple(free[:n])
            for w in list(self.workers.values()):
                if w.chips and w.idle and w.ready.is_set():
                    self._kill_worker(w)
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"no {n} free TPU chip(s) on this node: "
                    f"{len(self._chip_procs)} chips, {len(free)} free "
                    f"(a fractional TPU request owns a whole chip)")
            await asyncio.sleep(0.05)

    async def _materialize_env(self, env: dict, pkg_uris: list,
                               runtime_env: dict | None):
        """Resolve a runtime_env into (py_executable, cwd), mutating
        `env` and appending acquired cache URIs to `pkg_uris`.

        Reference _private/runtime_env/, scaled: env_vars merge into the
        process env; working_dir becomes the cwd; py_modules prepend to
        PYTHONPATH; plugin keys (pip envs, custom plugins) may swap the
        interpreter. Workers are keyed by the env hash, so an env
        mismatch forces a fresh process (worker_pool.h runtime-env-keyed
        pools)."""
        cwd = None
        if runtime_env:
            from ray_tpu._private.runtime_env import PKG_NS, PKG_SCHEME

            env.update({str(k): str(v) for k, v in
                        (runtime_env.get("env_vars") or {}).items()})

            async def _resolve(entry):
                if isinstance(entry, str) and entry.startswith(PKG_SCHEME):
                    path = self.pkg_cache.dir_if_present(entry)
                    if path is None:
                        data = await self.head.call("kv_get", {
                            "ns": PKG_NS,
                            "key": entry[len(PKG_SCHEME):].encode(),
                        })
                        if data is None:
                            raise FileNotFoundError(
                                f"package {entry} not in cluster KV")
                        path = self.pkg_cache.extract(entry, data)
                    # acquire NOW, before any later await: a concurrent
                    # release could otherwise GC this dir mid-spawn
                    self.pkg_cache.acquire(entry)
                    pkg_uris.append(entry)
                    return path
                return entry

            cwd = await _resolve(runtime_env.get("working_dir"))
            mods = [await _resolve(m)
                    for m in (runtime_env.get("py_modules") or [])]
            if cwd:
                # the worker runs `python -m ray_tpu...` from the new cwd:
                # keep the framework importable alongside the working_dir
                import ray_tpu as _pkg

                repo_root = os.path.dirname(os.path.dirname(_pkg.__file__))
                mods = [cwd, repo_root, *mods]
            if mods:
                prev = env.get("PYTHONPATH", "")
                env["PYTHONPATH"] = os.pathsep.join(
                    [*mods, prev] if prev else mods
                )
        py_exe = sys.executable
        if runtime_env:
            # plugin keys (pip envs, custom plugins): materialize into
            # the same refcounted cache, let them swap the interpreter
            from ray_tpu._private import runtime_env_plugins as rep

            ctx = rep.RuntimeEnvContext(env=env, py_executable=py_exe,
                                        cwd=cwd)
            pkg_uris.extend(
                await rep.apply_plugins(runtime_env, ctx, self.pkg_cache))
            py_exe, cwd = ctx.py_executable, ctx.cwd
        return py_exe, cwd

    def _fork_worker(self, worker_id: bytes, py_exe: str, env: dict,
                     cwd, pkg_uris: list, job_id: bytes | None,
                     chips: tuple[int, ...],
                     runtime_env: dict | None) -> WorkerHandle:
        """Fork the worker process and register its handle (synchronous:
        the handle is in self.workers before any await, so cap counts
        stay accurate for the next gate holder)."""
        if job_id:
            env["RAY_TPU_JOB_ID"] = job_id.hex()
        proc = subprocess.Popen(
            [py_exe, "-m", "ray_tpu.core.worker_proc"],
            env=env, cwd=cwd,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        handle = WorkerHandle(worker_id, proc)
        handle.job_id = job_id
        handle.chips = chips
        for c in chips:
            self._chip_procs[c] = proc
        handle.env_hash = _env_hash(runtime_env)
        handle.pkg_uris = pkg_uris  # acquired in _materialize_env
        self.workers[worker_id] = handle
        asyncio.ensure_future(self._drain_worker_logs(handle))
        return handle

    async def _drain_worker_logs(self, w: WorkerHandle):
        """Forward worker stdout/stderr lines to the head log channel."""
        loop = asyncio.get_running_loop()

        def _read(stream, kind):
            # per-process log file under the session log dir (reference
            # session_latest/logs/worker-*.out|err + log_monitor.py): the
            # live pubsub stream stays, the file is what survives a
            # driver disconnect and what /api/logs serves
            path = os.path.join(
                self.log_dir, f"worker-{w.worker_id.hex()[:12]}.{kind}")
            os.makedirs(self.log_dir, exist_ok=True)
            with open(path, "ab", buffering=0) as logf:
                for line in iter(stream.readline, b""):
                    logf.write(line)
                    text = line.decode(errors="replace").rstrip()
                    if text:
                        loop.call_soon_threadsafe(
                            self._publish_log, w.worker_id, kind, text
                        )
            stream.close()

        for stream, kind in ((w.proc.stdout, "out"), (w.proc.stderr, "err")):
            if stream is not None:
                loop.run_in_executor(None, _read, stream, kind)

    def _publish_log(self, worker_id: bytes, kind: str, text: str):
        if self.head is not None and not self.head.closed:
            asyncio.ensure_future(self._push_log(worker_id, kind, text))

    async def _push_log(self, worker_id, kind, text):
        try:
            await self.head.oneway("worker_log", {
                "worker_id": worker_id, "node_id": self.node_id,
                "kind": kind, "line": text,
            })
        except Exception:
            pass

    async def rpc_store_put(self, conn, p):
        """ray:// remote-driver put: land the object in THIS node's store
        as a pinned primary, exactly like a local seal (client.py)."""
        from ray_tpu.core.object_store import StoreFullError

        oid = p["object_id"]
        data = p["data"]
        table = p["meta_table"]
        if self.store.contains(oid):
            return True
        # same pressure behavior as a LOCAL put (worker._put_plasma):
        # evict + wait for async GC/spill within the retry budget — a
        # remote driver must not fail where a local one would succeed
        deadline = time.monotonic() + cfg.get("put_pressure_retry_s")
        while True:
            try:
                wbuf = self.store.create_object(oid, len(data), len(table))
                break
            except StoreFullError:
                self.store.evict(len(data))
                try:
                    wbuf = self.store.create_object(
                        oid, len(data), len(table))
                    break
                except StoreFullError:
                    if time.monotonic() > deadline:
                        return False
                    await asyncio.sleep(0.05)
        wbuf.data[:] = data
        wbuf.meta[:] = table
        wbuf.seal()
        await self.rpc_object_sealed(conn, {
            "object_id": oid, "owner": p.get("owner"), "size": len(data),
        })
        return True

    async def rpc_store_get(self, conn, p):
        """ray:// remote-driver get: serve (pulling first if remote) the
        object's raw parts over the wire."""
        oid = p["object_id"]
        if not self.store.contains(oid):
            ok = await self._ensure_local(oid)
            if not ok and not self.store.contains(oid):
                return None
        buf = self.store.get(oid)
        if buf is None:
            return None
        # zero-copy serve: the object body rides the out-of-band frame
        # as a memoryview over the pinned segment; the pin drops once
        # the transport has consumed it
        return OobReply({"meta_table": bytes(buf.metadata)},
                        [buf.data], release=buf.release)

    async def rpc_list_logs(self, conn, p):
        """Log files on this node (reference dashboard log_manager)."""
        try:
            files = sorted(os.listdir(self.log_dir))
        except FileNotFoundError:
            return []
        out = []
        for fn in files:
            try:
                out.append({
                    "file": fn,
                    "bytes": os.path.getsize(
                        os.path.join(self.log_dir, fn)),
                })
            except OSError:
                continue
        return out

    async def rpc_read_log(self, conn, p):
        """Tail (or range-read) one log file. The name is confined to the
        session log dir — no path traversal."""
        fn = os.path.basename(p["file"])
        path = os.path.join(self.log_dir, fn)
        if not os.path.exists(path):
            return None
        tail = int(p.get("tail_bytes", 64 * 1024))
        with open(path, "rb") as f:
            f.seek(0, os.SEEK_END)
            size = f.tell()
            start = int(p["offset"]) if "offset" in p else max(
                0, size - tail)
            f.seek(start)
            data = f.read(min(tail, 4 * 1024 * 1024))
        return {"file": fn, "offset": start, "size": size,
                "data": data.decode(errors="replace")}

    async def rpc_register_executor(self, conn, p):
        """A spawned worker process reports its direct-RPC address."""
        w = self.workers.get(p["worker_id"])
        if w is None:
            return False
        w.addr, w.port = p["addr"], p["port"]
        w.client = AsyncRpcClient(w.addr, w.port)
        await w.client.connect()
        w.ready.set()
        self._signal_worker_free()
        return True

    def _signal_worker_free(self):
        """Wake _pop_worker waiters (a worker went idle / died / spawned)."""
        self._free_ver = getattr(self, "_free_ver", 0) + 1
        ev = getattr(self, "_worker_free_ev", None)
        if ev is not None:
            ev.set()

    def _pool_worker_cap(self) -> int:
        """Soft cap on POOL (non-actor) worker processes per node —
        reference worker_pool.h maximum_startup_concurrency analog. A
        flood of zero-cpu tasks must queue for workers, not fork-storm
        the host (observed: 1000 concurrent num_cpus=0 tasks spawning
        375 processes). Actor workers are dedicated and exempt."""
        cap = cfg.get("max_pool_workers_per_node")
        if cap:
            return int(cap)
        return max(4, int(2 * self.resources_total.get("CPU", 2)))

    _RESERVED = b"__spawn_reserved__"

    class PoolSaturated(TimeoutError):
        """No pool worker freed within the wait budget — the node is
        healthy but at its worker cap; the task should requeue, not
        fail."""

    async def _pop_worker(self, job_id: bytes | None,
                          n_chips: int = 0,
                          runtime_env: dict | None = None, *,
                          wait: bool = True,
                          spawn_wait: bool = True,
                          allow_pipeline: bool = False) -> WorkerHandle | None:
        """Idle worker of the same job, runtime env AND chip count, else
        spawn (worker_pool.h PopWorker; env mismatch forces a new
        process, and so does a chip mismatch: a process's platform and
        chips are fixed at spawn).
        At the pool cap: evict an idle MISMATCHED worker to make room,
        else wait for one to free (wait=False returns None instead — the
        lease fast path must not camp on granted resources)."""
        want = _env_hash(runtime_env)
        deadline = time.monotonic() + cfg.get("worker_register_timeout_s")
        if not hasattr(self, "_worker_free_ev"):
            self._worker_free_ev = asyncio.Event()
        while True:
            # snapshot BEFORE scanning: any free between scan and clear()
            # bumps the version and forces an immediate rescan
            ver = getattr(self, "_free_ver", 0)
            for w in self.workers.values():
                if w.idle and w.ready.is_set() and w.job_id == job_id \
                        and getattr(w, "env_hash", None) == want \
                        and len(w.chips) == n_chips \
                        and w.proc.poll() is None:
                    w.idle_since = time.monotonic()
                    return w

            def _pipeline_candidate():
                # no idle match: pipeline onto the least-loaded MATCHING
                # busy worker under the depth cap — the exec queue hides
                # the dispatch→done round trip (the queued-path analog of
                # lease-push pipelining, direct_task_transport.h:211).
                # NEVER a blocked worker: its exec thread is parked in
                # get() on nested work — stacking more tasks behind it
                # is the nested-task deadlock.
                depth = cfg.get("pool_dispatch_depth")
                best = None
                for w in self.workers.values():
                    if (w.actor_id is None and w.busy_task is None
                            and not w.blocked
                            and w.ready.is_set() and w.job_id == job_id
                            and getattr(w, "env_hash", None) == want
                            and len(w.chips) == n_chips
                            and w.proc.poll() is None
                            and 0 < len(w.pool_inflight) < depth):
                        if best is None or len(w.pool_inflight) < len(
                                best.pool_inflight):
                            best = w
                return best

            # blocked workers don't hold a slot: each one parked in
            # get() justifies one replacement (reference releases the
            # blocked worker's CPU and spawns a backfill) — up to a hard
            # process ceiling, or unbounded recursion (f blocking on
            # f.remote() all the way down) re-creates the fork storm the
            # cap exists to prevent; past the ceiling, work queues.
            total_pool = sum(1 for w in self.workers.values()
                             if w.actor_id is None)
            if total_pool >= 4 * self._pool_worker_cap():
                n_pool = total_pool  # at ceiling: behave as saturated
            else:
                n_pool = sum(1 for w in self.workers.values()
                             if w.actor_id is None and not w.blocked)
            if n_pool >= self._pool_worker_cap():
                # no matching idle worker and no room: evict the longest-
                # idle MISMATCHED pool worker (job/env churn must not
                # permanently starve new work — incl. idle TPU holders,
                # whose cull exemption protects only their own job)
                victims = [w for w in self.workers.values()
                           if w.actor_id is None and w.idle
                           and w.ready.is_set()]
                if victims:
                    self._kill_worker(min(victims,
                                          key=lambda w: w.idle_since))
                    n_pool -= 1
                elif allow_pipeline:
                    # queued dispatch only — a LEASE must get a worker to
                    # itself (the owner pushes depth-10 bursts assuming a
                    # dedicated exec thread; stacking those behind another
                    # task starves them)
                    cand = _pipeline_candidate()
                    if cand is not None:
                        return cand
            if n_pool < self._pool_worker_cap():
                if not spawn_wait:
                    # lease fast path: spawning takes ~100-400ms and the
                    # grant RPC blocks the owner's submit loop — kick the
                    # spawn in the background and refuse; the owner's
                    # retry (pending pump) grants once it registers
                    async def _bg_spawn():
                        try:
                            # the cap re-check runs INSIDE the startup
                            # gate (recheck_pool_cap): several refusals
                            # can park spawns at the gate before any
                            # forks, and a pre-gate check would not see
                            # them — only spawns still under the cap at
                            # their turn may fork.
                            await self._spawn_worker_registered(
                                job_id, n_chips, runtime_env,
                                recheck_pool_cap=True,
                                gate_deadline=time.monotonic() + cfg.get(
                                    "worker_register_timeout_s"))
                        except (asyncio.TimeoutError, self.PoolSaturated):
                            pass  # cap/gate filled; the queue path covers
                        except Exception as e:  # noqa: BLE001
                            logger.warning("background spawn failed: %s", e)

                    asyncio.ensure_future(_bg_spawn())
                    return None
                w = await self._spawn_worker_registered(
                    job_id, n_chips, runtime_env, reserve=True,
                    recheck_pool_cap=True, gate_deadline=deadline)
                if w is None:
                    continue  # cap filled while parked at the gate
                return w
            if not wait:
                return None
            if time.monotonic() > deadline:
                raise self.PoolSaturated(
                    f"no pool worker available within budget "
                    f"(cap {self._pool_worker_cap()})")
            # wait for a free signal, not a poll: hundreds of waiters
            # polling starves the event loop. The version counter closes
            # the lost-wakeup race — a worker freed between our scan and
            # clear() would otherwise cost a silent 200ms stall per task
            # (this was the queued-path throughput ceiling).
            self._worker_free_ev.clear()
            if getattr(self, "_free_ver", 0) != ver:
                continue  # freed since our scan; rescan immediately
            try:
                await asyncio.wait_for(self._worker_free_ev.wait(),
                                       timeout=0.2)
            except asyncio.TimeoutError:
                pass

    def _kill_worker(self, w: WorkerHandle):
        self.workers.pop(w.worker_id, None)
        self._signal_worker_free()  # pool count dropped; waiters may spawn
        # pop: kill + death-reap can BOTH run for one handle (e.g. the
        # OOM path); the refcount must release exactly once
        for uri in w.__dict__.pop("pkg_uris", ()):
            self.pkg_cache.release(uri)
        if w.client is not None:
            asyncio.ensure_future(w.client.close())
        if w.proc.poll() is None:
            w.proc.terminate()

            async def _escalate(proc=w.proc):
                # don't block the event loop on proc.wait; SIGKILL after
                # grace. Poll in small steps so cancellation (agent
                # shutdown) lands promptly, and kill in ``finally`` so a
                # cancelled escalation still never leaks the process.
                try:
                    deadline = time.monotonic() + _TERM_GRACE_S
                    while time.monotonic() < deadline:
                        if proc.poll() is not None:
                            return
                        await asyncio.sleep(0.05)
                finally:
                    if proc.poll() is None:
                        proc.kill()

            try:
                task = asyncio.ensure_future(_escalate())
                self._escalations.add(task)
                task.add_done_callback(self._escalations.discard)
            except RuntimeError:  # no running loop (shutdown path)
                try:
                    w.proc.wait(timeout=2)
                except subprocess.TimeoutExpired:
                    w.proc.kill()

    async def _reap_loop(self):
        """Detect dead workers; cull long-idle non-TPU workers; expire
        worker leases whose owners stopped renewing."""
        while not self._dead:
            await asyncio.sleep(0.2)
            now = time.monotonic()
            idle_reclaim = cfg.get("worker_lease_idle_reclaim_s")
            if self.task_queue or getattr(self, "_pop_waiters", 0) > 0:
                # queued tasks are waiting on pool room: momentarily-idle
                # leases must hand their workers back sooner than 1.5s
                # (0.5s, not lower: reclaiming leases that are merely
                # between refill bursts churns revocation failovers)
                idle_reclaim = min(idle_reclaim, 0.5)
            for lease_id, lease in list(self.leases.items()):
                if now > lease["expires"]:
                    if lease.get("active"):
                        # a direct-pushed task is still running: revoking
                        # now would hand its cpu to someone else and
                        # double-run the task — extend until it finishes
                        lease["expires"] = now + 1.0
                    else:
                        self._release_lease(lease_id)
                elif (not lease.get("active")
                      and now - lease["last_activity"] > idle_reclaim):
                    # idle well under TTL: hand the worker back to the
                    # pool so other owners/shapes aren't starved by
                    # parked leases (the owner is notified and re-grants
                    # in one RTT if its burst resumes)
                    self._release_lease(lease_id)
            for w in list(self.workers.values()):
                code = w.proc.poll()
                if code is not None:
                    await self._on_worker_death(w, code)
                elif (w.idle and not w.chips and w.ready.is_set()
                      and now - w.idle_since > IDLE_CULL_S):
                    self._kill_worker(w)

    async def _on_worker_death(self, w: WorkerHandle, code: int):
        self.workers.pop(w.worker_id, None)
        self._signal_worker_free()  # pool count dropped; waiters may spawn
        for uri in w.__dict__.pop("pkg_uris", ()):
            self.pkg_cache.release(uri)
        if code not in (0, None):  # durable failure record on the head
            try:
                # oneway: a hung head must not park the reap loop behind
                # an observability report
                await self.head.oneway("report_worker_failure", {
                    "worker_id": w.worker_id, "node_id": self.node_id,
                    "exit_code": code,
                    "reason": ("actor process died" if w.actor_id
                               else "worker process died"),
                })
            except Exception:  # noqa: BLE001 — observability best-effort
                pass
        if w.actor_id is not None:
            # actor process died → control plane decides restart
            for r, v in (w.actor_resources or {}).items():
                self._release(r, v, w.actor_bundle)
            try:
                await self.head.call("actor_failed", {
                    "actor_id": w.actor_id,
                    "reason": f"worker exited with code {code}",
                })
            except (rpc.ConnectionLost, rpc.RpcError):
                pass
        for lease_id, lease in list(self.leases.items()):
            if lease["worker_id"] == w.worker_id:
                # release + owner revocation notice (the owner resubmits
                # any in-flight direct-pushed task through the queue)
                self._release_lease(lease_id)
                for tid, spec in list(self.running.items()):
                    if spec.get("_lease_id") == lease_id:
                        self.running.pop(tid, None)
                        await self._notify_task_failed(
                            spec, f"leased worker died (exit {code})"
                        )
        for tid in [w.busy_task, *list(w.pool_inflight)]:
            if tid is None:
                continue
            spec = self.running.pop(tid, None)
            if spec is not None:
                self._free_task_resources(spec)
                await self._notify_task_failed(
                    spec, f"worker died with exit code {code}"
                )
        w.pool_inflight.clear()

    async def _notify_task_failed(self, spec: dict, reason: str,
                                  retriable: bool = True):
        """Tell the owner so it can retry or raise (task_manager.h:174)."""
        owner = spec.get("owner")
        if not owner:
            return
        try:
            cli = await self._peer_worker(owner)
            if cli is not None:
                await cli.oneway("task_failed", {
                    "task_id": spec["task_id"], "reason": reason,
                    "retriable": retriable,
                })
        except (rpc.ConnectionLost, rpc.RpcError, OSError):
            pass

    _worker_peer_clients: dict[tuple, AsyncRpcClient]

    async def _peer_worker(self, owner: dict) -> AsyncRpcClient | None:
        key = (owner["addr"], owner["port"])
        cache = getattr(self, "_wpc", None)
        if cache is None:
            cache = self._wpc = {}
        cli = cache.get(key)
        if cli is not None and not cli.closed:
            return cli
        cli = AsyncRpcClient(owner["addr"], owner["port"])
        try:
            await cli.connect(retries=3)
        except rpc.ConnectionLost:
            return None
        cache[key] = cli
        return cli

    # ---------------- resources ----------------

    def _fits(self, need: dict, pool: dict) -> bool:
        return all(pool.get(r, 0.0) >= v - 1e-9 for r, v in need.items())

    def _take(self, need: dict, pool: dict):
        for r, v in need.items():
            pool[r] = pool.get(r, 0.0) - v

    def _give(self, need: dict, pool: dict):
        for r, v in need.items():
            pool[r] = pool.get(r, 0.0) + v

    def _task_pool(self, spec: dict, pin: bool = False) -> dict | None:
        """Resource pool a task draws from: a PG bundle or the node pool.

        bundle_index < 0 means "any bundle of the PG" (reference
        bundle_index=-1): the fitting local bundle is chosen fresh each
        call, and PINNED onto the spec only when `pin=True` — dispatch
        pins at GRANT time (immediately before _take, no await between)
        so the grant and the eventual free draw from the same pool,
        while a requeued task stays free to land on whichever bundle
        has room next scan."""
        pgid = spec.get("pg_id")
        if pgid:
            idx = spec.get("bundle_index", 0)
            if idx is None or idx < 0:
                need = spec.get("resources", {})
                fallback = None
                for (g, i), pool in self.bundle_available.items():
                    if g != pgid:
                        continue
                    if self._fits(need, pool):
                        if pin:
                            spec["bundle_index"] = i
                            spec["_any_bundle"] = True
                        return pool
                    fallback = pool
                # full bundles: return one anyway so dispatch waits on
                # capacity rather than treating the PG as absent
                return fallback
            return self.bundle_available.get((pgid, idx))
        return self.resources_available

    def _free_task_resources(self, spec: dict):
        if spec.get("_granted"):
            pool = self._task_pool(spec)
            if pool is not None:
                self._give(spec.get("resources", {}), pool)
            spec["_granted"] = False
            if spec.pop("_any_bundle", None):
                # the pin was a grant-time choice, not a user constraint:
                # a requeued task is free to land on any bundle next scan
                spec["bundle_index"] = -1

    def _release(self, r, v, bundle_key=None):
        pool = (self.bundle_available.get(bundle_key)
                if bundle_key else self.resources_available)
        if pool is not None:
            pool[r] = pool.get(r, 0.0) + v

    # ---------------- task scheduling ----------------

    async def rpc_submit_task(self, conn, p):
        """Entry from a local worker/driver or a spilling peer agent."""
        # boundary validation (typed TaskSpec; `_`-prefixed node-local
        # annotations from a forwarding peer pass through unchecked)
        try:
            spec = task_spec.TaskSpec.from_wire(p)
        except task_spec.InvalidTaskSpec as e:
            raise rpc.RpcError(f"rejected task spec: {e}") from None
        spec.setdefault("_spills", 0)
        target = await self._locality_target(spec) or self._choose_node(spec)
        if target is not None and target != self.node_id \
                and spec["_spills"] < SPILL_MAX:
            spec["_spills"] += 1
            ok = await self._forward_task(spec, target)
            if ok:
                return {"queued": "remote", "node": target}
        self.task_queue.append(spec)
        # Tell the owner where the task landed so it can fail/retry it if
        # this node dies while the task is queued or running (the dying
        # agent can't report; reference: owner-held leases detect raylet
        # death via channel breakage).
        if spec.get("owner"):
            asyncio.ensure_future(self._notify_task_located(spec))
        self._kick_dispatch()
        return {"queued": "local"}

    async def rpc_submit_task_batch(self, conn, p):
        """Windowed batch from an owner's submission pump: one ack covers
        the whole batch, so .remote() never blocks per task (the owner
        pipelines these; reference pipelines lease pushes instead,
        direct_task_transport.h:211)."""
        out = []
        for spec in p["specs"]:
            out.append(await self.rpc_submit_task(conn, spec))
        return {"n": len(out)}

    async def _notify_dep_lost(self, spec: dict, oid: bytes):
        try:
            cli = await self._peer_worker(spec["owner"])
            if cli is not None:
                await cli.oneway("dep_lost", {
                    "task_id": spec["task_id"], "object_id": oid,
                })
        except (rpc.ConnectionLost, rpc.RpcError, OSError):
            pass

    async def _notify_task_located(self, spec: dict,
                                   node_id: bytes | None = None):
        try:
            cli = await self._peer_worker(spec["owner"])
            if cli is not None:
                await cli.oneway("task_located", {
                    "task_id": spec["task_id"],
                    "node_id": node_id or self.node_id,
                    # forward-hop depth: the notifies from every hop of a
                    # spill chain race to the owner, and only the deepest
                    # one names the node actually holding the task
                    "hop": spec.get("_spills", 0),
                })
        except (rpc.ConnectionLost, rpc.RpcError, OSError):
            pass

    async def _locality_target(self, spec: dict) -> bytes | None:
        """Locality-aware placement (reference lease_policy.h +
        hybrid_scheduling_policy's locality term): when a task's plasma
        deps weigh more than locality_min_bytes, prefer the alive node
        already holding the most dependency bytes — moving the task beats
        moving the data."""
        deps = spec.get("deps") or []
        if not deps or spec.get("pg_id") or spec.get("scheduling_strategy") \
                or spec.get("_spills", 0) >= SPILL_MAX:
            return None
        # cheap outs before a head round-trip: single-node clusters and
        # all-deps-local submissions gain nothing from the directory
        if not any(v.get("alive") and nid != self.node_id
                   for nid, v in self.cluster_view.items()):
            return None
        if all(self.store.contains(d) for d in deps):
            return None
        try:
            info = await self.head.call(
                "object_locations_bulk", {"object_ids": list(deps)},
                timeout=2.0,
            )
        except (rpc.ConnectionLost, rpc.RpcError, asyncio.TimeoutError):
            return None
        per_node: dict[bytes, float] = {}
        for meta in info.values():
            weight = float(meta.get("size") or 1)
            for nid in meta["locations"]:
                per_node[nid] = per_node.get(nid, 0.0) + weight
        if not per_node:
            return None
        best, best_bytes = max(per_node.items(), key=lambda kv: kv[1])
        if best_bytes < cfg.get("locality_min_bytes"):
            return None
        need = spec.get("resources", {})
        if best == self.node_id:
            return None  # local queueing path handles it
        view = self.cluster_view.get(best)
        if view is None or not view.get("alive"):
            return None
        if all(view.get("resources_total", {}).get(r, 0) >= v
               for r, v in need.items()):
            return best
        return None

    def _choose_node(self, spec: dict) -> bytes | None:
        """Hybrid policy (hybrid_scheduling_policy.h:29): local first while
        it fits; else the alive node with best availability."""
        need = spec.get("resources", {})
        if spec.get("pg_id"):
            # PG tasks must run where the bundle is committed
            key = (spec["pg_id"], spec.get("bundle_index", 0))
            if key in self.bundle_available:
                return self.node_id
            pg_nodes = spec.get("bundle_nodes")
            if pg_nodes:
                return pg_nodes[spec.get("bundle_index", 0)]
            return self.node_id
        strategy = spec.get("scheduling_strategy")
        if isinstance(strategy, dict) and strategy.get("node_id"):
            return strategy["node_id"]  # node affinity
        if self._native_sched is not None:
            return self._native_choose(spec, need,
                                       spread=(strategy == "SPREAD"))
        if self._fits(need, self.resources_available):
            return self.node_id
        if not self._fits(need, self.resources_total):
            # can never run here; find any node whose total fits
            best, best_avail = None, -1.0
            for nid, view in self.cluster_view.items():
                if not view.get("alive") or nid == self.node_id:
                    continue
                tot = view.get("resources_total", {})
                if all(tot.get(r, 0) >= v for r, v in need.items()):
                    avail = view.get("resources_available", {}).get("CPU", 0)
                    if avail > best_avail:
                        best, best_avail = nid, avail
            return best
        # fits in total but busy now: spill if a peer has free capacity
        best, best_avail = None, 0.0
        for nid, view in self.cluster_view.items():
            if not view.get("alive") or nid == self.node_id:
                continue
            av = view.get("resources_available", {})
            if all(av.get(r, 0) >= v for r, v in need.items()):
                score = av.get("CPU", 0)
                if score > best_avail:
                    best, best_avail = nid, score
        if best is not None:
            return best
        return self.node_id  # queue locally

    def _native_choose(self, spec: dict, need: dict,
                       spread: bool = False) -> bytes | None:
        """Hybrid top-k placement via the C++ core (_native/scheduler.cc).

        The native view is resynced from the gossiped cluster_view each
        decision (tens of nodes x a handful of resources — microseconds in
        C++), so there is exactly one source of truth and no incremental-
        update drift.
        """
        sched = self._native_sched
        local_hex = self.node_id.hex()
        sched.upsert_node(local_hex, self.resources_total,
                          self.resources_available)
        seen = {local_hex}
        for nid, view in self.cluster_view.items():
            if nid == self.node_id:
                continue
            hid = nid.hex()
            seen.add(hid)
            sched.upsert_node(
                hid,
                view.get("resources_total", {}),
                view.get("resources_available", {}),
                alive=bool(view.get("alive")),
            )
        for hid in (self._native_known or set()) - seen:  # departed nodes
            sched.remove_node(hid)
        self._native_known = seen
        from ray_tpu._native.scheduler import PICK_PLACED, PICK_QUEUE

        status, node = sched.pick(
            need,
            local_node_id=local_hex,
            threshold=cfg.get("scheduler_hybrid_threshold"),
            top_k=cfg.get("scheduler_top_k"),
            spread=spread,
            seed=int.from_bytes(spec.get("task_id", b"\0")[:8], "little"),
        )
        if status == PICK_PLACED and node:
            return bytes.fromhex(node)
        if status == PICK_QUEUE:
            # Busy everywhere: queue locally when this node could ever run
            # it, else queue at the least-utilized feasible node.
            if self._fits(need, self.resources_total):
                return self.node_id
            return bytes.fromhex(node) if node else None
        return None  # infeasible cluster-wide

    _native_known: set | None = None

    async def _forward_task(self, spec: dict, node_id: bytes) -> bool:
        cli = await self._peer_agent(node_id)
        if cli is None:
            return False
        fwd = {k: v for k, v in spec.items() if not k.startswith("_")}
        fwd["_spills"] = spec["_spills"]
        try:
            await cli.call("submit_task", fwd)
        except (rpc.ConnectionLost, rpc.RpcError):
            return False
        # the SENDER also tells the owner where the task went: if the
        # target dies before its own task_located fires, the owner would
        # otherwise never associate the task with the dead node — the
        # task silently vanishes (no retry, get() hangs)
        if spec.get("owner"):
            asyncio.ensure_future(
                self._notify_task_located(spec, node_id)
            )
        return True

    async def _peer_agent(self, node_id: bytes) -> AsyncRpcClient | None:
        cli = self._peer_clients.get(node_id)
        if cli is not None and not cli.closed:
            return cli
        view = self.cluster_view.get(node_id)
        if view is None or not view.get("alive"):
            return None
        cli = AsyncRpcClient(view["addr"], view["port"])
        try:
            await cli.connect(retries=3)
        except rpc.ConnectionLost:
            return None
        self._peer_clients[node_id] = cli
        return cli

    def _kick_dispatch(self):
        ev = getattr(self, "_dispatch_ev", None)
        if ev is not None:
            ev.set()

    async def _dispatch_loop(self):
        """LocalTaskManager: stage deps → grant resources → run
        (local_task_manager.cc:101 DispatchScheduledTasksToWorkers)."""
        self._dispatch_ev = asyncio.Event()
        while not self._dead:
            self._dispatch_ev.clear()
            progressed = await self._dispatch_once()
            if not progressed:
                try:
                    await asyncio.wait_for(self._dispatch_ev.wait(),
                                           timeout=0.2)
                except asyncio.TimeoutError:
                    pass

    async def _dispatch_once(self) -> bool:
        if not self.task_queue:
            return False
        progressed = False
        # worker availability is a dispatch resource (reference
        # LocalTaskManager waits on PopWorker): dispatch at most as many
        # tasks as there are idle pool workers + spawn headroom this tick.
        # Already-granted tasks still waiting in _pop_worker count against
        # the room, or back-to-back ticks (no await between grants and
        # worker spawns) would over-grant the whole queue.
        room = self._pool_worker_cap() - getattr(self, "_pop_waiters", 0)
        depth = cfg.get("pool_dispatch_depth")
        for w in self.workers.values():
            if w.actor_id is None and not w.blocked \
                    and not (w.idle and w.ready.is_set()):
                # blocked workers don't consume room (their slot is
                # backfillable — _pop_worker excludes them from the cap),
                # and a pipeline-capable busy worker can absorb at least
                # one more task into its exec queue
                if (w.busy_task is None and w.ready.is_set()
                        and 0 < len(w.pool_inflight) < depth):
                    continue
                room -= 1
        # Bound the saturated scan: when nothing is being granted (no
        # worker room or no resources), rotating the whole queue per tick
        # is O(n^2) churn across a drain (each task_done kicks a tick).
        # A look-ahead window still finds smaller shapes queued behind
        # big ones and keeps dep prefetch warm for imminent tasks.
        stalled = 0
        for _ in range(len(self.task_queue)):
            if stalled > 128:
                break
            spec = self.task_queue.popleft()
            pool = self._task_pool(spec)
            if pool is None:
                # PG bundle not here (yet) — requeue
                self.task_queue.append(spec)
                stalled += 1
                continue
            need = spec.get("resources", {})
            if (pool is self.resources_available
                    and self._actor_reservations
                    and not self._fits_with_reservations(need)):
                # a pending actor has dibs on the next freed resources
                self.task_queue.append(spec)
                stalled += 1
                continue
            if not self._fits(need, pool):
                # A task this node can never satisfy re-evaluates the
                # cluster as nodes join (autoscaled capacity) instead of
                # queueing forever.
                if (not spec.get("pg_id")
                        and not self._fits(need, self.resources_total)
                        and spec.get("_spills", 0) < SPILL_MAX):
                    target = self._choose_node(spec)
                    if target is not None and target != self.node_id:
                        spec["_spills"] += 1
                        if await self._forward_task(spec, target):
                            progressed = True
                            continue
                        spec["_spills"] -= 1
                self.task_queue.append(spec)
                stalled += 1
                continue
            deps = spec.get("deps", [])
            missing = [d for d in deps if not self.store.contains(d)
                       and not self._is_inline(d, spec)]
            if missing:
                now = time.monotonic()
                # the submitter's consumer tags (weights broadcast, kv
                # handoff, checkpoint restore) attribute the dep pulls
                ftags = spec.get("fetch_tags") or None
                if not spec.get("_fetching"):
                    spec["_fetching"] = True
                    spec["_fetching_since"] = now
                    for d in missing:
                        asyncio.ensure_future(self._ensure_local(
                            d, priority=pull_manager.PRI_TASK_ARG,
                            tags=ftags))
                elif now - spec.get("_fetching_since", now) > DEP_LOST_S:
                    # No copy appeared anywhere: tell the owner so it can
                    # lineage-reconstruct (object_recovery_manager.h:90),
                    # then restart the fetch cycle for the recomputed copy.
                    if spec.get("owner"):
                        for d in missing:
                            asyncio.ensure_future(
                                self._notify_dep_lost(spec, d))
                    spec["_fetching_since"] = now
                    for d in missing:
                        asyncio.ensure_future(self._ensure_local(
                            d, priority=pull_manager.PRI_TASK_ARG,
                            tags=ftags))
                self.task_queue.append(spec)
                stalled += 1
                continue
            if room <= 0:
                # every pool worker is busy and the pool is at cap: leave
                # the task queued; _kick_dispatch fires when a worker
                # frees.
                self.task_queue.append(spec)
                stalled += 1
                continue
            room -= 1
            if spec.get("pg_id") and (spec.get("bundle_index", 0) or 0) < 0:
                # pin the any-bundle choice at GRANT time (no await since
                # the scan above, so the fitting bundle is unchanged)
                pool = self._task_pool(spec, pin=True)
            self._take(need, pool)
            spec["_granted"] = True
            stalled = 0
            progressed = True
            # count the waiter AT GRANT TIME: ensure_future only schedules
            # _run_task, and this loop can tick many times before it runs —
            # counting inside _run_task left room computed against stale
            # state, granting the entire queue in one burst (observed
            # _pop_waiters at -545 equivalents)
            self._pop_waiters = getattr(self, "_pop_waiters", 0) + 1
            asyncio.ensure_future(self._run_task(spec))
        return progressed

    def _fits_with_reservations(self, need: dict) -> bool:
        """Does `need` fit after pending actor reservations are held back?"""
        shadow = dict(self.resources_available)
        for res in self._actor_reservations:
            for r, v in res.items():
                shadow[r] = shadow.get(r, 0) - v
        return self._fits(need, shadow)

    def _is_inline(self, dep: bytes, spec: dict) -> bool:
        return dep in spec.get("inline_deps", ())

    async def _run_task(self, spec: dict):
        # NOTE: the matching _pop_waiters increment happened at grant time
        # in _dispatch_once (see comment there)
        try:
            w = await self._pop_worker(
                spec.get("job_id"),
                n_chips=accelerator.chips_for(spec.get("resources")),
                runtime_env=spec.get("runtime_env"),
                allow_pipeline=True,
            )
        except self.PoolSaturated:
            # node healthy, merely at its worker cap for the whole wait
            # budget: requeue rather than fail the task
            self._free_task_resources(spec)
            spec.pop("_granted", None)
            self.task_queue.append(spec)
            self._kick_dispatch()
            return
        except Exception as e:  # noqa: BLE001 — any spawn failure
            # (register timeout, exec OSError, runtime_env plugin create
            # error, bad pip config …) must free the granted resources
            # and fail the task — an escape here leaks the CPUs forever
            # and hangs the owner's get()
            self._free_task_resources(spec)
            await self._notify_task_failed(spec,
                                           f"worker spawn failed: {e!r}")
            return
        finally:
            self._pop_waiters -= 1
        if w.busy_task == self._RESERVED:
            w.busy_task = None  # reservation consumed by this dispatch
        w.pool_inflight.add(spec["task_id"])
        self.running[spec["task_id"]] = spec
        spec["_worker_id"] = w.worker_id
        try:
            # coalesced fire: dispatch bursts cost one send() per loop
            # tick instead of one per task
            w.client.fire(
                "execute_task",
                {k: v for k, v in spec.items() if not k.startswith("_")},
            )
        except (rpc.ConnectionLost, rpc.RpcError, OSError) as e:
            self.running.pop(spec["task_id"], None)
            w.pool_inflight.discard(spec["task_id"])
            self._signal_worker_free()
            self._free_task_resources(spec)
            await self._notify_task_failed(spec, f"dispatch failed: {e}")
            return
        tid = spec["task_id"]
        while w.blocked and len(w.pool_inflight) > 1:
            # the worker blocked while this dispatch was in flight: the
            # blocked-fire's reclaim may have run before our send hit
            # the wire, leaving this task stranded behind the parked
            # thread — drain again (idempotent). Bounded retry rather
            # than a one-shot: RPC handlers dispatch via ensure_future,
            # so nothing guarantees the worker enqueued our task before
            # a drain scan ran; retry until the task is reclaimed, done,
            # or the worker unparks (50ms grain, worker enqueue is µs).
            await self._reclaim_pipelined(w, w._parked_tid)
            cur = self.running.get(tid)
            if cur is None or cur.get("_worker_id") != w.worker_id:
                break  # reclaimed (requeued) or already completed
            await asyncio.sleep(0.05)

    # -- worker leases (reference direct_task_transport.h:110
    # RequestNewWorkerIfNeeded + lease caching per SchedulingKey): the
    # owner leases a granted worker once, then pushes repeat same-shape
    # tasks straight to it, skipping the agent's queue/dispatch hop. --

    @property
    def LEASE_TTL_S(self):  # read per call: honors late config overrides
        return cfg.get("worker_lease_ttl_s")

    def _shape_spillable(self, need: dict) -> bool:
        """Could any OTHER alive node's total resources fit this shape?
        Refusals carry this bit so owners know whether pipelining onto an
        existing lease would steal work from cluster spillback."""
        return any(
            v.get("alive") and nid != self.node_id
            and all(v.get("resources_total", {}).get(r, 0) >= x
                    for r, x in need.items() if x > 0)
            for nid, v in self.cluster_view.items()
        )

    async def rpc_lease_worker(self, conn, p):
        need = p.get("resources", {})
        refusal = {"spillable": self._shape_spillable(need)}
        if self.task_queue or getattr(self, "_pop_waiters", 0) > 0:
            # Queued work dispatches first: lease grants + their
            # background spawns otherwise consume every pool slot and a
            # single queued task starves until the lease traffic
            # quiesces (observed: one queued num_cpus=0 task waited 4s
            # in _pop_worker behind 299 lease pushes, gating its whole
            # batch). Owners fall back to their existing leases
            # (depth-10 pipelining) or queued submission.
            return refusal
        cap = self._pool_worker_cap()
        # leases never monopolize the pool: the queued-dispatch path
        # keeps a slice of worker slots it can claim without waiting for
        # lease traffic to quiesce. Tiny pools (cap < 4) reserve nothing
        # — a 1-slot reserve there would disable leasing outright.
        reserve = max(1, cap // 8) if cap >= 4 else 0
        if len(self.leases) >= cap - reserve:
            return refusal
        if not self._fits(need, self.resources_available):
            return refusal  # busy: owner falls back to queued submission
        if self._actor_reservations and not self._fits_with_reservations(
            need
        ):
            # a pending actor has dibs — the fast path must honor the
            # same holdback as the dispatch loop or leases starve actors
            return refusal
        # take BEFORE the await: worker spawn can suspend for seconds and
        # the dispatch loop (or a concurrent lease) would double-book the
        # same resources
        self._take(need, self.resources_available)
        try:
            # wait=False: the lease fast path must not camp on granted
            # resources at the pool cap — returning None makes the owner
            # fall back to queued submission
            w = await self._pop_worker(
                p.get("job_id"), n_chips=accelerator.chips_for(need),
                runtime_env=p.get("runtime_env"), wait=False,
                spawn_wait=False,
            )
        except (asyncio.TimeoutError, OSError):
            w = None
        if w is None:
            for r, v in need.items():
                self._release(r, v)
            return refusal
        lease_id = os.urandom(8)
        w.busy_task = b"__lease__" + lease_id
        now = time.monotonic()
        self.leases[lease_id] = {
            "worker_id": w.worker_id,
            "resources": dict(need),
            "expires": now + self.LEASE_TTL_S,
            "active": set(),  # in-flight direct-pushed task ids (owner
            # pipelines up to worker_lease_depth onto one lease)
            "last_activity": now,
            "owner": p.get("owner"),
        }
        return {"lease_id": lease_id, "worker_id": w.worker_id,
                "addr": w.addr, "port": w.port,
                "ttl_s": self.LEASE_TTL_S,
                # grants carry the spill bit too: an owner that hits its
                # lease cap without ever seeing a refusal must still know
                # whether owner-side queueing would steal spillback work
                "spillable": refusal["spillable"]}

    async def rpc_renew_lease(self, conn, p):
        lease = self.leases.get(p["lease_id"])
        if lease is None:
            return False
        now = time.monotonic()
        lease["expires"] = now + self.LEASE_TTL_S
        lease["last_activity"] = now
        return True

    async def rpc_return_lease(self, conn, p):
        return self._release_lease(p["lease_id"])

    async def rpc_lease_tasks_lost(self, conn, p):
        """Owner's liveness probe confirmed these direct-pushed tasks
        never reached the leased worker (lost execute_task fire): drop
        them from the lease's active set and `running` so the lease can
        expire/reclaim normally instead of being extended forever for
        tasks that will never run — the other half of the owner-side
        failover (the owner resubmits them through the queue)."""
        lease = self.leases.get(p["lease_id"])
        now = time.monotonic()
        for tid in p.get("task_ids", ()):
            if lease is not None:
                lease["active"].discard(tid)
            spec = self.running.get(tid)
            if spec is not None and spec.get("_lease_id") == p["lease_id"]:
                self.running.pop(tid, None)
            # a released lease migrates its actives to pool_inflight
            # (_release_lease): scrub those too, or the worker stays
            # pinned busy for a push that never arrived
            for w in self.workers.values():
                if tid in w.pool_inflight:
                    w.pool_inflight.discard(tid)
                    if not w.pool_inflight and w.busy_task is None:
                        w.idle_since = now
                        self._signal_worker_free()
        if lease is not None:
            lease["last_activity"] = now
        self._kick_dispatch()
        return True

    async def rpc_lease_tasks_started(self, conn, p):
        """Batched lease_task_started (owners buffer per burst: the
        per-frame dispatch cost on this loop is the multi-owner
        throughput ceiling)."""
        for item in p["items"]:
            await self.rpc_lease_task_started(conn, item)
        return True

    async def rpc_lease_task_started(self, conn, p):
        """Owner pushed a task to its leased worker: track it so the
        worker-death path can notify the owner (the push itself skipped
        this agent)."""
        lease = self.leases.get(p["lease_id"])
        if lease is None:
            return False
        spec = p["spec"]
        tid = spec["task_id"]
        if tid in self._done_before_started:
            # the worker's task_done outran this fire — never register a
            # spec for an already-finished task (it would leak forever)
            self._done_before_started.discard(tid)
            return True
        spec["_leased"] = True
        spec["_lease_id"] = p["lease_id"]
        spec["_worker_id"] = lease["worker_id"]
        lease["active"].add(tid)
        lease["last_activity"] = time.monotonic()
        self.running[tid] = spec
        return True

    def _release_lease(self, lease_id: bytes) -> bool:
        lease = self.leases.pop(lease_id, None)
        if lease is None:
            return False
        if not lease.pop("_blocked_released", None):
            # blocked-borrow already released them (rpc_worker_blocked)
            for r, v in lease["resources"].items():
                self._release(r, v)
        w = self.workers.get(lease["worker_id"])
        if w is not None:
            w.busy_task = None
            # Direct-pushed tasks can STILL be executing on this worker
            # (owner returned the lease while a long task runs, e.g. one
            # blocked on nested work): migrate them to pool_inflight so
            # the worker is NOT treated as idle — re-leasing or
            # dispatching onto it would starve the new work behind the
            # running task (observed: 10 pushed tasks lost per lease).
            for tid in lease.get("active", ()):
                if tid in self.running:
                    w.pool_inflight.add(tid)
                    self.running[tid]["_lease_migrated"] = True
            if not w.pool_inflight:
                w.idle_since = time.monotonic()
            self._signal_worker_free()
        if lease.get("owner"):
            # agent-initiated revocation (TTL lapse / actor reclaim): tell
            # the owner so its cache doesn't push to an unleased worker
            asyncio.ensure_future(self._notify_lease_revoked(lease))
        self._kick_dispatch()
        return True

    async def _notify_lease_revoked(self, lease: dict):
        try:
            cli = await self._peer_worker(lease["owner"])
            if cli is not None:
                await cli.oneway("lease_revoked", {
                    "worker_id": lease["worker_id"],
                })
        except (rpc.ConnectionLost, rpc.RpcError, OSError):
            pass

    async def rpc_dump_stacks(self, conn, p):
        """Aggregate thread stacks across this node's workers (dashboard
        profiling endpoint; reference reporter_agent.py:348)."""
        out = []
        for w in list(self.workers.values()):
            if w.client is None or w.client.closed:
                continue
            try:
                out.append(await w.client.call("dump_stacks", {},
                                               timeout=5.0))
            except (rpc.ConnectionLost, rpc.RpcError,
                    asyncio.TimeoutError):
                pass
        return {"node_id": self.node_id, "workers": out}

    async def rpc_profile_workers(self, conn, p):
        """Sample-profile every worker on this node CONCURRENTLY for
        duration_s (reporter_agent.py:355 CpuProfiling analog)."""
        duration = float(p.get("duration_s", 2.0))
        calls = []
        targets = []
        for w in list(self.workers.values()):
            if w.client is None or w.client.closed:
                continue
            targets.append(w)
            calls.append(w.client.call(
                "profile",
                {"duration_s": duration,
                 "interval_s": p.get("interval_s", 0.01)},
                timeout=duration + 15.0,
            ))
        results = await asyncio.gather(*calls, return_exceptions=True)
        out = []
        for w, r in zip(targets, results):
            if isinstance(r, dict):
                out.append(r)
            else:  # a failed profile must be visible, not a missing row
                out.append({"worker_id": w.worker_id, "samples": {},
                            "error": repr(r)})
        return {"node_id": self.node_id, "workers": out}

    async def rpc_tasks_done(self, conn, p):
        """Batched leased-task completions (executors flush every ~50ms;
        lease active-set bookkeeping tolerates the latency)."""
        for tid in p["task_ids"]:
            self._task_done_one(tid)
        self._kick_dispatch()
        return True

    async def rpc_worker_blocked(self, conn, p):
        """Worker parked in get() on nested work (reference
        NotifyDirectCallTaskBlocked): free its pool slot AND the blocked
        task's granted CPUs so dispatch can backfill — N workers blocked
        on nested num_cpus>=1 children must not wedge the node on either
        the slot axis or the resource axis."""
        w = self.workers.get(p["worker_id"])
        if w is not None:
            w.blocked += 1
            w._parked_tid = p.get("task_id") or b""
            spec = self.running.get(p.get("task_id") or b"")
            if spec is not None and spec.get("_granted") \
                    and not spec.get("_blocked_released"):
                # release while parked; re-taken on unblock (temporary
                # oversubscription, same as the reference's CPU borrow).
                # _free_task_resources clears _granted, so a death or
                # completion in the window cannot double-free.
                self._free_task_resources(spec)
                spec["_blocked_released"] = True
            self._signal_worker_free()  # a slot just opened
            # A LEASED worker parked in a nested get holds its lease's
            # resources with no per-task grant to borrow from — on a
            # full node that starves the very producer task the parked
            # one waits on (observed: 4 blocked reduce leases pinning
            # all 4 CPUs while one map task sat queued forever).
            # Borrow the LEASE's resources while any of its tasks is
            # parked; re-taken on unblock, same temporary
            # oversubscription contract as the per-task release above.
            if w.busy_task and w.busy_task.startswith(b"__lease__"):
                lease = self.leases.get(w.busy_task[len(b"__lease__"):])
                if lease is not None \
                        and not lease.get("_blocked_released"):
                    for r, v in lease["resources"].items():
                        self._release(r, v)
                    lease["_blocked_released"] = True
            self._kick_dispatch()
            await self._reclaim_pipelined(w, p.get("task_id") or b"")
        return True

    async def _reclaim_pipelined(self, w, parked_tid: bytes):
        """Pull the blocked worker's queued-but-unstarted pipelined tasks
        back into the agent queue. The dispatch guard (`not w.blocked`)
        can't close the race where a child lands in the window between
        its parent's submit and the worker_blocked fire: the child would
        then sit in the exec queue behind a parent parked in get() ON
        that child — a permanent hang. Drain is cooperative: the worker
        returns only ids it actually pulled, so nothing double-runs."""
        cands = [t for t in w.pool_inflight
                 if t != parked_tid and t in self.running
                 and not self.running[t].get("_leased")]
        if not cands or w.client is None or w.client.closed:
            return
        try:
            r = await w.client.call("drain_pending", {"task_ids": cands},
                                    timeout=5.0)
        except (rpc.ConnectionLost, rpc.RpcError, OSError,
                asyncio.TimeoutError):
            return  # worker died/hung: the reap path fails tasks over
        for tid in r["task_ids"]:
            spec = self.running.pop(tid, None)
            if spec is None:
                continue
            w.pool_inflight.discard(tid)
            self._free_task_resources(spec)
            spec.pop("_granted", None)
            spec.pop("_worker_id", None)
            self.task_queue.append(spec)
        if r["task_ids"]:
            if not w.pool_inflight:
                w.idle_since = time.monotonic()
            self._signal_worker_free()
            self._kick_dispatch()

    async def rpc_worker_unblocked(self, conn, p):
        w = self.workers.get(p["worker_id"])
        if w is not None and w.blocked > 0:
            w.blocked -= 1
            if not w.blocked:
                w._parked_tid = b""
                if w.busy_task and w.busy_task.startswith(b"__lease__"):
                    lease = self.leases.get(
                        w.busy_task[len(b"__lease__"):])
                    if lease is not None \
                            and lease.pop("_blocked_released", None):
                        # re-take even into negative availability: the
                        # leased tasks resume NOW (mirror of the
                        # per-task re-take below)
                        self._take(lease["resources"],
                                   self.resources_available)
        spec = self.running.get(p.get("task_id") or b"")
        if spec is not None and spec.pop("_blocked_released", None):
            # re-take even if it drives availability negative: the task
            # resumes NOW; new grants wait until the pool recovers
            pool = self._task_pool(spec)
            if pool is not None:
                self._take(spec.get("resources", {}), pool)
                spec["_granted"] = True
        return True

    async def rpc_task_done(self, conn, p):
        """Worker reports completion; frees resources, worker back to pool."""
        self._task_done_one(p["task_id"])
        self._kick_dispatch()
        return True

    def _task_done_one(self, tid: bytes):
        spec = self.running.pop(tid, None)
        if spec is None:
            # possibly a leased task whose started-fire hasn't landed yet
            self._done_before_started.add(tid)
            self._done_order.append(tid)
            while len(self._done_order) > 10_000:  # bounded, evict oldest
                self._done_before_started.discard(self._done_order.popleft())
        elif spec.get("_leased"):
            # lease holds the resources/worker until returned or expired
            lease = self.leases.get(spec.get("_lease_id", b""))
            if lease is not None:
                lease["active"].discard(tid)
                lease["last_activity"] = time.monotonic()
            elif spec.get("_lease_migrated"):
                # lease was released mid-task; the task was migrated to
                # pool_inflight accounting (resources already freed with
                # the lease — only the idle bit needs clearing here)
                w = self.workers.get(spec.get("_worker_id", b""))
                if w is not None:
                    w.pool_inflight.discard(tid)
                    if not w.pool_inflight:
                        w.idle_since = time.monotonic()
                    self._signal_worker_free()
        else:
            self._free_task_resources(spec)
            w = self.workers.get(spec.get("_worker_id", b""))
            if w is not None:
                w.pool_inflight.discard(tid)
                if not w.pool_inflight:
                    w.idle_since = time.monotonic()
                # below-depth again: waiters may pipeline onto it
                self._signal_worker_free()

    async def rpc_cancel_task(self, conn, p):
        tid = p["task_id"]
        for i, spec in enumerate(self.task_queue):
            if spec["task_id"] == tid:
                del self.task_queue[i]
                await self._notify_task_failed(spec, "cancelled",
                                               retriable=False)
                return {"cancelled": "queued"}
        spec = self.running.get(tid)
        if spec is not None and p.get("force"):
            w = self.workers.get(spec.get("_worker_id", b""))
            if w is not None:
                self._kill_worker(w)
            # _kill_worker removed the handle, so the reap loop will never
            # see this death — clean up the task here.
            self.running.pop(tid, None)
            if spec.get("_leased"):
                # the LEASE holds this worker's resources (direct-pushed
                # task): release it — a stale entry with the cancelled
                # task still in its active set would never expire and
                # leak the cpu — and fail over any other tasks pipelined
                # onto the killed worker.
                lease_id = spec.get("_lease_id", b"")
                self._release_lease(lease_id)
                for otid, ospec in list(self.running.items()):
                    if ospec.get("_lease_id") == lease_id:
                        self.running.pop(otid, None)
                        await self._notify_task_failed(
                            ospec, "leased worker killed by cancel"
                        )
            else:
                self._free_task_resources(spec)
            self._kick_dispatch()
            await self._notify_task_failed(spec, "cancelled",
                                           retriable=False)
            return {"cancelled": "running"}
        if spec is not None:
            # found but force=False: tell the owner the task IS here so it
            # doesn't treat the reply as "maybe still in a submit batch"
            return {"cancelled": "running_noforce"}
        return {"cancelled": None}

    # ---------------- actors ----------------

    async def rpc_start_actor(self, conn, p):
        """Control plane placed an actor here: reserve + spawn + create.

        PG actors draw from their committed bundle's pool (mirroring
        _task_pool; reference converts bundles to indexed resources that PG
        actors consume instead of the node pool)."""
        need = p.get("resources", {})
        bundle_key = None
        if p.get("pg_id"):
            bidx = p.get("bundle_index", -1)
            keys = ([(p["pg_id"], bidx)] if bidx >= 0 else
                    [k for k in self.bundle_available if k[0] == p["pg_id"]])
            for key in keys:
                pool = self.bundle_available.get(key)
                if pool is not None and self._fits(need, pool):
                    bundle_key = key
                    break
            if bundle_key is None:
                raise rpc.RpcError("insufficient resources in pg bundle")
            self._take(need, self.bundle_available[bundle_key])
        else:
            if not self._fits(need, self.resources_available):
                # Actor-priority wait: a saturating task flood must not
                # starve actor creation (tasks would otherwise grab every
                # freed cpu; with tasks blocked on this very actor that
                # deadlocks). The reservation makes the dispatch loop
                # leave room, and idle worker leases are reclaimed.
                if not await self._wait_for_actor_resources(need):
                    raise rpc.RpcError("insufficient resources")
            self._take(need, self.resources_available)
        asyncio.ensure_future(self._start_actor_async(p, need, bundle_key))
        return True

    async def _wait_for_actor_resources(self, need: dict,
                                        timeout: float = 60.0) -> bool:
        self._actor_reservations.append(need)
        try:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if self._fits(need, self.resources_available):
                    return True
                # Idle leases give way to actors — but only past the
                # OWNER's own reuse horizon (0.8*TTL since last activity,
                # plus slack): inside that window the owner may reserve-
                # and-push at any moment without asking the agent, so
                # reclaiming would double-book the worker.
                now_ = time.monotonic()
                grace = self.LEASE_TTL_S * 0.9
                for lease_id, lease in list(self.leases.items()):
                    if (not lease.get("active")  # empty set = no in-flight
                            and now_ - lease.get("last_activity", 0)
                            > grace):
                        self._release_lease(lease_id)
                        break
                if self._fits(need, self.resources_available):
                    return True
                await asyncio.sleep(0.05)
            return self._fits(need, self.resources_available)
        finally:
            self._actor_reservations.remove(need)

    async def _start_actor_async(self, p: dict, need: dict,
                                 bundle_key=None):
        try:
            try:
                w = await self._spawn_worker_registered(
                    p.get("job_id"), n_chips=accelerator.chips_for(need),
                    runtime_env=p.get("runtime_env"), reserve=True,
                )
            except asyncio.TimeoutError:
                raise rpc.RpcError(
                    "actor worker failed to register within "
                    f"{cfg.get('worker_register_timeout_s')}s "
                    "(startup timeout)") from None
            w.busy_task = None  # reservation consumed
            w.actor_id = p["actor_id"]
            w.actor_resources = need
            w.actor_bundle = bundle_key
            await w.client.call("create_actor", {
                "actor_id": p["actor_id"], "spec": p["spec"],
                "max_concurrency": p.get("max_concurrency", 1),
                "concurrency_groups": p.get("concurrency_groups") or {},
                "method_groups": p.get("method_groups") or {},
            }, timeout=cfg.get("actor_create_timeout_s"))
            await self.head.call("actor_started", {
                "actor_id": p["actor_id"], "addr": w.addr, "port": w.port,
                "worker_id": w.worker_id,
            })
        except Exception as e:  # noqa: BLE001 — any failure fails the actor
            logger.warning("actor start failed: %s", e)
            for r, v in need.items():
                self._release(r, v, bundle_key)
            try:
                await self.head.call("actor_failed", {
                    "actor_id": p["actor_id"],
                    "reason": f"creation failed: {e}",
                })
            except (rpc.ConnectionLost, rpc.RpcError):
                pass

    async def rpc_kill_actor_worker(self, conn, p):
        for w in list(self.workers.values()):
            if w.actor_id == p["actor_id"]:
                self._kill_worker(w)
                # reap path won't see it (already removed) → report here
                for r, v in (w.actor_resources or {}).items():
                    self._release(r, v, w.actor_bundle)
                await self.head.call("actor_failed", {
                    "actor_id": p["actor_id"],
                    "reason": p.get("reason", "killed"),
                })
                return True
        return False

    # ---------------- placement group bundles ----------------

    async def rpc_prepare_bundle(self, conn, p):
        key = (p["pg_id"], p["bundle_index"])
        need = p["resources"]
        if not self._fits(need, self.resources_available):
            return False
        self._take(need, self.resources_available)
        self.bundles[key] = {"resources": need, "state": "PREPARED"}
        return True

    async def rpc_commit_bundle(self, conn, p):
        key = (p["pg_id"], p["bundle_index"])
        b = self.bundles.get(key)
        if b is None:
            return False
        b["state"] = "COMMITTED"
        self.bundle_available[key] = dict(b["resources"])
        self._kick_dispatch()
        return True

    async def rpc_cancel_bundle(self, conn, p):
        key = (p["pg_id"], p["bundle_index"])
        b = self.bundles.pop(key, None)
        if b is not None:
            self._give(b["resources"], self.resources_available)
        self.bundle_available.pop(key, None)
        return True

    async def rpc_return_bundle(self, conn, p):
        return await self.rpc_cancel_bundle(conn, p)

    # ---------------- object manager ----------------

    async def rpc_read_object_chunk(self, conn, p):
        """Peer agents pull objects chunk by chunk (object_manager.cc:633).

        Outbound pacing (the pull-design analog of reference
        push_manager.h:29's per-peer in-flight windows): before serving
        another chunk, wait while THIS peer's transport write buffer
        holds more than transfer_outbound_window_bytes — a slow or
        flooded receiver backs up its own connection and only its own
        transfers pace; other peers' connections are independent. The
        sender's memory per peer stays bounded at window + one chunk.

        The wait is event-driven: the peer's transport water marks are
        set to the window once, and every waiter parks in drain() until
        the transport's resume_writing wakes them — ONE per-peer wakeup
        instead of N independent 5 ms poll loops. If the buffer is still
        over the window at the deadline the peer is flooded beyond
        pacing: refuse RETRYABLY ({"busy": True}) rather than stacking
        another chunk onto a connection already minutes behind. The
        drain wait is short (20s vs the old 60s poll) BECAUSE the
        refusal is retryable — the puller backs off client-side instead
        of pinning a server handler, and its own wall-clock budget then
        bounds how long one flooded location can stall a pull."""
        if fault_injection.enabled():
            act, delay_s = fault_injection.fire_async(
                "object.read_chunk", oid=p["object_id"].hex(),
                offset=p["offset"])
            if act in ("delay", "stall"):
                await asyncio.sleep(delay_s)
            elif act == "drop":
                # the chunk is "lost": surface it as the retryable busy
                # refusal so the puller's backoff path re-requests it
                return {"busy": True, "retry_after_s": 0.05}
        # QoS grant for the serve side, classed by the request's
        # self-declared {requester, qos, owner} tags. A denied window
        # rides the SAME retryable refusal as pacing/flooding — this is
        # exactly how an in-flight bulk transfer is preempted at chunk
        # granularity by a higher class: its next chunk parks client-side
        # and the resumed pull re-requests the same offset, byte-identical.
        try:
            from ray_tpu._private import net_qos as _qos

            hint = _qos.try_acquire(
                p.get("requester", "?"), p.get("qos", "bulk"),
                _chunk_size(), owner=p.get("owner", "unknown"))
        except Exception as e:  # NetPaceError (injected drop) included
            return {"busy": True, "retry_after_s": 0.1,
                    "paced": str(e)[:120]}
        if hint > 0:
            return {"busy": True, "retry_after_s": hint, "paced": True}
        if conn is not None:
            # Serve gate: ~2 chunks buffered per connection, not the full
            # window. Pipelining depth lives in the puller's OUTSTANDING
            # REQUESTS (queued here, resident and cheap) — responses
            # stream out of a small transport buffer at line rate. Large
            # buffered responses would be actively worse: asyncio's
            # transport memmoves its whole pending bytearray on every
            # partial send, so a 32MB backlog burns more memory bandwidth
            # than the payload itself. The configured window remains the
            # absolute flooded-peer cap.
            window = int(cfg.get("transfer_outbound_window_bytes"))
            gate = min(window, 2 * _chunk_size())
            if self._conn_write_buffered(conn) > gate:
                if not conn.state.get("paced"):
                    conn.state["paced"] = True
                    try:
                        conn.writer.transport.set_write_buffer_limits(
                            high=gate, low=max(1, gate // 2))
                    except Exception:  # noqa: BLE001 — transport mid-close
                        pass
                try:
                    await asyncio.wait_for(conn.drain(), timeout=20.0)
                except asyncio.TimeoutError:
                    return {"busy": True, "retry_after_s": 0.5}
            # per-peer inflight: this peer's transport write backlog is
            # exactly the bytes the pacing window is holding for it
            try:
                from ray_tpu._private import net_accounting as _net

                _net.set_inflight(p.get("requester", "?"),
                                  self._conn_write_buffered(conn))
            except Exception:  # noqa: BLE001 — gauge is best-effort
                pass
        return self._read_object_chunk(p, conn)

    @staticmethod
    def _conn_write_buffered(conn) -> int:
        try:
            return conn.writer.transport.get_write_buffer_size()
        except Exception:  # noqa: BLE001 — transport mid-close
            return 0

    def _read_object_chunk(self, p, conn=None):
        """Serve one chunk ZERO-COPY: the reply carries a memoryview
        slice of the pinned shm object through the rpc layer's
        out-of-band framing (no bytes() materialization, no msgpack
        re-framing); the pin is released only after the transport has
        consumed the view.

        The pin is cached per (connection, oid) across the transfer —
        one store_get/store_release pair per pull instead of one per
        chunk — and dropped on the final chunk, on disconnect, or by
        the TTL sweep (an abandoned puller must not pin the store)."""
        oid, offset = p["object_id"], p["offset"]
        pins = (conn.state.setdefault("serve_pins", {})
                if conn is not None else None)
        ent = pins.get(oid) if pins is not None else None
        buf = ent[0] if ent is not None else self.store.get(oid)
        if buf is None:
            # store miss but a spill file exists: serve the chunk from
            # disk through the SAME OOB framing — the puller reads a
            # spilled object without forcing the spilling node to
            # re-materialize it in its (already pressured) store first
            return self._read_spill_chunk(p, conn)
        total = buf.data.nbytes
        end = min(offset + _chunk_size(), total)
        view = buf.data[offset:end]
        meta = buf.metadata if offset == 0 else b""
        if conn is not None:
            # tx attribution from the puller's self-declared identity
            # ({requester, qos, owner} riding the chunk request) — the
            # exact mirror of the rx accounting on its side
            try:
                from ray_tpu._private import flight_recorder as _fr
                from ray_tpu._private import net_accounting as _net

                _net.account_tx(p.get("requester", "?"),
                                p.get("qos", "bulk"),
                                p.get("owner", "unknown"), end - offset)
                now = time.monotonic()
                _fr.record("transfer", "transfer.serve_chunk", now, now,
                           attrs={"oid": oid.hex()[:16], "offset": offset,
                                  "bytes": end - offset,
                                  "peer": p.get("requester", "?")},
                           flush=False)
            except Exception:  # noqa: BLE001 — serving must not fail
                pass
        if pins is None:
            # direct/local caller (no transport to hold the view for):
            # legacy inline copy, release immediately
            try:
                return {"total": total, "meta": meta,
                        "chunk": bytes(view)}
            finally:
                buf.release()
        # Release once this connection has served the whole object,
        # counted in BYTES — pipelined pulls complete out of order, so
        # "served the final offset" alone says nothing about earlier
        # chunks still in flight. The byte count lives OUTSIDE the pin
        # entry (serve_counts): out-of-order serving can release the
        # pin on the tail chunk while earlier chunks are still queued,
        # and those must re-pin WITHOUT resetting the count or the
        # re-pin never reaches total and holds the store until the TTL
        # sweep (a 1GB pull would strand 7x64MB behind such pins). A
        # striped pull splits the object across sources so no single
        # connection reaches total — the count is dropped once the full
        # object (or the tail) has been served, and stragglers fall to
        # the idle sweep (SERVE_PIN_TTL_S). A retried chunk can
        # double-count and release early; later chunks simply re-pin.
        counts = conn.state.setdefault("serve_counts", {})
        if ent is None:
            ent = pins[oid] = [buf, time.monotonic()]
        ent[1] = time.monotonic()
        cent = counts.get(oid)
        n = (cent[0] if cent is not None else 0) + (end - offset)
        if n >= total:
            # fully served — drop the count too
            counts.pop(oid, None)
        else:
            # keep the count even when the tail releases the pin below:
            # chunks still in flight re-pin and must keep accumulating.
            # The timestamp lets the sweep distinguish a live pin-less
            # count (tail released the pin, earlier chunks in flight)
            # from an abandoned one.
            counts[oid] = [n, time.monotonic()]
        if n >= total or end >= total:
            pins.pop(oid, None)
            release = buf.release
        else:
            release = None
        return OobReply({"total": total, "meta": meta}, [view],
                        release=release)

    def _read_spill_chunk(self, p, conn=None):
        """Serve one chunk of a SPILLED object straight from its spill
        file (layout: 8-byte meta_len | meta | data), closing the
        restore detour: a remote puller no longer needs the spilling
        node to reload the whole object into its store before the first
        chunk can flow. No pin is involved — the file is immutable
        until `delete_spilled` — so reads at any offset are safe, and
        each read is one bounded chunk (never the whole file) on the
        agent's loop."""
        oid, offset = p["object_id"], p["offset"]
        path = self.spilled_files.get(oid)
        if path is None:
            return None
        try:
            with open(path, "rb") as f:
                fsize = os.fstat(f.fileno()).st_size
                meta_len = int.from_bytes(f.read(8), "little")
                total = max(0, fsize - 8 - meta_len)
                if offset >= total and total:
                    return None
                meta = f.read(meta_len) if offset == 0 else b""
                f.seek(8 + meta_len + offset)
                chunk = f.read(min(_chunk_size(), total - offset))
        except OSError:
            return None
        if conn is not None:
            try:
                from ray_tpu._private import flight_recorder as _fr
                from ray_tpu._private import net_accounting as _net

                _net.account_tx(p.get("requester", "?"),
                                p.get("qos", "bulk"),
                                p.get("owner", "unknown"), len(chunk))
                now = time.monotonic()
                _fr.record("transfer", "transfer.serve_chunk", now, now,
                           attrs={"oid": oid.hex()[:16], "offset": offset,
                                  "bytes": len(chunk), "spill": True,
                                  "peer": p.get("requester", "?")},
                           flush=False)
            except Exception:  # noqa: BLE001 — serving must not fail
                pass
            return OobReply({"total": total, "meta": meta}, [chunk])
        return {"total": total, "meta": meta, "chunk": chunk}

    def _release_serve_pins(self, conn, *, older_than: float | None = None):
        pins = conn.state.get("serve_pins")
        if pins:
            now = time.monotonic()
            for oid, ent in list(pins.items()):
                if older_than is None or now - ent[1] > older_than:
                    pins.pop(oid, None)
                    ent[0].release()
        # served-byte counts that outlived their pin (striped pulls
        # never reach total on one connection) hold no store resource,
        # but prune them so the dict can't grow without bound. A
        # pin-less count can be LIVE, though: a pipelined pull's tail
        # chunk releases the pin while earlier chunks are still in
        # flight, and resetting the count then would strand the re-pin
        # until the TTL — so only prune counts idle past the same
        # older_than threshold as the pins (disconnect drops all).
        counts = conn.state.get("serve_counts")
        if counts:
            pins = conn.state.get("serve_pins") or {}
            now = time.monotonic()
            for oid, cent in list(counts.items()):
                if oid not in pins and (
                        older_than is None or now - cent[1] > older_than):
                    counts.pop(oid, None)

    async def _serve_pin_sweep_loop(self):
        while not self._dead:
            await asyncio.sleep(SERVE_PIN_TTL_S / 3)
            try:
                for conn in list(self.server.conns):
                    self._release_serve_pins(conn,
                                             older_than=SERVE_PIN_TTL_S)
            except Exception:  # noqa: BLE001 — sweep must not die
                logger.exception("serve-pin sweep failed")

    async def _on_server_disconnect(self, conn):
        self._release_serve_pins(conn)

    async def rpc_fetch_object(self, conn, p):
        """Local worker asks: make this object present in the node store.
        Optional {"qos", "owner"} tags declare the CONSUMER the pull
        serves (weights broadcast, kv handoff, checkpoint restore) —
        they ride into the pull's pacer grants and byte attribution so
        per-consumer transfer numbers fall out of net_accounting."""
        oid = p["object_id"]
        tags = None
        if p.get("qos") or p.get("owner"):
            tags = {"qos": str(p.get("qos") or "bulk"),
                    "owner": str(p.get("owner") or "unknown")}
        return bool(await self._ensure_local(
            oid, timeout=p.get("timeout", 60.0), tags=tags))

    async def _ensure_local(self, oid: bytes, timeout: float = 60.0,
                            priority: int = pull_manager.PRI_GET,
                            tags: dict | None = None) -> bool:
        """Make the object present locally via the pull scheduler:
        priority-ordered (task args > gets > restores) and admission-
        gated on store headroom (pull_manager.py; reference
        pull_manager.h:52). `tags` ({"qos", "owner"}) declare the
        consumer the pull serves; the scheduler dedups concurrent
        requests per oid, so the first declarer's tags win."""
        if self.store.contains(oid):
            return True
        own_tags = bool(tags) and oid not in self._fetch_tags
        if own_tags:
            self._fetch_tags[oid] = dict(tags)
        if self._pull_sched is None:
            self._pull_sched = pull_manager.PullScheduler(
                self._pull_object, self.store,
                max_active=cfg.get("pull_max_active"),
                watermark=cfg.get("pull_admission_watermark"))
        req = asyncio.ensure_future(
            self._pull_sched.request(oid, priority, timeout))
        if own_tags:
            # The tag entry must outlive the REQUEST, not this await:
            # the request is shielded, so a cancelled/timed-out caller
            # returns while the pull is still running and may not have
            # read its tags yet — a finally here would silently strip
            # the transfer's consumer attribution. Pop when the request
            # itself completes instead.
            req.add_done_callback(
                lambda _f: self._fetch_tags.pop(oid, None))
        return await asyncio.shield(req)

    async def _pull_object(self, oid: bytes, deadline: float,
                           reserve=lambda n: None) -> bool:
        # consumer tags declared by the fetch_object caller (read, not
        # popped: the declaring request's done-callback owns the
        # entry's lifetime, which spans this whole pull even if the
        # declaring RPC was cancelled mid-await)
        tags = self._fetch_tags.get(oid) or {}
        while time.monotonic() < deadline:
            try:
                info = await self.head.call("object_wait_location", {
                    "object_id": oid,
                    "timeout": max(0.1, deadline - time.monotonic()),
                })
            except (rpc.ConnectionLost, rpc.RpcError):
                # head restarting: the heartbeat loop reconnects; retry
                await asyncio.sleep(0.3)
                continue
            if info is None:
                return False
            reserve(info.get("size") or 0)  # admission sees these bytes
            if self.node_id in info["locations"]:
                return True  # a local writer beat us to it
            if not info["locations"] and info.get("spilled"):
                # only a spilled copy exists
                spill_node = bytes.fromhex(
                    info["spilled"].split("//", 1)[1].split("/", 1)[0]
                )
                if spill_node == self.node_id:
                    # already under this oid's admission slot: restore
                    # directly (re-entering the scheduler would dedup
                    # onto our own future and deadlock)
                    await self._restore_from_disk(oid)
                else:
                    cli = await self._peer_agent(spill_node)
                    if cli is not None:
                        # pull the chunks STRAIGHT off the peer's spill
                        # file (served by _read_spill_chunk through the
                        # same OOB framing as live objects) — no remote
                        # store re-materialization, no double transfer
                        try:
                            if await self._pull_from(
                                    [cli], oid, nids=[spill_node],
                                    owner=(tags.get("owner")
                                           or _owner_label(
                                               info.get("owner"))),
                                    qos=tags.get("qos", "bulk")):
                                await self.head.call(
                                    "object_add_location", {
                                        "object_id": oid,
                                        "node_id": self.node_id,
                                    })
                                self._kick_dispatch()
                                return True
                        except StoreFullError:
                            await asyncio.sleep(0.2)
                            continue
                        # direct spill read failed (file gone? agent
                        # mid-restart): fall back to the restore detour
                        # and loop for the live copy
                        try:
                            await cli.call("restore_object",
                                           {"object_id": oid})
                        except (rpc.ConnectionLost, rpc.RpcError):
                            pass
                await asyncio.sleep(0.05)
                continue
            pulled = False
            clis = []
            nids = []
            for nid in info["locations"]:
                cli = await self._peer_agent(nid)
                if cli is not None:
                    clis.append(cli)
                    nids.append(nid)
            if clis:
                try:
                    # every reachable holder goes in: the pipelined pull
                    # stripes its chunk window across all of them and
                    # fails over chunk-by-chunk
                    pulled = await self._pull_from(
                        clis, oid, nids=nids,
                        owner=(tags.get("owner")
                               or _owner_label(info.get("owner"))),
                        qos=tags.get("qos", "bulk"))
                except StoreFullError:
                    # store saturated even after LRU eviction: back off
                    # and retry within the deadline — the admission
                    # watermark keeps concurrent pulls from compounding
                    await asyncio.sleep(0.2)
            if pulled:
                await self.head.call("object_add_location", {
                    "object_id": oid, "node_id": self.node_id,
                })
                self._kick_dispatch()
                return True
            await asyncio.sleep(0.1)
        return False

    async def _read_chunk_backoff(self, cli: AsyncRpcClient, oid: bytes,
                                  offset: int, budget_s: float | None = None,
                                  attrib: dict | None = None,
                                  peer: str | None = None,
                                  into: memoryview | None = None):
        """read_object_chunk with bounded backoff on the server's
        retryable {"busy": True} refusal (its pacing deadline expired:
        our own connection is flooded, or the QoS window parked us
        behind a higher class). Bounded by WALL CLOCK, not
        attempt count — each refused attempt can itself block in the
        server's drain wait, so counting attempts alone could pin a pull
        on one flooded location for minutes. The backoff curve is live-
        tunable (transfer_busy_backoff_initial_s / _mult / _max_s and
        transfer_busy_budget_s, read per-use like
        object_transfer_chunk_bytes). `into` pre-registers a scatter
        destination: the chunk's OOB bytes land directly in it (the shm
        write buffer) with no intermediate copy — the call deliberately
        carries NO rpc timeout (see AsyncRpcClient.call), so only
        connection death interrupts it, and a dead read loop can no
        longer write into the buffer. Returns the chunk dict, or None
        (missing / still flooded — the outer pull loop retries other
        locations within its own deadline)."""
        backoff = float(cfg.get("transfer_busy_backoff_initial_s"))
        if budget_s is None:
            budget_s = float(cfg.get("transfer_busy_budget_s"))
        deadline = time.monotonic() + budget_s
        req = {"object_id": oid, "offset": offset}
        if attrib:
            # {requester, qos, owner} ride the request so the SERVER can
            # attribute its tx bytes symmetrically with our rx
            req.update(attrib)
        if peer is not None:
            # pull-issue grant against the SOURCE peer's window: a chunk
            # request parks here (asleep on the loop, never blocking it)
            # while higher-class traffic owns the link; a pace deadline
            # or injected net.pace drop fails typed and the outer pull
            # loop retries other sources — never a wedged transfer
            from ray_tpu._private import net_qos as _qos

            try:
                await _qos.acquire_async(
                    peer, (attrib or {}).get("qos", "bulk"), _chunk_size(),
                    owner=(attrib or {}).get("owner", "unknown"),
                    timeout=max(1.0, deadline - time.monotonic()))
            except _qos.NetPaceError:
                return None
        # only pass oob_into when scatter is actually engaged: test
        # doubles (and any duck-typed client) need not know the kwarg
        kw = {"oob_into": into} if into is not None else {}
        while True:
            part = await cli.call("read_object_chunk", req, **kw)
            if not (isinstance(part, dict) and part.get("busy")):
                return part
            if time.monotonic() > deadline:
                return None
            await asyncio.sleep(
                min(backoff, float(cfg.get("transfer_busy_backoff_max_s"))))
            backoff *= float(cfg.get("transfer_busy_backoff_mult"))

    async def _await_sealed(self, oid: bytes, timeout: float = 10.0) -> bool:
        """Another writer (concurrent pull or local producer) holds the
        unsealed buffer for `oid`: wait for it to seal instead of
        propagating ObjectExistsError up the pull."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.store.contains(oid):
                return True
            await asyncio.sleep(0.01)
        return False

    async def _pull_from(self, clis, oid: bytes, *, nids=None,
                         owner: str = "unknown",
                         qos: str = "bulk") -> bool:
        """Pipelined multi-source pull (object_manager.cc:633 redesigned
        around the pull RTT): chunk 0 establishes total size + metadata,
        then a sliding window of transfer_pull_pipeline_depth concurrent
        chunk requests keeps the pipe full — arriving chunks land at
        their offset in the pre-created write buffer, so out-of-order
        completion is fine. Under transfer_scatter_read (the default)
        each chunk is scatter-read DIRECTLY into its offset slice of the
        write buffer — no reader-side bytes, one copy socket→shm. With
        several source locations the window is striped across them
        (round-robin by worker), and a chunk whose assigned source fails
        retries the remaining sources before the pull gives up (a retry
        rewrites the same slice byte-identically, so a half-scattered
        chunk can never leak a silent zero gap). Failure paths abort the
        half-written buffer. `qos`/`owner` tag the pacer grants and byte
        attribution with the consuming subsystem."""
        if not isinstance(clis, (list, tuple)):
            clis = [clis]
        t0 = time.monotonic()
        # rx attribution: peer label per source + the self-declared
        # identity each chunk request carries for the server's tx side
        if nids is not None and len(nids) == len(clis):
            labels = [nid.hex()[:8] for nid in nids]
        else:
            labels = [f"src{i}" for i in range(len(clis))]
        label_of = {id(c): lbl for c, lbl in zip(clis, labels)}
        rx_by: dict[str, int] = {}
        attrib = {"requester": self.node_id.hex()[:8], "qos": qos,
                  "owner": owner}
        try:
            first = None
            lead_lbl = labels[0] if labels else "?"
            for lead in clis:
                try:
                    first = await self._read_chunk_backoff(
                        lead, oid, 0, attrib=attrib,
                        peer=label_of[id(lead)])
                except (rpc.ConnectionLost, rpc.RpcError, OSError):
                    first = None  # dead lead: try the next holder
                if first is not None:
                    lead_lbl = label_of[id(lead)]
                    break
            if first is None:
                return False
            total, meta = first["total"], first["meta"]
            chunk0 = _part_chunk(first)
            if self.store.contains(oid):
                return True
            try:
                wbuf = self.store.create_object(oid, total, len(meta))
            except ObjectExistsError:
                return await self._await_sealed(oid)
            try:
                n0 = len(chunk0)
                rx_by[lead_lbl] = rx_by.get(lead_lbl, 0) + n0
                wbuf.data[0:n0] = chunk0
                if n0 == 0 and total > 0:
                    wbuf.abort()
                    return False
                # step = the SERVER's chunk size (len of a full chunk),
                # so offsets line up even if our config disagrees
                offsets = deque(range(n0, total, n0)) if n0 else deque()
                depth = max(1, int(cfg.get("transfer_pull_pipeline_depth")))
                st = {"inflight": 0, "peak": 1, "chunks": 1,
                      "scattered": 0, "failed": False}

                async def read_one(cli, off, want, into):
                    """One source's chunk, or (None, False): connection
                    loss / rpc errors / a WRONG-SIZED reply (a source
                    with a different chunk-size config would leave a
                    silent zero gap in the sealed object) all mean 'try
                    the next source', not 'abort the pull'. Returns
                    (data, scattered): scattered means the bytes already
                    sit at their offset in the write buffer and `data`
                    aliases it — no copy needed (or allowed)."""
                    try:
                        part = await self._read_chunk_backoff(
                            cli, oid, off, attrib=attrib,
                            peer=label_of[id(cli)], into=into)
                    except (rpc.ConnectionLost, rpc.RpcError, OSError):
                        return None, False
                    if part is None:
                        return None, False
                    data = _part_chunk(part)
                    if len(data) != want:
                        return None, False
                    lbl = label_of[id(cli)]
                    rx_by[lbl] = rx_by.get(lbl, 0) + len(data)
                    return data, bool(part.get("oob_scattered"))

                async def fetch_chunks(widx: int):
                    own = clis[widx % len(clis)]
                    while offsets and not st["failed"]:
                        off = offsets.popleft()
                        want = min(n0, total - off)
                        # scatter destination: the chunk's slice of the
                        # shm write buffer (knob read per-chunk so the
                        # bench can flip it live). A failed attempt may
                        # leave it half-written; the failover below
                        # rewrites the SAME slice in full.
                        into = wbuf.data[off:off + want] \
                            if cfg.get("transfer_scatter_read") else None
                        st["inflight"] += 1
                        st["peak"] = max(st["peak"], st["inflight"])
                        try:
                            data, scat = await read_one(
                                own, off, want, into)
                            if data is None:
                                for alt in clis:
                                    if alt is own:
                                        continue
                                    data, scat = await read_one(
                                        alt, off, want, into)
                                    if data is not None:
                                        break
                        finally:
                            st["inflight"] -= 1
                        if data is None:
                            st["failed"] = True
                            return
                        if not scat:
                            wbuf.data[off:off + len(data)] = data
                        else:
                            st["scattered"] += 1
                        st["chunks"] += 1

                n_workers = min(depth, len(offsets))
                if n_workers:
                    results = await asyncio.gather(
                        *(fetch_chunks(i) for i in range(n_workers)),
                        return_exceptions=True,
                    )
                    for r in results:
                        if isinstance(r, BaseException):
                            st["failed"] = True
                            if not isinstance(r, (rpc.ConnectionLost,
                                                  rpc.RpcError, OSError)):
                                raise r
                if st["failed"]:
                    wbuf.abort()
                    return False
                if meta:
                    wbuf.meta[:] = meta
                wbuf.seal()
                dt = time.monotonic() - t0
                self._record_pull(oid, total, st, len(clis), dt,
                                  owner=owner, qos=qos)
                try:
                    from ray_tpu._private import flight_recorder as _fr
                    from ray_tpu._private import net_accounting as _net

                    for lbl, n in rx_by.items():
                        _net.account_rx(lbl, qos, owner, n)
                    _fr.record(
                        "transfer", "transfer.pull", t0, t0 + dt,
                        attrs={"oid": oid.hex()[:16], "bytes": total,
                               "chunks": st["chunks"],
                               "sources": len(clis),
                               "peak_inflight": st["peak"],
                               "owner": owner})
                except Exception:  # noqa: BLE001 — accounting only
                    pass
                return True
            except Exception:
                wbuf.abort()
                raise
        except (rpc.ConnectionLost, rpc.RpcError, OSError):
            return False

    def _record_pull(self, oid: bytes, total: int, st: dict,
                     n_sources: int, dt: float, *,
                     owner: str = "unknown", qos: str = "bulk"):
        ts = self.transfer_stats
        ts["pulls"] += 1
        ts["pull_bytes"] += total
        ts["pull_chunks"] += st["chunks"]
        ts["pull_max_inflight"] = max(ts["pull_max_inflight"], st["peak"])
        ts["last_pull"] = {
            "oid": oid.hex(), "bytes": total, "chunks": st["chunks"],
            "scattered": st.get("scattered", 0),
            "sources": n_sources, "max_inflight": st["peak"],
            "seconds": round(dt, 6), "owner": owner, "qos": qos,
        }
        try:
            m = _transfer_metrics()
            m["bytes"].inc(total)
            m["inflight_peak"].set(st["peak"])
        except Exception:  # noqa: BLE001 — metrics never block the pull
            pass

    async def rpc_object_sealed(self, conn, p):
        """Local worker sealed an object: register location + pin primary."""
        oid = p["object_id"]
        self.store.pin(oid, True)  # primary copy: spilled, never evicted
        self.primaries[oid] = p.get("size", 0)
        try:
            await self.head.call("object_add_location", {
                "object_id": oid, "node_id": self.node_id,
                "owner": p.get("owner"), "size": p.get("size", 0),
            })
        except (rpc.ConnectionLost, rpc.RpcError):
            # head down/restarting: the reconnect path re-announces every
            # primary, so the directory converges once it is back
            pass
        self._kick_dispatch()
        self._maybe_spill()
        return True

    async def rpc_free_objects(self, conn, p):
        for oid in p["object_ids"]:
            self.store.pin(oid, False)
            self.store.delete(oid)
            self.primaries.pop(oid, None)
            path = self.spilled_files.pop(oid, None)
            if path is not None:
                try:
                    os.unlink(path)
                except OSError:
                    pass
            try:
                await self.head.call("object_remove_location", {
                    "object_id": oid, "node_id": self.node_id,
                })
            except (rpc.ConnectionLost, rpc.RpcError):
                pass
        return True

    # ---------------- memory monitor ----------------
    # reference: common/memory_monitor.h:52 + raylet worker-killing
    # policies (worker_killing_policy.h): above the usage threshold, kill
    # the newest retriable (task) worker first — its owner retries the
    # task; actor workers only as a last resort.

    async def _memory_monitor_loop(self):
        interval = cfg.get("memory_monitor_interval_s")
        while not self._dead:
            await asyncio.sleep(interval)
            try:
                await self._oom_kill_if_needed()
            except Exception:  # noqa: BLE001 — monitor must not die
                logger.exception("memory monitor error")

    async def _oom_kill_if_needed(self) -> bool:
        import psutil

        frac = psutil.virtual_memory().percent / 100.0
        if frac <= cfg.get("memory_usage_kill_fraction"):
            return False
        return await self._oom_kill_once(frac)

    async def _oom_kill_once(self, frac: float = 1.0) -> bool:
        """Kill the newest task worker (retriable-FIFO policy)."""
        candidates = [w for w in self.workers.values()
                      if (w.busy_task is not None or w.pool_inflight)
                      and w.actor_id is None]
        if not candidates:
            candidates = [w for w in self.workers.values()
                          if w.actor_id is not None]
        if not candidates:
            return False
        victim = max(candidates, key=lambda w: w.started_at)
        logger.warning(
            "memory pressure (%.0f%%): killing newest worker %s (task %s)",
            frac * 100, victim.worker_id.hex()[:8],
            victim.busy_task.hex()[:8] if victim.busy_task else "-",
        )
        self._kill_worker(victim)
        await self._on_worker_death(victim, -9)
        return True

    # ---------------- spilling ----------------
    # reference: local_object_manager.h:110 SpillObjects /
    # :122 AsyncRestoreSpilledObject; IO here is node-local files (the
    # FileSystemStorage analog), URLs carry the owning node id so any
    # agent can route a restore request.

    def _maybe_spill(self):
        cap = self.store.capacity()
        if cap <= 0 or self._spilling:
            return
        if self.store.used_bytes() > self.SPILL_HIGH * cap:
            self._spilling = True
            asyncio.ensure_future(self._spill_until_low())

    async def _spill_until_low(self):
        try:
            cap = self.store.capacity()
            target = self.SPILL_LOW * cap
            # oldest primaries first (insertion order = seal order)
            for oid in list(self.primaries):
                if self.store.used_bytes() <= target:
                    break
                await self._spill_one(oid)
        finally:
            self._spilling = False

    def _spill_url(self, path: str) -> str:
        """Spill url format; the control plane parses the node id back out
        of it (rpc_object_spilled), so every producer must share this."""
        return f"file://{self.node_id.hex()}/{path}"

    async def _spill_one(self, oid: bytes) -> bool:
        buf = self.store.get(oid)
        if buf is None:
            self.primaries.pop(oid, None)
            return False
        try:
            os.makedirs(self.spill_dir, exist_ok=True)
            path = os.path.join(self.spill_dir, oid.hex())
            meta = bytes(buf.metadata)
            size = len(buf.data)
            # chunked write through the same framing discipline as the
            # wire path: one monolithic f.write(buf.data) of a multi-GB
            # object would wedge the agent's io loop for the whole
            # kernel copy — yield between chunks like _restore_from_disk
            with open(path, "wb") as f:
                f.write(len(meta).to_bytes(8, "little"))
                f.write(meta)
                step = _chunk_size()
                off = 0
                while off < size:
                    f.write(buf.data[off:off + step])
                    off += step
                    await asyncio.sleep(0)
        finally:
            buf.release()
        self.spilled_files[oid] = path
        url = self._spill_url(path)
        try:
            await self.head.call("object_spilled",
                                 {"object_id": oid, "url": url})
            await self.head.call("object_remove_location", {
                "object_id": oid, "node_id": self.node_id,
            })
        except (rpc.ConnectionLost, rpc.RpcError):
            pass
        self.primaries.pop(oid, None)
        self.store.pin(oid, False)
        self.store.delete(oid)
        logger.info("spilled %s (%d bytes) to %s", oid.hex()[:12], size, path)
        return True

    async def rpc_restore_object(self, conn, p):
        """Reload a spilled object into the local store, through the
        pull scheduler at PRI_RESTORE: a restore ALLOCATES store space,
        so it must queue behind task-arg and get pulls for admission
        (reference pull_manager.h:52 deprioritizes restores the same
        way) instead of allocating unconditionally under pressure."""
        oid = p["object_id"]
        if self.store.contains(oid):
            return True
        if self.spilled_files.get(oid) is None:
            return False
        if self._pull_sched is None:
            self._pull_sched = pull_manager.PullScheduler(
                self._pull_object, self.store,
                max_active=cfg.get("pull_max_active"),
                watermark=cfg.get("pull_admission_watermark"))
        return bool(await asyncio.shield(self._pull_sched.request(
            oid, pull_manager.PRI_RESTORE,
            timeout=p.get("timeout", 60.0),
            pull_fn=self._restore_pull)))

    async def _restore_pull(self, oid: bytes, deadline: float,
                            reserve=lambda n: None) -> bool:
        """PullScheduler transfer fn for restores: local disk, not a
        peer. reserve() reports the file size so admission accounts the
        incoming bytes before the store allocation happens."""
        path = self.spilled_files.get(oid)
        if path is not None:
            try:
                reserve(os.path.getsize(path))
            except OSError:
                pass
        return await self._restore_from_disk(oid)

    async def _restore_from_disk(self, oid: bytes) -> bool:
        """The actual spill-file -> store reload, through the same
        chunked zero-intermediate-copy discipline as the wire path: the
        payload is readinto() the store write buffer chunk by chunk —
        no whole-file bytes materialization (the old path paid
        file -> bytes -> shm, two copies of the object) — yielding to
        the loop between chunks so a multi-GB restore cannot wedge the
        agent's io loop."""
        if self.store.contains(oid):
            return True
        path = self.spilled_files.get(oid)
        if path is None:
            return False
        t0 = time.monotonic()
        try:
            fsize = os.path.getsize(path)
            f = open(path, "rb")
        except OSError:
            return False
        stored = False
        dsize = 0
        try:
            meta_len = int.from_bytes(f.read(8), "little")
            meta = f.read(meta_len)
            dsize = max(0, fsize - 8 - meta_len)
            need = dsize + meta_len
            for _ in range(len(self.primaries) + 2):
                wbuf = None
                try:
                    wbuf = self.store.create_object(oid, dsize, meta_len)
                    step = _chunk_size()
                    off = 0
                    while off < dsize:
                        want = min(step, dsize - off)
                        got = f.readinto(wbuf.data[off:off + want])
                        if not got:
                            raise OSError(f"short spill file {path}")
                        off += got
                        await asyncio.sleep(0)
                    if meta:
                        wbuf.meta[:] = meta
                    wbuf.seal()
                    wbuf = None
                    stored = True
                    break
                except ObjectExistsError:
                    # concurrent writer (another restore/pull) owns the
                    # buffer: wait for its seal rather than fighting
                    stored = await self._await_sealed(oid)
                    break
                except OSError:
                    if wbuf is not None:
                        wbuf.abort()
                    break  # truncated/unreadable spill file
                except Exception:
                    if wbuf is not None:
                        wbuf.abort()
                    f.seek(8 + meta_len)
                    # store full: evict unpinned copies, then swap out
                    # other primaries (spill) until the restore fits
                    self.store.evict(need)
                    swapped = False
                    for other in list(self.primaries):
                        if other != oid:
                            swapped = await self._spill_one(other)
                            if swapped:
                                break
                    if not swapped:
                        break
        finally:
            f.close()
        if not stored:
            # keep the spill file: the object is still recoverable later
            return False
        try:
            from ray_tpu._private import flight_recorder as _fr

            _fr.record("transfer", "transfer.restore", t0, time.monotonic(),
                       attrs={"oid": oid.hex()[:16], "bytes": dsize,
                              "owner": "checkpoint"})
        except Exception:  # noqa: BLE001 — observability best-effort
            pass
        self.store.pin(oid, True)
        self.primaries[oid] = dsize
        self.spilled_files.pop(oid, None)
        try:
            os.unlink(path)
        except OSError:
            pass
        await self.head.call("object_add_location", {
            "object_id": oid, "node_id": self.node_id,
            "restored": True,
        })
        self._kick_dispatch()
        return True

    async def rpc_node_info(self, conn, p):
        return {
            "node_id": self.node_id,
            "store_name": self.store_name,
            "resources_total": self.resources_total,
            "resources_available": self.resources_available,
            "num_workers": len(self.workers),
            "queued": len(self.task_queue),
            "running": len(self.running),
            "store_used": self.store.used_bytes(),
            "store_capacity": self.store.capacity(),
            "transfer_stats": dict(self.transfer_stats),
        }


def run_node_agent(head_addr: str, head_port: int, *, host="127.0.0.1",
                   port=0, resources=None, store_capacity=512 * 1024 * 1024,
                   session_id=None, ready_queue=None):
    """Run an agent as a dedicated process."""
    async def _main():
        agent = NodeAgent(
            head_addr, head_port, host=host, port=port, resources=resources,
            store_capacity=store_capacity, session_id=session_id,
        )
        actual = await agent.start()
        if ready_queue is not None:
            ready_queue.put(actual)
        await asyncio.Event().wait()

    asyncio.run(_main())
