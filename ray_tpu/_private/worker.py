"""CoreWorker: the library linked into every driver and executor.

Analog of the reference core-worker (`src/ray/core_worker/core_worker.h:284`
+ the Cython binding `_raylet.pyx`): owns task submission, the in-process
memory store for small results, object put/get/wait, actor handles and
per-actor ordered submission queues, retries, and the worker's own RPC
server (results are pushed owner-directly, as in the reference's
direct task/actor transports, `transport/direct_task_transport.h:75`).

Ownership model (reference reference_count.h:61, redesigned around the
centralized directory): the worker that creates a ref (task submission or
put) is its owner; small values live in the owner's memory store and are
served to borrowers via the owner's RPC; large values live in the node shm
store with locations tracked by the control-plane directory. Distributed GC:
every process counts its live ObjectRefs plus submitted-task pins and
reports 0<->1 transitions to the directory, which deletes all cluster
copies when the last reference anywhere drops (borrowers are just other
processes' counts — no owner long-poll protocol needed when the directory
is the single source of truth). Lost objects whose producing TaskSpec is
known are lineage-reconstructed by resubmitting the task
(object_recovery_manager.h:90).
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading
import time
import traceback
from typing import Any

from ray_tpu._private import rpc, serialization, task_spec
from ray_tpu._private import trace as _trace
from ray_tpu._private.ids import (
    ActorID,
    JobID,
    ObjectID,
    TaskID,
    WorkerID,
    _Counter,
)
from ray_tpu._private.rpc import AsyncRpcClient, EventLoopThread, RpcServer
from ray_tpu.core.object_store import ObjectStoreClient, StoreFullError

logger = logging.getLogger(__name__)

from ray_tpu._private import config as _config

INLINE_MAX = _config.get("inline_object_max_bytes")  # under: inline; over: shm
FUNC_NS = "funcs"

# Ambient consumer tags for plasma fetches issued on this thread: a
# fetch_context(qos=, owner=) scope makes every fetch_object RPC inside
# it declare WHICH subsystem the pull serves (weights broadcast, kv
# handoff, checkpoint restore). The agent threads the tags into the
# pull's pacer grants and net_accounting rows, so per-consumer transfer
# numbers need no bespoke plumbing at each call site.
_fetch_tags = threading.local()


class fetch_context:
    """with fetch_context(qos="kv", owner="kv-handoff"): ray_tpu.get(ref)

    Nestable; the innermost scope wins. `qos` is a pacer class
    ("kv" | "collective" | "bulk"), `owner` a free-form consumer label."""

    def __init__(self, qos: str | None = None, owner: str | None = None):
        self._tags = {}
        if qos is not None:
            self._tags["qos"] = str(qos)
        if owner is not None:
            self._tags["owner"] = str(owner)
        self._prev = None

    def __enter__(self):
        self._prev = getattr(_fetch_tags, "tags", None)
        _fetch_tags.tags = self._tags or None
        return self

    def __exit__(self, *exc):
        _fetch_tags.tags = self._prev
        return False


def current_fetch_tags() -> dict | None:
    return getattr(_fetch_tags, "tags", None)


class RayTaskError(Exception):
    """A task raised; carries the remote traceback (reference RayTaskError)."""

    def __init__(self, message: str, cause: Exception | None = None):
        super().__init__(message)
        self.cause = cause


class RayActorError(Exception):
    pass


class ObjectLostError(Exception):
    pass


class GetTimeoutError(Exception):
    pass


class DynamicReturns:
    """Descriptor value of a num_returns="dynamic" task's 0th return: the
    ids of the objects the generator produced (reference
    _raylet.pyx:186 ObjectRefGenerator's backing list)."""

    __slots__ = ("object_ids",)

    def __init__(self, object_ids: list[bytes]):
        self.object_ids = object_ids

    def __reduce__(self):
        return (DynamicReturns, (self.object_ids,))


class _ResultEntry:
    """One object's owner-side state."""

    __slots__ = ("event", "payload", "error", "in_plasma", "size", "spec",
                 "reconstructing", "escaped", "owned")

    def __init__(self):
        self.event = threading.Event()
        self.payload = None     # serialized [meta, bufs] when inline
        self.error = None       # serialized exception payload
        self.in_plasma = False
        self.size = 0
        self.spec = None        # producing TaskSpec (lineage / retries)
        self.reconstructing = False  # a lineage resubmit is in flight
        # the ref left this process (task arg, nested in a stored value):
        # the owner-side entry must outlive the local refcount
        self.escaped = False
        # this process owns the object (put / submitted the producing
        # task): its resolution is PUSHED to us, so gets may park on the
        # event instead of polling the directory
        self.owned = False

    @property
    def ready(self):
        return self.event.is_set()


class CoreWorker:
    """One per process (driver or executor)."""

    def __init__(self, *, head_addr: str, head_port: int,
                 agent_addr: str, agent_port: int, store_name: str,
                 node_id: bytes, job_id: bytes,
                 worker_id: bytes | None = None, is_driver: bool = False):
        self.worker_id = worker_id or WorkerID.from_random().binary()
        self.job_id = job_id
        self.node_id = node_id
        self.is_driver = is_driver
        self.io = EventLoopThread("ray_tpu-worker-io")
        self.head = rpc.SyncRpcClient(head_addr, head_port, self.io,
                                      reconnect=True)
        self.agent = rpc.SyncRpcClient(agent_addr, agent_port, self.io)
        # store_name=None: remote (ray://) driver with no co-located shm
        # store — RemoteDriverWorker overrides the plasma paths with agent
        # RPCs instead
        self.store = (ObjectStoreClient.attach(store_name)
                      if store_name is not None else None)
        self.memory: dict[bytes, _ResultEntry] = {}
        # RLock, not Lock: ObjectRef.__del__ (→ remove_local_ref) can run
        # REENTRANTLY on whatever thread triggers GC — including the io
        # thread while it already holds this lock inside _entry(). With a
        # plain Lock that is a single-thread self-deadlock that freezes
        # the whole io loop (observed: actor-death storms in the elastic
        # chaos tests wedged every sync RPC forever).
        self._mem_lock = threading.RLock()
        self.task_counter = _Counter()
        self.put_counter = _Counter()
        self._func_cache: dict[bytes, Any] = {}
        self._exported_funcs: set[bytes] = set()
        # actor bookkeeping (owner side)
        self._actor_info: dict[bytes, dict] = {}
        self._actor_clients: dict[bytes, rpc.SyncRpcClient] = {}
        self._actor_seq: dict[bytes, _Counter] = {}
        self._actor_pending: dict[bytes, set[bytes]] = {}  # aid → task_ids
        self._peer_clients: dict[tuple, rpc.SyncRpcClient] = {}
        # direct-task worker leases (direct_task_transport.h:110 lease
        # caching per SchedulingKey): resources-shape -> granted worker
        self._lease_cache: dict[tuple, dict] = {}
        # task_id -> (key, lease_id): lease_id disambiguates when an
        # expired-busy lease is replaced under the same scheduling key
        self._lease_tasks: dict[bytes, tuple] = {}
        self._lease_lock = threading.Lock()
        # buffered lease_tasks_started notifications (one frame per burst)
        self._lease_started_buf: list[dict] = []
        self._lease_started_lock = threading.Lock()
        # (task_id, retries_left) -> ts: per-attempt failure dedup
        self._failing_tasks: dict[tuple, float] = {}
        self._lock = threading.Lock()
        # Pipelined queued submission (reference pipelines lease pushes,
        # direct_task_transport.h:211; we pipeline the agent submit hop):
        # .remote() appends here and returns; a pump coroutine on the io
        # loop ships windowed batches via submit_task_batch.
        self._submit_buf: list[dict] = []
        self._submit_lock = threading.Lock()
        self._submit_inflight = 0  # batches on the wire (guarded by lock)
        self._submit_pump_running = False
        self._submit_kicked = False
        # tasks this owner cancelled: a lease-revoked failover racing the
        # agent's cancel notification must not resubmit them
        self._cancelled_tasks: set[bytes] = set()
        # liveness pump for owner-held pending lease tasks (guarded by
        # _lease_lock): retries grants / flushes stalled pendings to the
        # agent queue so long-running in-flight tasks can't strand them
        self._pending_pump_running = False

        # the worker's own RPC server (owner endpoint + executor endpoint)
        self.server = RpcServer("127.0.0.1", 0)
        self._install_routes()
        self.port = self.io.run(self.server.start())
        self.addr = "127.0.0.1"
        self.head.call("register_worker", {
            "worker_id": self.worker_id, "node_id": node_id,
            "addr": self.addr, "port": self.port, "job_id": job_id,
        })
        self.head.on_push("actor_update", self._on_actor_update)
        self.head.call("subscribe", {"channel": "actor_update"})
        # task_id -> node_id where the task was queued/ran; used to fail or
        # retry in-flight tasks when that node dies (the dying agent cannot
        # send task_failed itself).
        self._task_nodes: dict[bytes, bytes] = {}
        self._task_node_hops: dict[bytes, int] = {}
        self._dead_nodes: set[bytes] = set()
        self.head.on_push("node_dead", self._on_node_dead)
        self.head.call("subscribe", {"channel": "node_dead"})
        # resurrection (a dead-marked node re-registered): stop failing
        # tasks routed to it
        self.head.on_push(
            "node_added",
            lambda p: self._dead_nodes.discard(p.get("node_id")),
        )
        self.head.call("subscribe", {"channel": "node_added"})
        # tid -> (count, last_ts): routing failovers are retry-free, so
        # they MUST be rate-limited or a stale dead-node view turns into
        # an unbounded resubmit storm
        self._routing_failures: dict[bytes, tuple[int, float]] = {}
        # Head restart (GCS FT): the SyncRpcClient reconnects transparently;
        # we must re-register and re-subscribe on the fresh connection.
        self.head.on_reconnect = self._resync_head
        # Failure-event listeners (the collective layer registers here):
        # peer-lost fires when a cached peer RPC connection closes
        # (fastest signal that a peer process died); node-dead fans the
        # control plane's heartbeat-timeout events out beyond task
        # routing. Callbacks run on the io thread and must not block.
        self._peer_lost_listeners: list = []
        self._node_dead_listeners: list = []
        # Reference counting (reference_count.h:61 semantics, centralized):
        # per-oid local count; 0<->1 transitions reported to the directory,
        # which frees cluster copies when no process holds a reference.
        self._local_refs: dict[bytes, int] = {}
        # RLock for the same GC-reentrancy reason as _mem_lock: __del__
        # may fire mid-critical-section on the owning thread
        self._refs_lock = threading.RLock()
        # decrefs that arrived (via GC) while this thread held a ref/mem
        # lock: applied on the next clean remove_local_ref call (deque:
        # append/popleft are thread-safe without a lock)
        import collections as _collections

        self._deferred_decrefs: "_collections.deque[bytes]" = \
            _collections.deque()
        # task_id -> dep oids pinned for the task's lifetime (submitted-task
        # references, reference_count.h:115)
        self._task_pins: dict[bytes, list[bytes]] = {}
        self._job_payload: dict | None = None
        self._packaged_envs: dict[str, dict] = {}

    def _resync_head(self):
        try:
            self.head.call("register_worker", {
                "worker_id": self.worker_id, "node_id": self.node_id,
                "addr": self.addr, "port": self.port, "job_id": self.job_id,
            })
            for ch in ("actor_update", "node_dead"):
                self.head.call("subscribe", {"channel": ch})
            if self._job_payload is not None:
                # restore is_driver/job conn state on the fresh head
                self.head.call("register_job", self._job_payload)
            # replay our live references: the rebuilt directory must not
            # GC objects this process still holds
            with self._refs_lock:
                held = list(self._local_refs)
            for oid in held:
                self.head.fire("ref_add", {
                    "object_id": oid, "worker_id": self.worker_id,
                })
        except (rpc.ConnectionLost, rpc.RpcError):
            pass

    def register_job(self, payload: dict):
        """Register the driver's job; remembered for head-restart resync."""
        self._job_payload = payload
        self.head.call("register_job", payload)

    # ------------- helpers -------------

    @property
    def owner_address(self) -> dict:
        return {"worker_id": self.worker_id, "addr": self.addr,
                "port": self.port}

    def _install_routes(self):
        for name in dir(self):
            if name.startswith("rpc_"):
                self.server.handlers[name[4:]] = getattr(self, name)

    def _entry(self, oid: bytes) -> _ResultEntry:
        with self._mem_lock:
            e = self.memory.get(oid)
            if e is None:
                e = self.memory[oid] = _ResultEntry()
            return e

    def shutdown(self):
        try:
            self._flush_submits(timeout=5.0)
        except Exception:
            pass
        try:
            self.io.run(self.server.stop(), timeout=5)
        except Exception:
            pass
        try:
            self.head.close()
            self.agent.close()
            for c in self._actor_clients.values():
                c.close()
            for c in self._peer_clients.values():
                c.close()
        except Exception:
            pass
        self.io.stop()
        if self.store is not None:
            self.store.close()

    # ------------- owner-side RPC (results pushed to us) -------------

    async def rpc_push_results(self, conn, p):
        """Batched results from one executor (one frame per drain window
        instead of one per result — the owner loop is the task-storm
        throughput ceiling on small hosts)."""
        for msg in p["items"]:
            await self.rpc_push_result(conn, msg)
        return True

    async def rpc_push_result(self, conn, p):
        """An executor finished a task we own (or serves a borrowed get)."""
        if p.get("task_id") and not p.get("partial"):
            self._task_nodes.pop(p["task_id"], None)
            self._task_node_hops.pop(p["task_id"], None)
            self._release_task_pins(p["task_id"])
            # no unlocked membership pre-check: the submitter records the
            # lease task under _lease_lock and this result can land while
            # it still holds it — _on_lease_task_done checks under the
            # lock and no-ops for non-leased tasks
            self._on_lease_task_done(p["task_id"], failed=False)
        oid = p["object_id"]
        if p.get("dynamic_items"):
            # generator items live as long as their descriptor object
            try:
                self.head.fire("object_nested", {
                    "outer": oid, "inners": p["dynamic_items"],
                })
            except (rpc.ConnectionLost, rpc.RpcError, OSError):
                pass
        e = self._entry(oid)
        e.reconstructing = False
        if p.get("error") is not None:
            e.error = p["error"]
        elif p.get("in_plasma"):
            e.in_plasma = True
            e.size = p.get("size", 0)
        else:
            e.payload = p["payload"]
        e.event.set()
        return True

    async def rpc_task_failed(self, conn, p):
        """Node agent reports a task's worker died → retry or error out."""
        threading.Thread(
            target=self._handle_task_failed, args=(p,), daemon=True
        ).start()
        return True

    def _handle_task_failed(self, p):
        tid = p["task_id"]
        if tid in self._cancelled_tasks:
            p = {**p, "retriable": False, "reason": "cancelled"}
        self._task_nodes.pop(tid, None)
        self._task_node_hops.pop(tid, None)
        self._on_lease_task_done(tid, failed=True)
        spec = None
        with self._mem_lock:
            for e in self.memory.values():
                if e.spec is not None and e.spec["task_id"] == tid:
                    spec = e.spec
                    break
        if spec is None:
            return
        # Already completed (e.g. node died after pushing results): no-op.
        n_ret = spec.get("num_returns", 1)
        if n_ret == "dynamic":
            n_ret = 1
        return_oids = [
            ObjectID.for_task_return(TaskID(tid), i).binary()
            for i in range(n_ret)
        ]
        with self._mem_lock:
            if all(
                self.memory.get(oid) is not None and self.memory[oid].ready
                for oid in return_oids
            ):
                return
        # Attempt-level dedup: a leased-worker death sends BOTH an agent
        # task_failed and a lease_revoked fail-over for the same attempt —
        # only one may burn a retry. Keying on (task, retries_left) lets a
        # RESUBMITTED attempt's own later failure through (same task id,
        # decremented counter), unlike a plain time window.
        if p.get("routing_failure"):
            # a stale view sent the task to an already-dead node; nothing
            # executed, so resubmission neither burns a retry nor counts
            # as this attempt's failure (self-correcting once the view
            # refreshes). Rate-limited HARD: one per task per 2s, max 5 —
            # a falsely-dead node echoes a task_located per queued copy,
            # and unbounded retry-free resubmits once snowballed a 600k
            # agent queue. Beyond the cap, fall through to the normal
            # retry path (which burns retries and terminates).
            n, last = self._routing_failures.get(tid, (0, 0.0))
            now = time.monotonic()
            if n < 5:
                if now - last < 2.0:
                    return  # a recent resubmit of this task is in flight
                self._routing_failures[tid] = (n + 1, now)
                if len(self._routing_failures) > 10_000:
                    self._routing_failures.clear()
                try:
                    self.agent.call("submit_task", spec)
                except (rpc.ConnectionLost, rpc.RpcError):
                    pass
                else:
                    return
        attempt_key = (tid, spec.get("retries_left", 0))
        now = time.monotonic()
        with self._lease_lock:
            ts = self._failing_tasks.get(attempt_key)
            if ts is not None and now - ts < 120.0:
                return
            self._failing_tasks[attempt_key] = now
            for k, t0 in list(self._failing_tasks.items()):
                if now - t0 > 240.0:
                    del self._failing_tasks[k]
        if p.get("retriable", True) and spec.get("retries_left", 0) > 0:
            spec["retries_left"] -= 1
            logger.warning("retrying task %s (%s left): %s", tid.hex()[:8],
                           spec["retries_left"], p.get("reason"))
            try:
                self.agent.call("submit_task", spec)
                return
            except (rpc.ConnectionLost, rpc.RpcError):
                pass
        err = serialization.pack_payload(
            RayTaskError(f"task failed: {p.get('reason', 'worker died')}")
        )
        self._release_task_pins(spec["task_id"])
        n_ret = spec.get("num_returns", 1)
        if n_ret == "dynamic":
            n_ret = 1
        for i in range(n_ret):
            oid = ObjectID.for_task_return(
                TaskID(spec["task_id"]), i
            ).binary()
            e = self._entry(oid)
            e.error = err
            e.event.set()

    async def rpc_task_located(self, conn, p):
        """An agent accepted (or forwarded) one of our tasks.

        Notifies from every hop of a spill chain race here out of order;
        only the deepest hop names the node actually holding the task, so
        keep the max-hop report per attempt (hops only grow)."""
        tid = p["task_id"]
        hop = p.get("hop", 0)
        prev = self._task_node_hops.get(tid, -1)
        if hop < prev:
            return True
        self._task_node_hops[tid] = hop
        if len(self._task_node_hops) > 50_000:
            self._task_node_hops.clear()
        self._task_nodes[tid] = p["node_id"]
        if p["node_id"] in self._dead_nodes:
            # stale cluster views can forward a task to a node whose
            # death we already processed — its node_dead event will never
            # come again, so fail over right now (the per-attempt dedup
            # keeps this from burning extra retries)
            self._task_nodes.pop(p["task_id"], None)
            self._task_node_hops.pop(p["task_id"], None)
            threading.Thread(
                target=self._handle_task_failed,
                args=({"task_id": p["task_id"],
                       "reason": "routed to dead node",
                       "retriable": True, "routing_failure": True},),
                daemon=True,
            ).start()
        return True

    def add_peer_lost_listener(self, fn) -> None:
        """fn((addr, port)) runs on the io thread when a cached peer RPC
        connection closes; must not block (spawn a thread for real work)."""
        if fn not in self._peer_lost_listeners:
            self._peer_lost_listeners.append(fn)

    def add_node_dead_listener(self, fn) -> None:
        """fn(payload) runs on the io thread for every node_dead event."""
        if fn not in self._node_dead_listeners:
            self._node_dead_listeners.append(fn)

    def _notify_peer_lost(self, key: tuple) -> None:
        # evict the dead client FIRST: a reformed collective group (or
        # any later caller) must redial rather than receive the cached
        # closed client — keeping it would re-abort every fresh
        # incarnation that reuses the same (addr, port)
        stale = self._peer_clients.pop(key, None)
        if stale is not None:
            try:
                stale.close()
            except Exception:  # noqa: BLE001 — already dead
                pass
        for fn in list(self._peer_lost_listeners):
            try:
                fn(key)
            except Exception:  # noqa: BLE001 — listeners are best-effort
                logger.exception("peer-lost listener failed")

    def _on_node_dead(self, payload: dict):
        dead = payload.get("node_id")
        self._dead_nodes.add(dead)
        for fn in list(self._node_dead_listeners):
            try:
                fn(payload)
            except Exception:  # noqa: BLE001
                logger.exception("node-dead listener failed")
        if len(self._dead_nodes) > 1000:
            self._dead_nodes.pop()
        # Proactive lineage reconstruction: the directory names objects
        # whose LAST copy died with the node (no surviving location, no
        # spill file). Resubmit their producing tasks NOW — consumers
        # hit a warm (or already recomputed) copy instead of paying a
        # fetch-miss timeout first (reference object_recovery_manager
        # RecoverObject, triggered here from the death event).
        lost = [oid for oid in payload.get("lost_objects") or ()
                if (e := self.memory.get(oid)) is not None
                and e.spec is not None]
        if lost:
            def _recover(oids=lost):
                for oid in oids:
                    ent = self.memory.get(oid)
                    if ent is None:
                        continue
                    try:
                        self._maybe_reconstruct(oid, ent)
                    except Exception:  # noqa: BLE001 — best effort
                        logger.exception("proactive reconstruction of %s "
                                         "failed", oid.hex()[:12])
            # one thread for the whole event; _maybe_reconstruct makes
            # blocking head/agent calls that must not run on the io loop
            threading.Thread(target=_recover, daemon=True).start()
        stranded = [tid for tid, nid in self._task_nodes.items()
                    if nid == dead]
        for tid in stranded:
            self._task_nodes.pop(tid, None)
            self._task_node_hops.pop(tid, None)
            threading.Thread(
                target=self._handle_task_failed,
                args=({"task_id": tid,
                       "reason": f"node died: {payload.get('reason')}",
                       "retriable": True},),
                daemon=True,
            ).start()

    async def rpc_get_object(self, conn, p):
        """A borrower asks us (the owner) for a small object's value."""
        oid = p["object_id"]
        e = self.memory.get(oid)
        if e is None or not e.ready:
            return None
        if e.error is not None:
            return {"error": e.error}
        if e.in_plasma:
            return {"in_plasma": True, "size": e.size}
        return {"payload": e.payload}

    def _on_actor_update(self, view: dict):
        aid = view["actor_id"]
        self._actor_info[aid] = view
        if view["state"] == "DEAD":
            old = self._actor_clients.pop(aid, None)
            if old is not None:
                old.close()
            self._fail_pending_actor_tasks(
                aid, view.get("death_reason") or "actor died"
            )
        elif view["state"] == "RESTARTING":
            old = self._actor_clients.pop(aid, None)
            if old is not None:
                old.close()

    def _fail_pending_actor_tasks(self, aid: bytes, reason: str):
        pend = self._actor_pending.get(aid, set())
        err = serialization.pack_payload(RayActorError(reason))
        for tid in list(pend):
            oid = ObjectID.for_task_return(TaskID(tid), 0).binary()
            e = self._entry(oid)
            if not e.ready:
                e.error = err
                e.event.set()
        pend.clear()

    # ------------- reference counting -------------

    def add_local_ref(self, oid: bytes):
        with self._refs_lock:
            n = self._local_refs.get(oid, 0)
            self._local_refs[oid] = n + 1
            first = n == 0
        if first:
            try:
                self.head.fire("ref_add", {
                    "object_id": oid, "worker_id": self.worker_id,
                })
            except (rpc.ConnectionLost, rpc.RpcError, OSError):
                pass

    def remove_local_ref(self, oid: bytes):
        # GC can run ObjectRef.__del__ → here while THIS thread already
        # holds one of these (reentrant) locks mid-critical-section; a
        # reentrant pop could then corrupt an in-flight iteration
        # ("dict changed size during iteration"). Defer the decref to
        # the next clean call instead of mutating under the caller.
        self._deferred_decrefs.append(oid)
        if self._refs_lock._is_owned() or self._mem_lock._is_owned():
            # can't apply under the caller's critical section — and the
            # process may never drop another ref, so don't wait for a
            # future call here: the io loop drains once the owner
            # unwinds (lock sections are tiny dict ops, never RPCs, so
            # the loop blocks at most momentarily)
            try:
                self.io.loop.call_soon_threadsafe(self._drain_decrefs)
            except RuntimeError:
                pass  # loop closed at shutdown: nothing left to pin
            return
        self._drain_decrefs()

    def _drain_decrefs(self):
        if self._refs_lock._is_owned() or self._mem_lock._is_owned():
            return  # re-entered under a lock; a scheduled drain retries
        # drain until empty AFTER the last application: an application
        # can itself trigger GC and defer more decrefs — exiting before
        # re-checking would strand them (pinning cluster copies)
        while True:
            try:
                deferred = self._deferred_decrefs.popleft()
            except IndexError:
                return
            self._remove_local_ref_now(deferred)

    def _remove_local_ref_now(self, oid: bytes):
        with self._refs_lock:
            n = self._local_refs.get(oid, 0) - 1
            if n <= 0:
                self._local_refs.pop(oid, None)
            else:
                self._local_refs[oid] = n
            last = n == 0
        if last:
            # Reclaim the owner-side entry (inline payload + spec) unless
            # the ref escaped this process — escaped refs may still be
            # resolved by borrowers through our RPC endpoint.
            with self._mem_lock:
                e = self.memory.get(oid)
                if e is not None and not e.escaped:
                    self.memory.pop(oid, None)
            try:
                self.head.fire("ref_del", {
                    "object_id": oid, "worker_id": self.worker_id,
                })
            except (rpc.ConnectionLost, rpc.RpcError, OSError):
                pass

    def _pin_task_deps(self, task_id: bytes, oids: list[bytes]):
        if not oids:
            return
        self._task_pins[task_id] = oids
        for oid in oids:
            self.add_local_ref(oid)

    def _release_task_pins(self, task_id: bytes):
        for oid in self._task_pins.pop(task_id, ()):
            self.remove_local_ref(oid)

    # ------------- function export -------------

    def export_function(self, func, by_source: bool = False) -> bytes:
        import hashlib

        blob = (serialization.pack_callable_source(func) if by_source
                else serialization.pack_payload(func))
        meta, bufs = blob
        h = hashlib.blake2b(digest_size=16)
        h.update(meta)
        for b in bufs:
            h.update(b)
        func_id = h.digest()
        if func_id not in self._exported_funcs:
            # intermediate keys durable=False: the FINAL put's group
            # commit persists the whole export in one snapshot write
            # instead of one ~20ms commit window per key
            self.head.call("kv_put", {
                "ns": FUNC_NS, "key": func_id, "value": meta,
                "durable": not bufs,
            })
            # store buffers alongside (rare for functions to have any)
            if bufs:
                for i, b in enumerate(bufs):
                    self.head.call("kv_put", {
                        "ns": FUNC_NS, "key": func_id + b"/%d" % i,
                        "value": bytes(b), "durable": False,
                    })
                self.head.call("kv_put", {
                    "ns": FUNC_NS, "key": func_id + b"/n",
                    "value": str(len(bufs)).encode(),
                })
            self._exported_funcs.add(func_id)
        return func_id

    def load_function(self, func_id: bytes):
        fn = self._func_cache.get(func_id)
        if fn is not None:
            return fn
        meta = self.head.call("kv_get", {"ns": FUNC_NS, "key": func_id})
        if meta is None:
            raise RayTaskError(f"function {func_id.hex()} not found in KV")
        nbuf = self.head.call("kv_get", {"ns": FUNC_NS, "key": func_id + b"/n"})
        bufs = []
        if nbuf is not None:
            for i in range(int(nbuf)):
                bufs.append(self.head.call(
                    "kv_get", {"ns": FUNC_NS, "key": func_id + b"/%d" % i}
                ))
        fn = serialization.maybe_materialize_source_fn(
            serialization.unpack_payload([meta, bufs]))
        self._func_cache[func_id] = fn
        return fn

    # ------------- put / get / wait -------------

    def put(self, value, *, inline: bool | None = None) -> bytes:
        """Store a value; returns object id (we are the owner).

        Single-copy: serialization keeps pickle-5 buffers as memoryviews
        over the caller's arrays; the plasma path writes them straight
        into the shm segment (the ONLY copy), the inline path
        materializes once into the owner entry (the payload must not
        alias caller buffers the user may mutate). ``inline=False``
        forces the plasma path regardless of size: only sealed store
        objects are announced to the directory, so a ref handed to
        third processes through a side channel (actor state, another
        task's result) stays fetchable cluster-wide."""
        oid = ObjectID.for_put(
            WorkerID(self.worker_id), self.put_counter.next()
        ).binary()
        meta, views, nested_refs, size = serialization.serialize_views(value)
        if nested_refs:
            # refs serialized inside this value stay alive as long as the
            # value does (reference AddNestedObjectIds semantics)
            inners = []
            for r in nested_refs:
                ie = self._entry(r.binary())
                ie.escaped = True
                inners.append(r.binary())
            try:
                self.head.fire("object_nested",
                               {"outer": oid, "inners": inners})
            except (rpc.ConnectionLost, rpc.RpcError, OSError):
                pass
        e = self._entry(oid)
        e.owned = True
        if size <= INLINE_MAX and inline is not False:
            e.payload = [meta, [bytes(v) for v in views]]
        else:
            self._put_plasma(oid, [meta, views])
            e.in_plasma = True
            e.size = size
        e.event.set()
        return oid

    def _put_plasma(self, oid: bytes, payload):
        """payload = [meta, bufs]; bufs may be memoryviews (single-copy
        put path) or bytes — either way each part is written into the
        shm segment exactly once."""
        meta, bufs = payload
        # layout: size table in the object metadata, concatenated parts in
        # the body, so deserialize can slice zero-copy (shared with the
        # ray:// remote data plane — serialization.pack_part_table).
        table, total = serialization.pack_part_table(meta, bufs)
        # Under pressure, block briefly for eviction + async GC to free
        # space (reference create_request_queue.cc admission behavior).
        deadline = time.monotonic() + _config.get("put_pressure_retry_s")
        while True:
            try:
                wbuf = self.store.create_object(oid, total, len(table))
                break
            except StoreFullError:
                self.store.evict(total)
                try:
                    wbuf = self.store.create_object(oid, total, len(table))
                    break
                except StoreFullError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.05)
        off = 0
        for part in [meta] + list(bufs):
            n = serialization._nbytes(part)
            wbuf.data[off:off + n] = part
            off += n
        wbuf.meta[:] = table
        wbuf.seal()
        # Pin locally BEFORE the async announce: the agent's primary pin
        # only lands with the announce, and an unpinned fresh object
        # could be LRU-evicted by a concurrent pressure eviction in the
        # window. The agent re-pins idempotently; free()/spill unpin.
        self.store.pin(oid, True)
        # Async announce (coalesced fire): the seal itself is durable in
        # the local store, so put() need not pay the worker→agent→head
        # round trip per object — remote consumers rendezvous through the
        # directory's object_wait_location long-poll, which fires once
        # the announce lands. A free() racing the announce is healed by
        # the directory's freed-tombstone path. Loss bound: fire drops
        # frames only when THIS worker↔agent connection breaks, and that
        # connection is not reconnecting — a worker that lost its
        # node-local agent cannot submit, lease, or fetch either (node
        # fate-sharing), so a silently unannounced-but-sealed object
        # cannot outlive the failure domain that produced it.
        self.agent.fire("object_sealed", {
            "object_id": oid, "owner": self.owner_address, "size": total,
        })

    def _read_plasma(self, oid: bytes):
        buf = self.store.get(oid)
        if buf is None:
            return None
        parts = serialization.unpack_parts(buf.metadata, buf.data)
        value = serialization.loads_oob(parts[0], parts[1:])
        # Zero-copy: numpy arrays in `value` view the store segment directly.
        # The ObjectBuffer's refcount pin must outlive every such array, so
        # each array's weakref-finalizer holds a strong ref to `buf`; when
        # the last array dies, buf is collected and the store ref released.
        if parts[1:]:
            _pin_buffers_to_arrays(value, buf)
        return value

    def get(self, object_ids: list[bytes], timeout: float | None = None):
        deadline = None if timeout is None else time.monotonic() + timeout
        # Executors tell their agent while they are parked in get() so
        # the pool can backfill their slot and never pipeline onto them
        # (reference NotifyDirectCallTaskBlocked, core_worker.cc) —
        # without this, N workers blocked on nested tasks deadlock an
        # N-slot pool. No-op for drivers (_notify_blocked → False).
        blocked = False
        try:
            out = []
            for oid in object_ids:
                if not blocked and not self._entry(oid).ready:
                    blocked = self._notify_blocked()
                out.append(self._get_one(oid, deadline))
            return out
        finally:
            if blocked:
                self._notify_unblocked()

    def _notify_blocked(self) -> bool:
        return False  # drivers are not pool workers

    def _notify_unblocked(self) -> None:
        pass

    def _get_one(self, oid: bytes, deadline):
        e = self._entry(oid)
        while True:
            if e.ready:
                if e.error is not None:
                    err = serialization.unpack_payload(e.error)
                    if isinstance(err, Exception):
                        raise err
                    raise RayTaskError(str(err))
                if e.in_plasma:
                    return self._fetch_plasma(oid, deadline)
                return serialization.unpack_payload(e.payload)
            # Not resolved here: maybe it's a borrowed ref → ask around.
            if self._try_resolve_remote(oid):
                continue
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                raise GetTimeoutError(f"get timed out on {oid.hex()[:12]}")
            if deadline is None and e.owned:
                # owned + nothing to resolve remotely: the result (or a
                # failure-path error) is PUSHED to this process, so park
                # on the event — the hot path takes zero poll wakeups.
                # The slice is bounded (not infinite) as a lost-push
                # backstop: result pushes are fire-and-forget, so a
                # push dropped on a breaking connection is only
                # recoverable through the directory re-check on wakeup
                # (plasma results announce their location out of band).
                e.event.wait(timeout=0.5)
                continue
            e.event.wait(timeout=0.1 if remaining is None
                         else min(0.1, remaining))

    def _fetch_plasma(self, oid: bytes, deadline):
        while True:
            value = self._read_plasma(oid)
            if value is not None:
                return value
            fetch_cap = _config.get("fetch_retry_timeout_s")
            timeout = fetch_cap if deadline is None else max(
                0.1, deadline - time.monotonic())
            req = {"object_id": oid, "timeout": min(timeout, fetch_cap)}
            tags = current_fetch_tags()
            if tags:
                req.update(tags)  # consumer {qos, owner} attribution
            ok = self.agent.call("fetch_object", req)
            if not ok:
                if deadline is not None and time.monotonic() > deadline:
                    raise GetTimeoutError(oid.hex())
                # Owner may still be computing, or every copy died with its
                # node: lineage reconstruction resubmits the producing task
                # (object_recovery_manager.h:90 RecoverObject semantics).
                e = self.memory.get(oid)
                if e is not None and e.spec is not None:
                    self._maybe_reconstruct(oid, e)
                time.sleep(0.1)

    def _maybe_reconstruct(self, oid: bytes, e: "_ResultEntry") -> bool:
        """Resubmit the producing task of a lost object (lineage recovery).

        The task keeps its original task_id, so the recomputed result lands
        on the same return object ids; waiting fetch loops pick up the new
        location. Idempotent per loss event via the reconstructing flag.
        Guards against duplicate execution: no resubmit while the producer
        is still queued/running somewhere, or while a live copy exists
        (merely-slow transfers are not losses)."""
        with self._mem_lock:
            if e.spec is None or e.reconstructing:
                return e.spec is not None
            e.reconstructing = True
        if e.spec["task_id"] in self._task_nodes:
            # producer still in flight on a live node; its push will land
            e.reconstructing = False
            return True
        try:
            info = self.head.call("object_locations", {"object_id": oid})
        except (rpc.ConnectionLost, rpc.RpcError):
            info = None
        if info and (info.get("locations") or info.get("spilled")):
            # a copy exists: the fetch is slow, not lost
            e.reconstructing = False
            return True
        spec = dict(e.spec)
        logger.warning("reconstructing %s via task %s (%s)",
                       oid.hex()[:12], spec["task_id"].hex()[:8],
                       spec.get("name"))
        try:
            self.agent.call("submit_task", spec)
            return True
        except (rpc.ConnectionLost, rpc.RpcError):
            e.reconstructing = False
            return False

    async def rpc_dep_lost(self, conn, p):
        """An agent could not fetch a task dependency anywhere: if we own
        the dep's lineage, recompute it (the agent keeps retrying its
        fetch and dispatches once the new copy appears).

        Runs off-thread: _maybe_reconstruct makes a blocking agent call,
        which must not run on this (the io-loop) thread."""
        oid = p["object_id"]
        e = self.memory.get(oid)
        if e is not None and e.spec is not None:
            threading.Thread(
                target=self._maybe_reconstruct, args=(oid, e), daemon=True
            ).start()
        return True

    def _try_resolve_remote(self, oid: bytes) -> bool:
        """Resolve a ref we don't own: directory first, then owner."""
        info = None
        try:
            info = self.head.call("object_locations", {"object_id": oid})
        except (rpc.ConnectionLost, rpc.RpcError):
            return False
        e = self._entry(oid)
        if info and info.get("locations"):
            if not e.ready:
                e.in_plasma = True
                e.event.set()
            return True
        owner = (info or {}).get("owner")
        if owner and owner["worker_id"] != self.worker_id:
            cli = self._peer(owner)
            if cli is not None:
                try:
                    res = cli.call("get_object", {"object_id": oid})
                except (rpc.ConnectionLost, rpc.RpcError):
                    res = None
                if res:
                    if res.get("error") is not None:
                        e.error = res["error"]
                    elif res.get("in_plasma"):
                        e.in_plasma = True
                        e.size = res.get("size", 0)
                    else:
                        e.payload = res["payload"]
                    e.event.set()
                    return True
        return False

    def _peer(self, owner: dict) -> rpc.SyncRpcClient | None:
        key = (owner["addr"], owner["port"])
        cli = self._peer_clients.get(key)
        if cli is not None:
            return cli
        try:
            cli = rpc.SyncRpcClient(owner["addr"], owner["port"], self.io)
        except rpc.ConnectionLost:
            return None
        # connection loss to a peer is the fastest death signal the
        # collective abort path has; notify listeners from the read
        # loop's teardown (io thread — listeners must not block)
        cli.client.on_close = lambda k=key: self._notify_peer_lost(k)
        self._peer_clients[key] = cli
        return cli

    def wait(self, object_ids: list[bytes], num_returns: int,
             timeout: float | None):
        deadline = None if timeout is None else time.monotonic() + timeout
        ready: list[bytes] = []
        pending = list(object_ids)
        blocked = False  # executor parked here: agent backfills the slot
        try:
            # first passes come one interval in — not on entry, where a
            # wide wait() would burst one directory call per ref
            last_resolve = time.monotonic()
            last_resolve_owned = last_resolve
            while True:
                still = []
                # Owned pending refs are PUSHED to us — polling the
                # directory for them is pure head load (a wait() over a
                # large in-flight round once drove thousands of
                # object_locations calls/s, starving the very dispatch
                # loop that had to complete the tasks). Borrowed refs
                # resolve remotely at 10 passes/s; owned refs get a 1/s
                # backstop pass because result pushes are fire-and-
                # forget — a push lost on a breaking connection is only
                # recoverable through the directory (plasma results
                # announce their location out of band).
                now = time.monotonic()
                resolve = now - last_resolve >= 0.1
                if resolve:
                    last_resolve = now
                resolve_owned = now - last_resolve_owned >= 1.0
                if resolve_owned:
                    last_resolve_owned = now
                for oid in pending:
                    e = self._entry(oid)
                    if not e.ready and (resolve_owned
                                        or (resolve and not e.owned)):
                        self._try_resolve_remote(oid)
                    if e.ready:
                        ready.append(oid)
                    else:
                        still.append(oid)
                pending = still
                if len(ready) >= num_returns or not pending:
                    return ready, pending
                if deadline is not None and time.monotonic() >= deadline:
                    return ready, pending
                if not blocked:
                    blocked = self._notify_blocked()
                time.sleep(0.01)
        finally:
            if blocked:
                self._notify_unblocked()

    def free(self, object_ids: list[bytes]):
        plasma = []
        with self._mem_lock:
            for oid in object_ids:
                e = self.memory.pop(oid, None)
                if e is not None and e.in_plasma:
                    plasma.append(oid)
        if plasma:
            try:
                self.agent.call("free_objects", {"object_ids": plasma})
                for oid in plasma:
                    self.head.call("free_object", {"object_id": oid})
            except (rpc.ConnectionLost, rpc.RpcError):
                pass

    def _prepare_runtime_env(self, runtime_env: dict) -> dict:
        """Package local working_dir / py_modules dirs into cluster-wide
        pkg:// URIs (reference runtime_env packaging.py). Memoized on a
        stat FINGERPRINT of the dirs (edited content re-packages — a
        path-only key would ship stale code forever), and the blobs'
        KV presence is revalidated so a head restart (packages are
        durable=False) triggers a re-upload instead of spawn failures."""
        import json as _json

        from ray_tpu._private import runtime_env as _re

        key = _json.dumps(runtime_env, sort_keys=True, default=str)
        fp = _re.dir_fingerprint(runtime_env)
        cached = self._packaged_envs.get(key)
        if (cached is not None and cached[0] == fp
                and _re.uris_present(cached[1], self.head)):
            return cached[1]
        packaged = _re.package_local_dirs(runtime_env, self.head)
        self._packaged_envs[key] = (fp, packaged)
        return packaged

    # ------------- task submission -------------

    def submit_task(self, func, args: tuple, kwargs: dict, *,
                    num_returns: int = 1, resources: dict | None = None,
                    retries: int = 3, pg_id: bytes | None = None,
                    bundle_index: int = -1, bundle_nodes: list | None = None,
                    scheduling_strategy=None, runtime_env: dict | None = None,
                    name: str = "",
                    func_id: bytes | None = None,
                    fetch_tags: dict | None = None) -> list[bytes]:
        if func_id is None:
            func_id = self.export_function(func)
        # parent chain: drivers are roots; executor-submitted tasks chain
        # through their own worker ids via the counter namespace
        task_id = TaskID.for_task(
            JobID(self.job_id), TaskID(b"\x00" * 8 + self.worker_id[:8]),
            self.task_counter.next(),
        ).binary()
        args_spec, deps, inline_values = self._pack_args(args, kwargs)
        # typed construction: schema-validated at build (reference backs
        # this with a protobuf TaskSpecification, task_spec.h — here the
        # schema lives in task_spec.py and both ends validate)
        spec = task_spec.TaskSpec.build(
            task_id=task_id,
            job_id=self.job_id,
            func_id=func_id,
            name=name or getattr(func, "__name__", "task"),
            args=args_spec,
            inline_values=inline_values,
            num_returns=num_returns,
            resources=resources or {"CPU": 1.0},
            owner=self.owner_address,
            deps=deps,
            retries_left=retries,
            pg_id=pg_id,
            bundle_index=bundle_index if pg_id is not None else None,
            bundle_nodes=(bundle_nodes or []) if pg_id is not None else None,
            scheduling_strategy=scheduling_strategy,
            runtime_env=(self._prepare_runtime_env(runtime_env)
                         if runtime_env else None),
            trace=_trace.for_submit(),
            fetch_tags=fetch_tags,
        )
        n_ret = 1 if num_returns == "dynamic" else num_returns
        return_ids = [
            ObjectID.for_task_return(TaskID(task_id), i).binary()
            for i in range(n_ret)
        ]
        for oid in return_ids:
            e = self._entry(oid)
            e.spec = spec
            e.owned = True
        # Submitted-task references: args stay pinned until the task
        # completes or exhausts retries (reference_count.h:115).
        self._pin_task_deps(task_id, list(deps))
        if not self._try_lease_submit(spec):
            self._enqueue_submit(spec)
        return return_ids

    # -- pipelined queued submission: the agent hop must not serialize
    # .remote() (async batch throughput was within 9% of sync when every
    # submit blocked on its ack). Specs buffer here; a pump on the io
    # loop ships them as windowed submit_task_batch calls with a bounded
    # number of batches in flight. Failure backstop: a batch that errors
    # fails its tasks through the normal retry machinery. --

    def _enqueue_submit(self, spec: dict):
        with self._submit_lock:
            self._submit_buf.append(spec)
            if self._submit_pump_running or self._submit_kicked:
                return  # one wakeup per burst, not one per task
            self._submit_kicked = True
        self.io.call_soon(self._kick_submit_pump)

    def _kick_submit_pump(self):  # io loop only
        with self._submit_lock:
            self._submit_kicked = False
            if self._submit_pump_running:
                return
            self._submit_pump_running = True
        import asyncio

        asyncio.ensure_future(self._submit_pump())

    async def _submit_pump(self):
        import asyncio

        from ray_tpu._private import config as _cfg

        batch_max = _cfg.get("submit_batch_max")
        window = _cfg.get("submit_pipeline_depth")
        inflight: set = set()
        try:
            while True:
                with self._submit_lock:
                    batch = self._submit_buf[:batch_max]
                    del self._submit_buf[:len(batch)]
                    if not batch and not inflight:
                        # terminal check under the lock: a concurrent
                        # enqueue after this point re-kicks via call_soon,
                        # which cannot interleave with this (same loop)
                        self._submit_pump_running = False
                        return
                    if batch:
                        self._submit_inflight += 1
                if not batch:
                    _done, inflight = await asyncio.wait(
                        inflight, return_when=asyncio.FIRST_COMPLETED
                    )
                    continue
                while len(inflight) >= window:
                    _done, inflight = await asyncio.wait(
                        inflight, return_when=asyncio.FIRST_COMPLETED
                    )
                inflight.add(
                    asyncio.ensure_future(self._send_submit_batch(batch))
                )
        except BaseException:
            self._submit_pump_running = False
            raise

    async def _send_submit_batch(self, specs: list[dict]):
        import asyncio

        # late-cancel filter: cancel_task may have marked specs that were
        # already popped from _submit_buf into this batch
        if self._cancelled_tasks:
            specs = [s for s in specs
                     if s["task_id"] not in self._cancelled_tasks]
            if not specs:
                return
        try:
            await self.agent.client.call(
                "submit_task_batch", {"specs": specs}, timeout=60.0
            )
        except (rpc.ConnectionLost, rpc.RpcError,
                asyncio.TimeoutError) as e:
            reason = f"submit failed: {type(e).__name__}"
            threading.Thread(
                target=self._fail_submit_batch, args=(specs, reason),
                daemon=True,
            ).start()
        finally:
            with self._submit_lock:
                self._submit_inflight -= 1

    def _fail_submit_batch(self, specs: list[dict], reason: str):
        for spec in specs:
            self._handle_task_failed({
                "task_id": spec["task_id"], "reason": reason,
                "retriable": True,
            })

    def cancel_task(self, task_id: bytes, force: bool = False):
        """Cancel before it ships (still in the submit buffer) or via the
        agent once it has (reference CancelTask covers both queue states)."""
        self._cancelled_tasks.add(task_id)
        if len(self._cancelled_tasks) > 10_000:
            self._cancelled_tasks.clear()
        with self._submit_lock:
            for i, s in enumerate(self._submit_buf):
                if s["task_id"] == task_id:
                    del self._submit_buf[i]
                    break
            else:
                s = None
        if s is None:
            # owner-held pending lease task: cancel before it ships
            with self._lease_lock:
                for entry in self._lease_cache.values():
                    for i, cand in enumerate(entry["pending"]):
                        if cand["task_id"] == task_id:
                            s = cand
                            del entry["pending"][i]
                            break
                    if s is not None:
                        break
        if s is not None:
            self._handle_task_failed({
                "task_id": task_id, "reason": "cancelled",
                "retriable": False,
            })
            return {"cancelled": "buffered"}
        r = self.agent.call("cancel_task", {
            "task_id": task_id, "force": force,
        })
        if r.get("cancelled") is None:
            # possibly in an in-flight submit batch (popped from the
            # buffer but not yet landed): the _cancelled_tasks mark
            # filters it out of the batch; re-check the agent once the
            # window has surely flushed
            self._flush_submits(timeout=2.0)
            r = self.agent.call("cancel_task", {
                "task_id": task_id, "force": force,
            })
        return r

    def _flush_submits(self, timeout: float = 10.0):
        """Block until every buffered spec has been acked by the agent
        (or errored into the retry path). Used at shutdown so a driver
        that exits right after .remote() doesn't strand tasks."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._submit_lock:
                clear = not self._submit_buf and self._submit_inflight == 0
            if clear:
                with self._lease_lock:
                    clear = not any(e["pending"]
                                    for e in self._lease_cache.values())
            if clear:
                return True
            time.sleep(0.002)
        return False

    # -- direct-task lease caching (direct_task_transport.h:110): repeat
    # same-shape tasks push straight to a leased worker, skipping the
    # agent queue/dispatch hop. The agent still learns about each leased
    # task (async fire) so its worker-death machinery covers them. --

    def _lease_key(self, spec) -> tuple | None:
        if (spec.get("pg_id") or spec.get("scheduling_strategy")
                or spec.get("runtime_env")
                or spec.get("num_returns") == "dynamic"):
            return None
        inline = spec.get("inline_values", {})
        for d in spec.get("deps", []):
            if d not in inline and (
                    self.store is None or not self.store.contains(d)):
                return None  # remote dep: the agent's dep staging handles it
        return tuple(sorted(spec.get("resources", {}).items()))

    def _try_lease_submit(self, spec) -> bool:
        # LOCK DISCIPLINE: never touch the io loop (agent.call / oneway —
        # both block on it) while holding _lease_lock: the io thread takes
        # the same lock in _on_lease_task_done, which deadlocks the loop.
        # The lease is reserved (inflight bumped + task recorded) BEFORE
        # the push, so a result can never race its own bookkeeping.
        #
        # Policy (reference direct_task_transport.h:110 lease pool +
        # :211 pipelining, adapted): parallelism first — prefer an IDLE
        # leased worker, then GRANT another lease (up to
        # worker_lease_max_per_key), and only when the local node refuses
        # AND no other alive node could fit the shape (the refusal's
        # `spillable` bit) pipeline up to worker_lease_depth tasks onto
        # the least-loaded lease. A spillable shape falls back to queued
        # submission instead, so cluster spillback keeps working.
        from ray_tpu._private import config as _cfg

        if not _cfg.get("worker_lease_enabled"):
            return False
        key = self._lease_key(spec)
        if key is None:
            return False
        depth = _cfg.get("worker_lease_depth")
        max_leases = _cfg.get("worker_lease_max_per_key")
        now = time.monotonic()
        tid = spec["task_id"]
        to_return: list[bytes] = []
        lease = None
        with self._lease_lock:
            entry = self._lease_cache.get(key)
            if entry is None:
                entry = self._lease_cache[key] = {
                    "leases": [], "no_grant_until": 0.0, "spillable": True,
                    "pending": [],
                }
            # Idle staleness must be checked OWNER-side with margin under
            # the agent's idle-reclaim threshold: pushing to a lease the
            # agent reclaimed a moment ago double-books the worker (the
            # push still executes) AND resubmits the task via the
            # revocation failover — double execution.
            idle_stale = _cfg.get("worker_lease_idle_reclaim_s") * 0.6
            keep = []
            for l in entry["leases"]:
                stale = (l["inflight"] == 0
                         and (now > l["expires"]
                              or now - l.get("_last_use", now) > idle_stale))
                if stale:
                    to_return.append(l["lease_id"])
                else:
                    keep.append(l)
            entry["leases"] = keep
            for l in keep:
                if l["inflight"] == 0:
                    lease = l
                    break
            if lease is not None:
                lease["inflight"] = 1
                lease["_last_use"] = now
                self._lease_tasks[tid] = (key, lease["lease_id"], now)
            want_grant = (lease is None and len(keep) < max_leases
                          and now >= entry["no_grant_until"])
            if lease is None and not want_grant and keep \
                    and not entry["spillable"]:
                # Local node refused recently and nowhere else fits the
                # shape: pipeline up to depth onto the least-loaded leased
                # worker (deep worker queues also let executors batch
                # their result pushes), then hold overflow OWNER-SIDE
                # (reference SchedulingKey queues) — returning results
                # refill leases directly, so the drain never touches the
                # agent loop.
                cand = min(keep, key=lambda l: l["inflight"])
                if cand["inflight"] < depth:
                    lease = cand
                    lease["inflight"] += 1
                    lease["_last_use"] = now
                    self._lease_tasks[tid] = (key, lease["lease_id"], now)
                elif len(entry["pending"]) < _cfg.get(
                        "worker_lease_pending_max"):
                    if not entry["pending"]:
                        entry["pending_since"] = now
                    entry["pending"].append(spec)
                    start_pump = not self._pending_pump_running
                    if start_pump:
                        self._pending_pump_running = True
                        self.io.call_soon(self._start_pending_pump)
                    return True
        for lid in to_return:
            self.agent.fire("return_lease", {"lease_id": lid})
        if lease is None and want_grant:
            try:
                grant = self.agent.call("lease_worker", {
                    "resources": spec.get("resources", {}),
                    "job_id": self.job_id,
                    "owner": self.owner_address,
                }, timeout=10.0)
            except (rpc.ConnectionLost, rpc.RpcError):
                return False
            if not grant or "lease_id" not in grant:
                with self._lease_lock:
                    entry = self._lease_cache.get(key)
                    if entry is not None:
                        entry["no_grant_until"] = now + 0.2
                        entry["spillable"] = bool(
                            (grant or {}).get("spillable", True)
                        )
                return False
            lease = {
                **grant, "inflight": 1, "_last_use": now,
                "expires": now + grant["ttl_s"] * 0.8,
            }
            with self._lease_lock:
                entry = self._lease_cache.get(key)
                if entry is None or len(entry["leases"]) >= max_leases:
                    self._lease_tasks.pop(tid, None)
                    self.agent.fire("return_lease",
                                    {"lease_id": grant["lease_id"]})
                    return False
                entry["spillable"] = bool(grant.get("spillable", True))
                entry["leases"].append(lease)
                self._lease_tasks[tid] = (key, lease["lease_id"], now)
        if lease is None:
            return False
        return self._lease_push(key, lease, spec, requeue_on_fail=False)

    def _lease_push(self, key: tuple, lease: dict, spec: dict,
                    requeue_on_fail: bool) -> bool:
        """Push a reserved task to its leased worker. Called from submit
        threads AND from the io loop (refill on result); the send is a
        coalesced fire either way. requeue_on_fail routes the task to the
        agent queue when the push fails (refill has no caller to return
        False to)."""
        tid = spec["task_id"]
        push = {k: v for k, v in spec.items() if not k.startswith("_")}
        push["leased"] = True  # lets the executor batch its done-reports
        addr = {"addr": lease["addr"], "port": lease["port"]}
        # from the io loop, only a CACHED peer is safe (_peer's connect
        # blocks on this very loop); leases pushed at least once from a
        # submit thread always have one
        if threading.current_thread() is self.io.thread:
            cli = self._peer_clients.get((lease["addr"], lease["port"]))
        else:
            cli = self._peer(addr)
        # a closed client means the frame could only land in a dead
        # transport — SyncRpcClient.fire would swallow that silently
        # (the historical "lost execute_task fire" wedge: the task sat
        # leased forever while the pool idled)
        ok = cli is not None and not cli.client.closed
        if ok:
            try:
                from ray_tpu._private import fault_injection as _fi

                if _fi.enabled() and _fi.fire(
                        "worker.lease_push",
                        task=spec.get("name", "")) == "drop":
                    pass  # chaos: simulate the push lost in the write
                    # path — bookkeeping stays, the probe must recover
                else:
                    # fire, not a blocking oneway: the io-loop round
                    # trip per push (~1ms thread hop) was the
                    # submission ceiling. An async write failure means
                    # the leased worker died — the agent's worker-death
                    # → lease_revoked path fails the task over to the
                    # queue; the liveness probe (_pending_pump) covers
                    # writes lost with the worker still alive.
                    cli.fire("execute_task", push)
            except (rpc.ConnectionLost, rpc.RpcError):
                ok = False
        if not ok:
            with self._lease_lock:
                self._lease_tasks.pop(tid, None)
            # the whole lease is suspect (its connection just failed):
            # sweep every OTHER task recorded on it through the shared
            # failover helper — it drops the lease, drains pendings,
            # tells the agent (lease_tasks_lost + return_lease), and
            # resubmits — instead of leaving them as unprobeable
            # orphans for the pump to find later
            self._fail_lost_lease_tasks(key, lease["lease_id"], [])
            if requeue_on_fail:
                self._enqueue_submit(spec)
            return False
        # async: let the agent track the leased task so its worker-death
        # notification path covers direct pushes too (slim spec: the
        # agent only needs identity/owner/shape for failover + cancel).
        # Buffered: one lease_tasks_started frame per burst — the agent
        # loop's per-frame dispatch is the multi-owner throughput
        # ceiling, so started-tracking must not cost a frame per task.
        self._buffer_lease_started({
            "lease_id": lease["lease_id"],
            "spec": {k: push[k] for k in
                     ("task_id", "job_id", "name", "resources", "owner",
                      "num_returns") if k in push},
        })
        # owner-side node tracking for direct pushes (they bypass the
        # agents' task_located notifies entirely)
        self._task_nodes[tid] = self.node_id
        # the liveness pump must run while ANY lease task is in flight:
        # it is the only recovery for a push lost with the worker alive
        self._ensure_lease_pump()
        return True

    def _ensure_lease_pump(self):
        with self._lease_lock:
            if self._pending_pump_running:
                return
            self._pending_pump_running = True
        self.io.call_soon(self._start_pending_pump)

    def _buffer_lease_started(self, item: dict):
        with self._lease_started_lock:
            self._lease_started_buf.append(item)
            if len(self._lease_started_buf) > 1:
                return  # a flush is already scheduled for this burst
        try:
            self.io.loop.call_soon_threadsafe(self._flush_lease_started)
        except RuntimeError:  # loop closed mid-shutdown
            pass

    def _flush_lease_started(self):  # io loop
        with self._lease_started_lock:
            items = self._lease_started_buf
            self._lease_started_buf = []
        if items:
            self.agent.fire("lease_tasks_started", {"items": items})

    def _start_pending_pump(self):  # io loop
        import asyncio

        asyncio.ensure_future(self._pending_pump())

    async def _pending_pump(self):
        """Lease liveness pump. While any scheduling key holds owner-side
        pending tasks, keep them live: re-try lease grants once the
        refusal window lapses and flush pendings that made no progress
        for 2s to the agent queue (in-flight tasks may be long-running;
        the agent can spawn workers or spill where the owner cannot).

        While any lease task is IN FLIGHT, additionally run the
        delivery probe (_probe_lease_tasks): a pushed execute_task is an
        unacked fire, and a frame lost with the worker still alive used
        to wedge a whole round of tasks — leased forever, pool idle —
        until the 600s test watchdog (ROADMAP 'owner-lease liveness
        wedge'). The probe detects undelivered pushes in ~probe_s and
        fails them over through the queue."""
        import asyncio

        from ray_tpu._private import config as _cfg

        max_leases = _cfg.get("worker_lease_max_per_key")
        loop = asyncio.get_running_loop()
        try:
            while True:
                await asyncio.sleep(0.1)
                now = time.monotonic()
                drains: list[dict] = []
                grant_keys: list[tuple] = []
                with self._lease_lock:
                    busy_keys = [k for k, e in self._lease_cache.items()
                                 if e["pending"]]
                    if not busy_keys and not self._lease_tasks:
                        self._pending_pump_running = False
                        return
                    for key in busy_keys:
                        e = self._lease_cache[key]
                        stalled = (now - e.get("pending_since", now)) > 2.0
                        if not e["leases"] or stalled:
                            drains.extend(e["pending"])
                            e["pending"] = []
                        elif (now >= e["no_grant_until"]
                              and len(e["leases"]) < max_leases):
                            grant_keys.append(key)
                for s in drains:
                    self._enqueue_submit(s)
                for key in grant_keys:
                    await self._pump_grant_one(key, loop)
                await self._probe_lease_tasks(now)
        except Exception:
            with self._lease_lock:
                self._pending_pump_running = False
            raise

    async def _probe_lease_tasks(self, now: float):
        """Fail over lease tasks whose execute_task push never reached
        the worker. The worker records every task id at frame ingress
        (Executor._seen_tids); probing over the SAME connection the push
        used makes the reply a delivery barrier (TCP FIFO + in-order
        frame dispatch): 'unknown' means the push is not behind us in
        the pipe — it was lost — so resubmission cannot double-execute."""
        from ray_tpu._private import config as _cfg

        probe_s = _cfg.get("worker_lease_probe_s")
        groups: dict[tuple, list[bytes]] = {}
        orphans: list[tuple] = []  # (key, lease_id, tid)
        with self._lease_lock:
            for tid, rec in self._lease_tasks.items():
                key, lid, pushed = rec
                if now - pushed < probe_s:
                    continue
                entry = self._lease_cache.get(key)
                lease = None
                if entry is not None:
                    lease = next((l for l in entry["leases"]
                                  if l["lease_id"] == lid), None)
                if lease is None:
                    # lease record already dropped but the task was
                    # never completed or failed over: orphan (keep its
                    # lease_id — the AGENT may still hold the task
                    # active on that lease / migrated to pool_inflight,
                    # pinning the worker until it is told)
                    orphans.append((key, lid, tid))
                else:
                    if now - lease.get("_last_probe", 0.0) < probe_s:
                        continue  # a long-RUNNING task is re-probed
                        # once per probe period, not per pump tick
                    groups.setdefault(
                        (lease["addr"], lease["port"], lid, key),
                        []).append(tid)
            for (_a, _p, lid, key) in groups:
                entry = self._lease_cache.get(key)
                if entry is not None:
                    for l in entry["leases"]:
                        if l["lease_id"] == lid:
                            l["_last_probe"] = now
        by_lease: dict = {}
        for key, lid, tid in orphans:
            by_lease.setdefault((key, lid), []).append(tid)
        for (key, lid), tids in by_lease.items():
            self._fail_lost_lease_tasks(key, lid, tids)
        for (addr, port, lid, key), tids in groups.items():
            cli = self._peer_clients.get((addr, port))
            if cli is None or cli.client.closed:
                # No cached client. Usually the connection died after
                # the push (eviction via _notify_peer_lost) — but it
                # can also mean the FIRST connect from a submit thread
                # is still in progress (the task is recorded before
                # _lease_push's _peer() call); give that window extra
                # probe periods before declaring the lease dead, or a
                # slow connect double-executes every task on it.
                with self._lease_lock:
                    ages = [now - self._lease_tasks[t][2]
                            for t in tids if t in self._lease_tasks]
                if not ages or min(ages) < 3 * probe_s:
                    continue
                # connection gone for good: everything unacked on it is
                # undeliverable — sweep the lease (same at-least-once
                # contract as the worker-death lease_revoked failover)
                self._fail_lost_lease_tasks(key, lid, tids)
                continue
            if (cli._fire_buf or cli.client._fire_out
                    or cli.client._fire_drain_task is not None):
                continue  # unflushed fires: barrier not valid yet
            try:
                res = await cli.client.call(
                    "probe_tasks", {"task_ids": tids}, timeout=5.0)
            except Exception:  # noqa: BLE001 — probe itself failed:
                continue  # connection teardown will re-enter above
            known = set(res.get("known", ()))
            lost = [t for t in tids if t not in known]
            if lost:
                # the connection is ALIVE (the probe answered) and the
                # barrier proved these frames never arrived: fail over
                # ONLY the lost tasks and KEEP the lease — the known
                # ones are delivered and running; sweeping them too
                # would double-execute work the probe just confirmed
                self._fail_lost_lease_tasks(key, lid, lost,
                                            sweep=False)

    def _fail_lost_lease_tasks(self, key, lease_id, tids: list[bytes],
                               *, sweep: bool = True):
        """Owner-side recovery for confirmed-lost pushes.

        sweep=True (connection dead / lease being torn down): drop the
        lease, sweep EVERY task recorded on it into the failover, tell
        the agent (active set + pool_inflight scrub + lease return) —
        the same at-least-once contract as worker-death revocation.

        sweep=False (connection alive, probe isolated the losses): fail
        over ONLY `tids`, decrement the lease's in-flight count for
        them, and KEEP the lease serving its delivered tasks."""
        drain: list[dict] = []
        tids = list(tids)
        with self._lease_lock:
            if sweep and lease_id is not None:
                # leaving younger tasks behind on a dropped lease would
                # orphan them with the agent still pinning the worker
                tids.extend(
                    t for t, rec in self._lease_tasks.items()
                    if rec[1] == lease_id and t not in tids)
            for tid in tids:
                self._lease_tasks.pop(tid, None)
            if key is not None:
                entry = self._lease_cache.get(key)
                if entry is not None:
                    if sweep:
                        entry["leases"] = [
                            l for l in entry["leases"]
                            if l["lease_id"] != lease_id
                        ]
                        if not entry["leases"] and entry["pending"]:
                            drain = entry["pending"]
                            entry["pending"] = []
                    else:
                        for l in entry["leases"]:
                            if l["lease_id"] == lease_id:
                                # their results will never arrive to
                                # decrement this
                                l["inflight"] = max(
                                    0, l["inflight"] - len(tids))
        if lease_id is not None:
            try:
                self.agent.fire("lease_tasks_lost",
                                {"lease_id": lease_id, "task_ids": tids})
                if sweep:
                    self.agent.fire("return_lease",
                                    {"lease_id": lease_id})
            except (rpc.ConnectionLost, rpc.RpcError):
                pass
        for s in drain:
            self._enqueue_submit(s)
        if not tids:
            return  # lease dropped + agent told; nothing to fail over
        logger.warning(
            "lease liveness probe: %d task(s) lost on lease %s; "
            "failing over to queued submission", len(tids),
            lease_id.hex()[:8] if lease_id else "<dropped>")

        def _failover(ts=list(tids)):
            for tid in ts:
                self._handle_task_failed(
                    {"task_id": tid, "reason": "lease push lost",
                     "retriable": True})
        threading.Thread(target=_failover, daemon=True).start()

    async def _pump_grant_one(self, key: tuple, loop):
        import asyncio

        with self._lease_lock:
            e = self._lease_cache.get(key)
            if e is None or not e["pending"]:
                return
            res = dict(e["pending"][0].get("resources", {}))
        import asyncio

        try:
            grant = await self.agent.client.call("lease_worker", {
                "resources": res, "job_id": self.job_id,
                "owner": self.owner_address,
            }, timeout=10.0)
        except (rpc.ConnectionLost, rpc.RpcError, asyncio.TimeoutError):
            return
        now = time.monotonic()
        if not grant or "lease_id" not in grant:
            with self._lease_lock:
                e = self._lease_cache.get(key)
                if e is not None:
                    e["no_grant_until"] = now + 0.2
                    e["spillable"] = bool(
                        (grant or {}).get("spillable", True))
            return
        # peer connect must not block this loop
        await loop.run_in_executor(
            None, self._peer, {"addr": grant["addr"], "port": grant["port"]}
        )
        lease = {**grant, "inflight": 1, "_last_use": now,
                 "expires": now + grant["ttl_s"] * 0.8}
        spec = None
        with self._lease_lock:
            e = self._lease_cache.get(key)
            if e is None or not e["pending"]:
                spec = None
            else:
                e["spillable"] = bool(grant.get("spillable", True))
                e["leases"].append(lease)
                spec = e["pending"].pop(0)
                e["pending_since"] = now
                self._lease_tasks[spec["task_id"]] = (
                    key, lease["lease_id"], now)
        if spec is None:
            self.agent.fire("return_lease", {"lease_id": grant["lease_id"]})
            return
        self._lease_push(key, lease, spec, requeue_on_fail=True)

    async def rpc_lease_revoked(self, conn, p):
        """Agent reclaimed our lease (TTL lapse, actor priority, or the
        leased worker died): drop the cache entry and fail over any task
        still in flight on it — the direct push may have raced the
        agent's own task tracking, so the owner is the backstop."""
        wid = p.get("worker_id")
        orphans: list[bytes] = []
        drain: list[dict] = []
        with self._lease_lock:
            dead_ids = set()
            for entry in self._lease_cache.values():
                for lease in entry["leases"]:
                    if lease.get("worker_id") == wid:
                        dead_ids.add(lease["lease_id"])
                entry["leases"] = [
                    l for l in entry["leases"]
                    if l["lease_id"] not in dead_ids
                ]
                if not entry["leases"] and entry["pending"]:
                    drain.extend(entry["pending"])
                    entry["pending"] = []
            orphans.extend(
                tid for tid, rec in self._lease_tasks.items()
                if rec[1] in dead_ids
            )
        for s in drain:
            self._enqueue_submit(s)
        if orphans:
            def _failover(tids=orphans):
                for tid in tids:
                    self._handle_task_failed(
                        {"task_id": tid, "reason": "lease revoked",
                         "retriable": True})
            # one thread for the whole revocation: a reclaim that caught
            # a deep pipeline would otherwise fork a thread per task
            threading.Thread(target=_failover, daemon=True).start()
        return True

    def _on_lease_task_done(self, task_id: bytes, failed: bool):
        refill: list[dict] = []
        drain: list[dict] = []
        with self._lease_lock:
            rec = self._lease_tasks.pop(task_id, None)
            if rec is None:
                return
            key, lease_id = rec[0], rec[1]
            entry = self._lease_cache.get(key)
            if entry is None:
                return
            lease = next(
                (l for l in entry["leases"] if l["lease_id"] == lease_id),
                None,
            )
            if lease is None:
                return  # the task's lease was dropped/replaced already
            if failed:
                # worker likely died; agent released its half already
                entry["leases"].remove(lease)
                if not entry["leases"] and entry["pending"]:
                    drain = entry["pending"]
                    entry["pending"] = []
            else:
                lease["inflight"] = max(0, lease["inflight"] - 1)
                lease["_last_use"] = time.monotonic()
                lease["expires"] = time.monotonic() + lease["ttl_s"] * 0.8
                if entry["pending"]:
                    # refill: top the lease back up to depth from the
                    # owner-side queue — the drain loop (result → next
                    # pushes) never touches the agent (reference lease
                    # pipelining), and deep worker queues let executors
                    # batch result pushes
                    from ray_tpu._private import config as _cfg

                    depth = _cfg.get("worker_lease_depth")
                    refill = []
                    while entry["pending"] and lease["inflight"] < depth:
                        s = entry["pending"].pop(0)
                        lease["inflight"] += 1
                        self._lease_tasks[s["task_id"]] = (
                            key, lease_id, time.monotonic())
                        refill.append(s)
                    if refill:
                        entry["pending_since"] = time.monotonic()
                        lease["_last_use"] = entry["pending_since"]
        for s in drain:
            self._enqueue_submit(s)
        if failed:
            return
        now = time.monotonic()
        if now - lease.get("_last_renew", 0.0) > lease["ttl_s"] * 0.25:
            # rate-limited: one renew per TTL quarter, not one per result
            lease["_last_renew"] = now
            try:
                self.agent.fire("renew_lease",
                                {"lease_id": lease["lease_id"]})
            except (rpc.ConnectionLost, rpc.RpcError):
                pass
        for s in refill:
            self._lease_push(key, lease, s, requeue_on_fail=True)

    def _pack_args(self, args, kwargs):
        """Serialize args; extract refs as deps; inline owned small values.

        Returns (args_payload, plasma_deps, inline_values{oid: payload}).
        The agent stages plasma deps locally before dispatch; inline values
        travel in the spec (reference: dependency resolver inlining,
        transport/dependency_resolver.cc).
        """
        meta, views, refs, size = serialization.serialize_views(
            (args, kwargs))
        deps: list[bytes] = []
        inline_values: dict[bytes, list] = {}
        for ref in refs:
            oid = ref.binary()
            e = self.memory.get(oid)
            if e is not None:
                e.escaped = True
            if e is not None and e.ready and not e.in_plasma:
                if e.error is None:
                    inline_values[oid] = e.payload
                else:
                    inline_values[oid] = ["__error__", e.error]
            elif e is not None and not e.ready:
                # pending result we own: executor will pull from us on demand
                inline_values[oid] = ["__owner__", self.owner_address]
                deps_marker = None  # noqa: F841 — documents intent
            else:
                deps.append(oid)
        if size > INLINE_MAX:
            # big args → plasma object (single-copy: views go straight
            # into the segment), executor reads locally after staging
            args_oid = ObjectID.for_put(
                WorkerID(self.worker_id), self.put_counter.next()
            ).binary()
            self._put_plasma(args_oid, [meta, views])
            e = self._entry(args_oid)
            e.owned = True
            e.in_plasma = True
            e.event.set()
            deps.append(args_oid)
            return {"args_oid": args_oid}, deps, inline_values
        return {"payload": [meta, [bytes(v) for v in views]]}, \
            deps, inline_values

    # ------------- actor submission (owner side) -------------

    def register_actor(self, *, actor_id: bytes, cls, args, kwargs,
                       name=None, namespace="default", detached=False,
                       max_restarts=0, resources=None, pg_id=None,
                       bundle_index=-1, max_concurrency=1,
                       get_if_exists=False,
                       runtime_env: dict | None = None,
                       concurrency_groups: dict | None = None,
                       method_groups: dict | None = None) -> dict:
        spec = serialization.pack_payload((cls, args, kwargs))
        reply = self.head.call(
            "register_actor",
            task_spec.ActorCreationSpec.build(
                actor_id=actor_id, job_id=self.job_id,
                name=name, namespace=namespace, detached=detached,
                max_restarts=max_restarts,
                resources=resources or {"CPU": 1.0},
                spec=spec, owner_addr=self.owner_address,
                pg_id=pg_id, bundle_index=bundle_index,
                max_concurrency=max_concurrency,
                get_if_exists=get_if_exists,
                runtime_env=(self._prepare_runtime_env(runtime_env)
                             if runtime_env else None),
                concurrency_groups=concurrency_groups or {},
                method_groups=method_groups or {},
            ),
        )
        return reply

    def _actor_client(self, actor_id: bytes) -> rpc.SyncRpcClient:
        cli = self._actor_clients.get(actor_id)
        if cli is not None:
            return cli
        # as long as the agent gives the actor to come up (it turns DEAD
        # there past that, which ends this wait at once)
        deadline = time.monotonic() + _config.get(
            "worker_register_timeout_s") + _config.get(
            "actor_create_timeout_s")
        while time.monotonic() < deadline:
            info = self._actor_info.get(actor_id)
            if info is None or info["state"] not in ("ALIVE", "DEAD"):
                info = self.head.call("wait_actor_alive", {
                    "actor_id": actor_id,
                    "timeout": max(0.1, deadline - time.monotonic()),
                })
                if info is not None:
                    self._actor_info[actor_id] = info
            if info is None:
                raise RayActorError(f"actor {actor_id.hex()[:12]} unknown")
            if info["state"] == "DEAD":
                raise RayActorError(
                    f"actor is dead: {info.get('death_reason')}"
                )
            if info["state"] == "ALIVE" and info.get("worker_addr"):
                addr, port = info["worker_addr"]
                try:
                    cli = rpc.SyncRpcClient(addr, port, self.io)
                except rpc.ConnectionLost:
                    time.sleep(0.1)
                    continue
                self._actor_clients[actor_id] = cli
                return cli
            time.sleep(0.05)
        raise RayActorError(
            f"timed out waiting for actor {actor_id.hex()[:12]}"
        )

    def submit_actor_task(self, actor_id: bytes, method_name: str,
                          args, kwargs, *, num_returns: int = 1,
                          concurrency_group: str | None = None,
                          fetch_tags: dict | None = None) -> list[bytes]:
        seq = self._actor_seq.setdefault(actor_id, _Counter()).next()
        task_id = TaskID.for_actor_task(ActorID(actor_id), seq).binary()
        args_spec, deps, inline_values = self._pack_args(args, kwargs)
        call = task_spec.ActorTaskSpec.build(
            task_id=task_id,
            actor_id=actor_id,
            method=method_name,
            args=args_spec,
            inline_values=inline_values,
            deps=deps,
            num_returns=num_returns,
            owner=self.owner_address,
            seq=seq,
            concurrency_group=concurrency_group,
            trace=_trace.for_submit(),
            fetch_tags=fetch_tags,
        )
        return_ids = [
            ObjectID.for_task_return(TaskID(task_id), i).binary()
            for i in range(num_returns)
        ]
        for oid in return_ids:
            self._entry(oid).owned = True
        self._actor_pending.setdefault(actor_id, set()).add(task_id)
        self._send_actor_call(actor_id, call)
        return return_ids

    def _send_actor_call(self, actor_id: bytes, call: dict):
        try:
            cli = self._actor_client(actor_id)
            # fire (coalesced outbox), not a blocking oneway: per-call io
            # round trips capped 1:1 actor throughput ~1k/s. An async
            # write failure means the actor's worker died — the
            # actor_update DEAD/RESTARTING push fails over _actor_pending.
            cli.fire("actor_call", call)
        except (rpc.ConnectionLost, rpc.RpcError, RayActorError) as e:
            err = serialization.pack_payload(
                e if isinstance(e, RayActorError) else RayActorError(str(e))
            )
            for i in range(call["num_returns"]):
                oid = ObjectID.for_task_return(
                    TaskID(call["task_id"]), i
                ).binary()
                entry = self._entry(oid)
                entry.error = err
                entry.event.set()
            self._actor_pending.get(actor_id, set()).discard(call["task_id"])

    def actor_task_finished(self, actor_id: bytes, task_id: bytes):
        self._actor_pending.get(actor_id, set()).discard(task_id)

    def kill_actor(self, actor_id: bytes, no_restart: bool = True,
                   blocking: bool = True, timeout: float = 60.0):
        msg = {"actor_id": actor_id, "no_restart": no_restart}
        if blocking and threading.current_thread() is not self.io.thread:
            try:
                self.head.call("kill_actor", msg, timeout=timeout)
            except (TimeoutError, asyncio.TimeoutError):
                # a wedged kill path must not hang teardown forever:
                # downgrade to fire-and-forget (the head applies it when
                # it can; reap/escalation owns the process itself)
                logger.warning("kill_actor %s timed out after %.0fs; "
                               "downgrading to fire-and-forget",
                               actor_id.hex()[:12], timeout)
                self.head.fire("kill_actor", msg)
        else:
            self.head.fire("kill_actor", msg)


def _noop(buf):
    pass


def _pin_buffers_to_arrays(value, buf, depth: int = 0):
    """Attach `buf` to the lifetime of every zero-copy ndarray in `value`."""
    import weakref

    import numpy as np

    if depth > 4:
        return
    if isinstance(value, np.ndarray):
        if value.base is not None:  # a view → backed by the store segment
            weakref.finalize(value, _noop, buf)
        return
    if isinstance(value, dict):
        for v in value.values():
            _pin_buffers_to_arrays(v, buf, depth + 1)
    elif isinstance(value, (list, tuple)):
        for v in value:
            _pin_buffers_to_arrays(v, buf, depth + 1)
    else:
        try:
            weakref.finalize(value, _noop, buf)
        except TypeError:
            pass  # immutable scalar-like: data was copied by pickle anyway
