"""Public runtime API: init/remote/get/put/wait + actors + placement groups.

Analog of reference `python/ray/_private/worker.py` (init:1123, get:2425,
put:2549, wait:2611, kill:2767) + `remote_function.py:241` + `actor.py:660`.
Local-mode init runs the control plane and node agent on background event
loops in the driver process while executors are real subprocesses — the
same topology the reference gets from gcs_server/raylet processes, minus
two process hops on localhost.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
import time
from typing import Any, Sequence

from ray_tpu._private import serialization
from ray_tpu._private.ids import ActorID, JobID, PlacementGroupID
from ray_tpu._private.rpc import EventLoopThread
from ray_tpu._private.worker import (
    CoreWorker,
    GetTimeoutError,
    ObjectLostError,
    RayActorError,
    RayTaskError,
)

logger = logging.getLogger(__name__)

_state_lock = threading.RLock()
_worker: CoreWorker | None = None
_cluster = None  # LocalCluster when we started one


def _set_global_worker(worker):
    global _worker
    _worker = worker


def _get_worker() -> CoreWorker:
    if _worker is None:
        raise RuntimeError(
            "ray_tpu.init() has not been called in this process"
        )
    return _worker


class ObjectRef:
    """Reference to a (possibly pending) object. Reference: ObjectRef in
    _raylet.pyx; serializing a ref inside task args registers it as a
    dependency via serialization.note_object_ref.

    Each live ObjectRef counts one local reference in this process's
    CoreWorker (reference_count.h:102 AddLocalReference analog); the count
    transitions 0↔1 are reported to the control-plane directory, which
    frees cluster-wide copies when no process holds a reference
    (centralized redesign of the owner/borrower protocol — the directory
    already is the single source of object locations)."""

    __slots__ = ("_id", "_counted")

    def __init__(self, id_bytes: bytes):
        self._id = id_bytes
        self._counted = False
        w = _worker
        if w is not None:
            try:
                w.add_local_ref(id_bytes)
                self._counted = True
            except Exception:  # noqa: BLE001 — refcounting is best-effort
                pass

    def binary(self) -> bytes:
        return self._id

    def hex(self) -> str:
        return self._id.hex()

    def __hash__(self):
        return hash(self._id)

    def __eq__(self, other):
        return isinstance(other, ObjectRef) and other._id == self._id

    def __repr__(self):
        return f"ObjectRef({self._id.hex()[:16]}…)"

    def as_future(self):
        """asyncio.Future resolving to the object (reference
        ObjectRef.as_future / `await ref` in _raylet.pyx). One shared
        resolver thread multiplexes every pending await via wait() —
        gathering thousands of refs costs one thread, not one each."""
        import asyncio

        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        # pass the ref itself: the resolver must keep it alive or the
        # awaited object could be GC-freed cluster-wide mid-await
        _future_resolver().register(self, loop, fut)
        return fut

    def __await__(self):
        return self.as_future().__await__()

    def __reduce__(self):
        serialization.note_object_ref(_RefProxy(self._id))
        return (ObjectRef, (self._id,))

    def __del__(self):
        if getattr(self, "_counted", False):
            w = _worker
            if w is not None:
                try:
                    w.remove_local_ref(self._id)
                except Exception:  # noqa: BLE001 — interpreter teardown
                    pass


class _FutureResolver:
    """One thread resolving every awaited ref (wait() multiplexing)."""

    def __init__(self):
        # oid -> (ref, [(loop, fut)]): holding the ref pins its refcount
        # (GC must not free an object someone is awaiting)
        self._pending: dict[bytes, tuple] = {}
        self._lock = threading.Lock()
        self._wake = threading.Event()
        threading.Thread(target=self._drive, daemon=True,
                         name="ray_tpu-await").start()

    def register(self, ref: "ObjectRef", loop, fut):
        with self._lock:
            entry = self._pending.get(ref._id)
            if entry is None:
                entry = self._pending[ref._id] = (ref, [])
            entry[1].append((loop, fut))
        self._wake.set()

    def _drive(self):
        while True:
            with self._lock:
                oids = list(self._pending)
            if not oids:
                self._wake.wait()
                self._wake.clear()
                continue
            try:
                ready, _ = _get_worker().wait(
                    oids, num_returns=1, timeout=0.5
                )
            except Exception:  # noqa: BLE001 — cluster going down
                time.sleep(0.2)
                continue
            for oid in ready:
                with self._lock:
                    entry = self._pending.pop(oid, None)
                if entry is None:
                    continue
                # fetch on a small pool: one slow get (spill restore,
                # remote pull) must not head-of-line-block every other
                # pending await in the process
                self._pool().submit(self._resolve_one, entry)

    def _pool(self):
        import concurrent.futures

        if getattr(self, "_fetch_pool", None) is None:
            self._fetch_pool = concurrent.futures.ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="ray_tpu-await-fetch"
            )
        return self._fetch_pool

    @staticmethod
    def _resolve_one(entry):
        ref, waiters = entry
        # NOTE: copy the except target — CPython deletes it at block
        # exit, racing the loop callback
        err = val = None
        try:
            val = get(ref)
        except BaseException as e:  # noqa: BLE001
            err = e
        for loop, fut in waiters:
            def resolve(fut=fut, err=err, val=val):
                if fut.cancelled():
                    return
                if err is not None:
                    fut.set_exception(err)
                else:
                    fut.set_result(val)

            try:
                loop.call_soon_threadsafe(resolve)
            except RuntimeError:
                pass  # loop closed; waiter is gone


_resolver: _FutureResolver | None = None


def _future_resolver() -> _FutureResolver:
    global _resolver
    with _state_lock:
        if _resolver is None:
            _resolver = _FutureResolver()
        return _resolver


class _RefProxy:
    """What the serializer's collector records (binary only)."""

    __slots__ = ("_id",)

    def __init__(self, id_bytes):
        self._id = id_bytes

    def binary(self):
        return self._id


class LocalCluster:
    """In-process head: control plane + node agent on a background loop.

    Reference analog: `_private/node.py` starting gcs_server + raylet
    (node.py:1147 start_head_processes) — here they're asyncio services on
    a daemon thread; executors remain separate OS processes.
    """

    def __init__(self, *, resources: dict | None = None,
                 store_capacity: int = 512 * 1024 * 1024,
                 heartbeat_timeout_s: float = 10.0):
        from ray_tpu.core.control_plane import ControlPlane
        from ray_tpu.core.node_agent import NodeAgent, detect_resources

        self.io = EventLoopThread("ray_tpu-cluster")
        self.session_id = os.urandom(4).hex()
        self.cp = ControlPlane(heartbeat_timeout_s=heartbeat_timeout_s)
        self.head_port = self.io.run(self.cp.start())
        res = resources if resources is not None else detect_resources()
        self.agent = NodeAgent(
            "127.0.0.1", self.head_port, resources=res,
            store_capacity=store_capacity, session_id=self.session_id,
        )
        self.agent_port = self.io.run(self.agent.start())

    def stop(self):
        from ray_tpu._private import accelerator

        try:
            # (the agent waits for the workers it gave chips to: they
            # keep them for some seconds after they died)
            self.io.run(self.agent.stop(),
                        timeout=accelerator.CHIP_WAIT_S + 10)
            self.io.run(self.cp.stop(), timeout=10)
        except Exception:
            pass
        self.io.stop()


def init(address: str | None = None, *, num_cpus: float | None = None,
         resources: dict | None = None,
         object_store_memory: int = 512 * 1024 * 1024,
         namespace: str = "default", log_to_driver: bool = True,
         _heartbeat_timeout_s: float = 10.0) -> dict:
    """Start (or connect to) a cluster. Reference: worker.py:1123 ray.init."""
    global _worker, _cluster
    with _state_lock:
        if _worker is not None:
            return {"address": "existing"}
        if address is not None and address.startswith("ray://"):
            # remote (agent-less) driver: full CoreWorker protocol over
            # TCP, plasma data plane via agent RPCs (_private/client.py)
            from ray_tpu._private.client import connect as _client_connect

            _worker = _client_connect(address, namespace=namespace)
            if log_to_driver:
                _worker.head.on_push("logs", _print_worker_log)
                _worker.head.call("subscribe", {"channel": "logs"})
            atexit.register(shutdown)
            return {"address": address, "mode": "client"}
        if address is None:
            res = dict(resources or {})
            if num_cpus is not None:
                res["CPU"] = float(num_cpus)
            elif "CPU" not in res:
                from ray_tpu.core.node_agent import detect_resources

                res = {**detect_resources(), **res}
            if "TPU" not in res:
                # naming the CPUs must not hide the chips: work that
                # asks for TPU would wait forever for a resource the
                # node never offered
                from ray_tpu._private import accelerator

                chips = accelerator.detect_tpu_chips()
                if chips:
                    res["TPU"] = float(chips)
            res.setdefault("memory", 8 * 2**30)
            _cluster = LocalCluster(
                resources=res, store_capacity=object_store_memory,
                heartbeat_timeout_s=_heartbeat_timeout_s,
            )
            head_addr, head_port = "127.0.0.1", _cluster.head_port
            agent_addr, agent_port = "127.0.0.1", _cluster.agent_port
            store_name = _cluster.agent.store_name
            node_id = _cluster.agent.node_id
        else:
            head_addr, head_port_s = address.rsplit(":", 1)
            head_port = int(head_port_s)
            # connect to this node's agent via the head's cluster view
            import msgpack  # noqa: F401 — ensure dep present

            from ray_tpu._private import rpc as _rpc

            io = EventLoopThread("ray_tpu-probe")
            probe = _rpc.SyncRpcClient(head_addr, head_port, io)
            view = probe.call("get_cluster_view", {})
            probe.close()
            io.stop()
            if not view["nodes"]:
                raise RuntimeError("cluster has no alive nodes")
            # the driver attaches a node's SHARED-MEMORY store, so it must
            # be co-located with that node: prefer loopback/local agents
            import socket as _socket

            local = {"127.0.0.1", "0.0.0.0", "localhost",
                     _socket.gethostname()}
            try:
                local.add(_socket.gethostbyname(_socket.gethostname()))
            except OSError:
                pass
            candidates = [n for n in view["nodes"]
                          if n["alive"] and n["addr"] in local]
            if not candidates:
                raise RuntimeError(
                    "no node agent runs on this host; a driver must "
                    "connect through a local agent (its object store is "
                    "shared memory) — start one with "
                    "`python -m ray_tpu.scripts start --address ...`"
                )
            me = candidates[0]
            agent_addr, agent_port = me["addr"], me["port"]
            io2 = EventLoopThread("ray_tpu-probe2")
            probe2 = _rpc.SyncRpcClient(agent_addr, agent_port, io2)
            info = probe2.call("node_info", {})
            probe2.close()
            io2.stop()
            node_id = info["node_id"]
            store_name = info["store_name"]

        job_id = JobID.from_random().binary()
        worker = CoreWorker(
            head_addr=head_addr, head_port=head_port,
            agent_addr=agent_addr, agent_port=agent_port,
            store_name=store_name, node_id=node_id, job_id=job_id,
            is_driver=True,
        )
        worker.namespace = namespace
        worker.register_job({
            "job_id": job_id,
            "driver_addr": [worker.addr, worker.port],
        })
        if log_to_driver:
            worker.head.on_push("logs", _print_worker_log)
            worker.head.call("subscribe", {"channel": "logs"})
        _worker = worker
        atexit.register(shutdown)
        return {"address": f"{head_addr}:{head_port}", "job_id": job_id}


def _print_worker_log(p):
    import sys

    stream = sys.stderr if p.get("kind") == "err" else sys.stdout
    wid = p.get("worker_id", b"").hex()[:6]
    line = p.get("line", "")
    # structured tqdm_ray progress lines render in place, not as logs
    from ray_tpu.experimental.tqdm_ray import maybe_render

    if maybe_render(line):
        return
    # jax/XLA emit volumes of WARNING noise; keep driver output readable
    print(f"({wid}) {line}", file=stream)


def shutdown():
    global _worker, _cluster
    with _state_lock:
        if _worker is not None:
            try:
                _worker.head.call("finish_job", {"job_id": _worker.job_id})
            except Exception:
                pass
            _worker.shutdown()
            _worker = None
        if _cluster is not None:
            _cluster.stop()
            _cluster = None


def is_initialized() -> bool:
    return _worker is not None


# ---------------- tasks ----------------

class RemoteFunction:
    """Reference: remote_function.py:241 RemoteFunction._remote."""

    def __init__(self, func, *, num_returns=1, num_cpus=1.0, num_tpus=0.0,
                 resources=None, max_retries=3, scheduling_strategy=None,
                 runtime_env=None):
        self._func = func
        self._opts = {
            "num_returns": num_returns,
            "num_cpus": num_cpus,
            "num_tpus": num_tpus,
            "resources": resources or {},
            "max_retries": max_retries,
            "scheduling_strategy": scheduling_strategy,
            "runtime_env": runtime_env,
            "fetch_tags": None,
        }
        self.__name__ = getattr(func, "__name__", "remote_function")

    def __getstate__(self):
        # drop the per-worker export cache: it holds the CoreWorker
        # (locks, sockets) and is process-local by definition
        state = dict(self.__dict__)
        state.pop("_func_id_cache", None)
        return state

    def options(self, **kw) -> "RemoteFunction":
        new = RemoteFunction(self._func)
        new._opts = {**self._opts}
        for k, v in kw.items():
            if k in new._opts:
                new._opts[k] = v
            elif k == "placement_group":
                new._opts["placement_group"] = v
            elif k == "placement_group_bundle_index":
                new._opts["placement_group_bundle_index"] = v
            elif k == "name":
                new._opts["name"] = v
            else:
                raise TypeError(f"unknown option {k}")
        return new

    def remote(self, *args, **kwargs):
        w = _get_worker()
        o = self._opts
        # export once per (worker, function): re-cloudpickling the
        # function per .remote() dominated bursty submission profiles
        cache = getattr(self, "_func_id_cache", None)
        if cache is None or cache[0] is not w:
            # cross-interpreter envs ship SOURCE, not bytecode: a
            # python_version worker can't execute this minor's code
            # objects (serialization.pack_callable_source)
            by_source = bool(
                (o.get("runtime_env") or {}).get("python_version"))
            cache = (w, w.export_function(self._func,
                                          by_source=by_source))
            self._func_id_cache = cache
        res = {"CPU": float(o["num_cpus"]), **o["resources"]}
        if o["num_tpus"]:
            res["TPU"] = float(o["num_tpus"])
        pg = o.get("placement_group")
        pg_kw = {}
        if pg is not None:
            pg_kw = {
                "pg_id": pg.id.binary(),
                "bundle_index": o.get("placement_group_bundle_index", -1),
                "bundle_nodes": pg.bundle_nodes,
            }
        ids = w.submit_task(
            self._func, args, kwargs,
            num_returns=o["num_returns"], resources=res,
            retries=o["max_retries"],
            scheduling_strategy=o["scheduling_strategy"],
            runtime_env=o.get("runtime_env"),
            name=o.get("name", self.__name__), func_id=cache[1],
            fetch_tags=o.get("fetch_tags"), **pg_kw,
        )
        refs = [ObjectRef(i) for i in ids]
        return refs[0] if o["num_returns"] in (1, "dynamic") else refs

    def bind(self, *args, **kwargs):
        """Build a lazy DAG node (reference dag_node.py:23 .bind)."""
        from ray_tpu.dag.dag_node import _bind

        return _bind(self, *args, **kwargs)

    def __call__(self, *a, **kw):
        raise TypeError(
            f"remote function {self.__name__} cannot be called directly; "
            "use .remote()"
        )


# ---------------- actors ----------------

class ActorMethod:
    def __init__(self, handle: "ActorHandle", name: str):
        self._handle = handle
        self._name = name
        self._num_returns = 1
        self._concurrency_group = None
        self._fetch_tags = None

    def options(self, num_returns=1, concurrency_group=None,
                fetch_tags=None, **_):
        """fetch_tags={"qos": ..., "owner": ...} tags the executor-side
        ObjectRef arg fetches (and the cross-node pulls behind them)
        with the consuming subsystem for pacing + byte attribution."""
        m = ActorMethod(self._handle, self._name)
        m._num_returns = num_returns
        m._concurrency_group = concurrency_group
        m._fetch_tags = dict(fetch_tags) if fetch_tags else None
        return m

    def remote(self, *args, **kwargs):
        w = _get_worker()
        ids = w.submit_actor_task(
            self._handle._actor_id, self._name, args, kwargs,
            num_returns=self._num_returns,
            concurrency_group=self._concurrency_group,
            fetch_tags=self._fetch_tags,
        )
        refs = [ObjectRef(i) for i in ids]
        return refs[0] if self._num_returns == 1 else refs

    def bind(self, *args, **kwargs):
        """Lazy DAG node over this actor method (dag_node.py:23)."""
        from ray_tpu.dag.dag_node import _bind

        return _bind(self, *args, **kwargs)


class ActorHandle:
    """Reference: actor.py ActorHandle; serializable across tasks.

    Lifetime (simplified from the reference's all-handles refcount): the
    handle returned by `.remote()` owns the actor — when it is GC'd, the
    actor is terminated, unless the actor is named or detached. Copies that
    traveled through serialization never own.
    """

    def __init__(self, actor_id: bytes, owns: bool = False):
        self._actor_id = actor_id
        self._owns = owns

    def __getattr__(self, name):
        # "__ray_tpu_*" names are framework hooks (e.g. the collective-group
        # init installed by CollectiveActorMixin) and are callable remotely.
        if name.startswith("_") and not name.startswith("__ray_tpu_"):
            raise AttributeError(name)
        return ActorMethod(self, name)

    def __repr__(self):
        return f"ActorHandle({self._actor_id.hex()[:12]}…)"

    def __reduce__(self):
        return (ActorHandle, (self._actor_id,))

    def __del__(self):
        if getattr(self, "_owns", False) and _worker is not None:
            try:
                _worker.kill_actor(self._actor_id, no_restart=True,
                                   blocking=False)
            except Exception:
                pass  # interpreter shutdown / cluster already gone

    @property
    def _id(self):
        return self._actor_id


class ActorClass:
    def __init__(self, cls, *, num_cpus=1.0, num_tpus=0.0, resources=None,
                 max_restarts=0, max_concurrency=1, runtime_env=None,
                 concurrency_groups=None):
        self._cls = cls
        self._opts = {
            "num_cpus": num_cpus, "num_tpus": num_tpus,
            "resources": resources or {}, "max_restarts": max_restarts,
            "max_concurrency": max_concurrency, "name": None,
            "namespace": None, "lifetime": None, "get_if_exists": False,
            "placement_group": None, "placement_group_bundle_index": -1,
            "runtime_env": runtime_env,
            "concurrency_groups": concurrency_groups or {},
        }

    def options(self, **kw) -> "ActorClass":
        new = ActorClass(self._cls)
        new._opts = {**self._opts}
        for k, v in kw.items():
            if k not in new._opts:
                raise TypeError(f"unknown actor option {k}")
            new._opts[k] = v
        return new

    def remote(self, *args, **kwargs) -> ActorHandle:
        w = _get_worker()
        o = self._opts
        if (o.get("runtime_env") or {}).get("python_version"):
            # actor class payloads ship as bytecode (cloudpickle); a
            # cross-minor worker cannot unpickle them — fail at the
            # submission site with the reason, not on the worker with
            # a bad-marshal error
            raise ValueError(
                "runtime_env 'python_version' is not supported for "
                "actors: class payloads ship as bytecode, which is "
                "interpreter-minor-specific (tasks support it via "
                "source shipping)")
        res = {"CPU": float(o["num_cpus"]), **o["resources"]}
        if o["num_tpus"]:
            res["TPU"] = float(o["num_tpus"])
        aid = ActorID.from_random().binary()
        pg = o.get("placement_group")
        reply = w.register_actor(
            actor_id=aid, cls=self._cls, args=args, kwargs=kwargs,
            name=o["name"],
            namespace=o["namespace"] or getattr(w, "namespace", "default"),
            detached=(o["lifetime"] == "detached"),
            max_restarts=o["max_restarts"], resources=res,
            pg_id=pg.id.binary() if pg else None,
            bundle_index=o["placement_group_bundle_index"],
            max_concurrency=o["max_concurrency"],
            get_if_exists=o["get_if_exists"],
            runtime_env=o.get("runtime_env"),
            concurrency_groups=o.get("concurrency_groups"),
            # walk the full class (incl. inherited methods) for
            # @method(concurrency_group=...) annotations
            method_groups={
                name: fn.__ray_tpu_method_opts__["concurrency_group"]
                for name in dir(self._cls)
                for fn in [getattr(self._cls, name, None)]
                if getattr(fn, "__ray_tpu_method_opts__", {}).get(
                    "concurrency_group"
                )
            },
        )
        owns = o["name"] is None and o["lifetime"] != "detached" \
            and not reply.get("existing")
        return ActorHandle(reply["actor_id"], owns=owns)

    def __call__(self, *a, **kw):
        raise TypeError("actor class cannot be instantiated directly; "
                        "use .remote()")


# ---------------- decorators ----------------

def remote(*args, **kwargs):
    """@remote decorator for functions and classes (reference
    worker.py:2939 ray.remote)."""

    def _wrap(target):
        if isinstance(target, type):
            return ActorClass(
                target,
                num_cpus=kwargs.get("num_cpus", 1.0),
                num_tpus=kwargs.get("num_tpus", 0.0),
                resources=kwargs.get("resources"),
                max_restarts=kwargs.get("max_restarts", 0),
                max_concurrency=kwargs.get("max_concurrency", 1),
                runtime_env=kwargs.get("runtime_env"),
                concurrency_groups=kwargs.get("concurrency_groups"),
            )
        return RemoteFunction(
            target,
            num_returns=kwargs.get("num_returns", 1),
            num_cpus=kwargs.get("num_cpus", 1.0),
            num_tpus=kwargs.get("num_tpus", 0.0),
            resources=kwargs.get("resources"),
            max_retries=kwargs.get("max_retries", 3),
            scheduling_strategy=kwargs.get("scheduling_strategy"),
            runtime_env=kwargs.get("runtime_env"),
        )

    if len(args) == 1 and callable(args[0]) and not kwargs:
        return _wrap(args[0])
    return _wrap


def method(**kwargs):
    """Decorator for actor methods (num_returns); stored as attribute."""

    def _wrap(fn):
        fn.__ray_tpu_method_opts__ = kwargs
        return fn

    return _wrap


# ---------------- object API ----------------

def put(value, *, _inline: bool | None = None) -> ObjectRef:
    """Store ``value``; ``_inline=False`` forces even a small value into
    the shared object store (announced + directory-registered) instead
    of the owner-inline fast path. Inline objects are resolvable only
    through paths that carry owner info (task args/results); a ref that
    travels a SIDE CHANNEL — actor state, a buffer/queue actor, a later
    unrelated task result — needs the store copy for third processes to
    fetch it (e.g. rl/experience.py trajectory handoff)."""
    return ObjectRef(_get_worker().put(value, inline=_inline))


class ObjectRefGenerator:
    """Result of getting a num_returns="dynamic" task's ref: an iterable of
    the per-item ObjectRefs (reference _raylet.pyx:186)."""

    def __init__(self, refs: list[ObjectRef]):
        self._refs = refs

    def __iter__(self):
        return iter(self._refs)

    def __len__(self):
        return len(self._refs)

    def __getitem__(self, i):
        return self._refs[i]

    def __repr__(self):
        return f"ObjectRefGenerator({len(self._refs)} refs)"


def _wrap_dynamic(value):
    from ray_tpu._private.worker import DynamicReturns

    if isinstance(value, DynamicReturns):
        return ObjectRefGenerator([ObjectRef(i) for i in value.object_ids])
    return value


def get(refs, *, timeout: float | None = None):
    w = _get_worker()
    single = isinstance(refs, ObjectRef)
    if single:
        refs = [refs]
    values = [
        _wrap_dynamic(v)
        for v in w.get([r.binary() for r in refs], timeout=timeout)
    ]
    return values[0] if single else values


def wait(refs: Sequence[ObjectRef], *, num_returns: int = 1,
         timeout: float | None = None):
    w = _get_worker()
    ready, pending = w.wait(
        [r.binary() for r in refs], num_returns, timeout
    )
    by_id = {r.binary(): r for r in refs}
    return [by_id[i] for i in ready], [by_id[i] for i in pending]


def kill(actor: ActorHandle, *, no_restart: bool = True):
    _get_worker().kill_actor(actor._actor_id, no_restart)


def cancel(ref: ObjectRef, *, force: bool = False):
    w = _get_worker()
    e = w.memory.get(ref.binary())
    if e is not None and e.spec is not None:
        w.cancel_task(e.spec["task_id"], force)


def get_actor(name: str, namespace: str = "default") -> ActorHandle:
    w = _get_worker()
    view = w.head.call("get_actor", {"name": name, "namespace": namespace})
    if view is None or view["state"] == "DEAD":
        raise ValueError(f"no live actor named '{name}'")
    return ActorHandle(view["actor_id"])


def free(refs: Sequence[ObjectRef]):
    _get_worker().free([r.binary() for r in refs])


# ---------------- placement groups ----------------

class PlacementGroup:
    def __init__(self, pg_id: PlacementGroupID, bundle_nodes=None):
        self.id = pg_id
        self.bundle_nodes = bundle_nodes or []

    def ready(self, timeout: float = 60.0) -> bool:
        w = _get_worker()
        res = w.head.call("wait_pg_ready", {
            "pg_id": self.id.binary(), "timeout": timeout,
        })
        if res and res.get("state") == "CREATED":
            self.bundle_nodes = res["bundle_nodes"]
            return True
        return False

    def __reduce__(self):
        return (_restore_pg, (self.id.binary(), self.bundle_nodes))


def _restore_pg(pg_id_bin, bundle_nodes):
    return PlacementGroup(PlacementGroupID(pg_id_bin), bundle_nodes)


def placement_group(bundles: list[dict], strategy: str = "PACK",
                    name: str = "") -> PlacementGroup:
    """Reference: util/placement_group.py:34."""
    w = _get_worker()
    pgid = PlacementGroupID.from_random()
    res = w.head.call("create_pg", {
        "pg_id": pgid.binary(), "bundles": bundles, "strategy": strategy,
        "job_id": w.job_id, "name": name,
    })
    return PlacementGroup(pgid, res.get("bundle_nodes"))


def remove_placement_group(pg: PlacementGroup):
    _get_worker().head.call("remove_pg", {"pg_id": pg.id.binary()})


# ---------------- cluster info ----------------

def cluster_resources() -> dict:
    w = _get_worker()
    view = w.head.call("get_cluster_view", {})
    total: dict[str, float] = {}
    for n in view["nodes"]:
        if n["alive"]:
            for r, v in n["resources_total"].items():
                total[r] = total.get(r, 0) + v
    return total


def available_resources() -> dict:
    w = _get_worker()
    view = w.head.call("get_cluster_view", {})
    total: dict[str, float] = {}
    for n in view["nodes"]:
        if n["alive"]:
            for r, v in n["resources_available"].items():
                total[r] = total.get(r, 0) + v
    return total


def nodes() -> list[dict]:
    w = _get_worker()
    return _get_worker().head.call("get_cluster_view", {})["nodes"]


def list_tasks(limit: int = 10_000) -> list[dict]:
    """Task lifecycle events (reference state API `ray list tasks` +
    gcs_task_manager.h:61 event store)."""
    w = _get_worker()
    return w.head.call("list_task_events", {"limit": limit})


def list_objects(limit: int = 1000) -> list[dict]:
    """Cluster object directory entries (`ray list objects` analog)."""
    w = _get_worker()
    return w.head.call("list_objects", {"limit": limit})


def list_actors() -> list[dict]:
    w = _get_worker()
    return w.head.call("list_actors", {})


def list_jobs() -> list[dict]:
    w = _get_worker()
    return w.head.call("list_jobs", {})


def timeline(filename: str | None = None) -> list:
    """Chrome-trace events from the task-event store (reference
    _private/profiling.py:123 chrome_tracing_dump). Load the result in
    chrome://tracing or Perfetto; pid = node, tid = worker."""
    events = list_tasks()
    trace = []
    # task_id -> its complete event, for joining flow arrows. Flight-
    # recorder spans and user profile marks carry synthetic ids and are
    # never flow parents.
    by_task = {ev["task_id"].hex(): ev for ev in events
               if ev.get("state") not in ("PROFILE", "SPAN")}
    for ev in events:
        is_span = ev.get("state") == "SPAN"
        args = {"state": ev.get("state"), "task_id": ev["task_id"].hex()}
        if is_span:
            # span attributes (byte counts, wait breakdowns, ...) land
            # verbatim in the Perfetto args pane
            args.update(ev.get("attrs") or {})
        tr = ev.get("trace") or {}
        if tr:
            tid = tr.get("trace_id")
            # hex so the dump is valid JSON (trace ids are bytes on
            # the wire)
            args["trace_id"] = tid.hex() if isinstance(tid, bytes) \
                else tid
            if tr.get("parent"):
                args["parent_span"] = tr["parent"]
        if is_span:
            cat = ev.get("kind") or "span"
        elif ev.get("state") == "PROFILE":
            # user spans (util/profiling.py profile()) land in their own
            # category so Perfetto can filter them
            cat = "user_span"
        else:
            cat = "task"
        trace.append({
            "name": ev.get("name", "task"),
            "cat": cat,
            "ph": "X",  # complete event
            "ts": ev["start_s"] * 1e6,
            "dur": max(0.0, (ev["end_s"] - ev["start_s"]) * 1e6),
            "pid": ev["node_id"].hex()[:8],
            "tid": ev["worker_id"].hex()[:8],
            "args": args,
        })
        # flow arrow parent -> child joins submit→execute→nested-submit
        # into one connected trace (reference tracing_helper.py context
        # propagation; Chrome "s"/"f" flow events on the shared id)
        parent = by_task.get(tr.get("parent") or "")
        if parent is not None:
            flow_id = ev["task_id"].hex()[:16]
            common = {"name": "submit", "cat": "trace",
                      "id": flow_id}
            trace.append({**common, "ph": "s",
                          "ts": parent["start_s"] * 1e6,
                          "pid": parent["node_id"].hex()[:8],
                          "tid": parent["worker_id"].hex()[:8]})
            trace.append({**common, "ph": "f", "bp": "e",
                          "ts": ev["start_s"] * 1e6,
                          "pid": ev["node_id"].hex()[:8],
                          "tid": ev["worker_id"].hex()[:8]})
    if filename:
        import json

        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


__all__ = [
    "init", "shutdown", "is_initialized", "remote", "method", "get", "put",
    "wait", "kill", "cancel", "get_actor", "free", "ObjectRef",
    "ActorHandle", "PlacementGroup", "placement_group",
    "remove_placement_group", "cluster_resources", "available_resources",
    "nodes", "timeline", "list_tasks", "list_objects", "list_actors", "list_jobs",
    "RayTaskError", "RayActorError", "GetTimeoutError", "ObjectLostError",
]
