"""Serve control plane + data plane.

- Controller (reference controller.py:79): a named actor holding the
  deployment table; reconciles desired replica count by starting/killing
  replica actors; rolling redeploy replaces replicas of older versions.
- Replica (reference _private/replica.py:296): an actor hosting the user
  class; handles requests with actor max_concurrency =
  max_concurrent_queries.
- Handle/Router (reference handle.py:78 + _private/router.py:227): client-
  side router, power-of-two-choices over per-replica in-flight counts with
  max_concurrent_queries backpressure.
"""

from __future__ import annotations

import logging
import random
import threading
import time
from typing import Any

import ray_tpu

logger = logging.getLogger(__name__)

CONTROLLER_NAME = "__serve_controller__"


@ray_tpu.remote(num_cpus=0)
class _ReplicaActor:
    """Hosts one copy of the user deployment class."""

    def __init__(self, cls_blob, init_args, init_kwargs):
        from ray_tpu._private import serialization

        cls = serialization.unpack_payload(cls_blob)
        self._user = cls(*init_args, **init_kwargs)
        self._req_lock = threading.Lock()
        self._num_inflight = 0

    def handle_request(self, method: str, args, kwargs, model_id: str = ""):
        import ray_tpu as rt
        from ray_tpu.serve.multiplex import _set_model_id

        with self._req_lock:
            self._num_inflight += 1
        try:
            # set unconditionally: pooled executor threads would otherwise
            # leak a previous request's model id into non-multiplexed
            # requests
            _set_model_id(model_id)
            # deployment-graph edges arrive as ObjectRefs nested in the
            # args list (the runtime only auto-resolves top-level task
            # args) — resolve them here so composed deployments pipeline
            # replica to replica without a driver hop
            args = [
                rt.get(a, timeout=300) if isinstance(a, rt.ObjectRef) else a
                for a in args
            ]
            kwargs = {
                k: rt.get(v, timeout=300) if isinstance(v, rt.ObjectRef) else v
                for k, v in kwargs.items()
            }
            fn = (self._user if method == "__call__"
                  else getattr(self._user, method))
            return fn(*args, **kwargs)
        finally:
            with self._req_lock:
                self._num_inflight -= 1

    def num_inflight(self) -> int:
        """Requests currently executing here (drain poll target)."""
        with self._req_lock:
            return self._num_inflight

    def reconfigure(self, user_config):
        if hasattr(self._user, "reconfigure"):
            self._user.reconfigure(user_config)
        return True

    def health(self):
        return True


@ray_tpu.remote(num_cpus=0, concurrency_groups={"poll": 32, "metrics": 4})
class _Controller:
    """Deployment table + replica reconciliation (controller.py:79) with a
    long-poll push channel (long_poll.py:186 analog) and queue-metric
    autoscaling (autoscaling_policy.py:10 analog, driven by handle-side
    in-flight reports)."""

    AUTOSCALE_PERIOD_S = 1.0

    def __init__(self):
        import threading as th

        from ray_tpu.serve.long_poll import LongPollHost

        self.deployments: dict[str, dict] = {}
        self.routes: dict[str, str] = {}  # route_prefix -> deployment
        self.long_poll_host = LongPollHost()
        self._metrics: dict[str, dict] = {}  # name -> {handle_id: (t, n)}
        self._lock = th.RLock()
        self._stop = th.Event()
        th.Thread(target=self._autoscale_loop, daemon=True).start()

    # -- control --

    ROLLING_BATCH_FRACTION = 0.34  # replicas replaced per rolling round
    # Settle before the first idle check: must cover the window in which a
    # handle that has not yet seen the unpublish push keeps routing here —
    # including the handle poll loop's 1.0s error-backoff sleep — so those
    # in-transit requests arrive (and count) before any kill decision.
    DRAIN_SETTLE_S = 1.5
    DRAIN_TIMEOUT_S = 30.0  # then kill even if still busy

    def deploy(self, name: str, cls_blob, init_args, init_kwargs,
               num_replicas: int, max_concurrent_queries: int,
               version: str, resources: dict,
               route_prefix: str | None = None,
               autoscaling_config: dict | None = None,
               user_config: dict | None = None):
        """Deploy or redeploy.

        A version change is a ROLLING replacement (reference
        _private/deployment_state.py rollout semantics): new replicas start
        and join the routing table in batches, and each displaced old
        replica is drained — unpublished, then killed only once its
        in-flight count reaches zero — so a redeploy under live traffic
        drops no requests.
        """
        import math

        import ray_tpu as rt

        with self._lock:
            old = self.deployments.get(name)
            if autoscaling_config:
                num_replicas = autoscaling_config.get(
                    "min_replicas", num_replicas
                )
            new_cfg = {
                "version": version,
                "max_concurrent_queries": max_concurrent_queries,
                "cls_blob": cls_blob,
                "init_args": init_args,
                "init_kwargs": init_kwargs,
                "resources": resources,
                "autoscaling": autoscaling_config,
                "user_config": user_config,
            }

            if old is None:
                replicas = self._start_batch(num_replicas, new_cfg)
                self.deployments[name] = {"replicas": replicas, **new_cfg}
                # route goes live only once replicas are healthy: the
                # proxy must never resolve a prefix to an empty deployment
                self._set_route(name, route_prefix)
                self._publish(name)
                return num_replicas

            if old["version"] == version:
                # same code version: scale / reconfigure in place
                old.update(new_cfg)
                survivors = list(old["replicas"])
                cur = len(survivors)
                victims: list = []
                if num_replicas > cur:
                    # _start_batch applies user_config to the fresh ones
                    old["replicas"] = survivors + self._start_batch(
                        num_replicas - cur, new_cfg)
                elif num_replicas < cur:
                    victims = survivors[num_replicas:]
                    survivors = survivors[:num_replicas]
                    old["replicas"] = survivors
                self._set_route(name, route_prefix)
                # publish BEFORE draining so routers stop sending to the
                # victims immediately (reconfigure below can be slow)
                self._publish(name)
                self._drain_and_kill(victims)
                if user_config is not None:
                    rt.get([r.reconfigure.remote(user_config)
                            for r in survivors], timeout=300)
                return num_replicas

            # rolling replacement
            batch = max(1, math.ceil(
                num_replicas * self.ROLLING_BATCH_FRACTION))
            old_replicas = list(old["replicas"])
            old_version = old["version"]
            new_replicas: list = []
            d = self.deployments[name] = {
                "replicas": list(old_replicas), **new_cfg}
            try:
                while len(new_replicas) < num_replicas or old_replicas:
                    n = min(batch,
                            max(0, num_replicas - len(new_replicas)))
                    new_replicas.extend(self._start_batch(n, new_cfg))
                    # retire as many old replicas as possible while keeping
                    # the serving set at the target size mid-roll
                    n_retire = min(
                        len(old_replicas),
                        max(0, len(new_replicas) + len(old_replicas)
                            - num_replicas),
                    )
                    retired = old_replicas[:n_retire]
                    old_replicas = old_replicas[n_retire:]
                    d["replicas"] = new_replicas + old_replicas
                    self._publish(name)  # handles stop routing to retired
                    self._drain_and_kill(retired)
                self._set_route(name, route_prefix)
            except Exception:
                # mid-roll failure: keep serving with whatever started plus
                # the surviving old replicas (already-retired ones are
                # gone). The recorded version stays the OLD one — old-code
                # replicas are still serving, and a retry of the same
                # deploy must re-enter THIS rolling path, not the
                # same-version scale path.
                d["replicas"] = new_replicas + old_replicas
                d["version"] = old_version
                self._publish(name)
                raise
        return num_replicas

    def _set_route(self, name: str, route_prefix: str | None):
        if route_prefix:
            self.routes[route_prefix] = name
            self.long_poll_host.set("routes", dict(self.routes))

    def _start_batch(self, n: int, cfg: dict) -> list:
        """Start n replicas and wait for their constructors + initial
        reconfigure; on ANY failure, reap every replica of the batch
        (never leak actors whose health was not confirmed)."""
        import ray_tpu as rt

        fresh = [
            self._start_replica(
                cfg["cls_blob"], cfg["init_args"], cfg["init_kwargs"],
                cfg["resources"], cfg["max_concurrent_queries"],
            )
            for _ in range(n)
        ]
        try:
            rt.get([r.health.remote() for r in fresh], timeout=300)
            if cfg.get("user_config") is not None:
                rt.get([r.reconfigure.remote(cfg["user_config"])
                        for r in fresh], timeout=300)
        except Exception:
            for r in fresh:
                try:
                    rt.kill(r)
                except Exception:  # noqa: BLE001
                    pass
            raise
        return fresh

    def _drain_and_kill(self, replicas: list):
        """Gracefully retire replicas that are no longer published: wait
        for their in-flight requests to finish, then kill — in the
        background so deploys/autoscaling don't block on slow requests."""
        import ray_tpu as rt

        if not replicas:
            return

        def _idle_twice(r) -> bool:
            """num_inflight counts only requests that entered
            handle_request — a request can sit in the actor's mailbox
            between a decrement and the next increment. Two zero reads
            with a gap bound that window: a queued request starts
            executing (and counts) well within the gap."""
            if rt.get(r.num_inflight.remote(), timeout=10) > 0:
                return False
            time.sleep(0.25)
            return rt.get(r.num_inflight.remote(), timeout=10) == 0

        def _drain():
            time.sleep(self.DRAIN_SETTLE_S)
            deadline = time.time() + self.DRAIN_TIMEOUT_S
            pending = list(replicas)
            while pending and time.time() < deadline:
                still = []
                for r in pending:
                    try:
                        idle = _idle_twice(r)
                    except rt.RayActorError:
                        continue  # already dead — nothing to kill
                    except Exception:  # noqa: BLE001 — busy/slow reply:
                        still.append(r)  # NOT dead; keep until idle/deadline
                        continue
                    if not idle:
                        still.append(r)
                        continue
                    try:
                        rt.kill(r)
                    except Exception:  # noqa: BLE001
                        pass
                pending = still
                if pending:
                    time.sleep(0.1)
            for r in pending:  # drain timeout: kill regardless
                try:
                    rt.kill(r)
                except Exception:  # noqa: BLE001
                    pass

        threading.Thread(target=_drain, daemon=True).start()

    def _start_replica(self, cls_blob, init_args, init_kwargs, resources,
                       max_concurrent_queries):
        from ray_tpu.serve.api import _ReplicaActor

        return _ReplicaActor.options(
            num_cpus=resources.get("CPU", 0),
            num_tpus=resources.get("TPU", 0),
            max_concurrency=max_concurrent_queries,
        ).remote(cls_blob, init_args, init_kwargs)

    def _publish(self, name: str):
        d = self.deployments.get(name)
        if d is None:
            self.long_poll_host.drop(f"replicas:{name}")
            return
        self.long_poll_host.set(f"replicas:{name}", {
            "actor_ids": [r._actor_id for r in d["replicas"]],
            "max_concurrent_queries": d["max_concurrent_queries"],
            "version": d["version"],
        })

    def get_replicas(self, name: str):
        d = self.deployments.get(name)
        if d is None:
            return None
        return {
            "actor_ids": [r._actor_id for r in d["replicas"]],
            "max_concurrent_queries": d["max_concurrent_queries"],
            "version": d["version"],
        }

    def get_routes(self):
        return dict(self.routes)

    def list_deployments(self):
        return {
            name: {"num_replicas": len(d["replicas"]),
                   "version": d["version"]}
            for name, d in self.deployments.items()
        }

    def delete(self, name: str):
        import ray_tpu as rt

        with self._lock:
            d = self.deployments.pop(name, None)
            for prefix, dep in list(self.routes.items()):
                if dep == name:
                    del self.routes[prefix]
            self.long_poll_host.set("routes", dict(self.routes))
            self._publish(name)
            if d:
                for r in d["replicas"]:
                    try:
                        rt.kill(r)
                    except Exception:  # noqa: BLE001
                        pass
            return d is not None

    # -- long poll (dedicated group so blocked polls never starve control)

    @ray_tpu.method(concurrency_group="poll")
    def long_poll(self, snapshot: dict, timeout: float = 10.0):
        return self.long_poll_host.poll(snapshot, timeout)

    # -- autoscaling --

    @ray_tpu.method(concurrency_group="metrics")
    def report_metrics(self, name: str, handle_id: str, in_flight: int,
                       ttft_p99_s: float | None = None):
        import time as t

        self._metrics.setdefault(name, {})[handle_id] = (
            t.time(), in_flight, ttft_p99_s)

    def _autoscale_loop(self):
        while not self._stop.wait(self.AUTOSCALE_PERIOD_S):
            try:
                self._autoscale_once()
            except Exception:  # noqa: BLE001
                logger.exception("autoscale tick failed")

    def _autoscale_once(self):
        with self._lock:
            for name, d in list(self.deployments.items()):
                try:
                    self._autoscale_deployment(name, d)
                except Exception:  # noqa: BLE001 — one bad deployment
                    logger.exception("autoscale failed for %s", name)

    def _autoscale_deployment(self, name: str, d: dict):
        import time as t

        import ray_tpu as rt

        cfg = d.get("autoscaling")
        if not cfg:
            return
        from ray_tpu.autoscaler.demand_scheduler import (
            serve_replica_demand,
        )

        now = t.time()
        fresh = [r for r in self._metrics.get(name, {}).values()
                 if now - r[0] < 5.0]
        total = sum(r[1] for r in fresh)
        ttfts = [r[2] for r in fresh if len(r) > 2 and r[2] is not None]
        desired = serve_replica_demand(
            queue_depth=0, inflight=total,
            n_replicas=len(d["replicas"]),
            min_replicas=cfg.get("min_replicas", 1),
            max_replicas=cfg.get("max_replicas", 8),
            target_queue_per_replica=cfg.get(
                "target_num_ongoing_requests_per_replica", 2),
            ttft_p99_s=max(ttfts) if ttfts else None,
            target_ttft_s=cfg.get("target_ttft_s"))
        cur = len(d["replicas"])
        if desired > cur:
            new = [
                self._start_replica(
                    d["cls_blob"], d["init_args"], d["init_kwargs"],
                    d["resources"], d["max_concurrent_queries"],
                )
                for _ in range(desired - cur)
            ]
            try:
                rt.get([r.health.remote() for r in new], timeout=60)
            except Exception:  # noqa: BLE001
                # failed/slow constructors: reap, retry next tick
                # (never leak unregistered actors)
                for r in new:
                    try:
                        rt.kill(r)
                    except Exception:  # noqa: BLE001
                        pass
                raise
            d["replicas"].extend(new)
            self._publish(name)
        elif desired < cur:
            victims = d["replicas"][desired:]
            d["replicas"] = d["replicas"][:desired]
            self._publish(name)
            # same zero-drop contract as redeploys: drain, then kill
            self._drain_and_kill(victims)


# ---------------- driver-side API ----------------

def start():
    """Start (or connect to) the serve controller."""
    try:
        return ray_tpu.get_actor(CONTROLLER_NAME)
    except ValueError:
        pass
    return _Controller.options(
        name=CONTROLLER_NAME, lifetime="detached"
    ).remote()


def _controller():
    return ray_tpu.get_actor(CONTROLLER_NAME)


PROXY_NAME = "__serve_http_proxy__"


def start_http_proxy(host: str = "127.0.0.1",
                     port: int = 0) -> tuple[str, int]:
    """Start (or connect to) the HTTP ingress; returns (host, port).

    reference http_proxy.py:481 HTTPProxyActor — one ingress actor; routes
    come from @serve.deployment(route_prefix=...) via controller long-poll.
    """
    from ray_tpu.serve.http_proxy import HTTPProxyActor

    start()
    try:
        proxy = ray_tpu.get_actor(PROXY_NAME)
    except ValueError:
        proxy = HTTPProxyActor.options(
            name=PROXY_NAME, lifetime="detached"
        ).remote(host, port)
    return tuple(ray_tpu.get(proxy.address.remote(), timeout=120))


def shutdown():
    for h in _handle_cache.values():
        h.close()
    _handle_cache.clear()
    try:
        proxy = ray_tpu.get_actor(PROXY_NAME)
        ray_tpu.kill(proxy)
    except ValueError:
        pass
    try:
        c = _controller()
    except ValueError:
        return
    for name in list(ray_tpu.get(c.list_deployments.remote(), timeout=60)):
        ray_tpu.get(c.delete.remote(name), timeout=60)
    ray_tpu.kill(c)


class Deployment:
    """Result of @serve.deployment on a class."""

    def __init__(self, cls, *, num_replicas=1, max_concurrent_queries=8,
                 resources=None, name=None, route_prefix=None,
                 autoscaling_config=None, user_config=None,
                 min_replicas=None, max_replicas=None,
                 target_ttft_s=None):
        self._cls = cls
        self.num_replicas = num_replicas
        self.max_concurrent_queries = max_concurrent_queries
        self.resources = resources or {"CPU": 0}
        self.name = name or cls.__name__
        self.route_prefix = route_prefix
        # first-class serving-tier knobs fold into autoscaling_config
        # (the controller's scale loop and the LLM pool both read them)
        if (min_replicas is not None or max_replicas is not None
                or target_ttft_s is not None):
            autoscaling_config = dict(autoscaling_config or {})
            if min_replicas is not None:
                autoscaling_config["min_replicas"] = min_replicas
            if max_replicas is not None:
                autoscaling_config["max_replicas"] = max_replicas
            if target_ttft_s is not None:
                autoscaling_config["target_ttft_s"] = target_ttft_s
        self.autoscaling_config = autoscaling_config
        self.user_config = user_config

    def options(self, **kw) -> "Deployment":
        merged = {
            "num_replicas": self.num_replicas,
            "max_concurrent_queries": self.max_concurrent_queries,
            "resources": self.resources,
            "name": self.name,
            "route_prefix": self.route_prefix,
            "autoscaling_config": self.autoscaling_config,
            "user_config": self.user_config,
        }
        merged.update(kw)
        return Deployment(self._cls, **merged)

    def bind(self, *args, **kwargs):
        """Node in a deployment graph (serve/graph.py; reference
        deployment_graph.py)."""
        from ray_tpu.serve.graph import DeploymentNode

        return DeploymentNode(self, args, kwargs)


def deployment(_cls=None, **kw):
    """@serve.deployment decorator (reference api.py deployment)."""
    if _cls is not None:
        return Deployment(_cls)

    def wrap(cls):
        return Deployment(cls, **kw)

    return wrap


def run(dep: Deployment, *, name: str | None = None, init_args=(),
        init_kwargs=None, version: str = "1",
        user_config: dict | None = None) -> "DeploymentHandle":
    """Deploy (or redeploy) and return a handle."""
    from ray_tpu._private import serialization

    start()
    name = name or dep.name
    cls_blob = serialization.pack_callable(dep._cls)
    c = _controller()
    ray_tpu.get(
        c.deploy.remote(
            name, cls_blob, list(init_args), init_kwargs or {},
            dep.num_replicas, dep.max_concurrent_queries, version,
            dep.resources,
            dep.route_prefix or f"/{name}",
            dep.autoscaling_config,
            user_config if user_config is not None else dep.user_config,
        ),
        timeout=600,
    )
    return get_handle(name)


_handle_cache: dict[str, "DeploymentHandle"] = {}


def get_handle(name: str) -> "DeploymentHandle":
    """Handles are cached per deployment: each one owns a long-poll
    thread, so per-request construction would leak threads and saturate
    the controller's poll group."""
    h = _handle_cache.get(name)
    if h is None or h._closed:
        h = _handle_cache[name] = DeploymentHandle(name)
    return h


class DeploymentHandle:
    """Client-side router (reference handle.py:78 + router.py:227).

    Replica choice: power-of-two-choices on the handle's local in-flight
    counts; a replica at max_concurrent_queries is skipped (backpressure).
    """

    def __init__(self, name: str):
        import os

        self.name = name
        self._handle_id = os.urandom(6).hex()
        self._replicas: list = []
        self._max_q = 8
        self._inflight: dict[int, int] = {}
        self._lock = threading.Lock()
        self._version = None
        self._poll_version = 0
        self._closed = False
        self._refresh()
        # LongPollClient analog (long_poll.py:68): learn about redeploys/
        # autoscaling pushes; doubles as the queue-metrics reporter that
        # feeds the controller's autoscaler.
        threading.Thread(target=self._poll_loop, daemon=True).start()

    def _refresh(self):
        info = ray_tpu.get(
            _controller().get_replicas.remote(self.name), timeout=60
        )
        if info is None:
            raise ValueError(f"no deployment named '{self.name}'")
        self._apply(info)

    def _apply(self, info: dict):
        with self._lock:
            old_ids = [r._actor_id for r in self._replicas]
            old_counts = dict(self._inflight)
            self._replicas = [
                ray_tpu.ActorHandle(aid) for aid in info["actor_ids"]
            ]
            self._max_q = info["max_concurrent_queries"]
            self._version = info["version"]
            # carry in-flight counts across by replica identity — a scale
            # event must not zero the accounting for surviving replicas
            by_id = {aid: old_counts.get(i, 0)
                     for i, aid in enumerate(old_ids)}
            self._inflight = {
                i: by_id.get(aid, 0)
                for i, aid in enumerate(info["actor_ids"])
            }

    def _poll_loop(self):
        key = f"replicas:{self.name}"
        while not self._closed:
            try:
                c = _controller()
                with self._lock:
                    total = sum(self._inflight.values())
                c.report_metrics.remote(
                    self.name, self._handle_id, total
                )
                changed = ray_tpu.get(
                    c.long_poll.remote(
                        {key: self._poll_version}, 2.0
                    ),
                    timeout=30,
                )
                if key in changed:
                    version, info = changed[key]
                    self._poll_version = version
                    if info is not None:
                        self._apply(info)
            except Exception:  # noqa: BLE001 — controller down/rolling
                time.sleep(1.0)

    def close(self):
        self._closed = True

    def method(self, method_name: str) -> "_HandleMethod":
        return _HandleMethod(self, method_name)

    def options(self, *, multiplexed_model_id: str = "",
                method_name: str = "__call__",
                affinity_key: str = "") -> "_HandleMethod":
        """``multiplexed_model_id`` routes to the replica that prefers
        that model AND tells it which model the request is for.
        ``affinity_key`` only routes: calls sharing a key prefer one
        replica (a stream's submit and polls), and the replica never
        sees it — it is not a model id."""
        return _HandleMethod(self, method_name,
                             model_id=multiplexed_model_id,
                             route_key=affinity_key)

    def remote(self, *args, **kwargs):
        return self.method("__call__").remote(*args, **kwargs)

    def _assign(self, model_id: str = "") -> int:
        """Pick a replica (two random choices, fewer in-flight wins);
        blocks while every replica is at max_concurrent_queries. A
        multiplexed model id hashes to a preferred replica so its LRU
        cache stays warm (reference multiplex routing hint)."""
        deadline = time.monotonic() + 60.0
        while True:
            with self._lock:
                n = len(self._replicas)
                if model_id:
                    # process-stable hash: the proxy and every driver must
                    # agree on the preferred replica or caches thrash
                    from ray_tpu.utils.hashing import stable_hash

                    pref = stable_hash(model_id) % n
                    if self._inflight[pref] < self._max_q:
                        self._inflight[pref] += 1
                        return pref
                idxs = random.sample(range(n), min(2, n))
                idx = min(idxs, key=lambda i: self._inflight[i])
                if self._inflight[idx] < self._max_q:
                    self._inflight[idx] += 1
                    return idx
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"all {len(self._replicas)} replicas of "
                    f"'{self.name}' at max_concurrent_queries"
                )
            time.sleep(0.002)

    def _done(self, idx: int):
        with self._lock:
            # the index may be gone after a scale-down/redeploy push; the
            # departed replica's count went with it
            if idx in self._inflight:
                self._inflight[idx] -= 1


class _HandleMethod:
    def __init__(self, handle: DeploymentHandle, method: str,
                 model_id: str = "", route_key: str = ""):
        self._h = handle
        self._method = method
        self._model_id = model_id
        self._route_key = route_key or model_id

    def remote(self, *args, **kwargs):
        h = self._h
        for attempt in (0, 1):
            idx = h._assign(self._route_key)
            try:
                replica = h._replicas[idx]
                ref = replica.handle_request.remote(self._method,
                                                    list(args), kwargs,
                                                    self._model_id)
            except Exception:
                h._done(idx)
                if attempt == 0:
                    # replicas may have been rolled by a redeploy: refresh
                    # the routing table once and retry
                    h._refresh()
                    continue
                raise
            _track_completion(h, idx, ref)
            return ref


def _track_completion(handle: DeploymentHandle, idx: int, ref):
    """Decrement the in-flight count when the reply actually lands (not on
    a wait timeout — a still-running request must keep holding its
    max_concurrent_queries slot), off-thread."""

    def _waiter():
        try:
            while True:
                ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=60)
                if ready:
                    return
        except Exception:  # noqa: BLE001 — replica died; slot comes back
            pass
        finally:
            handle._done(idx)

    threading.Thread(target=_waiter, daemon=True).start()
