"""Dashboard head: HTTP server over the control plane's state.

Reference: dashboard/head.py (aiohttp app + module loader),
state_aggregator.py:133 (list endpoints), modules/metrics (Prometheus),
modules/reporter (node stats + stack dumps). Endpoints:

  GET /api/nodes     cluster nodes incl. psutil stats
  GET /api/actors    actor table
  GET /api/jobs      job table
  GET /api/tasks     recent task events
  GET /api/objects   object directory sample
  GET /api/cluster   summary (alive nodes, resource totals)
  GET /api/stacks    thread stacks of every worker (py-spy analog)
  GET /api/logs      per-node log files; ?node_id=&file= tails one
  GET /api/timeline  Chrome-trace JSON (tasks + flight-recorder spans)
  GET /api/slo       TTFT/TBT/step-time percentiles + straggler rank
  GET /api/events    cluster events + task_events_dropped_total
  GET /metrics       Prometheus text format (cluster + user metrics)

Runs inside the driver (or any process with cluster access) on a
background thread; `ray_tpu.scripts start --head` can host it next to
the control plane. :func:`stop_dashboard` ends the threads of every
head this process started (a test's teardown: a process that hosts the
dashboard for its lifetime has no need of it).
"""

from __future__ import annotations

import json
import threading
from urllib.parse import urlsplit

import ray_tpu


def _prom_escape(s: str) -> str:
    return s.replace("\\", r"\\").replace('"', r'\"').replace("\n", r"\n")


def _to_prometheus(rows: list[dict], cluster: dict) -> str:
    """Render aggregated metric rows + built-in cluster gauges."""
    lines: list[str] = []
    builtins_ = [
        ("ray_tpu_cluster_nodes_alive", "gauge",
         "Alive nodes", [], cluster["nodes_alive"]),
        ("ray_tpu_cluster_cpus_total", "gauge",
         "Total CPUs", [], cluster["cpus_total"]),
        ("ray_tpu_cluster_cpus_available", "gauge",
         "Available CPUs", [], cluster["cpus_available"]),
        ("ray_tpu_cluster_tasks_queued", "gauge",
         "Queued tasks", [], cluster["tasks_queued"]),
    ]
    seen_help: set[str] = set()
    for row in builtins_ + [
        (r["name"], r["kind"], r["description"], r["tags"], r["value"])
        for r in rows
    ]:
        name, kind, desc, tags, value = row
        stat = None
        clean_tags = []
        for k, v in tags:
            if k == "__stat__":
                stat = v
            else:
                clean_tags.append((k, v))
        metric = name
        if stat == "sum":
            metric = f"{name}_sum"
        elif any(k == "le" for k, _ in clean_tags):
            metric = f"{name}_bucket"
        if name not in seen_help:
            seen_help.add(name)
            lines.append(f"# HELP {name} {_prom_escape(desc or name)}")
            lines.append(f"# TYPE {name} {kind}")
        label = ",".join(
            f'{k}="{_prom_escape(str(v))}"' for k, v in clean_tags
        )
        lines.append(
            f"{metric}{{{label}}} {value}" if label else f"{metric} {value}"
        )
        if metric.endswith("_bucket") and any(
            k == "le" and v == "+Inf" for k, v in clean_tags
        ):
            # the +Inf bucket IS the count; exposition requires an
            # explicit name_count series for rate(_sum)/rate(_count)
            base_label = ",".join(
                f'{k}="{_prom_escape(str(v))}"'
                for k, v in clean_tags if k != "le"
            )
            cnt = f"{name}_count"
            lines.append(
                f"{cnt}{{{base_label}}} {value}" if base_label
                else f"{cnt} {value}"
            )
    return "\n".join(lines) + "\n"


def _hist_percentiles(rows: list[dict], name: str, *,
                      group_key: str | None = None) -> dict:
    """Percentiles from aggregated histogram rows (`rpc_get_metrics`).

    Bucket rows carry an ``("le", bound)`` tag with CUMULATIVE counts;
    the ``("__stat__", "sum")`` row carries the value sum. Linear
    interpolation inside the winning bucket; a hit landing in the +Inf
    bucket clamps to the largest finite bound. Returns
    ``{group: {count, mean_s, p50_s, p90_s, p99_s}}`` keyed by the
    `group_key` tag value ("" when ungrouped — other tag dimensions are
    summed together)."""
    buckets: dict[str, dict[float, float]] = {}
    sums: dict[str, float] = {}
    for r in rows:
        if r["name"] != name:
            continue
        tags = dict(tuple(t) for t in r["tags"])
        grp = tags.get(group_key, "") if group_key else ""
        if tags.get("__stat__") == "sum":
            sums[grp] = sums.get(grp, 0.0) + r["value"]
            continue
        if "le" not in tags:
            continue
        le = float("inf") if tags["le"] == "+Inf" else float(tags["le"])
        g = buckets.setdefault(grp, {})
        g[le] = g.get(le, 0) + r["value"]
    out: dict[str, dict] = {}
    for grp, bs in buckets.items():
        total = bs.get(float("inf"), 0)
        if total <= 0:
            continue
        res = {"count": int(total),
               "mean_s": round(sums.get(grp, 0.0) / total, 6)}
        for q, label in ((0.5, "p50_s"), (0.9, "p90_s"), (0.99, "p99_s")):
            target = q * total
            prev_b, prev_c = 0.0, 0.0
            val = prev_b
            for b in sorted(bs):
                c = bs[b]
                if c >= target:
                    if b == float("inf"):
                        val = prev_b
                    else:
                        span = c - prev_c
                        frac = ((target - prev_c) / span) if span > 0 else 1.0
                        val = prev_b + frac * (b - prev_b)
                    break
                if b != float("inf"):
                    prev_b = b
                prev_c = c
            res[label] = round(val, 6)
        out[grp] = res
    return out


class DashboardHead:
    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self.host = host
        self.port = port
        self._ready = threading.Event()
        self._loop = self._server = None
        self._thread = threading.Thread(target=self._drive, daemon=True,
                                        name="ray_tpu-dashboard")
        self._thread.start()

    # -- state access (all through the connected worker's head client) --

    def _head(self):
        from ray_tpu._private.api import _get_worker

        return _get_worker().head

    def _cluster_summary(self) -> dict:
        nodes = self._head().call("get_cluster_view", {})["nodes"]
        alive = [n for n in nodes if n["alive"]]
        return {
            "nodes_alive": len(alive),
            "nodes_total": len(nodes),
            "cpus_total": sum(
                n["resources_total"].get("CPU", 0) for n in alive
            ),
            "cpus_available": sum(
                n["resources_available"].get("CPU", 0) for n in alive
            ),
            "tpus_total": sum(
                n["resources_total"].get("TPU", 0) for n in alive
            ),
            "tasks_queued": sum(n.get("queued", 0) for n in alive),
            "tasks_running": sum(n.get("running", 0) for n in alive),
        }

    def _slo_summary(self) -> dict:
        """TTFT / TBT / step-time percentiles from the head's metric
        store, plus slowest-rank straggler attribution (which rank is
        slowest and which step segment its time went to)."""
        rows = self._head().call("get_metrics", {})
        ttft = _hist_percentiles(rows, "serve_ttft_seconds")
        tbt = _hist_percentiles(rows, "serve_tbt_seconds")
        step = _hist_percentiles(rows, "train_step_seconds",
                                 group_key="rank")
        seg: dict[str, dict[str, float]] = {}
        for r in rows:
            if r["name"] != "train_step_segment_seconds_total":
                continue
            tags = dict(tuple(t) for t in r["tags"])
            seg.setdefault(tags.get("rank", "?"), {})[
                tags.get("segment", "?")] = r["value"]
        straggler = None
        if step:
            slowest = max(step, key=lambda rk: step[rk]["mean_s"])
            segs = seg.get(slowest, {})
            straggler = {
                "rank": slowest,
                "mean_step_s": step[slowest]["mean_s"],
                "dominant_segment":
                    max(segs, key=segs.get) if segs else None,
                "segments_s": {k: round(v, 6) for k, v in segs.items()},
            }
        # per-tenant SLO verdicts: the serve histograms carry a tenant
        # tag, so fair-queueing outcomes are observable here, not just
        # asserted in tests ("-" = untagged traffic)
        per_tenant: dict[str, dict] = {}
        for tn, pct in _hist_percentiles(
                rows, "serve_ttft_seconds", group_key="tenant").items():
            per_tenant.setdefault(tn or "-", {})["ttft"] = pct
        for tn, pct in _hist_percentiles(
                rows, "serve_tbt_seconds", group_key="tenant").items():
            per_tenant.setdefault(tn or "-", {})["tbt"] = pct
        # speculative-decode acceptance per engine: the counters pair
        # (decode_engine_spec_proposed/accepted_total) tells an operator
        # whether the draft is earning its keep — acceptance_rate near 0
        # means the verify pays the wide forward for nothing
        spec: dict[str, dict] = {}
        for r in rows:
            if r["name"] not in ("decode_engine_spec_proposed_total",
                                 "decode_engine_spec_accepted_total"):
                continue
            tags = dict(tuple(t) for t in r["tags"])
            ent = spec.setdefault(tags.get("engine", "?"),
                                  {"proposed": 0.0, "accepted": 0.0})
            key = ("proposed" if r["name"].endswith("proposed_total")
                   else "accepted")
            ent[key] += r["value"]
        for ent in spec.values():
            ent["acceptance_rate"] = round(
                ent["accepted"] / ent["proposed"], 4) \
                if ent["proposed"] else 0.0
        # overload-guardian posture: current ladder level plus shed /
        # deadline-fast-fail tallies, so an operator can tell "tenant B
        # is seeing retryable 'overloaded' errors" apart from "the pool
        # is broken" at a glance
        degradation: dict = {"level": 0, "shed": {}, "deadline_failfast": 0.0}
        for r in rows:
            if r["name"] == "pool_degradation_level":
                degradation["level"] = max(
                    degradation["level"], int(r["value"]))
            elif r["name"] == "pool_shed_total":
                tags = dict(tuple(t) for t in r["tags"])
                key = (f"{tags.get('tenant', '-') or '-'}"
                       f"/{tags.get('reason', '?') or '?'}")
                degradation["shed"][key] = \
                    degradation["shed"].get(key, 0.0) + r["value"]
            elif r["name"] == "pool_deadline_failfast_total":
                degradation["deadline_failfast"] += r["value"]
        return {"ttft": ttft.get("", {}), "tbt": tbt.get("", {}),
                "per_tenant": per_tenant, "speculation": spec,
                "train_step": step, "straggler": straggler,
                "degradation": degradation}

    def _agent_call(self, node: dict, method: str, payload: dict,
                    timeout: float = 10.0):
        from ray_tpu._private import rpc as _rpc
        from ray_tpu._private.api import _get_worker

        cli = _rpc.SyncRpcClient(node["addr"], node["port"],
                                 _get_worker().io)
        try:
            return cli.call(method, payload, timeout=timeout)
        finally:
            cli.close()

    def _api(self, path: str, query: dict):
        head = self._head()
        if path == "/api/nodes":
            return head.call("get_cluster_view", {})["nodes"]
        if path == "/api/actors":
            return head.call("list_actors", {})
        if path == "/api/jobs":
            return head.call("list_jobs", {})
        if path == "/api/tasks":
            return head.call("list_task_events",
                             {"limit": int(query.get("limit", 1000))})
        if path == "/api/objects":
            return head.call("list_objects",
                             {"limit": int(query.get("limit", 1000))})
        if path == "/api/cluster":
            return self._cluster_summary()
        if path == "/api/events":
            events = head.call("list_events", {
                "limit": int(query.get("limit", 1000)),
                "kind": query.get("kind")})
            try:
                obs = head.call("obs_stats", {})
            except Exception:  # noqa: BLE001 — older head
                obs = {}
            return {"events": events,
                    "task_events_dropped_total":
                        obs.get("task_events_dropped_total", 0)}
        if path == "/api/timeline":
            from ray_tpu._private import api as _api

            return _api.timeline()
        if path == "/api/slo":
            return self._slo_summary()
        if path == "/api/op_stats":
            return head.call("op_stats", {})
        if path == "/api/worker_failures":
            return head.call("list_worker_failures",
                             {"limit": int(query.get("limit", 1000))})
        if path == "/api/logs":
            # list log files per node; ?node_id=<hex>&file=<name> fetches
            # a tail (&tail_bytes=N) — reference dashboard/modules/log
            node_hex = query.get("node_id")
            fname = query.get("file")
            nodes = [n for n in head.call("get_cluster_view", {})["nodes"]
                     if n["alive"]]
            if node_hex and fname:
                n = next((n for n in nodes
                          if n["node_id"].hex() == node_hex), None)
                if n is None:
                    return {"error": f"no alive node {node_hex}"}
                return self._agent_call(n, "read_log", {
                    "file": fname,
                    "tail_bytes": int(query.get("tail_bytes", 65536)),
                })
            out = []
            for n in nodes:
                try:
                    files = self._agent_call(n, "list_logs", {})
                except Exception as e:  # noqa: BLE001
                    files = {"error": str(e)}
                out.append({"node_id": n["node_id"].hex(),
                            "files": files})
            return out
        if path == "/api/profile":
            # ?duration=N seconds of statistical sampling across every
            # worker on every node; collapsed-stack counts per worker
            duration = min(float(query.get("duration", 2.0)), 30.0)
            nodes = [n for n in head.call("get_cluster_view", {})["nodes"]
                     if n["alive"]]

            # fan out CONCURRENTLY so every node's sample window covers
            # the same wall-clock period (a sequential sweep would take
            # N_nodes x duration and never observe the cluster at once)
            def _one(n):
                try:
                    return self._agent_call(
                        n, "profile_workers", {"duration_s": duration},
                        timeout=duration + 20.0)
                except Exception as e:  # noqa: BLE001
                    return {"node_id": n["node_id"].hex(),
                            "error": str(e)}

            if not nodes:
                return []
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=min(16, len(nodes))) as ex:
                return list(ex.map(_one, nodes))
        if path == "/api/stacks":
            nodes = head.call("get_cluster_view", {})["nodes"]
            out = []
            for n in nodes:
                if not n["alive"]:
                    continue
                try:
                    out.append(self._agent_call(n, "dump_stacks", {}))
                except Exception as e:  # noqa: BLE001
                    out.append({"node_id": n["node_id"],
                                "error": str(e)})
            return out
        return None

    # -- http plumbing (same raw-asyncio pattern as serve's proxy) --

    def _drive(self):
        import asyncio

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        async def boot():
            server = await asyncio.start_server(
                self._serve_conn, self.host, self.port
            )
            self.port = server.sockets[0].getsockname()[1]
            self._loop, self._server = loop, server
            self._ready.set()

        loop.run_until_complete(boot())
        loop.run_forever()
        loop.close()

    def stop(self, timeout: float = 5.0) -> None:
        """Closes the listening socket, ends the threads that served
        requests (the loop's default executor) and the loop's own."""
        import asyncio

        loop = self._loop
        if loop is None or not self._thread.is_alive():
            return

        async def close():
            self._server.close()
            await loop.shutdown_default_executor()
            loop.stop()

        asyncio.run_coroutine_threadsafe(close(), loop)
        self._thread.join(timeout)

    def wait_ready(self, timeout: float = 30.0) -> tuple[str, int]:
        if not self._ready.wait(timeout):
            raise TimeoutError("dashboard failed to bind")
        return self.host, self.port

    async def _serve_conn(self, reader, writer):
        import asyncio

        try:
            while True:
                line = await reader.readline()
                if not line or line in (b"\r\n", b"\n"):
                    break
                method, target, _ = line.decode().split(" ", 2)
                clen = 0
                while True:  # headers (Content-Length matters for PUT)
                    h = await reader.readline()
                    if h in (b"\r\n", b"\n", b""):
                        break
                    name, _, val = h.decode().partition(":")
                    if name.strip().lower() == "content-length":
                        clen = int(val.strip() or 0)
                body = await reader.readexactly(clen) if clen else b""
                status, ctype, payload = await asyncio.get_running_loop() \
                    .run_in_executor(None, self._dispatch, target,
                                     method, body)
                writer.write(
                    f"HTTP/1.1 {status}\r\nContent-Type: {ctype}\r\n"
                    f"Content-Length: {len(payload)}\r\n"
                    "Connection: keep-alive\r\n\r\n".encode() + payload
                )
                await writer.drain()
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass

    def _dispatch(self, target: str, method: str = "GET",
                  body: bytes = b""):
        parts = urlsplit(target)
        query = {
            k: v for k, v in
            (kv.split("=", 1) for kv in parts.query.split("&") if "=" in kv)
        }
        try:
            if parts.path == "/metrics":
                rows = self._head().call("get_metrics", {})
                try:
                    obs = self._head().call("obs_stats", {})
                    rows = rows + [{
                        "name": "task_events_dropped_total",
                        "kind": "counter",
                        "description": "task/span events evicted from the "
                                       "head's bounded event ring",
                        "tags": [],
                        "value": obs.get("task_events_dropped_total", 0),
                    }]
                except Exception:  # noqa: BLE001
                    pass
                text = _to_prometheus(rows, self._cluster_summary())
                return "200 OK", "text/plain; version=0.0.4", text.encode()
            if parts.path == "/api/serve/applications":
                # declarative serve over REST (reference
                # dashboard/modules/serve/serve_head.py): GET = status,
                # PUT = apply a config document
                from ray_tpu.serve import schema as serve_schema

                if method == "PUT":
                    cfg = json.loads(body.decode() or "{}")
                    names = serve_schema.apply(cfg)
                    return ("200 OK", "application/json",
                            json.dumps({"deployed": names}).encode())
                return ("200 OK", "application/json",
                        json.dumps(serve_schema.status(),
                                   default=_jsonable).encode())
            data = self._api(parts.path, query)
            if data is None:
                return ("404 Not Found", "application/json",
                        json.dumps({"error": parts.path}).encode())
            return ("200 OK", "application/json",
                    json.dumps(data, default=_jsonable).encode())
        except Exception as e:  # noqa: BLE001
            return ("500 Internal Server Error", "application/json",
                    json.dumps({"error": str(e)}).encode())


def _jsonable(o):
    if isinstance(o, bytes):
        return o.hex()
    return repr(o)


_started: list[DashboardHead] = []


def start_dashboard(host: str = "127.0.0.1",
                    port: int = 0) -> tuple[str, int]:
    """Start the dashboard in this (cluster-connected) process; returns
    its (host, port)."""
    d = DashboardHead(host, port)
    _started.append(d)
    return d.wait_ready()


def stop_dashboard() -> None:
    """Stops every head :func:`start_dashboard` started in this
    process."""
    while _started:
        _started.pop().stop()
