"""HTTP ingress for serve deployments.

Reference: serve/_private/http_proxy.py:255 HTTPProxy (+ :173
LongestPrefixRouter) — an actor per ingress node running an HTTP server
that resolves the route prefix to a deployment and forwards the request
through a DeploymentHandle. The reference embeds uvicorn/ASGI; this image
has no uvicorn, so the server is a raw asyncio HTTP/1.1 implementation —
~line-for-capability: longest-prefix routing, JSON bodies, query params,
404/500 mapping, route table refreshed by long-poll from the controller.

GET /prefix?a=1 -> handle.remote({query params})
POST /prefix    -> handle.remote(json_body)
Response: JSON-encoded return value, 200; unknown route 404; user
exception 500 with the error string.

Token streaming: a POST body with {"stream": true} switches the
response to HTTP/1.1 chunked transfer-encoding. The proxy calls the
deployment's `submit_stream(body)` (-> {"rid"|"sid"}), then loops
`poll_stream(id)` and writes each non-empty token batch as one chunk
(a JSON line `{"tokens": [...]}`), ending with `{"done": true}` — the
serve-side analog of job_submission log tailing, built for
serve/llm.py and serve/llm_pool.py streams.
"""

from __future__ import annotations

import json
import logging
import threading
import time
from urllib.parse import parse_qs, urlsplit

import ray_tpu
from ray_tpu._private import flight_recorder as _fr

logger = logging.getLogger(__name__)


def _stamp_recv(req: dict) -> None:
    """Birth stamp of an LLM request: when the proxy had parsed its
    body (epoch seconds on the recorder's clock). Rides the request to
    the pool and the replica, whose first-token span reads it; whatever
    a client sent under the key is replaced."""
    req["stamps"] = {"proxy_recv": _fr.wall(time.monotonic())}


def _match_route(routes: dict[str, str], path: str) -> str | None:
    """Longest matching prefix (LongestPrefixRouter:173)."""
    best = None
    for prefix in routes:
        clean = prefix.rstrip("/") or "/"
        if path == clean or path.startswith(clean + "/") or clean == "/":
            if best is None or len(clean) > len(best):
                best = prefix
    return best


class _ProxyServer:
    """The in-process server; lives inside the proxy actor's worker."""

    def __init__(self, host: str, port: int):
        self.host = host
        self.port = port
        self.routes: dict[str, str] = {}
        self._handles: dict[str, object] = {}
        self._ready = threading.Event()
        self._loop = None
        threading.Thread(target=self._drive, daemon=True).start()

    def _drive(self):
        import asyncio

        self._loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self._loop)

        async def boot():
            server = await asyncio.start_server(
                self._serve_conn, self.host, self.port
            )
            self.port = server.sockets[0].getsockname()[1]
            self._ready.set()

        self._loop.run_until_complete(boot())
        self._loop.run_forever()

    def wait_ready(self, timeout: float = 30.0) -> int:
        if not self._ready.wait(timeout):
            raise TimeoutError("http proxy failed to bind")
        return self.port

    def _handle_for(self, name: str):
        from ray_tpu.serve.api import get_handle

        h = self._handles.get(name)
        if h is None:
            h = self._handles[name] = get_handle(name)
        return h

    async def _serve_conn(self, reader, writer):
        import asyncio

        try:
            while True:
                line = await reader.readline()
                if not line or line in (b"\r\n", b"\n"):
                    break
                method, target, _ = line.decode().split(" ", 2)
                headers = {}
                while True:
                    h = await reader.readline()
                    if h in (b"\r\n", b"\n", b""):
                        break
                    k, _, v = h.decode().partition(":")
                    headers[k.strip().lower()] = v.strip()
                body = b""
                n = int(headers.get("content-length", 0))
                if n:
                    body = await reader.readexactly(n)
                req = None
                if body:
                    try:
                        req = json.loads(body)
                    except json.JSONDecodeError:
                        req = None
                if isinstance(req, dict) and req.get("stream"):
                    handled = await self._serve_stream(writer, target,
                                                       req)
                    if handled:
                        if headers.get("connection",
                                       "").lower() == "close":
                            break
                        continue
                    # not a streaming-capable deployment (submit_stream
                    # missing/failed before any bytes went out): fall
                    # through to the normal dispatch so schemas that
                    # happen to carry a "stream" key keep working
                status, payload = await asyncio.get_running_loop() \
                    .run_in_executor(None, self._dispatch, method,
                                     target, body)
                data = json.dumps(payload).encode()
                writer.write(
                    f"HTTP/1.1 {status}\r\n"
                    f"Content-Type: application/json\r\n"
                    f"Content-Length: {len(data)}\r\n"
                    "Connection: keep-alive\r\n\r\n".encode() + data
                )
                await writer.drain()
                if headers.get("connection", "").lower() == "close":
                    break
        except (ConnectionError, asyncio.IncompleteReadError):
            pass
        finally:
            try:
                writer.close()
            except Exception:  # noqa: BLE001
                pass

    async def _serve_stream(self, writer, target: str,
                            req: dict) -> bool:
        """Chunked-transfer token streaming (see module docstring).
        Submit/poll run on the executor pool (they block on actor
        calls); only the writes happen on the loop. Returns False —
        with NOTHING written — when the route is missing or the
        deployment cannot accept the stream, so the caller falls back
        to the normal dispatch path."""
        import asyncio

        _stamp_recv(req)
        loop = asyncio.get_running_loop()
        parts = urlsplit(target)
        route = _match_route(self.routes, parts.path)

        def _chunk(payload: dict) -> bytes:
            data = (json.dumps(payload) + "\n").encode()
            return f"{len(data):x}\r\n".encode() + data + b"\r\n"

        if route is None:
            return False  # normal dispatch owns the 404
        name = self.routes[route]
        try:
            handle = self._handle_for(name)
            # submit and every poll must land on the SAME replica (the
            # stream state lives there); a shared affinity key pins
            # both to one preferred replica when the deployment runs
            # more than one (best-effort under backpressure — the LLM
            # pool architecture keeps its pool deployment at one replica
            # precisely so this can never diverge). A routing key only:
            # sent as a multiplexed model id it reached LLMPool, which
            # refused the stream as a request for an unregistered model
            import os as _os

            skey = _os.urandom(8).hex()
            sub = await loop.run_in_executor(
                None, lambda: ray_tpu.get(
                    handle.options(affinity_key=skey,
                                   method_name="submit_stream")
                    .remote(req),
                    timeout=120))
            rid = sub.get("rid", sub.get("sid"))
        except Exception as e:  # noqa: BLE001 — submit failed before
            # any response bytes: let the normal dispatch serve it
            logger.debug("stream submit to %s failed (%s); falling "
                         "back to plain dispatch", name, e)
            return False
        writer.write(
            "HTTP/1.1 200 OK\r\n"
            "Content-Type: application/x-ndjson\r\n"
            "Transfer-Encoding: chunked\r\n"
            "Connection: keep-alive\r\n\r\n".encode())
        await writer.drain()
        try:
            while True:
                out = await loop.run_in_executor(
                    None, lambda: ray_tpu.get(
                        handle.options(affinity_key=skey,
                                       method_name="poll_stream")
                        .remote(rid),
                        timeout=120))
                if out["tokens"]:
                    writer.write(_chunk({"tokens": out["tokens"]}))
                    await writer.drain()
                if out["done"]:
                    break
                await asyncio.sleep(0.02)
            writer.write(_chunk({"done": True}))
        except Exception as e:  # noqa: BLE001 — mid-stream failure:
            # status already went out; signal in-band and terminate
            logger.warning("stream to %s failed: %s", name, e)
            try:
                writer.write(_chunk({"error": str(e)}))
            except Exception:  # noqa: BLE001
                pass
        writer.write(b"0\r\n\r\n")
        await writer.drain()
        return True

    def _dispatch(self, method: str, target: str, body: bytes):
        """Blocking route->handle call; runs on the executor pool."""
        parts = urlsplit(target)
        route = _match_route(self.routes, parts.path)
        if route is None:
            return "404 Not Found", {"error": f"no route for {parts.path}"}
        name = self.routes[route]
        if body:
            try:
                arg = json.loads(body)
            except json.JSONDecodeError:
                arg = body.decode(errors="replace")
            # an LLM request by its shape; any other deployment gets
            # its body as it was sent
            if isinstance(arg, dict) and "prompt_ids" in arg:
                _stamp_recv(arg)
        else:
            arg = {
                k: v[0] if len(v) == 1 else v
                for k, v in parse_qs(parts.query).items()
            }
        try:
            handle = self._handle_for(name)
            result = ray_tpu.get(handle.remote(arg), timeout=120)
            return "200 OK", result
        except Exception as e:  # noqa: BLE001 — user errors -> 500
            logger.warning("proxy request to %s failed: %s", name, e)
            return "500 Internal Server Error", {"error": str(e)}


@ray_tpu.remote(num_cpus=0)
class HTTPProxyActor:
    """reference http_proxy.py:481 HTTPProxyActor."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        self._server = _ProxyServer(host, port)
        self._server.wait_ready()
        self._stop = threading.Event()
        threading.Thread(target=self._route_loop, daemon=True).start()

    def _route_loop(self):
        """Track the controller's route table via long-poll."""
        from ray_tpu.serve.api import _controller

        version = 0
        while not self._stop.wait(0.0):
            try:
                c = _controller()
                if version == 0:
                    self._server.routes = ray_tpu.get(
                        c.get_routes.remote(), timeout=30
                    )
                changed = ray_tpu.get(
                    c.long_poll.remote({"routes": version}, 5.0),
                    timeout=30,
                )
                if "routes" in changed:
                    version, routes = changed["routes"]
                    self._server.routes = routes or {}
            except Exception:  # noqa: BLE001
                import time

                time.sleep(1.0)

    def address(self) -> tuple[str, int]:
        return self._server.host, self._server.port

    def ready(self) -> bool:
        return True
