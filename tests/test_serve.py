"""Serve v0 tests: deploy/route/batch + batched jitted llama decode.

Reference analogs: python/ray/serve/tests/test_standalone.py,
test_batching.py, scaled to the handle (HTTP-less) data path.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(head_resources={"CPU": 4, "memory": 4 * 2**30})
    c.connect()
    yield c
    serve.shutdown()
    c.shutdown()


def test_deploy_and_route(cluster):
    @serve.deployment(num_replicas=2, max_concurrent_queries=4)
    class Echo:
        def __init__(self, tag):
            self.tag = tag

        def __call__(self, x):
            import os

            return (self.tag, x, os.getpid())

    h = serve.run(Echo, name="echo", init_args=("v1",))
    outs = ray_tpu.get(
        [h.remote(i) for i in range(20)], timeout=120
    )
    assert all(tag == "v1" and x == i for (tag, x, _), i in
               zip(outs, range(20)))
    # both replicas served traffic
    pids = {pid for (_, _, pid) in outs}
    assert len(pids) == 2


def test_redeploy_new_version(cluster):
    """Rolling redeploy under concurrent load drops ZERO requests.

    Old replicas are drained (unpublished, killed only when idle), so every
    request issued during the roll succeeds — returning the old or the new
    version, never an error."""
    import threading

    @serve.deployment(num_replicas=2)
    class V:
        def __init__(self, v):
            self.v = v

        def __call__(self, _):
            import time as t

            t.sleep(0.02)  # keep requests in flight during the roll
            return self.v

    h = serve.run(V, name="v", init_args=("one",))
    assert ray_tpu.get(h.remote(0), timeout=60) == "one"

    results: list = []
    errors: list = []
    stop = threading.Event()

    def fire():
        while not stop.is_set():
            try:
                results.append(ray_tpu.get(h.remote(0), timeout=60))
            except Exception as e:  # noqa: BLE001 — the assertion target
                errors.append(e)

    threads = [threading.Thread(target=fire) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        h = serve.run(V, name="v", init_args=("two",), version="2")
        # keep firing a moment after the roll completes
        deadline = time.time() + 5
        while time.time() < deadline and "two" not in results[-8:]:
            time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)

    assert not errors, f"requests failed during rolling redeploy: {errors[:3]}"
    assert set(results) <= {"one", "two"}
    assert "two" in results  # the roll completed into the new version
    assert ray_tpu.get(h.remote(0), timeout=60) == "two"


def test_method_call(cluster):
    @serve.deployment(num_replicas=1)
    class M:
        def stats(self):
            return {"ok": True}

    h = serve.run(M, name="m")
    assert ray_tpu.get(h.method("stats").remote(), timeout=60) == {
        "ok": True
    }


def test_batching_groups_requests(cluster):
    @serve.deployment(num_replicas=1, max_concurrent_queries=16)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        def _handle(self, items):
            self.batch_sizes.append(len(items))
            return [i * 10 for i in items]

        def __call__(self, x):
            return self._handle(x)

        def sizes(self):
            return self.batch_sizes

    h = serve.run(Batched, name="batched")
    refs = [h.remote(i) for i in range(16)]
    outs = ray_tpu.get(refs, timeout=120)
    assert sorted(outs) == [i * 10 for i in range(16)]
    sizes = ray_tpu.get(h.method("sizes").remote(), timeout=60)
    # at least one multi-request batch formed
    assert max(sizes) > 1


def test_serve_llama_decode(cluster):
    """Replica hosting tiny-llama behind ``serve.batch``: batched
    requests decoded greedily, four tokens each, by the uncached forward
    on one fixed [4, 8] buffer (one compile; causal attention keeps the
    zeros behind a position out of it; the serving engine has tests of
    its own), p50 latency asserted."""

    @serve.deployment(num_replicas=1, max_concurrent_queries=16)
    class LM:
        def __init__(self):
            import jax

            jax.config.update("jax_platforms", "cpu")
            from ray_tpu.models import llama

            cfg = llama.LlamaConfig.tiny()
            self.params = llama.init_params(cfg, jax.random.PRNGKey(0))
            self.forward = jax.jit(lambda p, t: llama.forward(p, t, cfg))

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05)
        def _generate(self, prompts):
            import numpy as np

            buf = np.zeros((4, 8), np.int32)
            buf[:len(prompts), :4] = np.stack(prompts)
            for t in range(4, 8):
                logits = self.forward(self.params, buf)
                buf[:, t] = np.asarray(logits[:, t - 1]).argmax(axis=-1)
            return list(buf[:len(prompts)])

        def __call__(self, prompt):
            return self._generate(prompt)

    h = serve.run(LM, name="lm")
    prompt = np.array([1, 2, 3, 4], dtype=np.int32)
    # warm (compile)
    first = ray_tpu.get(h.remote(prompt), timeout=300)
    assert first.shape == (8,)
    assert list(first[:4]) == [1, 2, 3, 4]

    lat: list[float] = []

    def one():
        t0 = time.perf_counter()
        out = ray_tpu.get(h.remote(prompt), timeout=120)
        lat.append(time.perf_counter() - t0)
        assert out.shape == (8,)

    threads = [threading.Thread(target=one) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(lat) == 8
    p50 = sorted(lat)[len(lat) // 2]
    assert p50 < 5.0  # CPU tiny-llama, batched: comfortably sub-5s


def test_config_file_deploy(cluster, tmp_path):
    """Declarative deploy from a YAML config (reference serve schema +
    `serve deploy` CLI): import_path resolution, per-deployment overrides,
    and redeploy-by-reapply."""
    import sys
    import textwrap

    mod = tmp_path / "my_service_mod.py"
    mod.write_text(textwrap.dedent("""
        from ray_tpu import serve

        @serve.deployment(num_replicas=1)
        class Greeter:
            def __init__(self, greeting="hi"):
                self.greeting = greeting
                self.punct = ""

            def reconfigure(self, cfg):
                self.punct = cfg.get("punct", "")

            def __call__(self, name):
                return f"{self.greeting} {name}{self.punct}"
    """))
    cfg = tmp_path / "serve.yaml"
    cfg.write_text(textwrap.dedent("""
        applications:
          - name: greeter
            import_path: my_service_mod:Greeter
            route_prefix: /greet
            version: "1"
            init_kwargs:
              greeting: hello
            deployments:
              - name: Greeter
                num_replicas: 2
                max_concurrent_queries: 4
                user_config:
                  punct: "!"
    """))
    sys.path.insert(0, str(tmp_path))
    try:
        from ray_tpu.serve import schema as serve_schema

        names = serve_schema.apply(str(cfg))
        assert names == ["greeter"]
        h = serve.get_handle("greeter")
        assert ray_tpu.get(h.remote("world"), timeout=60) == "hello world!"
        st = serve_schema.status()
        assert st["greeter"]["num_replicas"] == 2

        # re-apply with a new version: rolling redeploy via config
        cfg.write_text(cfg.read_text().replace('version: "1"',
                                               'version: "2"')
                       .replace("greeting: hello", "greeting: hey"))
        serve_schema.apply(str(cfg))
        assert ray_tpu.get(h.remote("you"), timeout=60) == "hey you!"

        # malformed config rejected
        import pytest as _pytest

        with _pytest.raises(ValueError):
            serve_schema.apply({"applications": [{"name": "x"}]})
    finally:
        sys.path.remove(str(tmp_path))


def test_serve_rest_api(cluster, tmp_path):
    """Declarative serve over the dashboard REST endpoint (reference
    dashboard/modules/serve): PUT /api/serve/applications applies a
    config document; GET returns running deployments."""
    import http.client
    import json
    import sys
    import textwrap

    from ray_tpu.dashboard import start_dashboard

    mod = tmp_path / "rest_service_mod.py"
    mod.write_text(textwrap.dedent("""
        from ray_tpu import serve

        @serve.deployment(num_replicas=1)
        class Adder:
            def __call__(self, x):
                return x + 100
    """))
    sys.path.insert(0, str(tmp_path))
    try:
        host, port = start_dashboard()
        conn = http.client.HTTPConnection(host, port, timeout=120)
        body = json.dumps({"applications": [{
            "name": "adder",
            "import_path": "rest_service_mod:Adder",
            "route_prefix": "/adder",
        }]})
        conn.request("PUT", "/api/serve/applications", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = json.loads(resp.read())
        assert resp.status == 200, out
        assert out["deployed"] == ["adder"]

        conn.request("GET", "/api/serve/applications")
        resp = conn.getresponse()
        status = json.loads(resp.read())
        assert resp.status == 200
        assert status["adder"]["num_replicas"] == 1
        conn.close()

        h = serve.get_handle("adder")
        assert ray_tpu.get(h.remote(1), timeout=60) == 101
    finally:
        sys.path.remove(str(tmp_path))
