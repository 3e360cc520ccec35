"""The ``serve`` driver: one pool, one replica, load over HTTP.

This process orchestrates and stays off JAX: ``ray_tpu.init()``, the
pool through ``serve.run``, the HTTP proxy, then the load generator as a
child process. The replica, which its node agent granted the chip(s),
is the only process that touches them. (Pool deployment, HTTP client and
teardown order are a copy of ``chip_smoke.py``'s.)
"""

from __future__ import annotations

import http.client
import json
import os
import random
import shutil
import subprocess
import sys
import threading
import time

from benchmark import cluster, loadgen, manifest, stats, traffic
from benchmark.manifest import REHEARSAL, model_fields


class BenchFailure(RuntimeError):
    """The run cannot report: nothing is printed, the exit code is not 0."""


def _post(addr, body: dict, timeout: float = 900.0) -> list:
    """One streamed request, blocking -> its tokens (set-up only: warm-up
    and the correctness probe; the measured load is ``loadgen``'s)."""
    deadline = time.monotonic() + 60.0
    while True:
        conn = http.client.HTTPConnection(*addr, timeout=timeout)
        try:
            conn.request("POST", "/llm", json.dumps({**body, "stream": True}),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            if r.status == 404 and time.monotonic() < deadline:
                time.sleep(0.3)  # the proxy has not learnt the route yet
                continue
            if r.status != 200 \
                    or r.getheader("Transfer-Encoding") != "chunked":
                raise BenchFailure(f"set-up request failed: {r.status} "
                                   f"{r.read()[:300]!r}")
            toks = []
            for line in r:
                msg = json.loads(line) if line.strip() else {}
                if "error" in msg:
                    raise BenchFailure(f"set-up stream failed: {msg}")
                toks.extend(msg.get("tokens", ()))
            return [int(t) for t in toks]
        finally:
            conn.close()


def shrink_for_rehearsal(tr: dict) -> dict:
    """The CPU rehearsal's traffic: same structure, a sixteenth of every
    length, 4 slots. Says nothing about speed; finds wrong control flow."""
    tr = json.loads(json.dumps(tr))
    eng = tr["engine"]
    eng.update(slots=4, max_len=max(48, eng["max_len"] // 16),
               chunk_tokens=4,
               prompt_buckets=[max(4, b // 16) for b in eng["prompt_buckets"]])
    sh = tr["shapes"]
    if sh["mode"] == "entries":
        sh["entries"] = [[max(2, p // 16), max(2, o // 16)]
                         for p, o in sh["entries"]]
    else:
        for d in (sh["prompt"], sh["output"]):
            for k in ("lo", "hi", "median"):
                if k in d:
                    d[k] = max(2, d[k] // 16)
    if tr["loop"] == "closed":
        tr["clients"] = 6
        tr["window"]["opens_after_completed"] = 4
    else:
        tr["window"]["ramp_s"] = 1.0
    tr["trace_seconds"] = 1
    return tr


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        rehearse: bool, t_start: float, work_dir: str) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.api import Deployment

    from benchmark.serve_members import BenchPool

    tr = shrink_for_rehearsal(cell["traffic"]) if rehearse \
        else cell["traffic"]
    model = (REHEARSAL if rehearse else "") + cell["config_name"]
    vocab = model_fields(model)["vocab_size"]
    eng = dict(tr["engine"])
    own_cluster = cluster.start()
    dep = Deployment(BenchPool, max_concurrent_queries=256,
                     resources={"CPU": 0}, route_prefix="/llm")
    serve.run(dep, name="llm", init_kwargs=dict(
        model_size=model, slots=eng["slots"], max_len=eng["max_len"],
        chunk_tokens=eng["chunk_tokens"],
        prompt_buckets=tuple(eng["prompt_buckets"]), seed=seed,
        min_replicas=1, max_replicas=1, prefill_workers=0, autoscale=False,
        chunk_delay_s=0.0, spec_depth=0, prefix_cache_block=0))
    pool = serve.get_handle("llm")

    def call(method, *args, timeout=900):
        return ray_tpu.get(pool.method(method).remote(*args),
                           timeout=timeout)

    log_dir = os.path.join(work_dir, "trace")
    try:
        addr = serve.start_http_proxy()
        # warm every shape the traffic uses and no other: one prompt per
        # prefill bucket, a chunk of decode each
        rnd = random.Random(seed)
        n = eng["chunk_tokens"] + 1
        for b in sorted(eng["prompt_buckets"]):
            ids = [rnd.randrange(1, vocab) for _ in range(b - 1)]
            got = _post(addr, {"prompt_ids": ids, "max_tokens": n})
            if len(got) != n:
                raise BenchFailure(f"warm-up: {len(got)} tokens, not {n}")
        # correctness, outside the window: one greedy request through the
        # whole served path, then the plain reference in the replica
        probe_prompt = ids[:127]
        probe_tokens = _post(addr, {"prompt_ids": probe_prompt,
                                    "max_tokens": 24})
        check = call("bench_call", "reference_check", probe_prompt,
                     probe_tokens)
        print(f"benchmark: served tokens against the reference: {check}",
              file=sys.stderr, flush=True)

        plan = traffic.plan(tr, seed, vocab, seconds)
        plan.update(addr=list(addr), vocab=vocab, seconds=seconds)
        if trace and tr.get("idle_probe_requests"):
            plan["idle_probe"] = {
                "n": int(tr["idle_probe_requests"]),
                "body": {"prompt_ids": probe_prompt[:32], "max_tokens": 1}}
        plan_path = os.path.join(work_dir, "plan.json")
        result_path = os.path.join(work_dir, "result.json")
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        gen = subprocess.Popen(
            [sys.executable, "-m", "benchmark.loadgen", plan_path,
             result_path], cwd=manifest.ROOT, env=env,
            stdout=subprocess.PIPE, stderr=sys.stderr, text=True)
        try:
            line = gen.stdout.readline()
            if not line.startswith("OPEN "):
                raise BenchFailure(f"load generator said {line!r}")
            t_open = float(line.split()[1])
            before = call("stats")
            if trace:
                # a short window of its own inside the measured one
                def _trace():
                    time.sleep(min(2.0, seconds / 4))
                    shutil.rmtree(log_dir, ignore_errors=True)
                    call("bench_call", "start_trace", log_dir)
                    time.sleep(min(float(tr.get("trace_seconds", 4)),
                                   seconds / 2))
                    call("bench_call", "stop_trace")

                th = threading.Thread(target=_trace)
                th.start()
            time.sleep(max(0.0, t_open + seconds - time.monotonic()))
            after = call("stats")
            if trace:
                th.join()
            if gen.wait(timeout=300) != 0:
                raise BenchFailure("load generator failed")
        finally:
            if gen.poll() is None:
                gen.kill()
                gen.wait()
        with open(result_path) as f:
            result = json.load(f)
    finally:
        try:
            # the pool's replicas are its own actors: deleting the
            # deployment alone would orphan them, still holding the chip
            call("shutdown", timeout=120)
        finally:
            serve.shutdown()
            if own_cluster:
                ray_tpu.shutdown()
    cluster.wait_chips_free()
    rep_before, rep_after = (
        next(iter(s["per_replica"].values())) for s in (before, after))
    return reduce(tr, result, rep_before, rep_after, model=model,
                  check=check, seconds=seconds, t_start=t_start,
                  log_dir=log_dir if trace else None)


def reduce(tr, result, before, after, *, model, check, seconds,
           t_start, log_dir) -> dict:
    """Client records, counter deltas and the trace -> the run's facts."""
    t_open, t_close = result["t_open"], result["t_close"]
    recs = result["records"]
    if tr["loop"] == "closed":
        # judged on tokens that arrived inside the window; a request
        # counts as attempted when its outcome fell inside it
        done = [r for r in recs if (r["ok"] or r["error"])
                and t_open <= r["end"] < t_close]
        measured = [r for r in done if r["ok"]]
        arrivals = [a for r in recs for a in r["arrivals"]]
        admitted = [r for r in recs if r["sent"] is not None
                    and t_open <= r["sent"] < t_close]
    else:
        done = [r for r in recs if r["measured"]]
        measured = [r for r in done if r["ok"]]
        arrivals = [a for r in done for a in r["arrivals"]]
        admitted = done
    failed = [r for r in done if not r["ok"]]
    wrong = [r for r in measured
             if r["n_tokens"] != r["max_tokens"] or not r["ids_in_vocab"]]
    start = {r["i"]: r.get("due", r["sent"]) for r in recs}
    # a failed or refused request misses every latency limit: it counts
    # with the time after which the client gives up
    ttft = [(r["arrivals"][0][0] - start[r["i"]]) if r["ok"]
            else loadgen.REQUEST_TIMEOUT_S for r in done]
    tpot = [(r["arrivals"][-1][0] - r["arrivals"][0][0])
            / (r["n_tokens"] - 1) for r in measured if r["n_tokens"] > 1]
    compiles = (after["device"]["compile"]["requests"]
                - before["device"]["compile"]["requests"])
    if compiles:
        print(f"benchmark: {compiles} compilation(s) inside the window",
              file=sys.stderr, flush=True)
    if tr["loop"] == "open":
        # what the one sweep for the knee reads: does the queue grow?
        half = t_open + seconds / 2
        early, late = ([t for r, t in zip(done, ttft)
                        if (r["due"] < half) == first] for first in (1, 0))
        print("benchmark: open loop: ttft median of the window's first "
              f"half {stats.median(early):.3f} s, second half "
              f"{stats.median(late):.3f} s; {len(done)} due, "
              f"{sum(r['end'] < t_close + 5 for r in measured)} done within "
              "5 s of the close", file=sys.stderr, flush=True)
    dev = after["device"]
    facts = {
        "attempted": len(done), "failed": len(failed),
        "correct": bool(check["ok"] and not wrong and not compiles
                        and measured),
        "setup_s": t_open - t_start,
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"],
                   "memory_peak_bytes": max(
                       (b or 0) for b in dev["peak_bytes_in_use"])},
        "client": {
            "ttft_s": ttft, "tpot_s": tpot,
            "tokens_in_window": stats.tokens_in_window(
                arrivals, t_open, t_close),
            "lateness_s": [r["sent"] - r["due"] for r in done
                           if "due" in r and r["sent"] is not None],
            "idle_ttft_s": result.get("idle_ttft_s", []),
            "admitted": len(admitted),
            "shapes": [(r["prompt_len"], r["max_tokens"]) for r in done],
        },
        "counters": {k: after[k] - before[k]
                     for k in ("pumps", "total_tokens")
                     if k in after and k in before},
        "engine": dict(tr["engine"]), "model": model,
        "window_s": seconds, "log_dir": log_dir,
    }
    return facts
