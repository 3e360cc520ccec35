"""Llama decoder training + decode throughput on one chip, one phase per
process: `python bench.py --only 350m|1b|decode|engine|serve|serve2`.

Each invocation prints ONE JSON line that names the device it ran on,
and fails (non-zero exit, no line) when there is no TPU, the device kind
has no entry in PEAK_FLOPS, or the phase raises. A chip belongs to one
process: the train/decode/engine phases compute in this process, the
serve phases keep this process off JAX and serve from a replica that
its node agent granted the chip (ray_tpu/_private/accelerator.py). The
benchmark proper (cells, medians, traces) is a later PR's;
`chip_smoke.py` is the pass/fail proof that the path starts on the chip.
"""

import argparse
import json
import os
import sys
import time

# Keep the CPU test-env override out of the bench path (preserve other flags).
_flags = os.environ.get("XLA_FLAGS", "").split()
_kept = [f for f in _flags if "xla_force_host_platform_device_count" not in f]
if _kept != _flags:
    if _kept:
        os.environ["XLA_FLAGS"] = " ".join(_kept)
    else:
        os.environ.pop("XLA_FLAGS")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.parallel import MeshConfig, build_mesh, use_mesh  # noqa: E402
from ray_tpu.train import (  # noqa: E402
    batch_sharding,
    init_train_state,
    make_train_step,
)

# bf16 peak FLOP/s per chip by jax device_kind (Google Cloud TPU docs);
# a device that is not listed is an error, never a default
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v4": 275e12,
}


def claim_chip() -> dict:
    """This process computes on the chip: place the compile cache, and
    fail here if JAX found no TPU or one this file has no peak for."""
    from ray_tpu._private import accelerator

    return _require_tpu(accelerator.claim_device())


def _require_tpu(dev: dict) -> dict:
    if dev["platform"] != "tpu":
        raise RuntimeError(f"bench.py measures the chip; got {dev}")
    if dev["kind"] not in PEAK_FLOPS:
        raise KeyError(
            f"no peak FLOP/s on record for device kind {dev['kind']!r}; "
            f"add it to PEAK_FLOPS with its source")
    return {"platform": dev["platform"], "kind": dev["kind"],
            "count": dev["count"]}


def _sync(x) -> float:
    return float(jax.block_until_ready(x))


def bench_train(size: str, batch: int, seq: int, *, windows: int = 8,
                n_steps: int = 5, grads_dtype=None,
                remat_policy: str = "dots_flash_qkv_mlp") -> dict:
    cfg = llama.llama2_size(size)
    cfg = llama.LlamaConfig(
        **{
            **cfg.__dict__,
            "vocab_size": 32128,
            "max_seq_len": seq,
            "dtype": "bfloat16",
            "remat": True,
            # default: save the flash (out, lse) residuals so the backward
            # reuses them instead of re-running the forward attention
            "remat_policy": remat_policy,
        }
    )
    n_params = cfg.num_params()
    device = claim_chip()

    mesh = build_mesh(MeshConfig(), jax.devices()[:1])
    # single-HBM-pass adamw with bf16 moments (train/optim.py): optax's
    # chain costs ~20 ms/step at 350M; low-precision moments halve the
    # moment traffic on top
    from ray_tpu.train.optim import fused_adamw

    opt = fused_adamw(1e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16,
                      nu_dtype=jnp.bfloat16)
    state, state_sh = init_train_state(
        lambda k: llama.init_params(cfg, k),
        llama.param_logical_axes(cfg),
        opt,
        mesh,
        key=jax.random.PRNGKey(0),
    )
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh, state_sh,
        compute_grad_norm=False,  # telemetry pass the bench doesn't read
        grads_dtype=grads_dtype,
    )

    toks = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    with use_mesh(mesh):
        data = jax.device_put(data, batch_sharding(mesh))

        for _ in range(2):  # compile + warm
            state, metrics = step(state, data)
        _sync(metrics["loss"])
        t0 = time.perf_counter()
        _sync(metrics["loss"])
        sync_overhead = time.perf_counter() - t0

        # best of N windows: a one-chip machine shares its host's CPU
        # cores, so a single window can absorb a neighbour's burst
        dt = float("inf")
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(n_steps):
                state, metrics = step(state, data)
            loss = _sync(metrics["loss"])
            dt = min(dt, time.perf_counter() - t0 - sync_overhead)

    tokens_per_sec = batch * seq * n_steps / dt
    model_flops = 6.0 * n_params * tokens_per_sec  # fwd+bwd FLOPs/token ~6N
    mfu = model_flops / PEAK_FLOPS[device["kind"]]
    return {
        "device": device,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4),
        "n_params": n_params,
        "batch": batch,
        "seq": seq,
        "step_time_s": round(dt / n_steps, 4),
        "loss": round(loss, 4),
    }


def bench_decode(size: str, batch: int, prompt_len: int, new_tokens: int,
                 *, windows: int = 5) -> dict:
    """KV-cache serving throughput: prefill + `new_tokens` greedy decode
    steps, the whole loop inside ONE jit (generate_scan): one dispatch
    per sequence, not one per token."""
    device = claim_chip()
    cfg = llama.llama2_size(size)
    cfg = llama.LlamaConfig(
        **{
            **cfg.__dict__,
            "vocab_size": 32128,
            "max_seq_len": prompt_len + new_tokens,
            "dtype": "bfloat16",
        }
    )
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0, cfg.vocab_size,
        dtype=jnp.int32,
    )
    max_len = prompt_len + new_tokens

    def run():
        cache = llama.init_cache(cfg, batch, max_len)
        out, _ = llama.generate_scan(params, prompt, cfg, new_tokens, cache)
        return _sync(out[0, -1])

    run()  # compile
    dt = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        run()
        dt = min(dt, time.perf_counter() - t0)
    toks_per_s = batch * new_tokens / dt
    return {
        "device": device,
        "decode_tokens_per_sec": round(toks_per_s, 1),
        "per_stream_tokens_per_sec": round(toks_per_s / batch, 1),
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "n_params": cfg.num_params(),
    }


def bench_decode_engine(size: str, *, slots: int = 8,
                        prompt_len: int = 128, new_tokens: int = 128,
                        n_requests: int = 32,
                        chunk_tokens: int = 32) -> dict:
    """Continuous-batching ENGINE throughput (decode_engine.py driven
    directly, ideal arrivals): the ceiling the serve path approaches
    once HTTP/actor host overhead is excluded."""
    import numpy as np

    from ray_tpu.models.decode_engine import RaggedDecoder

    device = claim_chip()
    cfg = llama.llama2_size(size)
    cfg = llama.LlamaConfig(**{
        **cfg.__dict__, "vocab_size": 32128,
        "max_seq_len": prompt_len + new_tokens + 32,
        "dtype": "bfloat16", "remat": False,
    })
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    eng = RaggedDecoder(params, cfg, slots=slots,
                        max_len=prompt_len + new_tokens + 32,
                        chunk_tokens=chunk_tokens,
                        prompt_buckets=(prompt_len,))
    rng = np.random.RandomState(0)

    def req():
        return rng.randint(1, 30000, prompt_len).astype(np.int32)

    sid = eng.submit(req(), chunk_tokens)  # compile prefill + chunk
    eng.drain()
    eng.pop_finished(sid)

    sids = [eng.submit(req(), new_tokens) for _ in range(n_requests)]
    t0 = time.perf_counter()
    eng.drain()
    dt = time.perf_counter() - t0
    total = sum(len(eng.finished[s].tokens) for s in sids
                if s in eng.finished)
    return {
        "device": device,
        "engine_tokens_per_sec": round(total / dt, 1),
        "slots": slots, "chunk_tokens": chunk_tokens,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "n_requests": n_requests,
    }


def bench_decode_serve(size: str, *, slots: int = 8,
                       prompt_len: int = 128, new_tokens: int = 128,
                       n_requests: int = 32, concurrency: int = 16,
                       chunk_tokens: int = 32, replicas: int = 1,
                       prefill_workers: int = 0,
                       prefix_cache_block: int = 0) -> dict:
    """E2E SERVING decode: the 1B model behind a Serve deployment with
    chunked continuous batching (serve/llm.py + models/decode_engine.py),
    measured through the HTTP proxy — concurrent requests share one slot
    batch, new streams admitted as slots free. Reports aggregate HTTP
    tokens/s plus TTFT and chunk-normalized per-token latency
    percentiles (tokens arrive per chunk; each positive inter-stamp gap
    is divided by the tokens it delivered).

    replicas > 1 (or prefill_workers/prefix_cache_block set) swaps the
    single LLMServer for an LLMPool deployment (serve/llm_pool.py):
    shared admission queue, N decode replicas adopting ONE published
    weight blob, optional dedicated prefill workers and prefix/KV
    cache. Extra outputs then: replicas, prefix_cache_hit_rate."""
    import http.client
    import random
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.api import Deployment
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.serve.llm_pool import LLMPool

    pooled = (replicas > 1 or prefill_workers > 0
              or prefix_cache_block > 0)
    # the pool publishes the f32 master tree as ONE object (4,660 MiB at
    # 1B widths); the lone server initialises its own weights
    ray_tpu.init(num_cpus=4,
                 object_store_memory=(8 if pooled else 1) * 2**30)
    try:
        init_kwargs = {
            "model_size": size, "slots": slots,
            "max_len": prompt_len + new_tokens + 32,
            "chunk_tokens": chunk_tokens,
            "prompt_buckets": (prompt_len,),
        }
        if pooled:
            # the pool holds no chip; it asks one for each member
            cls, max_q, res = LLMPool, max(64, 2 * concurrency), {"CPU": 0}
            init_kwargs.update(
                min_replicas=replicas, max_replicas=replicas,
                prefill_workers=prefill_workers,
                prefix_cache_block=prefix_cache_block)
        else:
            cls, max_q, res = (LLMServer, max(16, 2 * slots),
                               {"CPU": 0, "TPU": 1})
        dep = Deployment(cls, max_concurrent_queries=max_q,
                         resources=res, route_prefix="/llm")
        serve.run(dep, name="llm", init_kwargs=init_kwargs)
        host, port = serve.start_http_proxy()

        def post(path, body):
            conn = http.client.HTTPConnection(host, port, timeout=590)
            try:
                conn.request("POST", path, json.dumps(body),
                             {"Content-Type": "application/json"})
                r = conn.getresponse()
                return r.status, json.loads(r.read() or b"null")
            finally:
                conn.close()

        # wait for the proxy to learn the route + the replica to warm
        # (first request compiles prefill + decode chunk)
        rnd = random.Random(0)
        warm = {"prompt_ids": [rnd.randrange(1, 30000)
                               for _ in range(prompt_len)],
                "max_tokens": chunk_tokens}
        deadline = time.time() + 600
        while time.time() < deadline:
            try:
                status, _ = post("/llm", warm)
                if status == 200:
                    break
            except OSError:
                pass
            time.sleep(1.0)

        results: list[dict | None] = [None] * n_requests
        errors: list[str] = []

        def one(i):
            # per-request RNG, seeded by request index: the shared
            # module-level Random is unlocked (thread-racy draws) and
            # order-dependent — prompts must be identical run to run for
            # the benchmark to be comparable
            r = random.Random(1000 + i)
            body = {"prompt_ids": [r.randrange(1, 30000)
                                   for _ in range(prompt_len)],
                    "max_tokens": new_tokens}
            try:
                status, data = post("/llm", body)
                if status == 200:
                    results[i] = data
                else:
                    errors.append(f"http {status}")
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        t0 = time.perf_counter()
        threads: list[threading.Thread] = []
        sem = threading.Semaphore(concurrency)

        def worker(i):
            with sem:
                one(i)

        for i in range(n_requests):
            th = threading.Thread(target=worker, args=(i,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0
        if errors:
            raise RuntimeError(
                f"{len(errors)} of {n_requests} requests failed; first: "
                f"{errors[0][:300]}")

        done = [r for r in results if r]
        total_tokens = sum(len(r["tokens"]) for r in done)
        ttfts, per_tok = [], []
        for r in done:
            stamps = r["token_times_s"]
            ttfts.append(stamps[0] - r["submitted_s"])
            gaps = np.diff(np.asarray(stamps))
            pos = gaps[gaps > 0]
            if len(pos):
                per_tok.extend(pos / chunk_tokens)
        out = {
            "serve_tokens_per_sec": round(total_tokens / dt, 1),
            "n_ok": len(done),
            "concurrency": concurrency, "slots": slots,
            "chunk_tokens": chunk_tokens,
            "prompt_len": prompt_len, "new_tokens": new_tokens,
            "replicas": replicas,
        }
        st = ray_tpu.get(
            serve.get_handle("llm").method("stats").remote(), timeout=60)
        if pooled:
            out["prefix_cache_hit_rate"] = st.get("prefix_cache_hit_rate")
            out["pool_ttft_p99_s"] = st.get("ttft_p99_s")
            devices = [r["device"] for r in st["per_replica"].values()]
        else:
            devices = [st["device"]]
        # as each replica's own process reports it
        out["device"] = [_require_tpu(d) for d in devices][0]
        if ttfts:
            out["ttft_p50_s"] = round(float(np.percentile(ttfts, 50)), 3)
            out["ttft_p99_s"] = round(float(np.percentile(ttfts, 99)), 3)
        if per_tok:
            out["per_token_p50_ms"] = round(
                1000 * float(np.percentile(per_tok, 50)), 2)
            out["per_token_p99_ms"] = round(
                1000 * float(np.percentile(per_tok, 99)), 2)
        return out
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()


# One phase per process (a chip belongs to one process at a time).
# bf16 grads: the optimizer's update math stays f32 (masters are f32);
# only the grad tree itself rides bf16, halving its HBM traffic.
PHASES = {
    "350m": lambda: bench_train("350m", 8, 2048, grads_dtype=jnp.bfloat16),
    "1b": lambda: bench_train("1b", 2, 2048, grads_dtype=jnp.bfloat16,
                              remat_policy="flash_qkv"),
    "decode": lambda: bench_decode("1b", 8, 128, 128),
    "engine": lambda: bench_decode_engine("1b"),
    "serve": lambda: bench_decode_serve("1b"),
    # the pool on three chips (2 decode replicas, one prefill worker,
    # prefix cache): needs a host with that many
    "serve2": lambda: bench_decode_serve(
        "1b", replicas=2, prefill_workers=1, prefix_cache_block=32,
        concurrency=32),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", required=True, choices=sorted(PHASES))
    args = ap.parse_args()
    print(json.dumps(PHASES[args.only]()))


if __name__ == "__main__":
    sys.exit(main())
