"""Does the system start on the chip? The quickest proof, kept at the root.

Drives the main path once through the entry points a user calls, at the
full width and depth of the repo's "1b" Llama (d_model 2048, 22 layers,
16 heads / 8 kv heads, d_ff 5632, vocabulary 32,128, bf16 compute;
random weights from ``--seed``), with small request and step counts:

    python chip_smoke.py             # one chip: serve, train, kernels
    python chip_smoke.py --chips 4   # four chips: sharded train, 4 replicas

- serve: ``ray_tpu.init()`` -> ``serve.run(Deployment(LLMPool, ...))`` with
  one decode replica that its node agent granted the chip -> requests
  over the HTTP proxy (one streamed, greedy, seeded-sampled twice), once
  with speculative decoding on and once, from a second replica start,
  with it off. Every request returns the asked number of tokens, seed
  replay is exact, the replica reports the TPU from inside its own
  process, no other process holds a chip, and the second start reads
  compiled programs from the cache. Spec-on and spec-off tokens are
  compared: equal in f32; in bf16 they part, and a child that holds the
  chip checks that they part at near-tied logits only (see
  ``serve_phase``, ``spec_parting``, ``kernel_parting``).
- train: ``JaxTrainer``, one worker that owns the chip, the 1B recipe
  (``model_fields``; b2 x T2048, fused_adamw with bf16 moments, bf16
  grads, flash_qkv remat) for a few steps: finite, falling loss, the
  flash kernel in the compiled step, peak HBM reported by the worker.
- kernels: in a child that holds the chip, flash forward and backward at
  the 1B shape against ``attention_reference``, and the compiled 1B
  forward has the kernel in it.

With ``--chips 4`` only what exists across chips runs, each beside what
it is compared with: the 1B train step on an fsdp=2 x tp=2 mesh in one
worker against the same step on one chip, and LLMPool with four decode
replicas, each its own process on its own chip, against one replica.

This process never initialises a JAX backend: a chip belongs to one
process at a time, and here that is always a worker. Every phase prints
one JSON line. The last line of a run that passed is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
as the chip-holding workers reported it; any failure exits non-zero
without that line. Needs no network and stops what it starts.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import threading
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
KERNEL = "tpu_custom_call"


@dataclasses.dataclass(frozen=True)
class Plan:
    """What a run drives. The defaults are the contract: full 1B widths
    and depth on the TPU. tests/test_chip_smoke.py rehearses the control
    flow on the CPU with ``Plan.tiny()``; the command line cannot."""

    model_size: str = "1b"
    platform: str = "tpu"
    chips: int = 1
    seed: int = 0
    # serve: 8 slots x 288 rows, prompts of 128 and 20 tokens (buckets
    # 128 and 32), 24 new tokens; speculation depth 4 on a 1-layer draft
    slots: int = 8
    max_len: int = 288
    chunk_tokens: int = 8
    prompt_buckets: tuple = (32, 128)
    prompt_lens: tuple = (128, 20)
    max_tokens: int = 24
    # train: the 1B recipe
    batch: int = 2
    seq: int = 2048
    steps: int = 4
    # kernels: (batch, seq, q heads, kv heads, head dim) of the 1B step
    flash_shape: tuple = (2, 2048, 16, 8, 128)
    # hybrid: the KDA / MLA block (models/ling.py) at its published
    # widths, prompts that cross a chunk boundary of the KDA prefill
    hybrid_widths: str = "published"
    hybrid_lens: tuple = (65, 200)
    # and a sliding-window layer's ring (models/exaone.py: 128 rows a
    # slot at the published widths) filled past two wraps
    ring_steps: int = 300
    # and the KDA decode step's kernel against the XLA body for this
    # many tokens (models/ling.py's state, ops/kda_step.py)
    kda_steps: int = 24
    # and one gated MLA layer of models/instella.py at the published
    # widths: prompts the flash kernel takes whole (one block)
    latent_lens: tuple = (256, 1024)
    # and one KDA and one gated NoPE GQA layer of models/solar.py at the
    # published widths (64 heads): a prompt in one segment and in two
    segment_lens: tuple = (200, 4096)
    # and the chunkwise delta rule's kernel (ops/kda_chunk.py) at that
    # block's 64 heads against the XLA body and the recurrence, this
    # many rows in two calls, S carried
    kda_chunk_rows: int = 4096
    # and one Mamba-2 layer of models/granite.py at the published widths
    # (128 heads of 64 x 128): a prompt in one segment and in two
    ssm_lens: tuple = (200, 4096)
    # and one gated short convolution and one GQA layer of 64-wide heads
    # of models/lfm2.py at the published widths: a prompt in one segment
    # and in two, the step's attention two heads a lane tile
    sconv_lens: tuple = (200, 4096)
    # and the four kernels of ops/dsa.py at the published widths of
    # models/dots.py (64 index heads of 128, 128 heads of 128 + 64 / 128
    # over latent rows of 640) and of models/glm_dsa.py (32 index heads,
    # 64 heads of 192 + 64 / 256): this many query rows over twice the keys
    dsa_rows: int = 2048

    @staticmethod
    def tiny(**kw) -> "Plan":
        base = dict(model_size="tiny", platform="cpu", slots=4, max_len=96,
                    chunk_tokens=4, prompt_buckets=(8, 32),
                    prompt_lens=(24, 5), max_tokens=12, batch=2, seq=32,
                    steps=3, flash_shape=(2, 128, 4, 2, 64),
                    hybrid_widths="tiny", hybrid_lens=(9, 21), ring_steps=20,
                    kda_steps=5, latent_lens=(9, 21), segment_lens=(9, 21),
                    ssm_lens=(9, 21), sconv_lens=(9, 21), dsa_rows=128,
                    kda_chunk_rows=32)
        return Plan(**{**base, **kw})

    @property
    def on_tpu(self) -> bool:
        return self.platform == "tpu"


class SmokeFailure(AssertionError):
    """A check of a phase did not hold."""


def check(cond, what: str, **facts):
    if not cond:
        raise SmokeFailure(f"{what}: {json.dumps(facts, default=str)}")


# --------------------------------------------------------------- set-up


def build_native() -> dict:
    """Build the C++ scheduler and object store from the committed .cc
    files. Any .so already lying there was built by someone else from
    who knows what: it goes first. No toolchain, or a failed build, is an
    error here — not a silent pure-Python scheduler."""
    from ray_tpu import _native
    from ray_tpu.core.object_store import _build as store_build

    t0 = time.monotonic()
    for so in (os.path.join(os.path.dirname(_native.__file__),
                            "_scheduler.so"), store_build.SO):
        if os.path.exists(so):
            os.remove(so)
    built = [_native.ensure_built("scheduler"), store_build.ensure_built()]
    return {"built": [os.path.relpath(p, REPO) for p in built],
            "seconds": round(time.monotonic() - t0, 1)}


def model_fields(size: str, max_len: int, **kw) -> dict:
    """LlamaConfig fields of the model a phase runs: the repo's named
    size with the 32,128 vocabulary in bf16 (as serve/llm.py builds
    it), or the test-sized stand-in for the CPU rehearsal."""
    from ray_tpu.models import llama

    if size == "tiny":
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=128, dtype="float32")
    else:
        base = {**llama.llama2_size(size).__dict__, "vocab_size": 32128,
                "dtype": "bfloat16"}
    return {**base, "max_seq_len": max_len, **kw}


def store_bytes(plan: Plan) -> int:
    """Object store for the run: the pool publishes the f32 master tree
    as ONE object (4,660 MiB at 1B widths). Fails with the reason if
    shared memory here cannot hold it."""
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(**model_fields(plan.model_size, plan.max_len))
    need = int(cfg.num_params() * 4 * 1.25) + 2**30
    free = shutil.disk_usage("/dev/shm").free
    check(free > need + 2**30,
          "shared memory cannot hold the published weights",
          need_bytes=need, dev_shm_free_bytes=free)
    return need


def wait_chips_free(timeout: float = 60.0) -> None:
    """Each chip-holding process is gone before the next one starts."""
    from ray_tpu._private import accelerator

    deadline = time.monotonic() + timeout
    while accelerator.chip_holders():
        check(time.monotonic() < deadline, "a process still holds a chip",
              holders=accelerator.chip_holders())
        time.sleep(0.2)


def check_device(plan: Plan, dev: dict, chips: int, who: str) -> dict:
    """``dev`` is what a worker reported from inside its own process."""
    check(dev["platform"] == plan.platform, f"{who} is on the wrong platform",
          want=plan.platform, got=dev)
    if plan.on_tpu:
        check(dev["count"] == chips and len(dev["granted_chips"]) == chips
              and len(dev["nodes"]) == chips,
              f"{who} does not see exactly its {chips} chip(s)", got=dev)
    return {"platform": dev["platform"], "kind": dev["kind"],
            "count": dev["count"]}


# ---------------------------------------------------------------- serve


def _post(addr, body: dict, timeout: float = 300.0):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("POST", "/llm", json.dumps(body),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        if r.status != 200 or not body.get("stream"):
            return r.status, json.loads(r.read() or b"null")
        check(r.getheader("Transfer-Encoding") == "chunked",
              "asked for a stream, got one plain reply (the proxy falls "
              "back to it when the deployment refuses the stream)",
              headers=r.getheaders())
        toks, done = [], False
        for line in r:  # http.client de-chunks the NDJSON line by line
            msg = json.loads(line) if line.strip() else {}
            check("error" not in msg, "stream failed mid-way", msg=msg)
            toks.extend(msg.get("tokens", ()))
            done = done or bool(msg.get("done"))
        check(done, "stream ended without its done message", tokens=toks)
        return 200, {"tokens": toks}
    finally:
        conn.close()


def ask(addr, body: dict) -> list:
    """One request over HTTP -> its tokens. A 404 means the proxy has
    not learnt the route yet; anything else that is not 200 fails."""
    deadline = time.monotonic() + 60.0
    while True:
        status, payload = _post(addr, body)
        if status == 404 and time.monotonic() < deadline:
            time.sleep(0.5)
            continue
        check(status == 200, "request failed", status=status,
              payload=payload, body={**body, "prompt_ids": "..."})
        return [int(t) for t in payload["tokens"]]


def serve_requests(plan: Plan) -> dict:
    """The handful of requests every pool in this run answers."""
    import random

    rnd = random.Random(plan.seed)
    vocab = model_fields(plan.model_size, 0)["vocab_size"]
    long_p, short_p = ([rnd.randrange(1, vocab) for _ in range(n)]
                       for n in plan.prompt_lens)
    n = plan.max_tokens
    # In this order. The stream goes first: it is polled, so the cold
    # compiles it sets off (a prefill bucket and the decode chunk) cannot
    # run into the proxy's 120 s limit on a plain request. Greedy before
    # sampled: a spec-off engine decodes with the argmax chunk until it
    # has seen a sampled request, and both chunks should run.
    return {
        "streamed": {"prompt_ids": short_p, "max_tokens": n,
                     "stream": True},
        "greedy": {"prompt_ids": long_p, "max_tokens": n},
        "sampled": {"prompt_ids": long_p, "max_tokens": n,
                    "temperature": 1.0, "top_p": 0.9, "seed": 1234},
    }


UNJOINED_LIMIT = 0.01  # of the engine's programs' device time


def capture_check(plan: Plan, pool, addr) -> dict:
    """A short capture of the pool's one replica under two requests
    (``LLMPool.trace_replicas``): it must leave ``program_parts.json``
    beside the trace with a map for the programs the engine ran, and on
    the chip every operation on ``XLA Ops`` inside an execution of those
    programs must be found in it by name: the one join only the chip can
    check (the reader's own, ``benchmark/part_reduce.py``). -> what was
    found; fails where more than ``UNJOINED_LIMIT`` of their time is
    not."""
    import ray_tpu
    from benchmark import part_reduce, trace_reduce

    with tempfile.TemporaryDirectory(prefix="chip_smoke_trace_") as tmp:
        t0 = time.monotonic()
        tracing = pool.method("trace_replicas").remote(tmp, 3.0)
        time.sleep(0.5)
        for name in ("greedy", "sampled"):
            ask(addr, serve_requests(plan)[name])
        dirs = ray_tpu.get(tracing, timeout=600)
        took = time.monotonic() - t0
        doc = part_reduce.load_map(dirs[0])
        check(doc is not None and doc["programs"],
              "the capture left no program_parts.json, or an empty one",
              dir=dirs[0], found=doc)
        found = {"programs": {k: [v["what"] for v in vs]
                              for k, vs in doc["programs"].items()},
                 "map_s": doc["seconds"], "capture_s": round(took, 2),
                 "map_bytes": os.path.getsize(
                     os.path.join(dirs[0], part_reduce.FILE))}
        table = part_reduce.by_part(trace_reduce.load_xplane(dirs[0]), doc)
    check((table is not None) == plan.on_tpu,
          "a device plane in the capture, or none on the chip", table=table)
    if table is None:  # (a rehearsal: the CPU leaves no device plane)
        return found
    ours = {p: parts for p, parts in table["programs"].items()
            if p in doc["programs"]}
    total = sum(sum(parts.values()) for parts in ours.values())
    unjoined = sum(parts.get(part_reduce.UNJOINED, 0.0)
                   for parts in ours.values())
    found.update(device_s=round(total, 4), unjoined_s=round(unjoined, 6),
                 by_part=table["programs"], mixed_s=table["mixed_s"],
                 unscoped_ops=table["unscoped_ops"])
    check(total > 0 and unjoined <= UNJOINED_LIMIT * total,
          "operations of the engine's programs are missing from "
          "program_parts.json", **found)
    return found


def run_pool(plan: Plan, *, replicas: int, spec: bool, copies: int = 1):
    """Deploy one LLMPool, answer the requests (``copies`` of each at
    once, so that every replica of a wide pool gets some), check what
    must hold inside one pool, tear it down. -> (answers, facts)."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import accelerator
    from ray_tpu.serve.api import Deployment
    from ray_tpu.serve.llm_pool import LLMPool

    t0 = time.monotonic()
    dep = Deployment(LLMPool, max_concurrent_queries=64,
                     resources={"CPU": 0}, route_prefix="/llm")
    serve.run(dep, name="llm", init_kwargs=dict(
        model_size=plan.model_size, slots=plan.slots, max_len=plan.max_len,
        chunk_tokens=plan.chunk_tokens, prompt_buckets=plan.prompt_buckets,
        seed=plan.seed, min_replicas=replicas, max_replicas=replicas,
        prefill_workers=0, autoscale=False, chunk_delay_s=0.0,
        spec_depth=4 if spec else 0, spec_draft_layers=1 if spec else 0))
    try:
        addr = serve.start_http_proxy()
        start_s = time.monotonic() - t0
        answers: dict = {}
        first_s: dict = {}
        for name, body in serve_requests(plan).items():
            t1 = time.monotonic()
            got: list = [None] * copies
            errs: list = []

            def one(i, body=body):
                try:
                    got[i] = ask(addr, body)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    errs.append(e)

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(copies)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            if errs:
                raise errs[0]
            first_s[name] = round(time.monotonic() - t1, 2)
            check(all(len(t) == plan.max_tokens for t in got),
                  f"{name}: wrong number of tokens",
                  want=plan.max_tokens, got=[len(t) for t in got])
            check(all(t == got[0] for t in got),
                  f"{name}: replicas disagree on the same request", got=got)
            answers[name] = got[0]
        replay = ask(addr, serve_requests(plan)["sampled"])
        check(replay == answers["sampled"], "seed replay is not exact",
              first=answers["sampled"], again=replay)

        st = ray_tpu.get(serve.get_handle("llm").method("stats").remote(),
                         timeout=120)
        reps = st["per_replica"]
        check(len(reps) == replicas and all(
            "device" in r for r in reps.values()), "replica stats missing",
            got=reps)
        devices = [check_device(plan, r["device"], 1, name)
                   for name, r in reps.items()]
        check(st["device"]["platform"] == "cpu",
              "the pool process must not be on a chip", got=st["device"])
        holders = accelerator.chip_holders()
        want = {r["device"]["pid"] for r in reps.values()} \
            if plan.on_tpu else set()
        check(set(holders) == want,
              "chips are held by other processes than the decode replicas "
              "(pool, proxy and driver must hold none)",
              holders=holders, replica_pids=sorted(want))
        if plan.on_tpu:
            nodes = [tuple(r["device"]["nodes"]) for r in reps.values()]
            check(len(set(nodes)) == replicas,
                  "replicas do not hold distinct chips", nodes=nodes)
        check(all(r["total_tokens"] > 0 for r in reps.values()),
              "a replica answered nothing",
              tokens={k: r["total_tokens"] for k, r in reps.items()})
        if spec:
            check(all(r["spec"]["accepted"] > 0 for r in reps.values()),
                  "speculation accepted no draft token",
                  spec={k: r.get("spec") for k, r in reps.items()})
        facts = {
            "replicas": replicas, "spec": spec, "device": devices[0],
            **({"capture": capture_check(
                plan, serve.get_handle("llm"), addr)}
               if replicas == 1 else {}),
            "chips": sorted(r["device"]["nodes"] for r in reps.values()),
            "start_s": round(start_s, 1), "first_request_s": first_s,
            "compile": {k: r["device"]["compile"] for k, r in reps.items()},
            "peak_bytes_in_use": {k: r["device"]["peak_bytes_in_use"]
                                  for k, r in reps.items()},
            "spec_acceptance": {k: r["spec"]["acceptance_rate"]
                                for k, r in reps.items() if "spec" in r},
        }
        return answers, facts
    finally:
        # the pool's replicas are its own actors: deleting the
        # deployment would orphan them, still holding their chips
        ray_tpu.get(serve.get_handle("llm").method("shutdown").remote(),
                    timeout=120)
        serve.shutdown()
        wait_chips_free()


def check_same_answers(a: dict, b: dict, what: str) -> None:
    for name in a:
        check(a[name] == b[name], f"{what}: {name} tokens differ",
              first=a[name], second=b[name])


def agreement(a: dict, b: dict) -> dict:
    """Per request, how many leading tokens two pools agree on."""
    return {name: next((i for i, (x, y) in enumerate(zip(a[name], b[name]))
                        if x != y), len(a[name])) for name in a}


NEAR_TIE = 0.05  # logits have unit spread at the 1B widths; bf16 keeps
# 8 bits of them


def parting_margins(params, cfg, prompts, plain, spec) -> list:
    """Per prompt, where two decodes of it first part and how far the
    two tokens there lie under the best of the model's OWN logits (the
    uncached forward in f32 at the highest matmul precision, over the
    prompt and the tokens both agree on): ``{"at", "margin"}``, or
    ``None`` where they agree throughout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama

    f32 = dataclasses.replace(cfg, dtype="float32", use_flash=False,
                              remat=False)
    out: list = []
    with jax.default_matmul_precision("highest"):
        for prompt, a, b in zip(prompts, plain, spec):
            t = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                     None)
            if t is None:
                out.append(None)
                continue
            seq = np.concatenate([prompt, a[:t]]).astype(np.int32)
            lg = np.asarray(llama.forward(params, jnp.asarray(seq)[None],
                                          f32)[0, -1], np.float32)
            out.append({"at": t, "margin": float(
                lg.max() - min(lg[a[t]], lg[b[t]]))})
    return out


def spec_parting(model: dict, seed: int, slots: int, max_len: int,
                 chunk_tokens: int, bucket: int, max_tokens: int,
                 prompts: int = 4) -> dict:
    """Runs in a process that holds the device: ``prompts`` seeded
    prompts of ``bucket`` tokens through two engines on the same
    weights, speculation off (a 1-wide step) and on (depth 4, the whole
    model as its own draft, so that only the width differs), greedy.
    -> ``parting_margins`` of the two and the device."""
    import jax
    import numpy as np

    from ray_tpu._private import accelerator
    from ray_tpu.models import llama
    from ray_tpu.models.decode_engine import RaggedDecoder

    accelerator.claim_device()
    cfg = llama.LlamaConfig(**model)
    params = llama.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    asked = [rng.randint(1, cfg.vocab_size, bucket).astype(np.int32)
             for _ in range(prompts)]

    def decode(spec: bool) -> list:
        eng = RaggedDecoder(params, cfg, slots=slots, max_len=max_len,
                            chunk_tokens=chunk_tokens,
                            prompt_buckets=(bucket,),
                            spec_depth=4 if spec else 0,
                            spec_draft_layers=cfg.n_layers)
        sids = [eng.submit(p, max_tokens) for p in asked]
        eng.drain()
        return [list(eng.finished[s].tokens) for s in sids]

    return {"partings": parting_margins(params, cfg, asked, decode(False),
                                        decode(True)),
            "device": accelerator.device_report()}


def kernel_parting(model: dict, kv_heads: list, seed: int, slots: int,
                   max_len: int, chunk_tokens: int, buckets: list,
                   lens: list, max_tokens: int,
                   interpret: bool = False) -> dict:
    """Runs in a process that holds the device: for each head layout
    (``kv_heads`` under the model's query heads) seeded prompts of
    ``lens`` tokens, fewer than ``slots`` (the slots decode at different
    positions and some stay empty), through two engines on the same
    weights, greedy: the decode step's attention as the backend gives it
    (on a TPU the ``decode_attn`` kernel of ops/decode_attention.py;
    with ``interpret``, the CPU rehearsal's, the kernel in the Pallas
    interpreter) and as the XLA body over every row of the layer.
    -> per layout ``parting_margins`` of the two and how many kernel
    calls the first engine's programs were traced with, and the
    device."""
    import functools
    from unittest import mock

    import jax
    import numpy as np

    from ray_tpu._private import accelerator
    from ray_tpu.models import llama
    from ray_tpu.models.decode_engine import RaggedDecoder
    from ray_tpu.ops import decode_attention as da

    accelerator.claim_device()
    rng = np.random.RandomState(seed)
    out = {}
    for hkv in kv_heads:
        cfg = llama.LlamaConfig(**{**model, "n_kv_heads": hkv})
        params = llama.init_params(cfg, jax.random.PRNGKey(seed))
        asked = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
                 for n in lens]
        traced = []

        def counted(*a, _kernel=da._decode_attn, **kw):
            traced.append(1)
            return _kernel(*a, **kw)

        def decode(**how) -> list:
            jax.clear_caches()  # the programs are cached by their shapes,
            # not by the attention they were traced with
            with mock.patch.object(da, "decode_attention", functools.partial(
                    da.decode_attention, **how)), \
                    mock.patch.object(da, "_decode_attn", counted):
                eng = RaggedDecoder(params, cfg, slots=slots,
                                    max_len=max_len,
                                    chunk_tokens=chunk_tokens,
                                    prompt_buckets=tuple(buckets))
                sids = [eng.submit(p, max_tokens) for p in asked]
                eng.drain()
            return [list(eng.finished[s].tokens) for s in sids]

        kernel = decode(**({"interpret": True} if interpret else {}))
        calls = len(traced)
        out[f"{cfg.n_heads}/{hkv}"] = {
            "kernel_calls_traced": calls,
            "partings": parting_margins(params, cfg, asked, kernel,
                                        decode(use_kernel=False))}
    return {"layouts": out, "device": accelerator.device_report()}


def prefill_parting(model: dict, kv_heads: list, seed: int, slots: int,
                    max_len: int, buckets: list, lens: list,
                    interpret: bool = False) -> dict:
    """Runs in a process that holds the device: for each head layout
    seeded prompts of ``lens`` tokens, each padded to its bucket, through
    the engine's one-row prefill call twice on the same weights, greedy:
    attention over the prompt's rows as the backend gives it (on a TPU
    the ``flash_fwd`` kernel; with ``interpret``, the CPU rehearsal's,
    the kernel in the Pallas interpreter) and as the plain product
    (``use_flash=False``). -> per layout ``parting_margins`` of the two
    first tokens, how many kernel calls the first program was traced
    with, the largest difference between the rows the two left in the
    slot, and the device."""
    import functools
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import accelerator
    from ray_tpu.models import decode_engine as de
    from ray_tpu.models import llama_slots
    from ray_tpu.models import llama
    from ray_tpu.ops import flash_attention as fa

    accelerator.claim_device()
    rng = np.random.RandomState(seed)
    out = {}
    for hkv in kv_heads:
        cfg = llama.LlamaConfig(**{**model, "n_kv_heads": hkv})
        params = llama.serving_params(
            cfg, llama.init_params(cfg, jax.random.PRNGKey(seed)))
        asked = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
                 for n in lens]
        traced = []

        def counted(*a, _kernel=fa._flash_fwd, **kw):
            traced.append(1)
            return _kernel(*a, **kw)

        def first_tokens(cfg) -> tuple:
            toks, rows = [], []
            for slot, prompt in enumerate(asked):
                width = next(b for b in sorted(buckets) if len(prompt) <= b)
                row = np.zeros((1, width), np.int32)
                row[0, :len(prompt)] = prompt
                cache, _, tok0, *_ = de._prefill_batch_into_slots(
                    params, row, np.array([len(prompt)], np.int32),
                    np.array([slot], np.int32), np.zeros(1, np.uint32),
                    np.zeros(1, np.float32), np.ones(1, np.float32),
                    llama_slots.init_ragged_cache(cfg, slots, max_len),
                    jnp.zeros((slots,), jnp.int32), cfg)
                toks.append([int(tok0[0])])
                rows.append(np.asarray(
                    cache["k"][:, slot, :len(prompt)], np.float32))
            return toks, rows

        with mock.patch.object(fa, "_flash_fwd", counted), \
                mock.patch.object(fa, "flash_attention", functools.partial(
                    fa.flash_attention, interpret=interpret)):
            kernel, k_rows = first_tokens(dataclasses.replace(
                cfg, use_flash=True if interpret else None))
        calls = len(traced)
        plain, p_rows = first_tokens(dataclasses.replace(
            cfg, use_flash=False))
        out[f"{cfg.n_heads}/{hkv}"] = {
            "kernel_calls_traced": calls,
            "rows_max_diff": max(float(np.abs(a - b).max())
                                 for a, b in zip(k_rows, p_rows)),
            "partings": parting_margins(params, cfg, asked, kernel, plain)}
    return {"layouts": out, "device": accelerator.device_report()}


def chip_child(plan: Plan, call: str, args: dict) -> dict:
    """``chip_smoke.<call>(**args)`` in a child that holds the chip (or,
    in a rehearsal, the CPU), with the platform and chip in its
    environment as a node agent would hand them out. -> what it
    returned."""
    from ray_tpu._private import accelerator

    host_chips = accelerator.detect_tpu_chips()
    env = {**os.environ, **accelerator.worker_env(
        (0,) if plan.on_tpu else (), host_chips)}
    code = ("import json, sys, chip_smoke; print(json.dumps("
            f"chip_smoke.{call}(**json.loads(sys.argv[1]))))")
    proc = subprocess.run(
        [sys.executable, "-c", code, json.dumps(args)], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600)
    check(proc.returncode == 0, f"{call} child failed",
          stderr=proc.stderr[-3000:])
    wait_chips_free()
    return json.loads(proc.stdout.strip().splitlines()[-1])


def serve_phase(plan: Plan) -> dict:
    """Speculation on, then off from a second replica start. The two
    decode with different programs (a depth+1-wide verify against a
    1-wide step). In f32 those give the same tokens bit for bit, which
    is what the CPU rehearsal holds them to. In bf16 — what every named
    size serves in — the chip rounds the two widths differently, and
    with random weights the top two of 32,128 logits are often closer
    than that rounding. So on the chip the first token, which both pools
    take from the same prefill program, must agree, and how far the rest
    agrees is reported. What is asserted of the parting is asserted
    where the weights are at hand: a child that holds the chip decodes
    with both widths at the model's widths and one layer
    (``spec_parting``), and wherever the two part, both tokens must be
    near-ties of the model's own f32 logits: a flip inside the
    arithmetic's noise, not a leak of speculative state. (A CPU rounds
    a bf16 product once whatever its width: there the two agree in bf16
    too, tests/test_decode_spec.py.) The same is asked of the decode
    step's attention (``kernel_parting``): the ``decode_attn`` kernel,
    which sums a slot's rows block by block under a running maximum,
    against the XLA body over every row, at 16 / 8 and 16 / 16 heads
    (InternLM2's and OLMoE's layouts) with slots at different positions
    and some empty; and of the cold prefill (``prefill_parting``): its
    first token with attention over the prompt's rows through the
    ``flash_fwd`` kernel against the plain product, at the same two
    layouts, each prompt padded to its bucket."""
    on, on_facts = run_pool(plan, replicas=1, spec=True)
    off, off_facts = run_pool(plan, replicas=1, spec=False)
    agree = agreement(on, off)
    exact = model_fields(plan.model_size, 0)["dtype"] == "float32"
    check(all(n == plan.max_tokens if exact else n >= 1
              for n in agree.values()),
          "speculation on and off disagree where they must not",
          agree=agree, on=on, off=off)
    widths = chip_child(plan, "spec_parting", {
        "model": model_fields(plan.model_size, plan.max_len, n_layers=1,
                              remat=False, use_flash=False),
        "seed": plan.seed, "slots": plan.slots, "max_len": plan.max_len,
        "chunk_tokens": plan.chunk_tokens,
        "bucket": max(plan.prompt_buckets), "max_tokens": plan.max_tokens})
    check_device(plan, widths["device"], 1, "spec_parting child")
    parted = [p for p in widths["partings"] if p is not None]
    check(not parted if exact else all(
        p["margin"] < NEAR_TIE for p in parted),
        "speculation on and off part where the model's own logits do not "
        "tie", partings=widths["partings"], near_tie=NEAR_TIE)
    heads = model_fields(plan.model_size, 0)["n_heads"]
    attn_args = {
        "model": model_fields(plan.model_size, plan.max_len, n_layers=1,
                              remat=False, use_flash=False),
        "kv_heads": [heads // 2, heads], "seed": plan.seed,
        "slots": plan.slots, "max_len": plan.max_len,
        "chunk_tokens": plan.chunk_tokens,
        "buckets": list(plan.prompt_buckets),
        "lens": [*plan.prompt_lens, max(plan.prompt_lens) // 2 + 1],
        "max_tokens": plan.max_tokens, "interpret": not plan.on_tpu}
    attn = chip_child(plan, "kernel_parting", attn_args)
    check_device(plan, attn["device"], 1, "kernel_parting child")
    for layout, found in attn["layouts"].items():
        check(found["kernel_calls_traced"] > 0,
              "the decode step was traced without the decode_attn kernel",
              layout=layout)
        check(all(p is None or p["margin"] < NEAR_TIE
                  for p in found["partings"]),
              "the decode_attn kernel and the XLA body part where the "
              "model's own logits do not tie", layout=layout,
              partings=found["partings"], near_tie=NEAR_TIE)
    shape = {k: attn_args[k] for k in ("model", "kv_heads", "seed", "slots",
                                       "max_len", "buckets", "lens")}
    first = chip_child(plan, "prefill_parting", {
        **shape, "interpret": not plan.on_tpu})
    check_device(plan, first["device"], 1, "prefill_parting child")
    for layout, found in first["layouts"].items():
        check(found["kernel_calls_traced"] > 0,
              "the cold prefill was traced without the flash_fwd kernel",
              layout=layout)
        check(all(p is None or p["margin"] < NEAR_TIE
                  for p in found["partings"]),
              "the prefill's first token through the flash kernel and "
              "through the plain product part where the model's own logits "
              "do not tie", layout=layout, partings=found["partings"],
              near_tie=NEAR_TIE)
    warm = next(iter(off_facts["compile"].values()))
    if warm["requests"]:  # the persistent cache is on in this run
        check(warm["hits"] > 0,
              "the second replica start found nothing in the compile cache",
              compile=off_facts["compile"])
    cold = next(iter(on_facts["compile"].values()))
    return {"device": on_facts["device"], "tokens_per_request":
            plan.max_tokens, "seed_replay_exact": True,
            "spec_on_vs_off_agreeing_tokens": agree,
            "spec_on_vs_off_partings": widths["partings"],
            "decode_attn_vs_xla_body": attn["layouts"],
            "prefill_flash_vs_product": first["layouts"],
            "near_tie": NEAR_TIE,
            "compile_s": cold["seconds"], "compile_s_second_start":
            warm["seconds"], "spec_on": on_facts, "spec_off": off_facts}


def pool4_phase(plan: Plan) -> dict:
    """Four decode replicas, each its own process on its own chip,
    answer the same seeded requests with the tokens one replica gives."""
    one, one_facts = run_pool(plan, replicas=1, spec=False)
    four, four_facts = run_pool(plan, replicas=4, spec=False, copies=8)
    check_same_answers(one, four, "one replica vs four")
    return {"device": four_facts["device"], "distinct_chips":
            four_facts["chips"], "tokens_equal_one_replica": True,
            "compile_s": next(iter(one_facts["compile"].values()))["seconds"],
            "one_replica": one_facts, "four_replicas": four_facts}


# ---------------------------------------------------------------- train


def _train_loop(config: dict) -> None:
    """Runs in the train worker (shipped by value): the 1B recipe on
    the mesh the config names, a few steps on one seeded batch."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import accelerator
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshConfig, build_mesh, use_mesh
    from ray_tpu.train import (batch_sharding, init_train_state,
                               make_train_step, session)
    from ray_tpu.train.optim import fused_adamw

    cfg = llama.LlamaConfig(**config["model"])
    mesh = build_mesh(MeshConfig(**config["mesh"]), jax.devices())
    opt = fused_adamw(1e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16,
                      nu_dtype=jnp.bfloat16)
    state, state_sh = init_train_state(
        lambda k: llama.init_params(cfg, k), llama.param_logical_axes(cfg),
        opt, mesh, key=jax.random.PRNGKey(config["seed"]))
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh, state_sh,
        compute_grad_norm=False, grads_dtype=jnp.bfloat16)
    toks = np.random.RandomState(config["seed"]).randint(
        0, cfg.vocab_size, (config["batch"], config["seq"] + 1)
    ).astype(np.int32)
    losses, step_s = [], []
    with use_mesh(mesh):
        data = jax.device_put(
            {"inputs": toks[:, :-1], "targets": toks[:, 1:]},
            batch_sharding(mesh))
        t0 = time.monotonic()
        compiled = step.lower(state, data).compile()
        compile_s = time.monotonic() - t0
        text = compiled.as_text()
        for _ in range(config["steps"]):
            t0 = time.monotonic()
            state, metrics = compiled(state, data)
            losses.append(float(metrics["loss"]))  # waits for the device
            step_s.append(time.monotonic() - t0)
    session.report({
        "losses": losses, "step_s": step_s, "compile_s": compile_s,
        "has_kernel": "tpu_custom_call" in text,
        "collectives": {op: text.count(op) for op in (
            "all-reduce(", "all-gather(", "reduce-scatter(")},
        "device": accelerator.device_report(),
        "params_bytes_per_device": [
            sum(s.data.nbytes for leaf in jax.tree.leaves(state.params)
                for s in leaf.addressable_shards if s.device == d)
            for d in jax.local_devices()],
    })


def run_trainer(plan: Plan, *, chips: int, mesh: dict, batch: int) -> dict:
    """One JaxTrainer run on a worker that owns ``chips`` chips."""
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    res = {"CPU": 1, **({"TPU": chips} if plan.on_tpu else {})}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_train_") as tmp:
        result = JaxTrainer(
            _train_loop,
            train_loop_config={
                "model": model_fields(
                    plan.model_size, plan.seq, remat=True,
                    remat_policy="flash_qkv"),
                "mesh": mesh, "seed": plan.seed, "batch": batch,
                "seq": plan.seq, "steps": plan.steps},
            scaling_config=ScalingConfig(
                num_workers=1, resources_per_worker=res,
                platform=plan.platform,
                # the CPU rehearsal widens its worker to a virtual mesh
                devices_per_worker=None if plan.on_tpu else chips),
            run_config=RunConfig(name="chip_smoke", storage_path=tmp),
        ).fit()
    wait_chips_free()
    out = dict(result.metrics)
    losses = out["losses"]
    check(all(x == x and abs(x) < 1e4 for x in losses)
          and losses[-1] < losses[0], "loss is not finite and falling",
          losses=losses)
    check(out["has_kernel"] == plan.on_tpu,
          "flash kernel in the compiled step: expected on the TPU only",
          has_kernel=out["has_kernel"])
    out["device_summary"] = check_device(plan, out["device"], chips,
                                         "train worker")
    return out


def _train_facts(out: dict, batch: int) -> dict:
    return {"batch": batch, "losses": out["losses"],
            "compile_s": round(out["compile_s"], 1),
            "compile_cache": out["device"]["compile"],
            "step_s_median": round(statistics.median(out["step_s"][1:]), 4),
            "peak_bytes_in_use": out["device"]["peak_bytes_in_use"],
            "kernel_in_step": out["has_kernel"]}


def train_phase(plan: Plan) -> dict:
    """The 1B recipe (``model_fields``). The compiler puts the b2 step at 16,352 MiB
    of a 16 GB chip; if the chip refuses it the batch is cut to 1, never
    a width, the depth or the vocabulary, and the cut is reported."""
    from ray_tpu.train.backend_executor import TrainingFailedError

    batch = plan.batch
    try:
        out = run_trainer(plan, chips=1, mesh={}, batch=batch)
    except TrainingFailedError as e:
        if "RESOURCE_EXHAUSTED" not in str(e) or batch == 1:
            raise
        print(f"train: batch {batch} does not fit the chip, cutting to 1",
              file=sys.stderr, flush=True)
        wait_chips_free()
        batch = 1
        out = run_trainer(plan, chips=1, mesh={}, batch=batch)
    return {"device": out["device_summary"], **_train_facts(out, batch),
            "batch_cut_to_fit": batch != plan.batch}


LOSS_TOLERANCE = 0.05  # |loss(4 chips) - loss(1 chip)|, bf16 compute


def train4_phase(plan: Plan) -> dict:
    """The same seeded global batch on one chip and on an fsdp=2 x tp=2
    mesh over four: losses agree, the kernel is in the sharded step, and
    all four devices hold parameters."""
    one = run_trainer(plan, chips=1, mesh={}, batch=plan.batch)
    four = run_trainer(plan, chips=4, mesh={"fsdp": 2, "tp": 2},
                       batch=plan.batch)
    diffs = [abs(a - b) for a, b in zip(one["losses"], four["losses"])]
    check(max(diffs) <= LOSS_TOLERANCE, "four-chip losses leave the "
          "one-chip losses", one=one["losses"], four=four["losses"],
          tolerance=LOSS_TOLERANCE)
    held = four["params_bytes_per_device"]
    check(len(held) == 4 and min(held) > 0 and max(held) < sum(held) / 2,
          "parameters are not spread over the four devices", held=held)
    check(four["collectives"]["all-reduce("] > 0
          and four["collectives"]["all-gather("] > 0,
          "the sharded step has no collectives", got=four["collectives"])
    return {"device": four["device_summary"], "mesh": "fsdp=2 x tp=2",
            "max_loss_diff": max(diffs), "loss_tolerance": LOSS_TOLERANCE,
            "params_bytes_per_device": held,
            "collectives": four["collectives"],
            "one_chip": _train_facts(one, plan.batch),
            "four_chips": _train_facts(four, plan.batch),
            "compile_s": round(four["compile_s"], 1)}


# -------------------------------------------------------------- kernels

FLASH_TOLERANCE = 3e-2  # max |flash - reference| over max |reference|


PREFILL_ROWS = (256, 512, 1024)  # the doc cells' buckets
MXU_FLOPS = 197e12  # a v5e's bf16 matrix unit (benchmark/peaks.json)


def flash_prefill_ms(hq: int, hkv: int, d: int, interpret: bool,
                     layers: int = 24) -> dict:
    """The flash forward as a cold prefill calls it: batch 1, a bucket's
    rows, ``layers`` calls in one program, each taking the last one's
    output for its queries as a model's layers follow each other. -> per
    bucket the best of five runs in ms a call beside the time of the
    call's causal FLOPs (2 x P^2 x Hq x d: under 2,048 rows the kernel
    takes a row's keys in one pass and multiplies twice that) at the
    matrix unit's peak. A rehearsal times the interpreter: no speed."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    out = {}
    for rows in PREFILL_ROWS:
        q, k, v = (jax.random.normal(key, (1, rows, h, d), jnp.bfloat16)
                   for key, h in zip(jax.random.split(jax.random.PRNGKey(1),
                                                      3), (hq, hkv, hkv)))

        @jax.jit
        def calls(q, k, v):
            return jax.lax.fori_loop(0, layers, lambda _, o: flash_attention(
                o, k, v, causal=True, interpret=interpret), q)

        jax.block_until_ready(calls(q, k, v))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(calls(q, k, v))
            best = min(best, time.perf_counter() - t0)
        out[str(rows)] = {
            "ms": round(1e3 * best / layers, 4),
            "causal_flops_ms": round(
                1e3 * 2 * rows * rows * hq * d / MXU_FLOPS, 4)}
    return out


def kernels_check(shape: list, model: dict, batch: int, seq: int,
                  interpret: bool) -> dict:
    """Runs in a child that holds the chip: flash forward and backward
    against ``attention_reference`` on the same device, and the compiled
    forward of the model takes the kernel (``use_flash=None``: the
    dispatch decides from the backend it finds)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import llama
    from ray_tpu.ops.attention import attention_reference
    from ray_tpu.ops.flash_attention import flash_attention

    accelerator.claim_device()
    b, t, hq, hkv, d = shape
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (b, t, hq, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, t, hkv, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, t, hkv, d), jnp.bfloat16)
    w = jax.random.normal(kw, (b, t, hq, d), jnp.float32)

    def outputs(fn):
        def f(q, k, v):
            o = fn(q, k, v).astype(jnp.float32)
            return (o * w).sum(), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o, *grads)

    got = outputs(functools.partial(flash_attention, causal=True,
                                    interpret=interpret))
    want = outputs(functools.partial(attention_reference, causal=True))
    errs = {}
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        g, r = g.astype(jnp.float32), r.astype(jnp.float32)
        errs[name] = float(jnp.max(jnp.abs(g - r)) / jnp.max(jnp.abs(r)))

    cfg = llama.LlamaConfig(**model)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    t0 = time.monotonic()
    text = jax.jit(lambda p, x: llama.forward(p, x, cfg)).lower(
        params, tokens).compile().as_text()
    return {"rel_err": errs, "forward_has_kernel": KERNEL in text,
            "forward_compile_s": round(time.monotonic() - t0, 1),
            "prefill_ms": flash_prefill_ms(
                hq, hkv, d, interpret, layers=2 if interpret else 24),
            "device": accelerator.device_report()}


def kernels_phase(plan: Plan) -> dict:
    out = chip_child(plan, "kernels_check", {
        "shape": list(plan.flash_shape), "batch": plan.batch,
        "seq": plan.seq, "interpret": not plan.on_tpu,
        "model": model_fields(plan.model_size, plan.seq, remat=True,
                              remat_policy="flash_qkv")})
    check(max(out["rel_err"].values()) <= FLASH_TOLERANCE,
          "flash kernel leaves the reference", got=out["rel_err"],
          tolerance=FLASH_TOLERANCE)
    check(out["forward_has_kernel"] == plan.on_tpu,
          "flash kernel in the compiled forward: expected on the TPU only",
          got=out["forward_has_kernel"])
    return {"device": check_device(plan, out["device"], 1, "kernels child"),
            "flash_shape": list(plan.flash_shape),
            "rel_err_vs_reference": out["rel_err"],
            "tolerance": FLASH_TOLERANCE,
            "kernel_in_forward": out["forward_has_kernel"],
            "flash_fwd_ms_at_batch_1": out["prefill_ms"],
            "compile_s": out["device"]["compile"]["seconds"]}


# A layer's two forms against each other on the chip, bf16: largest
# difference over the largest value. Readings at the published widths,
# prompts of 65, 200 and 700 tokens (my chip run, PR 32): KDA outputs
# 0.0030-0.0033, state 0.00004-0.00016, convolution rows 0.0005-0.0009
# (a projection of one row and of T rows rounds differently on the
# chip; the CPU reads 0); MLA outputs 0.0034-0.0078, rows up to 0.0024.
# Four times the largest; a form that drops a norm or a decay reads 0.3
# and more.
HYBRID_TOLERANCE = 0.03


def hybrid_check(widths: str, lens: list, seed: int) -> dict:
    """Runs in a child that holds the chip: one KDA layer's chunkwise
    prefill against its own stepping (outputs, the final state S and the
    convolution rows), and one MLA layer's unabsorbed prefill against
    the absorbed decode step over the latent rows, in the compute type,
    for prompts of ``lens`` tokens. -> relative errors by length."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import ling

    accelerator.claim_device()
    # layers 0-4 KDA, layer 5 MLA; dense MLPs only (they are not run)
    kw = dict(n_layers=6, first_k_dense=6, vocab_size=1024)
    cfg = ling.LingConfig.tiny(**kw, dtype="bfloat16") if widths == "tiny" \
        else ling.LingConfig(**kw)
    layers = ling.init_params(cfg, jax.random.PRNGKey(seed))["layers"]
    kda, mla = layers[0]["attn"], layers[5]["attn"]

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    @jax.jit
    def both(x):
        t = x.shape[1]
        xs = jnp.moveaxis(x, 1, 0)[:, :, None]  # [T, 1, 1, D]
        on = jnp.ones((1,), bool)
        y_kda, st = ling.kda_prefill(cfg, kda, x, jnp.array([t]))
        zero = jax.tree_util.tree_map(jnp.zeros_like, st)
        st_step, y_kda_step = jax.lax.scan(
            lambda s, x_t: ling.kda_step(cfg, kda, x_t, s, on)[::-1],
            zero, xs)
        y_mla, rows = ling.mla_prefill(cfg, mla, x)
        cache, y_mla_step = jax.lax.scan(
            lambda c, xp: ling.mla_step(cfg, mla, xp[0], c, xp[1])[::-1],
            jax.tree_util.tree_map(jnp.zeros_like, rows),
            (xs, jnp.arange(t)[:, None]))
        rows, cache = (jnp.concatenate([c["latent"], c["k_rope"]], -1)
                       for c in (rows, cache))
        return {
            "kda_out": (y_kda, jnp.moveaxis(y_kda_step[:, :, 0], 0, 1)),
            "kda_state": (st["s"], st_step["s"]),
            "kda_conv": (st["conv"], st_step["conv"]),
            "mla_out": (y_mla, jnp.moveaxis(y_mla_step[:, :, 0], 0, 1)),
            "mla_rows": (rows, cache)}

    errs = {}
    for t in lens:
        x = jax.random.normal(jax.random.PRNGKey(seed + t),
                              (1, t, cfg.d_model), cfg.compute_dtype)
        errs[str(t)] = {k: rel(a, b) for k, (a, b) in both(x).items()}
    return {"rel_err": errs, "device": accelerator.device_report()}


# The kernel computes the XLA body's float32 lines in another order of
# sums at most: PR 41's chip runs read 0.0 for state and output.
KDA_KERNEL_TOLERANCE = 1e-6


def kda_kernel_check(widths: str, steps: int, seed: int,
                     interpret: bool = False) -> dict:
    """Runs in a child that holds the chip: one KDA layer's decode step
    for ``steps`` tokens over six slots of which two are not active, the
    recurrence as the backend gives it (on a TPU at the published widths
    the ``kda_step`` kernel, in place; with ``interpret`` the kernel in
    the Pallas interpreter) against the XLA body on the same inputs.
    -> the largest relative error of the state and of the outputs,
    whether the inactive slots' state came back bit for bit
    (``inactive_kept``), and whether the layer's own step
    (``ling.kda_step``) compiles to a program with the kernel in it
    (``in_program``: the backend's and the shape's choice)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import ling
    from ray_tpu.ops import kda_step as ks

    accelerator.claim_device()
    kw = dict(n_layers=1, first_k_dense=1, vocab_size=1024)
    cfg = ling.LingConfig.tiny(**kw, dtype="bfloat16") if widths == "tiny" \
        else ling.LingConfig(**kw)
    p = ling.init_params(cfg, jax.random.PRNGKey(seed))["layers"][0]["attn"]
    slots, h, dk = 6, cfg.n_heads, cfg.kda_head_dim
    active = jnp.arange(slots) % 3 != 1
    kernel = functools.partial(ks.kda_step, **(
        {"interpret": True} if interpret else {}))
    k_s, k_x = jax.random.split(jax.random.PRNGKey(seed + 1))
    s0 = jax.random.normal(k_s, (slots, h, dk, dk), jnp.float32)
    conv0 = jnp.zeros((slots, cfg.conv_kernel - 1, 3 * h * dk),
                      cfg.compute_dtype)
    xs = jax.random.normal(k_x, (steps, slots, 1, cfg.d_model),
                           cfg.compute_dtype)

    @jax.jit
    def both(s0, xs):
        def one(carry, x_t):
            s_kernel, s_body, conv = carry
            q, k, v, g, beta, _, proj = ling._kda_inputs(cfg, p, x_t, conv)
            vectors = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                       active)
            s_kernel, o_kernel = kernel(s_kernel, *vectors)
            s_body, o_body = ks.kda_step(s_body, *vectors, use_kernel=False)
            conv = jnp.concatenate([conv, proj], axis=1)[:, 1:]
            return (s_kernel, s_body, conv), (o_kernel, o_body)

        (s_kernel, s_body, _), (o_kernel, o_body) = jax.lax.scan(
            one, (s0, s0, conv0), xs)
        return s_kernel, s_body, o_kernel, o_body

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    s_kernel, s_body, o_kernel, o_body = both(s0, xs)
    state = {"s": s0, "conv": conv0}
    text = jax.jit(functools.partial(ling.kda_step, cfg, p)).lower(
        xs[0], state, active).compile().as_text()
    return {"rel_err": {"state": rel(s_kernel, s_body),
                        "out": rel(o_kernel, o_body)},
            "inactive_kept": bool(jnp.array_equal(s_kernel[~active],
                                                  s0[~active])),
            "moved": rel(s_kernel[active], s0[active]),
            "in_program": any(
                KERNEL in line and "kda_step" in line.split(" = ")[0]
                for line in text.splitlines()),
            "steps": steps, "device": accelerator.device_report()}


# Kernel and XLA body are the same float32 sums in another order, and
# both stray from the recurrence a token at a time by its own rounding
# (my chip run, PR 47, 4,096 rows at 64 heads in two calls: 7e-7 / 2e-7
# between the two for o / S, 4e-6 / 4e-6 from the recurrence for either).
KDA_CHUNK_TOLERANCE = 1e-4


def kda_chunk_check(widths: str, rows: int, seed: int,
                    interpret: bool = False) -> dict:
    """Runs in a child that holds the chip: the chunkwise delta rule
    over ``rows`` rows of one KDA layer of ``models/solar.py`` (64 heads
    at the published widths, beta to 2, a decay without a lower bound;
    q, k, v, g, beta as the layer's own ``_kda_inputs`` makes them) in
    two calls with ``S`` carried, as the backend gives it (on a TPU the
    ``kda_chunk`` kernel; with ``interpret`` the kernel in the Pallas
    interpreter), against the XLA body in the same two calls and
    against the recurrence a token at a time. -> the largest relative
    errors of o and S between each two of the three, whether the
    layer's own segment (``solar.kda_segment``) compiles to a program
    with the kernel in it (``in_program``: the backend's and the
    shape's choice), and the same two facts of what makes q, k, v and g
    (``inputs_rel_err``: ``ops.kda_inputs`` as the backend gives it, on
    a TPU the ``kda_inputs`` kernel, against its XLA body;
    ``inputs_in_program``)."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import solar
    from ray_tpu.ops import kda_chunk as kc
    from ray_tpu.ops.kda_step import kda_recurrence

    accelerator.claim_device()
    kw = dict(n_layers=2, vocab_size=1024, n_experts=8, top_k=2)
    cfg = solar.SolarConfig.tiny(**kw, dtype="bfloat16") \
        if widths == "tiny" else solar.SolarConfig(**kw)
    p = solar.init_params(cfg, jax.random.PRNGKey(seed))["layers"][1]["attn"]
    state = solar.kda_empty(cfg, 1)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (1, rows, cfg.d_model), cfg.compute_dtype)
    kernel = functools.partial(kc.kda_chunk, chunk=cfg.kda_chunk, **(
        {"interpret": True} if interpret else {}))
    body = functools.partial(kc.kda_chunked, chunk=cfg.kda_chunk)

    @jax.jit
    def three(x):
        *qkvgb, _ = solar._kda_inputs(cfg, p, x, state["conv"])
        half = rows // 2
        found = {}
        for name, form in (("kernel", kernel), ("body", body)):
            o1, s = form(*(a[:, :half] for a in qkvgb), state["s"])
            o2, s = form(*(a[:, half:] for a in qkvgb), s)
            found[name] = (jnp.concatenate([o1, o2], axis=1), s)
        s, o = jax.lax.scan(lambda s, xs: kda_recurrence(s, *xs), state["s"],
                            tuple(jnp.moveaxis(a, 1, 0) for a in qkvgb))
        found["recurrence"] = (jnp.moveaxis(o, 0, 1), s)
        return found

    def rel(a, b):
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    found = three(x)

    def made():  # (jitted anew: the dispatch is read when it is traced)
        return jax.jit(lambda x: solar._kda_inputs(
            cfg, p, x, state["conv"])[:4])(x)

    given = made()
    as_given = solar._kda_qkvg
    solar._kda_qkvg = functools.partial(as_given, use_kernel=False)
    try:
        from_body = made()
    finally:
        solar._kda_qkvg = as_given
    text = jax.jit(functools.partial(solar.kda_segment, cfg, p)).lower(
        x[:, :rows // 2], state, 0, jnp.array([rows])).compile().as_text()

    def in_program(kernel):
        return any(KERNEL in line and kernel in line.split(" = ")[0]
                   for line in text.splitlines())

    return {"rel_err": {
                f"{a}_{b}": {"out": rel(found[a][0], found[b][0]),
                             "state": rel(found[a][1], found[b][1])}
                for a, b in (("kernel", "body"), ("kernel", "recurrence"),
                             ("body", "recurrence"))},
            "in_program": in_program("kda_chunk"),
            "inputs_rel_err": max(rel(a, b)
                                  for a, b in zip(given, from_body)),
            "inputs_in_program": in_program("kda_inputs"),
            "rows": rows, "heads": cfg.n_heads,
            "device": accelerator.device_report()}


def ring_check(widths: str, steps: int, seed: int,
               interpret: bool = False) -> dict:
    """Runs in a child that holds the chip: a sliding-window layer's
    ring (``models/exaone.py``: ``window`` rows a slot, written at
    ``pos % window``) filled for ``steps`` positions, past two wraps,
    four slots that start at different positions and a fifth inactive;
    at every step the decode step's attention over the ring as the
    backend gives it (on a TPU the ``decode_attn`` kernel on the
    128-row stack, lengths ``min(pos + 1, window)``; with ``interpret``
    the kernel in the Pallas interpreter) against the XLA body over the
    same rows. -> the largest relative error over the steps, and how
    many wraps the first slot made."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import exaone
    from ray_tpu.ops.decode_attention import decode_attention

    accelerator.claim_device()
    cfg = exaone.ExaoneConfig.tiny(dtype="bfloat16") if widths == "tiny" \
        else exaone.ExaoneConfig()
    w, slots, layer = cfg.sliding_window, 5, 1
    start = jnp.array([0, 3, w - 1, w + 5, 0], jnp.int32)
    active = jnp.array([True, True, True, True, False])
    ring = jnp.zeros((2, slots, w, cfg.kv_width), cfg.compute_dtype)
    kernel = functools.partial(decode_attention, **(
        {"interpret": True} if interpret else {}))

    def one(carry, key):
        k_ring, v_ring, pos = carry
        q, k, v = (jax.random.normal(kk, (slots, 1, h, cfg.head_dim),
                                     cfg.compute_dtype)
                   for kk, h in zip(jax.random.split(key, 3), (
                       cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads)))
        at = (layer, jnp.arange(slots), pos % w)
        k_ring = k_ring.at[at].set(k.reshape(slots, -1))
        v_ring = v_ring.at[at].set(v.reshape(slots, -1))
        lengths = jnp.where(active, jnp.minimum(pos + 1, w), 0)
        got = kernel(q, k_ring, v_ring, layer, lengths)
        want = decode_attention(q, k_ring, v_ring, layer, lengths,
                                use_kernel=False)
        err = jnp.max(jnp.abs(got.astype(jnp.float32)
                              - want.astype(jnp.float32)))
        return (k_ring, v_ring, pos + active), (
            err, jnp.max(jnp.abs(want.astype(jnp.float32))))

    _, (errs, sizes) = jax.lax.scan(
        one, (ring, ring, start), jax.random.split(
            jax.random.PRNGKey(seed), steps))
    return {"rel_err": float(jnp.max(errs) / jnp.max(sizes)),
            "wraps": steps // w, "window": w,
            "device": accelerator.device_report()}


def latent_check(widths: str, lens: list, seed: int) -> dict:
    """Runs in a child that holds the chip: one gated MLA layer of
    ``models/instella.py`` (YaRN's rotary on interleaved pairs), its
    unabsorbed prefill (the serving call: forward-only flash on a TPU)
    against its absorbed decode step over the slot's rows (on a TPU the
    ``decode_attn_latent`` kernel, lengths ``pos + 1``), position by
    position, in the compute type, for prompts of ``lens`` tokens.
    -> relative errors of the outputs and of the rows by length."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import instella
    from ray_tpu.ops import decode_attention as da

    accelerator.claim_device()
    kw = dict(n_layers=1, first_k_dense=1, vocab_size=1024)
    cfg = instella.InstellaConfig.tiny(**kw, dtype="bfloat16") \
        if widths == "tiny" else instella.InstellaConfig(**kw)
    p = instella.init_params(cfg, jax.random.PRNGKey(seed))[
        "layers"][0]["attn"]

    @jax.jit
    def both(x):
        t = x.shape[1]
        y, rows = instella.mla_prefill(
            cfg, p, x, instella._rotation(cfg, jnp.arange(t)[None]))

        def one(cache, xp):
            x_t, pos = xp
            lengths = (pos + 1).astype(jnp.int32)
            plan = da.visits(lengths, t, da.block_rows(t, da.LATENT_BLOCK_ROWS))
            y_t, cache = instella.mla_step(
                cfg, p, x_t, instella._rotation(cfg, pos[:, None]), cache,
                0, pos, lengths, plan)
            return cache, y_t

        cache, y_step = jax.lax.scan(
            one, jnp.zeros((1, *rows.shape), rows.dtype),
            (jnp.moveaxis(x, 1, 0)[:, :, None], jnp.arange(t)[:, None]))
        return {"latent_out": (y, jnp.moveaxis(y_step[:, :, 0], 0, 1)),
                "latent_rows": (rows, cache[0])}

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    errs = {}
    for t in lens:
        x = jax.random.normal(jax.random.PRNGKey(seed + t),
                              (1, t, cfg.d_model), cfg.compute_dtype)
        errs[str(t)] = {k: rel(a, b) for k, (a, b) in both(x).items()}
    return {"rel_err": errs, "device": accelerator.device_report()}


def segment_check(widths: str, lens: list, seed: int) -> dict:
    """Runs in a child that holds the chip: the fifth block's two kinds
    of layer (``models/solar.py``) at the published widths, each form
    against the other, in the compute type, for prompts of ``lens``
    tokens: a KDA layer's prefill in row segments, ``S`` and the
    convolution rows carried (2,048 rows a segment), against its own
    stepping (on a TPU the ``kda_step`` kernel at 64 heads, beta to 2);
    a gated GQA layer without positions through ``attend_bucket`` (the
    serving prefill's forward-only flash call on a TPU) against its
    decode step over the slot's rows
    (``decode_attn``, lengths ``pos + 1``). -> relative errors by
    length, and how many segments each length ran in."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import moe, solar
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops.attention import attend_bucket

    accelerator.claim_device()
    kw = dict(n_layers=2, vocab_size=1024, n_experts=8, top_k=2)
    cfg = solar.SolarConfig.tiny(**kw, dtype="bfloat16") \
        if widths == "tiny" else solar.SolarConfig(**kw)
    gqa, kda = (layer["attn"] for layer in solar.init_params(
        cfg, jax.random.PRNGKey(seed))["layers"])

    @jax.jit
    def both(x):
        t = x.shape[1]
        xs = jnp.moveaxis(x, 1, 0)[:, :, None]  # [T, 1, 1, D]
        on, lens_ = jnp.ones((1,), bool), jnp.array([t])
        empty = solar.kda_empty(cfg, 1)
        st, y_kda = moe.in_segments(
            lambda state, seg: solar.kda_segment(
                cfg, kda, seg[1], state, seg[0], lens_)[::-1],
            empty, x, moe.segment_rows(t, cfg.kda_chunk))
        st_step, y_kda_step = jax.lax.scan(
            lambda s, x_t: solar.kda_step(cfg, kda, x_t, s, on)[::-1],
            empty, xs)
        q, k, v = solar._qkv(cfg, gqa, x)
        y_gqa = solar._gqa_out(cfg, gqa, x, attend_bucket(q, k, v))

        def one(cache, xp):
            x_t, pos = xp
            q, k, v = solar._qkv(cfg, gqa, x_t)
            kc, vc = (c.at[0, 0, pos[0]].set(r.reshape(-1))
                      for c, r in zip(cache, (k, v)))
            o = da.decode_attention(q, kc, vc, 0, (pos + 1).astype(jnp.int32))
            return (kc, vc), solar._gqa_out(cfg, gqa, x_t, o)

        rows = jnp.zeros((1, 1, t, cfg.kv_width), cfg.compute_dtype)
        (kc, _), y_gqa_step = jax.lax.scan(
            one, (rows, rows), (xs, jnp.arange(t)[:, None]))
        return {"kda_out": (y_kda, jnp.moveaxis(y_kda_step[:, :, 0], 0, 1)),
                "kda_state": (st["s"], st_step["s"]),
                "kda_conv": (st["conv"], st_step["conv"]),
                "gqa_out": (y_gqa, jnp.moveaxis(y_gqa_step[:, :, 0], 0, 1)),
                "gqa_rows": (k.reshape(1, t, -1), kc[0])}

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    errs = {}
    for t in lens:
        x = jax.random.normal(jax.random.PRNGKey(seed + t),
                              (1, t, cfg.d_model), cfg.compute_dtype)
        errs[str(t)] = {k: rel(a, b) for k, (a, b) in both(x).items()}
    return {"rel_err": errs, "device": accelerator.device_report(),
            "segments": {str(t): solar.SLOTS.prefill_segments(cfg, t)
                         for t in lens}}


# Kernel and XLA body are the same float32 arithmetic; the output's sum
# over the state's 128 lanes is taken in another order.
SSD_KERNEL_TOLERANCE = 1e-5


def ssm_check(widths: str, lens: list, seed: int,
              interpret: bool = False, block: str = "granite") -> dict:
    """Runs in a child that holds the chip: one Mamba-2 layer of the
    seventh block (``models/granite.py``: 128 heads, one group) or with
    ``block="nemotron"`` of the twelfth (``models/nemotron.py``: 64
    heads whose B and C come in eight groups, the gated norm by group;
    the mixer's functions are the seventh block's) at the published
    widths, in the compute type, for prompts of ``lens`` tokens: its
    prefill in row
    segments, ``H`` and the convolution rows carried (the chunked scan,
    ``ops/ssd_chunk.py``), against its own stepping (on a TPU the
    ``ssd_step`` kernel in place; with ``interpret`` the kernel in the
    Pallas interpreter); and six slots' step through the kernel against
    the XLA body with two slots inactive. -> relative errors by length,
    the kernel's against the body, whether the inactive slots' state
    came back bit for bit, whether the layer's step compiles to a
    program with the kernel in it, and each length's segments."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import granite, moe
    from ray_tpu.ops import ssd_step as ss

    accelerator.claim_device()
    if block == "nemotron":
        from ray_tpu.models import nemotron

        kw = dict(pattern="M", vocab_size=1024, n_experts=8, top_k=2)
        cfg = nemotron.NemotronConfig.tiny(**kw, dtype="bfloat16") \
            if widths == "tiny" else nemotron.NemotronConfig(**kw)
        p = nemotron.init_params(cfg, jax.random.PRNGKey(seed))["layers"][
            0]["mix"]
    else:
        kw = dict(n_layers=1, layer_types=("mamba",), vocab_size=1024,
                  n_experts=8, top_k=2)
        cfg = granite.GraniteConfig.tiny(**kw, dtype="bfloat16") \
            if widths == "tiny" else granite.GraniteConfig(**kw)
        p = granite.init_params(cfg, jax.random.PRNGKey(seed))["layers"][0][
            "attn"]
    kernel = functools.partial(ss.ssd_step, **(
        {"interpret": True} if interpret else {}))

    @jax.jit
    def both(x):
        t = x.shape[1]
        xs = jnp.moveaxis(x, 1, 0)[:, :, None]  # [T, 1, 1, D]
        on, lens_ = jnp.ones((1,), bool), jnp.array([t])
        empty = granite.ssm_empty(cfg, 1)
        st, y = moe.in_segments(
            lambda state, seg: granite.ssm_segment(
                cfg, p, seg[1], state, seg[0], lens_)[::-1],
            empty, x, moe.segment_rows(t, cfg.ssm_chunk))
        st_step, y_step = jax.lax.scan(
            lambda s, x_t: granite.ssm_step(cfg, p, x_t, s, on)[::-1],
            empty, xs)
        return {"out": (y, jnp.moveaxis(y_step[:, :, 0], 0, 1)),
                "state": (st["h"], st_step["h"]),
                "conv": (st["conv"], st_step["conv"])}

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    errs = {}
    for t in lens:
        x = jax.random.normal(jax.random.PRNGKey(seed + t),
                              (1, t, cfg.d_model), cfg.compute_dtype)
        errs[str(t)] = {k: rel(a, b) for k, (a, b) in both(x).items()}
    slots, h, hd, n = 6, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    active = jnp.arange(slots) % 3 != 1
    key = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    h0 = ss.pack(jax.random.normal(key[0], (slots, h, hd, n), jnp.float32))
    vectors = (0.1 * jax.random.normal(key[1], (slots, h, hd)),
               jax.random.uniform(key[2], (slots, h)),
               jax.random.normal(key[3], (slots, cfg.ssm_groups, n)),
               jax.random.normal(key[4], (slots, cfg.ssm_groups, n)), active)
    h_kernel, y_kernel = jax.jit(kernel)(h0, *vectors)
    h_body, y_body = ss.ssd_step(h0, *vectors, use_kernel=False)
    state = granite.ssm_empty(cfg, slots)
    text = jax.jit(functools.partial(granite.ssm_step, cfg, p)).lower(
        jnp.zeros((slots, 1, cfg.d_model), cfg.compute_dtype), state,
        active).compile().as_text()
    return {"rel_err": errs,
            "kernel": {"state": rel(h_kernel, h_body),
                       "out": rel(y_kernel, y_body)},
            "inactive_kept": bool(jnp.array_equal(h_kernel[~active],
                                                  h0[~active])),
            "in_program": any(
                KERNEL in line and "ssd_step" in line.split(" = ")[0]
                for line in text.splitlines()),
            "segments": {str(t): cfg.slot_model.prefill_segments(cfg, t)
                         for t in lens},
            "groups": cfg.ssm_groups,
            "device": accelerator.device_report()}


def sconv_check(widths: str, lens: list, seed: int,
                interpret: bool = False) -> dict:
    """Runs in a child that holds the chip: the eleventh block's two
    kinds of layer (``models/lfm2.py``) at the published widths, each
    form against the other, in the compute type, for prompts of ``lens``
    tokens: a gated short convolution's prefill in row segments, the
    two rows of ``u`` carried (2,048 rows a segment), against its own
    stepping; a GQA layer of 64-wide heads with its head norms and
    rotation through ``ops.attention.attend_rows`` in segments at an
    offset (``flash_fwd`` on a TPU) against its decode step over the
    slot's rows (on a TPU the ``decode_attn`` kernel, two heads a lane
    tile; with ``interpret`` the kernel in the Pallas interpreter); and
    that kernel against the XLA body over six slots at ragged lengths,
    one inactive. -> relative errors by length, the kernel's against
    the body, whether the layer's step compiles to a program with the
    kernel in it, and each length's segments."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import lfm2, moe
    from ray_tpu.ops import decode_attention as da
    from ray_tpu.ops.attention import attend_rows

    accelerator.claim_device()
    kw = dict(n_layers=2, layer_types=("conv", "full_attention"),
              vocab_size=1024, n_dense_layers=2, dense_d_ff=256)
    cfg = lfm2.Lfm2Config.tiny(**kw, dtype="bfloat16") \
        if widths == "tiny" else lfm2.Lfm2Config(**kw)
    conv, gqa = lfm2.init_params(cfg, jax.random.PRNGKey(seed))["layers"]
    attend = functools.partial(da.decode_attention, **(
        {"interpret": True} if interpret else {}))
    hkv, hd = cfg.n_kv_heads, cfg.head_dim

    @jax.jit
    def both(x):
        t = x.shape[1]
        seg = moe.segment_rows(t)
        xs = jnp.moveaxis(x, 1, 0)[:, :, None]  # [T, 1, 1, D]
        on, lens_ = jnp.ones((1,), bool), jnp.array([t])
        rows0 = jnp.zeros((1, cfg.conv_kernel - 1, cfg.d_model), x.dtype)
        rows, y = moe.in_segments(
            lambda rows, s: lfm2.conv_segment(
                cfg, conv, s[1], rows, s[0], lens_)[::-1], rows0, x, seg)
        rows_step, y_step = jax.lax.scan(
            lambda r, x_t: lfm2.conv_step(cfg, conv, x_t, r, on)[::-1],
            rows0, xs)

        def segment(carry, s):
            k_all, v_all = carry
            start, x_seg = s
            at = start + jnp.arange(x_seg.shape[1], dtype=jnp.int32)
            q, k, v = lfm2._qkv(cfg, gqa, x_seg, at[None])
            k_all, v_all = (jax.lax.dynamic_update_slice(
                a, r.transpose(0, 2, 1, 3), (0, 0, start, 0))
                for a, r in ((k_all, k), (v_all, v)))
            o = attend_rows(q.transpose(0, 2, 1, 3), k_all, v_all,
                            offset=start)
            return (k_all, v_all), o.transpose(0, 2, 1, 3).reshape(
                1, x_seg.shape[1], -1)

        heads = jnp.zeros((1, hkv, t, hd), x.dtype)
        (k_all, _), y_gqa = moe.in_segments(segment, (heads, heads), x, seg)

        def one(cache, xp):
            x_t, pos = xp
            q, k, v = lfm2._qkv(cfg, gqa, x_t, pos[None])
            kc, vc = (c.at[0, 0, pos[0]].set(r.reshape(-1))
                      for c, r in zip(cache, (k, v)))
            o = attend(q, kc, vc, 0, (pos + 1).astype(jnp.int32))
            return (kc, vc), o.reshape(1, 1, -1)

        stack = jnp.zeros((1, 1, t, cfg.kv_width), x.dtype)
        (kc, _), y_gqa_step = jax.lax.scan(
            one, (stack, stack), (xs, jnp.arange(t)[:, None]))
        return {"conv_out": (y, jnp.moveaxis(y_step[:, :, 0], 0, 1)),
                "conv_rows": (rows, rows_step),
                "gqa_out": (y_gqa, jnp.moveaxis(y_gqa_step[:, :, 0], 0, 1)),
                "gqa_rows": (k_all.transpose(0, 2, 1, 3).reshape(1, t, -1),
                             kc[0])}

    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    errs = {}
    for t in lens:
        x = jax.random.normal(jax.random.PRNGKey(seed + t),
                              (1, t, cfg.d_model), cfg.compute_dtype)
        errs[str(t)] = {k: rel(a, b) for k, (a, b) in both(x).items()}
    slots, rows = 6, 96
    lengths = jnp.array([rows, 1, 0, 17, 64, 33], jnp.int32)
    key = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    q = jax.random.normal(key[0], (slots, 1, cfg.n_heads, hd),
                          cfg.compute_dtype)
    k, v = (jax.random.normal(kk, (2, slots, rows, cfg.kv_width),
                              cfg.compute_dtype) for kk in key[1:])
    o_kernel = jax.jit(attend)(q, k, v, 1, lengths)
    o_body = da.decode_attention(q, k, v, 1, lengths, use_kernel=False)
    text = jax.jit(da.decode_attention).lower(
        q, k, v, 1, lengths).compile().as_text()
    return {"rel_err": errs, "kernel": rel(o_kernel, o_body),
            "inactive_zero": not bool(jnp.any(o_kernel[2])),
            "heads_a_tile": da._heads_a_tile(hd, hd, hkv),
            "in_program": any(
                KERNEL in line and "decode_attn" in line.split(" = ")[0]
                for line in text.splitlines()),
            "segments": {str(t): lfm2.SLOTS.prefill_segments(cfg, t)
                         for t in lens},
            "device": accelerator.device_report()}


DSA_KERNEL_TOLERANCE = 2e-2  # bf16 outputs of either form, relative


def _mhc_parting(cfg, rows: int, key) -> float:
    """The tenth block's residual streams round one sublayer
    (``glm_next.hc_read`` / ``hc_write``: streams in the compute type,
    coefficients float32 with the streams' axes leading, the mixes
    written out) against a float32 ``jax.numpy`` body (the paper's
    lines, einsums at the highest precision) on ``rows`` rows of seeded
    streams and leaves: the larger relative error of what the sublayer
    reads and of the streams it leaves."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import glm_next

    n, d, f32 = cfg.hc_mult, cfg.d_model, jnp.float32
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (2, rows, n, d), cfg.compute_dtype)
    y = jax.random.normal(ks[1], (2, rows, d), cfg.compute_dtype)
    p = {"hc_phi": jax.random.normal(ks[2], (n * d, 2 * n + n * n), f32)
         * (n * d) ** -0.5,
         "hc_b": jax.random.normal(ks[3], (2 * n + n * n,), f32),
         "hc_alpha": 1.0 + 0.25 * jax.random.normal(ks[4], (3,), f32)}

    @jax.jit
    def program(x, y):
        u, mix = glm_next.hc_read(cfg, p, x)
        return u, glm_next.hc_write(cfg, x, y, mix)

    with jax.default_matmul_precision("highest"):
        xs, ys = x.astype(f32), y.astype(f32)
        flat = xs.reshape(2, rows, -1)
        c = (flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                  + cfg.hc_eps)) @ p["hc_phi"]
        a, b = p["hc_alpha"], p["hc_b"]
        pre = jax.nn.sigmoid(a[0] * c[..., :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * c[..., n:2 * n] + b[n:2 * n])
        res = jnp.exp(a[2] * c[..., 2 * n:] + b[2 * n:]).reshape(
            2, rows, n, n)
        for _ in range(cfg.hc_sinkhorn_iters):
            res = res / (res.sum(-1, keepdims=True) + cfg.hc_eps)
            res = res / (res.sum(-2, keepdims=True) + cfg.hc_eps)
        want_u = jnp.einsum("btn,btnd->btd", pre, xs)
        want_x = jnp.einsum("btij,btjd->btid", res, xs) \
            + post[..., None] * ys[:, :, None]
    u, new = program(x, y)
    return max(float(jnp.max(jnp.abs(got.astype(f32) - want))
                     / jnp.max(jnp.abs(want)))
               for got, want in ((u, want_u), (new, want_x)))


def dsa_check(widths: str, rows: int, seed: int,
              interpret: bool = False, block: str = "dots") -> dict:
    """Runs in a child that holds the chip: the four kernels of
    ``ops/dsa.py`` at the eighth block's widths (``models/dots.py``) or,
    with ``block="glm_dsa"``, at the ninth's (``models/glm_dsa.py``: 32
    index heads, heads of 192 + 64 beside values of 256, 64 heads a
    step) or, with ``block="glm_next"``, at the tenth's
    (``models/glm_next.py``: heads of 256 + 0, NO rotated part, latent
    rows of 512; the index over keys POOLED four rows a block, whole
    blocks chosen and the open block read besides; and the streams'
    coefficients and mixes, ``glm_next.hc_read`` / ``hc_write``, against
    a float32 ``jax.numpy`` body: ``rel_err["mhc"]``), in the compute
    type, each against its XLA body: ``rows`` query
    rows at
    offset ``rows`` over ``2 * rows`` keys through ``dsa_index`` (every
    causal score), ``dsa_kth`` (the selected SETS must be equal: the
    selection is exact) and ``dsa_attn`` (the masked flash kernel over
    eight heads), and four slots' decode step over ``2 * rows`` latent
    rows through ``dsa_decode_attn``, one slot inactive (zeros, bit for
    bit). With ``interpret`` the kernels run in the Pallas interpreter.
    -> relative errors, whether the sets are equal, the rows chosen."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import accelerator
    from ray_tpu.models import dots, glm_dsa, glm_next
    from ray_tpu.ops import dsa

    accelerator.claim_device()
    config = {"dots": dots.DotsConfig, "glm_dsa": glm_dsa.GlmDsaConfig,
              "glm_next": glm_next.GlmNextConfig}[block]
    cfg = config.tiny(dtype="bfloat16") if widths == "tiny" else config()
    k = cfg.kind(False) if block == "dots" else cfg.mla
    hi, di, dt = cfg.index_heads, cfg.index_head_dim, cfg.compute_dtype
    pool = dots.index_pool(cfg)  # (1: a key a row)
    top = min(cfg.index_topk, rows) // pool
    keys, blocks = 2 * rows, min(128, rows)
    how = {"interpret": True, "block_q": blocks, "block_k": blocks} \
        if interpret else {"use_kernel": True}
    rng = iter(jax.random.split(jax.random.PRNGKey(seed), 12))

    def rel(a, b, where=True):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.max(jnp.where(where, jnp.abs(a - b), 0))
                     / jnp.max(jnp.abs(b)))

    q_i = jax.random.normal(next(rng), (1, rows, hi, di), dt)
    w = jax.random.normal(next(rng), (1, rows, hi), jnp.float32)
    k_i = jax.random.normal(next(rng), (1, keys, di), dt)
    at = jnp.arange(rows)[:, None] + rows  # the query rows' positions
    if pool > 1:  # (a key a block of ``pool`` rows; whole blocks alone)
        k_i = dots.pooled_keys(k_i, pool)
    causal = (pool * jnp.arange(keys // pool)[None, :] + pool - 1 <= at)[None]
    scores = jax.jit(lambda *a: dsa.index_scores(
        *a, rows, pool=pool, **how))(q_i, w, k_i)
    body = dsa.index_scores_xla(q_i, w, k_i)
    select = {"interpret": True} if interpret else {"use_kernel": True}
    chosen = jax.jit(lambda s: dsa.select(s, causal, top, **select))(body)
    sets_equal = bool(jnp.array_equal(
        chosen, dsa.select(body, causal, top, use_kernel=False)))

    def read(chosen, at):  # -> the bias a query at ``at`` attends over
        if pool > 1:
            return dots.pooled_bias(chosen, at, pool, keys)[0]
        return jnp.where(chosen, 0.0, dsa.NEG).astype(jnp.bfloat16)

    def rotated(*shape):  # (a kind without a rotated part hands None)
        return jax.random.normal(next(rng), shape, dt) if k.dr else None

    bias = read(chosen, at)
    qkv = (jax.random.normal(next(rng), (1, 8, rows, k.dn), dt),
           rotated(1, 8, rows, k.dr),
           jax.random.normal(next(rng), (1, 8, keys, k.dn), dt),
           rotated(1, keys, k.dr),
           jax.random.normal(next(rng), (1, 8, keys, k.dv), dt), bias)
    scale = (k.dn + k.dr) ** -0.5
    attn = jax.jit(lambda *a: dsa.masked_attention(
        *a, rows, scale=scale, **how))(*qkv)
    lengths = jnp.array([keys, 0, keys - 5, 3], jnp.int32)
    valid = jnp.arange(keys // pool)[None, :] < (lengths // pool)[:, None]
    step_bias = read(dsa.select(
        jax.random.normal(next(rng), (4, keys // pool)), valid, top,
        use_kernel=False), (lengths - 1)[:, None])
    stack = jax.random.normal(next(rng), (2, 4, keys, k.row_width), dt)
    q_row = jax.random.normal(next(rng), (4, k.heads, k.row_width), dt)
    step = jax.jit(lambda *a: dsa.decode_attention_masked(
        *a, dv=k.kv_lora, scale=scale, block=min(1024, keys), **(
            {"interpret": True} if interpret else {"use_kernel": True})))(
        q_row, stack, 1, lengths, step_bias)
    step_body = dsa.attend_latent_masked(q_row, stack[1], lengths,
                                         step_bias, k.kv_lora, scale)
    streams = {}
    if block == "glm_next":
        streams["mhc"] = _mhc_parting(cfg, rows, next(rng))
    return {"rel_err": {
                "dsa_index": rel(scores, body, causal),
                "dsa_attn": rel(attn, dsa.masked_attention_xla(*qkv, scale)),
                "dsa_decode_attn": rel(step, step_body), **streams},
            "sets_equal": sets_equal, "chosen": int(chosen.sum()),
            "inactive_zero": not bool(jnp.any(step[1] != 0)),
            "rows": rows, "device": accelerator.device_report()}


def hybrid_phase(plan: Plan) -> dict:
    out = chip_child(plan, "hybrid_check", {
        "widths": plan.hybrid_widths, "lens": list(plan.hybrid_lens),
        "seed": plan.seed})
    worst = max(v for by_len in out["rel_err"].values()
                for v in by_len.values())
    check(worst <= HYBRID_TOLERANCE,
          "a hybrid layer's two forms part (KDA chunkwise / stepping, "
          "MLA unabsorbed / absorbed)", got=out["rel_err"],
          tolerance=HYBRID_TOLERANCE)
    ring = chip_child(plan, "ring_check", {
        "widths": plan.hybrid_widths, "steps": plan.ring_steps,
        "seed": plan.seed, "interpret": not plan.on_tpu})
    check_device(plan, ring["device"], 1, "ring child")
    check(ring["wraps"] >= 2 and ring["rel_err"] <= HYBRID_TOLERANCE,
          "the decode_attn kernel and the XLA body part on a sliding "
          "layer's ring", got=ring, tolerance=HYBRID_TOLERANCE)
    kda = chip_child(plan, "kda_kernel_check", {
        "widths": plan.hybrid_widths, "steps": plan.kda_steps,
        "seed": plan.seed, "interpret": not plan.on_tpu})
    check_device(plan, kda["device"], 1, "kda kernel child")
    check(max(kda["rel_err"].values()) <= KDA_KERNEL_TOLERANCE
          and kda["inactive_kept"] and kda["moved"] > 0
          and kda["in_program"] == plan.on_tpu,
          "the kda_step kernel and the XLA body part on a KDA layer's "
          "state, an inactive slot's state moved, or the layer's step "
          "holds no kernel on the chip", got=kda,
          tolerance=KDA_KERNEL_TOLERANCE)
    chunk = chip_child(plan, "kda_chunk_check", {
        "widths": plan.hybrid_widths, "rows": plan.kda_chunk_rows,
        "seed": plan.seed, "interpret": not plan.on_tpu})
    check_device(plan, chunk["device"], 1, "kda chunk child")
    check(max(v for pair in chunk["rel_err"].values()
              for v in pair.values()) <= KDA_CHUNK_TOLERANCE
          and chunk["in_program"] == plan.on_tpu
          and chunk["inputs_rel_err"] <= KDA_CHUNK_TOLERANCE
          and chunk["inputs_in_program"] == plan.on_tpu,
          "the kda_chunk kernel, the XLA body and the recurrence part on "
          "a KDA layer's rows, the kda_inputs kernel and its XLA body on "
          "q, k, v and g, or the layer's segment lacks a kernel on the "
          "chip", got=chunk, tolerance=KDA_CHUNK_TOLERANCE)
    latent = chip_child(plan, "latent_check", {
        "widths": plan.hybrid_widths, "lens": list(plan.latent_lens),
        "seed": plan.seed})
    check_device(plan, latent["device"], 1, "latent child")
    check(max(v for by_len in latent["rel_err"].values()
              for v in by_len.values()) <= HYBRID_TOLERANCE,
          "a gated MLA layer's two forms part (unabsorbed through the "
          "flash kernel / absorbed over the slot's rows)",
          got=latent["rel_err"], tolerance=HYBRID_TOLERANCE)
    segment = chip_child(plan, "segment_check", {
        "widths": plan.hybrid_widths, "lens": list(plan.segment_lens),
        "seed": plan.seed})
    check_device(plan, segment["device"], 1, "segment child")
    check(max(v for by_len in segment["rel_err"].values()
              for v in by_len.values()) <= HYBRID_TOLERANCE,
          "a layer of the block without positions parts from its other "
          "form (KDA in carried segments / stepping at 64 heads, gated "
          "GQA through the flash kernel / over the slot's rows)",
          got=segment["rel_err"], tolerance=HYBRID_TOLERANCE)
    mamba = {}
    for block in ("granite", "nemotron"):
        mamba[block] = ssm = chip_child(plan, "ssm_check", {
            "widths": plan.hybrid_widths, "lens": list(plan.ssm_lens),
            "seed": plan.seed, "interpret": not plan.on_tpu, "block": block})
        check_device(plan, ssm["device"], 1, "ssm child")
        check(max(v for by_len in ssm["rel_err"].values()
                  for v in by_len.values()) <= HYBRID_TOLERANCE
              and max(ssm["kernel"].values()) <= SSD_KERNEL_TOLERANCE
              and ssm["inactive_kept"] and ssm["in_program"] == plan.on_tpu,
              f"at the widths of models/{block}.py a Mamba-2 layer's "
              "chunked scan in carried segments parts from its stepping, "
              "the ssd_step kernel from the XLA body, an inactive slot's "
              "state moved, or the layer's step holds no kernel on the "
              "chip", got=ssm, tolerance=HYBRID_TOLERANCE)
    sconv = chip_child(plan, "sconv_check", {
        "widths": plan.hybrid_widths, "lens": list(plan.sconv_lens),
        "seed": plan.seed, "interpret": not plan.on_tpu})
    check_device(plan, sconv["device"], 1, "sconv child")
    check(max(v for by_len in sconv["rel_err"].values()
              for v in by_len.values()) <= HYBRID_TOLERANCE
          and sconv["kernel"] <= HYBRID_TOLERANCE and sconv["inactive_zero"]
          and sconv["in_program"] == plan.on_tpu,
          "a gated short convolution in carried segments parts from its "
          "stepping, a GQA layer of narrow heads through the flash kernel "
          "from its decode step, the decode_attn kernel at two heads a "
          "tile from the XLA body, or the step holds no kernel on the "
          "chip", got=sconv, tolerance=HYBRID_TOLERANCE)
    sparse = {}
    for block in ("dots", "glm_dsa", "glm_next"):
        sparse[block] = found = chip_child(plan, "dsa_check", {
            "widths": plan.hybrid_widths, "rows": plan.dsa_rows,
            "seed": plan.seed, "interpret": not plan.on_tpu, "block": block})
        check_device(plan, found["device"], 1, "dsa child")
        check(max(found["rel_err"].values()) <= DSA_KERNEL_TOLERANCE
              and found["sets_equal"] and found["inactive_zero"],
              f"at the widths of models/{block}.py a kernel of ops/dsa.py "
              "parts from its XLA body, the selection's kernel chose "
              "another set than the counting passes, or an inactive "
              "slot's output is not zeros", got=found,
              tolerance=DSA_KERNEL_TOLERANCE)
    return {"device": check_device(plan, out["device"], 1, "hybrid child"),
            "lens": list(plan.hybrid_lens), "rel_err": out["rel_err"],
            "dsa": {block: {k: found[k] for k in (
                "rel_err", "sets_equal", "chosen", "rows")}
                for block, found in sparse.items()},
            "ssm": {k: mamba["granite"][k] for k in (
                "rel_err", "kernel", "segments", "inactive_kept",
                "in_program")},
            "ssm_groups": {k: mamba["nemotron"][k] for k in (
                "rel_err", "kernel", "segments", "inactive_kept",
                "in_program", "groups")},
            "sconv": {k: sconv[k] for k in (
                "rel_err", "kernel", "segments", "heads_a_tile",
                "in_program")},
            "segment": {k: segment[k] for k in ("rel_err", "segments")},
            "ring": {k: ring[k] for k in ("rel_err", "wraps", "window")},
            "latent": latent["rel_err"],
            "kda_kernel": {k: kda[k] for k in (
                "rel_err", "inactive_kept", "in_program", "steps")},
            "kda_chunk": {k: chunk[k] for k in (
                "rel_err", "in_program", "rows", "heads")},
            "tolerance": HYBRID_TOLERANCE,
            "compile_s": out["device"]["compile"]["seconds"]}


# ------------------------------------------------------------------ run

ONE_CHIP = (("serve", serve_phase), ("train", train_phase),
            ("kernels", kernels_phase), ("hybrid", hybrid_phase))
FOUR_CHIPS = (("train4", train4_phase), ("pool4", pool4_phase))


def run(plan: Plan, phases=None, out=None) -> int:
    """Run the phases in order on an initialised cluster; one JSON line
    each on ``out`` (stdout); the contract's last line only if every
    phase passed."""
    from jax._src import xla_bridge

    out = out or sys.stdout
    if phases is None:
        phases = FOUR_CHIPS if plan.chips == 4 else ONE_CHIP
    devices = []
    for name, phase in phases:
        t0 = time.monotonic()
        try:
            facts = phase(plan)
            # (a CPU rehearsal runs under pytest, which has one)
            check(not (plan.on_tpu and xla_bridge.backends_are_initialized()),
                  "the orchestrating process initialised a JAX backend")
        except BaseException:  # noqa: BLE001 — reported, then exit code 1
            traceback.print_exc()
            print(f"chip_smoke: phase {name!r} FAILED after "
                  f"{time.monotonic() - t0:.0f}s", file=sys.stderr,
                  flush=True)
            return 1
        devices.append(facts.pop("device"))
        print(json.dumps({
            "phase": name, "ok": True,
            "seconds": round(time.monotonic() - t0, 1),
            "compile_seconds": facts.pop("compile_s"), "device": devices[-1],
            "checked": facts}), file=out, flush=True)
    final = devices[0]
    if plan.chips == 4:  # the process that drove all four reports four
        final = max(devices, key=lambda d: d["count"])
    # (a CPU rehearsal's workers see the test suite's virtual devices)
    if final["platform"] != plan.platform \
            or (plan.on_tpu and final["count"] != plan.chips) \
            or any(d["kind"] != final["kind"] for d in devices):
        print(f"chip_smoke: phases disagree on the device: {devices}",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": final}), file=out, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    plan = Plan(chips=args.chips, seed=args.seed)

    # This process orchestrates and must be UNABLE to take a chip.
    # Workers do not inherit the pin: the node agent sets each worker's
    # platform from its TPU grant (ray_tpu/_private/accelerator.py).
    os.environ["JAX_PLATFORMS"] = "cpu"
    import ray_tpu
    from ray_tpu._private import accelerator, api

    found = accelerator.detect_tpu_chips()
    if found < plan.chips:
        print(f"chip_smoke: needs {plan.chips} TPU chip(s), found {found} "
              f"device node(s) ({accelerator.chip_device_paths()})",
              file=sys.stderr)
        return 1
    # Standard output carries this script's JSON lines and nothing else,
    # so that the result is its LAST line: whatever else writes to stdout
    # from here on (worker logs forwarded to the driver) goes to stderr.
    out, sys.stdout = os.fdopen(os.dup(sys.stdout.fileno()), "w"), sys.stderr
    try:
        print(json.dumps({"phase": "build", "ok": True, **build_native()}),
              file=out, flush=True)
        ray_tpu.init(object_store_memory=store_bytes(plan))
        if api._cluster.agent._native_sched is None:
            print("chip_smoke: the node agent fell back to the pure-Python "
                  "scheduler", file=sys.stderr)
            return 1
        return run(plan, out=out)
    except BaseException:  # noqa: BLE001 — reported, then exit code 1
        traceback.print_exc()
        return 1
    finally:
        ray_tpu.shutdown()
        sys.stdout = sys.__stdout__


if __name__ == "__main__":
    sys.exit(main())
