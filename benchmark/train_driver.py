"""The ``train`` driver: ``JaxTrainer``, one worker that owns the chip(s).

The worker's loop is a copy of ``chip_smoke.py``'s ``_train_loop``
wiring (the repo's 1B recipe: ``flash_qkv`` remat, ``fused_adamw`` with
bf16 moments, bf16 gradients) with a measured window in place of its
four steps: a fresh seeded batch of token ids is put on the device every
step, so the input path is in the loop, and every step ends by reading
its loss. This process orchestrates and stays off JAX.
"""

from __future__ import annotations

import os
import sys
import tempfile

from benchmark import cluster, manifest, stats

TINY_JOB = dict(batch=2, seq=32, check_seq=16)  # the CPU rehearsal's


def _train_loop(config: dict) -> None:
    """Runs in the train worker (shipped by value)."""
    import faulthandler
    import shutil
    import sys
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

    from benchmark import reference

    faulthandler.enable()  # a fatal signal in the runtime leaves a stack
    t_worker = time.monotonic()

    def stage(what):  # where set-up time goes, on the worker's stderr
        print(f"benchmark: train worker: {what} at "
              f"+{time.monotonic() - t_worker:.1f} s", file=sys.stderr,
              flush=True)

    accelerator.claim_device()
    stage(f"{len(jax.devices())} device(s) claimed")
    m = config["model"]
    cfg = llama.LlamaConfig(**m, max_seq_len=config["seq"], remat=True,
                            remat_policy="flash_qkv")
    mesh = build_mesh(MeshConfig(**config["mesh"]), jax.devices())
    opt = fused_adamw(1e-4, weight_decay=0.01, mu_dtype=jnp.bfloat16,
                      nu_dtype=jnp.bfloat16)
    seed = config["seed"] % (2**31 - 1)
    state, state_sh = init_train_state(
        lambda k: llama.init_params(cfg, k), llama.param_logical_axes(cfg),
        opt, mesh, key=jax.random.PRNGKey(seed))
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh, state_sh,
        compute_grad_norm=False, grads_dtype=jnp.bfloat16)
    stage("state initialised")
    rs = np.random.RandomState(seed % (2**32))
    sharding = batch_sharding(mesh)

    def fresh_batch(batch, seq):
        toks = rs.randint(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
        return jax.device_put(
            {"inputs": toks[:, :-1], "targets": toks[:, 1:]}, sharding)

    with use_mesh(mesh):
        data = fresh_batch(config["batch"], config["seq"])
        compiled = step.lower(state, data).compile()
        # the runtime's peak_bytes_in_use leaves a program's temporaries
        # out (PERF.md, PR 21); the compiler knows the step's own
        step_temp_bytes = int(getattr(compiled.memory_analysis(),
                                      "temp_size_in_bytes", 0) or 0)
        stage("step compiled")
        # correctness, outside the window: the program's loss against
        # the plain reference's on the same short sequences
        chk = fresh_batch(config["check_batch"], config["check_seq"])
        got = float(jax.jit(lambda p, b: llama.loss_fn(p, b, cfg)[0])(
            state.params, chk))
        want = float(jax.jit(
            lambda p, b: reference.loss(p, b["inputs"], b["targets"], m))(
            state.params, chk))
        stage("loss checked against the reference")
        for _ in range(2):  # warm: the step and the input path
            state, metrics = compiled(state, fresh_batch(
                config["batch"], config["seq"]))
            float(metrics["loss"])
        compile_before = dict(accelerator.device_report()["compile"])
        losses, ends, traced_span = [], [], None
        trace_at = 2 if config["trace_dir"] else -1
        tracing = False
        t_open = time.monotonic()
        while time.monotonic() < t_open + config["seconds"]:
            if len(ends) == trace_at:
                shutil.rmtree(config["trace_dir"], ignore_errors=True)
                t_trace = time.monotonic()
                jax.profiler.start_trace(config["trace_dir"])
                tracing = True
            state, metrics = compiled(state, fresh_batch(
                config["batch"], config["seq"]))
            losses.append(float(metrics["loss"]))  # waits for the device
            ends.append(time.monotonic())
            if tracing and (len(ends) >= trace_at + config["trace_steps"]
                            or ends[-1] >= t_open + config["seconds"]):
                jax.profiler.stop_trace()
                tracing, trace_at = False, -1
                traced_span = (t_trace, time.monotonic())
    report = accelerator.device_report()
    session.report({
        "t_open": t_open, "step_ends": ends, "losses": losses,
        "traced_span": traced_span,
        "check_loss": got, "reference_loss": want,
        "step_temp_bytes": step_temp_bytes,
        "memory_stats": jax.local_devices()[0].memory_stats(),
        "compiles_in_window": report["compile"]["requests"]
        - compile_before["requests"],
        "device": report,
    })


def run(cell: dict, *, seed: int, seconds: float, trace: bool,
        rehearse: bool, t_start: float, work_dir: str) -> dict:
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark import reference

    job = dict(cell["traffic"])
    chips = cell["chips"]
    model = dict(manifest.TINY_FIELDS) if rehearse \
        else manifest.llama_fields(cell["config"])
    if rehearse:
        job.update(TINY_JOB, batch=TINY_JOB["batch"] * max(
            1, job["mesh"].get("fsdp", 1)))
    data_shards = job["mesh"].get("fsdp", 1) * job["mesh"].get("dp", 1)
    log_dir = os.path.join(work_dir, "trace")
    own_cluster = cluster.start()
    res = {"CPU": 1, **({} if rehearse else {"TPU": chips})}
    try:
        with tempfile.TemporaryDirectory(prefix="run_", dir=work_dir) as tmp:
            result = JaxTrainer(
                _train_loop,
                train_loop_config={
                    "model": model, "mesh": job["mesh"], "seed": seed,
                    "batch": job["batch"], "seq": job["seq"],
                    "check_batch": data_shards,
                    "check_seq": job.get("check_seq", 512),
                    "seconds": seconds,
                    "trace_dir": log_dir if trace else None,
                    "trace_steps": int(job.get("trace_steps", 6))},
                scaling_config=ScalingConfig(
                    num_workers=1, resources_per_worker=res,
                    platform="cpu" if rehearse else "tpu",
                    devices_per_worker=chips if rehearse else None),
                run_config=RunConfig(name="benchmark", storage_path=tmp),
            ).fit()
    finally:
        if own_cluster:
            ray_tpu.shutdown()
    cluster.wait_chips_free()
    if result.metrics is None:
        raise RuntimeError(f"the train worker reported nothing: {result}")
    out = dict(result.metrics)
    tokens_per_step = job["batch"] * job["seq"]
    rate, n_steps = stats.whole_steps(
        out["step_ends"], out["t_open"], seconds, tokens_per_step)
    losses = out["losses"][:n_steps]
    finite = all(x == x and abs(x) < 1e4 for x in losses)
    agrees = abs(out["check_loss"] - out["reference_loss"]) \
        <= reference.TRAIN_LOSS_TOL
    print(f"benchmark: loss {out['check_loss']:.5f} against the "
          f"reference's {out['reference_loss']:.5f} (tolerance "
          f"{reference.TRAIN_LOSS_TOL}); {out['compiles_in_window']} "
          f"compilation(s) inside the window; step temporaries "
          f"{out['step_temp_bytes']} B; memory_stats {out['memory_stats']}",
          file=sys.stderr, flush=True)
    if out["traced_span"]:
        # starting and stopping the profiler costs seconds (most on four
        # chips): a traced run's own rate is that of its other steps
        t0, t1 = out["traced_span"]
        starts = [out["t_open"], *out["step_ends"][:-1]]
        clear = [e - b for b, e in zip(starts, out["step_ends"])
                 if e <= t0 or b >= t1]
        rate = tokens_per_step * len(clear) / sum(clear)
    dev = out["device"]
    return {
        "attempted": n_steps, "failed": 0 if finite else 1,
        "correct": bool(finite and agrees
                        and not out["compiles_in_window"]),
        "setup_s": out["t_open"] - t_start,
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"],
                   # live arrays at their peak as the runtime counts
                   # them, plus the step program's temporaries
                   "memory_peak_bytes": max(
                       (b or 0) for b in dev["peak_bytes_in_use"])
                   + out["step_temp_bytes"]},
        "train": {"tokens_per_s": rate, "steps": n_steps,
                  "tokens_per_step": tokens_per_step, "seq": job["seq"],
                  "batch": job["batch"], "model": model, "chips": chips,
                  "mesh": job["mesh"]},
        "window_s": seconds, "log_dir": log_dir if trace else None,
    }
