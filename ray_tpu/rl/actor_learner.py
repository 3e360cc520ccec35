"""Podracer-style actor–learner loop: RLHF-shaped post-training that
runs the repo's two halves as ONE system (arXiv:2104.06272).

Dataflow (every arrow is an existing subsystem, now closed into a loop):

    rollout actors ──submit_stream(sampled)──> serve LLMPool replicas
         │  tokens + per-token behavior logprobs (streamed)
         ▼
    ray_tpu.put(trajectory)  ── zero-copy ref ──> ExperienceBuffer
         │                                      (versioned, FIFO claims)
         ▼
    DCN learner gang (JaxTrainer backend="dcn", in-place elastic):
       claim shard -> V-trace/PPO-clip policy gradient
       -> dcn_allreduce_grads -> SGD step -> checkpoint
         │ rank 0: ray_tpu.put(new weights) — ONE put
         ▼
    driver on_report -> LLMPool.publish_weights(ref, version)
       -> every replica + prefill worker adopts at its next chunk
          boundary (bounded staleness), buffer evicts stale experience

Failure surface, inherited rather than re-invented:

- A decode-replica death mid-rollout fails over inside the pool: same
  weight version ⇒ bit-exact seed-replay splice (sampling rides
  (seed, position) RNG lanes); version already republished ⇒ the stream
  closes cleanly at the emitted prefix — either way the rollout actor
  hands the buffer exactly one internally-consistent trajectory.
- A learner-rank death resumes IN-PLACE (survivors keep processes and
  JIT caches); the buffer's claim/rollback protocol re-delivers exactly
  the trajectories whose update was lost with the failure and never
  re-delivers ones already inside the restored checkpoint.

Off-policy correction: each trajectory carries the weight version and
the exact behavior logprobs it was sampled under; the learner computes
target logprobs under CURRENT weights and lets `rl/vtrace.py` clip the
importance ratios — the bounded-staleness window (buffer
``max_version_lag``) bounds how far those ratios drift.
"""

from __future__ import annotations

import collections
import logging
import os
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

import ray_tpu

logger = logging.getLogger(__name__)

# rank 0 keeps its recently-published weight trees referenced until the
# driver has adopted them: the put happens here (worker process) but the
# driver's deserialized ref lands an instant later — dropping ours in
# between would let the store free the blob mid-handoff.
_published_refs: collections.deque = collections.deque(maxlen=8)


def default_reward(prompt: np.ndarray, tokens: list,
                   vocab_size: int = 256) -> np.ndarray:
    """Synthetic dense reward: 1 for every generated token in the low
    half of the vocab. Trivially improvable by a tiny policy, which is
    exactly what an end-to-end harness wants to measure."""
    t = np.asarray(tokens, np.int64)
    return (t < vocab_size // 2).astype(np.float32)


@dataclass
class ActorLearnerConfig:
    # model (must mirror the pool's build_model config so the frozen
    # init and the learner's params are the same network)
    model_size: str = "tiny"
    max_len: int = 96
    model_seed: int = 0
    # rollout
    n_rollout_actors: int = 1
    prompt_len: int = 8          # prompts are padded/bucketed to this
    max_new: int = 8
    temperature: float = 1.0
    top_p: float = 1.0
    base_seed: int = 0
    reward_fn: Callable | None = None  # (prompt, tokens) -> [T] rewards
    # learner
    iterations: int = 8
    trajectories_per_iter: int = 8
    num_learners: int = 1
    min_learners: int | None = None
    learner_devices: int | None = None
    lr: float = 4.0  # per-TOKEN step: grads are summed then divided by
    # the GLOBAL token count (world-split-invariant mean)
    gamma: float = 0.9
    rho_bar: float = 1.0
    c_bar: float = 1.0
    clip_eps: float = 0.3
    entropy_coeff: float = 0.01
    publish_every: int = 1
    max_version_lag: int | None = 4
    claim_timeout_s: float = 180.0
    # sync_mode: rollouts produce EXACTLY trajectories_per_iter per
    # weight version and then wait for the next publish — on-policy
    # lockstep (Podracer's synchronous Sebulba flavor). With one rollout
    # actor the whole loop is bit-deterministic under fixed seeds: no
    # stream ever spans a weight swap, so trajectory content cannot
    # depend on publish timing. Async (default) overlaps generation
    # with learning and leans on the V-trace correction instead.
    sync_mode: bool = False
    # failure budgets (forwarded to RunConfig)
    max_failures: int = 1
    max_inplace_resumes: int = 8
    storage_path: str | None = None
    # chaos: fault specs armed inside learner workers (first incarnation
    # only) / the driver's rollout threads
    worker_specs: list = field(default_factory=list)


# ---------------------------------------------------------------------------
# learner side (runs inside each DCN train worker)
# ---------------------------------------------------------------------------


def _stack_batch(trajs: list[dict], prompt_pad: int, max_new: int):
    """Left-aligned [prompt | gen | pad] rows — generation must sit
    directly after the true prompt (causal attention over a contiguous
    prefix), padding only at the tail."""
    b = len(trajs)
    seq_len = prompt_pad + max_new
    out = {
        "tokens": np.zeros((b, seq_len), np.int32),
        "prompt_len": np.zeros((b,), np.int32),
        "gen_tokens": np.zeros((b, max_new), np.int32),
        "behavior_logp": np.zeros((b, max_new), np.float32),
        "rewards": np.zeros((b, max_new), np.float32),
        "mask": np.zeros((b, max_new), np.float32),
        "dones": np.ones((b, max_new), np.float32),
    }
    for i, t in enumerate(trajs):
        p = np.asarray(t["prompt"], np.int32)
        g = np.asarray(t["tokens"], np.int32)[:max_new]
        n, m = len(p), len(g)
        if n > prompt_pad:
            raise ValueError(f"prompt {n} > prompt_pad {prompt_pad}")
        out["tokens"][i, :n] = p
        out["tokens"][i, n:n + m] = g
        out["prompt_len"][i] = n
        out["gen_tokens"][i, :m] = g
        out["behavior_logp"][i, :m] = np.asarray(
            t["logprobs"], np.float32)[:m]
        out["rewards"][i, :m] = np.asarray(t["rewards"], np.float32)[:m]
        out["mask"][i, :m] = 1.0
        out["dones"][i, :m] = 0.0
        if m:
            out["dones"][i, m - 1] = 1.0
    return out


def _pg_loss(params, batch, baseline, cfg, gamma, rho_bar, c_bar,
             clip_eps, temperature, entropy_coeff):
    """V-trace-corrected clipped policy gradient, SUMMED over the batch
    (the caller divides by the GLOBAL token count after the gradient
    allreduce, so any world-size split of the same trajectory set
    yields the same update).

    behavior logprobs came from the serving engine (the temperature/
    top-p distribution that actually sampled the tokens, possibly a
    version or more behind); targets are the same transformation under
    current weights — their ratio is the off-policy correction keyed on
    weight version that V-trace clips at rho_bar/c_bar."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.rl.vtrace import vtrace

    logits = llama.forward(params, batch["tokens"], cfg)
    logits = logits.astype(jnp.float32)
    t_new = batch["gen_tokens"].shape[1]
    # gen token t is predicted from sequence position prompt_len-1+t
    pos = (batch["prompt_len"][:, None] - 1
           + jnp.arange(t_new, dtype=jnp.int32)[None, :])
    tok_logits = jnp.take_along_axis(
        logits, pos[:, :, None], axis=1)  # [B, T, V]
    logp_all = jax.nn.log_softmax(
        tok_logits / jnp.maximum(temperature, 1e-6))
    tgt_logp = jnp.take_along_axis(
        logp_all, batch["gen_tokens"][:, :, None], axis=2)[..., 0]
    mask = batch["mask"]
    beh = batch["behavior_logp"]
    rewards = (batch["rewards"] - baseline) * mask
    values = jnp.zeros_like(rewards)
    n_traj = rewards.shape[0]
    _, adv = vtrace(
        beh.T, tgt_logp.T, rewards.T, values.T,
        jnp.zeros((n_traj,), jnp.float32), batch["dones"].T,
        gamma=gamma, rho_bar=rho_bar, c_bar=c_bar)
    adv = adv.T  # [B, T], stop-gradient'd by vtrace
    ratio = jnp.exp(tgt_logp - beh)
    surr = jnp.minimum(
        ratio * adv,
        jnp.clip(ratio, 1.0 - clip_eps, 1.0 + clip_eps) * adv)
    ent = -(jnp.exp(logp_all) * logp_all).sum(-1)
    loss = -(surr * mask).sum() - entropy_coeff * (ent * mask).sum()
    aux = {"entropy": (ent * mask).sum() / jnp.maximum(mask.sum(), 1.0),
           "mean_ratio": (ratio * mask).sum()
           / jnp.maximum(mask.sum(), 1.0)}
    return loss, aux


def _learner_loop(config: dict):
    """The per-worker gang loop (runs under JaxTrainer backend="dcn").

    `get_dataset_shard`-style sharding, but over a STREAM: instead of a
    static block list, each rank claims a disjoint FIFO shard of the
    experience queue per iteration, tagged (iteration, incarnation) so
    the buffer's rollback keeps delivery exact across in-place
    resumes."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu._private import fault_injection as _fi
    from ray_tpu.serve.llm import build_model
    from ray_tpu.train import dcn_allreduce_grads, session
    from ray_tpu.train.checkpoint import Checkpoint

    hp = config["hp"]
    buffer = config["buffer"]
    rank = session.get_world_rank()
    world = session.get_world_size()
    group = session.get_collective_group()
    seq = session.get_resume_seq()
    if seq == 0 and config.get("worker_specs"):
        _fi.configure(config["worker_specs"])

    # identical init to the pool's frozen weights: same build_model seed
    params, cfg = build_model(
        hp["model_size"], max_len=hp["max_len"], seed=hp["model_seed"])
    start_it = 0
    ck = session.get_checkpoint()
    if ck is not None:
        d = ck.to_dict()
        params = jax.tree_util.tree_map(jnp.asarray, d["params"])
        start_it = int(d["iteration"])
    if rank == 0:
        # exactness sweep: re-open claims whose update died after the
        # restored checkpoint; finalize ones the checkpoint contains
        ray_tpu.get(buffer.rollback.remote(start_it, seq), timeout=60)

    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        _pg_loss, cfg=cfg, gamma=hp["gamma"], rho_bar=hp["rho_bar"],
        c_bar=hp["c_bar"], clip_eps=hp["clip_eps"],
        temperature=hp["temperature"],
        entropy_coeff=hp["entropy_coeff"]), has_aux=True))

    n_total = int(hp["trajectories_per_iter"])
    for it in range(start_it, int(hp["iterations"])):
        version = it + 1
        want = n_total // world + (1 if rank < n_total % world else 0)
        entries: list[dict] = []
        deadline = time.monotonic() + float(hp["claim_timeout_s"])
        while len(entries) < want:
            out = ray_tpu.get(
                buffer.claim.remote(f"rank{rank}", want - len(entries),
                                    version, seq),
                timeout=60)
            entries.extend(out["entries"])
            if len(entries) >= want:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"rank {rank} starved: {len(entries)}/{want} "
                    f"trajectories after {hp['claim_timeout_s']}s "
                    f"(iteration {version})")
            time.sleep(0.02)
        trajs = []
        for e in entries:
            t = e["traj"]
            if isinstance(t, dict) and isinstance(
                    t.get("ref"), ray_tpu.ObjectRef):
                t = ray_tpu.get(t["ref"], timeout=120)
            trajs.append(t)
        batch = _stack_batch(
            [t for t in trajs if len(t["tokens"])],
            int(hp["prompt_len"]), int(hp["max_new"]))

        # global reward stats FIRST: the baseline must be identical on
        # every rank or the summed gradients are not world-invariant
        local = np.asarray(
            [float(batch["rewards"].sum()), float(batch["mask"].sum()),
             float(len(trajs))], np.float64)
        tot = dcn_allreduce_grads({"s": local}, group, op="sum",
                                  timeout=60.0)["s"]
        baseline = float(tot[0] / max(tot[1], 1.0))
        mean_reward = baseline

        (loss, aux), grads = grad_fn(
            params, {k: jnp.asarray(v) for k, v in batch.items()},
            jnp.float32(baseline))
        host_grads = dcn_allreduce_grads(grads, group, op="sum",
                                         timeout=60.0)
        # per-token mean step: invariant to how trajectories split
        # across ranks AND to trajectory length mix
        scale = hp["lr"] / max(float(tot[1]), 1.0)
        params = jax.tree_util.tree_map(
            lambda p, g: p - scale * jnp.asarray(g), params, host_grads)

        loss_tot = dcn_allreduce_grads(
            {"l": np.asarray([float(loss)], np.float64)}, group,
            op="sum", timeout=60.0)["l"][0]
        metrics = {
            "iteration": version, "version": version,
            "mean_reward": mean_reward,
            "loss": float(loss_tot) / max(float(tot[1]), 1.0),
            "entropy": float(aux["entropy"]),
            "mean_ratio": float(aux["mean_ratio"]),
            "claimed": len(entries), "world": world,
        }
        ckpt = None
        if rank == 0:
            host = jax.tree_util.tree_map(
                lambda a: np.asarray(jax.device_get(a)), params)
            ckpt = Checkpoint.from_dict(
                {"params": host, "iteration": version},
                os.path.join(config["ck_dir"], f"ck_s{seq}_{version}"))
            if version % int(hp["publish_every"]) == 0 \
                    or version == int(hp["iterations"]):
                wref = ray_tpu.put(host, _inline=False)
                _published_refs.append(wref)  # outlive the handoff
                metrics["weights_ref"] = {"ref": wref}
                metrics["publish_t"] = time.monotonic()
        session.report(metrics, checkpoint=ckpt)


# ---------------------------------------------------------------------------
# driver side
# ---------------------------------------------------------------------------


class ActorLearnerLoop:
    """Drive rollouts on a serving pool and a DCN learner gang as one
    closed post-training loop. The pool may be shared with live traffic
    — rollout streams are ordinary sampled requests."""

    BACKPRESSURE_FACTOR = 2  # buffer high-water: N x one iteration —
    # bounds how stale (in versions) queued experience can grow when
    # rollouts outpace the learner; vtrace clips what remains
    # free consumed trajectories this many iterations behind the newest
    # checkpoint: deep enough that a corrupt-checkpoint fallback
    # (checkpoint_num_to_keep=2) never rolls back past freed claims
    FINALIZE_LAG = 4

    def __init__(self, config: ActorLearnerConfig, *,
                 pool=None, pool_kwargs: dict | None = None):
        from ray_tpu.rl.experience import ExperienceBuffer
        from ray_tpu.serve.llm_pool import LLMPool

        self.cfg = config
        self._own_pool = pool is None
        if pool is None:
            kw = dict(model_size=config.model_size,
                      max_len=config.max_len, seed=config.model_seed,
                      prompt_buckets=(config.prompt_len,),
                      autoscale=False)
            kw.update(pool_kwargs or {})
            pool = LLMPool(**kw)
        self.pool = pool
        self.buffer = ray_tpu.remote(num_cpus=0)(
            ExperienceBuffer).remote(
                max_version_lag=config.max_version_lag)
        ray_tpu.get(self.buffer.size.remote(), timeout=120)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._rollout_stats = {
            "trajectories": 0, "tokens": 0, "truncated": 0,
            "errors": 0, "dup_rejected": 0}
        self._rollout_lock = threading.Lock()
        self._publishes: list[tuple[int, float]] = []
        self._adoption_lat: list[float] = []
        # last version every replica has ACTUALLY swapped in (not just
        # staged): the sync-mode rollout gate — generating against the
        # publish version alone could start a stream under old weights
        self._adopted_version = 0

    # ---- rollout actors (threads driving the pool's streaming API) ----

    def _make_prompt(self, rng: np.random.RandomState) -> list[int]:
        n = self.cfg.prompt_len
        return [int(x) for x in rng.randint(1, 250, n)]

    def _rollout_loop(self, idx: int):
        from ray_tpu._private import fault_injection as _fi

        cfg = self.cfg
        reward_fn = cfg.reward_fn or default_reward
        rng = np.random.RandomState(cfg.base_seed * 9176 + 77 * idx + 1)
        high_water = self.BACKPRESSURE_FACTOR * cfg.trajectories_per_iter
        # sync mode: this actor's per-version quota (actors split the
        # iteration batch; remainder to the low indices)
        quota = (cfg.trajectories_per_iter // cfg.n_rollout_actors
                 + (1 if idx < cfg.trajectories_per_iter
                    % cfg.n_rollout_actors else 0))
        my_version = 0
        produced = 0
        local_seq = 0
        while not self._stop.is_set():
            try:
                if cfg.sync_mode:
                    cur_v = self._adopted_version
                    if cur_v > my_version:
                        my_version, produced = cur_v, 0
                    if produced >= quota:
                        time.sleep(0.002)  # wait for the next publish
                        continue
                elif ray_tpu.get(self.buffer.size.remote(),
                                 timeout=60) >= high_water:
                    time.sleep(0.05)
                    continue
                prompt = self._make_prompt(rng)
                seed = int(rng.randint(0, 2 ** 31 - 1))
                sub = self.pool.submit_stream({
                    "prompt_ids": prompt, "max_tokens": cfg.max_new,
                    "temperature": cfg.temperature, "top_p": cfg.top_p,
                    "seed": seed})
                toks: list[int] = []
                lps: list[float] = []
                version = sub.get("weights_version", 0)
                truncated = False
                while not self._stop.is_set():
                    out = self.pool.poll_stream(sub["rid"])
                    toks.extend(out["tokens"])
                    lps.extend(out.get("logprobs", []))
                    version = out.get("weights_version", version)
                    if out.get("done"):
                        truncated = bool(out.get("truncated"))
                        break
                    time.sleep(0.004)
                if not toks:
                    continue
                # chaos site: a rollout actor crashing/stalling between
                # generation and the buffer add ("drop" loses the
                # trajectory BEFORE accounting — a never-born rollout)
                if _fi.fire("rl.rollout", actor=idx) == "drop":
                    continue
                local_seq += 1
                traj = {
                    "prompt": np.asarray(prompt, np.int32),
                    "tokens": np.asarray(toks, np.int32),
                    "logprobs": np.asarray(lps, np.float32),
                    "rewards": np.asarray(
                        reward_fn(np.asarray(prompt, np.int32), toks),
                        np.float32),
                    "version": int(version), "seed": seed,
                }
                # _inline=False: the ref travels a SIDE CHANNEL (buffer
                # actor -> learner claim) — only a sealed store object
                # is fetchable by a third process
                ref = ray_tpu.put(traj, _inline=False)
                added = ray_tpu.get(self.buffer.add.remote({
                    "key": (idx, local_seq), "version": int(version),
                    "traj": {"ref": ref}}), timeout=60)
                produced += 1
                with self._rollout_lock:
                    st = self._rollout_stats
                    st["trajectories"] += 1
                    st["tokens"] += len(toks)
                    st["truncated"] += int(truncated)
                    st["dup_rejected"] += int(not added["accepted"])
            except Exception:  # noqa: BLE001 — the pool may be mid-
                # failover or draining; a rollout actor retries forever
                with self._rollout_lock:
                    self._rollout_stats["errors"] += 1
                time.sleep(0.1)

    # ---- weight publishing (driver, via the trainer's report stream) --

    def _on_report(self, metrics: dict):
        wr = metrics.pop("weights_ref", None)
        if wr is None:
            return
        t0 = time.monotonic()
        try:
            v = self.pool.publish_weights(
                wr["ref"], version=int(metrics["version"]))
            ray_tpu.get(self.buffer.set_version.remote(v), timeout=60)
            # unpin trajectories whose update is durably checkpointed
            # beyond any resume fallback (bounds buffer + store growth)
            self.buffer.finalize_through.remote(v - self.FINALIZE_LAG)
            if self.pool.wait_version(v, timeout=60.0):
                self._adoption_lat.append(time.monotonic() - t0)
            # bump even on a wait timeout (a dying replica must not
            # deadlock the sync-mode rollout gate)
            self._adopted_version = v
            self._publishes.append((v, time.monotonic()))
        except Exception:  # noqa: BLE001 — a failed publish leaves
            # replicas on the previous version; the next one catches up
            logger.exception("weight publish for version %s failed",
                             metrics.get("version"))

    # ---- lifecycle ----

    def run(self) -> dict:
        """Blocking: rollouts + learner gang to completion. Returns the
        training summary (reward curve, resume/publish accounting,
        buffer conservation stats)."""
        from ray_tpu.train import (
            JaxTrainer, RunConfig, ScalingConfig)

        cfg = self.cfg
        storage = cfg.storage_path or tempfile.mkdtemp(
            prefix="ray_tpu_actor_learner_")
        self._stop.clear()
        self._threads = [
            threading.Thread(target=self._rollout_loop, args=(i,),
                             daemon=True, name=f"rollout-{i}")
            for i in range(cfg.n_rollout_actors)
        ]
        for t in self._threads:
            t.start()
        hp = {
            "model_size": cfg.model_size, "max_len": cfg.max_len,
            "model_seed": cfg.model_seed,
            "prompt_len": cfg.prompt_len, "max_new": cfg.max_new,
            "temperature": cfg.temperature,
            "iterations": cfg.iterations,
            "trajectories_per_iter": cfg.trajectories_per_iter,
            "lr": cfg.lr, "gamma": cfg.gamma, "rho_bar": cfg.rho_bar,
            "c_bar": cfg.c_bar, "clip_eps": cfg.clip_eps,
            "entropy_coeff": cfg.entropy_coeff,
            "publish_every": cfg.publish_every,
            "claim_timeout_s": cfg.claim_timeout_s,
        }
        trainer = JaxTrainer(
            _learner_loop,
            train_loop_config={
                "hp": hp, "buffer": self.buffer,
                "ck_dir": os.path.join(storage, "learner_ckpts"),
                "worker_specs": list(cfg.worker_specs),
            },
            scaling_config=ScalingConfig(
                num_workers=cfg.num_learners,
                resources_per_worker={"CPU": 1}, backend="dcn",
                min_workers=cfg.min_learners,
                devices_per_worker=cfg.learner_devices,
                placement_strategy="PACK"),
            run_config=RunConfig(
                name="actor_learner", storage_path=storage,
                max_failures=cfg.max_failures,
                max_inplace_resumes=cfg.max_inplace_resumes,
                on_report=self._on_report),
        )
        try:
            result = trainer.fit()
        finally:
            self._stop.set()
            for t in self._threads:
                t.join(timeout=30)
        buffer_stats = ray_tpu.get(self.buffer.stats.remote(),
                                   timeout=60)
        rewards = [m["mean_reward"] for m in result.metrics_history
                   if "mean_reward" in m]
        with self._rollout_lock:
            rollout_stats = dict(self._rollout_stats)
        return {
            "result": result,
            "rewards": rewards,
            "error": result.error,
            "resumes": result.resumes,
            "buffer": buffer_stats,
            "rollouts": rollout_stats,
            "publishes": len(self._publishes),
            "final_version": (self._publishes[-1][0]
                              if self._publishes else 0),
            "adoption_latency_s": (
                float(np.mean(self._adoption_lat))
                if self._adoption_lat else None),
        }

    def shutdown(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)
        try:
            ray_tpu.kill(self.buffer)
        except Exception:  # noqa: BLE001
            pass
        if self._own_pool:
            try:
                self.pool.shutdown()
            except Exception:  # noqa: BLE001
                pass
