"""JaxTrainer: the user-facing training service.

Reference: `python/ray/train/base_trainer.py:555` (`fit`),
`data_parallel_trainer.py:58` (`DataParallelTrainer`), failure handling
`backend_executor.py:557/:618`. TPU-native: the "backend" is either one
jax.distributed cluster per run (`backend="jax"`) or one standalone jax
process per worker synced over the gang's DCN collective
(`backend="dcn"`); DP/FSDP/TP/SP strategies are mesh-axis configuration
inside the user loop, not separate trainer subclasses.

Failure handling is two-tier:

- **in-place resume** (dcn backend, `RAY_TPU_TRAIN_INPLACE_RESUME`, the
  common path): survivors keep their processes/JIT caches/device state;
  the executor heals the gang (respawn-or-shrink, re-grow when capacity
  returns), reforms the collective, rebalances dataset shards, and
  warm-restarts the loops from the latest valid checkpoint. Budgeted by
  `RunConfig.max_inplace_resumes`.
- **gang restart** (the fallback, and the only path for a broken
  jax.distributed mesh): tear everything down, re-place, re-rendezvous,
  resume from checkpoint. Budgeted by `RunConfig.max_failures`.

Both paths are counted in `train_resume_total{mode}` with the last
resume's latency in `train_resume_seconds{mode}`.
"""

from __future__ import annotations

import logging
import os
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from ray_tpu._private import config as _config
from ray_tpu._private.worker import RayActorError, GetTimeoutError
from ray_tpu.train.backend_executor import (
    BackendExecutor,
    TrainingFailedError,
)
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager

logger = logging.getLogger(__name__)

# worker-loop exception TYPES that mean "the infrastructure failed", not
# "the user's code is wrong" — retriable under the failure budgets. The
# worker reports the typed name, so no traceback-text probing is needed.
INFRA_ERROR_TYPES = frozenset({
    "CollectiveAbortError",    # a peer died mid-collective
    "CollectiveTimeoutError",  # a stranded collective op (lost frames)
    # NOT plain "TimeoutError": collective stalls raise the typed
    # CollectiveTimeoutError and object fetches raise GetTimeoutError,
    # so a bare TimeoutError is almost certainly the user's own code —
    # it must propagate, not burn the failure budgets on retries.
    "GetTimeoutError",         # an object fetch outlived its deadline
    "WorkerDiedError",         # a rank's actor vanished (synthesized)
    "InjectedFault",           # chaos-injected in-process crash
    "CheckpointCorruptError",  # torn/bit-rotted checkpoint on restore
})

_resume_metrics = None


def _get_resume_metrics():
    global _resume_metrics
    if _resume_metrics is None:
        from ray_tpu.util import metrics as M

        _resume_metrics = {
            "total": M.Counter(
                "train_resume_total",
                "training resumes by mode (inplace = survivors kept "
                "their processes; gang = full teardown + restart)",
                tag_keys=("mode",),
            ),
            "latency": M.Gauge(
                "train_resume_seconds",
                "latency of the last training resume",
                tag_keys=("mode",),
            ),
        }
    return _resume_metrics


def _record_resume(mode: str, seconds: float) -> None:
    try:
        m = _get_resume_metrics()
        m["total"].inc(1, {"mode": mode})
        m["latency"].set(seconds, {"mode": mode})
    except Exception:  # noqa: BLE001 — accounting never blocks recovery
        pass


@dataclass
class ScalingConfig:
    """Reference: air/config.py ScalingConfig.

    ``backend="dcn"`` runs one standalone jax process per worker with
    cross-worker sync over the gang's collective group (the elastic,
    in-place-resumable mode); ``"jax"`` spans one jax.distributed mesh
    across workers. ``min_workers`` is the elastic floor: an in-place
    resume may shrink the gang to it while capacity is gone (None = not
    elastic; any shrink forces a gang restart). ``max_restarts`` > 0
    lets heal() RESPAWN a dead rank into its placement slot (that many
    times total) before it resorts to shrinking — the world size is
    preserved, survivors' own blocks never move (their cursors stay
    put), and the dead rank's blocks re-land on the emptiest members
    first (normally all on the replacement; adopted blocks restart
    unconsumed — at-least-once)."""

    num_workers: int = 1
    resources_per_worker: dict = field(default_factory=lambda: {"CPU": 1})
    devices_per_worker: int | None = None  # virtual CPU devices (tests)
    # a CHECK, not a switch: a worker runs on the chips of its "TPU"
    # grant in resources_per_worker, else on the CPU; startup fails if
    # that is not the platform named here (None = no check)
    platform: str | None = None
    placement_strategy: str = "SPREAD"
    backend: str = "jax"  # "jax" (one mesh) | "dcn" (per-worker jax)
    min_workers: int | None = None
    max_restarts: int = 0


@dataclass
class RunConfig:
    """Reference: air/config.py RunConfig + FailureConfig.

    The two failure budgets are separate on purpose: an in-place resume
    costs ~a reform (cheap, common), a gang restart costs a full
    re-place + re-rendezvous + cold JIT (expensive, rare) — so the cheap
    path gets the bigger allowance and never eats the gang budget."""

    name: str = "train_run"
    storage_path: str | None = None
    max_failures: int = 0
    checkpoint_num_to_keep: int = 2
    max_inplace_resumes: int = 8
    # driver-side callback invoked once per completed lockstep step with
    # rank 0's metrics dict, BEFORE it enters metrics_history — a
    # streaming consumer (e.g. the actor-learner loop publishing the
    # weights ref a learner reported) may mutate/pop keys it consumes.
    # Exceptions are logged, never fatal to training.
    on_report: Callable[[dict], None] | None = None


@dataclass
class Result:
    """Reference: air/result.py Result."""

    metrics: dict | None
    checkpoint: Checkpoint | None
    metrics_history: list[dict]
    error: str | None = None
    # resume accounting: {"inplace": n, "gang": m}
    resumes: dict | None = None


class JaxTrainer:
    """Gang-scheduled SPMD training over a jax.distributed mesh or a
    DCN-synced gang of per-worker jax processes.

    `train_loop_per_worker(config)` runs identically on every worker
    (single-program multi-host, the JAX model); it reports via
    `ray_tpu.train.session.report(metrics, checkpoint=...)`. With
    ``datasets={"train": blocks}``, each worker reads its elastic shard
    via `session.get_dataset_shard("train")`.
    """

    def __init__(self, train_loop_per_worker: Callable[[dict], Any],
                 *, train_loop_config: dict | None = None,
                 scaling_config: ScalingConfig | None = None,
                 run_config: RunConfig | None = None,
                 datasets: dict | None = None,
                 resume_from_checkpoint: Checkpoint | None = None):
        self.train_fn = train_loop_per_worker
        self.config = train_loop_config or {}
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_from_checkpoint = resume_from_checkpoint

    # ---- failure-path helpers ----

    @staticmethod
    def _shutdown_quietly(executor: BackendExecutor | None) -> None:
        """Teardown must never mask the failure that caused it: a raise
        out of `shutdown()` (dead agents, half-closed RPC) is logged and
        swallowed so the ORIGINAL gang error always propagates."""
        if executor is None:
            return
        try:
            executor.shutdown()
        except Exception as e:  # noqa: BLE001 — teardown is best-effort
            logger.warning(
                "executor shutdown raised (%s: %s); suppressing so the "
                "original failure propagates", type(e).__name__, e)

    def _resume_checkpoint(self, ckpt_mgr: CheckpointManager,
                           suspect: Checkpoint | None):
        """Newest checkpoint that passes checksum verification; when the
        failure WAS a corrupt restore, the checkpoint the run actually
        restored from (``suspect``) is dropped first so the retry falls
        back — NOT whatever is latest, which may be a newer, perfectly
        good checkpoint registered after the restore began."""
        if suspect is not None:
            seed = self.resume_from_checkpoint
            if seed is not None and suspect.path == seed.path:
                # the user's seed checkpoint lives outside the manager:
                # drop our reference, never rmtree the user's data
                logger.warning(
                    "resume_from_checkpoint failed restore (%s); dropping "
                    "it", suspect.path)
                self.resume_from_checkpoint = None
            elif ckpt_mgr.owns(suspect):
                logger.warning(
                    "discarding checkpoint that failed restore: %s",
                    suspect.path)
                ckpt_mgr.discard(suspect)
            else:
                # a user-loop restore of a path this run doesn't manage:
                # deleting it isn't ours to do, and the managed chain is
                # not implicated
                logger.warning(
                    "corrupt checkpoint %s is outside this run's "
                    "manager; leaving it in place", suspect.path)
        # read-proportional: shard crcs verify lazily worker-side during
        # restore; a full driver-side crc of every archive would re-read
        # the whole checkpoint on the latency-critical in-place path
        valid = ckpt_mgr.latest_valid(full=False)
        if valid is not None:
            return valid
        # the user-supplied seed checkpoint is outside the manager, so it
        # is never auto-discarded — verify it too, or a corrupt one would
        # be re-restored on every retry until the budgets are exhausted
        if self.resume_from_checkpoint is not None:
            from ray_tpu.train.checkpoint import (
                CheckpointCorruptError, verify_checkpoint)

            try:
                verify_checkpoint(self.resume_from_checkpoint.path)
            except CheckpointCorruptError as e:
                logger.warning(
                    "resume_from_checkpoint failed verification (%s); "
                    "dropping it and restarting from scratch", e)
                self.resume_from_checkpoint = None
        return self.resume_from_checkpoint

    def fit(self) -> Result:
        """Reference base_trainer.py:555: run to completion. Worker
        failure resumes in-place when the backend supports it, else
        restarts the whole gang — each under its own budget."""
        storage = self.run_config.storage_path or tempfile.mkdtemp(
            prefix=f"ray_tpu_{self.run_config.name}_"
        )
        ckpt_mgr = CheckpointManager(
            os.path.join(storage, "checkpoints"),
            num_to_keep=self.run_config.checkpoint_num_to_keep,
        )
        gang_left = self.run_config.max_failures
        inplace_left = self.run_config.max_inplace_resumes
        resume = self.resume_from_checkpoint
        history: list[dict] = []
        resumes = {"inplace": 0, "gang": 0}
        executor: BackendExecutor | None = None
        gang_t0: float | None = None  # times re-place + re-rendezvous

        while True:
            try:
                if executor is None:
                    executor = BackendExecutor(
                        self.scaling.num_workers,
                        resources_per_worker=(
                            self.scaling.resources_per_worker),
                        devices_per_worker=self.scaling.devices_per_worker,
                        platform=self.scaling.platform,
                        strategy=self.scaling.placement_strategy,
                        backend=self.scaling.backend,
                        min_workers=self.scaling.min_workers,
                        datasets=self.datasets,
                        max_restarts=self.scaling.max_restarts,
                    )
                    executor.start()
                    if gang_t0 is not None:
                        _record_resume("gang", time.monotonic() - gang_t0)
                        gang_t0 = None
                executor.start_training(
                    self.train_fn, self.config,
                    resume_ckpt_path=resume.path if resume else None,
                    resume_seq=resumes["inplace"] + resumes["gang"],
                )
                final = self._drain(executor, ckpt_mgr, history)
                self._shutdown_quietly(executor)
                return Result(
                    # full verify: a checkpoint torn on the FINAL step is
                    # never re-restored by the run, so without this the
                    # caller would be handed the corrupt one while an
                    # older valid checkpoint sits unused in the manager
                    metrics=final, checkpoint=ckpt_mgr.latest_valid(),
                    metrics_history=history, resumes=dict(resumes),
                )
            except (RayActorError, GetTimeoutError, TimeoutError,
                    RuntimeError) as e:
                # TimeoutError covers driver-side infra deadlines (e.g.
                # CollectiveTimeoutError out of the start()/reform
                # rendezvous) — user code never runs on the driver here,
                # so a timeout in this block is never a user error
                # Infra failures (peer death mid-collective, lost actors,
                # torn checkpoints, injected chaos) are retriable under
                # the failure budgets; anything else the user loop raised
                # is a user error and propagates. Classified by the TYPED
                # error_type the worker reported, not a traceback probe.
                etype = getattr(e, "error_type", "") \
                    if isinstance(e, TrainingFailedError) else ""
                infra = (not isinstance(e, TrainingFailedError)
                         or etype in INFRA_ERROR_TYPES
                         or bool(getattr(e, "dead_ranks", [])))
                can_inplace = (
                    infra
                    and executor is not None
                    and executor.supports_inplace_resume()
                    and inplace_left > 0
                    and bool(_config.get("train_inplace_resume"))
                )
                if isinstance(e, TrainingFailedError) and not (
                        infra and (gang_left > 0 or can_inplace)):
                    self._shutdown_quietly(executor)
                    raise
                # NOT `or resume`: _resume_checkpoint may have just
                # discarded (rmtree'd) the checkpoint `resume` points at;
                # None here legitimately means "restart from scratch"
                # a named corrupt checkpoint is actionable regardless of
                # which rank's error won the classification (a peer's
                # collective abort often outranks the corrupt-restore
                # report itself); only a path-less CheckpointCorruptError
                # falls back to blaming the resume checkpoint
                suspect = None
                epath = getattr(e, "error_path", "")
                if epath:
                    suspect = Checkpoint(epath)
                elif etype == "CheckpointCorruptError":
                    suspect = resume
                resume = self._resume_checkpoint(ckpt_mgr, suspect)
                if can_inplace:
                    t0 = time.monotonic()
                    try:
                        world = executor.heal_inplace()
                    except Exception as he:  # noqa: BLE001 — fall back
                        logger.warning(
                            "in-place resume failed (%s: %s); falling "
                            "back to gang restart",
                            type(he).__name__, he)
                        if isinstance(e, TrainingFailedError) \
                                and gang_left <= 0:
                            # the in-place claim is void and the gang
                            # budget is spent: raise exactly as the jax
                            # backend would, instead of demoting the
                            # failure to a Result.error string
                            self._shutdown_quietly(executor)
                            raise e
                    else:
                        inplace_left -= 1
                        resumes["inplace"] += 1
                        _record_resume("inplace", time.monotonic() - t0)
                        logger.warning(
                            "worker gang failed (%s); resumed IN-PLACE at "
                            "world %d (%d in-place resumes left) from %s",
                            e, world, inplace_left, resume)
                        continue
                self._shutdown_quietly(executor)
                executor = None
                if gang_left <= 0:
                    return Result(
                        metrics=history[-1] if history else None,
                        checkpoint=ckpt_mgr.latest_valid(),
                        metrics_history=history,
                        error=f"training failed: {e}",
                        resumes=dict(resumes),
                    )
                gang_left -= 1
                resumes["gang"] += 1
                gang_t0 = time.monotonic()
                logger.warning(
                    "worker gang failed (%s); restarting (%d retries left) "
                    "from %s", e, gang_left, resume,
                )

    def _drain(self, executor: BackendExecutor, ckpt_mgr: CheckpointManager,
               history: list[dict]) -> dict | None:
        """Lockstep result loop (reference TrainingIterator semantics).

        Reports are buffered per rank; one training step is recorded only
        once every rank has reported it, with rank 0's metrics as the
        authoritative copy — a slow worker can't cause duplicate or
        out-of-rank history entries. A dead rank or a worker error raises
        a typed TrainingFailedError carrying `error_type` (preferring the
        survivors' CollectiveAbortError over a generic death, since the
        type drives the in-place-vs-gang resume decision) and
        `dead_ranks`."""
        from collections import deque

        n = executor.num_workers
        pending = [deque() for _ in range(n)]
        finished = [False] * n
        final = None
        while True:
            rounds = executor.next_results(timeout=15.0)
            dead = [r for r, res in enumerate(rounds)
                    if res["type"] == "dead"]
            errors = [(r, res) for r, res in enumerate(rounds)
                      if res["type"] == "error"]
            if errors or dead:
                typed = next(
                    (res for _, res in errors
                     if res.get("error_type") == "CollectiveAbortError"),
                    None)
                pick = typed or (errors[0][1] if errors else None)
                if pick is not None:
                    err = TrainingFailedError(pick["error"])
                    err.error_type = pick.get("error_type", "")
                    # the corrupt-checkpoint path is harvested from ANY
                    # rank's report, not just the picked one: a peer's
                    # CollectiveAbortError may win the classification
                    # while one rank is the only witness of the torn
                    # checkpoint — losing its path would re-restore the
                    # same corrupt checkpoint on every retry
                    err.error_path = next(
                        (res.get("error_path", "") for _, res in errors
                         if res.get("error_type") ==
                         "CheckpointCorruptError"
                         and res.get("error_path")),
                        pick.get("error_path", ""))
                else:
                    err = TrainingFailedError(
                        f"worker rank(s) {dead} died: "
                        f"{rounds[dead[0]]['error']}")
                    err.error_type = "WorkerDiedError"
                err.dead_ranks = dead
                raise err
            for rank, res in enumerate(rounds):
                if res["type"] == "finished":
                    finished[rank] = True
                elif res["type"] == "report":
                    pending[rank].append(res)
            while all(pending):
                step_reports = [q.popleft() for q in pending]
                metrics = step_reports[0]["metrics"]  # true rank 0
                cb = self.run_config.on_report
                if cb is not None:
                    try:
                        cb(metrics)
                    except Exception:  # noqa: BLE001 — a consumer bug
                        logger.exception(  # must not kill training
                            "RunConfig.on_report callback failed")
                history.append(metrics)
                final = metrics
                ckpt = next(
                    (r.get("checkpoint") for r in step_reports
                     if r.get("checkpoint") is not None), None,
                )
                if ckpt is not None:
                    ckpt_mgr.register(ckpt, metrics)
            if all(finished):
                if any(pending):
                    raise TrainingFailedError(
                        "workers reported unequal numbers of results: "
                        f"{[len(q) for q in pending]} undrained per rank"
                    )
                return final
