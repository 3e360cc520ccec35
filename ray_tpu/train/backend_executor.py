"""Gang lifecycle for distributed JAX training.

Reference: `python/ray/train/_internal/backend_executor.py:44`
(`BackendExecutor`: `start:103`, `_create_placement_group:163`,
`_create_rank_world_size_mappings:271`, `start_training:341`,
`get_with_failure_handling:557`). TPU-native backends:

- ``backend="jax"`` (default): every worker joins one **jax.distributed**
  cluster, so a single pjit/shard_map program spans all workers' devices —
  the mesh IS the communication backend (SURVEY §2.7/§2.8 mapping).
  Coordinator address is published through the control-plane KV, mirroring
  the reference's `_setup_torch_process_group` TCP-store rendezvous off
  worker 0. A broken mesh cannot be reformed, so failures here restart
  the whole gang.
- ``backend="dcn"``: every worker is its OWN jax process (one slice
  representative); cross-worker gradient sync rides the gang's cpu
  collective group (`train.dcn_allreduce_grads` over `collective/ring.py`).
  Because no shared mesh spans processes, a dead rank is survivable
  **in-place**: :meth:`heal_inplace` quiesces survivors, heals the gang
  (respawn-or-shrink, then re-grow when capacity returns), reforms the
  collective under a bumped epoch, rebalances dataset-shard assignments,
  and :meth:`start_training` warm-restarts the loops — survivors keep
  their processes, JIT caches, and device state.
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable

import ray_tpu
from ray_tpu._private import config
from ray_tpu.train.worker_group import WorkerGroup

logger = logging.getLogger(__name__)

KV_NS = "train"


# ---- functions shipped to workers (module-level → plain cloudpickle) ----


def _pick_coordinator(worker) -> str:
    """Run on worker 0: bind a free port on this host for jax.distributed."""
    import socket

    from ray_tpu._private.api import _get_worker

    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    host = _get_worker().addr
    return f"{host}:{port}"


def _widen_cpu_worker(devices_per_worker: int | None) -> None:
    """``devices_per_worker`` virtual devices for a CPU worker; must run
    before the backend initialises. A worker's platform is not chosen
    here: the node agent decided it at spawn (the chips of its ``TPU``
    grant, else the CPU — _private/accelerator.py)."""
    import os

    from ray_tpu._private import accelerator

    if devices_per_worker and not accelerator.granted_chips():
        # append (not skip-if-present): xla takes the LAST occurrence, so
        # this overrides any inherited device-count flag from the spawner
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{devices_per_worker}"
        ).strip()


def _claim_device(platform: str | None) -> None:
    """Initialise this worker's backend (after jax.distributed, where
    there is one) and hold ``ScalingConfig.platform`` against what it
    got — the field is a check, not a switch."""
    from ray_tpu._private import accelerator

    got = accelerator.claim_device()["platform"]
    if platform and got != platform:
        raise RuntimeError(
            f"ScalingConfig.platform={platform!r} but this worker came "
            f"up on {got!r}: ask for the chip in resources_per_worker "
            f"(e.g. {{'TPU': 1}}), it cannot be switched to from here")


def _setup_backend(worker, coordinator: str, world_size: int,
                   devices_per_worker: int | None, platform: str | None):
    """Join the jax.distributed cluster (rank = worker_idx)."""
    import jax

    _widen_cpu_worker(devices_per_worker)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=world_size,
        process_id=worker.worker_idx,
        initialization_timeout=120,
    )
    _claim_device(platform)
    worker.state["world_size"] = world_size
    return {
        "rank": jax.process_index(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }


def _setup_backend_local(worker, world_size: int,
                         devices_per_worker: int | None,
                         platform: str | None):
    """dcn backend: standalone jax per worker — no cross-process mesh to
    rendezvous (cross-worker sync rides the gang's cpu collective), which
    is exactly what makes a membership change survivable in-place."""
    import os

    import jax

    _widen_cpu_worker(devices_per_worker)
    _claim_device(platform)
    worker.state["world_size"] = world_size
    return {"pid": os.getpid(), "local_devices": jax.local_device_count()}


def _start_training(worker, fn_blob, config: dict,
                    resume_ckpt_path: str | None, rank: int | None = None,
                    world_size: int | None = None,
                    collective_group: str | None = None,
                    shard_plan: dict | None = None, resume_seq: int = 0):
    """Launch the user train loop on a thread (session.py:144 analog).

    ``rank``/``world_size`` default to the actor's identity (cold start);
    a warm resume passes the post-heal gang position explicitly — after a
    shrink, ranks are compacted and worker_idx is an identity, not a
    rank. ``shard_plan`` maps dataset name -> (blocks, assigned indices)
    — blocks is None for a survivor that already holds the list;
    existing :class:`~ray_tpu.train.session.DataShard` objects in the
    actor's state are REASSIGNED (cursor preserved) rather than rebuilt,
    so survivors of an in-place resume do not restart from epoch 0.
    """
    import threading

    from ray_tpu._private import serialization
    from ray_tpu.train import session as S
    from ray_tpu.train.checkpoint import Checkpoint

    fn = serialization.unpack_payload(fn_blob)
    if rank is None:
        rank = worker.worker_idx
    if world_size is None:
        world_size = worker.state.get("world_size", 1)

    shards = worker.state.setdefault("dataset_shards", {})
    for name, (blocks, indices) in (shard_plan or {}).items():
        sh = shards.get(name)
        if sh is None:
            if blocks is None:
                # the driver believed this worker already held the
                # blocks; surface the inconsistency as a typed failure
                # (→ gang fallback) instead of a later IndexError
                raise RuntimeError(
                    f"dataset {name!r}: no blocks shipped to a worker "
                    f"with no existing shard")
            shards[name] = S.DataShard(name, blocks, indices)
        else:
            sh.reassign(indices, blocks=blocks)
    if resume_seq and resume_ckpt_path is None:
        # warm resume with NO checkpoint: the model restarts from
        # scratch, so the training that consumed these blocks is lost —
        # cursors have nothing to anchor to and must restart with the
        # model or this epoch trains on a strict subset of the data
        for sh in shards.values():
            sh.load_state({"epoch": 0, "consumed": []})

    sess = S._init_session(
        world_rank=rank,
        world_size=world_size,
        resume_checkpoint=(
            Checkpoint(resume_ckpt_path) if resume_ckpt_path else None
        ),
        collective_group=collective_group,
        resume_seq=resume_seq,
        dataset_shards=shards,
    )

    def _run():
        try:
            fn(config or {})
        except BaseException as e:  # noqa: BLE001 — surfaced to the driver
            sess.error = e
        finally:
            sess.finished.set()

    t = threading.Thread(target=_run, daemon=True, name="train_loop")
    worker.state["train_thread"] = t
    t.start()
    return True


def _next_result(worker, timeout: float = 10.0):
    """Poll one report from the session queue (get_next_results analog)."""
    import queue as _q

    from ray_tpu.train import session as S

    sess = S._get_session()
    deadline = time.monotonic() + timeout
    while True:
        try:
            item = sess.results.get(timeout=0.1)
            return {"type": "report", **item}
        except _q.Empty:
            if sess.finished.is_set() and sess.results.empty():
                if sess.error is not None:
                    import traceback

                    tb = "".join(traceback.format_exception(sess.error))
                    # the exception TYPE rides as data so the driver can
                    # classify (e.g. CollectiveAbortError => retriable
                    # infra failure) without probing the traceback text;
                    # the path attribute (CheckpointCorruptError) lets
                    # it discard the checkpoint that actually failed
                    return {"type": "error", "error": tb,
                            "error_type": type(sess.error).__name__,
                            "error_path": str(
                                getattr(sess.error, "path", "") or "")}
                return {"type": "finished"}
            if time.monotonic() > deadline:
                return {"type": "pending"}


def _state_empty(worker):
    """True when this process has never run a backend setup — the marker
    of a runtime-RESTARTED actor: same actor id, fresh process, empty
    ``worker.state`` (the control plane re-runs only ``__init__``)."""
    return "world_size" not in worker.state


def _quiesce(worker, timeout: float):
    """Unwind this survivor's old train loop before a warm resume.

    Aborts every live collective incarnation in the process (waking
    threads blocked in recvs), drains unconsumed reports (the queue(1)
    backpressure could otherwise park the thread in ``report`` forever),
    and waits for the loop thread to exit. ``ok=False`` means the
    survivor is wedged in user code — the driver falls back to a gang
    restart rather than double-running loops in one process."""
    import os
    import queue as _q

    from ray_tpu.collective import collective as col
    from ray_tpu.train import session as S

    sess = S._session
    t = worker.state.get("train_thread")
    if sess is None and t is None:
        return {"ok": True, "fresh": True, "pid": os.getpid()}
    col.abort_all_local("in-place resume: driver quiescing survivors")
    deadline = time.monotonic() + timeout
    done = False
    while True:
        if sess is not None:
            while True:  # drain report backpressure
                try:
                    sess.results.get_nowait()
                except _q.Empty:
                    break
        done = sess.finished.wait(0.2) if sess is not None else True
        if done or time.monotonic() > deadline:
            break
    if t is not None and done:
        t.join(timeout=max(1.0, deadline - time.monotonic()))
    alive = bool(t is not None and t.is_alive())
    etype = None
    if sess is not None and sess.error is not None:
        etype = type(sess.error).__name__
    return {"ok": bool(done and not alive), "pid": os.getpid(),
            "error_type": etype}


def _gather_tolerant(refs: list, timeout: float) -> list:
    """Fetch every ref under ONE shared deadline, returning the raised
    exception (instead of raising) for refs that fail — per-rank failure
    must not sink the whole round, and detection cost must not scale
    with the number of dead ranks."""
    deadline = time.monotonic() + timeout
    out: list[Any] = []
    for ref in refs:
        try:
            out.append(ray_tpu.get(
                ref, timeout=max(0.1, deadline - time.monotonic())))
        except Exception as e:  # noqa: BLE001 — dead/unreachable rank
            out.append(e)
    return out


class TrainingFailedError(RuntimeError):
    """Raised by the driver's result loop. ``error_type`` carries the
    worker exception's TYPE name (typed classification, no traceback
    probing); ``error_path`` the failing checkpoint's path when the type
    is CheckpointCorruptError; ``dead_ranks`` lists gang positions whose
    result fetch failed at the actor layer (process death)."""

    error_type: str = ""
    error_path: str = ""
    dead_ranks: list[int]

    def __init__(self, *args):
        super().__init__(*args)
        self.dead_ranks = []


class BackendExecutor:
    """Start a worker gang, wire the chosen backend, stream results; the
    trainer drives restarts — and, on the dcn backend, in-place resumes."""

    def __init__(self, num_workers: int,
                 resources_per_worker: dict | None = None,
                 devices_per_worker: int | None = None,
                 platform: str | None = None,
                 strategy: str = "SPREAD",
                 backend: str = "jax",
                 min_workers: int | None = None,
                 datasets: dict | None = None,
                 max_restarts: int = 0):
        if backend not in ("jax", "dcn"):
            raise ValueError(f"backend must be 'jax' or 'dcn', "
                             f"got {backend!r}")
        self.num_workers = num_workers
        self.target_workers = num_workers
        self.min_workers = min_workers if min_workers is not None \
            else num_workers
        self.resources_per_worker = resources_per_worker
        self.devices_per_worker = devices_per_worker
        self.platform = platform
        self.strategy = strategy
        self.backend = backend
        # >0 makes heal()'s respawn branch reachable: a dead rank gets a
        # same-slot replacement before the gang considers shrinking
        self.max_restarts = max_restarts
        self.datasets = dict(datasets or {})
        self.worker_group: WorkerGroup | None = None
        self.group_name: str | None = None
        self.start_count = 0  # gang cold-starts (tests assert no re-entry)
        # dataset name -> {actor_id: [block indices]}
        self._assignments: dict[str, dict[bytes, list[int]]] = {}
        # actor ids whose DataShards already hold the block lists (so
        # warm resumes re-send index lists, not the dataset)
        self._seeded_ids: set[bytes] = set()
        # actor_id -> in-flight _next_result ref whose fetch timed out
        # while the rank was alive: re-fetched next round (the report is
        # already off the worker's queue — dropping the ref loses it)
        self._result_refs: dict[bytes, Any] = {}

    def start(self):
        self.start_count += 1
        self.worker_group = WorkerGroup(
            self.num_workers,
            resources_per_worker=self.resources_per_worker,
            strategy=self.strategy,
            max_restarts=self.max_restarts,
        )
        if self.backend == "dcn":
            infos = self.worker_group.execute(
                _setup_backend_local, self.num_workers,
                self.devices_per_worker, self.platform, timeout=180.0,
            )
            self.group_name = self.worker_group.init_collective(
                link_tx=self._live_link_tx())
        else:
            coordinator = self.worker_group.execute_single(
                0, _pick_coordinator)
            # Bounded: a half-formed jax.distributed rendezvous must fail
            # fast so the trainer's gang-restart logic can take over.
            infos = self.worker_group.execute(
                _setup_backend, coordinator, self.num_workers,
                self.devices_per_worker, self.platform, timeout=180.0,
            )
        self._seed_assignments()
        logger.info("train backend up (%s): %s", self.backend, infos)
        return infos

    @staticmethod
    def _live_link_tx() -> dict[str, float] | None:
        """Cluster-wide per-peer tx byte tally from the head's metric
        rows — the signal link-aware ring formation orders ranks by.
        Driver-local accounting only sees this process's sends, which is
        blind to serving/bulk traffic between agents (the colocation
        case); the head aggregates every node's export. None (fall back
        to local accounting, then identity order) when the head is
        unreachable — placement is an optimization, never a gate."""
        try:
            from ray_tpu._private.api import _get_worker
            from ray_tpu.autoscaler.demand_scheduler import link_tx_by_peer

            rows = _get_worker().head.call("get_metrics", {}) or []
            tx = link_tx_by_peer(rows)
            return tx or None
        except Exception:  # noqa: BLE001 — best-effort signal
            return None

    # ---- dataset shard assignment (driver-side source of truth) ----

    def _seed_assignments(self):
        self._assignments = {}
        self._seeded_ids = set()
        workers = self.worker_group.workers
        for name, blocks in self.datasets.items():
            per: dict[bytes, list[int]] = {w._actor_id: []
                                           for w in workers}
            for i in range(len(blocks)):
                per[workers[i % len(workers)]._actor_id].append(i)
            self._assignments[name] = per

    def _rebalance_assignments(self):
        """Re-split after a membership change: survivors keep their
        indices where possible (their DataShard cursors stay valid);
        orphaned indices (dead ranks') go to the lightest-loaded workers
        first, then loads are LEVELLED — excess blocks move off
        overloaded survivors so a worker re-grown after an earlier
        shrink gets real work instead of an empty assignment (a moved
        index restarts its epoch cursor on the adoptee: at-least-once,
        same as orphan adoption). Most-recently-adopted indices move
        first, so a survivor's longest-held blocks keep their cursors."""
        workers = self.worker_group.workers
        for name, per in self._assignments.items():
            n_blocks = len(self.datasets[name])
            keep = {w._actor_id: list(per.get(w._actor_id, []))
                    for w in workers}
            assigned = set()
            for v in keep.values():
                assigned.update(v)
            orphans = [i for i in range(n_blocks) if i not in assigned]
            for i in orphans:
                # ties prefer members with no prior assignment (a fresh
                # respawn/grow), so a same-size replacement re-adopts
                # its predecessor's blocks instead of a survivor
                # picking up extra at-least-once re-reads
                tgt = min(
                    range(len(workers)),
                    key=lambda k: (len(keep[workers[k]._actor_id]),
                                   workers[k]._actor_id in per, k),
                )
                keep[workers[tgt]._actor_id].append(i)
            lo = n_blocks // len(workers)  # floor: the minimum fair share
            for taker in [v for v in keep.values() if len(v) < lo]:
                while len(taker) < lo:
                    donor = max(keep.values(), key=len)
                    if len(donor) <= lo:
                        break  # can't happen while sum == n_blocks
                    taker.append(donor.pop())
            self._assignments[name] = keep

    def _shard_plan(self, w) -> dict:
        """One worker's dataset assignments. Block lists are O(dataset)
        and immutable, so they ship only on a worker's FIRST plan (fresh
        actor); survivors of an in-place resume get blocks=None and keep
        the list their DataShard already holds — a resume re-sends a few
        indices per dataset, not the data."""
        fresh = w._actor_id not in self._seeded_ids
        return {
            name: (self.datasets[name] if fresh else None,
                   per.get(w._actor_id, []))
            for name, per in self._assignments.items()
        }

    # ---- training lifecycle ----

    def start_training(self, train_fn: Callable, config: dict,
                       resume_ckpt_path: str | None = None, *,
                       resume_seq: int = 0):
        from ray_tpu._private import serialization

        # in-flight result refs belong to the PREVIOUS session's loops;
        # pairing them with the new incarnation would desync lockstep
        self._result_refs.clear()
        blob = serialization.pack_callable(train_fn)
        workers = self.worker_group.workers
        refs = [
            w.execute.remote(
                _start_training, blob, config, resume_ckpt_path, r,
                len(workers), self.group_name, self._shard_plan(w),
                resume_seq,
            )
            for r, w in enumerate(workers)
        ]
        ray_tpu.get(refs, timeout=300)
        # only after the gang-wide get: a failed dispatch retries with
        # blocks included, which the worker side handles idempotently
        self._seeded_ids = {w._actor_id for w in workers}

    def next_results(self, timeout: float = 10.0) -> list[dict]:
        """One lockstep round of per-worker results.

        Dead-rank tolerant: an actor-layer failure for one rank becomes a
        typed ``{"type": "dead"}`` entry instead of sinking the whole
        round — the driver needs the SURVIVORS' typed errors to decide
        between an in-place resume and a gang restart. A failed fetch is
        cross-checked with a ping first (same starvation hazard as the
        quiesce gather: one slow fetch exhausts the shared deadline and
        would mark every later, healthy rank dead). An alive rank's
        timed-out ref is KEPT and re-fetched next round — the worker
        already popped that report off its session queue, so dropping
        the ref would lose the report (and any checkpoint riding it)
        and desync _drain's lockstep accounting."""
        workers = self.worker_group.workers
        refs = []
        for w in workers:
            ref = self._result_refs.pop(w._actor_id, None)
            if ref is None:
                ref = w.execute.remote(_next_result, timeout)
            refs.append(ref)
        results = _gather_tolerant(refs, timeout + 60)
        lost = [r for r, res in enumerate(results)
                if isinstance(res, Exception)]
        if lost:
            alive = self.worker_group.probe(timeout=5.0, indices=lost)
            for r, up in zip(lost, alive):
                if up:
                    self._result_refs[workers[r]._actor_id] = refs[r]
                    results[r] = {"type": "pending"}
        return [
            {"type": "dead", "error": f"{type(r).__name__}: {r}"}
            if isinstance(r, Exception) else r
            for r in results
        ]

    # ---- in-place elastic resume (dcn backend) ----

    def supports_inplace_resume(self) -> bool:
        return self.backend == "dcn" and self.worker_group is not None

    def heal_inplace(self, *, regrow: bool = True) -> int:
        """Make the gang trainable again WITHOUT tearing it down.

        1. Quiesce survivors (abort live incarnations, join old loop
           threads) — a wedged survivor raises, falling back to the gang
           path. 2. `WorkerGroup.heal()` (respawn-or-shrink). 3. Re-grow
           toward the target world while capacity allows. 4. Local
           backend setup on fresh members only. 5. `reform_collective()`
           under a bumped epoch. 6. Rebalance dataset-shard assignments.
        Returns the new world size; survivors' processes, JIT caches, and
        device state are untouched throughout.
        """
        if not self.supports_inplace_resume():
            raise RuntimeError(
                f"in-place resume unsupported: backend={self.backend!r} "
                f"(a broken jax.distributed mesh cannot be reformed)")
        wg = self.worker_group
        quiesce_s = float(config.get("train_quiesce_timeout_s"))
        # keyed by the stable actor id, NOT id(handle): dead handles
        # are GC'd during heal() and CPython reuses their addresses,
        # which would misclassify a fresh spawn as a survivor
        old_ids = {w._actor_id for w in wg.workers}

        refs = [w.execute.remote(_quiesce, quiesce_s) for w in wg.workers]
        results = _gather_tolerant(refs, quiesce_s + 30)
        wedged = [r for r, res in enumerate(results)
                  if not isinstance(res, Exception) and not res.get("ok")]
        # a failed fetch usually means the rank is dead (heal() reaps
        # it), but a slow-but-alive survivor could also starve the shared
        # deadline — cross-check with a ping: alive + unquiesced = wedged
        # (warm-restarting it would double-run train loops in one
        # process)
        lost = [r for r, res in enumerate(results)
                if isinstance(res, Exception)]
        if lost:
            alive = wg.probe(timeout=5.0, indices=lost)
            wedged.extend(r for r, up in zip(lost, alive) if up)
        if wedged:
            wedged.sort()
            raise RuntimeError(
                f"in-place resume: survivor ranks {wedged} still running "
                f"user code after {quiesce_s}s quiesce")

        world = wg.heal(wait_restart_s=quiesce_s)
        if regrow and world < self.target_workers:
            # capacity returned = the placement bundles are fillable again
            world = wg.grow(self.target_workers)
        if world < self.min_workers:
            raise RuntimeError(
                f"in-place resume: world size {world} below the elastic "
                f"floor min_workers={self.min_workers}")

        fresh = [w for w in wg.workers if w._actor_id not in old_ids]
        # a runtime-restarted actor (max_restarts > 0) KEPT its actor id
        # but lost its process state — actor-id bookkeeping would treat
        # it as an intact survivor (no backend setup, blocks withheld),
        # wedging every subsequent resume. Detect by state emptiness and
        # reclassify as a fresh member.
        carried = [w for w in wg.workers if w._actor_id in old_ids]
        if carried:
            reborn = [
                w for w, empty in zip(carried, ray_tpu.get(
                    [w.execute.remote(_state_empty) for w in carried],
                    timeout=60))
                if empty
            ]
            if reborn:
                fresh.extend(reborn)
                for w in reborn:
                    self._seeded_ids.discard(w._actor_id)
        if fresh:
            ray_tpu.get(
                [w.execute.remote(_setup_backend_local, world,
                                  self.devices_per_worker, self.platform)
                 for w in fresh],
                timeout=180,
            )
        # no world-size broadcast: every post-heal start_training passes
        # rank/world explicitly (the state default is a cold-start path),
        # so a gang-wide RPC round here would buy nothing on the
        # latency-critical resume
        wg.reform_collective(
            timeout=float(config.get("collective_reform_timeout_s")),
            link_tx=self._live_link_tx())
        self._rebalance_assignments()
        self.num_workers = world
        logger.info(
            "in-place heal complete: world %d (%d fresh member(s), "
            "%d survivor(s) kept their processes)",
            world, len(fresh), world - len(fresh))
        return world

    def shutdown(self):
        if self.worker_group is not None:
            self.worker_group.shutdown()
            self.worker_group = None
