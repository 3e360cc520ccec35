"""Central config-flag system.

Reference: `src/ray/common/ray_config_def.h:18` — ~200 `RAY_CONFIG(type,
name, default)` macros overridable via env vars. Same mechanism here:
every tunable below reads `RAY_TPU_<UPPER_NAME>` at first access, parsed
to the default's type; `_system_config` dicts passed to `ray_tpu.init`
override programmatically (propagated head -> workers via env, like the
reference's GCS-stored system config).
"""

from __future__ import annotations

import os
import threading
from typing import Any

_DEFS: dict[str, Any] = {
    # -- node agent / data plane --
    "object_transfer_chunk_bytes": 4 * 1024 * 1024,
    "idle_worker_cull_s": 60.0,          # ray_config_def.h:542 analog
    "task_spill_max_forwards": 2,
    "locality_min_bytes": 1024 * 1024,  # prefer data-local nodes above this
    # hybrid policy (hybrid_scheduling_policy.h:29 analog): stay local under
    # this critical-resource utilization; tie-break among top-k by seed
    "scheduler_hybrid_threshold": 0.75,
    "scheduler_top_k": 3,
    "scheduler_use_native": True,        # C++ picker; False = pure Python
    "dep_lost_reconstruct_s": 10.0,
    "spill_high_fraction": 0.8,          # spill primaries above this fill
    "spill_low_fraction": 0.5,           # ...until back under this
    "worker_register_timeout_s": 60.0,
    # how long an actor's constructor may run: the agent fails the actor
    # past it, and a caller waits that long (plus the register timeout)
    # for the actor before its call fails. Was 120 s at the agent and
    # 60 s at callers; on a four-chip host the 1B LLMPool's constructor
    # (weights on the CPU, then four replica starts) outlived the 60.
    "actor_create_timeout_s": 300.0,
    # pull admission (pull_manager.py; reference pull_manager.h:52)
    "pull_max_active": 8,
    "pull_admission_watermark": 0.8,
    # outbound transfer pacing (the pull-based analog of reference
    # push_manager.h:29 per-peer in-flight chunk windows): bytes of
    # object chunks one node will serve CONCURRENTLY to one peer
    "transfer_outbound_window_bytes": 32 * 1024 * 1024,
    # cross-host pull pipelining: concurrent in-flight chunk requests
    # per pull (sized so depth * chunk == the 32MB outbound window —
    # the sender paces at exactly the window, the puller keeps the pipe
    # full instead of paying one RTT per 4MB chunk). When the directory
    # reports >1 holder, the in-flight window is striped across sources.
    "transfer_pull_pipeline_depth": 8,
    # receive-side scatter-read: pull chunks land DIRECTLY in the shm
    # write buffer (rpc client reads into a pre-registered destination
    # view) instead of materializing reader-side bytes first. Read
    # per-chunk like object_transfer_chunk_bytes, so it can be flipped
    # live (the bench records on/off back to back).
    "transfer_scatter_read": True,
    # StreamReader limit for rpc client connections: with asyncio's
    # 64KB default the transport pauses every ~128KB, costing ~32
    # pause/resume cycles per 4MB pull chunk. This is a growth cap,
    # not a preallocation — small-message connections stay tiny.
    # Read at connect time (reconnect to apply).
    "rpc_reader_buffer_bytes": 8 * 1024 * 1024,
    # busy-refusal retry backoff (_read_chunk_backoff): initial sleep,
    # multiplier, per-sleep cap, and the wall-clock budget for one
    # chunk. All read per-use so a live cluster can be retuned (e.g.
    # shrink the cap when a QoS pacer park hint dominates the sleep).
    "transfer_busy_backoff_initial_s": 0.1,
    "transfer_busy_backoff_mult": 1.6,
    "transfer_busy_backoff_max_s": 2.0,
    "transfer_busy_budget_s": 60.0,
    # pre-fault object-store segments at creation: touch pages (and ask
    # for transparent hugepages where the kernel offers MADV_HUGEPAGE)
    # so pull-destination writes hit warm pages (~10 GB/s) instead of
    # paying first-touch faults (~0.4 GB/s) on the critical path.
    # prewarm_bytes caps how much of the heap head is touched up front
    # (the allocator is first-fit from the head, so the warm region IS
    # the pull-sized allocation pool); 0 disables, -1 warms the whole
    # segment.
    "object_store_prefault": True,
    "object_store_hugepages": True,
    "object_store_prewarm_bytes": 512 * 1024 * 1024,
    # auto-prewarm only stores at least this large: the sync page-touch
    # (~0.6s/512MB) is amortized by long-lived production stores, not
    # by the small throwaway stores test clusters create by the hundred
    "object_store_prefault_min_capacity": 1024 * 1024 * 1024,
    # queued-path pipelining: tasks the dispatcher may stack into one
    # pool worker's exec queue when no idle worker matches and the pool
    # is at cap (the queued analog of lease-push pipelining)
    "pool_dispatch_depth": 4,
    # soft cap on non-actor worker processes per node; 0 = auto
    # (max(4, 2*CPU)). See NodeAgent._pool_worker_cap.
    "max_pool_workers_per_node": 0,
    # concurrent worker STARTUPS per node (fork -> registered); 0 = auto
    # (max(2, host cpus)). Reference maximum_startup_concurrency
    # (worker_pool.h): unbounded concurrent spawns thrash the host's
    # cores with interpreter starts until every one misses the register
    # timeout — 50 concurrent actor creations on a 1-core box all failed.
    "worker_startup_concurrency": 0,
    # direct-task lease caching (direct_task_transport.h:110 analog)
    "worker_lease_ttl_s": 10.0,
    "worker_lease_enabled": True,
    # in-flight direct-pushed tasks per leased worker (reference
    # max_tasks_in_flight_per_worker, direct_task_transport.h:211):
    # pushes pipeline into the worker's exec queue, hiding submit RTT.
    # Only engaged when the local agent refused a new lease AND reported
    # no other node fits the shape (spillback stays intact).
    "worker_lease_depth": 10,
    # leased workers held concurrently per scheduling key (reference
    # leases are per-SchedulingKey worker pools); grants refuse when no
    # idle worker exists, so the pool cap bounds this naturally
    "worker_lease_max_per_key": 16,
    # owner-held tasks per key awaiting a lease slot (only on shapes the
    # agent reported unspillable; a 2s no-progress flush hands them to
    # the agent queue). Sized for 10k+-task drains staying owner-side.
    "worker_lease_pending_max": 20000,
    # agent reclaims a lease with no in-flight task after this idle time
    # (well under the TTL): multi-owner workloads would otherwise see
    # most of the worker pool pinned by idle leases between bursts
    "worker_lease_idle_reclaim_s": 1.5,
    # owner probes the leased worker for tasks in flight longer than
    # this (delivery barrier over the push connection): an execute_task
    # fire lost in the write path is detected and failed over in ~one
    # probe period instead of wedging until the test watchdog
    "worker_lease_probe_s": 3.0,
    # pipelined queued submission: .remote() enqueues; a background pump
    # ships windowed batches to the agent instead of blocking per task
    "submit_batch_max": 200,
    "submit_pipeline_depth": 4,
    # -- control plane --
    "heartbeat_timeout_s": 10.0,
    "heartbeat_period_fraction": 0.25,
    # -- core worker --
    "inline_object_max_bytes": 100 * 1024,
    "put_pressure_retry_s": 10.0,
    "fetch_retry_timeout_s": 60.0,
    # -- pallas kernels --
    "flash_block_q": 1024,  # v5e-tuned round 3: fewer, bigger grid cells
    "flash_block_k": 1024,  # win — per-cell overhead dominates at T=2048
    # single-pass fwd: q-heads computed per grid cell (1 = off); divides
    # n_heads, MHA only — amortizes per-cell overhead further. v5e
    # round-5 sweep at 350M/T=2048: 4 wins (0.455 MFU vs 0.443 at 1,
    # 0.447 at 2, 0.442 at 8 — VMEM pressure kills pipelining past 4).
    "flash_heads_per_block": 4,
    # fused-backward analog (MHA only, divides n_heads). Off by default:
    # measured at 350M/T=2048 the bwd's ~3x-larger tile set loses more to
    # VMEM pressure than the cell-count amortization wins (0.4615 vs
    # 0.4687 MFU back-to-back); the knob stays for other shapes.
    "flash_bwd_heads_per_block": 1,
    # mosaic scoped-VMEM ceiling for the flash kernels (MB). The default
    # scoped limit is 16MB but v5e physically has 128MB VMEM; multi-head
    # cells need the headroom for their [bq, s] f32 intermediates.
    "flash_vmem_limit_mb": 96,
    # full TaskSpec schema re-walk at the executor (specs arrive from the
    # already-validating local agent / owner build; see
    # task_spec.from_wire_trusted) — off on the hot path by default
    "revalidate_at_executor": False,
    # -- memory monitor --
    "memory_monitor_interval_s": 2.0,
    "memory_usage_kill_fraction": 0.95,  # memory_monitor.h:52 analog
    # -- collective (DCN path) --
    # transport for the process-group allreduce/allgather/reducescatter:
    # "ring" = chunked pipelined ring over p2p RPC (2*(N-1)/N bytes/rank),
    # "star" = legacy rank-0 tree (O(N*bytes) at the root; the fallback)
    "collective_transport": "ring",
    # wire codec for ring payloads: "none" (dtype passthrough), "bf16",
    # "int8" (EQuARX-style block-scaled with error feedback)
    "collective_codec": "none",
    # bytes per in-flight ring chunk; serialization of chunk k overlaps
    # the wire time of chunk k-1
    "collective_chunk_bytes": 1024 * 1024,
    # per-recv deadline inside group ops (env RAY_TPU_COLLECTIVE_TIMEOUT_S)
    "collective_timeout_s": 120.0,
    # block length for the int8 block-scaled codec (one f32 scale each)
    "collective_quant_block": 512,
    # gradient-bucket target size for train.dcn_allreduce_grads
    "collective_bucket_bytes": 4 * 1024 * 1024,
    # bound on abort detection while blocked in a collective recv: the
    # mailbox wait re-checks the group's abort flag at least this often
    # (abort events also wake waiters immediately via the mailbox
    # condition; this is the belt-and-braces floor)
    "collective_abort_poll_s": 0.5,
    # rendezvous deadline for reform_group after a membership change
    "collective_reform_timeout_s": 120.0,
    # -- cross-slice MPMD pipeline (parallel/mpmd_pipeline.py) --
    # microbatches per optimizer step; the 1F1B bubble fraction is
    # (S-1)/(M+S-1), so more microbatches amortize the pipeline fill
    "pipeline_microbatches": 8,
    # deadline for one stage-boundary activation/grad recv: a dead
    # neighbor stage surfaces as CollectiveTimeoutError at most this
    # late (abort frames usually beat it)
    "pipeline_p2p_timeout_s": 60.0,
    # -- elastic training (JaxTrainer + BackendExecutor) --
    # resume a collective-abort failure IN-PLACE when the backend
    # supports it (backend="dcn"): survivors keep their processes, JIT
    # caches, and device state; heal/reform/rebalance instead of a full
    # gang restart. False forces the legacy gang-restart path.
    "train_inplace_resume": True,
    # how long the in-place path waits for each survivor's old train
    # thread to unwind (after abort_all_local wakes it) before declaring
    # the survivor wedged and falling back to a gang restart
    "train_quiesce_timeout_s": 30.0,
    # -- outbound QoS pacer (_private/net_qos.py) --
    # master switch: every tagged send path consults the pacer (with an
    # unlimited rate this is just a per-peer tally)
    "net_qos_enabled": True,
    # per-peer pacing rate in megabits/s; 0 = unlimited (no parking,
    # no preemption — enforcement engages only under a finite rate)
    "net_qos_rate_mbps": 0.0,
    # token-bucket capacity per peer in bytes; 0 = auto (one refill
    # interval at the configured rate, floored at 4MB)
    "net_qos_window_bytes": 0,
    # guaranteed bulk fraction of each window interval: bulk may take
    # this share even while higher classes wait (anti-starvation)
    "net_qos_bulk_share": 0.2,
    # blocking-acquire deadline — a wedged window fails typed
    # (NetPaceError, retryable) instead of hanging the sender
    "net_qos_grant_timeout_s": 30.0,
    # -- fault injection (chaos tests) --
    # JSON list of injection specs (see _private/fault_injection.py);
    # declared here so set_system_config propagates it to spawned
    # workers via the RAY_TPU_FAULT_SPEC env var
    "fault_spec": "",
    # -- flight recorder (_private/flight_recorder.py) --
    # per-process span ring capacity (the postmortem window)
    "flight_recorder_ring_size": 4096,
    # postmortem bundle directory; "" = <tempdir>/ray_tpu_flight.
    # Propagated to spawned workers via env by set_system_config.
    "flight_recorder_dir": "",
    # background span-flush period (spans -> head task-event ring)
    "flight_recorder_flush_s": 0.5,
    # instrumentation kill switch — ONLY for the runtime_perf obs
    # family's uninstrumented baseline (propagates to spawned workers);
    # production always runs with it on
    "flight_recorder_enabled": True,
    # speculative decoding on the serving slot batch
    # (models/decode_engine.py). Both knobs are read at every pump —
    # live-flippable like transfer_scatter_read, so an operator (or the
    # bench) can kill or retune speculation on a running engine without
    # a restart and the next chunk obeys. serve_spec_enabled gates the
    # engine's configured depth; serve_spec_depth > 0 OVERRIDES the
    # per-engine constructor depth (0 = use the engine's own setting).
    # Emitted tokens are identical either way (the verify step emits
    # the target's own lane-sampled tokens; speculation only changes
    # how many arrive per dispatch), so flipping mid-stream is safe.
    "serve_spec_enabled": True,
    "serve_spec_depth": 0,
    # -- overload guardian (serve/overload.py) --
    # master switch: an LLMPool instantiates a per-pool brownout
    # controller that walks the L0-L3 degradation ladder off the pool's
    # own pressure signals (admission queue, TTFT p99, decode rate,
    # link saturation)
    "overload_enabled": True,
    # escalation watermark: queued admissions per live replica above
    # this reads as overload pressure
    "overload_queue_per_replica_high": 8.0,
    # recovery watermarks sit at this fraction of the escalation ones —
    # the hysteresis band between them is where the ladder holds still
    "overload_recovery_fraction": 0.5,
    # pressure must persist this long before the ladder climbs one level
    "overload_escalate_dwell_s": 1.0,
    # calm must persist this long before the ladder descends one level
    # (recovery re-climbs one level per dwell — never straight to L0)
    "overload_recover_dwell_s": 3.0,
    # L2 squeeze: the bulk share net_qos enforces while degraded
    # (restored to the prior value on recovery)
    "overload_bulk_share_squeezed": 0.05,
    # L2 squeeze: checkpoint ship defers up to this long while the
    # ladder sits at L2+ (then proceeds — freshness beats deferral)
    "overload_ship_defer_max_s": 15.0,
    # L3 shed: hard bound on admission-queue depth; every new request
    # beyond it is refused typed-retryable. Lowest-WFQ-weight tenants
    # shed earlier, at half this bound.
    "overload_shed_queue_bound": 64,
    # floor for the retry-after hint carried by PoolOverloadedError
    "overload_retry_after_min_s": 0.5,
    # link-saturation pressure threshold: the hottest peer's observed
    # bytes/s over the configured net_qos rate (0 rate = signal off)
    "overload_link_saturation": 0.9,
}

_cache: dict[str, Any] = {}
_overrides: dict[str, Any] = {}
_lock = threading.Lock()


def _parse(raw: str, default: Any) -> Any:
    t = type(default)
    if t is bool:
        return raw.lower() in ("1", "true", "yes", "on")
    return t(raw)


def get(name: str) -> Any:
    """Flag value: programmatic override > env RAY_TPU_<NAME> > default."""
    if name not in _DEFS:
        raise KeyError(f"unknown config flag: {name}")
    with _lock:
        if name in _overrides:
            return _overrides[name]
        if name in _cache:
            return _cache[name]
        default = _DEFS[name]
        raw = os.environ.get("RAY_TPU_" + name.upper())
        val = default if raw is None else _parse(raw, default)
        _cache[name] = val
        return val


def set_system_config(config: dict) -> None:
    """Programmatic overrides (ray.init(_system_config=...) analog); also
    exported to env so spawned workers inherit them."""
    with _lock:
        for k, v in config.items():
            if k not in _DEFS:
                raise KeyError(f"unknown config flag: {k}")
            _overrides[k] = v
            os.environ["RAY_TPU_" + k.upper()] = str(v)


def all_flags() -> dict[str, Any]:
    return {k: get(k) for k in _DEFS}
