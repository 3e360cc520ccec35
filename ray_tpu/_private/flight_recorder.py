"""Per-process flight recorder: a bounded span ring with postmortem dumps.

Every hot subsystem (ring collectives, object transfers, the serve
stack, trainer steps) records typed spans here. Two consumers:

* **Live**: a background flusher batches spans to the head over the
  EXISTING task-event channel as ``state="SPAN"`` events (unique
  ``b"fr:"``-prefixed task ids survive the head's last-event-per-task
  dedup), so ``ray_tpu.timeline()`` and the dashboard's
  ``/api/timeline`` render them with zero new control-plane RPCs.
* **Postmortem**: the ring itself (``deque(maxlen=N)``) holds the last
  N spans of THIS process; :func:`dump_bundle` writes them to a JSON
  bundle on worker death, collective abort, or injected fault — the
  black box for "what happened in the 2s before the failure".

Clock discipline: spans are timed with ``time.monotonic()``; one
wall-clock anchor captured at recorder init converts to epoch seconds
for the timeline (wall = mono + anchor), so durations never jump under
clock adjustment but cross-process rendering still lines up.

The profiler's clock: in a process that has already imported ``jax``
(a span never imports it) every :func:`span` is ALSO a
``jax.profiler.TraceAnnotation`` and every :func:`mark` an empty one, so
under any ``jax.profiler`` capture the program's spans land on their
thread's line of ``/host:CPU``, timed by the profiler beside the device
planes, with their attrs as the event's stats. With no capture running
an annotation is a flag test (about half a microsecond). The recorder
stays always on; the profiler decides whether an annotation is kept.

Overhead budget: ``record()`` on the hot path is a dict build + deque
append under a lock (no I/O, no syscalls beyond the clock reads); the
runtime_perf ``obs`` family holds it to <=3% on serve tokens/s and ring
allreduce. ``_suppressed()`` exists ONLY so that benchmark can measure
an uninstrumented baseline — production code never disables recording.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import tempfile
import threading
import time
from typing import Any

# bundles kept per dump directory (oldest pruned on each dump): bounds
# disk use under chaos soaks where every abort dumps
_MAX_BUNDLES = 20
# pending-flush backlog cap: past this the flusher is behind and new
# spans stay ring-only (still visible postmortem) instead of growing RSS
_MAX_PENDING = 20_000
_FLUSH_BATCH = 1000
_IMPORTED = time.monotonic()  # as early as this module knows of the process


def process_age_s() -> float:
    """How old this process is: its start time in ``/proc/self/stat``
    (field 22, clock ticks since boot) against the boot clock, so spawn,
    interpreter and imports are in it; where a platform gives neither,
    the time since this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic() - _IMPORTED


class _Recorder:
    def __init__(self):
        from ray_tpu._private import config as cfg

        size = int(cfg.get("flight_recorder_ring_size"))
        self.ring: collections.deque = collections.deque(maxlen=size)
        self.lock = threading.Lock()
        # wall = mono + anchor (single wall-clock read at init; every
        # span timestamp afterwards is monotonic)
        self.anchor = time.time() - time.monotonic()
        self.pending: list[dict] = []
        self.recorded = 0
        self.flush_dropped = 0
        self.last_dump: str | None = None
        self.flusher_started = False


_rec: _Recorder | None = None
_rec_lock = threading.Lock()
_enabled = True  # benchmark baseline only; see _suppressed()
# config-side kill switch, read once (workers spawned with
# RAY_TPU_FLIGHT_RECORDER_ENABLED=False start suppressed — the obs
# benchmark's cross-process baseline)
_cfg_enabled: bool | None = None


def _on() -> bool:
    global _cfg_enabled
    if _cfg_enabled is None:
        from ray_tpu._private import config as cfg

        try:
            _cfg_enabled = bool(cfg.get("flight_recorder_enabled"))
        except Exception:  # noqa: BLE001
            _cfg_enabled = True
    return _enabled and _cfg_enabled


def _get() -> _Recorder:
    global _rec
    r = _rec
    if r is None:
        with _rec_lock:
            r = _rec
            if r is None:
                r = _rec = _Recorder()
    return r


def wall(mono: float) -> float:
    """Convert a time.monotonic() stamp to epoch seconds using the
    recorder's single wall-clock anchor."""
    return mono + _get().anchor


def _annotation(name: str, attrs: dict):
    """An un-entered ``TraceAnnotation`` carrying ``attrs``, or None in
    a process that has not imported jax."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        return jax.profiler.TraceAnnotation(name, **_plain(attrs))
    except Exception:  # noqa: BLE001 — observability is best-effort
        return None


def _plain(attrs: dict) -> dict:
    """Attrs as the profiler's metadata takes them: numbers and strings
    (a bool as 0/1); anything else is left to the ring."""
    return {k: (int(v) if isinstance(v, bool) else v)
            for k, v in attrs.items()
            if isinstance(v, (bool, int, float, str))}


def record(kind: str, name: str, start_mono: float, end_mono: float, *,
           attrs: dict | None = None, trace: dict | None = None,
           flush: bool = True, annotate: bool = False) -> None:
    """Record a completed span (monotonic start/end stamps).

    ``flush=False`` keeps the span ring-only (postmortem visibility,
    no head traffic) — use it for per-chunk hot-path spans. ``trace``
    overrides the ambient trace context (``{"trace_id", "parent"}``)
    for spans recorded on behalf of another request (stream polls).
    ``annotate=True`` also leaves an instant event of the same name and
    attrs on the profiler's host line: for a span whose interval is only
    known afterwards (the attrs carry its parts).
    """
    if not _on():
        return
    if annotate:
        ann = _annotation(name, attrs or {})
        if ann is not None:
            with ann:
                pass
    r = _get()
    if trace is None:
        from ray_tpu._private import trace as _trace

        cur = _trace.current()
        if cur is not None:
            trace = {"trace_id": cur[0], "parent": cur[1]}
    span = {
        "kind": kind,
        "name": name,
        "start_s": start_mono + r.anchor,
        "end_s": end_mono + r.anchor,
        "trace": trace,
        "attrs": attrs or {},
    }
    with r.lock:
        r.ring.append(span)
        r.recorded += 1
        if flush:
            if len(r.pending) < _MAX_PENDING:
                r.pending.append(span)
            else:
                r.flush_dropped += 1
    if flush and not r.flusher_started:
        _ensure_flusher(r)


@contextlib.contextmanager
def span(kind: str, name: str, *, attrs: dict | None = None,
         flush: bool = True):
    """Context-manager form; yields the attrs dict so the body can
    attach fields (byte counts, breakdowns) before the span closes.
    The body runs inside a profiler annotation of the same name (module
    docstring); fields the body attached reach it when it closes."""
    a = dict(attrs) if attrs else {}
    ann = _annotation(name, a) if _on() else None
    n_entry = len(a)
    if ann is not None:
        ann.__enter__()
    t0 = time.monotonic()
    try:
        yield a
    finally:
        t1 = time.monotonic()
        if ann is not None:
            if len(a) > n_entry:
                ann.set_metadata(**_plain(
                    dict(list(a.items())[n_entry:])))
            ann.__exit__(None, None, None)
        record(kind, name, t0, t1, attrs=a, flush=flush)


def mark(kind: str, name: str, *, attrs: dict | None = None,
         trace: dict | None = None, flush: bool = True) -> None:
    """A zero-length span for a fact known only at one instant (a first
    token, a poll that found tokens): one ring entry with start = end
    and one empty profiler annotation carrying the attrs."""
    now = time.monotonic()
    record(kind, name, now, now, attrs=attrs, trace=trace, flush=flush,
           annotate=True)


# -- flusher: spans -> head task-event ring ------------------------------

def _ensure_flusher(r: _Recorder) -> None:
    with r.lock:
        if r.flusher_started:
            return
        r.flusher_started = True
    t = threading.Thread(target=_flush_loop, name="ray-tpu-fr-flush",
                         daemon=True)
    t.start()


def _flush_loop() -> None:
    from ray_tpu._private import config as cfg

    period = float(cfg.get("flight_recorder_flush_s"))
    while True:
        time.sleep(period)
        try:
            flush_now()
        except Exception:  # noqa: BLE001 — observability is best-effort
            pass


def flush_now() -> int:
    """Ship pending spans to the head; returns how many were sent.
    Safe to call from tests to avoid waiting a flush period."""
    from ray_tpu._private.api import _worker

    w = _worker
    r = _get()
    if w is None or getattr(w, "head", None) is None:
        return 0
    sent = 0
    while True:
        with r.lock:
            batch = r.pending[:_FLUSH_BATCH]
            del r.pending[:len(batch)]
        if not batch:
            return sent
        events = []
        for s in batch:
            ev = {
                # unique id -> survives the head's last-event-per-task
                # dedup; never collides with real 16-byte task ids
                "task_id": b"fr:" + os.urandom(8),
                "job_id": w.job_id,
                "name": s["name"],
                "state": "SPAN",
                "kind": s["kind"],
                "worker_id": w.worker_id,
                "node_id": w.node_id,
                "start_s": s["start_s"],
                "end_s": s["end_s"],
                "attrs": s["attrs"],
            }
            if s["trace"]:
                ev["trace"] = s["trace"]
            events.append(ev)
        w.head.fire("task_events", {"events": events})
        sent += len(events)


# -- postmortem bundles --------------------------------------------------

def bundle_dir() -> str:
    from ray_tpu._private import config as cfg

    d = cfg.get("flight_recorder_dir") or os.path.join(
        tempfile.gettempdir(), "ray_tpu_flight")
    os.makedirs(d, exist_ok=True)
    return d


def dump_bundle(reason: str, extra: dict | None = None) -> str | None:
    """Write this process's span ring to a postmortem bundle file.

    Called on injected faults (before the victim dies — including
    ``os._exit``, which skips destructors, so this runs synchronously
    first), on collective aborts (every survivor dumps), and on demand.
    Returns the bundle path, or None on failure (never raises)."""
    try:
        r = _get()
        with r.lock:
            spans = list(r.ring)
        meta: dict[str, Any] = {
            "reason": reason,
            "pid": os.getpid(),
            "wall_s": time.monotonic() + r.anchor,
            "spans_recorded": r.recorded,
            "flush_dropped": r.flush_dropped,
        }
        if extra:
            meta["extra"] = extra
        try:
            from ray_tpu._private.api import _worker

            if _worker is not None:
                meta["worker_id"] = _worker.worker_id.hex()
                meta["node_id"] = _worker.node_id.hex()
        except Exception:  # noqa: BLE001
            pass
        d = bundle_dir()
        path = os.path.join(
            d, f"fr-{os.getpid()}-{int(meta['wall_s'] * 1000)}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"meta": meta, "spans": spans}, f, default=str)
        os.replace(tmp, path)
        r.last_dump = path
        _prune_bundles(d)
        return path
    except Exception:  # noqa: BLE001 — must never mask the real failure
        return None


def _prune_bundles(d: str) -> None:
    try:
        files = sorted(
            (f for f in os.listdir(d)
             if f.startswith("fr-") and f.endswith(".json")),
            key=lambda f: os.path.getmtime(os.path.join(d, f)))
        for f in files[:-_MAX_BUNDLES]:
            os.unlink(os.path.join(d, f))
    except OSError:
        pass


def latest_bundles(n: int = 5) -> list[str]:
    """Newest-first postmortem bundle paths in the dump directory."""
    try:
        d = bundle_dir()
        files = sorted(
            (os.path.join(d, f) for f in os.listdir(d)
             if f.startswith("fr-") and f.endswith(".json")),
            key=os.path.getmtime, reverse=True)
        return files[:n]
    except OSError:
        return []


def stats() -> dict:
    r = _get()
    with r.lock:
        return {
            "ring_len": len(r.ring),
            "ring_cap": r.ring.maxlen,
            "recorded": r.recorded,
            "pending": len(r.pending),
            "flush_dropped": r.flush_dropped,
            "last_dump": r.last_dump,
        }


@contextlib.contextmanager
def _suppressed():
    """Benchmark-only: measure an uninstrumented baseline for the obs
    overhead floors. Never used by production code paths."""
    global _enabled
    _enabled = False
    try:
        yield
    finally:
        _enabled = True
