"""Who may touch the TPU, decided in one place.

A chip belongs to one process at a time, *and a dead one's for some
seconds more*: the first process that initialises a JAX TPU backend
takes every chip it can see, and any later process fails, hangs, or
comes up on the CPU. So the node agent decides
at SPAWN time, from the ``TPU`` amount of the work the worker is for
(:func:`worker_env`): a worker with a grant sees exactly its chips and
must come up on them, every other worker is pinned to the CPU platform.
JAX honours the plain ``JAX_PLATFORMS`` variable, so nothing re-asserts
a platform in code. Processes that compile for the chip call
:func:`claim_device` once before their first compile: it fails loudly
when a granted process is not on the TPU and places the persistent
compile cache; :func:`device_report` is what their reports carry.

A process in exit keeps its chips while the kernel closes them: the
worker of a four-chip job stood 13-21 s as a zombie leader (``Zl``, no
fd table left to read) after its ``benchmark.run`` had returned, and the
next job's worker, which reaches ``open(/dev/vfio/N)`` some 13 s after
it starts, died of ``Device or resource busy`` (``PERF.md`` section
7(e)). Both ends wait for it, bounded by :data:`CHIP_WAIT_S` and polled
every 50 ms: :func:`claim_device` before it initialises (a granted
process; :func:`node_busy` is how it learns), and the agent's ``stop``
for the workers it gave chips to. Whoever waited over 50 ms says so: a
``chip_wait`` mark of kind ``accel`` and one line on stderr.

The driver is the user's process: the agent never spawned it, so it is
the user's call whether it computes (then nothing else on the node may
hold a chip) or only orchestrates (then it should stay off JAX or set
``JAX_PLATFORMS=cpu`` itself, as ``chip_smoke.py`` does).
"""

from __future__ import annotations

import collections
import errno
import glob
import math
import os
import sys
import threading
import time

# chips handed to this worker by its node agent ("0" or "2,3"); unset or
# empty on a grantless worker
GRANT_ENV = "RAY_TPU_GRANTED_CHIPS"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

# how long a chip that a process in exit still holds is waited for (by a
# claim, by the agent's stop; ``benchmark/cluster.py``'s own figure), and
# how often the waiter looks
CHIP_WAIT_S = 60.0
CHIP_POLL_S = 0.05

# libtpu's chip bounds for a process that owns a SUBSET of the host's
# chips (a whole-host grant keeps the host's own topology variables)
_SUBSET_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def chip_device_paths() -> list[str]:
    """Device nodes libtpu opens, one per chip: ``/dev/accel<N>`` on
    hosts with the accel driver, ``/dev/vfio/<N>`` where chips are
    passed through vfio (the v5e machines this repo runs on). Numbered
    nodes only: bare ``/dev/accel`` is the DRM accelerator class
    directory and ``/dev/vfio/vfio`` the vfio container."""
    for pattern in ("/dev/accel[0-9]*", "/dev/vfio/[0-9]*"):
        paths = sorted(glob.glob(pattern))
        if paths:
            return paths
    return []


def detect_tpu_chips() -> int:
    """Count local TPU chips without initialising JAX (which would take
    them). ``RAY_TPU_CHIPS`` overrides for tests and virtual topologies."""
    chips = os.environ.get("RAY_TPU_CHIPS")
    if chips:
        return int(float(chips))
    return len(chip_device_paths())


def chips_for(resources: dict | None) -> int:
    """Whole chips a ``TPU`` amount needs; a fractional request still
    owns its chip's process, so it rounds up."""
    return math.ceil(float((resources or {}).get("TPU", 0)) - 1e-9)


def worker_env(chips: tuple[int, ...], host_chips: int) -> dict[str, str]:
    """Environment overrides for a worker spawned with ``chips`` (indices
    into the host's ``host_chips``; empty = no grant)."""
    if not chips:
        return {"JAX_PLATFORMS": "cpu", GRANT_ENV: ""}
    ids = ",".join(str(c) for c in chips)
    # an explicit platform list makes JAX FAIL when the TPU cannot
    # initialise, instead of warning and falling back to the CPU
    env = {"JAX_PLATFORMS": "tpu,cpu", GRANT_ENV: ids,
           "TPU_VISIBLE_CHIPS": ids}
    if len(chips) < host_chips:
        bounds = _SUBSET_BOUNDS.get(len(chips))
        if bounds is None:
            raise ValueError(
                f"cannot give one process {len(chips)} of {host_chips} "
                f"chips: grant 1, 2 or all of a host's chips")
        # the subset is its own one-host slice of that shape (tried on a
        # four-chip v5e host: four one-chip and two two-chip processes
        # side by side, each opening only its own /dev/vfio nodes)
        env.update({"TPU_CHIPS_PER_HOST_BOUNDS": bounds,
                    "TPU_HOST_BOUNDS": "1,1,1"})
    return env


def granted_chips() -> tuple[int, ...]:
    """Chip indices this process was spawned with (empty = none)."""
    raw = os.environ.get(GRANT_ENV, "")
    return tuple(int(c) for c in raw.split(",") if c)


def granted_nodes() -> list[str]:
    """The device nodes of :func:`granted_chips`, by index into
    :func:`chip_device_paths` (``TPU_VISIBLE_CHIPS`` counts the same
    way: chip 2 is ``/dev/vfio/2``)."""
    chips = granted_chips()
    nodes = chip_device_paths() if chips else []
    return [nodes[c] for c in chips if c < len(nodes)]


def node_busy(path: str) -> bool:
    """Whether some process holds this chip's node: opened read-write
    and closed at once. A vfio group opens once, so ``EBUSY`` is "held",
    by a process in exit too, which no ``/proc/<pid>/fd`` shows. Any
    other error (no such node, no right to it) counts as free: a box
    without chips behaves as it did."""
    try:
        os.close(os.open(path, os.O_RDWR | os.O_CLOEXEC))
    except OSError as e:
        return e.errno == errno.EBUSY
    return False


def report_chip_wait(t0: float, nodes: list[str], **attrs) -> float:
    """Tells of a wait for ``nodes`` that began at ``t0`` (monotonic), if
    it was over one poll: the ``chip_wait`` mark and a line on stderr.
    -> the milliseconds waited."""
    waited_ms = (time.monotonic() - t0) * 1e3
    if waited_ms > CHIP_POLL_S * 1e3:
        from ray_tpu._private import flight_recorder

        flight_recorder.mark("accel", "chip_wait", attrs={
            "waited_ms": round(waited_ms, 1), "nodes": ",".join(nodes),
            **attrs})
        print(f"[accel] pid {os.getpid()} waited {waited_ms:.0f} ms for "
              f"{' '.join(nodes)}" + "".join(
                  f" {k}={v}" for k, v in attrs.items()),
              file=sys.stderr, flush=True)
    return waited_ms


def wait_nodes_free(nodes: list[str]) -> list[str]:
    """Returns once none of ``nodes`` is busy, within a poll of the last
    one's release, or after :data:`CHIP_WAIT_S`. -> those still busy."""
    t0 = time.monotonic()
    busy = [n for n in nodes if node_busy(n)]
    if not busy:
        return []
    waited_for = busy
    while busy and time.monotonic() - t0 < CHIP_WAIT_S:
        time.sleep(CHIP_POLL_S)
        busy = [n for n in busy if node_busy(n)]
    report_chip_wait(t0, waited_for)
    return busy


_compile_stats: dict | None = None
_listening = False

# jax's three timed stages of a jitted function's first call, as
# ``jax.monitoring`` names them (``jax/_src/dispatch.py``)
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_HIT = "/jax/compilation_cache/cache_hits"
_BEGAN = "began"
_STAGES = (_TRACE, _LOWER, _BACKEND)
_TOTAL_KEY = {_TRACE: "trace_seconds", _LOWER: "lower_seconds"}
_TALLY_KEEP = 1024  # entries of a thread's tally kept, at least


class _ThreadTally(threading.local):
    """What jax compiled ON THIS THREAD, as a log: jax calls its
    listeners on the thread that compiles, so a caller that reads ``n``
    before a call and finds it moved afterwards owns the entries between
    (:func:`compile_mark`, :func:`compile_since`), whatever another
    thread compiles meanwhile. A stage that runs inside another (a
    jitted body traced inside its caller's trace, a small program
    compiled while something is traced) is that one's time already:
    ``depth`` counts the stages open, and only the outermost is logged."""

    def __init__(self):
        self.n = 0  # entries ever logged here: a caller's mark
        self.log: list = []  # the newest of them, (what, value)
        self.depth = 0
        self.outer = ""  # the stage that is open at depth 1

    def add(self, what: str, value: float) -> None:
        self.log.append((what, value))
        self.n += 1
        if len(self.log) > 2 * _TALLY_KEEP:
            del self.log[:_TALLY_KEEP]


tally = _ThreadTally()  # ``tally.n``: what :func:`compile_mark` returns


def _on_stage_begin(event: str, value: float, **_kw) -> None:
    # (jax says when a stage begins with a scalar, its start on
    # ``time.time()``'s clock: nothing here reads a clock)
    if event in _STAGES:
        t = tally
        t.depth += 1
        if t.depth == 1:
            t.outer = event
            t.add(_BEGAN, value)


def _total(key: str, value: float) -> None:
    if _compile_stats is not None:
        _compile_stats[key] += value


def _on_duration(event: str, duration: float, **_kw) -> None:
    t = tally
    if event == _CACHE_READ:
        # fired inside the backend stage, whose time holds it
        if t.depth == 1 and t.outer == _BACKEND:
            _total("cache_read_seconds", duration)
            t.add(event, duration)
        return
    if event not in _STAGES:
        return
    if event == _BACKEND:
        _total("seconds", duration)  # every one, as since PR 21
    t.depth = max(0, t.depth - 1)
    if t.depth == 0:
        if event != _BACKEND:
            _total(_TOTAL_KEY[event], duration)
        t.add(event, duration)


def _on_event(event: str, **_kw) -> None:
    if event in (_REQUEST, _HIT):
        _total("requests" if event == _REQUEST else "hits", 1)
        tally.add(event, 1)


def _place_compile_cache() -> dict:
    """Persistent compile cache, placed from outside: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX's own handling of it stands
    and no directory is set in code; otherwise one fixed path inside the
    checkout (the path is part of the cache key's home, so it must never
    move with a pid, a timestamp or a temp dir). Also counts, for this
    process, cache requests / hits and backend-compile seconds (every
    one, a cache read included) and, outermost stages only, the seconds
    of tracing, of lowering and of reading the cache; the same events go
    to the compiling thread's own tally (:class:`_ThreadTally`)."""
    global _compile_stats, _listening
    if _compile_stats is not None:
        return _compile_stats
    import jax
    from jax import monitoring

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    _compile_stats = {
        "dir": env_dir or COMPILE_CACHE_DIR, "requests": 0, "hits": 0,
        "seconds": 0.0, "trace_seconds": 0.0, "lower_seconds": 0.0,
        "cache_read_seconds": 0.0}
    if not _listening:  # once a process: the stages are counted by depth
        _listening = True
        monitoring.register_event_listener(_on_event)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_scalar_listener(_on_stage_begin)
    return _compile_stats


def compile_report() -> dict:
    """This process's compile counters, seconds rounded to the ms."""
    return {k: round(v, 3) if isinstance(v, float) else v
            for k, v in _place_compile_cache().items()}


def compile_mark() -> int:
    """Where this thread's compile tally stands: read before a call and
    compared after it, it tells whether the call compiled anything. (A
    caller on a request path reads ``tally.n`` itself: an attribute of a
    thread-local, no call.)"""
    return tally.n


def _since(mark: int) -> list:
    t = tally
    return t.log[max(0, len(t.log) - (t.n - mark)):] if t.n > mark else []


def compile_since(mark: int) -> dict | None:
    """What this thread traced, lowered and compiled since ``mark``, or
    None where it did nothing of the kind: ``requests`` / ``hits`` of the
    persistent cache, and the milliseconds of the outermost stages,
    which add up to no more than the time they took: ``trace_ms``
    (Python: the function to a jaxpr), ``lower_ms`` (the jaxpr to a
    module), ``cache_read_ms`` (a hit's read) and ``compile_ms`` (the
    rest of the backend stage: the cache key, XLA's compile where the
    cache missed, the write)."""
    entries = _since(mark)
    if not entries:
        return None
    sums = collections.Counter()
    for what, value in entries:
        sums[what] += value
    ms = lambda s: round(1e3 * s, 3)  # noqa: E731
    return {"requests": int(sums[_REQUEST]), "hits": int(sums[_HIT]),
            "trace_ms": ms(sums[_TRACE]), "lower_ms": ms(sums[_LOWER]),
            "compile_ms": ms(sums[_BACKEND] - sums[_CACHE_READ]),
            "cache_read_ms": ms(sums[_CACHE_READ])}


def compile_began(mark: int) -> float | None:
    """When the first stage since ``mark`` began on this thread, on
    ``time.time()``'s clock (jax's own stamp), or None."""
    return next((v for what, v in _since(mark) if what == _BEGAN), None)


_claim: dict | None = None


def claim_device() -> dict:
    """Call once, before the first compile, in every process that
    computes with JAX on a worker: initialises the backend this process
    was spawned for and returns what it got. A process that was granted
    a chip and is not on the TPU raises — there is no CPU path to fall
    back to silently. A granted chip that another process still holds
    (the worker of the job before, in exit) is waited for first: a busy
    device node is fatal to the backend's initialisation
    (``waited_ms``: that wait, 0 where it was under a poll)."""
    global _claim
    import jax

    _place_compile_cache()
    busy, nodes, t0 = [], granted_nodes(), time.monotonic()
    if nodes:
        # (not its own: where this process has touched the device
        # already, they read busy for as long as it lives)
        mine = _held_nodes(os.getpid(), set(nodes))
        busy = wait_nodes_free([n for n in nodes if n not in mine])
    # what the ``chip_wait`` mark says, by its rule: one probe is no wait
    waited_ms = 1e3 * (time.monotonic() - t0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        if not busy:
            raise
        raise RuntimeError(
            f"worker {os.getpid()}: {' '.join(busy)} still held by another "
            f"process after {CHIP_WAIT_S:.0f} s: {e}") from e
    _claim = {
        "pid": os.getpid(),
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "ids": [d.id for d in devices],
        "granted_chips": list(granted_chips()),
        "waited_ms": round(waited_ms, 1)
        if waited_ms > 1e3 * CHIP_POLL_S else 0.0,
    }
    if _claim["granted_chips"] and _claim["platform"] != "tpu":
        raise RuntimeError(
            f"worker {os.getpid()} was granted TPU chips "
            f"{_claim['granted_chips']} but JAX came up on "
            f"{_claim['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})")
    return dict(_claim)


def device_report() -> dict:
    """What :func:`claim_device` found, plus this process's compile
    counters ({dir, requests, hits, seconds} and, of the outermost
    stages, {trace_seconds, lower_seconds, cache_read_seconds}:
    :func:`_place_compile_cache`) and each device's peak
    bytes in use (None where the backend keeps no memory stats) — the
    facts a replica's ``stats()`` or a train worker's report carries."""
    import jax

    report = claim_device() if _claim is None else dict(_claim)
    # A one-chip process calls its device id 0 whichever chip it has;
    # the device nodes it holds open tell the chips apart. Read now, not
    # at the claim: libtpu opens them when the device is first used.
    report["nodes"] = _held_nodes(os.getpid(), set(chip_device_paths()))
    report["compile"] = compile_report()
    report["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()]
    return report


def _held_nodes(pid: int | str, nodes: set[str]) -> list[str]:
    fd_dir = f"/proc/{pid}/fd"
    held = set()
    try:
        fds = os.listdir(fd_dir)
    except OSError:  # process gone, or not ours to read
        return []
    for fd in fds:
        try:
            held.add(os.readlink(os.path.join(fd_dir, fd)))
        except OSError:  # closed meanwhile (our own listing's fd is one)
            continue
    return sorted(held & nodes)


def chip_holders() -> dict[int, list[str]]:
    """pid -> chip device nodes it holds open, from ``/proc`` (needs no
    JAX, so a process that must stay off the chip can still check who is
    on it). A process in exit has no fd table to read and still holds
    its chips until the kernel has closed them: a node that is busy
    while no table shows it is reported under pid 0."""
    nodes = set(chip_device_paths())
    if not nodes:
        return {}
    holders = {int(pid): _held_nodes(pid, nodes)
               for pid in os.listdir("/proc") if pid.isdigit()}
    holders = {pid: held for pid, held in holders.items() if held}
    shown = {n for held in holders.values() for n in held}
    in_exit = sorted(n for n in nodes - shown if node_busy(n))
    if in_exit:
        holders[0] = in_exit
    return holders
