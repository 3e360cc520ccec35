"""Test fixture backbone: an 8-device virtual CPU mesh.

Analog of the reference's fake-cluster test backbone
(reference: python/ray/cluster_utils.py:99 `Cluster`, conftest fixtures
python/ray/tests/conftest.py:359) — multi-"chip" semantics without TPU
hardware, via XLA host-platform virtual devices.

Must set env vars before jax initializes its backends, hence the top-of-file
placement and the sys.modules guard.
"""

import os
import sys
import tempfile

# Every process this suite starts (a cluster's workers, a script's
# children: hundreds a run) imports jax, and where the environment
# forbids bytecode files each compiles it from source again: 2.2 s a
# process for 0.7, 107 s for 58 over four of the runtime's files (PR 66).
# They get a bytecode cache under the run's temporary directory, so
# nothing is written into the checkout or beside the installation.
os.environ.pop("PYTHONDONTWRITEBYTECODE", None)
sys.pycache_prefix = os.environ.setdefault(
    "PYTHONPYCACHEPREFIX", os.path.join(
        tempfile.gettempdir(), f"ray_tpu_tests_pycache_{os.getuid()}"))
sys.dont_write_bytecode = False

# jax may already be imported (pytest plugins) with its config snapshotted from
# the env, so set both the env var and the live config; backends init lazily.
os.environ["JAX_PLATFORMS"] = "cpu"
# the persistent compile cache stays off under test, here and in every
# worker that inherits this environment: ray_tpu._private.accelerator
# would otherwise fill <repo>/.jax_cache with CPU programs
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in xla_flags:
    xla_flags += " --xla_force_host_platform_device_count=8"
# the suite's time is XLA's CPU compile of tiny programs: compile them
# unoptimised. Both sides of every comparison are compiled alike, and
# the CPU's code generation is not what ships.
if "xla_backend_optimization_level" not in xla_flags:
    xla_flags += (" --xla_backend_optimization_level=0"
                  " --xla_llvm_disable_expensive_passes=true")
os.environ["XLA_FLAGS"] = xla_flags.strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_compilation_cache", False)

assert jax.default_backend() == "cpu", (
    "jax backend initialized before conftest could force CPU; "
    f"got {jax.default_backend()}"
)


def pytest_configure(config):
    # tier-1 runs `-m "not slow"`; register the marker so long soaks
    # (test_chaos_soak.py) opt out without unknown-mark warnings
    config.addinivalue_line(
        "markers",
        "slow: long-running soak/perf tests excluded from the tier-1 run",
    )


@pytest.fixture(autouse=True)
def _hang_watchdog():
    """Convert silent suite wedges into diagnosed failures: if any single
    test runs over five minutes (four times the longest of the block and
    compile tests on the driver's box, 73 s, and over twice the longest
    rehearsal under ``benchmark_suite/``, 127 s: PR 65's junit file),
    faulthandler dumps EVERY thread's stack and the process exits — a
    monolithic `pytest tests/` run must never sit stalled for an hour
    with idle leaked workers (observed in r4: a cross-file hang wedged
    the suite >44min with zero output).

    The dump goes to a FILE (ray_tpu_hang_dump.log under the system
    temp dir), not stderr: pytest's default fd-level capture dup2s
    fd 2 before this conftest even imports, so both sys.stderr and
    sys.__stderr__ land in the doomed process's capture temp file —
    exactly what made the first watchdog firing an undiagnosable
    silent rc=1. A plain file survives the hard _exit."""
    import faulthandler

    faulthandler.dump_traceback_later(300, exit=True,
                                      file=_watchdog_log())
    yield
    faulthandler.cancel_dump_traceback_later()


_WATCHDOG_FH = None


def _watchdog_log():
    global _WATCHDOG_FH
    if _WATCHDOG_FH is None:
        path = os.path.join(tempfile.gettempdir(),
                            "ray_tpu_hang_dump.log")
        _WATCHDOG_FH = open(path, "a")  # noqa: SIM115 — must outlive tests
        print(f"[conftest] hang-watchdog dumps -> {path}")
    return _WATCHDOG_FH


def _kill_orphan_workers():
    """Reap ray_tpu worker processes that outlived their cluster: ones
    reparented to init (their spawning agent/head died) or still parented
    to this pytest process after module teardown. Leaked workers hold
    ports/sockets and wedge later modules' clusters."""
    import signal

    me = os.getpid()
    for pid_s in os.listdir("/proc"):
        if not pid_s.isdigit():
            continue
        pid = int(pid_s)
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ")
            if b"ray_tpu.core.worker_proc" not in cmd:
                continue
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().split()[3])
            if ppid in (me, 1):
                os.kill(pid, signal.SIGKILL)
        except (OSError, ValueError, IndexError):
            continue


@pytest.fixture(scope="module", autouse=True)
def _reap_leaked_workers():
    """Cross-file process hygiene (instantiated before, finalized after,
    every module-scoped cluster fixture): the worker processes a module
    left, and the threads of the dashboard heads it started (a head
    outlived its test and its cluster, with its loop's thread pool: PR
    65's run lost a worker that still ran one, ``PERF.md`` §7)."""
    yield
    _kill_orphan_workers()
    dashboard = sys.modules.get("ray_tpu.dashboard.head")
    if dashboard is not None:
        dashboard.stop_dashboard()


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture
def rng():
    return jax.random.PRNGKey(0)
