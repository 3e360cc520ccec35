"""One process per chip: the node agent hands chips out at spawn
(ray_tpu/_private/accelerator.py) and pins every other worker to the CPU.

No chip is needed: ``RAY_TPU_CHIPS=4`` gives the node four virtual ones,
and what is checked is the environment each worker is born with.
"""

import os

import pytest

import ray_tpu
from ray_tpu._private import accelerator

_KEYS = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", accelerator.GRANT_ENV,
         "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
         "JAX_COMPILATION_CACHE_DIR", "SMOKE_EXTRA")


@ray_tpu.remote
class Probe:
    def env(self) -> dict:
        return {k: os.environ.get(k) for k in _KEYS}


@ray_tpu.remote(num_cpus=0, num_tpus=1)
def probe_task() -> dict:
    return {k: os.environ.get(k) for k in _KEYS}


@pytest.fixture()
def four_chip_node(monkeypatch):
    monkeypatch.setenv("RAY_TPU_CHIPS", "4")
    # placed from outside: every worker must inherit it untouched
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    ray_tpu.init()
    yield
    ray_tpu.shutdown()


def test_each_granted_actor_gets_its_own_chip_and_the_rest_get_none(
        four_chip_node):
    assert ray_tpu.cluster_resources()["TPU"] == 4
    holders = [Probe.options(num_cpus=0, num_tpus=1).remote()
               for _ in range(4)]
    plain = Probe.options(num_cpus=0).remote()
    # a runtime_env adds variables; it can neither drop the cache
    # placement nor talk a grantless worker onto a chip
    sneaky = Probe.options(num_cpus=0, runtime_env={"env_vars": {
        "SMOKE_EXTRA": "1", "JAX_PLATFORMS": "tpu"}}).remote()
    envs = ray_tpu.get([h.env.remote() for h in holders], timeout=120)
    assert sorted(e["TPU_VISIBLE_CHIPS"] for e in envs) == list("0123")
    for e in envs:
        assert e["JAX_PLATFORMS"] == "tpu,cpu"
        assert e[accelerator.GRANT_ENV] == e["TPU_VISIBLE_CHIPS"]
        # one chip of four: its own one-host slice
        assert e["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
        assert e["TPU_HOST_BOUNDS"] == "1,1,1"
        assert e["JAX_COMPILATION_CACHE_DIR"] == "/placed/from/outside"
    for grantless in ray_tpu.get([plain.env.remote(), sneaky.env.remote()],
                                 timeout=120):
        assert grantless["JAX_PLATFORMS"] == "cpu"
        assert not grantless[accelerator.GRANT_ENV]
        assert grantless["TPU_VISIBLE_CHIPS"] is None
        assert grantless["JAX_COMPILATION_CACHE_DIR"] == \
            "/placed/from/outside"
    assert ray_tpu.get(sneaky.env.remote())["SMOKE_EXTRA"] == "1"

    # a chip comes back when its process is gone, and only then
    freed = envs[2]["TPU_VISIBLE_CHIPS"]
    ray_tpu.kill(holders[2])
    again = Probe.options(num_cpus=0, num_tpus=1).remote()
    assert ray_tpu.get(again.env.remote(),
                       timeout=120)["TPU_VISIBLE_CHIPS"] == freed


def test_tasks_and_wider_grants(four_chip_node):
    # a TPU task runs in a worker born with a chip, never in a reused
    # CPU worker
    plain = Probe.options(num_cpus=0).remote()
    assert ray_tpu.get(plain.env.remote(),
                       timeout=120)["JAX_PLATFORMS"] == "cpu"
    task_env = ray_tpu.get(probe_task.remote(), timeout=120)
    assert task_env["JAX_PLATFORMS"] == "tpu,cpu"
    assert task_env["TPU_VISIBLE_CHIPS"] in list("0123")
    # two chips in one process; the idle task worker's chip is reclaimed
    # if the pair needs it
    pair = Probe.options(num_cpus=0, num_tpus=2).remote()
    e = ray_tpu.get(pair.env.remote(), timeout=120)
    assert len(set(e["TPU_VISIBLE_CHIPS"].split(","))) == 2
    assert e["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    # the whole host: the host's own topology variables stand
    ray_tpu.kill(pair)
    whole = Probe.options(num_cpus=0, num_tpus=4).remote()
    e = ray_tpu.get(whole.env.remote(), timeout=120)
    assert e["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert e["TPU_CHIPS_PER_HOST_BOUNDS"] is None


def test_naming_the_cpus_does_not_hide_the_chips(monkeypatch):
    monkeypatch.setenv("RAY_TPU_CHIPS", "2")
    ray_tpu.init(num_cpus=2)
    try:
        res = ray_tpu.cluster_resources()
        assert res["CPU"] == 2 and res["TPU"] == 2
    finally:
        ray_tpu.shutdown()


@pytest.mark.parametrize("resources,chips", [
    (None, 0), ({}, 0), ({"CPU": 2}, 0), ({"TPU": 1}, 1),
    ({"TPU": 0.5}, 1), ({"TPU": 2.0}, 2), ({"TPU": 4}, 4)])
def test_chips_for_a_tpu_amount(resources, chips):
    assert accelerator.chips_for(resources) == chips


def test_three_of_four_chips_is_refused():
    with pytest.raises(ValueError, match="grant 1, 2 or all"):
        accelerator.worker_env((0, 1, 2), 4)


def test_detects_the_vfio_layout(monkeypatch):
    """The v5e machines expose /dev/vfio/<N> (plus the vfio container),
    not /dev/accel<N>; bare /dev/accel is not a chip either."""
    import glob

    layout = {"/dev/accel[0-9]*": [],
              "/dev/vfio/[0-9]*": ["/dev/vfio/3", "/dev/vfio/0"]}
    monkeypatch.delenv("RAY_TPU_CHIPS", raising=False)
    monkeypatch.setattr(glob, "glob", lambda pat: layout[pat])
    assert accelerator.chip_device_paths() == ["/dev/vfio/0", "/dev/vfio/3"]
    assert accelerator.detect_tpu_chips() == 2
    layout["/dev/accel[0-9]*"] = ["/dev/accel0"]
    assert accelerator.detect_tpu_chips() == 1
    monkeypatch.setenv("RAY_TPU_CHIPS", "8")
    assert accelerator.detect_tpu_chips() == 8


def test_a_granted_process_off_the_tpu_raises(monkeypatch):
    """This process is on the CPU (conftest). Told it was granted a chip,
    claiming the device must fail loudly instead of carrying on."""
    monkeypatch.setattr(accelerator, "_claim", None)
    assert accelerator.claim_device()["platform"] == "cpu"  # grantless: fine
    monkeypatch.setenv(accelerator.GRANT_ENV, "0")
    with pytest.raises(RuntimeError, match="granted TPU chips"):
        accelerator.claim_device()


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Unset: one
    fixed path inside the checkout."""
    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert accelerator.COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setattr(accelerator, "_compile_stats", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
        jax.config.update("jax_compilation_cache_dir", None)
        assert accelerator._place_compile_cache()["dir"] == "/from/outside"
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.setattr(accelerator, "_compile_stats", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert accelerator._place_compile_cache()["dir"] == \
            accelerator.COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == \
            accelerator.COMPILE_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_who_holds_a_chip_is_read_from_proc(tmp_path, monkeypatch):
    """A process finds the device nodes it holds itself (its own fd
    listing contains an fd that is gone by the time it is read) and the
    holders among all processes."""
    node = tmp_path / "vfio3"
    node.write_bytes(b"")
    monkeypatch.setattr(accelerator, "chip_device_paths",
                        lambda: [str(node)])
    assert accelerator.chip_holders() == {}
    with open(node):
        assert accelerator._held_nodes(os.getpid(), {str(node)}) == [
            str(node)]
        assert accelerator.chip_holders() == {os.getpid(): [str(node)]}
    assert accelerator.chip_holders() == {}


class _Tpu:
    """What ``jax.devices()`` gives a granted process on a chip."""
    platform, device_kind, id = "tpu", "TPU v5 lite", 0


@pytest.fixture
def granted_node(tmp_path, monkeypatch):
    """A process granted chip 0 of a host whose one device node is a
    temp file (a plain file cannot be busy: the tests patch
    ``node_busy``), JAX's answer a TPU's, the marks collected."""
    import jax

    from ray_tpu._private import flight_recorder

    node = tmp_path / "vfio0"
    node.write_bytes(b"")
    marks = []
    monkeypatch.setattr(accelerator, "chip_device_paths",
                        lambda: [str(node)])
    monkeypatch.setattr(accelerator, "_claim", None)
    monkeypatch.setenv(accelerator.GRANT_ENV, "0")
    monkeypatch.setattr(jax, "devices", lambda: [_Tpu()])
    monkeypatch.setattr(
        flight_recorder, "mark",
        lambda kind, name, attrs=None, **kw: marks.append(
            (kind, name, attrs)))
    return str(node), marks


def test_a_granted_claim_waits_for_a_chip_a_dying_process_holds(
        granted_node, monkeypatch, capsys):
    """The node reads busy for 0.3 s (a worker in exit still holds it):
    the claim goes on within a poll of its release, and says how long it
    waited; a free node costs one probe and says nothing."""
    import threading
    import time

    node, marks = granted_node
    probed = []
    freed = threading.Event()

    def busy(path):
        probed.append(path)
        return not freed.is_set()

    monkeypatch.setattr(accelerator, "node_busy", busy)
    threading.Timer(0.3, freed.set).start()
    t0 = time.monotonic()
    claim = accelerator.claim_device()
    waited = time.monotonic() - t0
    assert claim["platform"] == "tpu" and claim["granted_chips"] == [0]
    assert 0.3 <= waited < 0.3 + 4 * accelerator.CHIP_POLL_S
    assert set(probed) == {node} and len(probed) >= 4
    (kind, name, attrs), = marks
    assert (kind, name) == ("accel", "chip_wait")
    assert attrs["nodes"] == node and "leaving" not in attrs
    assert 300 <= attrs["waited_ms"] < 300 + 4e3 * accelerator.CHIP_POLL_S
    assert f"for {node}" in capsys.readouterr().err

    del probed[:], marks[:]
    monkeypatch.setattr(accelerator, "_claim", None)
    assert accelerator.claim_device()["platform"] == "tpu"
    assert probed == [node] and marks == []
    assert capsys.readouterr().err == ""


def test_a_chip_busy_past_the_bound_fails_with_its_node_named(
        granted_node, monkeypatch):
    """Past the bound the backend initialises as it always did, and what
    it raises of a busy node carries the node's name and the wait."""
    import jax

    node, marks = granted_node

    def refused():
        raise RuntimeError("Unable to initialize backend 'tpu': open(): "
                           "Device or resource busy")

    monkeypatch.setattr(accelerator, "CHIP_WAIT_S", 0.2)
    monkeypatch.setattr(accelerator, "node_busy", lambda path: True)
    monkeypatch.setattr(jax, "devices", refused)
    with pytest.raises(RuntimeError) as e:
        accelerator.claim_device()
    assert node in str(e.value) and "still held" in str(e.value)
    assert "Device or resource busy" in str(e.value)
    assert marks[0][2]["waited_ms"] >= 200
    # a refusal that is not about a held node is raised as it came
    monkeypatch.setattr(accelerator, "node_busy", lambda path: False)
    with pytest.raises(RuntimeError, match="^Unable to initialize"):
        accelerator.claim_device()


def test_a_grantless_claim_probes_nothing(monkeypatch):
    """No grant: no node is opened, nothing is listed."""
    def never(*a):
        raise AssertionError("a grantless process looked at a chip")

    monkeypatch.setattr(accelerator, "_claim", None)
    monkeypatch.delenv(accelerator.GRANT_ENV, raising=False)
    monkeypatch.setattr(accelerator, "node_busy", never)
    monkeypatch.setattr(accelerator, "chip_device_paths", never)
    assert accelerator.claim_device()["platform"] == "cpu"


def test_a_claim_does_not_wait_for_a_node_it_holds_itself(
        granted_node, monkeypatch):
    """A process that touched the device before its claim holds its own
    node: that one is not probed (it would read busy for ever)."""
    node, marks = granted_node
    monkeypatch.setattr(accelerator, "node_busy", lambda path: True)
    monkeypatch.setattr(accelerator, "CHIP_WAIT_S", 0.2)
    with open(node):
        assert accelerator.claim_device()["platform"] == "tpu"
    assert marks == []


def test_a_busy_node_that_no_fd_table_shows_is_held_by_pid_0(
        tmp_path, monkeypatch):
    """A process in exit has no fd table and still holds its chips:
    ``chip_holders`` reports the node, and nothing once it is free; a
    node some table shows is that process's and is not probed."""
    nodes = [str(tmp_path / f"vfio{i}") for i in range(2)]
    for n in nodes:
        open(n, "wb").close()
    busy = {nodes[1]}
    probed = []
    monkeypatch.setattr(accelerator, "chip_device_paths", lambda: nodes)
    monkeypatch.setattr(accelerator, "node_busy",
                        lambda n: probed.append(n) or n in busy)
    assert accelerator.chip_holders() == {0: [nodes[1]]}
    with open(nodes[0]):
        del probed[:]
        assert accelerator.chip_holders() == {os.getpid(): [nodes[0]],
                                              0: [nodes[1]]}
        assert probed == [nodes[1]]
    busy.clear()
    assert accelerator.chip_holders() == {}


def test_only_ebusy_reads_as_busy(tmp_path, monkeypatch):
    """The probe opens the node read-write and closes it at once. A node
    that does not exist or may not be opened counts as free (a box
    without chips or rights behaves as it did); ``EBUSY`` alone is
    "held"."""
    import errno

    node = tmp_path / "vfio0"
    assert accelerator.node_busy(str(node)) is False  # no such node
    node.write_bytes(b"")
    before = set(os.listdir("/proc/self/fd"))
    assert accelerator.node_busy(str(node)) is False
    assert set(os.listdir("/proc/self/fd")) == before  # closed again
    opened = []

    def refuse(code):
        def _open(path, flags):
            opened.append((path, flags & os.O_ACCMODE))
            raise OSError(code, os.strerror(code))
        return _open

    monkeypatch.setattr(os, "open", refuse(errno.EACCES))
    assert accelerator.node_busy(str(node)) is False
    monkeypatch.setattr(os, "open", refuse(errno.EBUSY))
    assert accelerator.node_busy(str(node)) is True
    assert opened == [(str(node), os.O_RDWR)] * 2
