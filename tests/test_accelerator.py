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
