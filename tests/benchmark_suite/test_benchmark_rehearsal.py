"""CPU rehearsal of the benchmark's two drivers at tiny sizes.

``python3 -m benchmark.run ... --rehearse-cpu`` runs a cell's whole
control flow (cluster, pool or trainer, load, reference check, result
line) with test-sized widths on the CPU and stamps ``platform: cpu``.
It says nothing about speed. One cell per driver is tier-1; the other
two cells are ``-m slow``: run them before a chip call that changes what
they drive. Without the flag the same command must refuse to report
from a box that has no TPU.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DOC, CHAT = "internlm2-1.8b.doc-saturated", "internlm2-1.8b.chat-steady"
TRAIN1 = "mistral-7b-v0.3-1chip.pretrain-4k"
TRAIN4 = "internlm2-1.8b.pretrain-4k-fsdp2tp2"
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(workload, *flags, trace=0, seconds=4):
    # (the suite's eight virtual devices are conftest's, not the cell's)
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_CHIPS", "XLA_FLAGS")}
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", workload,
         "--seed", "3000000001", "--seconds", str(seconds), "--trace",
         str(trace), *flags], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=420)


def _rehearse(workload, trace, chips):
    proc = _run(workload, "--rehearse-cpu", trace=trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, "standard output carries the result alone"
    line = json.loads(lines[0])
    assert set(line) == LINE_KEYS | ({"breakdown"} if trace else set())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
    return line


def test_the_real_path_refuses_to_report_without_a_tpu():
    proc = _run(DOC)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "needs 1 TPU chip(s), found 0" in proc.stderr


def test_serve_driver_rehearses_on_the_cpu():
    line = _rehearse(DOC, trace=0, chips=1)
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert line["metrics"]["out_tokens_per_s"]["value"] > 0


def test_train_driver_rehearses_on_the_cpu():
    # traced, on four virtual devices: the sharded job's whole path
    line = _rehearse(TRAIN4, trace=1, chips=4)
    assert {"busy_s", "window_s"} <= set(line["device"])
    # a CPU trace has no device plane: nothing is written under the
    # name of a device metric
    assert not line["metrics"].keys() & {
        "train_step_ms", "flash_fwd_roofline", "collective_exposed_share"}


@pytest.mark.slow
def test_chat_cell_rehearses_on_the_cpu():
    line = _rehearse(CHAT, trace=1, chips=1)
    assert {"gen_lateness_p95_ms", "idle_ttft_ms"} <= set(line["metrics"])


@pytest.mark.slow
def test_one_chip_train_cell_rehearses_on_the_cpu():
    line = _rehearse(TRAIN1, trace=0, chips=1)
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
