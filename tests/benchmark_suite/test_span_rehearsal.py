"""The program's spans reach the profiler's host plane end to end: a
traced CPU rehearsal of the saturated serving cell (tiny widths, never a
measurement) must bring back the per-layer metrics that are read from
``engine.prefill`` and ``serve.pump`` spans in the trace file, beside the
ones the cell had."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DOC = "internlm2-1.8b.doc-saturated"


def test_traced_rehearsal_reads_the_span_metrics():
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_CHIPS", "XLA_FLAGS")}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", DOC,
         "--seed", "3000000001", "--seconds", "4", "--trace", "1",
         "--rehearse-cpu"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    got = line["metrics"]
    assert {"tokens_per_pump.doc", "prefill_prompts_per_call.doc",
            "prefill_token_use_share.doc",
            "pump_host_work_ms.doc"} <= set(got)
    # 4 slots, 6 callers: a call holds between one prompt and four
    assert 1.0 <= got["prefill_prompts_per_call.doc"]["value"] <= 4.0
    assert 0.0 < got["prefill_token_use_share.doc"]["value"] <= 100.0
    assert got["pump_host_work_ms.doc"]["value"] > 0.0
    # every reader says how many spans it read
    assert "engine.prefill in the traced part" in proc.stderr
    assert "serve.pump in the traced part" in proc.stderr
