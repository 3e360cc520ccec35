"""Least time of a decode step (bytes it must read: bf16 weights once
and the live cache rows, at the HBM peak) over the measured step time.
No kernel is on the serving path; the chunk program stands in."""
from benchmark import model_math
from benchmark.metric_lib import DECODE, program_median_ms
from benchmark.manifest import model_fields


def read(facts):
    chunk_ms = program_median_ms(facts, DECODE)
    if chunk_ms is None or not facts["client"]["shapes"]:
        return None
    eng = facts["engine"]
    nbytes = model_math.decode_step_bytes(
        model_fields(facts["model"]), eng["slots"],
        model_math.mean_live_rows(facts["client"]["shapes"]))
    least = nbytes / model_math.peaks(
        facts["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / (chunk_ms / 1e3 / eng["chunk_tokens"])
