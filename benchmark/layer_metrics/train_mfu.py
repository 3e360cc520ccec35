"""FLOPs the forward and backward need per token (recomputation not
counted) x tokens/s of this run / (chips x the bf16 peak)."""
from benchmark import model_math


def read(facts):
    t = facts["train"]
    peak = model_math.peaks(facts["device"]["kind"])["bf16_flops_per_s"]
    flops = model_math.train_flops_per_token(t["model"], t["seq"])
    return 100.0 * flops * t["tokens_per_s"] / (t["chips"] * peak)
