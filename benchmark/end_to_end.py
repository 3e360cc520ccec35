"""What each end-to-end metric is, over ALL the work and ALL the time of
the window: a rate is taken over the whole window, a tail is the tail of
all requests (a failed or refused one counts with the client's timeout)."""

from __future__ import annotations

from benchmark import stats

VALUE = {
    "setup_s": lambda f: f["setup_s"],
    "ttft_p95_ms": lambda f: 1e3 * stats.percentile(f["client"]["ttft_s"], 95),
    "tpot_p50_ms": lambda f: 1e3 * stats.median(f["client"]["tpot_s"]),
    "out_tokens_per_s":
        lambda f: f["client"]["tokens_in_window"] / f["window_s"],
    "train_tokens_per_s": lambda f: f["train"]["tokens_per_s"],
}
