"""Percentiles, spreads and whole-step arithmetic: the yardstick's sums."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0-100), linear between closest ranks; a
    missing request (``inf``) sorts last and so lands in the tail."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if lo == hi or math.isinf(xs[hi]):
        return float(xs[hi] if k > lo else xs[lo])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def median(values) -> float:
    return float(statistics.median(values))


def spread(values) -> float:
    """Interquartile distance as a share of the median: the contract's
    measure (``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def whole_steps(step_ends, t_open: float, seconds: float, tokens_per_step: int):
    """Tokens per second over the whole steps that ended inside the
    window: ``step_ends`` are the instants each step's loss was read, in
    order, the first step starting at ``t_open``.
    -> (tokens per second, steps counted)."""
    inside = [t for t in step_ends if t <= t_open + seconds]
    if not inside:
        raise ValueError("no whole step inside the window")
    return len(inside) * tokens_per_step / (inside[-1] - t_open), len(inside)


def tokens_in_window(arrivals, t_open: float, t_close: float) -> int:
    """``arrivals`` are (instant, tokens) batches as a client read them."""
    return sum(n for t, n in arrivals if t_open <= t < t_close)
