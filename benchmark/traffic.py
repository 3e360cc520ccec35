"""One general traffic generator. A mix is a data file of parameters.

Variance reduction that every mix follows: the request shapes, their
order and their arrival times are fixed by the file (and, for an open
loop, by the window's length); ``seed`` chooses the token ids (and the
weights). Order is part of the work here: which prompts share a
static-width prefill call, and which requests queue behind a burst,
follow from it, and a tail is the tail of one particular order. So no
run draws its lengths or its order afresh, and two runs of one commit
differ only by the machine.

``shapes``:
- ``{"mode": "entries", "entries": [[prompt, out], ...]}``: the list as
  written, from its first entry, cycled.
- ``{"mode": "quantiles", "prompt": dist, "output": dist}``: the N
  stratified quantiles of each distribution, paired by one fixed
  shuffle.
  ``dist`` is ``{"dist": "loguniform", "lo", "hi"}`` or ``{"dist":
  "lognormal", "median", "sigma", "lo", "hi"}`` (clipped).

``arrivals`` (open loop): ``{"rate_per_s": r, "burst": b}``: bursts of
``b`` requests (1 = none) whose gaps are the stratified quantiles of an
exponential at ``r / b`` bursts a second, in one fixed shuffled order.
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist


def _strata(n: int):
    return [(i + 0.5) / n for i in range(n)]


def quantiles(dist: dict, n: int) -> list[int]:
    """The ``n`` stratified quantiles of ``dist``, as whole tokens."""
    kind = dist["dist"]
    if kind == "loguniform":
        lo, hi = math.log(dist["lo"]), math.log(dist["hi"])
        return [int(math.exp(lo + u * (hi - lo))) for u in _strata(n)]
    if kind == "lognormal":
        mu, nd = math.log(dist["median"]), NormalDist()
        xs = (math.exp(mu + dist["sigma"] * nd.inv_cdf(u))
              for u in _strata(n))
        return [int(min(max(x, dist["lo"]), dist["hi"])) for x in xs]
    raise ValueError(f"unknown distribution {kind!r}")


def exponential_gaps(rate: float, n: int) -> list[float]:
    """Stratified quantiles of an exponential, scaled so that they sum
    to ``n / rate`` exactly: every run offers the window the same load."""
    raw = [-math.log(1.0 - u) for u in _strata(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def _token_ids(rnd: random.Random, n: int, vocab: int) -> list[int]:
    return [rnd.randrange(1, vocab) for _ in range(n)]


ORDER_SEED = 20260927  # the one order every run offers


def shapes_for(spec: dict, n: int, order: random.Random) -> list[tuple]:
    """``n`` (prompt, out) shapes in the order they are offered."""
    if spec["mode"] == "entries":
        entries = [tuple(e) for e in spec["entries"]]
        return [entries[i % len(entries)] for i in range(n)]
    if spec["mode"] == "quantiles":
        prompts = quantiles(spec["prompt"], n)
        outs = quantiles(spec["output"], n)
        order.shuffle(prompts)
        order.shuffle(outs)
        return list(zip(prompts, outs))
    raise ValueError(f"unknown shapes mode {spec['mode']!r}")


def closed_plan(traffic: dict, seed: int, vocab: int) -> dict:
    """Closed loop: ``clients`` callers take the next entry of ONE shared
    list, cycling, so requests reach the system in the list's order."""
    rnd = random.Random(seed)
    n = len(traffic["shapes"]["entries"])
    shapes = shapes_for(traffic["shapes"], n, random.Random(ORDER_SEED))
    return {"loop": "closed", "clients": int(traffic["clients"]),
            "opens_after_completed":
                int(traffic["window"]["opens_after_completed"]),
            "requests": [{"prompt_ids": _token_ids(rnd, p, vocab),
                          "max_tokens": o} for p, o in shapes]}


def open_plan(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    """Open loop at a fixed rate: a ramp that is not measured, then the
    window's own N = rate x seconds requests, each with its due time."""
    rnd, order = random.Random(seed), random.Random(ORDER_SEED)
    arr = traffic["arrivals"]
    rate, burst = float(arr["rate_per_s"]), int(arr.get("burst", 1))
    ramp_s = float(traffic["window"]["ramp_s"])
    requests, t = [], 0.0
    for span, measured in ((ramp_s, False), (float(seconds), True)):
        n_bursts = max(1, round(rate * span / burst))
        gaps = exponential_gaps(rate / burst, n_bursts)
        order.shuffle(gaps)
        shapes = shapes_for(traffic["shapes"], n_bursts * burst, order)
        t_end, i = t + span, 0
        for g in gaps:
            t += g
            for _ in range(burst):
                p, o = shapes[i]
                i += 1
                # (the last gap's rounding must not push a request out)
                requests.append({"due": min(t, t_end - 1e-6),
                                 "measured": measured,
                                 "prompt_ids": _token_ids(rnd, p, vocab),
                                 "max_tokens": o})
        t = t_end
    return {"loop": "open", "ramp_s": ramp_s, "seconds": float(seconds),
            "requests": requests}


def plan(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    if traffic["loop"] == "closed":
        return closed_plan(traffic, seed, vocab)
    if traffic["loop"] == "open":
        return open_plan(traffic, seed, vocab, seconds)
    raise ValueError(f"unknown loop {traffic['loop']!r}")
