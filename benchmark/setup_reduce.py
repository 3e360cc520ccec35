"""A replica's bring-up, read from its ``serve.setup`` mark.

The replica keeps what its start and its programs' first calls cost
(``LLMServer.setup_record``: the parts of ``serve.replica_start`` and
the sums over the engine's ``engine.compiled`` marks, in ms, with three
stamps on ``time.monotonic``'s clock) and leaves it as a ring-only mark
where a capture starts, beside ``engine.state_init``: minutes after the
fact, inside the one file the readers see. Six readers under
``layer_metrics/`` (``setup_*``) divide it; all of them move ``setup_s``.

A program without the mark (a parent commit) gives ``None`` everywhere:
nothing here raises on it.
"""

from __future__ import annotations

import sys

from benchmark import span_reduce

MARK = "serve.setup"
# what of ``setup_s`` the replica's record explains, one after the other
# in time: the process before ``LLMServer.__init__``, the bring-up
# (claim, weights, engine), the first call of every program
EXPLAINED = ("process_age_ms", "replica_start_ms", "first_call_ms")


def record(facts, metric: str) -> dict | None:
    """The newest ``serve.setup`` mark's attrs (a run that captured
    twice holds two), or None."""
    evs = span_reduce.named(span_reduce.spans(facts), MARK)
    span_reduce._say(metric, len(evs), MARK)
    return max(evs, key=lambda ev: ev[1])[3] if evs else None


def _tell(facts, metric: str, rec: dict, value, unit: str) -> None:
    """One line: the metric beside ``setup_s`` and what the record
    explains of it; the first reader of a run also prints the record."""
    if not facts.setdefault("_setup_record_told", False):
        facts["_setup_record_told"] = True
        print(f"benchmark: {MARK}: {rec}", file=sys.stderr, flush=True)
    parts = {k: rec[k] / 1e3 for k in EXPLAINED if k in rec}
    line = f"benchmark: {metric}: {value} {unit}"
    setup_s = facts.get("setup_s")
    if setup_s is not None and len(parts) == len(EXPLAINED):
        explained = sum(parts.values())
        warm_up_s = (rec.get("last_compile_mono_ns", 0)
                     - rec.get("ready_mono_ns", 0)) / 1e9
        line += (f"; setup_s {setup_s:.3f} s, of which the replica "
                 f"explains {explained:.3f} s ("
                 + " + ".join(f"{k} {v:.3f}" for k, v in parts.items())
                 + f"), not {setup_s - explained:.3f} s (cluster start, "
                 "the warm-up prompts' device time, the reference check, "
                 f"the ramp); ready to the last first call {warm_up_s:.3f} s")
    print(line, file=sys.stderr, flush=True)


def seconds(facts, metric: str, *keys: str, beside: tuple = ()):
    """The sum of the record's ``keys`` (ms) in seconds, or None where
    the mark or one of them is missing; ``beside``: keys printed with
    it."""
    rec = record(facts, metric)
    if rec is None or not all(k in rec for k in keys):
        return None
    value = sum(rec[k] for k in keys) / 1e3
    also = "".join(f" ({k} {rec[k]})" for k in beside if k in rec)
    _tell(facts, metric, rec, f"{value:.3f}", "s" + also)
    return value


def cache_hit_share(facts, metric: str):
    """``proc_cache_hits`` / ``proc_compile_requests`` of the replica's
    process in percent: what the machine's persistent compile cache
    held of what the process asked it for. None without the mark or
    where nothing was asked."""
    rec = record(facts, metric)
    if rec is None or not rec.get("proc_compile_requests"):
        return None
    value = 100.0 * rec.get("proc_cache_hits", 0) \
        / rec["proc_compile_requests"]
    _tell(facts, metric, rec, f"{value:.1f}", "%")
    return value
