"""The program's own spans, read from the profiler's trace file.

In the process that holds the chip every flight-recorder span
(``ray_tpu/_private/flight_recorder.py``) is also a
``jax.profiler.TraceAnnotation``: an event on its thread's line of
``/host:CPU``, timed by the profiler on the clock of the device planes,
with the span's attrs as the event's stats. ``trace_reduce.load_xplane``
keeps names only, so this module reads the same file again for those
events and their attrs. Spans here are plain data, so that the
arithmetic can be tested on a small hand-made set:
``{"lines": [{"name": str, "events": [[name, start_ns, dur_ns,
{attr: value}], ...]}]}``, one line per host thread, program spans only.

A program that emits no such span (a parent commit) gives ``None``
everywhere: every reader built on this returns ``None`` and raises
nothing.
"""

from __future__ import annotations

import glob
import os
import re
import statistics
import sys

from benchmark import trace_reduce

PROGRAM = re.compile(r"^(serve|engine)\.")  # the recorder's serve family
# TraceMe's own encoding of metadata it could not attach as stats
ENCODED = re.compile(r"^(?P<name>[^#]+)#(?P<kv>.*)#$")


def _value(text: str):
    for cast in (int, float):
        try:
            return cast(text)
        except ValueError:
            pass
    return text


def split_name(raw: str) -> tuple[str, dict]:
    """``engine.prefill#bucket=512,prompts=1#`` -> the name and attrs."""
    m = ENCODED.match(raw)
    if not m:
        return raw, {}
    pairs = (kv.split("=", 1) for kv in m["kv"].split(",") if "=" in kv)
    return m["name"], {k: _value(v) for k, v in pairs}


def load(log_dir: str) -> dict | None:
    """The program spans of the newest ``*.xplane.pb`` under ``log_dir``
    as plain data, or None where there is no file or no such span."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    lines = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = []
            for ev in line.events:
                name, attrs = split_name(ev.name)
                if PROGRAM.match(name):
                    attrs.update(ev.stats)
                    events.append([name, int(ev.start_ns),
                                   int(ev.duration_ns), attrs])
            if events:
                lines.append({"name": line.name, "events": events})
    return {"lines": lines} if lines else None


def spans(facts: dict) -> dict | None:
    """The run's program spans, loaded once and kept in ``facts``."""
    if "spans" not in facts:
        log_dir = facts.get("log_dir")
        facts["spans"] = load(log_dir) if log_dir else None
    return facts["spans"]


def named(sp: dict | None, name: str) -> list:
    """Every event called ``name``, over all host lines."""
    if not sp:
        return []
    return [ev for line in sp["lines"] for ev in line["events"]
            if ev[0] == name]


def inside(line: dict, parent: list, name: str) -> list:
    """Events called ``name`` on ``line`` that lie within ``parent`` (a
    thread's spans nest: a child starts and ends inside its parent)."""
    _, s, d, _ = parent
    return [ev for ev in line["events"] if ev[0] == name
            and ev[1] >= s and ev[1] + ev[2] <= s + d]


def _say(metric: str, n: int, what: str) -> None:
    print(f"benchmark: {metric}: {n} {what} in the traced part",
          file=sys.stderr, flush=True)


def attr_values(facts, span_name: str, key: str, *, metric: str,
                where=None) -> list:
    """The ``key`` attr of every ``span_name`` event that carries it
    (and passes ``where``); the sample count goes to stderr."""
    evs = [ev for ev in named(spans(facts), span_name)
           if key in ev[3] and (where is None or where(ev[3]))]
    _say(metric, len(evs), f"{span_name} with {key}")
    return [ev[3][key] for ev in evs]


def attr_median(facts, span_name: str, key: str, *, metric: str,
                where=None):
    xs = attr_values(facts, span_name, key, metric=metric, where=where)
    return statistics.median(xs) if xs else None


def prefill_prompts_per_call(facts, metric: str):
    """Mean real prompts per call of the static-width prefill program:
    the engine's own count at the call (``engine.prefill``)."""
    xs = attr_values(facts, "engine.prefill", "prompts", metric=metric)
    return float(statistics.mean(xs)) if xs else None


def prefill_token_use_share(facts, metric: str):
    """Useful over attempted: the prompts' real tokens over the rows x
    bucket positions the prefill calls computed, in percent."""
    evs = [ev[3] for ev in named(spans(facts), "engine.prefill")
           if {"tokens", "rows", "bucket"} <= ev[3].keys()]
    _say(metric, len(evs), "engine.prefill")
    paid = sum(a["rows"] * a["bucket"] for a in evs)
    return 100.0 * sum(a["tokens"] for a in evs) / paid if paid else None


def pump_host_work_ms(facts, metric: str):
    """Median over ``serve.pump`` of its duration less its
    ``engine.readback`` child: what the host costs per chunk while the
    device is not being waited for. Also prints where the device's idle
    gaps fall among the program's spans."""
    sp = spans(facts)
    work = []
    for line in (sp or {"lines": []})["lines"]:
        for pump in (ev for ev in line["events"] if ev[0] == "serve.pump"):
            waited = sum(ev[2] for ev in inside(line, pump,
                                                "engine.readback"))
            work.append((pump[2] - waited) / 1e6)
    _say(metric, len(work), "serve.pump")
    if sp and facts.get("trace"):
        print("benchmark: device idle gaps by program span: "
              f"{charge_gaps(facts['trace'], sp)}; spans against the "
              f"device: {against_device(facts['trace'], sp)}",
              file=sys.stderr, flush=True)
    return statistics.median(work) if work else None


def against_device(trace: dict, sp: dict) -> dict:
    """That the spans and the device planes share one clock, in two
    numbers a reader can check: how long after the end of the decode
    chunk it waited for each ``engine.readback`` ended (ms: median and
    largest, over the read-backs with a chunk ending inside them), and
    the count of ``engine.prefill`` spans beside the executions of the
    prefill program that began inside the spans' part of the trace."""
    planes = trace_reduce.device_planes(trace)
    if not planes:
        return {}
    mods = [(trace_reduce.program_name(n), s, s + d) for n, s, d in next(
        (ln["events"] for ln in planes[0]["lines"]
         if ln["name"] == trace_reduce.MODULES_LINE), [])]
    chunk_ends = sorted(e for n, _, e in mods if n == "jit_decode_chunk")
    late = []
    for _, s, d, _ in named(sp, "engine.readback"):
        ends = [e for e in chunk_ends if s <= e <= s + d]
        if ends:
            late.append((s + d - ends[-1]) / 1e6)
    pumps = named(sp, "serve.pump")
    lo = min((ev[1] for ev in pumps), default=0)
    hi = max((ev[1] + ev[2] for ev in pumps), default=0)
    return {
        "readbacks": len(late),
        "readback_end_after_chunk_end_ms_median":
            statistics.median(late) if late else None,
        "readback_end_after_chunk_end_ms_max": max(late, default=None),
        "prefill_spans": sum(lo <= ev[1] <= hi for ev in
                             named(sp, "engine.prefill")),
        "prefill_executions": sum(
            n == "jit__prefill_batch_into_slots" and lo <= s <= hi
            for n, s, _ in mods),
    }


def charge_gaps(trace: dict, sp: dict, top: int = 8) -> list:
    """[[program span, seconds]]: each idle gap of the first device plane
    charged to the innermost (shortest) program span over its middle,
    ``outside-spans`` where there is none; the ``top`` largest."""
    planes = trace_reduce.device_planes(trace)
    win = trace_reduce.window(trace)
    if not planes or win is None:
        return []
    busy = trace_reduce.union(
        (s, s + d) for _, s, d in next(
            (ln["events"] for ln in planes[0]["lines"]
             if ln["name"] == trace_reduce.OPS_LINE), []))
    by_span: dict[str, float] = {}
    # (the 2000 largest, as trace_reduce.breakdown takes them)
    for s, e in sorted(trace_reduce.gaps(busy, win),
                       key=lambda g: g[0] - g[1])[:2000]:
        mid = (s + e) // 2
        over = [ev for line in sp["lines"] for ev in line["events"]
                if ev[2] > 0 and ev[1] <= mid < ev[1] + ev[2]]
        what = min(over, key=lambda ev: ev[2])[0] if over \
            else "outside-spans"
        by_span[what] = by_span.get(what, 0.0) + (e - s) / 1e9
    return [[k, v] for k, v in
            sorted(by_span.items(), key=lambda kv: -kv[1])[:top]]
