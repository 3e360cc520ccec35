"""Device time by model part: the trace read through the program's own
map.

A TPU trace's ``XLA Ops`` events carry the instruction's name and no
``op_name``; the replica that was captured writes, beside the
``.xplane.pb``, ``program_parts.json`` (``LLMServer.stop_trace``;
``ray_tpu/models/program_parts.py``): for every program its engine ran,
``{instruction: part}`` from the compiled text, one map a signature
(the prefill program is three programs under one name, one a bucket).
Here every event of the first device plane is put to its program as
``trace_reduce.breakdown`` puts it and looked up in that map. A leaf is
charged its duration, an event that holds others (a ``while``, a call)
the part of its span that none of them covers, so that the parts sum to
the time in which the device ran an operation.

Maps and traces are plain data, so the arithmetic is tested on a small
hand-made pair (``tests/benchmark_suite/test_part_reduce.py``). No map,
no device plane, a parent commit: every reader returns ``None`` and
raises nothing.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

from benchmark import trace_reduce

FILE = "program_parts.json"
UNSCOPED, UNJOINED, MIXED = "unscoped", "unjoined", "+mixed"
# (``trace_reduce.op_name`` names a custom call by what it returns)
_CUSTOM_CALL = re.compile(r"^custom-call/\d+out/")


def load_map(log_dir: str | None) -> dict | None:
    """``<log_dir>/program_parts.json`` as the replica wrote it, or None."""
    path = os.path.join(log_dir, FILE) if log_dir else ""
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def self_times(events) -> list:
    """[(name, start, the nanoseconds of its span no event inside it
    covers)] for the events of one line: a leaf's duration, a holder's
    rest."""
    out, stack = [], []  # stack: [name, start, end, covered by children]

    def close(until):
        while stack and stack[-1][2] <= until:
            name, s, e, covered = stack.pop()
            out.append((name, s, e - s - covered))

    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][3] += min(s + d, stack[-1][2]) - s
        stack.append([name, s, s + d, 0])
    close(float("inf"))
    return out


def part_of(entry: str) -> tuple[str, bool]:
    """A map's entry -> (part, whether its instruction mixes parts):
    ``attn/attn_window+mixed`` -> (``attn/attn_window``, True); an
    unscoped instruction's ``unscoped:<op_name>`` -> ``unscoped``."""
    mixed = entry.endswith(MIXED)
    return entry.removesuffix(MIXED).split(":", 1)[0], mixed


def by_part(trace: dict, doc: dict, top: int = 10) -> dict | None:
    """-> {"busy_s", "programs": {program: {part: seconds}}, "mixed_s":
    {program: seconds}, "unscoped_ops": [[program/instruction, op_name,
    seconds]]} from the first device plane. ``unscoped`` (the map has
    the instruction under no part) and ``unjoined`` (the map has no such
    instruction, or no such program) stand among a program's parts;
    ``mixed_s`` is time ALSO counted under a part. Executions of one
    program name under several ids (one a signature) each take the map
    of that name that knows most of their time."""
    planes = trace_reduce.device_planes(trace) if trace else []
    if not planes or not doc:
        return None
    lines = {ln["name"]: ln["events"] for ln in planes[0]["lines"]}
    ops = lines.get(trace_reduce.OPS_LINE, [])
    if not ops:
        return None
    mods = sorted(lines.get(trace_reduce.MODULES_LINE, []),
                  key=lambda e: e[1])
    starts = [m[1] for m in mods]
    # seconds of every instruction name, by execution id of its program
    seen: dict[str, dict[str, float]] = {}
    for name, s, ns in self_times(ops):
        i = bisect.bisect_right(starts, s) - 1
        run = mods[i][0] if i >= 0 and s < mods[i][1] + mods[i][2] else "-"
        by_name = seen.setdefault(run, {})
        name = _CUSTOM_CALL.sub("", name)
        by_name[name] = by_name.get(name, 0.0) + ns / 1e9
    programs: dict[str, dict[str, float]] = {}
    mixed: dict[str, float] = {}
    unscoped: dict[tuple, float] = {}
    for run, by_name in seen.items():
        program = trace_reduce.program_name(run)
        variants = [v["parts"] for v in doc["programs"].get(program, [])]
        parts = max(variants, default={}, key=lambda v: sum(
            t for n, t in by_name.items() if n in v))
        tally = programs.setdefault(program, {})
        for name, t in by_name.items():
            part, mixes = part_of(parts[name]) if name in parts \
                else (UNJOINED, False)
            tally[part] = tally.get(part, 0.0) + t
            if mixes:
                mixed[program] = mixed.get(program, 0.0) + t
            if part == UNSCOPED:
                op = (f"{program}/{name}", parts[name].partition(":")[2])
                unscoped[op] = unscoped.get(op, 0.0) + t
    busy = trace_reduce.total(trace_reduce.union(
        (s, s + d) for _, s, d in ops)) / 1e9
    return {"busy_s": busy, "programs": programs, "mixed_s": mixed,
            "unscoped_ops": [[*op, t] for op, t in sorted(
                unscoped.items(), key=lambda kv: -kv[1])[:top]]}


def table(facts: dict) -> dict | None:
    """The run's :func:`by_part`, made once, kept in ``facts`` and
    printed whole on stderr."""
    if "device_parts" not in facts:
        doc = load_map(facts.get("log_dir"))
        facts["device_parts"] = t = by_part(facts.get("trace"), doc)
        if t is not None:
            shown = {p: {k: round(v, 4) for k, v in sorted(
                parts.items(), key=lambda kv: -kv[1])}
                for p, parts in t["programs"].items()}
            print(f"benchmark: device time by part: {shown}; busy "
                  f"{t['busy_s']:.4f} s; mixed {t['mixed_s']}; the map: "
                  f"made in {doc.get('seconds')} s for engine "
                  f"{doc.get('engine')!r}; the largest unscoped "
                  f"operations: {t['unscoped_ops']}",
                  file=sys.stderr, flush=True)
    return facts["device_parts"]


def share_pct(facts: dict, part: str):
    """Seconds of ``part`` (with what stands under it: ``attn`` holds
    ``attn/attn_window``) over the device's busy seconds of the traced
    part, all programs together, in percent; None without a map."""
    t = table(facts)
    if t is None or not t["busy_s"]:
        return None
    return 100.0 * sum(
        s for parts in t["programs"].values() for p, s in parts.items()
        if p.split("/")[0] == part) / t["busy_s"]
