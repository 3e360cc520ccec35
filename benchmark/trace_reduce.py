"""From a profiler trace to numbers. The one place that knows how.

A trace here is plain data, so that the arithmetic can be tested on a
small recorded one: ``{"planes": [{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, dur_ns], ...]}]}]}``. ``load_xplane`` makes
that from the ``.xplane.pb`` the JAX profiler writes, with nothing but
JAX. On a TPU the device planes are ``/device:TPU:<n>``; their line
``XLA Modules`` has one event per program execution and ``XLA Ops`` one
per operation (an operation that holds others, a ``while`` or a fused
call, spans them). Host threads are lines of ``/host:CPU``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")


def op_name(raw: str) -> str:
    """An operation's event carries its whole HLO line (``%fusion.3 =
    bf16[8,2048]{...} fusion(...)``): keep the instruction's name. A
    custom call (on a TPU: a Pallas kernel) is named by XLA after the
    scope it was traced in (``%jvp__.1``, ``%checkpoint.12``), which says
    nothing, so it becomes ``custom-call/<n>out/<instruction>`` with the
    number of arrays it returns."""
    head, sep, rest = raw.partition(" = ")
    inst = head.lstrip("%")
    if sep and " custom-call(" in rest:
        n_out = rest.split(" custom-call(", 1)[0].count("[")
        return f"custom-call/{n_out}out/{inst}"
    return inst


def load_xplane(log_dir: str) -> dict:
    """The newest ``*.xplane.pb`` under ``log_dir`` as plain data."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[op_name(ev.name), int(ev.start_ns),
                       int(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ------------------------------------------------------------ intervals


def union(intervals) -> list[tuple[int, int]]:
    """Disjoint sorted cover of ``intervals`` ((start, end) pairs)."""
    out: list[list[int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """The part of disjoint sorted ``a`` that disjoint sorted ``b``
    does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(cover, window) -> list[tuple[int, int]]:
    return subtract([window], cover)


# --------------------------------------------------------------- planes


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _spans(events):
    return [(s, s + d) for _, s, d in events]


def window(trace: dict) -> tuple[int, int] | None:
    """First operation's start to last operation's end over the device
    planes: the traced window as the device saw it."""
    spans = [sp for p in device_planes(trace)
             for sp in _spans(_line(p, OPS_LINE))]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def busy(trace: dict) -> dict | None:
    """-> {"busy_s", "window_s"}: seconds in which an operation ran,
    averaged over the device planes, and the window's length."""
    win = window(trace)
    if win is None:
        return None
    per_plane = [total(union(_spans(_line(p, OPS_LINE))))
                 for p in device_planes(trace)]
    return {"busy_s": statistics.mean(per_plane) / 1e9,
            "window_s": (win[1] - win[0]) / 1e9}


def program_name(event_name: str) -> str:
    """``jit_decode_chunk(1234567)`` -> ``jit_decode_chunk``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def program_durations(trace: dict) -> dict[str, list[float]]:
    """Seconds of every execution of every program, over all device
    planes (a program sharded over four chips runs once on each)."""
    out: dict[str, list[float]] = {}
    for p in device_planes(trace):
        for name, _, d in _line(p, MODULES_LINE):
            out.setdefault(program_name(name), []).append(d / 1e9)
    return out


def program_share(trace: dict, program: str) -> float | None:
    """Device time of ``program`` over the window, mean over planes."""
    win, durs = window(trace), program_durations(trace)
    if win is None or program not in durs:
        return None
    return sum(durs[program]) / len(device_planes(trace)) \
        / ((win[1] - win[0]) / 1e9)


def leaves(events) -> list:
    """Events that hold no other event (a ``while`` or a call holds its
    body's operations and would otherwise count their time twice)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = []
    for i, (name, s, d) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[1] >= s + d or nxt[1] + nxt[2] > s + d:
            out.append((name, s, d))
    return out


def op_seconds(trace: dict, pattern: str) -> list[float]:
    """Per device plane, the summed seconds of the leaf operations whose
    name matches ``pattern`` (a regular expression, searched)."""
    rx = re.compile(pattern)
    return [sum(d for n, _, d in leaves(_line(p, OPS_LINE))
                if rx.search(n)) / 1e9 for p in device_planes(trace)]


def exposed_collective_share(trace: dict) -> float | None:
    """Time in collectives during which no other operation runs on that
    device, over the window; mean over the device planes."""
    win = window(trace)
    if win is None:
        return None
    shares = []
    for p in device_planes(trace):
        lv = leaves(_line(p, OPS_LINE))
        coll = union((s, s + d) for n, s, d in lv if COLLECTIVE.match(n))
        comp = union((s, s + d) for n, s, d in lv
                     if not COLLECTIVE.match(n))
        shares.append(total(subtract(coll, comp)) / (win[1] - win[0]))
    return statistics.mean(shares)


def op_kinds(trace: dict, top: int = 12) -> list:
    """Leaf operations of the first device plane grouped by the stem of
    their name (``fusion.12`` -> ``fusion``): [[stem, seconds], ...]."""
    planes = device_planes(trace)
    kinds: dict[str, float] = {}
    for name, _, d in leaves(_line(planes[0], OPS_LINE)) if planes else ():
        stem = re.sub(r"[.\d]+$", "", name)
        kinds[stem] = kinds.get(stem, 0.0) + d / 1e9
    return sorted(kinds.items(), key=lambda kv: -kv[1])[:top]


# ------------------------------------------------------------ breakdown


def _host_lines(trace: dict):
    for p in trace["planes"]:
        if p["name"].startswith("/host:"):
            for line in p["lines"]:
                if line["events"]:
                    yield line


def breakdown(trace: dict, top: int = 10) -> dict:
    """-> {"device_ops": [[program/op, seconds]], "idle_gaps": [[what the
    host was doing, seconds]]}, the ``top`` largest of each, from the
    first device plane. A gap is charged to the innermost host event
    that spans its middle."""
    planes = device_planes(trace)
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    plane = planes[0]
    mods = sorted(_line(plane, MODULES_LINE), key=lambda e: e[1])
    starts = [m[1] for m in mods]
    ops: dict[str, float] = {}
    lv = leaves(_line(plane, OPS_LINE))
    for name, s, d in lv:
        i = bisect.bisect_right(starts, s) - 1
        prog = program_name(mods[i][0]) if i >= 0 \
            and s < mods[i][1] + mods[i][2] else "-"
        key = f"{prog}/{name}"
        ops[key] = ops.get(key, 0.0) + d / 1e9
    win = window(trace)
    idle = gaps(union(_spans(_line(plane, OPS_LINE))), win)
    host = []
    for line in _host_lines(trace):
        evs = sorted(line["events"], key=lambda e: e[1])
        host.append((line["name"], evs, [e[1] for e in evs]))
    by_what: dict[str, float] = {}
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:2000]:
        mid, best = (s + e) // 2, None
        for lname, evs, st in host:
            i = bisect.bisect_right(st, mid) - 1
            # walk back over the events that start before the middle
            # and keep the shortest one that still spans it
            for k in range(i, max(-1, i - 64), -1):
                n, es, ed = evs[k]
                if es + ed > mid and (best is None or ed < best[1]):
                    best = (f"{lname}:{n}", ed)
        what = best[0] if best else "unattributed"
        by_what[what] = by_what.get(what, 0.0) + (e - s) / 1e9

    def largest(d):
        return [[k[:160], v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": largest(ops), "idle_gaps": largest(by_what)}
