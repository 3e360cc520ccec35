"""The load generator: one process off the chip, one thread, asyncio.

    python3 -m benchmark.loadgen <plan.json> <result.json>

Sends the plan's requests to the HTTP proxy, streamed, and stamps every
token batch as it arrives (``time.monotonic``, which all processes of
one machine share). It prints ``OPEN <instant>`` on its standard output
when the measured window opens, so that its parent can take counters
and start a trace at the same instant. Never imports JAX.
"""

from __future__ import annotations

import asyncio
import json
import sys
import time

REQUEST_TIMEOUT_S = 120.0


async def _stream(addr, body: dict, rec: dict, accepted=None,
                  started=None) -> None:
    """One streamed POST -> fills ``rec`` (sent, arrivals, ok, error).
    ``accepted`` is set once the reply's headers are in (the proxy sends
    them when the pool has queued the request at its replica),
    ``started`` when the first tokens are."""
    payload = json.dumps({**body, "stream": True}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(*addr)
        rec["sent"] = time.monotonic()
        writer.write(
            f"POST /llm HTTP/1.1\r\nHost: {addr[0]}\r\n"
            "Content-Type: application/json\r\nConnection: close\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
        await writer.drain()
        status = (await reader.readline()).split()
        chunked = False
        while True:
            h = await reader.readline()
            if h in (b"\r\n", b"\n", b""):
                break
            if h.lower().startswith(b"transfer-encoding") \
                    and b"chunked" in h.lower():
                chunked = True
        if len(status) < 2 or status[1] != b"200" or not chunked:
            rec["error"] = f"status {status[1:2]}, chunked={chunked}"
            return
        if accepted is not None:
            accepted.set()
        while True:  # one NDJSON message per HTTP chunk
            size = int((await reader.readline()).strip() or b"0", 16)
            if size == 0:
                break
            msg = json.loads(await reader.readexactly(size))
            await reader.readexactly(2)
            if "error" in msg:
                rec["error"] = str(msg["error"])
                return
            if msg.get("tokens"):
                rec["arrivals"].append((time.monotonic(),
                                        len(msg["tokens"])))
                rec["tokens"].extend(msg["tokens"])
                if started is not None:
                    started.set()
            if msg.get("done"):
                rec["ok"] = True
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        rec["end"] = time.monotonic()
        for ev in (accepted, started):
            if ev is not None:
                ev.set()
        if writer is not None:
            writer.close()


def _new_rec(i: int, body: dict, **kw) -> dict:
    return {"i": i, "prompt_len": len(body["prompt_ids"]),
            "max_tokens": body["max_tokens"], "sent": None, "end": None,
            "arrivals": [], "tokens": [], "ok": False, "error": None, **kw}


async def _one(addr, body, rec, accepted=None, started=None):
    try:
        await asyncio.wait_for(_stream(addr, body, rec, accepted, started),
                               REQUEST_TIMEOUT_S)
    except asyncio.TimeoutError:
        rec["error"] = rec["error"] or "timeout"
        rec["end"] = time.monotonic()


async def idle_probe(addr, body: dict, n: int) -> list[float]:
    """``n`` one-token requests, one after another, on the idle system:
    the floor of proxy -> pool -> replica -> one prefill -> back."""
    out = []
    for i in range(n):
        rec = _new_rec(i, body)
        await _one(addr, {**body, "max_tokens": 1}, rec)
        if rec["ok"] and rec["arrivals"]:
            out.append(rec["arrivals"][0][0] - rec["sent"])
    return out


async def run_closed(addr, plan: dict, seconds: float) -> dict:
    """``clients`` callers share one cursor over the request list. One
    request is handed out at a time and the next only when the system
    has accepted it, so requests are queued at the replica in the list's
    order in every run: which prompts share a static-width prefill call
    then follows from the list, not from a race between two callers. The
    first ``opens_after_completed`` requests (one per slot) go in one by
    one, each when the one before has its first token, so that the
    engine's first admissions are the same in every run too. The window
    opens when they have completed (every slot has turned over once)."""
    reqs, recs = plan["requests"], []
    state = {"next": 0, "completed": 0, "t_open": None, "stop": False}
    opened, turn = asyncio.Event(), asyncio.Lock()

    async def client():
        while not state["stop"]:
            async with turn:
                i = state["next"]
                state["next"] += 1
                body = reqs[i % len(reqs)]
                rec = _new_rec(i, body)
                recs.append(rec)
                accepted, started = asyncio.Event(), asyncio.Event()
                task = asyncio.ensure_future(
                    _one(addr, body, rec, accepted, started))
                staggered = i < plan["opens_after_completed"]
                try:
                    await (started if staggered else accepted).wait()
                except asyncio.CancelledError:
                    task.cancel()
                    raise
            await task
            state["completed"] += 1
            if state["t_open"] is None and \
                    state["completed"] >= plan["opens_after_completed"]:
                state["t_open"] = time.monotonic()
                opened.set()

    tasks = [asyncio.ensure_future(client())
             for _ in range(plan["clients"])]
    await asyncio.wait_for(opened.wait(), 300.0)
    t_open = state["t_open"]
    print(f"OPEN {t_open!r}", flush=True)
    await asyncio.sleep(max(0.0, t_open + seconds - time.monotonic()))
    state["stop"] = True
    t_close = t_open + seconds
    for t in tasks:  # callers in flight at the close are abandoned
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    return {"t_open": t_open, "t_close": t_close, "records": recs}


async def run_open(addr, plan: dict) -> dict:
    """Every request is sent when it is due, whatever came back so far,
    and timed from when it was due."""
    t0 = time.monotonic() + 0.2
    t_open = t0 + plan["ramp_s"]
    recs, tasks, announced = [], [], False
    for i, r in enumerate(plan["requests"]):
        due = t0 + r["due"]
        if not announced and due >= t_open:
            await asyncio.sleep(max(0.0, t_open - time.monotonic()))
            print(f"OPEN {t_open!r}", flush=True)
            announced = True
        await asyncio.sleep(max(0.0, due - time.monotonic()))
        body = {"prompt_ids": r["prompt_ids"],
                "max_tokens": r["max_tokens"]}
        rec = _new_rec(i, body, due=due, measured=r["measured"])
        recs.append(rec)
        tasks.append(asyncio.ensure_future(_one(addr, body, rec)))
    await asyncio.gather(*tasks)
    return {"t_open": t_open, "t_close": t_open + plan["seconds"],
            "records": recs}


async def main_async(plan: dict) -> dict:
    addr = tuple(plan["addr"])
    out = {}
    if plan.get("idle_probe"):
        out["idle_ttft_s"] = await idle_probe(
            addr, plan["idle_probe"]["body"], plan["idle_probe"]["n"])
    if plan["loop"] == "closed":
        out.update(await run_closed(addr, plan, plan["seconds"]))
    else:
        out.update(await run_open(addr, plan))
    return out


def main(argv) -> int:
    with open(argv[1]) as f:
        plan = json.load(f)
    result = asyncio.run(main_async(plan))
    for rec in result["records"]:  # ids are checked here, not shipped
        toks = rec.pop("tokens")
        rec["n_tokens"] = len(toks)
        rec["ids_in_vocab"] = all(0 <= t < plan["vocab"] for t in toks)
    with open(argv[2], "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
