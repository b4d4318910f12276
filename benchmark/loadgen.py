"""Drives a Plan against the server over HTTP (SSE), from one event loop.

Every request is timed from the instant it was DUE: its scheduled time in an
open loop, its send time in a closed one. A record keeps the arrival time of
every token frame, so the token rate is counted by arrival inside the window
and not by which requests happened to finish in it.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field
from typing import Awaitable, Callable, List, Optional

import aiohttp

from workgen import Plan, Request


@dataclass
class Record:
    rid: str
    tag: str
    measured: bool
    due: float                      # monotonic seconds
    query_tokens: int
    sent: float = 0.0
    first: Optional[float] = None   # first token frame
    done: Optional[float] = None    # "event: done" received
    frames: List[float] = field(default_factory=list)
    status: int = 0
    outcome: str = ""               # done | error:<text> | degraded | http:<n> | exc:<text>
    command: str = ""
    tokens: Optional[int] = None    # completion tokens, from the engine's record


async def _one(session: aiohttp.ClientSession, base: str, endpoint: str,
               req: Request, rec: Record) -> None:
    headers = {"X-Forwarded-For": req.client, "X-Request-ID": rec.rid}
    if req.session:
        headers["X-Session-ID"] = req.session
    rec.sent = time.monotonic()
    try:
        async with session.post(base + endpoint, json={"query": req.query},
                                headers=headers) as resp:
            rec.status = resp.status
            if resp.status != 200:
                rec.outcome = f"http:{resp.status}"
                await resp.read()
                return
            event, data, degraded = None, [], False
            async for raw in resp.content:
                line = raw.decode("utf-8", "replace").rstrip("\n")
                if line.startswith("event:"):
                    event = line[6:].strip()
                elif line.startswith("data:"):
                    data.append(line[5:].lstrip(" "))
                elif line == "":
                    now = time.monotonic()
                    if event is None and data:
                        rec.frames.append(now)
                        if rec.first is None:
                            rec.first = now
                    elif event == "done":
                        rec.done = now
                        rec.command = "\n".join(data)
                        rec.outcome = "degraded" if degraded else "done"
                    elif event == "degraded":
                        degraded = True
                    elif event == "error":
                        rec.outcome = "error:" + " ".join(data)[:200]
                    event, data = None, []
            if not rec.outcome:
                rec.outcome = "error:stream ended without done"
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
        rec.outcome = f"exc:{type(e).__name__}:{e}"[:200]


async def drive(plan: Plan, base: str, endpoint: str, seconds: float,
                rid_prefix: str,
                after_measured: Optional[Callable[[], Awaitable[None]]] = None):
    """Runs ramp, window and tail. Returns (records, t0) with t0 the
    monotonic time of the window's start. ``after_measured`` (the traced
    run's profile capture) runs once every measured request has ended, while
    unmeasured load is still offered."""
    records: List[Record] = []
    tasks: List[asyncio.Task] = []
    t0 = time.monotonic() + plan.ramp_s
    stop = asyncio.Event()
    timeout = aiohttp.ClientTimeout(total=None, sock_connect=10, sock_read=240)
    conn = aiohttp.TCPConnector(limit=0)
    serial = 0

    def new_record(req: Request, since_t0: float) -> Record:
        # measured or not is decided on the plan's own clock: t0 + 50.0 - t0
        # can round to either side of 50.0
        nonlocal serial
        rec = Record(f"{rid_prefix}-{serial}", req.tag,
                     0.0 <= since_t0 < seconds, t0 + since_t0, req.query_tokens)
        serial += 1
        records.append(rec)
        return rec

    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        if plan.loop == "open":
            async def offer() -> None:
                for req in plan.schedule:
                    due = t0 + req.due
                    delay = due - time.monotonic()
                    if delay > 0:
                        try:
                            await asyncio.wait_for(stop.wait(), delay)
                        except asyncio.TimeoutError:
                            pass
                    if stop.is_set():
                        return
                    rec = new_record(req, req.due)
                    tasks.append(asyncio.ensure_future(
                        _one(session, base, endpoint, req, rec)))
            producers = [asyncio.ensure_future(offer())]
        else:
            async def client(i: int, start: float) -> None:
                await asyncio.sleep(max(0.0, t0 + start - time.monotonic()))
                while not stop.is_set():
                    req = plan.next_request(i)
                    rec = new_record(req, time.monotonic() - t0)
                    await _one(session, base, endpoint, req, rec)
            producers = [asyncio.ensure_future(client(i, s))
                         for i, s in enumerate(plan.starts)]

        # The window, then the tail: load goes on until the last measured
        # request has ended (or tail_max_s has passed).
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        deadline = t0 + seconds + plan.tail_max_s
        while time.monotonic() < deadline:
            if all(r.outcome for r in records if r.measured):
                break
            await asyncio.sleep(0.05)
        if after_measured is not None:
            await after_measured()
        stop.set()
        # Unmeasured requests still in flight are abandoned, not awaited.
        for p in producers:
            if plan.loop == "closed":
                p.cancel()
        for t in tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*producers, *tasks, return_exceptions=True)
    return records, t0
