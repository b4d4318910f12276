#!/usr/bin/env python3
"""What a chunk costs on the device by the width of the window it carries.

Runs beside a benchmark run (as tools/poll_health.py does, whose port
search it shares) and keeps two things off the server's debug surface:
the scheduler's ring (``/debug/chunks``: every ``sched/fetch`` with its
chunk number and when its buffer arrived, every ``sched/dispatch`` with
its admissions) and, from each finished request's ``first_chunk`` span,
which chunk carried its window and how wide that was (``adm_w``).
``--report`` reduces a kept file: a chunk's time is from the later of the
previous chunk's arrival and its own dispatch to its own arrival (the
device runs one chunk at a time), grouped by the width it carried, 0 for
a plain chunk. The last polled ``/health.ragged`` rides along.

    python tools/window_chunk_times.py --out chiprun_out/w/chunks.jsonl &
    python3 benchmark/run.py --workload ... ; kill %1
    python tools/window_chunk_times.py --report chiprun_out/w/chunks.jsonl

Stdlib only, and it never imports jax: a chip belongs to one process.
"""

import argparse
import json
import os
import statistics
import time

from poll_health import get, listening_ports


def keep(out: str, every: float) -> None:
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    seen_events, seen_requests = set(), set()
    while True:
        for port in listening_ports():
            health = get(port, "/health")
            if not isinstance(health, dict) or "engine_ready" not in health:
                continue
            lines = []
            ring = get(port, "/debug/chunks?limit=512", timeout=5.0) or {}
            for e in ring.get("events") or ():
                key = (port, e.get("event"), e.get("chunk"), e.get("t0"))
                if e.get("event") in ("fetch", "dispatch") \
                        and key not in seen_events:
                    seen_events.add(key)
                    lines.append({"port": port, "ring": e})
            index = get(port, "/debug/requests?limit=64", timeout=5.0) or {}
            for r in index.get("requests") or ():
                rid = r.get("request_id")
                if rid is None or (port, rid) in seen_requests:
                    continue
                detail = get(port, f"/debug/requests/{rid}", timeout=5.0)
                first = [s for s in (detail or {}).get("spans", ())
                         if s.get("phase") == "first_chunk"]
                if not first:
                    continue                    # still in flight
                seen_requests.add((port, rid))
                lines.append({"port": port, "first_chunk": dict(
                    first[0].get("meta") or {},
                    ms=first[0]["end_ms"] - first[0]["start_ms"])})
            lines.append({"port": port, "ragged": health.get("ragged")})
            with open(out, "a") as f:
                for line in lines:
                    f.write(json.dumps(line) + "\n")
        time.sleep(every)


def report(path: str) -> dict:
    by_port = {}
    for raw in open(path):
        line = json.loads(raw)
        by_port.setdefault(line["port"], []).append(line)
    out = {}
    for port, lines in by_port.items():
        fetched, dispatched, width, ragged = {}, {}, {}, None
        for line in lines:
            e = line.get("ring")
            if e is not None and e.get("chunk") is not None:
                (fetched if e["event"] == "fetch" else dispatched)[
                    e["chunk"]] = e
            fc = line.get("first_chunk")
            if fc is not None and fc.get("chunk") is not None:
                width[fc["chunk"]] = max(width.get(fc["chunk"], 0),
                                         fc.get("adm_w") or 0)
            ragged = line.get("ragged") or ragged
        ms = {}
        for n, e in fetched.items():
            if n - 1 in fetched and n in dispatched:
                start = max(fetched[n - 1]["t1"], dispatched[n]["t0"])
                ms.setdefault(width.get(n, 0), []).append(
                    (e["t1"] - start) * 1000.0)
        if not ms:
            continue
        out[str(port)] = {
            "chunk_ms_by_window_width": {
                str(w): {"chunks": len(v),
                         "median": round(statistics.median(v), 1),
                         "min": round(min(v), 1), "max": round(max(v), 1)}
                for w, v in sorted(ms.items())},
            "ragged": ragged}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", help="file to keep the polls in")
    ap.add_argument("--every", type=float, default=2.0, help="seconds a poll")
    ap.add_argument("--report", help="reduce a kept file and print it")
    args = ap.parse_args()
    if args.report:
        print(json.dumps(report(args.report), indent=1))
        return 0
    keep(args.out, args.every)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
