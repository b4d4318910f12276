#!/usr/bin/env python3
"""Keep what a server's /health says while something else drives it.

A benchmark run (benchmark/run.py) prints metrics, not the sections they
were read from, and starts its server on a free port. This finds every
server of this program that listens on the loopback (the LISTEN rows of
/proc/net/tcp whose /health answers with ``engine_ready``), polls the
sections asked for, and appends one JSON line a poll to a file a server:
``<out>/<n>_<port>.jsonl``, ``n`` counting servers in the order they were
first seen. Every ``--ring-every``-th poll also keeps ``/debug/chunks``
(the scheduler thread's ring: sched/* regions with t0/t1).

    python tools/poll_health.py --out chiprun_out/health --every 3 &
    python3 benchmark/run.py --workload ... ; kill %1

Stdlib only, and it never imports jax: a chip belongs to one process.
"""

import argparse
import json
import os
import time
import urllib.request


def listening_ports() -> list:
    ports = set()
    for table in ("/proc/net/tcp", "/proc/net/tcp6"):
        try:
            rows = open(table).read().splitlines()[1:]
        except OSError:
            continue
        for row in rows:
            cols = row.split()
            if cols[3] == "0A":                      # LISTEN
                ports.add(int(cols[1].rsplit(":", 1)[1], 16))
    return sorted(ports)


def get(port: int, path: str, timeout: float = 2.0):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as resp:
            return json.loads(resp.read())
    except Exception:       # some other listener: not HTTP, not JSON, slow
        return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True, help="directory for the files")
    ap.add_argument("--every", type=float, default=3.0, help="seconds a poll")
    ap.add_argument("--sections", nargs="*",
                    default=["spans", "ssm", "kv_pool"])
    ap.add_argument("--ring-every", type=int, default=10,
                    help="keep /debug/chunks every N-th poll (0: never)")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    servers, not_ours = {}, {}      # port -> [file, polls]; port -> misses
    seen = 0
    while True:
        for port in listening_ports():
            if not_ours.get(port, 0) >= 3:          # some other listener
                continue
            health = get(port, "/health")
            if not isinstance(health, dict) or "engine_ready" not in health:
                if port not in servers:
                    not_ours[port] = not_ours.get(port, 0) + 1
                continue
            if port not in servers:
                servers[port] = [os.path.join(
                    args.out, f"{seen}_{port}.jsonl"), 0]
                seen += 1
            path, polls = servers[port]
            line = {"t": time.time(), "model": health.get("model")}
            line.update({k: health.get(k) for k in args.sections})
            if args.ring_every and polls % args.ring_every == 0:
                ring = get(port, "/debug/chunks?limit=512", timeout=5.0)
                line["ring"] = (ring or {}).get("events")
            with open(path, "a") as f:
                f.write(json.dumps(line) + "\n")
            servers[port][1] = polls + 1
        # a port may be the next run's server: forget what is gone
        live = set(listening_ports())
        not_ours = {p: n for p, n in not_ours.items() if p in live}
        servers = {p: v for p, v in servers.items() if p in live}
        time.sleep(args.every)


if __name__ == "__main__":
    raise SystemExit(main())
