#!/usr/bin/env python3
"""The knee sweep of an open-loop cell, made once when the cell is defined:
``python3 benchmark/sweep.py --workload <cell> --rates 1.5,2,2.5 --seconds 30``.

One server, the rates offered one after another (each with the mix's ramp and
tail). A rate is sustained if the wait does not grow through its window: the
median time to first token of the window's second half stays within 1.25 x
that of its first half. The knee is the highest sustained rate; the cell then
runs at 0.75 of it, written into benchmark/cells/<cell>.json by hand with the
table this prints.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import run as R
from arith import percentile


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import loadgen
    import workgen

    cell, cfg_entry, cfg_file, mix = R.resolve_cell(R.load_json(R.ROOT / "BENCHMARK.json"),
                                                    args.workload)
    children, base, env, _ = R.launch(cell, cfg_entry, cfg_file, args.seed, "sweep",
                                      args.rehearse)
    words = workgen.Words(None if args.rehearse else str(R.tokenizer_path()))
    try:
        R.wait_ready(children, base, time.monotonic() + R.READY_TIMEOUT_S)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            plan = workgen.build(mix, {"rate_rps": rate}, env, args.seed + i, args.seconds,
                                 words, R.REHEARSAL_SCALE if args.rehearse else 1.0)
            records, t0 = asyncio.run(loadgen.drive(
                plan, base, mix["endpoint"], args.seconds, f"w{i}"))
            m = sorted((r for r in records if r.measured), key=lambda r: r.due)
            ok = [r for r in m if r.outcome == "done"]
            half = len(m) // 2
            ttft = lambda rs: percentile([(r.first - r.due) * 1e3 for r in rs if r.first], 50)
            lat = [(r.done - r.due) * 1e3 for r in ok]
            a, b = ttft(m[:half]), ttft(m[half:])
            print("sweep: " + json.dumps({
                "rate_rps": rate, "offered": len(m), "done": len(ok),
                "ttft_p50_first_half_ms": a, "ttft_p50_second_half_ms": b,
                "sustained": bool(a and b and b <= 1.25 * a and len(ok) == len(m)),
                "ttft_p50_ms": ttft(m), "latency_p50_ms": percentile(lat, 50),
                "latency_p90_ms": percentile(lat, 90)}), flush=True)
            time.sleep(3.0)     # let the abandoned tail drain
    finally:
        for child in children:
            R.stop_child(child)
    return 0


if __name__ == "__main__":
    sys.exit(main())
