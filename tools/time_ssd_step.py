#!/usr/bin/env python3
"""Time Mamba-2's decode step ALONE on the chip (ISSUE 49), by the method of
tools/time_gated_delta_step.py: a pass is ``--layers`` calls, unrolled, a plane of
the state leaf each (the leaf carried by a scan over ``--passes`` passes, as the
chunk program's decode loop carries it), each call's output feeding the next
call's input, so one execution is ``--passes`` decode passes' worth of steps and
nothing else. One JSON line a case: ms a pass (the
median of ``--repeats`` executions over ``--passes``) and the share of its
floor, the MOVING rows' state read once and written once at the chip's HBM
bandwidth (16 rows x 6 layers x 2.1 MB x 2 = 403 MB = 0.49 ms at
nemotron-3-nano-30b-a3b's sizes; 11 rows 0.34 ms, 4 rows 0.12 ms). An execution
costs its launch and the wait for its result besides: give ``--passes 1 8`` and
read what a pass adds.

Cases: the kernel (``ops/ssd_scan.py::ssd_step_kernel``) at each of
``--block-heads`` heads a block, and ``jnp``, the plain ``ssd_step`` from and to
a plane sliced out of the leaf, as the model ran it before (it moves every
row, whatever ``--live`` says).

    chiprun -- python tools/time_ssd_step.py
    python tools/time_ssd_step.py --rehearse     # here: tiny, interpreted, no timing claim
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_HBM_BYTES_S = 819e9                            # benchmark/peaks.json, v5e


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--block-heads", type=int, nargs="*", default=[8, 16, 32, 64])
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--live", type=int, nargs="*", default=[16, 11, 4],
                    help="rows that move (the rest have dt = 0), one line each")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--passes", type=int, nargs="*", default=[8],
                    help="passes over the leaf's planes an execution, one line each")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    H, P, G, N, B, L = 64, 64, 8, 128, args.rows, args.layers
    widths, lives = args.block_heads, args.live
    if args.rehearse:
        H, P, G, N, B, L, widths, lives = 4, 8, 2, 16, 3, 2, [2, 4], [3, 1]
    r = np.random.default_rng(0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x0 = f32(r.normal(size=(B, 1, H, P)))
    A, D = f32(-r.uniform(0.5, 4.0, H)), f32(r.normal(size=H))
    Bm, Cm = f32(r.normal(size=(B, 1, G, N))), f32(r.normal(size=(B, 1, G, N)) * 0.1)

    def kernel(hb):
        return lambda x, dt, leaf, j: S.ssd_step_kernel(x, dt, A, Bm, Cm, D, leaf, j,
                                                        None, hb)

    def plain(x, dt, leaf, j):
        y, h = S.ssd_step(x, dt, A, Bm, Cm, D,
                          jax.lax.dynamic_index_in_dim(leaf, j, 0, False))
        return y, jax.lax.dynamic_update_index_in_dim(leaf, h, j, 0)

    cases = [(f"kernel-{hb}", kernel(hb)) for hb in widths] + [("jnp", plain)]
    for (name, call), live, passes in ((c, n, p) for c in cases for n in lives
                                       for p in args.passes):
        dt = f32(np.where((np.arange(B) < live)[:, None, None],
                          r.uniform(0.01, 0.5, (B, 1, H)), 0.0))

        def run_passes(x, leaf, call=call, passes=passes, dt=dt):
            def one_pass(carry, _):
                x, leaf = carry
                for j in range(L):      # unrolled, a plane a layer: the model's loop
                    y, leaf = call(x, dt, leaf, j)
                    x = y * 0.5 + x * 0.5
                return (x, leaf), None
            return jax.lax.scan(one_pass, (x, leaf), None, length=passes)[0]

        run = jax.jit(run_passes, donate_argnums=(1,))
        leaf = f32(r.normal(size=(L, B, H, P, N)) * 0.1)
        x, leaf = run(x0, leaf)
        x.block_until_ready()
        times = []
        for _ in range(1 if args.rehearse else args.repeats):
            t0 = time.perf_counter()
            x, leaf = run(x0, leaf)
            x.block_until_ready()
            times.append(time.perf_counter() - t0)
        ms = statistics.median(times) * 1e3 / passes
        moved = 2 * L * live * H * P * N * 4
        floor_ms = moved / _HBM_BYTES_S * 1e3
        line = {"case": name, "rows": B, "live_rows": live, "layers": L, "passes": passes,
                "heads": H, "head_dim": P, "state": N,
                "state_bytes_read_and_written": moved,
                "platform": jax.devices()[0].platform}
        if not args.rehearse:       # a CPU time is no device time
            line.update(ms_a_pass=round(ms, 4), floor_ms=round(floor_ms, 4),
                        share_of_floor=round(100.0 * floor_ms / ms, 1))
        print("ssd_step: " + json.dumps(line), flush=True)
        del leaf


if __name__ == "__main__":
    main()
