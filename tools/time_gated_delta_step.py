#!/usr/bin/env python3
"""Time the gated delta rule's decode step ALONE on the chip (ISSUE 46), by the
method of tools/time_latent_kernel.py: ``--layers`` calls scanned in one jit
over the state leaf's planes (the leaf on the carry, as ``_patterned_layers``
keeps it), each call's output feeding the next call's values, so one execution
is ``--passes`` decode passes' worth of steps and nothing else. One JSON line a
case: ms a pass (the median of ``--repeats`` executions over ``--passes``) and
the share of its floor, the state's bytes read once and written once at the
chip's HBM bandwidth (8 rows x 24 layers x 2.21 MB x 2 = 850 MB = 1.04 ms at
olmo-hybrid-7b's sizes). An execution costs its launch and the wait for its
result besides, which at one pass an execution is as long as the pass: give
``--passes 1 8`` and read what a pass adds.

Cases: the kernel (``ops/gated_delta.py::gated_delta_step_kernel``) at each of
``--block-heads`` heads a lane block, and ``jnp``, the plain ``gated_delta_step``
from and to a plane sliced out of the leaf, as the model ran it before.

    chiprun -- python tools/time_gated_delta_step.py
    python tools/time_gated_delta_step.py --rehearse     # here: tiny, interpreted, no timing claim
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
    ap.add_argument("--block-heads", type=int, nargs="*", default=[2, 6, 10, 30])
    ap.add_argument("--rows", type=int, default=8)
    ap.add_argument("--live", type=int, default=None,
                    help="rows that move (the rest have g = 0 and beta = 0); default all")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--passes", type=int, nargs="*", default=[1],
                    help="passes over the leaf's planes an execution, one line each")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    H, dk, dv, B, L = 30, 96, 192, args.rows, args.layers
    widths = args.block_heads
    if args.rehearse:
        H, dk, dv, B, L, widths = 4, 24, 64, 3, 2, [2, 4]
    live = B if args.live is None else args.live
    r = np.random.default_rng(0)
    moves = (np.arange(B) < live)[:, None, None]
    q = GD.l2_normalize(r.normal(size=(B, 1, H, dk)), dk ** -0.5)
    k = GD.l2_normalize(r.normal(size=(B, 1, H, dk)))
    v0 = jnp.asarray(r.normal(size=(B, 1, H, dv)), jnp.float32)
    g = jnp.asarray(np.where(moves, -r.uniform(1e-3, 0.7, (B, 1, H)), 0.0), jnp.float32)
    beta = jnp.asarray(np.where(moves, r.uniform(0.0, 2.0, (B, 1, H)), 0.0), jnp.float32)
    state_bytes = L * B * dk * H * dv * 4

    def kernel(hb):
        return lambda v, leaf, j: GD.gated_delta_step_kernel(q, k, v, g, beta, leaf, j, None, hb)

    def plain(v, leaf, j):
        o, S = GD.gated_delta_step(q, k, v, g, beta,
                                   jax.lax.dynamic_index_in_dim(leaf, j, 0, False))
        return o, jax.lax.dynamic_update_index_in_dim(leaf, S, j, 0)

    cases = [(f"kernel-{hb}", kernel(hb)) for hb in widths] + [("jnp", plain)]
    for (name, call), P in ((c, P) for c in cases for P in args.passes):
        def passes(v, leaf, call=call, P=P):
            def body(carry, i):
                v, leaf = carry
                o, leaf = call(v, leaf, i % L)
                return (o * 0.5 + v * 0.5, leaf), None
            return jax.lax.scan(body, (v, leaf), jnp.arange(P * L, dtype=jnp.int32))[0]

        run = jax.jit(passes, donate_argnums=(1,))
        leaf = jnp.asarray(r.normal(size=(L, B, dk, H * dv)) * 0.1, jnp.float32)
        v, leaf = run(v0, leaf)
        v.block_until_ready()
        times = []
        for _ in range(1 if args.rehearse else args.repeats):
            t0 = time.perf_counter()
            v, leaf = run(v0, leaf)
            v.block_until_ready()
            times.append(time.perf_counter() - t0)
        ms = statistics.median(times) * 1e3 / P
        floor_ms = 2 * state_bytes / _HBM_BYTES_S * 1e3
        line = {"case": name, "rows": B, "live_rows": live, "layers": L, "passes": P,
                "heads": H, "key_dim": dk, "value_dim": dv,
                "state_bytes_read_and_written": 2 * state_bytes,
                "platform": jax.devices()[0].platform}
        if not args.rehearse:       # a CPU time is no device time
            line.update(ms_a_pass=round(ms, 4), floor_ms=round(floor_ms, 4),
                        share_of_floor=round(100.0 * floor_ms / ms, 1))
        print("gated_delta_step: " + json.dumps(line), flush=True)
        del leaf


if __name__ == "__main__":
    main()
