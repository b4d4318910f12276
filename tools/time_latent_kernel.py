#!/usr/bin/env python3
"""Time the latent attention ALONE on the chip (ISSUE 38), by the method of
tools/time_ragged_kernel.py: the call is scanned over a stacked leaf's layers
inside one jit, each call's output feeding the next call's queries, so one
execution is ``--calls`` calls back to back and nothing else. One JSON line a
case: us a call and the share of the call's floor (the larger of its live
rows' bytes at the chip's HBM bandwidth and its (query, row) pairs' operations
at the chip's bf16 peak; benchmark/opsbytes_mla.py's counts).

Cases, at mistral-small-4-119b-2603-l9's sizes (32 heads, latent 256, rope 64,
page 64, the cell's 513-page table):

- ``decode``: 16 slots, one query each over ``--context`` cached rows: the
  chunk program's call.
- ``piece``: one slot, a 512-row window whose first row stands at
  ``--context``: an eager prefill piece over a filled pool.
- ``own-absorbed`` / ``own-expanded``: what decides the form of a window's OWN
  tokens: a 512-row window at position 0 (its own rows are all it reads)
  through the pool's absorbed kernel, against the expanded form in plain XLA
  (the rows' keys and values made by ``W_ukv``, then causal attention of 32
  heads of 128 + 128), which is what a piece would run for its own 512 rows
  BESIDE the kernel over the rows before them.

    chiprun -- python tools/time_latent_kernel.py
    python tools/time_latent_kernel.py --rehearse     # here: tiny, interpreted, no timing claim
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_HBM_BYTES_S, _BF16_FLOPS = 819e9, 197e12       # benchmark/peaks.json, v5e


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--case", nargs="*", default=["decode", "piece", "own-absorbed",
                                                  "own-expanded"])
    ap.add_argument("--context", type=int, default=20480)
    ap.add_argument("--calls", type=int, default=9)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops.latent_attention import expanded_attention
    from ai_agent_kubectl_tpu.ops.ragged_attention import (latent_attention_pool,
                                                           latent_query)

    H, C, R, N_, V, page, pages = 32, 256, 64, 64, 128, 64, 513
    context, L = args.context, args.calls
    if args.rehearse:
        H, C, R, N_, V, page, pages, context, L = 4, 32, 8, 8, 16, 16, 12, 100, 2
    rng = np.random.default_rng(0)
    dt = jnp.float32 if args.rehearse else jnp.bfloat16
    width = 512 if not args.rehearse else 32

    for case in args.case:
        B, W, pos = {"decode": (16, 1, context), "piece": (1, width, context)}.get(
            case, (1, width, 0))
        live = pos + W
        n_pages = -(-live // page)
        n_blocks = B * n_pages + 1
        leaf = jnp.asarray(rng.standard_normal((L, n_blocks, page // 2, 2 * (C + R))) * 0.3, dt)
        tables = np.full((B, pages), n_blocks, np.int32)
        tables[:, :n_pages] = np.arange(B * n_pages).reshape(B, n_pages)
        q0 = jnp.asarray(rng.standard_normal((B, W, H, C + R)) * 0.05, dt)
        q_lens = jnp.full((B,), W, jnp.int32)
        positions = jnp.full((B,), pos, jnp.int32)
        pairs = B * (W * pos + W * (W + 1) // 2)
        rows_read = B * live                    # each live row once a call, at least
        if case == "own-expanded":
            w_ukv = jnp.asarray(rng.standard_normal((C, H, N_ + V)) * C ** -0.5, dt)
            mask = jnp.tril(jnp.ones((W, W), bool))[None]

            # the window's own rows as token rows (the write has them so)
            leaf = (jnp.asarray(rng.standard_normal((L, 1, W, C)) * 0.3, dt),
                    jnp.asarray(rng.standard_normal((L, 1, W, R)) * 0.3, dt))

            def call(q, rows):
                c, kr = rows
                o = expanded_attention(q[..., :N_], q[..., C:C + R], c, kr,
                                       w_ukv[..., :N_], w_ukv[..., N_:], mask)
                return jnp.pad(o, ((0, 0),) * 3 + ((0, C + R - V),)).astype(q.dtype)
            flops = pairs * H * 2 * (N_ + R + V) + 2 * W * C * H * (N_ + V)
        else:
            def call(q, layer_leaf):
                o = latent_attention_pool(
                    latent_query(q[..., :C], q[..., C:]), layer_leaf, q_lens, positions,
                    jnp.asarray(tables), v_lanes=C, page_size=page)
                return jnp.pad(o, ((0, 0),) * 3 + ((0, R),)).astype(q.dtype)
            flops = pairs * H * 2 * (2 * C + R)

        @jax.jit
        def run(q, leaf):
            def body(q, layer_leaf):
                return call(q, layer_leaf) * 0.5 + q * 0.5, None
            return jax.lax.scan(body, q, leaf)[0]

        run(q0, leaf).block_until_ready()
        times = []
        for _ in range(1 if args.rehearse else args.repeats):
            t0 = time.perf_counter()
            run(q0, leaf).block_until_ready()
            times.append((time.perf_counter() - t0) / L)
        us = statistics.median(times) * 1e6
        floor_us = max(rows_read * (C + R) * 2 / _HBM_BYTES_S, flops / _BF16_FLOPS) * 1e6
        line = {"case": case, "slots": B, "window": W, "first_position": pos, "calls": L,
                "pairs": pairs, "flops": flops, "row_bytes": rows_read * (C + R) * 2,
                "platform": jax.devices()[0].platform}
        if not args.rehearse:       # a CPU time is no device time
            line.update(us_per_call=round(us, 1), floor_us=round(floor_us, 1),
                        share_of_floor=round(100.0 * floor_us / us, 1))
        print("latent: " + json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
