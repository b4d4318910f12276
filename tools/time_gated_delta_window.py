#!/usr/bin/env python3
"""Time a window of the gated delta rule ALONE on the chip (ISSUE 47), by the
method of tools/time_gated_delta_step.py: ``--layers`` calls of
``ops/gated_delta.py::gated_delta_scan`` scanned in one jit, each call's outputs
and state feeding the next call's values and state, so one execution is a
window program's worth of linear layers and nothing else (a call's keys,
decays and strengths are the last call's times a factor near 1, so the
compiler cannot make a call's ``T`` once for all of them). One JSON line a case
and shape (``--shapes``: rows x columns; 1x256 and 1x64 are an admission's
eager pieces on ``olmohybrid7b-agent-sessions``, 8x64 its chunk's window),
ms a CALL (the median of ``--repeats`` executions over ``--layers``):

- ``whole``: the scan as the model runs it;
- ``no_solve``: the same with the triangular system's solution replaced by its
  right-hand side (``T = I``: what everything but the solve costs);
- ``solve``: the solve alone, ``(I + A)^-1 rhs`` on a chunk's ``[64, 64]``
  strictly lower ``A`` and its 288 right-hand columns, every (row, chunk,
  head) at once.

An execution costs its launch and the wait for its result besides (~1 ms: a
24th of it is in every figure). On a tree from before ISSUE 47 the solve is
``jax.scipy.linalg.solve_triangular`` and the tool times that.

    chiprun -- python tools/time_gated_delta_window.py
    python tools/time_gated_delta_window.py --rehearse     # here: tiny, no timing claim
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*",
                    default=["1x256", "1x64", "8x64", "8x512"])
    ap.add_argument("--cases", nargs="*", default=["whole", "no_solve", "solve"])
    ap.add_argument("--block-rows", type=int, default=0,
                    help="rows a diagonal block of the inverse's substitution "
                         "(0: ops/gated_delta.py::_SOLVE_BLOCK, what the model runs)")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    H, dk, dv, L = 30, 96, 192, args.layers
    shapes = [tuple(int(n) for n in s.split("x")) for s in args.shapes]
    if args.rehearse:
        H, dk, dv, L, shapes = 4, 24, 40, 2, [(1, 37), (2, 64), (2, 150)]
    if args.block_rows:
        GD._SOLVE_BLOCK = args.block_rows
    if hasattr(GD, "_unit_lower_solve"):
        solve, name = GD._unit_lower_solve, "_unit_lower_solve"
    else:                       # a tree from before ISSUE 47
        name = "solve_triangular"

        def solve(A, rhs):
            return GD.solve_triangular(A + jnp.eye(A.shape[-1], dtype=A.dtype),
                                       rhs, lower=True, unit_diagonal=True)

    def median_ms(run, *a):
        out = run(*a)
        jax.block_until_ready(out)
        times = []
        for _ in range(1 if args.rehearse else args.repeats):
            t0 = time.perf_counter()
            out = run(*a)
            jax.block_until_ready(out)
            times.append(time.perf_counter() - t0)
        return statistics.median(times) * 1e3 / L

    # every call's keys, decays and strengths differ by a factor near 1:
    # nothing of a call is the same in the next, so nothing leaves the loop
    scale = 1.0 - 1e-4 * jnp.arange(L, dtype=jnp.float32)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    for (B, S), case in ((s, c) for s in shapes for c in args.cases):
        r = np.random.default_rng(0)
        C = min(GD.CHUNK, S)
        n = -(-S // C)
        if case == "solve":
            kc = GD.l2_normalize(r.normal(size=(B, n, H, C, dk)))
            A = jnp.tril(jnp.einsum("bnhik,bnhjk->bnhij", kc, kc)
                         * f32(r.uniform(0.0, 2.0, (B, n, H, C, 1))), -1)

            def calls(rhs):
                def body(rhs, c):
                    return solve(A * c, rhs) * 0.5 + rhs * 0.5, None
                return jax.lax.scan(body, rhs, scale)[0]

            ms = median_ms(jax.jit(calls),
                           f32(r.normal(size=(B, n, H, C, dv + dk))))
        else:
            q = GD.l2_normalize(r.normal(size=(B, S, H, dk)), dk ** -0.5)
            k = GD.l2_normalize(r.normal(size=(B, S, H, dk)))
            g = f32(-r.uniform(1e-3, 0.7, (B, S, H)))
            beta = f32(r.uniform(0.0, 2.0, (B, S, H)))

            def calls(v, S0):
                def body(carry, c):
                    v, S0 = carry
                    o, S1 = GD.gated_delta_scan(q * c, k * c, v, g * c, beta * c,
                                                S0)
                    return (o * 0.5 + v * 0.5, S1), None
                return jax.lax.scan(body, (v, S0), scale)[0]

            kept = getattr(GD, name)
            if case == "no_solve":
                setattr(GD, name, lambda A, rhs, **_: rhs)
            try:
                ms = median_ms(jax.jit(calls), f32(r.normal(size=(B, S, H, dv))),
                               f32(r.normal(size=(B, dk, H * dv)) * 0.1))
            finally:
                setattr(GD, name, kept)
        line = {"case": case, "rows": B, "columns": S, "chunks": n, "layers": L,
                "heads": H, "key_dim": dk, "value_dim": dv, "solve": name,
                "block_rows": getattr(GD, "_SOLVE_BLOCK", None),
                "platform": jax.devices()[0].platform}
        if not args.rehearse:       # a CPU time is no device time
            line["ms_a_call"] = round(ms, 4)
        print("gated_delta_window: " + json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
