#!/usr/bin/env python3
"""Time a window of the gated delta rule ALONE on the chip (ISSUE 47; the
kernel's case and the other size set since ISSUE 56), by the method of
tools/time_gated_delta_step.py: ``--layers`` calls scanned in one jit, each
call's outputs and state feeding the next call's values and state, so one
execution is a window program's worth of linear layers and nothing else (a
call's keys, decays and strengths are the last call's times a factor near 1, so
the compiler cannot make a call's ``T`` once for all of them). One JSON line a
case and shape (``--shapes``: rows x columns; 1x256 and 1x64 are an admission's
eager pieces on ``olmohybrid7b-agent-sessions``, 8x64 its chunk's window; 1x512
is an eager piece of ``qwen3next-l12-longlogs-replay``, 16x64 and 16x512 its
prologues), ms a CALL (the median of ``--repeats`` executions over
``--layers``):

- ``whole``: ``ops/gated_delta.py::gated_delta_scan`` from and to a plane
  sliced out of the state leaf, as the model ran a window before ISSUE 56 (it
  scans every row and every column, whatever ``--moving`` says);
- ``kernel``: ``ops/gated_delta_window.py::gated_delta_window`` on the whole
  leaf, the plane a traced ordinal: ``--moving`` rows bring ``--fill`` of the
  width each, ``--riders`` more rows ONE token each (a live decode row in a
  prologue), the others none (a tree from before ISSUE 56 has no such module
  and skips the case);
- ``no_solve``: ``whole`` with the triangular system's solution replaced by
  its right-hand side (``T = I``: what everything but the solve costs);
- ``solve``: the solve alone, ``(I + A)^-1 rhs`` on a chunk's ``[64, 64]``
  strictly lower ``A`` and its right-hand columns, every (row, chunk, head) at
  once;
- ``harness``: the loop with no scan in it (what a line's ms hold besides the
  call: the mix of the outputs into the next call's values over every row, the
  launch and the wait over the calls).

``--heads --key-heads --dk --dv``: olmo-hybrid-7b's 30 / 30 heads of 96 x 192
by default; ``--heads 32 --key-heads 16 --dk 128 --dv 128`` are
qwen3-next-80b-a3b-instruct-l12's. On a tree from before ISSUE 47 the solve
is ``jax.scipy.linalg.solve_triangular`` and the tool times that.

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


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", nargs="*",
                    default=["1x256", "1x64", "8x64", "8x512"])
    ap.add_argument("--cases", nargs="*",
                    default=["whole", "kernel", "no_solve", "solve", "harness"])
    ap.add_argument("--heads", type=int, default=30)
    ap.add_argument("--key-heads", type=int, default=30)
    ap.add_argument("--dk", type=int, default=96)
    ap.add_argument("--dv", type=int, default=192)
    ap.add_argument("--moving", type=int, nargs="*", default=[1],
                    help="rows that brought tokens (capped at a shape's rows)")
    ap.add_argument("--riders", type=int, nargs="*", default=[0],
                    help="rows beside them that brought ONE token")
    ap.add_argument("--fill", type=float, default=0.85,
                    help="share of the width a moving row's q_len is")
    ap.add_argument("--block-rows", type=int, default=0,
                    help="rows a diagonal block of the inverse's substitution "
                         "(0: ops/gated_delta.py::_SOLVE_BLOCK, what the model runs)")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--package-root", default=str(Path(__file__).resolve().parent.parent),
                    help="the checkout whose ops are timed")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(args.package_root).resolve()))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import gated_delta as GD
    try:
        from ai_agent_kubectl_tpu.ops import gated_delta_window as GW
    except ImportError:         # a tree from before ISSUE 56
        GW = None

    H, Hk, dk, dv, L = args.heads, args.key_heads, args.dk, args.dv, args.layers
    shapes = [tuple(int(n) for n in s.split("x")) for s in args.shapes]
    if args.rehearse:
        H, Hk, dk, dv, L, shapes = 4, 2, 24, 40, 2, [(1, 37), (3, 64), (2, 150)]
    if args.block_rows:
        GD._SOLVE_BLOCK = args.block_rows
    if hasattr(GD, "_unit_lower_solve"):
        solve, name = GD._unit_lower_solve, "_unit_lower_solve"
    else:                       # a tree from before ISSUE 47
        name = "solve_triangular"

        def solve(A, rhs):
            return GD.solve_triangular(A + jnp.eye(A.shape[-1], dtype=A.dtype),
                                       rhs, lower=True, unit_diagonal=True)

    def median_ms(calls, *a):
        """``calls`` gives back what it takes: an execution's results are the
        next one's arguments, donated (a leaf of 24 planes is copied by no
        execution)."""
        run = jax.jit(calls, donate_argnums=tuple(range(len(a))))
        a = jax.block_until_ready(run(*a))
        times = []
        for _ in range(1 if args.rehearse else args.repeats):
            t0 = time.perf_counter()
            a = jax.block_until_ready(run(*a))
            times.append(time.perf_counter() - t0)
        return statistics.median(times) * 1e3 / L

    # every call's keys, decays and strengths differ by a factor near 1:
    # nothing of a call is the same in the next, so nothing leaves the loop
    scale = 1.0 - 1e-4 * jnp.arange(L, dtype=jnp.float32)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    rows = sorted({(m, n) for m in args.moving for n in args.riders})
    for (B, S), case, (moving, riders) in (
            (s, c, m) for s in shapes for c in args.cases
            for m in (rows if c == "kernel" else rows[:1])):
        if case == "kernel" and GW is None:
            continue
        r = np.random.default_rng(0)
        C = min(GD.CHUNK, S)
        n = -(-S // C)
        moving = min(moving, B)
        riders = min(riders, B - moving)
        if case == "solve":
            kc = GD.l2_normalize(r.normal(size=(B, n, H, C, dk)))
            A = jnp.tril(jnp.einsum("bnhik,bnhjk->bnhij", kc, kc)
                         * f32(r.uniform(0.0, 2.0, (B, n, H, C, 1))), -1)

            def calls(rhs):
                def body(rhs, c):
                    return solve(A * c, rhs) * 0.5 + rhs * 0.5, None
                return jax.lax.scan(body, rhs, scale)[:1]

            ms = median_ms(calls, f32(r.normal(size=(B, n, H, C, dv + dk))))
        else:
            q_lens = np.where(np.arange(B) < moving, max(1, int(S * args.fill)),
                              np.where(np.arange(B) < moving + riders, 1, 0))
            if case != "kernel":
                q_lens[:] = S           # the scan goes over every column
            live = (np.arange(S)[None, :] < q_lens[:, None])[..., None]
            q = GD.l2_normalize(r.normal(size=(B, S, Hk, dk)), dk ** -0.5)
            k = GD.l2_normalize(r.normal(size=(B, S, Hk, dk)))
            g = f32(np.where(live, -r.uniform(1e-3, 0.7, (B, S, H)), 0.0))
            beta = f32(np.where(live, r.uniform(0.0, 2.0, (B, S, H)), 0.0))
            lens = jnp.asarray(q_lens, jnp.int32)

            def window(c, v, leaf, j):
                if case == "harness":
                    return v, leaf
                if case == "kernel":
                    return GW.gated_delta_window(q * c, k * c, v, g * c, beta * c,
                                                 leaf, j, lens)
                o, S1 = GD.gated_delta_scan(
                    q * c, k * c, v, g * c, beta * c,
                    jax.lax.dynamic_index_in_dim(leaf, j, 0, False))
                return o, jax.lax.dynamic_update_index_in_dim(leaf, S1, j, 0)

            def calls(v, leaf):
                def body(carry, cj):
                    v, leaf = carry
                    o, leaf = window(cj[0], v, leaf, cj[1])
                    return (o * 0.5 + v * 0.5, leaf), None
                return jax.lax.scan(body, (v, leaf),
                                    (scale, jnp.arange(L, dtype=jnp.int32)))[0]

            kept = getattr(GD, name)
            if case == "no_solve":
                setattr(GD, name, lambda A, rhs, **_: rhs)
            try:
                ms = median_ms(calls, f32(r.normal(size=(B, S, H, dv))),
                               f32(r.normal(size=(L, B, dk, H * dv)) * 0.1))
            finally:
                setattr(GD, name, kept)
        line = {"case": case, "rows": B, "columns": S, "chunks": n, "layers": L,
                "heads": H, "key_heads": Hk, "key_dim": dk, "value_dim": dv,
                "solve": name, "block_rows": getattr(GD, "_SOLVE_BLOCK", None),
                "platform": jax.devices()[0].platform}
        if case == "kernel":
            line.update(moving_rows=int(moving), one_token_rows=int(riders),
                        q_len=int(q_lens.max()))
        if not args.rehearse:       # a CPU time is no device time
            line["ms_a_call"] = round(ms, 4)
        print("gated_delta_window: " + json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
