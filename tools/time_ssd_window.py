#!/usr/bin/env python3
"""Time a Mamba-2 WINDOW's scan alone on the chip (ISSUE 54), by the method of
tools/time_ssd_step.py: a pass is ``--layers`` calls, a plane of the state leaf
each, the leaf carried by a scan over ``--passes`` passes, each call's output
feeding the next call's input, so one execution is ``--passes`` window passes'
worth of scans and nothing else. One JSON line a case: ms a call (the median of
``--repeats`` executions over passes x layers) beside its floor, the MOVING rows'
state read once and written once plus the window's operands and outputs (x, B, C,
dt in, y out: every row's, the projections made them) at the chip's HBM bandwidth.
An execution costs its launch and the wait for its result besides: give
``--passes 1 4`` and read what a pass adds.

Cases: the kernel (``ops/ssd_scan.py::ssd_window``, at each of ``--chunk`` tokens
a chunk; 0: what it chooses from the shapes) and ``jnp``, the plain ``ssd_scan``
from and to a plane sliced out of the leaf at the configuration's own
``ssm_chunk``, as the model ran a window before (it scans every row and every
column, whatever ``--moving`` says); ``harness``, the loop with no scan in it
(what a line's ms hold besides the call: the mix of ``y`` into the next call's
``x`` over every row, the launch and the wait over the calls). Shapes
``[rows, width]``: an eager piece ``1x512``, a prologue ``16x64``, the widest
window ``16x512``; ``--moving`` rows bring ``--fill`` of the width each (the
last chunk's tail keeps ``dt = 0``), the others none. Sizes: ``granite`` (64
heads of 64, state 128, ONE group, chunk 256, 36 layers in the cell; 6 here) and
``nemotron`` (8 groups, chunk 128, 6 layers).

    chiprun -- python tools/time_ssd_window.py
    python tools/time_ssd_window.py --rehearse     # here: tiny, interpreted, no timing claim
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
#: (heads, head_dim, groups, state, ssm_chunk)
SIZES = {"granite": (64, 64, 1, 128, 256), "nemotron": (64, 64, 8, 128, 128)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", nargs="*", default=list(SIZES), choices=list(SIZES))
    ap.add_argument("--shape", nargs="*", default=["1x512", "16x64", "16x512"])
    ap.add_argument("--moving", type=int, nargs="*", default=[1, 2, 16],
                    help="rows that brought tokens (capped at a shape's rows)")
    ap.add_argument("--fill", type=float, default=0.85,
                    help="share of the width a moving row's q_len is")
    ap.add_argument("--chunk", type=int, nargs="*", default=[0],
                    help="tokens a chunk of the kernel (0: window_chunk's choice)")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--passes", type=int, nargs="*", default=[4])
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--no-jnp", action="store_true", help="the kernel's cases alone")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    sizes = {n: SIZES[n] for n in args.sizes}
    shapes = [tuple(int(n) for n in s.split("x")) for s in args.shape]
    L, dtype = args.layers, jnp.bfloat16
    if args.rehearse:
        sizes = {"tiny-1-group": (4, 8, 1, 16, 16), "tiny-2-groups": (4, 8, 2, 16, 8)}
        shapes, L, dtype = [(1, 24), (3, 8)], 2, jnp.float32
    r = np.random.default_rng(0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    for name, (H, P, G, N, own_chunk) in sizes.items():
        A, D = f32(-r.uniform(0.5, 4.0, H)), f32(r.normal(size=H))
        for (B, W), moving in ((s, m) for s in shapes
                               for m in sorted({min(m, s[0]) for m in args.moving})):
            q = np.where(np.arange(B) < moving, max(1, int(W * args.fill)), 0)
            dt = f32(np.where(np.arange(W)[None, :, None] < q[:, None, None],
                              r.uniform(0.001, 0.1, (B, W, H)), 0.0))
            x0 = jnp.asarray(r.normal(size=(B, W, H, P)), dtype)
            Bm = jnp.asarray(r.normal(size=(B, W, G, N)), dtype)
            Cm = jnp.asarray(r.normal(size=(B, W, G, N)) * 0.1, dtype)
            q_lens = jnp.asarray(q, jnp.int32)

            def kernel(chunk):
                def call(x, leaf, j):
                    return S._window_call(
                        x, dt, A, Bm, Cm, D, leaf, j, q_lens,
                        chunk=chunk or S.window_chunk(W, own_chunk),
                        interpret=jax.default_backend() != "tpu")
                return call

            def plain(x, leaf, j):
                y, h = S.ssd_scan(x, dt, A, Bm, Cm, D,
                                  jax.lax.dynamic_index_in_dim(leaf, j, 0, False),
                                  own_chunk)
                return y, jax.lax.dynamic_update_index_in_dim(leaf, h, j, 0)

            cases = [(f"kernel-{c or S.window_chunk(W, own_chunk)}", kernel(c))
                     for c in args.chunk]
            if not args.no_jnp:
                cases.append((f"jnp-{own_chunk}", plain))
            cases.append(("harness", lambda x, leaf, j: (x, leaf)))
            for (case, call), passes in ((c, p) for c in cases for p in args.passes):
                def run_passes(x, leaf, call=call, passes=passes):
                    def one_pass(carry, _):
                        def layer(carry, j):
                            x, leaf = carry
                            y, leaf = call(x, leaf, j)
                            return ((y * 0.5 + x * 0.5).astype(x.dtype), leaf), None
                        return jax.lax.scan(layer, carry,
                                            jnp.arange(L, dtype=jnp.int32))[0], None
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
                ms = statistics.median(times) * 1e3 / (passes * L)
                item = x0.dtype.itemsize
                operands = B * W * ((2 * H * P + 2 * G * N) * item + H * 4)
                moved = 2 * moving * H * P * N * 4
                floor_ms = (moved + operands) / _HBM_BYTES_S * 1e3
                line = {"case": case, "sizes": name, "rows": B, "width": W,
                        "moving_rows": int(moving), "q_len": int(q.max()),
                        "layers": L, "passes": passes, "groups": G,
                        "state_bytes_read_and_written": moved,
                        "operand_and_output_bytes": operands,
                        "platform": jax.devices()[0].platform}
                if not args.rehearse:       # a CPU time is no device time
                    line.update(ms_a_call=round(ms, 4), floor_ms=round(floor_ms, 4),
                                share_of_floor=round(100.0 * floor_ms / ms, 1))
                print("ssd_window: " + json.dumps(line), flush=True)
                del leaf


if __name__ == "__main__":
    main()
