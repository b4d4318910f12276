#!/usr/bin/env python3
"""Time the kernels on a recurrent-state leaf ALONE on the chip: Mamba-2's
decode step and window (ops/ssd_scan.py, ISSUE 49 and 54) and the gated delta
rule's (ops/gated_delta.py, ops/gated_delta_window.py, ISSUE 46 and 56), one
loop around a table of four (ISSUE 57; four tools before it).

The loop: ``--layers`` calls scanned over the planes of the state leaf (the
plane a traced ordinal, the leaf on the carry and donated, as a chunk program
keeps it), scanned again over ``--passes`` passes, each call's output mixed
into the next call's input, so one execution is that many calls and nothing
else. One JSON line a case, ``<kernel>: {...}``: the median of ``--repeats``
executions as ms a PASS for a step (``--layers`` calls: a decode pass's worth)
and ms a CALL for a window, beside its floor, the MOVING rows' state read once
and written once plus a window's operands and outputs (every row's: the
projections made them) at the chip's HBM bandwidth. An execution costs its
launch and the wait for its result besides: give ``--passes 1 8`` and read what
a pass adds.

Cases (``--cases``): ``kernel`` (a step at each of ``--block-heads`` heads a
block, 0: what it chooses; ``ssd_window`` at each of ``--chunk`` tokens a chunk);
``jnp``, the plain form from and to a plane sliced out of the leaf, which the
tests hold the kernel to and the model ran before (it moves every row and scans
every column; ``gated_delta_window`` prints it as ``whole``); ``harness``, the
loop with no call in it (what a line's ms hold besides the call). A step's
``--live`` of ``--rows`` rows move. A window is ``--shapes`` rows x columns (an
eager piece ``1x512``, a prologue ``16x64``, the widest window ``16x512``):
``--moving`` rows bring ``--fill`` of the width each, ``--riders`` more (the
delta rule's) ONE token each, a live decode row in a prologue, the others none.
A flag left out takes its kernel's own default (``KERNELS``). ``--sizes``:
``granite`` (granite-4.0-h-micro: 64 heads of 64, state 128, ONE group, chunk
256) and ``nemotron`` (nemotron-3-nano-30b-a3b: 8 groups, chunk 128); ``olmo``
(olmo-hybrid-7b: 30 heads of 96 x 192) and ``qwen3next`` (qwen3-next-80b-a3b-
instruct: 16 key heads of 128 for 32 value heads of 128).

    chiprun -- python tools/time_state_kernels.py --kernel ssd_window
    python tools/time_state_kernels.py --rehearse     # here: tiny, interpreted, no timing claim
"""

from __future__ import annotations

import argparse
import itertools
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_HBM_BYTES_S = 819e9                            # benchmark/peaks.json, v5e
#: (heads, head_dim, groups, state, ssm_chunk); tiny-*: a rehearsal's
SSD = {"granite": (64, 64, 1, 128, 256), "nemotron": (64, 64, 8, 128, 128),
       "tiny-1-group": (4, 8, 1, 16, 16), "tiny-2-groups": (4, 8, 2, 16, 8)}
#: (value heads, key heads, key_dim, value_dim)
DELTA = {"olmo": (30, 30, 96, 192), "qwen3next": (32, 16, 128, 128),
         "tiny-2-for-4": (4, 2, 24, 40)}
#: a kernel's own defaults, and what a line's ms are over
KERNELS = {
    "ssd_step": dict(of=SSD, sizes=["nemotron"], tiny=["tiny-2-groups"], rows=16,
                     live=[16, 11, 4], block_heads=[8, 16, 32, 64], layers=6,
                     passes=[8], per="pass"),
    "ssd_window": dict(of=SSD, sizes=["granite", "nemotron"],
                       tiny=["tiny-1-group", "tiny-2-groups"],
                       shapes=["1x512", "16x64", "16x512"], moving=[1, 2, 16],
                       layers=6, passes=[4], per="call"),
    "gated_delta_step": dict(of=DELTA, sizes=["olmo"], tiny=["tiny-2-for-4"], rows=8,
                             live=None, block_heads=[2, 6, 10, 30], layers=24,
                             passes=[1], per="pass"),
    "gated_delta_window": dict(of=DELTA, sizes=["olmo"], tiny=["tiny-2-for-4"],
                               shapes=["1x256", "1x64", "8x64", "8x512"],
                               moving=[1], layers=24, passes=[1], per="call"),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kernel", nargs="*", default=list(KERNELS), choices=list(KERNELS))
    ap.add_argument("--cases", nargs="*", default=["kernel", "jnp", "harness"],
                    choices=["kernel", "jnp", "harness"])
    ap.add_argument("--sizes", nargs="*",
                    choices=[n for n in (*SSD, *DELTA) if "tiny" not in n])
    ap.add_argument("--block-heads", type=int, nargs="*")
    ap.add_argument("--rows", type=int)
    ap.add_argument("--live", type=int, nargs="*",
                    help="a step's rows that move (the rest have zero gates), one line each")
    ap.add_argument("--shapes", "--shape", nargs="*")
    ap.add_argument("--moving", type=int, nargs="*",
                    help="a window's rows that brought tokens (capped at a shape's rows)")
    ap.add_argument("--riders", type=int, nargs="*", default=[0],
                    help="rows beside them that brought ONE token (the delta rule's window)")
    ap.add_argument("--fill", type=float, default=0.85,
                    help="share of the width a moving row's q_len is")
    ap.add_argument("--chunk", type=int, nargs="*", default=[0],
                    help="tokens a chunk of ssd_window (0: window_chunk's choice)")
    ap.add_argument("--layers", type=int)
    ap.add_argument("--passes", type=int, nargs="*",
                    help="passes over the leaf's planes an execution, one line each")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import gated_delta as GD
    from ai_agent_kubectl_tpu.ops import gated_delta_window as GW
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    interpret = jax.default_backend() != "tpu"

    def on_a_plane(plain):
        """``plain(x, plane, j) -> (y, plane)`` from and to plane ``j`` sliced
        out of the leaf: how the model ran a layer before its kernel."""
        def call(x, leaf, j):
            y, new = plain(x, jax.lax.dynamic_index_in_dim(leaf, j, 0, False), j)
            return y, jax.lax.dynamic_update_index_in_dim(leaf, new, j, 0)
        return call

    def windows(own):
        """(rows, width, q_lens) of every ``--shapes`` x ``--moving`` x
        ``--riders``."""
        shapes = ([(1, 37), (3, 8)] if args.rehearse else
                  [tuple(int(n) for n in s.split("x")) for s in args.shapes or own["shapes"]])
        seen = set()
        for (B, W), riders in itertools.product(shapes, args.riders):
            for moving in sorted({min(m, B) for m in args.moving or own["moving"]}):
                q = np.where(np.arange(B) < moving, max(1, int(W * args.fill)),
                             np.where(np.arange(B) < moving + riders, 1, 0))
                if (W, *q) not in seen:         # (riders a shape has no rows for)
                    seen.add((W, *q))
                    yield B, W, q

    def steps(own):
        B = 3 if args.rehearse else args.rows or own["rows"]
        lives = [3, 1] if args.rehearse else args.live or own["live"] or [B]
        return [(B, min(n, B)) for n in lives]

    def ssd_step(r, dims, own):
        H, P, G, N, _ = dims
        A, D = f32(-r.uniform(0.5, 4.0, H)), f32(r.normal(size=H))
        for B, live in steps(own):
            Bm = f32(r.normal(size=(B, 1, G, N)))
            Cm = f32(r.normal(size=(B, 1, G, N)) * 0.1)
            dt = f32(np.where((np.arange(B) < live)[:, None, None],
                              r.uniform(0.01, 0.5, (B, 1, H)), 0.0))
            kernel = {f"kernel-{hb}": lambda x, leaf, j, hb=hb: S.ssd_step_kernel(
                x, dt, A, Bm, Cm, D, leaf, j, None, hb)
                for hb in ([2, 4] if args.rehearse else args.block_heads or own["block_heads"])}
            plain = on_a_plane(lambda x, h, j: S.ssd_step(x, dt, A, Bm, Cm, D, h))
            yield (dict(rows=B, live_rows=live, heads=H, head_dim=P, state=N),
                   f32(r.normal(size=(B, 1, H, P))), (B, H, P, N), kernel, {"jnp": plain},
                   2 * live * H * P * N * 4, 0)

    def ssd_window(r, dims, own):
        H, P, G, N, own_chunk = dims
        dtype = jnp.float32 if args.rehearse else jnp.bfloat16
        A, D = f32(-r.uniform(0.5, 4.0, H)), f32(r.normal(size=H))
        for B, W, q in windows(own):
            dt = f32(np.where(np.arange(W)[None, :, None] < q[:, None, None],
                              r.uniform(0.001, 0.1, (B, W, H)), 0.0))
            Bm = jnp.asarray(r.normal(size=(B, W, G, N)), dtype)
            Cm = jnp.asarray(r.normal(size=(B, W, G, N)) * 0.1, dtype)
            lens = jnp.asarray(q, jnp.int32)
            kernel = {f"kernel-{c}": lambda x, leaf, j, c=c: S._window_call(
                x, dt, A, Bm, Cm, D, leaf, j, lens, chunk=c, interpret=interpret)
                for c in (c or S.window_chunk(W, own_chunk) for c in args.chunk)}
            plain = on_a_plane(lambda x, h, j: S.ssd_scan(x, dt, A, Bm, Cm, D, h, own_chunk))
            yield (dict(rows=B, width=W, moving_rows=int((q > 0).sum()), q_len=int(q.max()),
                        groups=G),
                   jnp.asarray(r.normal(size=(B, W, H, P)), dtype), (B, H, P, N), kernel,
                   {f"jnp-{own_chunk}": plain}, 2 * int((q > 0).sum()) * H * P * N * 4,
                   B * W * ((2 * H * P + 2 * G * N) * dtype.dtype.itemsize + H * 4))

    def delta_operands(r, dims, B, W, live):
        """q, k, g, beta of a [B, W] window whose ``live`` [B, W] tokens move."""
        H, Hk, dk, _ = dims
        live = live[..., None]
        return (GD.l2_normalize(r.normal(size=(B, W, Hk, dk)), dk ** -0.5),
                GD.l2_normalize(r.normal(size=(B, W, Hk, dk))),
                f32(np.where(live, -r.uniform(1e-3, 0.7, (B, W, H)), 0.0)),
                f32(np.where(live, r.uniform(0.0, 2.0, (B, W, H)), 0.0)))

    def gated_delta_step(r, dims, own):
        H, Hk, dk, dv = dims
        for B, live in steps(own):
            q, k, g, beta = delta_operands(r, dims, B, 1, (np.arange(B) < live)[:, None])
            kernel = {f"kernel-{hb}": lambda v, leaf, j, hb=hb: GD.gated_delta_step_kernel(
                q, k, v, g, beta, leaf, j, None, hb)
                for hb in ([2, 4] if args.rehearse else args.block_heads or own["block_heads"])}
            plain = on_a_plane(lambda v, s, j: GD.gated_delta_step(q, k, v, g, beta, s))
            yield (dict(rows=B, live_rows=live, heads=H, key_dim=dk, value_dim=dv),
                   f32(r.normal(size=(B, 1, H, dv))), (B, dk, H * dv), kernel, {"jnp": plain},
                   2 * live * dk * H * dv * 4, 0)

    def gated_delta_window(r, dims, own):
        H, Hk, dk, dv = dims
        for B, W, lens in windows(own):
            q, k, g, beta = delta_operands(r, dims, B, W, np.arange(W)[None, :] < lens[:, None])
            q_lens = jnp.asarray(lens, jnp.int32)
            # every call's keys, decays and strengths differ by a factor near
            # 1: the compiler cannot make one call's ``T`` for all of them
            scaled = lambda j: (a * (1.0 - 1e-4 * j.astype(jnp.float32)) for a in (q, k, g, beta))

            def kernel(v, leaf, j):
                qs, ks, gs, bs = scaled(j)
                return GW.gated_delta_window(qs, ks, v, gs, bs, leaf, j, q_lens)

            def plain(v, s, j):
                qs, ks, gs, bs = scaled(j)
                return GD.gated_delta_scan(qs, ks, v, gs, bs, s)

            yield (dict(rows=B, columns=W, chunks=-(-W // min(GD.CHUNK, W)), heads=H,
                        key_heads=Hk, key_dim=dk, value_dim=dv, moving_rows=int((lens > 1).sum()),
                        one_token_rows=int((lens == 1).sum()), q_len=int(lens.max())),
                   f32(r.normal(size=(B, W, H, dv))), (B, dk, H * dv), {"kernel": kernel},
                   {"whole": on_a_plane(plain)}, 2 * int((lens > 0).sum()) * dk * H * dv * 4,
                   B * W * (2 * Hk * dk + 2 * H * dv + 2 * H) * 4)

    def median_s(call, x0, plane, L, passes, r):
        """Seconds an execution of ``passes`` x ``L`` calls, the median of
        ``--repeats`` after the one that compiles; the leaf is donated, an
        execution's results are the next one's arguments."""
        def run_passes(x, leaf):
            def layer(carry, j):
                x, leaf = carry
                y, leaf = call(x, leaf, j)
                return ((y * 0.5 + x * 0.5).astype(x.dtype), leaf), None

            def one_pass(carry, _):
                return jax.lax.scan(layer, carry, jnp.arange(L, dtype=jnp.int32))[0], None
            return jax.lax.scan(one_pass, (x, leaf), None, length=passes)[0]

        run = jax.jit(run_passes, donate_argnums=(1,))
        x, leaf = run(x0, f32(r.normal(size=(L,) + plane) * 0.1))
        x.block_until_ready()
        times = []
        for _ in range(1 if args.rehearse else args.repeats):
            t0 = time.perf_counter()
            x, leaf = run(x0, leaf)
            x.block_until_ready()
            times.append(time.perf_counter() - t0)
        return statistics.median(times)

    table = {"ssd_step": ssd_step, "ssd_window": ssd_window,
             "gated_delta_step": gated_delta_step, "gated_delta_window": gated_delta_window}
    for name in args.kernel:
        own = KERNELS[name]
        L = 2 if args.rehearse else args.layers or own["layers"]
        for size in own["tiny"] if args.rehearse else args.sizes or own["sizes"]:
            if size not in own["of"]:
                continue                        # the other recurrence's
            r = np.random.default_rng(0)
            for line, x0, plane, kernel, plain, moved, operands in table[name](
                    r, own["of"][size], own):
                cases = {**(kernel if "kernel" in args.cases else {}),
                         **(plain if "jnp" in args.cases else {}),
                         **({"harness": lambda x, leaf, j: (x, leaf)}
                            if "harness" in args.cases else {})}
                for (case, call), passes in itertools.product(
                        cases.items(), [1] if args.rehearse else args.passes or own["passes"]):
                    calls = L if own["per"] == "pass" else 1    # ... a line's ms are over
                    ms = median_s(call, x0, plane, L, passes, r) * 1e3 * calls / (passes * L)
                    floor_ms = (moved + operands) * calls / _HBM_BYTES_S * 1e3
                    out = {"case": case, "sizes": size, **line, "layers": L, "passes": passes,
                           "state_bytes_read_and_written": moved * calls,
                           "operand_and_output_bytes": operands * calls,
                           "platform": jax.devices()[0].platform}
                    if not args.rehearse:       # a CPU time is no device time
                        out.update({f"ms_a_{own['per']}": round(ms, 4),
                                    "floor_ms": round(floor_ms, 4),
                                    "share_of_floor": round(100.0 * floor_ms / ms, 1)})
                    print(f"{name}: " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
