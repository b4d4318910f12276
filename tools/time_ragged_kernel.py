#!/usr/bin/env python3
"""Time ``ops/ragged_attention.py::ragged_attention_pool`` ALONE on the chip.

The method that settled PR 30 and PR 32 (PERF.md Open question 11): the
kernel is scanned over a stacked pool's layers inside one jit at a cell's
decode shape, each call's output feeding the next call's queries, so one
execution is ``--calls`` kernel calls back to back and nothing else. It
prints, a geometry and a form, one JSON line: us a call, us a live block
(a block of ``pages_per_step`` pages that holds a live page), and the
share of the bytes' floor (live K and V bytes / the chip's HBM bandwidth
/ the time).

Forms take the kernel apart without a switch in the kernel: they patch
what the kernel's body calls while it is traced.

- ``shipped``: as the program runs it.
- ``copies``:  the two ``dot_general``s give zeros, so K and V buffers are
  never read and the MXU is idle: the page stream and the loop's scalar
  work alone (a sliver of mask and softmax arithmetic on the score tile
  stays).
- ``compute``: ``make_async_copy`` starts and waits for nothing: the
  arithmetic on buffers that are already there.

    chiprun -- python tools/time_ragged_kernel.py --geometry keye30b-longlogs mistral7b-chat \
        --form shipped copies compute
    python tools/time_ragged_kernel.py --rehearse          # here: tiny, interpreted, no timing claim

``--package-root DIR`` imports ``ai_agent_kubectl_tpu`` from another
checkout (a parent commit unpacked under ``.chipwork/``), so parent and
change can be timed in one call. It is a tool: no cell runs it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

#: v5e HBM bandwidth, bytes/s (benchmark/peaks.json).
_HBM_BYTES_S = 819e9

#: name -> (H, KV, layers of the stacked pool, table pages, pool blocks,
#: live contexts of the 16 slots or None for --live-pages over --live-slots,
#: sel operand set). Head dim 128, page 64, N 16, W 1 (--width): every
#: cell's decode call; a chip of the mesh holds 8Q/2KV.
GEOMETRIES = {
    "mistral7b-chat": (32, 8, 32, 65, 320, None, False),
    "mixtral6l-chat": (32, 8, 6, 65, 1040, None, False),
    "mixtral8x7b-tp4-chat": (8, 2, 32, 65, 1792, None, False),
    # contexts 6,144 ... 15,360 + 128 in 16 steps: 99-242 live pages a slot
    "keye30b-longlogs": (32, 4, 8, 257, 4096,
                         [6144 + 128 + (15360 - 6144) * i // 15
                          for i in range(16)], True),
    # MHA, 30 KV heads with ONE query head each: a pool row holds 32
    # (ModelConfig.kv_heads_paged) and the kernel runs 32Q/32KV, the two
    # spare heads zeros. 8 agents at 2.3k-3.4k + 128 keys, 8 slots dead
    "olmohybrid7b-sessions": (30, 30, 8, 64, 448,
                              [2300 + 128 + 157 * i for i in range(8)]
                              + [0] * 8, False),
    # ... and an eager piece (--width 512): one sequence 3,072 keys deep
    "olmohybrid7b-piece": (30, 30, 8, 64, 448, [3072] + [0] * 15, False),
}
_HD, _PAGE, _N = 128, 64, 16


def _case(name, live_slots, live_pages, width, rehearse):
    import jax
    import jax.numpy as jnp
    import numpy as np

    H, KV, L, pages, n_blocks, contexts, selects = GEOMETRIES[name]
    if KV > 8 and KV % 8:       # whole tiles of 8 heads a row, as the pool's
        spare = -KV % 8
        H, KV = H + spare * (H // KV), KV + spare
    hd = _HD
    if rehearse:    # control flow only: two layers, a narrow head, few pages
        L, hd, pages = 2, 16, min(pages, 40)
        if contexts:
            contexts = [c // 8 for c in contexts]
    if contexts is None:    # the call's live pages, spread over its slots
        contexts = [(live_pages // live_slots + (i < live_pages % live_slots))
                    * _PAGE - 7 for i in range(live_slots)] + \
            [0] * (_N - live_slots)
    ctx = np.asarray(contexts, np.int32)
    live = -(-ctx // _PAGE)                     # pages holding a live key
    n_blocks = max(n_blocks, int(live.sum())) if not rehearse \
        else int(live.sum()) + 1
    tables = np.full((_N, pages), n_blocks, np.int32)    # the sentinel
    nxt = 0
    for n in range(_N):
        tables[n, :live[n]] = nxt + np.arange(live[n])
        nxt += int(live[n])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    dt = jnp.bfloat16
    k = jax.random.normal(keys[0], (L, n_blocks, _PAGE, KV, hd), dt)
    v = jax.random.normal(keys[1], (L, n_blocks, _PAGE, KV, hd), dt)
    q = jax.random.normal(keys[2], (_N, width, H, hd), dt)
    q_lens = jnp.asarray((ctx > 0).astype(np.int32) * width)
    positions = jnp.asarray(np.maximum(ctx - width, 0))
    sel = None
    if selects:     # keep every third key: the mask's cost, not its choice
        sel = jnp.broadcast_to(
            (jnp.arange(pages * _PAGE) % 3 == 0)[None, None, :],
            (_N, width, pages * _PAGE))
    return dict(q=q, k=k, v=v, q_lens=q_lens, positions=positions,
                tables=jnp.asarray(tables), sel=sel, H=H, KV=KV, L=L,
                hd=hd, pages=pages, live=live)


class _NoCopy:
    def start(self):
        pass

    def wait(self):
        pass


@contextlib.contextmanager
def _form(ra, form):
    """Patch what the kernel's body calls for the time it is traced."""
    import jax
    import jax.numpy as jnp

    if form == "copies":
        real = jax.lax.dot_general

        def zeros(a, b, dims, **kw):
            shape = jax.eval_shape(
                lambda x, y: real(x, y, dims, **kw), a, b)
            return jnp.zeros(shape.shape, shape.dtype)

        jax.lax.dot_general = zeros
        try:
            yield
        finally:
            jax.lax.dot_general = real
    elif form == "compute":
        real = ra.pltpu.make_async_copy
        ra.pltpu.make_async_copy = lambda *a, **kw: _NoCopy()
        try:
            yield
        finally:
            ra.pltpu.make_async_copy = real
    else:
        yield


def time_one(ra, name, form, *, calls, reps, live_slots, live_pages, width,
             rehearse, max_depth, flat_scores):
    import jax
    import jax.numpy as jnp

    c = _case(name, live_slots, live_pages, width, rehearse)
    kernel = ra.ragged_attention_pool.__wrapped__    # a fresh trace a form
    if max_depth is not None:
        ra._STREAM_DEPTH_MAX = max_depth
    if flat_scores is not None:
        ra._FLAT_SCORES_MAX = flat_scores

    def run(q, k, v, q_lens, positions, tables, sel):
        def one(qc, layer):
            # the mask is each call's own, as the selector's is: one the
            # compiler can lift out of the scan hides what it costs to
            # hand it to the kernel
            mask = sel if sel is None else jnp.logical_or(
                sel, qc[:, :, 0, :1].astype(jnp.float32) > 1e30)
            out = kernel(qc, k, v, q_lens, positions, tables, layer, mask,
                         page_size=_PAGE, interpret=rehearse or None)
            return out.astype(qc.dtype), None

        layers = jnp.arange(calls, dtype=jnp.int32) % c["L"]
        return jax.lax.scan(one, q, layers)[0]

    args = (c["q"], c["k"], c["v"], c["q_lens"], c["positions"],
            c["tables"], c["sel"])
    with _form(ra, form):
        compiled = jax.jit(run).lower(*args).compile()
    for _ in range(2):
        compiled(*args).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        compiled(*args).block_until_ready()
        times.append(time.perf_counter() - t0)

    itemsize = c["k"].dtype.itemsize
    shape = (c["pages"], _PAGE, c["H"], c["KV"], c["hd"], width, itemsize)
    pps = ra.pages_per_step(*shape)
    depth = getattr(ra, "stream_depth", None)
    blocks = int(sum(-(-int(p) // pps) for p in c["live"]))
    live_pages_all = int(c["live"].sum())
    floor_us = (live_pages_all * 2 * _PAGE * c["KV"] * c["hd"] * itemsize
                / _HBM_BYTES_S * 1e6)
    us = statistics.median(times) / calls * 1e6
    steps = ra.grid_steps(_N, *shape)
    dev = jax.devices()[0]
    return {
        "geometry": name, "form": form,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "rehearsal": bool(rehearse),
        "heads": [c["H"], c["KV"]], "layers": c["L"],
        "table_pages": c["pages"], "pages_per_step": pps,
        "stream_depth": None if depth is None else depth(*shape),
        "as_stored": hasattr(ra, "_flat") and ra._flat(
            width, c["H"], c["KV"]),
        "width": width,
        "grid_steps": steps, "live_slots": int((c["live"] > 0).sum()),
        "live_pages": live_pages_all, "live_blocks": blocks,
        "us_per_call": round(us, 2),
        "us_per_call_min": round(min(times) / calls * 1e6, 2),
        "us_per_live_block": round(us / max(blocks, 1), 3),
        # PR 30's reading: a dead grid step is 0.05 us
        "us_per_live_block_less_steps": round(
            (us - 0.05 * steps) / max(blocks, 1), 3),
        "bytes_floor_us": round(floor_us, 2),
        "bytes_floor_share": round(floor_us / us, 4),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geometry", nargs="+", default=list(GEOMETRIES),
                    choices=list(GEOMETRIES))
    ap.add_argument("--form", nargs="+", default=["shipped"],
                    choices=["shipped", "copies", "compute"])
    ap.add_argument("--live-slots", type=int, default=6,
                    help="chat geometries: slots that decode (the rest "
                         "are frozen); PR 30 timed 6 and 16")
    ap.add_argument("--live-pages", type=int, default=39,
                    help="chat geometries: live pages of the call, spread "
                         "over its decoding slots (PR 30: 39 over 6, one "
                         "half-live block a slot)")
    ap.add_argument("--width", type=int, default=1,
                    help="query columns a live slot brings (1: a decode "
                         "row; 4: a verify window; up to one query tile)")
    ap.add_argument("--calls", type=int, default=64,
                    help="kernel calls in one execution")
    ap.add_argument("--reps", type=int, default=15)
    ap.add_argument("--max-depth", type=int, default=None,
                    help="override the ring's cap (_STREAM_DEPTH_MAX) to "
                         "settle it; ignored by a kernel that has none")
    ap.add_argument("--flat-scores", type=int, default=None,
                    help="override the score elements a key may cost a "
                         "tile that reads the block as stored "
                         "(_FLAT_SCORES_MAX; 0: every tile transposes)")
    ap.add_argument("--package-root", default=None,
                    help="import ai_agent_kubectl_tpu from this checkout")
    ap.add_argument("--label", default=None,
                    help="goes into every line (parent / change)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes through the interpreter on the CPU: "
                         "control flow only, its times mean nothing")
    ap.add_argument("--out", default="chiprun_out/time_ragged_kernel.jsonl")
    args = ap.parse_args(argv)

    if args.package_root:
        sys.path.insert(0, os.path.abspath(args.package_root))
    else:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    import jax

    from ai_agent_kubectl_tpu.ops import ragged_attention as ra

    if not args.rehearse and jax.default_backend() != "tpu":
        print("time_ragged_kernel: no TPU here; a kernel time comes from "
              "the chip only (--rehearse checks the control flow)",
              file=sys.stderr)
        return 1
    if args.rehearse:
        args.calls, args.reps = 2, 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for name in args.geometry:
        for form in args.form:
            line = time_one(ra, name, form, calls=args.calls, reps=args.reps,
                            live_slots=args.live_slots,
                            live_pages=args.live_pages,
                            width=args.width, rehearse=args.rehearse,
                            max_depth=args.max_depth,
                            flat_scores=args.flat_scores)
            if args.label:
                line = {"label": args.label, **line}
            text = json.dumps(line)
            print(text, flush=True)
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
