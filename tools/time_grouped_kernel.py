#!/usr/bin/env python3
"""Time ``parallel/moe.py``'s grouped expert kernel ALONE on the chip.

The sibling of ``tools/time_ragged_kernel.py`` (PERF.md Findings, PR 34):
``_grouped_ffn`` is scanned inside one jit at a cell's shape, each call's
output feeding a row of the next call's input, so one execution is
``--calls`` kernel calls back to back and nothing else. A geometry is a
configuration's expert shape, a shape is what the serving path hands the
kernel (a decode pass of the live slots, the 64-wide window of 16 slots
with every row live or with one admission's, an eager 512-wide piece of
one prompt, whole or a turn's part-filled last): pairs are dealt to experts as a
uniform router would, tiles are laid out as ``grouped_moe`` lays them
(the package's own ``_group_tile`` and grid bound). It prints, a geometry, a shape and a
form, one JSON line: us a call, us a live tile, us a live expert, and the
share of the bytes' floor (the live experts' stored bytes / the chip's
HBM bandwidth / the time).

Forms take the kernel apart without a switch in the kernel:

- ``shipped``:  as the program runs it.
- ``stream``:   every product gives zeros while the kernel is traced, so
  nothing is converted and the MXU is idle: the pipeline's weight stream,
  the grid's steps and the output writes alone.
- ``compute``:  every live tile names ONE expert, so nothing new is
  fetched after the first: conversion and products alone.
- ``products``: ``compute`` on weights handed over in the activation
  dtype already (nothing to convert).
- ``convert``:  a kernel of this file: an expert's stored blocks, resident,
  converted to the activation dtype into VMEM scratch, a grid step a live
  tile (what the conversion alone costs, whoever overlaps it).
- ``dead``:     no tile is live: a call's dead steps alone.

    chiprun -- python tools/time_grouped_kernel.py --geometry nemotron30b keye30b \
        --shape decode window-64 eager-512 --form shipped stream compute products convert dead
    python tools/time_grouped_kernel.py --rehearse       # here: tiny, interpreted, no timing claim

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

#: name -> (D, F, experts, picks a token, activation, gated)
GEOMETRIES = {
    # nemotron-3-nano-30b-a3b: two-matrix relu^2 experts, F no multiple of 128
    "nemotron30b": (2688, 1856, 128, 6, "relu2", False),
    # keye-vl-2.0-30b-a3b: gated three-matrix experts
    "keye30b": (2048, 768, 128, 8, "silu", True),
}

#: name -> (tokens of the call's static shape, live tokens among them);
#: decode: 16 slots, the pass's live ones (--live-tokens). The two windows
#: as the cells mostly run them: one admission's last 64 tokens beside 15
#: slots' decode tokens, and the second, part-filled piece of a turn.
SHAPES = {"decode": (16, None), "window-64": (16 * 64, 16 * 64),
          "eager-512": (512, 512), "window-64-one-admission": (16 * 64, 79),
          "eager-512-tail": (512, 249)}

_FORMS = ["shipped", "stream", "compute", "products", "convert", "dead"]


def _tiles(moe, static, tokens, k, experts, form, seed):
    """(tm, tile_expert, tile_live, live tiles, live experts): the pairs of
    ``tokens`` live tokens of a call of ``static`` picking ``k`` distinct
    experts each, uniformly."""
    import numpy as np

    rng = np.random.default_rng(seed)
    sizes = np.zeros(experts, np.int64)
    for _ in range(tokens):
        sizes[rng.choice(experts, size=k, replace=False)] += 1
    M = static * k
    tm = moe._group_tile(M, experts)
    n_tiles = getattr(moe, "_grid_tiles",
                      lambda M, tm, E: -(-M // tm) + E)(M, tm, experts)
    tiles_of = -(-sizes // tm)
    tile_end = np.cumsum(tiles_of)
    n_live = int(tile_end[-1])
    tile = np.arange(n_tiles)
    tile_expert = np.searchsorted(tile_end, np.minimum(tile, n_live - 1),
                                  side="right")
    tile_expert = np.clip(tile_expert, 0, experts - 1).astype(np.int32)
    tile_live = (tile < n_live).astype(np.int32)
    if form in ("compute", "products"):
        tile_expert[:] = 0
    if form == "dead":      # as the tiles after the last live one: one expert
        tile_live[:] = 0
        tile_expert[:] = 0
    return tm, tile_expert, tile_live, n_live, int((sizes > 0).sum())


@contextlib.contextmanager
def _zero_products(on):
    """Every product the kernel's body asks for gives zeros while it is
    traced (``jnp.dot`` and ``lax.dot_general``, as the module reaches
    them)."""
    if not on:
        yield
        return
    import jax
    import jax.numpy as jnp

    real_dg, real_dot = jax.lax.dot_general, jnp.dot

    def zeros_dg(a, b, dims, **kw):
        s = jax.eval_shape(lambda x, y: real_dg(x, y, dims, **kw), a, b)
        return jnp.zeros(s.shape, s.dtype)

    def zeros_dot(a, b, **kw):
        s = jax.eval_shape(lambda x, y: real_dot(x, y, **kw), a, b)
        return jnp.zeros(s.shape, s.dtype)

    jax.lax.dot_general, jnp.dot = zeros_dg, zeros_dot
    try:
        yield
    finally:
        jax.lax.dot_general, jnp.dot = real_dg, real_dot


def _convert_alone(weights, tile_expert, tile_live, tm, D, dtype, interpret):
    """The ``convert`` form's kernel: a grid step a tile, the expert's
    blocks as stored (resident: every tile names one expert), each
    converted whole into scratch of the activation dtype."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = len(weights)

    def kernel(te_ref, live_ref, *refs):
        w_refs, o_ref, scratch = refs[:n], refs[n], refs[n + 1:]

        @pl.when(live_ref[pl.program_id(0)] != 0)
        def _():
            for w_ref, s_ref in zip(w_refs, scratch):
                s_ref[...] = w_ref[0, 0].astype(dtype)
            o_ref[...] = scratch[0][:tm, :128].astype(o_ref.dtype)

    def expert(i, te, live):
        return (0, te[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(tile_expert.shape[0],),
        in_specs=[pl.BlockSpec((1, 1) + w.shape[2:], expert)
                  for w in weights],
        out_specs=pl.BlockSpec((tm, 128), lambda i, te, live: (i, 0)),
        scratch_shapes=[pltpu.VMEM(w.shape[2:], dtype) for w in weights])
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tile_expert.shape[0] * tm, 128),
                                       jnp.float32),
        interpret=interpret, name="convert_alone",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=100 * 2**20)}),
    )(tile_expert, tile_live, *weights)


def time_one(moe, ModelConfig, QuantInt8, name, shape, form, *, calls, reps,
             live_tokens, layers, rehearse, seed):
    import jax
    import jax.numpy as jnp

    D, F, E, k, activation, gated = GEOMETRIES[name]
    static, tokens = SHAPES[shape]
    tokens = tokens or live_tokens
    if rehearse:    # control flow only: narrow experts, few of them
        D, F, E, layers = 256, 29 * 8, 16, 2
        static, tokens = min(static, 48), min(tokens, 48)
    cfg = ModelConfig(name=f"time-{name}", vocab_size=256, dim=D, n_layers=1,
                      n_heads=2, n_kv_heads=2, head_dim=128, mlp_hidden=F,
                      n_experts=E, experts_per_token=k,
                      activation=activation)
    assert cfg.gated_mlp == gated
    tm, tile_expert, tile_live, n_live, live_experts = _tiles(
        moe, static, tokens, k, E, form, seed)
    n_tiles = tile_expert.shape[0]
    dt = jnp.bfloat16
    plain = form == "products"

    @jax.jit
    def make(key):
        def leaf(key, i, o):
            kq, ks = jax.random.split(key)
            if plain:
                return jax.random.normal(kq, (layers, E, i, o), dt) * 0.02
            return QuantInt8(
                q=jax.random.randint(kq, (layers, E, i, o), -127, 128,
                                     jnp.int8),
                scale=jax.random.uniform(ks, (layers, E, 1, o), jnp.float32,
                                         1e-4, 3e-4))
        ks = jax.random.split(key, 4)
        lp = {"w_up": leaf(ks[0], D, F), "w_down": leaf(ks[1], F, D)}
        if gated:
            lp["w_gate"] = leaf(ks[2], D, F)
        return lp, jax.random.normal(ks[3], (n_tiles * tm, D), dt)

    lp, xs = make(jax.random.PRNGKey(seed))
    te, tl = jnp.asarray(tile_expert), jnp.asarray(tile_live)

    def run(lp, xs, te, tl):
        def one(xc, layer):
            if form == "convert":
                ws = [lp[n].q for n in ("w_gate", "w_up", "w_down")
                      if n in lp]
                ys = _convert_alone(ws, te, tl, tm, D, dt, rehearse)
            else:
                ys = moe._grouped_ffn(cfg, lp, xc, te, tl, tm, layer)
            # the next call waits for this one: one row of its input
            return xc.at[0, :128].set(ys[0, :128].astype(xc.dtype)), None

        return jax.lax.scan(one, xs, jnp.arange(calls, dtype=jnp.int32)
                            % layers)[0]

    with _zero_products(form == "stream"):
        compiled = jax.jit(run).lower(lp, xs, te, tl).compile()
    temp = getattr(compiled.memory_analysis(), "temp_size_in_bytes", None)
    for _ in range(2):
        compiled(lp, xs, te, tl).block_until_ready()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        compiled(lp, xs, te, tl).block_until_ready()
        times.append(time.perf_counter() - t0)

    itemsize = 1      # the cells' experts are int8 as stored
    expert_bytes = (3 if gated else 2) * D * F * itemsize
    floor_us = live_experts * expert_bytes / _HBM_BYTES_S * 1e6
    us = statistics.median(times) / calls * 1e6
    dev = jax.devices()[0]
    resolved = getattr(moe, "grouped_kernel_shape", None)
    return {
        "geometry": name, "shape": shape, "form": form,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "rehearsal": bool(rehearse),
        "expert": [D, F], "matrices": 3 if gated else 2, "tokens": tokens,
        "static_tokens": static, "pairs": tokens * k, "tile_rows": tm, "grid_steps": n_tiles,
        "live_tiles": n_live, "live_experts": live_experts,
        "resolved": None if resolved is None else resolved(cfg, static),
        "temp_bytes": temp,
        "us_per_call": round(us, 2),
        "us_per_call_min": round(min(times) / calls * 1e6, 2),
        "us_per_live_tile": round(us / max(n_live, 1), 3),
        "us_per_live_expert": round(us / max(live_experts, 1), 3),
        "us_per_step": round(us / n_tiles, 4),
        "expert_bytes_floor_us": round(expert_bytes / _HBM_BYTES_S * 1e6, 3),
        "bytes_floor_us": round(floor_us, 2),
        "bytes_floor_share": round(floor_us / us, 4),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--geometry", nargs="+", default=list(GEOMETRIES),
                    choices=list(GEOMETRIES))
    ap.add_argument("--shape", nargs="+", default=["decode"],
                    choices=list(SHAPES))
    ap.add_argument("--form", nargs="+", default=["shipped"], choices=_FORMS)
    ap.add_argument("--live-tokens", type=int, default=11,
                    help="decode: the pass's live slots (the cells read "
                         "~47 and ~63 experts a layer pass: 10-11 live)")
    ap.add_argument("--layers", type=int, default=2,
                    help="layers of the stacked leaves the calls cycle over")
    ap.add_argument("--calls", type=int, default=32,
                    help="kernel calls in one execution")
    ap.add_argument("--reps", type=int, default=9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tile-rows", type=int, default=None,
                    help="rows of a tile, in place of the package's rule "
                         "(to settle the rule)")
    ap.add_argument("--package-root", default=None,
                    help="import ai_agent_kubectl_tpu from this checkout")
    ap.add_argument("--label", default=None,
                    help="goes into every line (parent / change)")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes through the interpreter on the CPU: "
                         "control flow only, its times mean nothing")
    ap.add_argument("--out", default="chiprun_out/time_grouped_kernel.jsonl")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.abspath(args.package_root) if args.package_root
                    else os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__))))
    import jax

    from ai_agent_kubectl_tpu.models.config import ModelConfig
    from ai_agent_kubectl_tpu.ops.quant import QuantInt8
    from ai_agent_kubectl_tpu.parallel import moe

    if not args.rehearse and jax.default_backend() != "tpu":
        print("time_grouped_kernel: no TPU here; a kernel time comes from "
              "the chip only (--rehearse checks the control flow)",
              file=sys.stderr)
        return 1
    if args.rehearse:
        args.calls, args.reps = 2, 1
    if args.tile_rows:
        moe._group_tile = lambda pairs, experts: args.tile_rows
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for name in args.geometry:
        for shape in args.shape:
            for form in args.form:
                try:
                    line = time_one(
                        moe, ModelConfig, QuantInt8, name, shape, form,
                        calls=args.calls, reps=args.reps,
                        live_tokens=args.live_tokens, layers=args.layers,
                        rehearse=args.rehearse, seed=args.seed)
                except Exception as e:     # a form the compiler refuses
                    line = {"geometry": name, "shape": shape, "form": form,
                            "error": f"{type(e).__name__}: {e}"[:400]}
                if args.label:
                    line = {"label": args.label, **line}
                if args.tile_rows:
                    line["tile_rows_forced"] = True
                text = json.dumps(line)
                print(text, flush=True)
                with open(args.out, "a") as f:
                    f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
