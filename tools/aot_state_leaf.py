#!/usr/bin/env python3
"""Does a configuration's engine program move its recurrent state whole? Read
off the programs compiled for a DESCRIBED v5e (no chip; nothing runs: sizes and
refusals, never a time).

For a configuration file of ``benchmark/configs`` with state-space layers (the
``ssm`` leaf) or, without them, linear-attention layers (the ``lin`` leaf), at
its own depth, batch, pool and widest bucket (``server_env``), three programs
are compiled as ``tests/test_tpu_aot.py`` compiles them: the decode ``forward``
(one token a slot), the engine's WINDOWED chunk program (its widest bucket,
grammar on, the valid rows packed) and ``jit_cow``. Of each: the temporaries,
and every instruction whose result is the size of the state leaf or of one of
its planes (float32, as the state is) and that is not the carried buffer itself,
by op, a fusion by its root's (``state_sized``). A leaf-sized
``copy`` a layer is what a window cost Nemotron's six layers before PR 49; at 36
layers of 1.2 GB it would be tens of GB a pass. One JSON line a program.
"""

from __future__ import annotations

import argparse
import collections
import json
import math
import re
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(1, str(ROOT / "benchmark"))
sys.path.insert(2, str(ROOT / "tests"))

#: ops that name a buffer without filling one
CARRIED = {"parameter", "get-tuple-element", "bitcast", "tuple", "while",
           "conditional", "call"}


def state_sized(hlo: str, size: int) -> dict:
    """The instructions with a float32 result of ``size`` elements (the state's
    dtype: a weight slice of as many int8 elements is not one), by where they
    stand: ``top`` = an instruction of a loop body or of the entry, by op (a
    ``fusion`` by its root's op, a kernel as ``custom-call``): each fills or
    rewrites a buffer that large; ``fused`` = inside a fusion, where only the
    root reaches memory."""
    comps, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            comps[comp] = []
        elif comp is not None:
            comps[comp].append(line)
    roots, fused = {}, set()
    for name, lines in comps.items():
        for line in lines:
            m = re.match(r"\s+ROOT %?[\w.\-]+ = .*? ([\w\-]+)\(", line)
            if m:
                roots[name] = m.group(1)
            for called in re.findall(r"kind=k\w+, calls=%?([\w.\-]+)", line):
                fused.add(called)
    top, inner = collections.Counter(), collections.Counter()
    for name, lines in comps.items():
        for line in lines:
            m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
            if not m or m.group(2) in CARRIED:
                continue
            dims = re.findall(r"f32\[([\d,]+)\]", m.group(1))
            if not any(math.prod(int(d) for d in x.split(",")) == size
                       for x in dims):
                continue
            op = m.group(2)
            if name in fused:
                inner[op] += 1
                continue
            if op == "fusion":
                called = re.search(r"calls=%?([\w.\-]+)", line)
                op = f"fusion:{roots.get(called.group(1)) if called else '?'}"
            top[op] += 1
    return {"top": dict(top), "fused": dict(inner)}


def report(name: str, compiled, leaf: tuple, started: float,
           dump: str = "") -> dict:
    hlo = compiled.as_text()
    if dump:
        Path(dump).mkdir(parents=True, exist_ok=True)
        (Path(dump) / f"{name}.hlo.txt").write_text(hlo)
    mem = compiled.memory_analysis()
    return {"program": name, "seconds": round(time.monotonic() - started, 1),
            "temp_GiB": round(mem.temp_size_in_bytes / 2 ** 30, 3),
            "argument_GiB": round(mem.argument_size_in_bytes / 2 ** 30, 3),
            "alias_GiB": round(mem.alias_size_in_bytes / 2 ** 30, 3),
            "kernels": hlo.count('custom_call_target="tpu_custom_call"'),
            "whiles": hlo.count(" while("),
            "leaf_sized": state_sized(hlo, math.prod(leaf)),
            "plane_sized": state_sized(hlo, math.prod(leaf[1:]))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="benchmark/configs/granite-4.0-h-micro.json")
    ap.add_argument("--layers", type=int, default=0,
                    help="source layers to compile (0: the file's own depth)")
    ap.add_argument("--programs", nargs="+",
                    default=["decode", "window", "chunk", "cow"],
                    choices=["decode", "window", "chunk", "cow"])
    ap.add_argument("--dump", default="", help="keep each program's HLO here")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import test_tpu_aot as aot
    from ai_agent_kubectl_tpu.models.transformer import KVCache, forward
    from ai_agent_kubectl_tpu.ops.quant import random_params_int8
    from ai_agent_kubectl_tpu.ops.ragged_attention import lane_heads
    from modelmap import key_map, model_config, sizes

    jax.default_backend = lambda: "tpu"     # the kernels compiled, not interpreted
    one_chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    cfg_file = json.loads((ROOT / args.config).read_text())
    sz = sizes(cfg_file)
    if args.layers:
        sz["num_hidden_layers"] = args.layers
    cfg = model_config("aot", sz, key_map(cfg_file))
    env = cfg_file["server_env"]
    B, page = int(env["DECODE_BATCH_SIZE"]), int(env["KV_POOL_PAGE"])
    n_blocks = int(env["KV_POOL_BLOCKS"])
    pages = int(env["MAX_SEQ_LEN"]) // page + 1
    W = int(env["PREFILL_BUCKETS"].split(",")[-1])
    n = lane_heads(cfg.head_dim, cfg.kv_heads_paged)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype), jax.eval_shape(
            lambda: KVCache.pool_zeros(cfg, n_blocks=n_blocks, page=page,
                                       slots=B, ring=cfg.sliding_ring(W, page),
                                       lane_heads=n)))
    state = "ssm" if cache.ssm is not None else "lin"
    leaf = getattr(cache, state).shape
    print(json.dumps({"config": cfg_file["name"], "n_layers": cfg.n_layers,
                      f"{state}_leaf": leaf, "k_leaf": cache.k.shape,
                      "lane_heads": n, "batch": B, "window": W}), flush=True)
    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))

    def step(packed):
        def fn(params, tok, pos, cache, wmask, tables, q_lens):
            return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                           attn_impl="ragged", token_mask=wmask,
                           write_mask=wmask, block_tables=tables,
                           q_lens=q_lens, logits_at=jnp.maximum(q_lens, 1) - 1,
                           packed_rows=packed)
        return fn

    for name in args.programs:
        t0 = time.monotonic()
        if name in ("decode", "window"):
            w = 1 if name == "decode" else W
            compiled = jax.jit(step(None if w == 1 else w + B),
                               donate_argnums=(3,)).lower(
                params, arg((B, w), jnp.int32), arg((B, w), jnp.int32), cache,
                arg((B, w), jnp.bool_), arg((B, pages), jnp.int32),
                arg((B,), jnp.int32)).compile()
        elif name == "chunk":
            # the engine's cache as _chunk_program makes it has no lane_heads:
            # hand it ours
            made = aot._engine_cache
            aot._engine_cache = lambda *a, **k: cache
            try:
                compiled = aot._chunk_program(None, one_chip, cfg, W, n_blocks,
                                              pages, B=B, engine_cache=True)
            finally:
                aot._engine_cache = made
        else:
            from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
            cow = BatchedJaxEngine._pool_cow_fn.fget(
                types.SimpleNamespace(kv_pool_page=page, mesh=None))
            scalar = arg((), jnp.int32)
            compiled = cow.lower(cache, scalar, scalar, scalar).compile()
        print(json.dumps(report(name, compiled, leaf, t0, args.dump)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
