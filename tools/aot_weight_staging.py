#!/usr/bin/env python3
"""What the TPU compiler does with the layer loop's stacked int8 weights, read
off the compiled program for a DESCRIBED v5e (no chip; nothing runs, so this
gives counts and bytes, never a time).

For every configuration file under ``benchmark/configs`` (or those named), the
pool+ragged ``forward`` the chunk program steps (batch and pool from the file's
``server_env``, one token a slot; ``--window W`` for a W-wide window with its
valid rows packed) is compiled as ``tests/test_tpu_aot.py`` compiles it, and
each loop body's weight traffic that is NOT a dot streaming its layer from HBM
is counted:

- ``staged``: fusions whose root is a ``dynamic-slice`` with an ``s8`` result
  in the alternate memory (``S(1)``): a layer's matrix sliced out of its stack
  into VMEM, blocking, before the dot that reads it;
- ``copies``: ``copy`` instructions with an ``s8`` result (the slice turned
  over for a dot whose result the compiler gave a head axis);
- ``bounces``: ``copy-start`` instructions over an ``s8`` array (a weight
  stack parked in the alternate memory and copied in or out beside the loop).

``--package-root DIR`` reads another checkout's program (the parent's under
``.chipwork/parent``). One JSON line a program on stdout.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def instructions(hlo: str):
    """(computation, name, result type, op, line) of every HLO instruction."""
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
        if m:
            yield comp, m.group(1), m.group(2), m.group(3), line


def _bytes(result: str) -> int:
    """Bytes of the first ``s8`` array in a result type (a ``copy-start``'s
    is a tuple that names the array twice)."""
    dims = re.search(r"s8\[([\d,]+)\]", result).group(1)
    return math.prod(int(d) for d in dims.split(","))


def weight_staging(hlo: str) -> dict:
    """The three counts of the module docstring (with the bytes each moves),
    over every computation of the compiled module: ``staged`` and ``copies``
    only exist inside a layer loop's body; ``bounces`` may sit beside it."""
    roots = {}                      # fused computation -> its root's op
    for comp, _, _, op, line in instructions(hlo):
        if re.match(r"\s+ROOT ", line):
            roots[comp] = op
    staged, copies, bounces = [], [], []
    for comp, name, result, op, line in instructions(hlo):
        if "s8[" not in result:
            continue
        if op == "fusion" and "S(1)" in result:
            called = re.search(r"calls=%?([\w.\-]+)", line)
            if called and roots.get(called.group(1)) == "dynamic-slice":
                staged.append((name, _bytes(result)))
        elif op == "copy":
            copies.append((name, _bytes(result)))
        elif op == "copy-start":
            bounces.append((name, _bytes(result)))
    return {"staged": len(staged), "staged_bytes": sum(b for _, b in staged),
            "copies": len(copies), "copy_bytes": sum(b for _, b in copies),
            "bounces": len(bounces), "bounce_bytes": sum(b for _, b in bounces),
            "s1_annotations": hlo.count("S(1)")}


def decode_program(cfg, sharding, *, batch=16, window=1, n_blocks=320,
                   page=64, pages=65, mesh=None, packed=False):
    """``forward`` over the pool through the ragged kernel at ``[batch,
    window]``, the cache donated, lowered and compiled for ``sharding``'s
    device(s); the caller has patched ``jax.default_backend`` to ``tpu``."""
    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.models import transformer as tf
    from ai_agent_kubectl_tpu.ops.quant import random_params_int8

    KVCache, forward = tf.KVCache, tf.forward

    def arg(shape, dtype, s=sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    shapes = jax.eval_shape(lambda k: random_params_int8(
        k, cfg, dtype=jnp.bfloat16, quantize_embed=True), jax.random.PRNGKey(0))
    # (of a uniform block every layer is an attention layer)
    pool = (cfg.n_of("*"), n_blocks, page, cfg.n_kv_heads, cfg.head_dim)
    if mesh is None:
        params = jax.tree_util.tree_map(lambda x: arg(x.shape, x.dtype), shapes)
        heads = sharding
    else:
        from jax.sharding import NamedSharding

        from ai_agent_kubectl_tpu.parallel.sharding import (param_shardings,
                                                            pool_cache_specs,
                                                            sanitize_spec)
        params = jax.tree_util.tree_map(
            lambda x, s: arg(x.shape, x.dtype, s), shapes,
            param_shardings(shapes, mesh, cfg))
        heads = NamedSharding(mesh, sanitize_spec(
            mesh, pool_cache_specs(cfg)["k"], pool))
    # the pool engine's cache as the engine builds it, abstract
    cache = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype), jax.eval_shape(
            lambda: KVCache.pool_zeros(
                cfg, n_blocks=n_blocks, page=page, slots=batch,
                ring=cfg.sliding_ring(512, page), dtype=jnp.bfloat16,
                counts_experts=cfg.grouped_experts)))
    if cache.k is not None:
        cache = dataclasses.replace(cache, k=arg(pool, jnp.bfloat16, heads),
                                    v=arg(pool, jnp.bfloat16, heads))

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", mesh=mesh, token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1,
                       packed_rows=(batch + window) if packed else None)

    B, W = batch, window
    return jax.jit(step, donate_argnums=(3,)).lower(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32)).compile()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("configs", nargs="*", help="configuration files "
                    "(default: every benchmark/configs/*.json)")
    ap.add_argument("--window", type=int, default=1)
    ap.add_argument("--layers", type=int, default=None,
                    help="compile this many layers, not the file's")
    ap.add_argument("--package-root", default=None)
    ap.add_argument("--dump", default=None, help="directory for the HLO text")
    args = ap.parse_args(argv)

    root = Path(args.package_root).resolve() if args.package_root else ROOT
    sys.path[:0] = [str(root), str(ROOT / "benchmark")]
    import jax
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from modelmap import key_map, mesh_of, model_config, sizes

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"     # the kernels' ``interpret`` default
    files = [Path(f) for f in args.configs] or sorted(
        (ROOT / "benchmark" / "configs").glob("*.json"))
    for f in files:
        cfg_file = json.loads(f.read_text())
        sz = sizes(cfg_file)
        if args.layers:
            sz["num_hidden_layers"] = args.layers
        cfg = model_config(cfg_file["name"], sz, key_map(cfg_file))
        env = cfg_file["server_env"]
        page = int(env.get("KV_POOL_PAGE", 64))
        mesh_axes = mesh_of(cfg_file)
        if mesh_axes:
            from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
            mesh = build_mesh(MeshConfig(**mesh_axes), topo.devices)
            sharding = NamedSharding(mesh, PartitionSpec())
        else:
            mesh, sharding = None, SingleDeviceSharding(topo.devices[0])
        batch = int(env.get("DECODE_BATCH_SIZE", 16))
        pages = int(env["MAX_SEQ_LEN"]) // page + 1
        compiled = decode_program(
            cfg, sharding, batch=batch, window=args.window,
            # the engine's default pool: every slot's whole table
            n_blocks=int(env.get("KV_POOL_BLOCKS", batch * pages)), page=page,
            pages=pages, mesh=mesh, packed=args.window > 1)
        hlo = compiled.as_text()
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            (Path(args.dump) / f"{cfg_file['name']}.w{args.window}.hlo").write_text(hlo)
        mem = compiled.memory_analysis()
        print(json.dumps({"config": cfg_file["name"], "layers": cfg.n_layers,
                          "window": args.window, "root": str(root),
                          **weight_staging(hlo),
                          "temp_MiB": round(mem.temp_size_in_bytes / 2 ** 20)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
