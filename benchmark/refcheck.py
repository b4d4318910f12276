"""Does the program agree with the configuration's plain reference?

A process of its own, run to its end before the server touches the chip (the
chip belongs to one process at a time, and nothing of the comparison may stay
in device memory), outside any timed window, at the configuration's published
widths cut to ``reference_check.layers`` layers so that the float32 reference
fits beside the int8 weights. The program side is the serving path: the
model's ``forward`` through the block-paged pool and the ragged attention
kernel — one ragged prefill window over two prompts of unequal length, then
decode steps through the cache. Where the configuration's file names a mesh,
params and pool are placed over it with the program's own parallel/sharding.py
functions and ``forward`` is given the mesh, as the engine does, so the
comparison vouches for the path the server takes. The reference side is the
full forward pass of the file named by the configuration, one sequence at a
time, unsharded float32 on one device. Logits are compared, never sampled
tokens.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(1, str(ROOT))

#: widths for the rehearsal (CPU, Pallas interpreted): same code, toy sizes.
REHEARSAL_SIZES = {"hidden_size": 128, "intermediate_size": 256,
                   "num_attention_heads": 4, "num_key_value_heads": 2,
                   "head_dim": 32, "vocab_size": 512}
PAGE = 64


def load_reference(path: str):
    spec = importlib.util.spec_from_file_location("plain_reference", ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def dequantize(leaf):
    """A program weight leaf (QuantInt8 or plain) as float32."""
    import jax.numpy as jnp

    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def layer_slice(leaf, i: int):
    import jax

    return jax.tree_util.tree_map(lambda a: a[i], leaf)


def reference_weights(params, n_layers: int) -> dict:
    """Called inside the reference's jit, so that the float32 copy of a weight
    lives only as long as the compiler needs it."""
    def leaf(v, i):
        one = layer_slice(v, i)
        q = getattr(one, "q", one)
        if q.ndim == 3:     # a layer's experts [E, in, out]: one array each
            return [dequantize(layer_slice(one, e)) for e in range(q.shape[0])]
        return dequantize(one)

    return {
        "embed": dequantize(params["embed"]),
        "final_norm": dequantize(params["final_norm"]),
        "lm_head": dequantize(params["lm_head"]),
        "layers": [{k: leaf(v, i) for k, v in params["layers"].items()}
                   for i in range(n_layers)],
    }


def place_on_mesh(axes: dict, cfg, params, cache, tables):
    """Params, pool and block tables over the configuration's mesh, placed as
    the engine places them (jax_engine.py::_setup_mesh and _load,
    batcher.py's pool): the program's own mesh builder and sharding policy."""
    import jax

    from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
    from ai_agent_kubectl_tpu.parallel.sharding import (replicate, shard_params,
                                                        shard_pool_cache)

    mesh_cfg = MeshConfig(**axes)
    if mesh_cfg.n_devices > len(jax.devices()):
        raise SystemExit(f"refcheck: the mesh {axes} wants {mesh_cfg.n_devices} devices; "
                         f"{len(jax.devices())} present")
    mesh = build_mesh(mesh_cfg, jax.devices()[:mesh_cfg.n_devices])
    return (mesh, shard_params(params, mesh, cfg), shard_pool_cache(cache, mesh, cfg),
            replicate(tables, mesh))


def weights_function(ref):
    """``weights_from_program(params, n_layers)`` of the reference's module, for
    a family whose weight tree ``reference_weights`` does not fit; else that."""
    return getattr(ref, "weights_from_program", reference_weights)


def run(cfg_file: dict, sz: dict, seed: int, rehearse: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.models.transformer import KVCache, forward
    from ai_agent_kubectl_tpu.ops.quant import random_params_int8
    from modelmap import key_map, mesh_of, model_config, rehearsal_mesh

    t0 = time.monotonic()
    chk = cfg_file["reference_check"]
    sz = dict(sz, num_hidden_layers=chk["layers"])
    if rehearse:
        sz.update(REHEARSAL_SIZES)
    cfg = model_config("refcheck", sz, key_map(cfg_file))
    # the reference reads these, whole on one device, whatever the mesh
    whole_params = params = random_params_int8(jax.random.PRNGKey(seed), cfg,
                                               dtype=jnp.bfloat16, quantize_embed=True)
    jax.block_until_ready(params)
    t_init = time.monotonic()

    lens = list(chk["prompt_tokens"])
    B, W, steps = len(lens), chk["window"], chk["decode_steps"]
    rng = np.random.default_rng(seed)
    total = max(lens) + steps
    toks = rng.integers(3, min(cfg.vocab_size, 1337), size=(B, total), dtype=np.int32)
    pages = -(-(W + steps) // PAGE)
    n_blocks = B * pages
    pool = (cfg.n_layers, n_blocks, PAGE, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.bfloat16), v=jnp.zeros(pool, jnp.bfloat16),
                    lengths=jnp.zeros((n_blocks,), jnp.int32))
    tables = jnp.arange(n_blocks, dtype=jnp.int32).reshape(B, pages)
    axes, mesh = mesh_of(cfg_file), None
    if rehearse:
        axes = rehearsal_mesh(axes)
    if axes:
        mesh, params, cache, tables = place_on_mesh(axes, cfg, params, cache, tables)

    def step(params, tok, pos, cache, wmask, q_lens):
        # engine/batcher.py::ragged_forward_step_fn, with every position's
        # logits kept (the server keeps only the last valid one).
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * PAGE,
                       attn_impl="ragged", mesh=mesh, token_mask=wmask, write_mask=wmask,
                       page_size=PAGE, block_tables=tables, q_lens=q_lens)

    step = jax.jit(step)
    cols = np.arange(W)[None, :]
    q_lens = np.asarray(lens, np.int32)
    win = np.zeros((B, W), np.int32)
    for b, n in enumerate(lens):
        win[b, :n] = toks[b, :n]
    logits, cache = step(params, jnp.asarray(win), jnp.asarray(np.broadcast_to(cols, (B, W)).astype(np.int32)),
                         cache, jnp.asarray(cols < q_lens[:, None]), jnp.asarray(q_lens))
    got = [[np.asarray(logits[b, :n])] for b, n in enumerate(lens)]
    for s in range(steps):
        tok = np.stack([toks[b, n + s] for b, n in enumerate(lens)])[:, None]
        pos = (q_lens + s)[:, None].astype(np.int32)
        logits, cache = step(params, jnp.asarray(tok), jnp.asarray(pos), cache,
                             jnp.ones((B, 1), bool), jnp.ones((B,), jnp.int32))
        for b in range(B):
            got[b].append(np.asarray(logits[b, :1]))

    t_program = time.monotonic()
    ref = load_reference(cfg_file["reference"])
    weights_of = weights_function(ref)
    ref_forward = jax.jit(lambda p, t: ref.forward(sz, weights_of(p, cfg.n_layers), t))
    rule = chk.get("clear_if")      # {"aux": <name in the reference's aux>, "min": x}
    clear_errs, unclear_errs, ref_std = [], [], []
    for b, n in enumerate(lens):
        # The whole row, one compiled shape for every sequence: attention is
        # causal, so the tokens past n + steps change nothing before them.
        want, aux = ref_forward(whole_params, jnp.asarray(toks[b]))
        want = np.asarray(want)[:n + steps]
        have = np.concatenate(got[b], axis=0)
        err = np.abs(have - want).max(axis=1)       # one number a position
        clear = np.ones(n + steps, bool)
        if rule:
            clear = np.asarray(aux[rule["aux"]])[:n + steps] >= rule["min"]
        clear_errs.append(err[clear])
        unclear_errs.append(err[~clear])
        ref_std.append(float(want.std()))
    clear_errs, unclear_errs = np.concatenate(clear_errs), np.concatenate(unclear_errs)
    std = float(np.mean(ref_std))
    # Every clear position is held to the tolerance one by one. A position the
    # configuration's rule calls unclear may read far off with nothing wrong
    # (see the configuration's tolerance_why), so those are held to the same
    # tolerance as a group, by their median.
    worst = float(clear_errs.max()) if clear_errs.size else float("nan")
    rel = worst / std
    rel_unclear = float(np.median(unclear_errs)) / std if unclear_errs.size else None
    share_unclear = unclear_errs.size / max(1, unclear_errs.size + clear_errs.size)
    # the rule's threshold is fitted to the published widths; at the
    # rehearsal's the share it calls unclear means nothing
    share_max = 1.0 if rehearse else chk.get("unclear_share_max", 0.0)
    ok = bool(np.isfinite(rel) and rel <= chk["tolerance_rel"]
              and (rel_unclear is None or rel_unclear <= chk["tolerance_rel"])
              and share_unclear <= share_max)
    del params, whole_params, cache
    return {"ok": ok, "mesh": axes, "devices": mesh.size if mesh else 1,
            "max_abs_err": worst, "ref_logit_std": std, "rel_err": rel,
            "rel_err_unclear_median": rel_unclear,
            "tolerance_rel": chk["tolerance_rel"], "positions_clear": int(clear_errs.size),
            "positions_unclear": int(unclear_errs.size), "layers": cfg.n_layers,
            "widths": "rehearsal" if rehearse else "published",
            "platform": jax.devices()[0].platform,
            "seconds": round(time.monotonic() - t0, 2),
            "seconds_init_program_reference": [
                round(t_init - t0, 2), round(t_program - t_init, 2),
                round(time.monotonic() - t_program, 2)]}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import jax
    from modelmap import fold_seed, sizes

    if not args.rehearse:
        # The engine's own rule (jax_engine.py::_setup_compile_cache): the
        # directory JAX_COMPILATION_CACHE_DIR names, else .jax_cache/ in the
        # checkout.
        from ai_agent_kubectl_tpu.config import DEFAULT_COMPILE_CACHE_DIR
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    cfg_file = json.loads(Path(args.config).read_text())
    result = run(cfg_file, sizes(cfg_file), fold_seed(args.seed), rehearse=args.rehearse)
    tmp = args.out + ".tmp"
    Path(tmp).write_text(json.dumps(result))
    os.replace(tmp, args.out)


if __name__ == "__main__":
    main()
