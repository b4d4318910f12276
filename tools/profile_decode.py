"""Decode-step attribution profiler (VERDICT r2 item 3).

Times each serving program in isolation on the current backend — the
engine-identical batched decode chunk and its ablations, the admission
prefill, the splice, sampling, the logits head, and the weight-read floor —
so step time is attributed to compute classes instead of guessed at.

Run on the bench chip:  python tools/profile_decode.py [--model gemma-2b-it]
"""

from __future__ import annotations

import argparse
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from ai_agent_kubectl_tpu.engine.sampling import sample_tokens_batched  # noqa: E402
from ai_agent_kubectl_tpu.models.config import get_config  # noqa: E402
from ai_agent_kubectl_tpu.models.transformer import (  # noqa: E402
    KVCache, forward, init_params,
)


def log(msg):
    print(msg, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gemma-2b-it")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--quant", default="",
                    choices=["", "int8", "w8a8", "int4"],
                    help="int8 weights+embedding (random_params_int8 — "
                         "how 7B-class models fit the chip); w8a8 "
                         "additionally runs layer matmuls s8xs8 on the MXU; "
                         "int4 packs projections to nibbles served by the "
                         "Pallas kernel (ops/quant4.py)")
    ap.add_argument("--kv-quant", default="", choices=["", "int8"])
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--bs-list", default="8,16,32,64",
                    help="decode batch sizes to sweep (trim for 7B HBM)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--chunks-only", action="store_true",
                    help="skip the standalone-piece timings (the chunk "
                         "sections carry the attribution)")
    args = ap.parse_args()

    cfg = get_config(args.model)
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[args.dtype]
    log(f"profile: {cfg.name} on {jax.devices()[0].platform}, "
        f"dtype={dtype.__name__} quant={args.quant or '-'} "
        f"kv_quant={args.kv_quant or '-'}")

    if args.quant in ("int8", "w8a8", "int4"):
        from ai_agent_kubectl_tpu.ops.quant import random_params_int8, to_w8a8

        params = random_params_int8(jax.random.PRNGKey(0), cfg, dtype=dtype,
                                    quantize_embed=True,
                                    int4=(args.quant == "int4"))
        if args.quant == "w8a8":
            params = to_w8a8(params)
    else:
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=dtype)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"params: {n_bytes/1e9:.2f} GB")

    # ---- weight-read floor: one pass over every param byte ----
    @jax.jit
    def read_weights(p):
        return sum(jnp.sum(x).astype(jnp.float32)
                   for x in jax.tree_util.tree_leaves(p))

    t = timeit(lambda: read_weights(params), args.reps)
    log(f"weight-read floor: {t:.2f} ms  ({n_bytes/1e9/t*1000:.0f} GB/s)")

    S_alloc = args.max_seq + args.chunk

    def make_chunk(N, kv_limit, sample: str):
        """Engine-identical decode chunk with ablations.
        sample: 'engine' (split+per-slot sampling) | 'argmax' (no RNG)."""

        def chunk(params, tok, pos, cache, key, temps, active):
            def body(carry, _):
                tok, pos, cache, key = carry
                logits, cache = forward(params, cfg, tok, pos, cache,
                                        kv_limit=kv_limit, attn_impl="dense")
                if sample == "engine":
                    key, sub = jax.random.split(key)
                    nxt = sample_tokens_batched(logits[:, 0], sub, temps)
                else:
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                nxt = jnp.where(active, nxt, tok[:, 0])
                pos = pos + active.astype(jnp.int32)[:, None]
                return (nxt[:, None], pos, cache, key), nxt

            (tok, pos, cache, key), toks = jax.lax.scan(
                body, (tok, pos, cache, key), None, length=args.chunk)
            return jnp.swapaxes(toks, 0, 1), tok, pos, cache, key

        return jax.jit(chunk, donate_argnums=(1, 2, 3))

    def run_chunk(N, kv_limit, sample="engine", reps=args.reps):
        fn = make_chunk(N, kv_limit, sample)
        tok = jnp.zeros((N, 1), jnp.int32)
        # Start positions so every timed step's KV write stays IN BOUNDS:
        # (reps+1) chunks run against an S_alloc cache, and out-of-bounds
        # scatter rows are silently dropped — which would time a step
        # without its cache-write traffic. Prefer the bench-realistic
        # mid-life position (320) when the cache is long enough.
        if S_alloc < (reps + 1) * args.chunk + 1:
            raise SystemExit(
                f"--max-seq {args.max_seq} too short for reps={reps} × "
                f"chunk={args.chunk}: timed KV writes would run out of "
                f"bounds (silently dropped scatters time a step without "
                f"its cache-write traffic). Lower --reps/--chunk or raise "
                f"--max-seq.")
        pos0 = max(0, min(320, S_alloc - (reps + 1) * args.chunk - 1))
        pos = jnp.full((N, 1), pos0, jnp.int32)
        cache = KVCache.zeros(cfg, N, S_alloc, dtype=dtype,
                              kv_quant=args.kv_quant)
        key = jax.random.PRNGKey(0)
        temps = jnp.zeros((N,), jnp.float32)
        active = jnp.ones((N,), jnp.bool_)
        toks, tok, pos, cache, key = fn(params, tok, pos, cache, key,
                                        temps, active)   # compile
        jax.block_until_ready(toks)
        t0 = time.perf_counter()
        for _ in range(reps):
            toks, tok, pos, cache, key = fn(params, tok, pos, cache, key,
                                            temps, active)
        jax.block_until_ready(toks)
        ms = (time.perf_counter() - t0) / reps
        return ms * 1000 / args.chunk  # per decode step

    bs_list = tuple(int(b) for b in args.bs_list.split(","))
    kv_mid = min(512, S_alloc)
    log(f"\n-- decode chunk: ms/step (engine-identical, kv={kv_mid}) --")
    for N in bs_list:
        per = run_chunk(N, kv_mid)
        log(f"bs={N:3d} kv={kv_mid} : {per:7.2f} ms/step = "
            f"{N/per*1000:6.0f} tok/s")

    bs_mid = bs_list[len(bs_list) // 2]
    log(f"\n-- kv-span sweep at bs={bs_mid} --")
    for kv in sorted({128, 256, kv_mid, S_alloc}):
        if kv > S_alloc:
            continue
        per = run_chunk(bs_mid, kv)
        log(f"bs={bs_mid} kv={kv:5d}: {per:7.2f} ms/step = "
            f"{bs_mid/per*1000:6.0f} tok/s")

    log(f"\n-- ablations at bs={bs_mid} kv={kv_mid} --")
    base = run_chunk(bs_mid, kv_mid, "engine")
    norng = run_chunk(bs_mid, kv_mid, "argmax")
    log(f"engine sampling : {base:7.2f} ms/step")
    log(f"argmax, no RNG  : {norng:7.2f} ms/step  (sampling+rng = {base-norng:+.2f})")

    if args.chunks_only:
        return

    # ---- standalone pieces ----
    h = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.dim), dtype)
    embed = params["embed"]

    from ai_agent_kubectl_tpu.ops.quant import tied_head

    @jax.jit
    def head(h):
        return tied_head(h, embed).astype(jnp.float32)

    t = timeit(lambda: head(h), args.reps)
    log(f"\nlogits head [32,{cfg.dim}]x[{cfg.vocab_size},{cfg.dim}]^T: {t:.2f} ms")

    logits = jax.random.normal(jax.random.PRNGKey(2), (32, cfg.vocab_size),
                               jnp.float32)
    key = jax.random.PRNGKey(3)
    temps0 = jnp.zeros((32,), jnp.float32)
    samp = jax.jit(sample_tokens_batched)
    t = timeit(lambda: samp(logits, key, temps0), args.reps)
    log(f"sample_tokens_batched greedy [32,{cfg.vocab_size}]: {t:.2f} ms")

    @jax.jit
    def split(key):
        return jax.random.split(key)

    t = timeit(lambda: split(key), args.reps)
    log(f"key split: {t:.2f} ms")

    # ---- admission prefill (prefix-hit suffix: bucket 64 @ kv 384,
    # clamped to the cache for short --max-seq geometries) ----
    pf_kv = min(384, args.max_seq)
    pf_off = max(0, min(273, args.max_seq - 65))

    def prefill(params, tokens, positions, cache, mask):
        return forward(params, cfg, tokens, positions, cache,
                       kv_limit=pf_kv, attn_impl="dense", token_mask=mask)

    if args.max_seq < 65:
        log("suffix prefill: skipped (--max-seq < 65 cannot hold the "
            "64-token bucket in bounds)")
        return
    pf = jax.jit(prefill, donate_argnums=(3,))
    tokens = jnp.zeros((1, 64), jnp.int32)
    positions = jnp.broadcast_to(pf_off + jnp.arange(64), (1, 64)).astype(jnp.int32)
    mask = jnp.ones((1, 64), jnp.float32)
    cache1 = KVCache.zeros(cfg, 1, args.max_seq, dtype=dtype,
                           kv_quant=args.kv_quant)
    logits_pf, cache1 = pf(params, tokens, positions, cache1, mask)
    jax.block_until_ready(logits_pf)
    t0 = time.perf_counter()
    for _ in range(args.reps):
        logits_pf, cache1 = pf(params, tokens, positions, cache1, mask)
    jax.block_until_ready(logits_pf)
    log(f"suffix prefill b64@kv{pf_kv} B=1: "
        f"{(time.perf_counter()-t0)/args.reps*1000:.2f} ms")

    # ---- dispatch overhead: trivial jitted op round trip ----
    @jax.jit
    def nop(x):
        return x + 1

    x = jnp.zeros((8,), jnp.float32)
    t = timeit(lambda: nop(x), 50)
    log(f"trivial dispatch+sync round trip: {t:.2f} ms")


def timeit(fn, reps):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1000


if __name__ == "__main__":
    main()
