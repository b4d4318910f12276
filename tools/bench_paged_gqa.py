"""Paged vs dense decode attention through the REAL serving path on a GQA
model (VERDICT r3 item 5's "earn its keep" bench).

An earlier chip run (not re-measured) had the paged kernel 4.7× faster
than dense *in isolation* on Llama-3-8B GQA geometry; this tool measures what actually
matters — end-to-end serving tok/s with ragged per-slot lengths — by
running the same workload through ``BatchedJaxEngine`` twice
(``DECODE_ATTN=dense`` KV-ladder vs ``DECODE_ATTN=paged``) and printing a
JSON comparison.

Geometry: Llama-3-8B (32L, 8 KV heads, head_dim 128 — the compiled paged
kernel's tileable shape), int8 weights (bf16 ~16 GB doesn't fit one v5e
chip beside the KV pool), random init (throughput is weight-value
independent). Raggedness: prompts padded to different buckets and staggered
max_tokens, so per-slot live KV spans diverge — the case the paged
kernel's per-slot page reads are built for, and the dense ladder's
max-over-batch bucket is worst at.

Each config runs in its own subprocess: freed HBM is only reliably
returned to the allocator at process exit (bench.py round-4 finding), so
tearing down the dense engine in-process would OOM the paged engine's
weight init. The parent never imports jax (a chip belongs to one process).

Usage:  python tools/bench_paged_gqa.py   (on a TPU host)
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

MODEL = os.environ.get("GQA_MODEL", "llama-3-8b-instruct")
BATCH = int(os.environ.get("GQA_BATCH", "16"))
# Live spans in this workload top out ≈ 420 tokens (bucket-256 prompt +
# 160 generated); 512 halves the KV pool vs the first attempt's 1024,
# which ran round 0 fine and then OOMed — int8-8B weights + a 2.1 GB pool
# left no headroom for allocator churn on a 16 GB chip.
MAX_SEQ = int(os.environ.get("GQA_MAX_SEQ", "512"))
PAGE = int(os.environ.get("GQA_PAGE", "128"))
ROUNDS = int(os.environ.get("GQA_ROUNDS", "3"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


async def serve_once(decode_attn: str) -> dict:
    import jax

    assert jax.devices()[0].platform == "tpu", "run on a TPU host"
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    from ai_agent_kubectl_tpu.engine.tokenizer import HFTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    cfg = get_config(MODEL)
    tok = HFTokenizer(
        Path(__file__).resolve().parent.parent / "ai_agent_kubectl_tpu"
        / "assets" / "tokenizer-k8s.json",
        cfg.bos_id, cfg.eos_ids, cfg.pad_id,
    )
    engine = BatchedJaxEngine(
        cfg,
        tokenizer=tok,
        dtype="bfloat16",
        quant="int8",
        max_seq_len=MAX_SEQ,
        prefill_buckets=(64, 128, 256, 512),
        batch_size=BATCH,
        chunk_len=16,
        decode_attn=decode_attn,
        kv_page_size=PAGE,
    )
    t0 = time.monotonic()
    await engine.start()
    log(f"[{decode_attn}] engine ready in {time.monotonic() - t0:.0f}s "
        f"(impl={engine._decode_impl}, page={engine.kv_page_size})")
    if decode_attn == "auto":
        # r5: the default must capture the paged win on GQA geometry
        # (resolve_decode_attn heuristic, VERDICT r4 weak #6).
        assert engine._decode_impl == "paged", engine._decode_impl
    else:
        assert engine._decode_impl == decode_attn

    # Ragged workload: pad some prompts toward larger buckets and stagger
    # generation lengths 32..160 so live spans diverge across slots.
    filler = "show the detailed rollout status and history for deployment "
    samples = []
    for r in range(ROUNDS):
        reqs = []
        for i in range(BATCH * 2):
            pad = filler * (i % 4)          # 0–3 fillers → varied buckets
            prompt = render_prompt(f"{pad}web-{r}-{i} in namespace team-{i % 5}")
            reqs.append((prompt, 32 + 32 * (i % 5)))
        t0 = time.monotonic()
        results = await asyncio.gather(*[
            engine.generate(p, max_tokens=m, temperature=0.0)
            for p, m in reqs
        ])
        dt = time.monotonic() - t0
        total = sum(x.completion_tokens for x in results)
        samples.append(total / dt)
        log(f"[{decode_attn}] round {r}: {total} tok in {dt:.2f}s = "
            f"{total / dt:.0f} tok/s")
    await engine.stop()
    return {"decode_attn": decode_attn,
            "tok_s_median": round(statistics.median(samples), 1),
            "samples": [round(s, 1) for s in samples]}


def run_child(decode_attn: str) -> dict:
    from bench import _run_phase

    r = _run_phase(["--impl", decode_attn], timeout=2400,
                   script=os.path.abspath(__file__))
    # _run_phase reports failures as explicit {"status": "timeout" |
    # "error"} entries (bench.py) — either shape is a failed child here.
    if r is None or "status" in r:
        raise RuntimeError(
            f"{decode_attn} child failed ({r}; see stderr above)")
    return r


def main() -> None:
    if "--impl" in sys.argv:
        impl = sys.argv[sys.argv.index("--impl") + 1]
        print(json.dumps(asyncio.run(serve_once(impl))), flush=True)
        return
    dense = run_child("dense")
    paged = run_child("paged")
    out = {
        "model": MODEL, "batch": BATCH, "max_seq": MAX_SEQ,
        "kv_page_size": PAGE, "quant": "int8",
        "dense": dense, "paged": paged,
        "paged_vs_dense": round(
            paged["tok_s_median"] / dense["tok_s_median"], 3),
    }
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
