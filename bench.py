"""Benchmark harness — one JSON line for the driver.

Measures the headline metric: aggregate decode throughput
(tokens/sec/chip) through the REAL serving path — ``render_prompt`` (system
prompt + query, exactly what /kubectl-command serves), prefix-KV cache
active, continuous-batching scheduler, tokenize → jit prefill → pipelined
jit decode chunks — plus the north-star latency clause measured on its own
terms (VERDICT r3 item 1):

- **Tokenizer is a real BPE** (in-repo asset, tools/train_tokenizer.py):
  the system prompt is 58 subword tokens, not 273 byte-tokens, so the
  prefix/suffix bucket profile and TTFT path match production token
  lengths. ``BENCH_TOKENIZER`` overrides the asset path; set it to a real
  Gemma/Llama tokenizer.json when one is available.
- **Gemma-7B phase** (the north-star model): quantized weights (bf16
  ~17 GB does not fit one chip's HBM), with a **TTFT distribution over 50
  single-stream requests** (p50/p99) plus a **device-side TTFT estimate**
  (marginal time of back-to-back prefill+sample dispatches, which strips
  the constant host→device round trip out of the figure).
  Decode is weight-read-bound, so weight bytes and batch size are the
  throughput levers: ``LADDER_7B`` tries bs=48 @ max_seq 192 with int8 KV
  first and falls back ((32, 192, int8 KV), then (16, 256) and (8, 256)
  with bf16 KV) if the KV pool + admission scratch don't fit beside the
  weights. Skipped off-TPU.
- **Gemma-2B phase** (BASELINE config 2 geometry, v5e-1): bf16 random-init,
  bs=64 — the headline tok/s/chip number (continuity with rounds 1–3).

**Each phase runs in its own subprocess**: round 4 measured that after a
7B engine is torn down in-process (del + gc + ``jax.clear_caches()``), the
next engine's weight init still hits RESOURCE_EXHAUSTED — freed HBM isn't
returned to the allocator promptly. Process exit is the only reliable
release, and it also means an OOM rung of the 7B ladder can't poison the
phases after it. The orchestrator itself never imports jax (a chip
belongs to one process; a parent holding it would starve the children).

Throughput is the MEDIAN of measured rounds (the chip shows ~2× run-to-run
variance; best-of is not an honest statistic — VERDICT r2 weak #5).

``vs_baseline`` is value / 2000 tok/s/chip — the BASELINE.json north-star
throughput target (the reference itself publishes no numbers; SURVEY.md §6).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import subprocess
import sys
import time
from typing import Optional

NORTH_STAR_TOK_S = 2000.0
TOKENIZER_ASSET = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "ai_agent_kubectl_tpu", "assets", "tokenizer-k8s.json",
)
# (batch_size, max_seq_len, kv_quant) rungs for the 7B phase, tried in
# order. Memory budget on a 16 GB v5e chip: int8 params ≈9.3 GB; Gemma-7B
# is MHA (16 KV heads × 256 head_dim ⇒ 459 KB of KV per token per slot
# bf16, 232 KB int8 — KV_QUANT=int8 is what lets bs>16 fit beside the
# weights; the bf16 bs=32 rung OOMed in round 4), and admission scratch
# adds ≤ bs × bucket × (KV bytes) in transients. max_seq 192 covers the
# ~75-token prompt + 64 generated with margin.
# bs=64 retried in round 5 after the fused int8-KV attention shrank the
# decode program: still RESOURCE_EXHAUSTED at serve time (the int8 tree
# 9.35 GB + 3 GB KV pool + admission scratch didn't leave enough HBM).
# Round 6 shrank the controllable term — admission scratch is now
# suffix-depth (kv_limit rows, not S_alloc), capped by ADMIT_SCRATCH_MB,
# and the warm thread's duplicates are serialized out (engine/batcher.py)
# — so the 64 rung leads the ladder again; 48 is the proven fallback.
LADDER_7B = ((64, 192, "int8"), (48, 192, "int8"), (32, 192, "int8"),
             (16, 256, ""), (8, 256, ""))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _steptime_summary(eng) -> Optional[dict]:
    """The engine's step-time sentinel digests (obs/steptime.py) for the
    artifact, plus a derived scalar the perf gate can band: the median
    decode-phase p50 ms/step across rungs with a meaningful sample."""
    fn = getattr(eng, "steptime_health", None)
    snap = fn() if callable(fn) else None
    if not snap or not snap.get("digests"):
        return None
    out: dict = {"digests": snap["digests"],
                 "trips_total": snap.get("trips_total", 0)}
    decode = [d["p50_ms"] for d in snap["digests"].values()
              if d.get("phase") in ("decode", "spec_verify")
              and d.get("count", 0) >= 8]
    if decode:
        out["decode_p50_ms"] = round(statistics.median(decode), 3)
    return out


def make_tokenizer(cfg):
    """Real BPE from the in-repo asset (or BENCH_TOKENIZER override);
    byte-level fallback only if the asset is missing."""
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, HFTokenizer

    path = os.environ.get("BENCH_TOKENIZER", TOKENIZER_ASSET)
    if os.path.isfile(path):
        return HFTokenizer(path, cfg.bos_id, cfg.eos_ids, cfg.pad_id), path
    log(f"bench: tokenizer asset {path} missing; falling back to bytes")
    return ByteTokenizer(), "byte-fallback"


async def throughput_phase(engine, *, conc: int, max_tokens: int,
                           rounds: int, tag: str) -> list:
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    samples = []
    for r in range(rounds):
        prompts = [
            render_prompt(f"list pods in namespace team-{tag}-{r}-{i}")
            for i in range(conc)
        ]
        t0 = time.monotonic()
        results = await asyncio.gather(*[
            engine.generate(p, max_tokens=max_tokens, temperature=0.0)
            for p in prompts
        ])
        dt = time.monotonic() - t0
        total = sum(r_.completion_tokens for r_ in results)
        hits = sum(r_.prefix_cache_hit for r_ in results)
        tok_s = total / dt
        samples.append(tok_s)
        log(f"bench[{tag}]: {total} tok across {conc} reqs in {dt:.2f}s = "
            f"{tok_s:.0f} tok/s ({hits}/{conc} prefix hits)")
    return samples


async def ttft_phase(engine, *, n: int, tag: str) -> dict:
    """Single-stream TTFT distribution through the serving path (p50/p99
    over n requests; first request discarded as residual warmup)."""
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    ttfts = []
    for i in range(n + 1):
        r = await engine.generate(
            render_prompt(f"describe deployment web-{tag}-{i}"),
            max_tokens=2, temperature=0.0,
        )
        assert r.prefix_cache_hit, "TTFT path must hit the prefix cache"
        ttfts.append(r.ttft_ms)
    ttfts = sorted(ttfts[1:])
    p50 = statistics.median(ttfts)
    p99 = ttfts[min(len(ttfts) - 1, int(round(0.99 * len(ttfts))) - 1)]
    log(f"bench[{tag}]: TTFT over {len(ttfts)} reqs: "
        f"p50={p50:.1f}ms p99={p99:.1f}ms min={ttfts[0]:.1f}ms")
    return {"ttft_p50_ms": round(p50, 2), "ttft_p99_ms": round(p99, 2),
            "ttft_min_ms": round(ttfts[0], 2), "ttft_n": len(ttfts)}


def profiled_device_ttft(engine) -> Optional[float]:
    """Trace-derived device TTFT (VERDICT r4 item 6): run ONE
    prefill+sample dispatch inside a jax.profiler trace and sum the
    device-side execution spans from the trace events — a measurement of
    the chip's actual occupancy for the first token, not an arithmetic
    inference from chained dispatches. Returns None when the platform
    exports no device events (the marginal estimate then stands alone)."""
    import glob
    import gzip
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    ids = engine.tokenizer.encode(render_prompt("get pods -o wide"))

    def once():
        logits, cache, n_prompt, hit = engine._prefill_prompt(ids, 2)
        return engine._sample_fn(
            logits, jax.random.PRNGKey(0), jnp.asarray(0.0, jnp.float32))

    once().block_until_ready()          # warm (all programs compiled)
    best = None
    for _ in range(3):
        d = tempfile.mkdtemp(prefix="ttft_trace_")
        try:
            with jax.profiler.trace(d):
                once().block_until_ready()
            # Sum the UNION of device-busy intervals, not raw durations:
            # a device pid can export hierarchical rows (modules / ops /
            # steps on different tids) whose spans overlap — a plain sum
            # would double-count the same chip time (code review r5).
            spans = []
            for p in glob.glob(d + "/plugins/profile/*/*.trace.json.gz"):
                ev = json.load(gzip.open(p)).get("traceEvents", [])
                pids = {e["pid"]: e["args"].get("name") for e in ev
                        if e.get("ph") == "M"
                        and e.get("name") == "process_name"}
                spans.extend(
                    (e["ts"], e["ts"] + e.get("dur", 0.0)) for e in ev
                    if e.get("ph") == "X"
                    and "TPU" in str(pids.get(e["pid"], "")))
            total = 0.0
            end = None
            for s, t in sorted(spans):
                if end is None or s > end:
                    total += t - s
                    end = t
                elif t > end:
                    total += t - end
                    end = t
        finally:
            shutil.rmtree(d, ignore_errors=True)
        if total > 0 and (best is None or total < best):
            best = total
    if best is None:
        log("bench: profiler exported no device events; "
            "ttft_device_profiled_ms unavailable")
        return None
    ms = best / 1000.0
    log(f"bench: device TTFT (profiler trace, sum of device spans, "
        f"best of 3) = {ms:.1f}ms")
    return round(ms, 2)


def device_ttft_phase(engine, *, reps: int = 8) -> float:
    """Device-side TTFT: splice + suffix prefill + first-token sample,
    measured as the MARGINAL cost of back-to-back dispatches. One dispatch
    pays device time + host→device round trips; K chained dispatches
    pay K × device time + the same constant
    overhead, so (T_K − T_1)/(K − 1) isolates the device span the serving
    path actually occupies the chip for (VERDICT r3 item 1c)."""
    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    ids = engine.tokenizer.encode(render_prompt("get pods -o wide"))

    def once():
        logits, cache, n_prompt, hit = engine._prefill_prompt(ids, 2)
        tok = engine._sample_fn(
            logits, jax.random.PRNGKey(0), jnp.asarray(0.0, jnp.float32))
        return tok

    once().block_until_ready()          # warm
    # Dispatch round trips are noisy; one (1-shot, chained)
    # pair can even come out negative-marginal. Take the best of several
    # trials — the marginal estimate is an upper-bound-noise measurement,
    # so min is the honest statistic for "device span".
    trials = []
    for _ in range(3):
        t0 = time.monotonic()
        once().block_until_ready()
        t1 = time.monotonic() - t0
        t0 = time.monotonic()
        toks = [once() for _ in range(reps)]
        toks[-1].block_until_ready()
        tk = time.monotonic() - t0
        trials.append((max((tk - t1) / (reps - 1), 0.0) * 1000.0,
                       t1 * 1000.0))
    dev_ms, one_shot = min(trials)
    log(f"bench: device-side TTFT ≈ {dev_ms:.1f}ms "
        f"(best of {len(trials)}; 1-shot {one_shot:.1f}ms incl. round "
        f"trips, {reps} chained)")
    return round(dev_ms, 2)


# ---------------------------------------------------------------------------
# Phases (each runs in its own subprocess; prints one JSON line on stdout)
# ---------------------------------------------------------------------------

async def phase_7b(batch_size: int, max_seq: int, kv_quant: str,
                   chunk_len: int = 16) -> dict:
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg7 = get_config("gemma-7b-it")
    tok7, _ = make_tokenizer(cfg7)
    log(f"bench: starting gemma-7b-it int8 phase (north-star model, "
        f"bs={batch_size} max_seq={max_seq} kv_quant={kv_quant or 'bf16'})")
    eng7 = BatchedJaxEngine(
        cfg7,
        tokenizer=tok7,
        dtype="bfloat16",
        quant="int8",            # bf16 (~17 GB) exceeds one chip's HBM
        kv_quant=kv_quant,
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        batch_size=batch_size,
        chunk_len=chunk_len,
    )
    t0 = time.monotonic()
    await eng7.start()
    log(f"bench: 7B engine ready in {time.monotonic() - t0:.1f}s")
    # System-prompt prefix reuse must be armed either way: the dense
    # ladder's resident PrefixKV, or the pool's radix-cached preload.
    assert eng7._prefix is not None or eng7._use_pool

    ttft7 = await ttft_phase(eng7, n=50, tag="7b")
    ttft7["ttft_device_ms"] = device_ttft_phase(eng7)
    profiled = profiled_device_ttft(eng7)
    if profiled is not None:
        ttft7["ttft_device_profiled_ms"] = profiled
    s7 = await throughput_phase(
        eng7, conc=batch_size, max_tokens=64, rounds=3, tag="7b")
    steptime = _steptime_summary(eng7)
    await eng7.stop()
    return {
        "step_time": steptime,
        "model": "gemma-7b-it",
        "dtype": "bfloat16",
        "quant": "int8",
        "kv_quant": kv_quant,
        "batch_size": batch_size,
        "max_seq_len": max_seq,
        "tokens_per_sec_per_chip": round(
            statistics.median(s7) / len(jax.devices()), 2),
        **ttft7,
    }


#: kubectl query set for the grammar sweep (ISSUE 11): the shapes the
#: service actually serves — short NL asks that decode to one command.
GRAMMAR_QUERIES = [
    "list all pods in kube-system",
    "describe the web deployment",
    "show logs for pod web-1 with the last 100 lines",
    "get services across all namespaces",
    "scale deployment web to 3 replicas",
    "show nodes with labels",
    "get the configmap app-config as yaml",
    "top pods by cpu",
    "delete the failed job importer-42",
    "get events sorted by timestamp",
    "describe service frontend in staging",
    "list persistent volume claims",
]


async def phase_grammar7b(batch_size: int, max_seq: int, kv_quant: str,
                          grammar: bool, chunk_len: int = 16) -> dict:
    """One rung of the ISSUE 11 grammar sweep: the kubectl query set
    decoded with GRAMMAR_DECODE off vs on at the bs=48 geometry,
    recording decode-steps-per-command and tok/s. The claim under test:
    most of a kubectl command is FORCED given the grammar (the
    "kubectl " head, flag completions, resource-kind tails), so the
    constrained rung should spend >=2x fewer decode steps per command —
    forced tokens ride suffix prefills, never decode steps — stacking
    multiplicatively with the pool's capacity win."""
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg7 = get_config("gemma-7b-it")
    tok7, _ = make_tokenizer(cfg7)
    log(f"bench: grammar7b rung bs={batch_size} grammar={grammar}")
    eng = BatchedJaxEngine(
        cfg7,
        tokenizer=tok7,
        dtype="bfloat16",
        quant="int8",
        kv_quant=kv_quant,
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        batch_size=batch_size,
        chunk_len=chunk_len,
        grammar_decode=grammar,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: grammar7b engine ready in {time.monotonic() - t0:.1f}s")
    prompts = [render_prompt(q) for q in GRAMMAR_QUERIES]
    n_cmds = 0
    n_tokens = 0
    t0 = time.monotonic()
    for _ in range(2):
        results = await asyncio.gather(*[
            eng.generate(p, max_tokens=48, temperature=0.0)
            for p in prompts])
        n_cmds += len(results)
        n_tokens += sum(r.completion_tokens for r in results)
    wall = time.monotonic() - t0
    stats = eng.stats()
    gh = stats.get("grammar") or {}
    await eng.stop()
    # Decode steps actually spent: masked steps when the grammar is on
    # (forced tokens ride prefills); every generated token otherwise.
    steps = gh.get("masked_steps_total", n_tokens) if grammar else n_tokens
    return {
        "model": "gemma-7b-it",
        "batch_size": batch_size,
        "kv_quant": kv_quant,
        "grammar": grammar,
        "commands": n_cmds,
        "completion_tokens": n_tokens,
        "decode_steps_per_command": round(steps / max(1, n_cmds), 2),
        "forced_tokens_total": gh.get("forced_tokens_total", 0),
        "forced_token_ratio": round(
            gh.get("forced_tokens_total", 0) / max(1, n_tokens), 4),
        "fast_forward_splices": gh.get("fast_forward_splices_total", 0),
        "tokens_per_sec_per_chip": round(
            n_tokens / wall / len(jax.devices()), 2),
    }


async def phase_spec7b(batch_size: int, max_seq: int, kv_quant: str,
                       spec: bool, spec_k: int, grammar: bool,
                       chunk_len: int = 16) -> dict:
    """One rung of the ISSUE 12 speculative-decode sweep: the kubectl
    query set decoded greedily with SPEC_DECODE off vs on over
    k ∈ {2,4,8} at the bs=48 geometry, recording tok/s AND the measured
    acceptance rate (the artifact must carry both — spec throughput is
    meaningless without the acceptance that produced it). The combined
    ``--grammar on`` rung measures the stacking with forced runs:
    forced tokens ride prefills (no drafting at all), masked sampled
    tokens draft/verify, and the two wins multiply. Checkpoints: set
    MODEL_PATH (7B) and SPEC_DRAFT_PATH (2B) for real-weight
    acceptance; random-init rungs still measure the verify-window
    mechanics honestly but accept near-nothing."""
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg7 = get_config("gemma-7b-it")
    tok7, _ = make_tokenizer(cfg7)
    log(f"bench: spec7b rung bs={batch_size} spec={spec} k={spec_k} "
        f"grammar={grammar}")
    eng = BatchedJaxEngine(
        cfg7,
        tokenizer=tok7,
        dtype="bfloat16",
        quant="int8",
        kv_quant=kv_quant,
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        batch_size=batch_size,
        chunk_len=chunk_len,
        model_path=os.environ.get("MODEL_PATH") or None,
        grammar_decode=grammar,
        spec_decode=spec,
        spec_draft_k=spec_k,
        spec_draft_model="gemma-2b-it",
        spec_draft_path=os.environ.get("SPEC_DRAFT_PATH") or None,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: spec7b engine ready in {time.monotonic() - t0:.1f}s")
    prompts = [render_prompt(q) for q in GRAMMAR_QUERIES]
    n_tokens = 0
    t0 = time.monotonic()
    for _ in range(2):
        results = await asyncio.gather(*[
            eng.generate(p, max_tokens=48, temperature=0.0)
            for p in prompts])
        n_tokens += sum(r.completion_tokens for r in results)
    wall = time.monotonic() - t0
    sh = eng.spec_health() or {}
    gh = (eng.grammar_health() or {}) if grammar else {}
    await eng.stop()
    return {
        "model": "gemma-7b-it",
        "batch_size": batch_size,
        "kv_quant": kv_quant,
        "spec": spec,
        "spec_k": spec_k,
        "grammar": grammar,
        "completion_tokens": n_tokens,
        "drafted_tokens_total": sh.get("drafted_tokens_total", 0),
        "accepted_tokens_total": sh.get("accepted_tokens_total", 0),
        "acceptance_ratio": sh.get("acceptance_ratio"),
        "forced_tokens_total": gh.get("forced_tokens_total", 0),
        "tokens_per_sec_per_chip": round(
            n_tokens / wall / len(jax.devices()), 2),
    }


async def phase_pipe7b(batch_size: int, max_seq: int, kv_quant: str,
                       pipe_depth: int, chunk_len: int = 16) -> dict:
    """One rung of the CHUNK_PIPE_DEPTH sweep (ISSUE 4): serving
    throughput at the 7B geometry with the given pipeline depth. Its own
    subprocess per rung (like every phase — torn-down engines don't
    return HBM promptly), throughput only (no TTFT distribution: the
    sweep's question is whether the serving number tracks the ~1,441
    tok/s device ceiling as the pipe deepens, and what depth 1 — the
    no-overlap baseline — loses to the fetch round trip)."""
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg7 = get_config("gemma-7b-it")
    tok7, _ = make_tokenizer(cfg7)
    log(f"bench: pipe7b rung bs={batch_size} depth={pipe_depth} "
        f"max_seq={max_seq} kv_quant={kv_quant or 'bf16'}")
    eng = BatchedJaxEngine(
        cfg7,
        tokenizer=tok7,
        dtype="bfloat16",
        quant="int8",
        kv_quant=kv_quant,
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        batch_size=batch_size,
        chunk_len=chunk_len,
        chunk_pipe_depth=pipe_depth,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: pipe7b engine ready in {time.monotonic() - t0:.1f}s")
    samples = await throughput_phase(
        eng, conc=batch_size, max_tokens=64, rounds=2,
        tag=f"pipe7b-d{pipe_depth}")
    stats = eng.stats()
    steptime = _steptime_summary(eng)
    await eng.stop()
    return {
        "model": "gemma-7b-it",
        "batch_size": batch_size,
        "max_seq_len": max_seq,
        "kv_quant": kv_quant,
        "pipe_depth": pipe_depth,
        "step_time": steptime,
        "device_termination": stats.get("device_termination", True),
        "wasted_decode_steps": stats.get("wasted_decode_steps", 0),
        "chunks_dispatched": stats.get("chunks_dispatched", 0),
        "chunks_pruned": stats.get("chunks_pruned", 0),
        "tokens_per_sec_per_chip": round(
            statistics.median(samples) / len(jax.devices()), 2),
    }


async def phase_tp7b(batch_size: int, max_seq: int, mesh: str,
                     model: str = "gemma-7b-it",
                     chunk_len: int = 8) -> dict:
    """One rung of the ISSUE 14 TP sweep: the MEASURED sharded decode
    step — pool under the mesh, f≈1 residual sharding, fused
    collectives — on whatever devices exist (the driver forces the
    8-virtual-device CPU mesh via JAX_PLATFORMS/XLA_FLAGS on a
    single-chip host; a real v5e-8 runs it on ICI). Times the
    engine-identical decode chunk directly (the attribution harness
    precedent: a step measurement needs the program, not live traffic)
    and bills its all-reduce share with obs/attribution.py, so the
    artifact carries step-time AND comm share per rung —
    ``tools/tp_projection.py --measured-json`` re-prices from exactly
    these numbers."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.obs.attribution import attribute_trace
    from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig

    want = MeshConfig.parse(mesh).n_devices
    if len(jax.devices()) < want:
        return {"skipped": f"mesh {mesh} wants {want} devices, "
                           f"have {len(jax.devices())}"}
    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = get_config(model)
    tok, _ = make_tokenizer(cfg)
    log(f"bench: tp7b rung bs={batch_size} mesh={mesh} model={model} "
        f"({'tpu' if on_tpu else 'cpu virtual mesh'})")
    eng = BatchedJaxEngine(
        cfg,
        tokenizer=tok,
        dtype="bfloat16" if on_tpu else "float32",
        quant="int8" if on_tpu else "",
        kv_quant="int8" if on_tpu else "",
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        attn_impl="dense" if not on_tpu else "auto",
        prefix_cache=False,
        mesh_shape=mesh,
        batch_size=batch_size,
        chunk_len=chunk_len,
        kv_pool=True,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: tp7b engine ready in {time.monotonic() - t0:.1f}s")
    try:
        sh = eng.sharding_health() or {}
        bucket = eng._kv_buckets[0]
        force = jnp.ones((batch_size,), jnp.bool_)
        # _tables only exists when the pool serves — a dp/pp/sp mesh
        # falls back to the dense ladder (the rung still measures it,
        # flagged by kv_pool_mesh_fallback in the artifact).
        tables_d = (eng._tables_d(eng._tables) if eng._use_pool
                    else None)

        def run(n: int):
            packed = None
            for _ in range(n):
                packed = eng._run_chunk(bucket, force, eng._no_corrupt_d,
                                        tables_d, spec=False)
            packed.block_until_ready()

        run(1)                       # settle layouts
        reps = 4
        t0 = time.monotonic()
        run(reps)
        step_ms = (time.monotonic() - t0) * 1e3 / (reps * chunk_len)

        # All-reduce share: trace 2 chunks, bill with the category
        # table (the v2 all_reduce category is the point — comm time
        # must be accounted, not lumped into "other").
        ar_ms = share = None
        try:
            with tempfile.TemporaryDirectory() as td:
                with jax.profiler.trace(td):
                    run(2)
                att = attribute_trace(td, 2 * chunk_len)
            cats = {c["name"]: c["ms_per_step"]
                    for c in att["categories"]}
            ar_ms = cats.get("all_reduce")
            if ar_ms is not None and step_ms > 0:
                share = round(ar_ms / step_ms, 4)
        except Exception as e:   # trace is best-effort per rung
            log(f"bench: tp7b attribution failed ({e}); "
                f"step time only")
        tp = max(1, want)
        return {
            "model": model,
            "mesh": mesh,
            "backend": "tpu" if on_tpu else "cpu-virtual",
            "bs": batch_size,
            "kv_bucket": bucket,
            "chunk_len": chunk_len,
            "step_ms": round(step_ms, 3),
            "tok_s_chip": round(batch_size / step_ms * 1e3 / tp, 1),
            "allreduce_ms": (round(ar_ms, 4)
                             if ar_ms is not None else None),
            "allreduce_share": share,
            "pool_sharded": sh.get("pool_sharded"),
            "residual_tp_fraction": sh.get("residual_tp_fraction"),
            "kv_pool_mesh_fallback": sh.get("kv_pool_mesh_fallback"),
        }
    finally:
        await eng.stop()


async def phase_tp_spec7b(batch_size: int, max_seq: int, mesh: str,
                          model: str = "gemma-7b-it", spec_k: int = 4,
                          chunk_len: int = 8) -> dict:
    """One rung of the ISSUE 18 Spec×TP sweep: speculative decoding
    SERVING UNDER the tensor-parallel mesh — sharded draft forwards,
    the (k+1)-window verify, and the per-position fold all running as
    one mesh program. Two measurements ride the artifact together,
    because neither is meaningful alone:

    - the spec chunk's step time, measured engine-identical like
      ``phase_tp7b`` (``spec_step_ms`` = ms per (k+1)-token verify
      window), and
    - the MEASURED acceptance ratio from a real serving burst (spec
      counters bill at consume time, so only live traffic moves them).

    ``tok_s_chip`` is the composition: verify windows/s x the tokens a
    window actually buys at the measured acceptance (1 + a*k) x bs,
    per chip — the number ``tools/tp_projection.py --acceptance``
    re-derives. On the 8-virtual-device CPU
    mesh the ratios are meaningful, absolute tok/s is not chip truth
    (same caveat as the tp_sweep); random-init draft rungs accept
    near-nothing and measure the verify-window mechanics honestly."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.obs.attribution import attribute_trace
    from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig

    want = MeshConfig.parse(mesh).n_devices
    if len(jax.devices()) < want:
        return {"skipped": f"mesh {mesh} wants {want} devices, "
                           f"have {len(jax.devices())}"}
    on_tpu = jax.devices()[0].platform == "tpu"
    cfg = get_config(model)
    # The 7B drafts with the 2B (one tokenizer family); a scaled-down
    # TP_SWEEP_MODEL drafts with itself — same-vocab requirement, and
    # the rung still measures the sharded draft/verify machinery.
    draft = "gemma-2b-it" if model == "gemma-7b-it" else model
    tok, _ = make_tokenizer(cfg)
    log(f"bench: tp_spec7b rung bs={batch_size} mesh={mesh} "
        f"model={model} draft={draft} k={spec_k} "
        f"({'tpu' if on_tpu else 'cpu virtual mesh'})")
    eng = BatchedJaxEngine(
        cfg,
        tokenizer=tok,
        dtype="bfloat16" if on_tpu else "float32",
        quant="int8" if on_tpu else "",
        kv_quant="int8" if on_tpu else "",
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        attn_impl="dense" if not on_tpu else "auto",
        prefix_cache=False,
        mesh_shape=mesh,
        batch_size=batch_size,
        chunk_len=chunk_len,
        kv_pool=True,
        spec_decode=True,
        spec_draft_k=spec_k,
        spec_draft_model=draft,
        spec_draft_path=os.environ.get("SPEC_DRAFT_PATH") or None,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: tp_spec7b engine ready in {time.monotonic() - t0:.1f}s")
    try:
        sh = eng.sharding_health() or {}
        bucket = eng._kv_buckets[0]
        force = jnp.ones((batch_size,), jnp.bool_)
        tables_d = eng._tables_d(eng._tables)
        windows = eng._spec_steps     # verify windows per spec chunk

        def run(n: int, spec: bool):
            packed = None
            for _ in range(n):
                packed = eng._run_chunk(bucket, force, eng._no_corrupt_d,
                                        tables_d, spec=spec)
            packed.block_until_ready()

        run(1, True)                  # settle layouts
        reps = 4
        t0 = time.monotonic()
        run(reps, True)
        spec_step_ms = (time.monotonic() - t0) * 1e3 / (reps * windows)
        run(1, False)
        t0 = time.monotonic()
        run(reps, False)
        plain_step_ms = ((time.monotonic() - t0) * 1e3
                         / (reps * chunk_len))

        # All-reduce share of the SPEC chunk (the draft's collectives
        # ride the same trace categories as the target's).
        ar_ms = share = None
        try:
            with tempfile.TemporaryDirectory() as td:
                with jax.profiler.trace(td):
                    run(2, True)
                att = attribute_trace(td, 2 * windows)
            cats = {c["name"]: c["ms_per_step"]
                    for c in att["categories"]}
            ar_ms = cats.get("all_reduce")
            if ar_ms is not None and spec_step_ms > 0:
                share = round(ar_ms / spec_step_ms, 4)
        except Exception as e:   # trace is best-effort per rung
            log(f"bench: tp_spec7b attribution failed ({e}); "
                f"step time only")

        # Measured acceptance needs live traffic (counters bill at
        # consume): one short greedy burst over the kubectl query set.
        prompts = [render_prompt(q) for q in GRAMMAR_QUERIES]
        await asyncio.gather(*[
            eng.generate(p, max_tokens=32, temperature=0.0)
            for p in prompts])
        sp = eng.spec_health() or {}
        a = sp.get("acceptance_ratio") or 0.0
        tp = max(1, want)
        # The composed number: windows/s x (1 + a*k) tokens bought per
        # window x bs slots, divided per chip.
        tok_s_chip = round(
            batch_size * (1e3 / spec_step_ms) * (1.0 + a * spec_k) / tp,
            1)
        steptime = _steptime_summary(eng)
        return {
            "model": model,
            "draft_model": draft,
            "mesh": mesh,
            "backend": "tpu" if on_tpu else "cpu-virtual",
            "bs": batch_size,
            "spec_k": spec_k,
            "kv_bucket": bucket,
            "chunk_len": chunk_len,
            "verify_windows_per_chunk": windows,
            "spec_step_ms": round(spec_step_ms, 3),
            "plain_step_ms": round(plain_step_ms, 3),
            "tok_s_chip": tok_s_chip,
            "acceptance_ratio": a,
            "drafted_tokens_total": sp.get("drafted_tokens_total", 0),
            "accepted_tokens_total": sp.get("accepted_tokens_total", 0),
            "allreduce_ms": (round(ar_ms, 4)
                             if ar_ms is not None else None),
            "allreduce_share": share,
            "pool_sharded": sh.get("pool_sharded"),
            "residual_tp_fraction": sh.get("residual_tp_fraction"),
            "draft_sharded": sh.get("draft_sharded"),
            "draft_kv_fallback": sh.get("draft_kv_fallback"),
            "step_time": steptime,
        }
    finally:
        await eng.stop()


async def phase_paged7b(batch_size: int, max_seq: int, kv_quant: str,
                        kv_pool: bool, pool_envelope_bs: int = 0,
                        agent_loop: bool = False,
                        chunk_len: int = 16) -> dict:
    """One rung of the ISSUE 10 kv-pool sweep: serving throughput at the
    7B geometry with the block-paged pool vs the dense KV ladder, at
    batch sizes the dense layout cannot even allocate (the acceptance
    claim: bs 48→192 on the SAME HBM budget). ``pool_envelope_bs`` pins
    the pool's block count to that many DENSE slots' worth of KV, so a
    bs=192 pool rung provably runs inside the dense bs=64 envelope.

    ``agent_loop`` instead measures the multi-turn scenario: 3-turn
    sessions re-sending their whole history each turn — with the radix
    tree, turn N+1 prefills only the unmatched suffix (incremental
    prefill), so turn-2/3 TTFT collapses vs the full-prefill baseline."""
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg7 = get_config("gemma-7b-it")
    tok7, _ = make_tokenizer(cfg7)
    # Page pinned at 64 (the floor a TPU engine raises it to anyway) so
    # the envelope block count is deterministic.
    page = 64
    pool_blocks = 0
    if kv_pool and pool_envelope_bs:
        pool_blocks = pool_envelope_bs * (-(-(max_seq + chunk_len) // page))
    log(f"bench: paged7b rung bs={batch_size} kv_pool={kv_pool} "
        f"blocks={pool_blocks or 'auto'} agent_loop={agent_loop}")
    eng = BatchedJaxEngine(
        cfg7,
        tokenizer=tok7,
        dtype="bfloat16",
        quant="int8",
        kv_quant=kv_quant,
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        batch_size=batch_size,
        chunk_len=chunk_len,
        kv_pool=kv_pool,
        kv_pool_page=page,
        kv_pool_blocks=pool_blocks,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: paged7b engine ready in {time.monotonic() - t0:.1f}s")
    out = {
        "model": "gemma-7b-it",
        "batch_size": batch_size,
        "max_seq_len": max_seq,
        "kv_quant": kv_quant,
        "kv_pool": kv_pool,
        "kv_pool_blocks": pool_blocks,
        "pool_envelope_bs": pool_envelope_bs,
    }
    if agent_loop:
        # 8 concurrent 3-turn sessions; each turn re-sends the full
        # history. Per-turn TTFT medians are the artifact: with the
        # radix tree, turn 2+ is incremental prefill.
        from ai_agent_kubectl_tpu.engine.prompts import render_prompt

        turn_ttfts: list = [[], [], []]

        async def session(i: int) -> None:
            history = render_prompt(f"describe deployment web-{i}")
            for turn in range(3):
                t0 = time.monotonic()
                first = None
                text = []
                async for piece in eng.generate_stream(
                        history, max_tokens=48, temperature=0.0):
                    if first is None:
                        first = time.monotonic() - t0
                    text.append(piece)
                turn_ttfts[turn].append((first or 0.0) * 1000.0)
                history = history + "".join(text) + f"\nand turn {turn + 2}?"

        await asyncio.gather(*[session(i) for i in range(8)])
        pool_stats = eng.stats().get("kv_pool") or {}
        radix = pool_stats.get("radix") or {}
        out.update({
            "agent_loop": True,
            "ttft_turn_ms": [round(statistics.median(t), 2)
                             for t in turn_ttfts if t],
            "radix_hit_tokens": radix.get("hit_tokens", 0),
            "radix_miss_tokens": radix.get("miss_tokens", 0),
            "cow_copies": pool_stats.get("cow_copies_total", 0),
        })
        await eng.stop()
        return out
    samples = await throughput_phase(
        eng, conc=batch_size, max_tokens=64, rounds=2,
        tag=f"paged7b-{'pool' if kv_pool else 'dense'}-bs{batch_size}")
    stats = eng.stats()
    pool_stats = stats.get("kv_pool") or {}
    await eng.stop()
    out.update({
        "tokens_per_sec_per_chip": round(
            statistics.median(samples) / len(jax.devices()), 2),
        "kv_pool_stats": pool_stats or None,
        "batch_occupancy_peak": stats.get("batch_occupancy", 0),
    })
    return out


async def phase_agent7b(batch_size: int, max_seq: int, kv_quant: str,
                        host_kv_blocks: int,
                        chunk_len: int = 16) -> dict:
    """One rung of the ISSUE 20 two-tier sweep: 8 concurrent 3-turn
    agent sessions re-sending their whole history each turn, on a pool
    sized to exactly the live slots' working set — the device tier
    CANNOT keep every session's chain cached between turns, so cold
    chains must leave it. With ``host_kv_blocks=0`` they are dropped and
    turn N pays a full re-prefill; with the host tier on they demote to
    pinned host RAM and onload back when the session returns. Per-turn
    TTFT medians are the artifact (``ttft_turn{1,2,3}_ms`` — the turn-N
    entries are the number the session SLO prices), alongside the
    demote/onload totals that prove which path served the turns."""
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg7 = get_config("gemma-7b-it")
    tok7, _ = make_tokenizer(cfg7)
    page = 64
    # Exactly the live working set (bs full-length chains): any cached
    # chain beyond the decoding slots must evict, which is the point —
    # eviction is what the host tier turns from a drop into a demote.
    pool_blocks = batch_size * (-(-(max_seq + chunk_len) // page))
    log(f"bench: agent7b rung bs={batch_size} blocks={pool_blocks} "
        f"host_kv_blocks={host_kv_blocks}")
    eng = BatchedJaxEngine(
        cfg7,
        tokenizer=tok7,
        dtype="bfloat16",
        quant="int8",
        kv_quant=kv_quant,
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        batch_size=batch_size,
        chunk_len=chunk_len,
        kv_pool=True,
        kv_pool_page=page,
        kv_pool_blocks=pool_blocks,
        host_kv_blocks=host_kv_blocks,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: agent7b engine ready in {time.monotonic() - t0:.1f}s")

    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    turn_ttfts: list = [[], [], []]

    async def session(i: int) -> None:
        history = render_prompt(f"describe deployment web-{i}")
        for turn in range(3):
            t0 = time.monotonic()
            first = None
            text = []
            async for piece in eng.generate_stream(
                    history, max_tokens=48, temperature=0.0):
                if first is None:
                    first = time.monotonic() - t0
                text.append(piece)
            turn_ttfts[turn].append((first or 0.0) * 1000.0)
            history = history + "".join(text) + f"\nand turn {turn + 2}?"

    await asyncio.gather(*[session(i) for i in range(8)])
    pool_stats = eng.stats().get("kv_pool") or {}
    radix = pool_stats.get("radix") or {}
    host = pool_stats.get("host_tier") or {}
    await eng.stop()
    out = {
        "model": "gemma-7b-it",
        "batch_size": batch_size,
        "max_seq_len": max_seq,
        "kv_quant": kv_quant,
        "kv_pool_blocks": pool_blocks,
        "host_kv_blocks": host_kv_blocks,
        "radix_hit_tokens": radix.get("hit_tokens", 0),
        "radix_miss_tokens": radix.get("miss_tokens", 0),
        "host_demoted": host.get("demoted_total", 0),
        "host_onloaded": host.get("onloaded_total", 0),
    }
    demoted = out["host_demoted"]
    if demoted:
        out["onload_hit_rate"] = round(out["host_onloaded"] / demoted, 4)
    for turn, samples in enumerate(turn_ttfts, start=1):
        if samples:
            out[f"ttft_turn{turn}_ms"] = round(
                statistics.median(samples), 2)
    return out


async def phase_ragged7b(batch_size: int, max_seq: int, kv_quant: str,
                         spec_k: int = 4, chunk_len: int = 16) -> dict:
    """One rung of the ISSUE 19 ragged-kernel sweep: a MIXED workload —
    staggered admissions arriving while earlier requests decode, spec
    verify riding the same chunks — served by the single ragged paged
    kernel. The artifact carries tok/s AND the compiled-program count
    (chunk + prefill + ragged sets): one kernel serves prefill, decode
    and verify from one program set. The workload staggers
    three admission waves (full bs, then bs/2 twice, offset by a
    quarter of the decode span) so ragged rungs actually exercise
    mixed prefill+decode+verify chunks rather than one clean burst."""
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg7 = get_config("gemma-7b-it")
    tok7, _ = make_tokenizer(cfg7)
    log(f"bench: ragged7b rung bs={batch_size} k={spec_k}")
    eng = BatchedJaxEngine(
        cfg7,
        tokenizer=tok7,
        dtype="bfloat16",
        quant="int8",
        kv_quant=kv_quant,
        max_seq_len=max_seq,
        prefill_buckets=(64, 128),
        batch_size=batch_size,
        chunk_len=chunk_len,
        kv_pool=True,
        model_path=os.environ.get("MODEL_PATH") or None,
        spec_decode=True,
        spec_draft_k=spec_k,
        spec_draft_model="gemma-2b-it",
        spec_draft_path=os.environ.get("SPEC_DRAFT_PATH") or None,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: ragged7b engine ready in {time.monotonic() - t0:.1f}s")
    programs = (len(getattr(eng, "_batch_chunk_fns", {}) or {})
                + len(getattr(eng, "_spec_chunk_fns", {}) or {})
                + len(getattr(eng, "_ragged_chunk_fns", {}) or {})
                + len(getattr(eng, "_pool_prefill_fns", {}) or {}))
    queries = [render_prompt(q) for q in GRAMMAR_QUERIES]

    async def wave(n: int, delay: float, tag: int) -> list:
        await asyncio.sleep(delay)
        return await asyncio.gather(*[
            eng.generate(queries[(tag + i) % len(queries)],
                         max_tokens=48, temperature=0.0)
            for i in range(n)])

    n_tokens = 0
    t0 = time.monotonic()
    for _ in range(2):
        # Staggered waves: the half-size waves land mid-decode, so the
        # ragged rung's admissions ride chunks that are also decoding
        # and verifying — the mixed-chunk case the kernel exists for.
        waves = await asyncio.gather(
            wave(batch_size, 0.0, 0),
            wave(batch_size // 2, 0.4, 1),
            wave(batch_size // 2, 0.8, 2))
        n_tokens += sum(r.completion_tokens
                        for w in waves for r in w)
    wall = time.monotonic() - t0
    stats = eng.stats()
    pool_stats = stats.get("kv_pool") or {}
    sh = eng.spec_health() or {}
    steptime = _steptime_summary(eng)
    await eng.stop()
    return {
        "model": "gemma-7b-it",
        "batch_size": batch_size,
        "max_seq_len": max_seq,
        "kv_quant": kv_quant,
        "spec_k": spec_k,
        "attention_regime": pool_stats.get("attention_regime"),
        "compiled_programs": programs,
        "completion_tokens": n_tokens,
        "acceptance_ratio": sh.get("acceptance_ratio"),
        "step_time": steptime,
        "tokens_per_sec_per_chip": round(
            n_tokens / wall / len(jax.devices()), 2),
    }


def phase_attr7b(batch_size: int, max_seq: int, kv_quant: str) -> dict:
    """Decode-step cost attribution for the 7B geometry that just served
    (VERDICT r5 weak #1): the engine-identical donated chunk under
    jax.profiler.trace, billed to op categories by the named-scope
    annotations (obs/attribution.py). Its own subprocess like every other
    phase — the trace capture and the chunk cache must not share HBM with
    a live serving engine."""
    import jax

    from ai_agent_kubectl_tpu.obs.attribution import (
        render_markdown, run_attribution, validate_attribution)

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}
    out = run_attribution(
        model="gemma-7b-it", quant="int8", kv_quant=kv_quant,
        batch_size=batch_size, chunk_len=16, max_seq=max_seq, reps=6)
    validate_attribution(out)
    log("bench[attr7b]: per-op-category decode-step attribution "
        f"(coverage {out['coverage_pct']:.1f}%):\n" + render_markdown(out))
    return out


async def phase_moe() -> dict:
    """Scaled Mixtral-geometry MoE serving through the REAL expert-
    parallel dispatch (MOE_IMPL=ep — GShard two-all_to_all program on a
    1-device expert mesh, degenerate collectives) with int8 expert
    weights (VERDICT r4 item 3). Same arch knobs as Mixtral-8x7B
    (8 experts, top-2 router, GQA 4:1, SiLU-GLU), dims scaled to fit one
    16 GB chip; feeds BASELINE row 4."""
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    if jax.devices()[0].platform != "tpu":
        return {"skipped": "not on TPU"}

    cfg = get_config(
        "mixtral-8x7b-instruct",
        dim=1024, n_layers=12, n_heads=16, n_kv_heads=4, head_dim=64,
        mlp_hidden=3584,
    )
    tok, _ = make_tokenizer(cfg)
    log("bench: starting scaled-Mixtral MoE phase (EP dispatch, int8 "
        "experts, ~0.9B params)")
    eng = BatchedJaxEngine(
        cfg,
        tokenizer=tok,
        dtype="bfloat16",
        quant="int8",            # includes the rank-4 expert stacks (r5)
        moe_impl="ep",           # the dispatch program, not dense eval
        max_seq_len=256,
        prefill_buckets=(64, 128),
        batch_size=32,
        chunk_len=16,
    )
    t0 = time.monotonic()
    await eng.start()
    log(f"bench: MoE engine ready in {time.monotonic() - t0:.1f}s "
        f"(mesh={dict(eng.mesh.shape) if eng.mesh else None})")
    assert eng.mesh is not None and "expert" in eng.mesh.axis_names
    samples = await throughput_phase(
        eng, conc=32, max_tokens=64, rounds=3, tag="moe")
    await eng.stop()
    return {
        "model": "mixtral-8x7b-geometry-scaled(dim=1024,L=12)",
        "quant": "int8 (incl. experts)",
        "moe_impl": "ep",
        "batch_size": 32,
        "tokens_per_sec_per_chip": round(
            statistics.median(samples) / len(jax.devices()), 2),
    }


async def phase_2b() -> dict:
    import jax

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    platform = jax.devices()[0].platform
    n_chips = len(jax.devices())
    on_tpu = platform == "tpu"

    if on_tpu:
        model_name, dtype, max_tokens = "gemma-2b-it", "bfloat16", 64
        batch_size, conc, rounds = 64, 64, 5
    else:
        model_name, dtype, max_tokens = "toy-8m", "float32", 32
        batch_size, conc, rounds = 4, 4, 3
    cfg = get_config(model_name)
    tokenizer, tok_path = (make_tokenizer(cfg) if on_tpu
                           else (ByteTokenizer(), "byte-fallback"))
    log(f"bench: platform={platform} chips={n_chips} model={model_name} "
        f"bs={batch_size} tokenizer={os.path.basename(str(tok_path))}")

    engine = BatchedJaxEngine(
        cfg,
        tokenizer=tokenizer,
        dtype=dtype,
        max_seq_len=1024,
        prefill_buckets=(64, 128, 256, 512),
        batch_size=batch_size,
        chunk_len=16,
    )
    t0 = time.monotonic()
    await engine.start()
    log(f"bench: engine ready in {time.monotonic() - t0:.1f}s")

    # The round-2 bench disabled the prefix cache and skipped the system
    # prompt entirely; this bench serves the true /kubectl-command path
    # and refuses to report numbers if the cache silently no-ops. Prefix
    # reuse is either the dense ladder's resident PrefixKV or the pool's
    # radix-cached preload (same rule the 7B phase already applies — the
    # pool is the default layout since PR 9, where _prefix stays None).
    assert engine._prefix is not None or engine._use_pool, \
        "prefix reuse must be active for the real serving path"
    if engine._prefix is not None:
        prefix_tokens = engine._prefix.n
    else:
        from ai_agent_kubectl_tpu.engine.prompts import SYSTEM_PROMPT
        prefix_tokens = len(engine.tokenizer.encode(SYSTEM_PROMPT))
    log(f"bench: prefix reuse ACTIVE ({prefix_tokens} tokens resident)")

    warm = await ttft_phase(engine, n=20, tag="2b-warm")
    samples = await throughput_phase(
        engine, conc=conc, max_tokens=max_tokens, rounds=rounds, tag="2b")
    tok_s_chip = statistics.median(samples) / n_chips
    steptime = _steptime_summary(engine)
    await engine.stop()

    return {
        "step_time": steptime,
        "platform": platform,
        "chips": n_chips,
        "model": model_name,
        "dtype": dtype,
        "batch_size": batch_size,
        "concurrency": conc,
        "rounds": rounds,
        "statistic": "median",
        "prefix_cache_active": True,
        "prefix_tokens": prefix_tokens,
        "tokenizer": os.path.basename(str(tok_path)),
        "tokens_per_sec_per_chip": round(tok_s_chip, 2),
        "single_stream_ttft_ms": warm["ttft_p50_ms"],
        "single_stream_ttft_p99_ms": warm["ttft_p99_ms"],
    }


# ---------------------------------------------------------------------------
# Orchestrator (no jax import here — a chip belongs to one process)
# ---------------------------------------------------------------------------

def _run_phase(args: list, timeout: float, script: str | None = None,
               env: dict | None = None) -> dict | None:
    """Run one phase subprocess; parse its final stdout line as JSON.

    ``script`` runs another file through the same hardened
    spawn-and-parse path. Failures return an EXPLICIT
    ``{"status": "timeout" | "error"}`` entry instead of None, and the
    orchestrator records those entries into the artifact — the perf
    gate (tools/perf_gate.py) must be able to tell "this phase got
    slower" from "this phase silently vanished". ``env`` overrides the
    child environment (the tp7b rungs force the 8-virtual-device CPU
    mesh)."""
    cmd = [sys.executable, script or os.path.abspath(__file__)] + args
    log(f"bench: spawn {' '.join(args)}")
    try:
        proc = subprocess.run(
            cmd, stdout=subprocess.PIPE, stderr=sys.stderr, timeout=timeout,
            env=env)
    except subprocess.TimeoutExpired:
        log(f"bench: phase {args} timed out after {timeout:.0f}s")
        return {"status": "timeout", "phase": list(args),
                "timeout_secs": timeout}
    if proc.returncode != 0:
        log(f"bench: phase {args} exited {proc.returncode}")
        return {"status": "error", "phase": list(args),
                "returncode": proc.returncode}
    lines = [ln for ln in proc.stdout.decode().splitlines() if ln.strip()]
    if not lines:
        return {"status": "error", "phase": list(args),
                "detail": "no stdout"}
    try:
        return json.loads(lines[-1])
    except json.JSONDecodeError:
        log(f"bench: phase {args} emitted non-JSON: {lines[-1]!r}")
        return {"status": "error", "phase": list(args),
                "detail": "non-JSON stdout"}


def _ok(r: dict | None) -> bool:
    """A phase result usable as data: present, not skipped-off-TPU, not
    an explicit failure entry."""
    return (isinstance(r, dict) and "skipped" not in r
            and "status" not in r)


def orchestrate() -> dict:
    # Phase failures are RECORDED, not silently dropped: the perf gate
    # must distinguish "this phase got slower" from "this phase timed
    # out / crashed / vanished" (tools/perf_gate.py).
    phase_failures: dict = {}

    # North-star model first (cleanest statement of the 7B numbers); each
    # rung is a fresh process so an OOM can't leak into later phases.
    extra7 = None
    for bs, max_seq, kvq in LADDER_7B:
        r = _run_phase(
            ["--phase", "7b", "--bs", str(bs), "--max-seq", str(max_seq),
             "--kv-quant", kvq],
            timeout=2400)
        if isinstance(r, dict) and "skipped" in r:
            log(f"bench: 7B phase skipped ({r['skipped']})")
            break
        if _ok(r):
            extra7 = r
            break
        phase_failures[f"7b_bs{bs}"] = r
        log(f"bench: 7B rung bs={bs} failed; trying next")

    if extra7 is not None:
        # Attribute the step at the geometry that served (same bs/max_seq/
        # kv_quant); a failed attribution must not cost the 7B numbers —
        # but its explicit failure entry rides the artifact.
        rattr = _run_phase(
            ["--phase", "attr7b", "--bs", str(extra7["batch_size"]),
             "--max-seq", str(extra7["max_seq_len"]),
             "--kv-quant", extra7["kv_quant"]],
            timeout=1200)
        if _ok(rattr) or (isinstance(rattr, dict) and "status" in rattr):
            extra7["step_attribution"] = rattr

        # CHUNK_PIPE_DEPTH sweep at the bs=64/48 rungs (ISSUE 4): one
        # subprocess per (bs, depth) — how far the serving number moves
        # toward the ~1,441 tok/s device ceiling as the pipe deepens on
        # top of device-side termination. The rung that just served
        # sweeps first; 48 (the proven fallback geometry) rides along
        # when a different rung won. A failed rung is logged and skipped
        # — the sweep is an artifact, never a gate on the 7B numbers.
        sweep = {}
        rungs = [extra7["batch_size"]]
        if 48 not in rungs:
            rungs.append(48)
        for bs in rungs:
            for depth in (1, 2, 3, 4):
                rp = _run_phase(
                    ["--phase", "pipe7b", "--bs", str(bs),
                     "--max-seq", str(extra7["max_seq_len"]),
                     "--kv-quant", extra7["kv_quant"],
                     "--pipe-depth", str(depth)],
                    timeout=1800)
                if isinstance(rp, dict) and "skipped" in rp:
                    log(f"bench: pipe7b bs={bs} depth={depth} "
                        f"skipped; continuing sweep")
                    continue
                if not _ok(rp):
                    # Explicit failure entry — "this rung timed out"
                    # must not read as "this rung was never tried".
                    sweep[f"bs{bs}_depth{depth}"] = rp
                    continue
                sweep[f"bs{bs}_depth{depth}"] = {
                    k: rp.get(k) for k in ("tokens_per_sec_per_chip",
                                           "wasted_decode_steps",
                                           "chunks_pruned",
                                           "step_time")
                }
        if sweep:
            extra7["pipe_depth_sweep"] = sweep

        # Block-paged KV pool sweep (ISSUE 10): bs 48→192 on the pool
        # (block count pinned to the DENSE bs=64 envelope so the rungs
        # provably share one HBM budget) vs the dense ladder (expected
        # to stop allocating past its bs=64 rung — a failed dense rung
        # is the datapoint, not an error), plus the 3-turn agent-loop
        # phase measuring incremental-prefill TTFT vs full prefill.
        kv_sweep: dict = {"pool": {}, "dense": {}}
        for bs in (48, 64, 96, 128, 192):
            rp = _run_phase(
                ["--phase", "paged7b", "--bs", str(bs),
                 "--max-seq", str(extra7["max_seq_len"]),
                 "--kv-quant", extra7["kv_quant"],
                 "--kv-pool", "on", "--pool-envelope-bs", "64"],
                timeout=1800)
            if _ok(rp):
                kv_sweep["pool"][f"bs{bs}"] = {
                    k: rp.get(k) for k in ("tokens_per_sec_per_chip",
                                           "kv_pool_blocks",
                                           "kv_pool_stats")}
            elif isinstance(rp, dict) and "status" in rp:
                kv_sweep["pool"][f"bs{bs}"] = rp
            if bs <= 96:
                rd = _run_phase(
                    ["--phase", "paged7b", "--bs", str(bs),
                     "--max-seq", str(extra7["max_seq_len"]),
                     "--kv-quant", extra7["kv_quant"],
                     "--kv-pool", "off"],
                    timeout=1800)
                if _ok(rd):
                    kv_sweep["dense"][f"bs{bs}"] = {
                        "tokens_per_sec_per_chip":
                        rd.get("tokens_per_sec_per_chip")}
                elif isinstance(rd, dict) and "status" in rd:
                    # The datapoint, recorded explicitly: the dense
                    # ladder stopped allocating/starting at this rung.
                    kv_sweep["dense"][f"bs{bs}"] = rd
        ragent = _run_phase(
            ["--phase", "paged7b", "--bs", "8",
             "--max-seq", str(extra7["max_seq_len"]),
             "--kv-quant", extra7["kv_quant"],
             "--kv-pool", "on", "--agent-loop"],
            timeout=1800)
        if _ok(ragent) or (isinstance(ragent, dict)
                           and "status" in ragent):
            kv_sweep["agent_loop"] = ragent
        ragent_dense = _run_phase(
            ["--phase", "paged7b", "--bs", "8",
             "--max-seq", str(extra7["max_seq_len"]),
             "--kv-quant", extra7["kv_quant"],
             "--kv-pool", "off", "--agent-loop"],
            timeout=1800)
        if _ok(ragent_dense) or (isinstance(ragent_dense, dict)
                                 and "status" in ragent_dense):
            kv_sweep["agent_loop_dense"] = ragent_dense
        if kv_sweep["pool"] or kv_sweep["dense"]:
            extra7["kv_pool_sweep"] = kv_sweep

        # Two-tier host offload sweep (ISSUE 20): the 8x3-turn agent
        # loop on a pool sized to force eviction, host tier off (cold
        # chains drop, returning turns re-prefill) vs on (chains demote
        # to host RAM and onload back). Turn-N TTFT is the headline —
        # the number the session SLO prices.
        agent_keys = ("ttft_turn1_ms", "ttft_turn2_ms", "ttft_turn3_ms",
                      "host_demoted", "host_onloaded", "onload_hit_rate",
                      "radix_hit_tokens", "kv_pool_blocks",
                      "host_kv_blocks")
        agent_sweep: dict = {}
        for mode, blocks in (("host_off", 0), ("host_on", 2048)):
            ra = _run_phase(
                ["--phase", "agent7b", "--bs", "8",
                 "--max-seq", str(extra7["max_seq_len"]),
                 "--kv-quant", extra7["kv_quant"],
                 "--host-kv-blocks", str(blocks)],
                timeout=1800)
            if _ok(ra):
                agent_sweep[mode] = {k: ra.get(k) for k in agent_keys}
            elif isinstance(ra, dict) and "status" in ra:
                agent_sweep[mode] = ra
        if agent_sweep:
            extra7["agent_sweep"] = agent_sweep

        # Grammar-constrained decode sweep (ISSUE 11): the kubectl
        # query set with the grammar off vs on at the bs=48 rung —
        # decode-steps-per-command is the headline (forced runs ride
        # prefills, so the constrained rung should halve it or better).
        gram_sweep: dict = {}
        for mode in ("off", "on"):
            rg = _run_phase(
                ["--phase", "grammar7b", "--bs", "48",
                 "--max-seq", str(extra7["max_seq_len"]),
                 "--kv-quant", extra7["kv_quant"],
                 "--grammar", mode],
                timeout=1800)
            if _ok(rg):
                gram_sweep[mode] = {
                    k: rg.get(k) for k in (
                        "decode_steps_per_command", "forced_token_ratio",
                        "fast_forward_splices", "tokens_per_sec_per_chip",
                        "completion_tokens")}
            elif isinstance(rg, dict) and "status" in rg:
                gram_sweep[mode] = rg
        if gram_sweep:
            extra7["grammar_sweep"] = gram_sweep

        # Speculative-decode sweep (ISSUE 12): off rung + on rungs over
        # k ∈ {2,4,8} at bs=48 (tok/s must be read against the measured
        # acceptance rate riding the same artifact), plus the grammar+
        # spec combined rung measuring the forced-run stacking.
        spec_sweep: dict = {}
        spec_keys = ("tokens_per_sec_per_chip", "acceptance_ratio",
                     "drafted_tokens_total", "accepted_tokens_total",
                     "completion_tokens", "forced_tokens_total")
        rs = _run_phase(
            ["--phase", "spec7b", "--bs", "48",
             "--max-seq", str(extra7["max_seq_len"]),
             "--kv-quant", extra7["kv_quant"], "--spec", "off"],
            timeout=1800)
        if _ok(rs):
            spec_sweep["off"] = {k: rs.get(k) for k in spec_keys}
        elif isinstance(rs, dict) and "status" in rs:
            spec_sweep["off"] = rs
        for k in (2, 4, 8):
            rs = _run_phase(
                ["--phase", "spec7b", "--bs", "48",
                 "--max-seq", str(extra7["max_seq_len"]),
                 "--kv-quant", extra7["kv_quant"],
                 "--spec", "on", "--spec-k", str(k)],
                timeout=1800)
            if _ok(rs):
                spec_sweep[f"k{k}"] = {kk: rs.get(kk)
                                       for kk in spec_keys}
            elif isinstance(rs, dict) and "status" in rs:
                spec_sweep[f"k{k}"] = rs
        rs = _run_phase(
            ["--phase", "spec7b", "--bs", "48",
             "--max-seq", str(extra7["max_seq_len"]),
             "--kv-quant", extra7["kv_quant"],
             "--spec", "on", "--spec-k", "4", "--grammar", "on"],
            timeout=1800)
        if _ok(rs):
            spec_sweep["k4_grammar"] = {k: rs.get(k) for k in spec_keys}
        elif isinstance(rs, dict) and "status" in rs:
            spec_sweep["k4_grammar"] = rs
        if spec_sweep:
            extra7["spec_sweep"] = spec_sweep

        # Ragged-kernel sweep (ISSUE 19): the mixed workload (staggered
        # admissions + spec verify in the same chunks) under the single
        # ragged paged kernel, at bs 48 and 192 (the pool geometry the
        # kernel is supposed to carry).
        # Keyed per bs like tp_spec_sweep so the perf gate's
        # dict walk reaches each rung's tok/s and program count; a
        # failed rung rides its key as an explicit {"status": ...}.
        ragged_sweep: dict = {}
        ragged_keys = ("tokens_per_sec_per_chip", "compiled_programs",
                       "attention_regime", "acceptance_ratio",
                       "completion_tokens", "step_time")
        for bs in (48, 192):
            rr = _run_phase(
                ["--phase", "ragged7b", "--bs", str(bs),
                 "--max-seq", str(extra7["max_seq_len"]),
                 "--kv-quant", extra7["kv_quant"]],
                timeout=1800)
            if isinstance(rr, dict) and "skipped" in rr:
                log(f"bench: ragged7b bs={bs} skipped ({rr['skipped']})")
                continue
            key = f"bs{bs}_ragged"
            if _ok(rr):
                ragged_sweep[key] = {k: rr.get(k) for k in ragged_keys}
            elif isinstance(rr, dict) and "status" in rr:
                ragged_sweep[key] = rr
                log(f"bench: ragged7b bs={bs} failed; continuing")
        if ragged_sweep:
            extra7["ragged_sweep"] = ragged_sweep

        # TP sweep (ISSUE 14): the MEASURED sharded step at bs 48/96/192
        # on the 8-virtual-device CPU mesh (a single-chip bench host has
        # no 8-way ICI; the virtual mesh measures the real programs —
        # collectives, pool sharding, f≈1 layout — with CPU arithmetic
        # under them, so step-time RATIOS and the all-reduce share are
        # meaningful, absolute tok/s is not chip truth). A v5e-8 host
        # runs the same rungs on ICI and its numbers ARE chip truth.
        # `tools/tp_projection.py --measured-json` re-prices from this
        # artifact. TP_SWEEP_MODEL scales the model down (the 7B's f32
        # host footprint may not fit small bench hosts).
        tp_model = os.environ.get("TP_SWEEP_MODEL", "gemma-7b-it")
        tp_env = dict(os.environ)
        if os.environ.get("TP_SWEEP_ON_DEVICE", "") != "1":
            tp_env["JAX_PLATFORMS"] = "cpu"
            tp_env["XLA_FLAGS"] = (
                tp_env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
        tp_rungs = []
        for bs in (48, 96, 192):
            rt = _run_phase(
                ["--phase", "tp7b", "--bs", str(bs), "--mesh", "tp=8",
                 "--max-seq", "256", "--model", tp_model],
                timeout=3600, env=tp_env)
            if isinstance(rt, dict) and "skipped" in rt:
                log(f"bench: tp7b rung bs={bs} skipped ({rt['skipped']})")
                continue
            # Failure entries ride the rung list explicitly.
            tp_rungs.append(rt)
            if not _ok(rt):
                log(f"bench: tp7b rung bs={bs} failed; continuing")
        if tp_rungs:
            extra7["tp_sweep"] = {"mesh": "tp=8", "model": tp_model,
                                  "rungs": tp_rungs}

        # Spec×TP sweep (ISSUE 18): speculative decoding SERVING UNDER
        # the tp=8 mesh at bs ∈ {48, 192} — spec-chunk step time +
        # MEASURED acceptance composed into one tok_s_chip per rung.
        # Keyed per-bs (not a rung list) so the perf gate's dict walk
        # reaches each rung's metrics; a failed rung rides its key as
        # an explicit {"status": ...} entry and gates as
        # timed_out/errored instead of silently vanishing.
        tp_spec_sweep: dict = {}
        for bs in (48, 192):
            rt = _run_phase(
                ["--phase", "tp_spec7b", "--bs", str(bs),
                 "--mesh", "tp=8", "--max-seq", "256",
                 "--model", tp_model, "--spec-k", "4"],
                timeout=3600, env=tp_env)
            if isinstance(rt, dict) and "skipped" in rt:
                log(f"bench: tp_spec7b rung bs={bs} skipped "
                    f"({rt['skipped']})")
                continue
            tp_spec_sweep[f"bs{bs}"] = rt
            if not _ok(rt):
                log(f"bench: tp_spec7b rung bs={bs} failed; continuing")
        if tp_spec_sweep:
            tp_spec_sweep["mesh"] = "tp=8"
            tp_spec_sweep["model"] = tp_model
            extra7["tp_spec_sweep"] = tp_spec_sweep

    rmoe = _run_phase(["--phase", "moe"], timeout=2400)

    r2 = _run_phase(["--phase", "2b"], timeout=2400)
    if not _ok(r2):
        raise RuntimeError(f"headline (2B/toy) bench phase failed: {r2}")

    tok_s_chip = r2.pop("tokens_per_sec_per_chip")
    extra = dict(r2)
    if _ok(rmoe):
        extra["mixtral_scaled_moe"] = rmoe
    elif isinstance(rmoe, dict) and "status" in rmoe:
        phase_failures["moe"] = rmoe
    if phase_failures:
        extra["phase_failures"] = phase_failures
    if extra7 is not None:
        extra["gemma_7b"] = extra7
        # Mirror the north-star latency clause at the top level, explicitly
        # tagged with the model it was measured on.
        extra["ttft_model"] = "gemma-7b-it"
        for k in ("ttft_p50_ms", "ttft_p99_ms", "ttft_device_ms"):
            extra[k] = extra7[k]

    return {
        "metric": "aggregate_decode_tokens_per_sec_per_chip",
        "value": tok_s_chip,
        "unit": "tok/s/chip",
        "vs_baseline": round(tok_s_chip / NORTH_STAR_TOK_S, 4),
        "extra": extra,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--phase", choices=["7b", "2b", "moe", "attr7b",
                                        "pipe7b", "paged7b", "agent7b",
                                        "grammar7b", "spec7b", "tp7b",
                                        "tp_spec7b", "ragged7b"],
                    default=None)
    ap.add_argument("--bs", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=256)
    ap.add_argument("--kv-quant", default="")
    ap.add_argument("--chunk-len", type=int, default=16)
    ap.add_argument("--pipe-depth", type=int, default=3)
    ap.add_argument("--kv-pool", choices=["on", "off"], default="on")
    ap.add_argument("--pool-envelope-bs", type=int, default=0)
    ap.add_argument("--agent-loop", action="store_true")
    ap.add_argument("--host-kv-blocks", type=int, default=0)
    ap.add_argument("--grammar", choices=["on", "off"], default="off")
    ap.add_argument("--spec", choices=["on", "off"], default="off")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--mesh", default="tp=8")
    ap.add_argument("--model", default="gemma-7b-it")
    ns = ap.parse_args()

    if ns.phase == "7b":
        result = asyncio.run(
            phase_7b(ns.bs, ns.max_seq, ns.kv_quant, ns.chunk_len))
    elif ns.phase == "paged7b":
        result = asyncio.run(
            phase_paged7b(ns.bs, ns.max_seq, ns.kv_quant,
                          ns.kv_pool == "on", ns.pool_envelope_bs,
                          ns.agent_loop, ns.chunk_len))
    elif ns.phase == "agent7b":
        result = asyncio.run(
            phase_agent7b(ns.bs, ns.max_seq, ns.kv_quant,
                          ns.host_kv_blocks, ns.chunk_len))
    elif ns.phase == "pipe7b":
        result = asyncio.run(
            phase_pipe7b(ns.bs, ns.max_seq, ns.kv_quant, ns.pipe_depth,
                         ns.chunk_len))
    elif ns.phase == "grammar7b":
        result = asyncio.run(
            phase_grammar7b(ns.bs, ns.max_seq, ns.kv_quant,
                            ns.grammar == "on", ns.chunk_len))
    elif ns.phase == "spec7b":
        result = asyncio.run(
            phase_spec7b(ns.bs, ns.max_seq, ns.kv_quant,
                         ns.spec == "on", ns.spec_k,
                         ns.grammar == "on", ns.chunk_len))
    elif ns.phase == "tp7b":
        result = asyncio.run(
            phase_tp7b(ns.bs, ns.max_seq, ns.mesh, ns.model,
                       ns.chunk_len))
    elif ns.phase == "tp_spec7b":
        result = asyncio.run(
            phase_tp_spec7b(ns.bs, ns.max_seq, ns.mesh, ns.model,
                            ns.spec_k, ns.chunk_len))
    elif ns.phase == "ragged7b":
        result = asyncio.run(
            phase_ragged7b(ns.bs, ns.max_seq, ns.kv_quant,
                           ns.spec_k, ns.chunk_len))
    elif ns.phase == "attr7b":
        result = phase_attr7b(ns.bs, ns.max_seq, ns.kv_quant)
    elif ns.phase == "2b":
        result = asyncio.run(phase_2b())
    elif ns.phase == "moe":
        result = asyncio.run(phase_moe())
    else:
        result = orchestrate()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
