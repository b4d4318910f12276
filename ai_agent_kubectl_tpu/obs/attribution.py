"""Decode-step cost attribution: profiler trace → per-op-category table.

Round 5 measured the Gemma-7B decode step at 33.3 ms (trace) of which
weights account for ~11.6 ms and attention ~2–3 ms — leaving the MAJORITY
of the step unattributed (VERDICT r5 weak #1). This module closes that
hole: it runs the engine-identical donated decode chunk under
``jax.profiler.trace``, parses the exported device-span timeline, and
bills every span to a named op category, so the table SUMS to the
measured step instead of waving at "~19 ms of non-weight work".

How spans get names worth billing: the model code is annotated with
``jax.named_scope`` blocks (models/transformer.py ``_layer``/``forward``,
engine/sampling.py, the batcher splice programs) whose scope paths XLA
stamps into each op's metadata — the profiler exports them on the op
events (``long_name``/``tf_op`` args), surviving fusion (a fusion's name
carries its root op's scope). Categorization is therefore keyword
matching on those scope paths first, HLO op-type heuristics second, and
an honest ``other_device`` bucket for what neither matches; device idle
inside the capture window lands in ``gaps`` (dispatch bubbles + fusion
boundaries). ``coverage_pct`` counts only the recognized categories —
the ≥90% acceptance bar means scope-tagged spans, not "everything we
couldn't name, summed".

Two entry points:

- ``run_attribution(...)`` — build the engine-identical chunk (same scan
  body, donation, sampling, masking as ``BatchedJaxEngine``), trace it,
  parse, validate, return the artifact dict. Used by
  ``tools/attribute_step.py`` and ``bench.py --phase attr7b``.
- ``attribute_trace(trace_dir, steps)`` — parse + categorize an existing
  trace directory (what ``POST /debug/profile`` captured, or a synthetic
  trace in tests).

jax is imported lazily inside the harness functions — the obs package
must stay importable (and the fake/openai deployments jax-free) when no
one ever attributes anything.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import Dict, Iterable, List, Optional, Tuple

SCHEMA_ID = "decode-step-attribution/v2"

#: category order is presentation order; "gaps" is computed (window −
#: device-busy union), everything else from span durations.
#: v2 (ISSUE 14) adds ``all_reduce``: the fused TP collectives
#: (reduce-scatter at the row-parallel GEMM outputs + all-gather at the
#: column-parallel inputs — the ``all_reduce`` named_scope in
#: models/transformer.py) were previously lumped into data_movement, so
#: the sharded step's comm time was invisible to
#: ``tools/attribute_step.py --check`` and tp_projection could never
#: reconcile its priced all-reduce term against a measurement.
CATEGORIES = (
    "weight_gemms",        # qkv/o/mlp/moe projections + embedding read
    "attention",           # score/probs dots over the live KV span
    "lm_head_sampling",    # 256k-vocab head projection + sampling chain
    "kv_write_splice",     # per-layer KV scatter + admission splices
    "norm_rope_residual",  # layernorms, RoPE, residual adds
    "all_reduce",          # TP collectives fused into the GEMM outputs
    "data_movement",       # copies, transposes, converts, layout changes
    "other_device",        # device-busy spans nothing above matched
    "gaps",                # device idle inside the capture window
)

#: scope-path keywords (from the jax.named_scope annotations), checked in
#: order — first hit wins. "attn_norm"/"mlp_norm" must land in norms, so
#: the norm rule precedes the weight-GEMM rule that would match their
#: enclosing "mlp" scope; the all_reduce scope precedes everything that
#: could match the constraint's enclosing o_proj/mlp scopes.
_SCOPE_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    ("all_reduce", ("all_reduce",)),
    ("lm_head_sampling", ("lm_head", "sampling")),
    ("kv_write_splice", ("kv_write", "kv_splice", "splice")),
    ("attention", ("attention", "flash", "ragged", "ring")),
    ("norm_rope_residual", ("attn_norm", "mlp_norm", "final_norm",
                            "rms_norm", "rope")),
    ("weight_gemms", ("qkv_proj", "o_proj", "mlp", "embed", "moe",
                      "expert")),
)

#: HLO op-name fallbacks for spans with no scope metadata (bare fusion
#: names, infeed/copy ops XLA inserts itself). Collective ops bill to
#: all_reduce (the comm category), never data_movement — partitioner-
#: emitted collectives don't always inherit the constraint's scope.
_HLO_RULES: Tuple[Tuple[str, Tuple[str, ...]], ...] = (
    # comm first: "reduce-scatter" must never match the kv rule's bare
    # "scatter".
    ("all_reduce", ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective")),
    ("kv_write_splice", ("scatter", "dynamic-update-slice",
                         "dynamic_update_slice")),
    ("lm_head_sampling", ("rng", "sort", "top-k", "topk")),
    ("data_movement", ("copy", "transpose", "bitcast", "convert",
                       "reshape", "concatenate", "broadcast", "tuple",
                       "infeed", "outfeed", "slice", "pad", "iota")),
    ("weight_gemms", ("dot", "convolution", "gemm", "matmul")),
)


def categorize(text: str) -> str:
    """Category for one span, from its name + metadata text."""
    t = text.lower()
    for cat, keys in _SCOPE_RULES:
        if any(k in t for k in keys):
            return cat
    for cat, keys in _HLO_RULES:
        if any(k in t for k in keys):
            return cat
    return "other_device"


# ------------------------------------------------------------- trace parse

def _load_trace_events(trace_dir: str) -> List[dict]:
    """All traceEvents from every profile file under ``trace_dir``
    (``plugins/profile/<run>/*.trace.json[.gz]`` — the layout
    ``jax.profiler.trace`` writes)."""
    events: List[dict] = []
    patterns = (
        os.path.join(trace_dir, "plugins", "profile", "*", "*.trace.json.gz"),
        os.path.join(trace_dir, "plugins", "profile", "*", "*.trace.json"),
    )
    for pat in patterns:
        for path in sorted(glob.glob(pat)):
            if path.endswith(".gz"):
                with gzip.open(path, "rt") as f:
                    data = json.load(f)
            else:
                with open(path) as f:
                    data = json.load(f)
            events.extend(data.get("traceEvents", []))
    return events


def _select_device_spans(
        events: Iterable[dict]) -> Tuple[List[Tuple[float, float, str]], str]:
    """(spans, source) — (start_us, end_us, text) op-level spans.

    Device pids are those whose process_name mentions TPU (bench.py's
    proven heuristic for this toolchain). Trace rows are hierarchical
    (modules / ops / steps on different tids) and a plain sum
    double-counts chip time (the r5 TTFT lesson), so within each device
    pid only the op-level rows are kept: tids whose thread_name matches
    "XLA Ops" when present, else the single busiest tid.

    With no device pid at all (CPU backend — the CI dryrun), fall back to
    the host-side XLA op executions (events carrying an ``hlo_op`` arg):
    not chip time, but the same parse/categorize path runs end to end.
    ``source`` reports which was used: "tpu_device" | "host_xla_ops" |
    "none".
    """
    proc_names: Dict[int, str] = {}
    thread_names: Dict[Tuple[int, int], str] = {}
    complete: List[dict] = []
    for e in events:
        ph = e.get("ph")
        if ph == "M":
            if e.get("name") == "process_name":
                proc_names[e.get("pid")] = str(
                    e.get("args", {}).get("name", ""))
            elif e.get("name") == "thread_name":
                thread_names[(e.get("pid"), e.get("tid"))] = str(
                    e.get("args", {}).get("name", ""))
        elif ph == "X":
            complete.append(e)

    device_pids = {pid for pid, name in proc_names.items() if "TPU" in name}
    spans: List[Tuple[float, float, str]] = []
    if not device_pids:
        for e in complete:
            args = e.get("args", {}) or {}
            if "hlo_op" not in args:
                continue
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
            if dur <= 0.0:
                continue
            text = " ".join(
                [str(e.get("name", ""))]
                + [str(v) for v in args.values() if isinstance(v, str)]
            )
            spans.append((ts, ts + dur, text))
        return spans, ("host_xla_ops" if spans else "none")
    for pid in device_pids:
        pid_events = [e for e in complete if e.get("pid") == pid]
        op_tids = {
            tid for (p, tid), name in thread_names.items()
            if p == pid and "xla op" in name.lower()
        }
        if not op_tids:
            # No labelled op line: keep the busiest tid (op rows dominate
            # module/step summaries in total duration).
            per_tid: Dict[int, float] = {}
            for e in pid_events:
                per_tid[e.get("tid")] = (per_tid.get(e.get("tid"), 0.0)
                                         + float(e.get("dur", 0.0)))
            if not per_tid:
                continue
            op_tids = {max(per_tid, key=per_tid.get)}
        for e in pid_events:
            if e.get("tid") not in op_tids:
                continue
            ts = float(e.get("ts", 0.0))
            dur = float(e.get("dur", 0.0))
            if dur <= 0.0:
                continue
            args = e.get("args", {}) or {}
            text = " ".join(
                [str(e.get("name", ""))]
                + [str(v) for v in args.values() if isinstance(v, str)]
            )
            spans.append((ts, ts + dur, text))
    return spans, "tpu_device"


def _union_ms(intervals: List[Tuple[float, float]]) -> float:
    """Total length (ms) of the union of [start, end] microsecond
    intervals (overlap-safe — hierarchical rows must not double-count)."""
    total = 0.0
    end: Optional[float] = None
    for s, t in sorted(intervals):
        if end is None or s > end:
            total += t - s
            end = t
        elif t > end:
            total += t - end
            end = t
    return total / 1000.0


def attribute_trace(trace_dir: str, steps: int, *,
                    meta: Optional[dict] = None) -> dict:
    """Parse ``trace_dir`` and bill device time to categories.

    ``steps`` = decode steps executed inside the capture (reps ×
    chunk_len); per-step numbers divide by it. Returns the artifact dict
    (schema ``decode-step-attribution/v2``), NOT yet validated — callers
    run ``validate_attribution`` so a parse bug can't silently ship a
    malformed artifact.
    """
    if steps < 1:
        raise ValueError("steps must be >= 1")
    spans, span_source = _select_device_spans(_load_trace_events(trace_dir))

    per_cat: Dict[str, List[Tuple[float, float]]] = {c: [] for c in CATEGORIES}
    per_op: Dict[str, Dict[str, float]] = {c: {} for c in CATEGORIES}
    for ts, te, text in spans:
        cat = categorize(text)
        per_cat[cat].append((ts, te))
        op = text.split(" ", 1)[0] or "?"
        per_op[cat][op] = per_op[cat].get(op, 0.0) + (te - ts) / 1000.0

    all_iv = [(s, t) for s, t, _ in spans]
    busy_ms = _union_ms(all_iv)
    window_ms = ((max(t for _, t, _ in spans) - min(s for s, _, _ in spans))
                 / 1000.0) if spans else 0.0
    gaps_ms = max(window_ms - busy_ms, 0.0)

    # Coverage is the UNION of every recognized category's intervals, not
    # their sum: concurrently-executing spans (host-XLA fallback streams,
    # multi-device pids) can overlap ACROSS categories, and a sum would
    # push coverage past 100% of the wall window. On a serial device
    # stream union == sum, so the chip number is unchanged.
    recognized_iv: List[Tuple[float, float]] = []
    categories = []
    for cat in CATEGORIES:
        if cat == "gaps":
            ms = gaps_ms
        else:
            ms = _union_ms(per_cat[cat])
        if cat not in ("other_device", "gaps"):
            recognized_iv.extend(per_cat[cat])
        top = sorted(per_op[cat].items(), key=lambda kv: -kv[1])[:5]
        categories.append({
            "name": cat,
            "ms_per_step": round(ms / steps, 4),
            "pct_of_step": round(100.0 * ms / window_ms, 2) if window_ms
            else 0.0,
            "top_ops": [{"name": n, "ms_per_step": round(v / steps, 4)}
                        for n, v in top],
        })

    recognized_ms = min(_union_ms(recognized_iv), window_ms)
    out = {
        "schema": SCHEMA_ID,
        "steps_measured": steps,
        "span_source": span_source,
        "n_device_spans": len(spans),
        "wall_ms_total": round(window_ms, 3),
        "device_busy_ms_total": round(busy_ms, 3),
        "step_ms": round(window_ms / steps, 4),
        "device_busy_ms_per_step": round(busy_ms / steps, 4),
        "categories": categories,
        "coverage_pct": round(100.0 * recognized_ms / window_ms, 2)
        if window_ms else 0.0,
        "unattributed_ms_per_step": round(
            (window_ms - recognized_ms) / steps, 4),
    }
    out.update(meta or {})
    return out


def validate_attribution(obj: dict) -> None:
    """Schema check for the attribution artifact (CI gates on it so the
    trace-parse path can't rot). Raises ``ValueError`` on any violation."""
    if not isinstance(obj, dict):
        raise ValueError("artifact must be a dict")
    if obj.get("schema") != SCHEMA_ID:
        raise ValueError(f"schema must be {SCHEMA_ID!r}, "
                         f"got {obj.get('schema')!r}")
    if obj.get("span_source") not in ("tpu_device", "host_xla_ops", "none"):
        raise ValueError(f"bad span_source {obj.get('span_source')!r}")
    for key, typ in (("steps_measured", int), ("n_device_spans", int),
                     ("wall_ms_total", (int, float)),
                     ("device_busy_ms_total", (int, float)),
                     ("step_ms", (int, float)),
                     ("coverage_pct", (int, float)),
                     ("unattributed_ms_per_step", (int, float)),
                     ("categories", list)):
        if not isinstance(obj.get(key), typ):
            raise ValueError(f"missing/mistyped field {key!r}")
    names = []
    for cat in obj["categories"]:
        if not isinstance(cat, dict):
            raise ValueError("category entries must be dicts")
        if cat.get("name") not in CATEGORIES:
            raise ValueError(f"unknown category {cat.get('name')!r}")
        names.append(cat["name"])
        for key in ("ms_per_step", "pct_of_step"):
            if not isinstance(cat.get(key), (int, float)) or cat[key] < 0:
                raise ValueError(f"category {cat['name']}: bad {key!r}")
        if not isinstance(cat.get("top_ops"), list):
            raise ValueError(f"category {cat['name']}: top_ops must be a list")
    if names != list(CATEGORIES):
        raise ValueError(
            f"categories must be exactly {list(CATEGORIES)} in order, "
            f"got {names}")
    if not (0.0 <= obj["coverage_pct"] <= 100.0):
        raise ValueError("coverage_pct out of [0, 100]")
    # The table must SUM to the step: categories (incl. gaps/other) cover
    # the window, up to rounding. Only enforceable on a real device
    # stream — host_xla_ops spans (the CPU dryrun fallback) run
    # concurrently on the executor pool, so their per-category sums can
    # legitimately exceed wall time.
    total_pct = sum(c["pct_of_step"] for c in obj["categories"])
    if (obj["span_source"] == "tpu_device" and obj["wall_ms_total"] > 0
            and not (95.0 <= total_pct <= 105.0)):
        raise ValueError(
            f"category percentages sum to {total_pct:.1f}, not ~100 — "
            "the table no longer sums to the measured step")


def render_markdown(obj: dict) -> str:
    """Markdown table for one attribution artifact."""
    lines = [
        "| Category | ms/step | % of step | top ops |",
        "|---|---|---|---|",
    ]
    for cat in obj["categories"]:
        tops = ", ".join(
            f"{o['name']} {o['ms_per_step']:.3f}" for o in cat["top_ops"][:3]
        ) or "—"
        lines.append(
            f"| {cat['name']} | {cat['ms_per_step']:.3f} "
            f"| {cat['pct_of_step']:.1f}% | {tops} |"
        )
    lines.append(
        f"| **step total** | **{obj['step_ms']:.3f}** | 100% "
        f"| coverage {obj['coverage_pct']:.1f}%, "
        f"unattributed {obj['unattributed_ms_per_step']:.3f} ms/step |"
    )
    return "\n".join(lines)


# --------------------------------------------------- engine-identical chunk

def run_attribution(*, model: str = "gemma-7b-it", quant: str = "int8",
                    kv_quant: str = "int8", dtype: str = "bfloat16",
                    batch_size: int = 48, chunk_len: int = 16,
                    max_seq: int = 192, kv_limit: Optional[int] = None,
                    reps: int = 6, top_k: int = 0, top_p: float = 1.0,
                    keep_trace: bool = False) -> dict:
    """Trace the engine-identical batched decode chunk and attribute it.

    "Engine-identical" means the same compiled program shape the serving
    scheduler dispatches (``BatchedJaxEngine._start_blocking``'s
    ``batched_chunk``): a donated ``lax.scan`` of ``chunk_len`` steps —
    forward with a KV-bucket limit and active-slot masking, per-slot
    batched sampling, position advance — over an ``S_alloc``-deep slot
    cache, starting mid-life so every timed KV write stays in bounds.
    The first (compile) execution runs OUTSIDE the capture; ``reps``
    chained executions run inside it with one forced sync at the end, so
    the window is wall-to-wall decode.
    """
    import shutil
    import tempfile
    import time

    import jax
    import jax.numpy as jnp

    from ..engine.jax_engine import kv_bucket_ladder
    from ..models.config import get_config
    from ..models.transformer import KVCache, forward, init_params

    cfg = get_config(model)
    jdtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[dtype]
    if quant == "int8":
        from ..ops.quant import random_params_int8

        params = random_params_int8(jax.random.PRNGKey(0), cfg, dtype=jdtype,
                                    quantize_embed=True)
    else:
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=jdtype)

    S_alloc = max_seq + chunk_len
    if kv_limit is None:
        kv_limit = kv_bucket_ladder(S_alloc)[-1]   # the serving top bucket

    # THE serving chunk body, not a copy: make_termination_chunk_fn is the
    # same builder BatchedJaxEngine compiles per KV bucket, so the traced
    # program is engine-identical by construction (only the forward
    # closure differs: single-device dense attention here).
    from ..engine.batcher import make_termination_chunk_fn

    def forward_step(params, tok, pos, cache, live):
        return forward(params, cfg, tok, pos, cache, kv_limit=kv_limit,
                       attn_impl="dense", token_mask=live[:, None],
                       write_mask=live)

    batched_chunk = make_termination_chunk_fn(
        forward_step, chunk_len, tuple(sorted(set(cfg.eos_ids))),
        top_k, top_p, vocab_size=cfg.vocab_size)

    fn = jax.jit(batched_chunk, donate_argnums=(1, 2, 3, 7, 8))

    N = batch_size
    if S_alloc < (reps + 2) * chunk_len + 1:
        raise ValueError(
            f"max_seq {max_seq} too short for reps={reps} × "
            f"chunk={chunk_len}: timed KV writes would run out of bounds "
            f"(silently dropped scatters time a step without its "
            f"cache-write traffic)")
    pos0 = max(0, min(320, S_alloc - (reps + 2) * chunk_len - 1))
    tok = jnp.zeros((N, 1), jnp.int32)
    pos = jnp.full((N, 1), pos0, jnp.int32)
    cache = KVCache.zeros(cfg, N, S_alloc, dtype=jdtype, kv_quant=kv_quant)
    seeds = jnp.zeros((N,), jnp.int32)
    no_corrupt = jnp.zeros((N,), jnp.bool_)
    temps = jnp.zeros((N,), jnp.float32)
    # All lanes force-live with an unreachable budget, and fresh all-live
    # carry state per dispatch: a sampled EOS from random-init weights
    # must not progressively park lanes and time a partially-masked step.
    force = jnp.ones((N,), jnp.bool_)
    budget = jnp.full((N,), 1 << 30, jnp.int32)

    def all_live():
        return jnp.ones((N,), jnp.bool_), jnp.zeros((N,), jnp.int32)

    def sync(x):
        jax.block_until_ready(x)
        import numpy as np

        leaf = jax.tree_util.tree_leaves(x)[0]
        np.asarray(jax.device_get(leaf[(0,) * leaf.ndim]))

    active, ngen = all_live()
    packed, tok, pos, cache, _, _ = fn(
        params, tok, pos, cache, seeds, temps, force, active,
        ngen, budget, no_corrupt)                         # compile + warm
    sync(packed)

    trace_dir = tempfile.mkdtemp(prefix="attr_step_")
    t0 = time.perf_counter()
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(reps):
                active, ngen = all_live()
                packed, tok, pos, cache, _, _ = fn(
                    params, tok, pos, cache, seeds, temps, force, active,
                    ngen, budget, no_corrupt)
            sync(packed)
        wall_s = time.perf_counter() - t0
        steps = reps * chunk_len
        out = attribute_trace(trace_dir, steps, meta={
            "model": cfg.name,
            "backend": jax.default_backend(),
            "quant": quant or "-",
            "kv_quant": kv_quant or "-",
            "dtype": dtype,
            "batch_size": N,
            "chunk_len": chunk_len,
            "max_seq": max_seq,
            "kv_limit": kv_limit,
            "reps": reps,
            "wall_ms_per_step_host": round(wall_s * 1000.0 / steps, 4),
        })
        if keep_trace:
            out["trace_dir"] = trace_dir
        return out
    finally:
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
