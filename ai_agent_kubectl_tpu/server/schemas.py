"""Request/response schemas (reference app.py:153-174), pydantic v2.

The wire contract is kept byte-compatible with the reference:
``Query{query}``, ``ExecuteRequest{execute}``, ``CommandResponse{
kubectl_command, execution_result, execution_error, from_cache, metadata}``,
``ExecutionMetadata{start_time, end_time, duration_ms, success,
error_type?, error_code?}``.

Additions (documented, additive-only): ``CommandResponse.engine_metadata``
carries engine phase timings (queue/prefill/decode, TTFT) when a local
engine served the request — the TPU-native analog of the reference's
``duration_ms`` bookkeeping (app.py:164,227).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from pydantic import BaseModel, Field


class Query(BaseModel):
    query: str = Field(..., min_length=3, description="Natural language query for kubectl.")


class ExecuteRequest(BaseModel):
    execute: str = Field(..., description="kubectl command to execute.")


class ExecutionMetadata(BaseModel):
    start_time: str
    end_time: str
    duration_ms: float
    success: bool
    error_type: Optional[str] = None
    error_code: Optional[str] = None


class EngineMetadata(BaseModel):
    """Per-request engine phase timings (TPU-native addition; SURVEY.md §5
    tracing row)."""

    queue_ms: float = 0.0
    prefill_ms: float = 0.0
    decode_ms: float = 0.0
    detok_ms: float = 0.0
    ttft_ms: float = 0.0
    prompt_tokens: int = 0
    completion_tokens: int = 0
    tokens_per_sec: float = 0.0
    prefix_cache_hit: bool = False
    engine: str = ""


class CommandResponse(BaseModel):
    kubectl_command: str
    execution_result: Optional[Dict[str, Any]] = None
    execution_error: Optional[Dict[str, Any]] = None
    from_cache: bool = False
    metadata: ExecutionMetadata
    engine_metadata: Optional[EngineMetadata] = None
    # True when the rule-based FallbackEngine served this response because
    # the real engine was failing (DEGRADED_FALLBACK + open breaker);
    # engine_metadata.engine is then "fallback-rules".
    degraded: bool = False
    # Per-phase millisecond breakdown of this request's lifecycle
    # (obs/trace.py) — the same numbers as the Server-Timing header and
    # the /debug/requests/{id} timeline, inline for clients that want
    # them without header parsing. Additive/optional: absent when no
    # trace context was active.
    timings: Optional[Dict[str, float]] = None


class HealthResponse(BaseModel):
    """Readiness-gated health (fixes the reference's static /health,
    app.py:348-354; SURVEY.md §3.3)."""

    status: str
    engine: str = ""
    engine_ready: bool = False
    model: str = ""
    # What JAX serves from, as jax.devices() reports it: device count,
    # platform ("tpu" | "cpu" | ...) and device kind ("TPU v5 lite").
    devices: int = 0
    platform: str = ""
    device_kind: str = ""
    # Failure-containment state (server/breaker.py): closed | half-open |
    # open, and whether an open breaker degrades to rule-based responses
    # instead of 503s.
    breaker: str = "closed"
    degraded_fallback: bool = False
    # Inner-ring containment (engine/containment.py): when the engine
    # last reset-and-replayed its decode state (ISO 8601) and why
    # (slot_health | scheduler_error | scheduler_death). None = never.
    last_reset: Optional[str] = None
    last_reset_cause: Optional[str] = None
    # Fleet deployments (engine/fleet.py, FLEET_SIZE > 1): the rollup —
    # replica counts by state, migration/hedge/drain/eject/rejoin
    # totals — plus a ``replicas`` list with each replica's state,
    # breaker, occupancy, and last reset/cause. None = no fleet layer.
    fleet: Optional[Dict[str, Any]] = None
    # QoS ring (engine/qos.py, ISSUE 7): per-lane queue depth, the
    # active brownout level and lane shares, preemptions in the last
    # minute, and scan-time expiry/displacement totals. None = engine
    # without the QoS scheduler (fake/openai single-sequence paths).
    qos: Optional[Dict[str, Any]] = None
    # SLO burn-rate engine (obs/slo.py, ISSUE 8): multi-window (5m/1h)
    # error-budget burn for TTFT and queue wait per lane, against the
    # SLO_TTFT_MS / SLO_INTERACTIVE_MS targets. None = engine without
    # the telemetry plane.
    slo: Optional[Dict[str, Any]] = None
    # Block-paged KV pool + radix prefix sharing (ISSUE 10,
    # engine/kv_pool.py): block counts by state (free/live/cached),
    # sharing + copy-on-write totals, and the radix tree's hit/miss
    # token counters. None = dense-KV engine (KV_POOL=false, a mesh
    # with a >1 data/pipe/seq axis, or the single-sequence/fake/openai
    # paths). TP/EP meshes serve the pool (ISSUE 14).
    kv_pool: Optional[Dict[str, Any]] = None
    # The cache kinds' sections, this one and the four below (one
    # description a kind, models/families.py; an engine gives them all
    # through ``family_health()``). Grouped expert GEMM and learned key
    # selection (ISSUE 31; ``_moe_section``, ``_sparse_section``): cumulative
    # experts_read / layer_passes, and decode rows live / selected, index
    # rows scanned, window rows, forward passes. None where the
    # configuration has neither.
    moe: Optional[Dict[str, Any]] = None
    sparse_attention: Optional[Dict[str, Any]] = None
    # Latent attention (ISSUE 38; ``_latent_section``): the compressed
    # cache's bytes a token (row_bytes, all layers), decode queries run
    # and the cached rows they read (summed over layers), prompt rows
    # prefilled by the absorbed and by the expanded form. None for a
    # model that caches K and V.
    latent_attention: Optional[Dict[str, Any]] = None
    # Attention of two kinds (ISSUE 40; ``_sliding_section``): the span
    # and the ring a decode slot keeps a sliding layer, decode queries
    # run and the keys they read in the sliding and in the full layers
    # (counted on the device), prompt rows prefilled and their pairs a
    # layer of each kind. None for a model whose attention layers are of
    # one kind.
    sliding_attention: Optional[Dict[str, Any]] = None
    # Linear attention by the gated delta rule (ISSUE 45;
    # ``_linear_section``): linear and full layers, the state's bytes a
    # sequence, decode rows and window rows the linear layers ran and the
    # chunks their scans ran over, decode queries and keys read in the
    # full layers (counted on the device). None for every other model.
    linear_attention: Optional[Dict[str, Any]] = None
    # Recurrent-state cache of a model with state-space layers (ISSUE 33;
    # engine/kv_pool.py::StateStore.stats, ``_state_section``):
    # snapshots held / capacity / bytes and their peak, snapshots taken /
    # evicted / skipped, restores, prefix tokens matched / usable /
    # recomputed, state bytes moved, layer passes by kind. None for every
    # other model.
    ssm: Optional[Dict[str, Any]] = None
    # The mixed chunks' admission windows (ISSUE 39; engine/batcher.py::
    # ragged_health, mirrored by the fake scheduler): ``window`` holds
    # chunks that carried one, the valid rows they brought, the rows
    # their prologues computed (width + batch each) and staged suffixes
    # deferred a chunk. None off the ragged regime.
    ragged: Optional[Dict[str, Any]] = None
    # Tensor-parallel serving (ISSUE 14, parallel/sharding.py): the
    # active mesh shape + device count, the residual TP fraction the
    # f≈1 policy achieves at the decode shape, whether the KV pool is
    # mesh-sharded, and the kv_pool_mesh_fallback flag (a requested
    # pool that fell back to the dense ladder must be visible). None =
    # no serving mesh.
    sharding: Optional[Dict[str, Any]] = None
    # Grammar-constrained decoding (ISSUE 11, constrain/): the active
    # profile, compiled-grammar hash + state/class counts, forced vs
    # masked token totals, and dead ends by cause. None = GRAMMAR_DECODE
    # off or an engine without the subsystem.
    grammar: Optional[Dict[str, Any]] = None
    # Speculative decoding (ISSUE 12, engine/batcher.py): draft model
    # id, k, live/degraded state, drafted/accepted totals and the
    # acceptance ratio. None = SPEC_DECODE off or an engine without the
    # subsystem.
    spec: Optional[Dict[str, Any]] = None
    # Zero-downtime weight rollout (ISSUE 13, engine/rollout.py): the
    # state machine position, target/stable checkpoint versions, the
    # canary replica + share, the per-replica version table, and
    # cumulative rollbacks by cause. None = engine without swap support
    # (the per-replica versions also appear in the fleet section).
    rollout: Optional[Dict[str, Any]] = None
    # Perf-regression sentinel (ISSUE 15, obs/steptime.py): per-(phase,
    # bucket) step-time digests (p50/p95/p99, baseline, trailing
    # tok/s), breach verdicts, and the edge-triggered trip total; the
    # fleet rollup attributes breaches to replicas. None = engine
    # without the chunked scheduler.
    steptime: Optional[Dict[str, Any]] = None
    # Incident capture (ISSUE 15, obs/incidents.py): ring occupancy,
    # captured/suppressed totals by trigger, and the newest incident id
    # (full bundles live behind token-gated /debug/incidents).
    incidents: Optional[Dict[str, Any]] = None
    # Engine spans (obs/trace.py): cumulative {count, total_ms, max_ms}
    # per span name since start — the request phases (queue_wait with
    # slot_wait_total_ms, prefill and its children admit_host /
    # stage_wait / first_chunk with chunks_ahead_total and
    # chunks_unready_total, decode, detokenize) and the scheduler's
    # sched/admit|dispatch|fetch|consume and their named children
    # — plus ``sched_thread_s``: the scheduler thread's wall time by
    # state (admit, dispatch, fetch_wait, consume, idle, other; they sum
    # to ``elapsed``) and ``chunks_consumed``; ``sched_starved_s``: the
    # part of it with no chunk program in flight and work at hand; and
    # ``sched_drained_s``: the part with the NEWEST launch of any kind
    # done, so that the device had nothing to run (``with_work`` and
    # ``no_work`` by state, ``total`` = with_work's, ``unseen``,
    # ``by_region``). None = engine without a chunk scheduler.
    spans: Optional[Dict[str, Any]] = None
