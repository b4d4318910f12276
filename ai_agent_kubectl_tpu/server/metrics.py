"""Prometheus metrics (reference app.py:136-138 + SURVEY.md §5 additions).

The reference exposed default HTTP metrics via
prometheus-fastapi-instrumentator. Here we register the equivalent request
counters/latency histograms on ``prometheus_client`` directly, plus the
engine-side gauges the TPU build adds: tokens/sec, batch occupancy, KV-pool
usage, TTFT histogram, cache hit counters.

A dedicated ``CollectorRegistry`` per app instance keeps tests isolated
(prometheus_client's global registry rejects duplicate registration).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Callable, Optional

from prometheus_client import (
    CollectorRegistry,
    Counter,
    Gauge,
    Histogram,
    generate_latest,
)
from prometheus_client.exposition import CONTENT_TYPE_LATEST

from ..engine.regime import REGIMES

_TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.15, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
_LATENCY_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
# Phase spans skew small (sub-ms safety checks next to multi-second
# decodes), so the phase histogram keeps finer low-end buckets.
_PHASE_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                  1.0, 2.5, 5.0, 10.0, 30.0, 60.0)


class WindowedRate:
    """Rolling-window event rate for the throughput gauge.

    ``engine_tokens_per_sec`` used to be ``.set()`` from each finished
    request's own throughput — so it only ever showed the LAST request
    (whichever response handler wrote last under concurrent decode, i.e.
    racy and meaningless at batch>1). It is now the average completion
    rate over a trailing window: every finished generation ``add()``s its
    token count here, and the /metrics scrape reads ``rate()``. The
    alternative (dropping the gauge for ``rate(engine_tokens_generated_
    total)`` in PromQL) was rejected because bench tooling and the probe
    scripts read the gauge directly without a Prometheus server in the
    loop; the counter remains for PromQL users who want custom windows.
    """

    def __init__(self, window_secs: float = 60.0,
                 timer: Callable[[], float] = time.monotonic):
        self.window_secs = window_secs
        self._timer = timer
        self._events: deque = deque()   # (t, count)

    def add(self, count: int, now: Optional[float] = None) -> None:
        if count <= 0:
            return
        now = self._timer() if now is None else now
        self._events.append((now, count))
        self._prune(now)

    def _prune(self, now: float) -> None:
        horizon = now - self.window_secs
        while self._events and self._events[0][0] < horizon:
            self._events.popleft()

    def rate(self, now: Optional[float] = None) -> float:
        """Events per second averaged over the trailing window. The
        denominator is the full window, not the span of observed events —
        a single burst 50 s ago reads as its amortized rate, and an idle
        window decays to 0 instead of freezing at the last burst."""
        now = self._timer() if now is None else now
        self._prune(now)
        total = sum(c for _, c in self._events)
        return total / self.window_secs if total else 0.0


class Metrics:
    """All service + engine metrics for one app instance."""

    content_type = CONTENT_TYPE_LATEST

    def __init__(self) -> None:
        self.registry = CollectorRegistry()
        r = self.registry

        # HTTP metrics (instrumentator parity)
        self.http_requests = Counter(
            "http_requests_total",
            "Total HTTP requests",
            ["method", "handler", "status"],
            registry=r,
        )
        self.http_latency = Histogram(
            "http_request_duration_seconds",
            "HTTP request latency",
            ["method", "handler"],
            buckets=_LATENCY_BUCKETS,
            registry=r,
        )

        # Service-layer metrics
        self.cache_hits = Counter(
            "response_cache_hits_total", "Query→command cache hits", registry=r
        )
        self.cache_misses = Counter(
            "response_cache_misses_total", "Query→command cache misses", registry=r
        )
        self.rate_limited = Counter(
            "rate_limited_total", "Requests rejected by the rate limiter", registry=r
        )
        self.unsafe_commands = Counter(
            "unsafe_commands_total",
            "Commands rejected by the safety validator",
            ["source"],  # llm | user
            registry=r,
        )
        self.executions = Counter(
            "kubectl_executions_total", "kubectl subprocess runs", ["outcome"], registry=r
        )

        # Engine metrics (TPU-native additions, SURVEY.md §5)
        self.ttft = Histogram(
            "engine_ttft_seconds", "Time to first token", buckets=_TTFT_BUCKETS, registry=r
        )
        self.gen_latency = Histogram(
            "engine_generate_seconds",
            "Full generation latency",
            buckets=_LATENCY_BUCKETS,
            registry=r,
        )
        self.tokens_generated = Counter(
            "engine_tokens_generated_total", "Completion tokens produced", registry=r
        )
        # Windowed, not last-request (see WindowedRate above): set at
        # scrape time from the trailing-60s completion rate.
        self.tokens_per_sec = Gauge(
            "engine_tokens_per_sec",
            "Decode throughput averaged over the trailing 60s window",
            registry=r,
        )
        self.batch_occupancy = Gauge(
            "engine_batch_occupancy", "Active slots in the decode batch", registry=r
        )
        self.queue_depth = Gauge(
            "engine_queue_depth", "Requests waiting for a decode slot", registry=r
        )
        self.kv_pool_used = Gauge(
            "engine_kv_pages_used", "KV cache pages in use", registry=r
        )
        self.kv_pool_total = Gauge(
            "engine_kv_pages_total", "KV cache pages allocated", registry=r
        )
        self.prefix_cache_hits = Counter(
            "engine_prefix_cache_hits_total", "Prefix-KV cache hits", registry=r
        )

        # Block-paged KV pool + radix prefix sharing (ISSUE 10,
        # engine/kv_pool.py + engine/radix_cache.py). ``state`` is the
        # closed free|live|cached set (live = mapped by >=1 slot,
        # cached = held only by the radix tree). The cumulative sharing
        # totals are delta-mirrored from stats()["kv_pool"] at scrape
        # time like the pipeline/containment counters.
        self.kv_pool_blocks = Gauge(
            "kv_pool_blocks",
            "KV pool blocks by state (free | live | cached)",
            ["state"],
            registry=r,
        )
        self.kv_blocks_shared = Counter(
            "kv_blocks_shared_total",
            "Shared-block mappings handed out by the radix tree "
            "(a full prefix block mapped into another slot's table)",
            registry=r,
        )
        self.kv_cow_copies = Counter(
            "kv_cow_copies_total",
            "Copy-on-write copies of partially-filled tail blocks",
            registry=r,
        )
        self.radix_hit_tokens = Counter(
            "radix_hit_tokens_total",
            "Prompt tokens whose KV was served from the radix tree "
            "(prefill skipped)",
            registry=r,
        )
        self.radix_miss_tokens = Counter(
            "radix_miss_tokens_total",
            "Prompt tokens prefilled because no cached prefix covered "
            "them",
            registry=r,
        )
        # Two-tier KV (ISSUE 20): host-RAM tier occupancy + demote/
        # onload flow. ``cause`` on the onload-fail counter is the
        # closed HostBlockStore.ONLOAD_FAIL_CAUSES set (corrupt |
        # exhausted) — cardinality bounded by construction.
        self.kv_host_blocks = Gauge(
            "kv_host_blocks",
            "Host-tier KV blocks by state (used | free)",
            ["state"],
            registry=r,
        )
        self.kv_blocks_demoted = Counter(
            "kv_blocks_demoted_total",
            "KV blocks demoted from HBM to the host-RAM tier",
            registry=r,
        )
        self.kv_blocks_onloaded = Counter(
            "kv_blocks_onloaded_total",
            "Host-tier KV blocks re-onloaded to HBM (checksum verified)",
            registry=r,
        )
        self.kv_onload_fail = Counter(
            "kv_onload_fail_total",
            "Host-tier onload failures by cause (corrupt = checksum "
            "mismatch, chain dropped + prefill fallback; exhausted = "
            "no device block free)",
            ["cause"],
            registry=r,
        )
        self.kv_host_dropped = Counter(
            "kv_host_blocks_dropped_total",
            "Host-tier blocks discarded (LRU displacement, corrupt-"
            "chain purge, or reset drain)",
            registry=r,
        )
        # Recurrent-state cache (ISSUE 33, engine/kv_pool.py::StateStore):
        # snapshots of a state-space model's state beside the block pool.
        self.state_snapshots = Gauge(
            "state_snapshots",
            "Recurrent-state snapshots the device store holds, by state "
            "(held | capacity | pinned | restore_depth_peak: the deepest "
            "LRU rank a restore found, what the store's size answers to)",
            ["state"],
            registry=r,
        )
        self.state_cache_events = Counter(
            "state_cache_events_total",
            "Recurrent-state cache events (snapshots_taken | "
            "snapshots_evicted | snapshots_skipped | restores)",
            ["event"],
            registry=r,
        )
        self.state_prefix_tokens = Counter(
            "state_prefix_tokens_total",
            "Prompt tokens the radix tree matched for a model with a "
            "recurrent state, by what became of them (usable: at or "
            "before the restored snapshot | recomputed: K/V matched but "
            "past it)",
            ["outcome"],
            registry=r,
        )
        self.state_bytes_moved = Counter(
            "state_bytes_moved_total",
            "Bytes of recurrent state copied between decode slots and "
            "the snapshot store",
            registry=r,
        )
        # Latent attention (ISSUE 38): mirrors of /health.latent_attention.
        self.latent_row_bytes = Gauge(
            "latent_cache_row_bytes",
            "Bytes one token keeps in the block pool of a latent-attention "
            "model, all layers (0: the model caches K and V)",
            registry=r,
        )
        self.latent_rows = Counter(
            "latent_attention_rows_total",
            "Rows of latent attention (decode_rows: decode queries run | "
            "latent_rows_read: cached rows they had before them, summed "
            "over layers | window_rows_absorbed | window_rows_expanded: "
            "prompt rows prefilled, by the form that attended them)",
            ["kind"],
            registry=r,
        )
        self._latent_seen: dict = {}
        self._state_seen: dict = {}
        self._kv_pool_seen = {"shared": 0, "cow": 0, "hit": 0, "miss": 0,
                              "demoted": 0, "onloaded": 0, "dropped": 0,
                              "fail_corrupt": 0, "fail_exhausted": 0}

        # Tensor-parallel serving (ISSUE 14, parallel/sharding.py):
        # the active mesh size, the residual TP fraction the f≈1 policy
        # achieves at the decode shape (1.0 = every residual-path
        # tensor batch-sharded), and the loud-fallback flag
        # for a KV pool forced back to the dense ladder by a
        # data/pipe/seq mesh axis. Gauges sampled at scrape time from
        # stats()["sharding"].
        self.mesh_devices = Gauge(
            "mesh_devices",
            "Devices in the active serving mesh (0 = single device)",
            registry=r,
        )
        self.sharding_residual_fraction = Gauge(
            "sharding_residual_fraction",
            "Residual TP-shardable fraction f achieved by the active "
            "sharding policy at the decode shape (1.0 = full f~1 "
            "residual-path sharding)",
            registry=r,
        )
        self.kv_pool_mesh_fallback = Gauge(
            "kv_pool_mesh_fallback",
            "1 when KV_POOL was requested but the mesh forced the "
            "dense KV ladder (data/pipe/seq axis > 1) — a silent "
            "dense fallback must be visible",
            registry=r,
        )
        # Spec decode under the mesh (ISSUE 18): whether the draft
        # world rides the mesh sharded, and whether its KV serves
        # replicated because the draft's KV heads don't divide tp (the
        # gather fallback — correct but off the shard-local fast path).
        self.spec_draft_sharded = Gauge(
            "spec_draft_sharded",
            "1 when the speculative draft model's params/KV are "
            "sharded over the serving mesh",
            registry=r,
        )
        self.spec_draft_kv_fallback = Gauge(
            "spec_draft_kv_fallback",
            "1 when the draft's KV heads do not divide the mesh's "
            "model axis and its KV cache serves replicated (gather "
            "fallback) — a silent gather must be visible",
            registry=r,
        )
        # Which attention regime serves (engine/regime.py) — enum-style
        # gauge (1 on the active label) so a fallback from ragged (int8
        # KV, non-dividing tp, no TPU) is a dashboard fact, not an
        # inference.
        self.decode_attention_regime = Gauge(
            "decode_attention_regime",
            "1 for the attention regime actually serving decode "
            "(ragged = one kernel for prefill/decode/spec-verify over "
            "the block pool; gather = dense gather over pool pages; "
            "dense = per-slot dense KV ladder)",
            ["regime"],
            registry=r,
        )

        # Decode-pipeline metrics (ISSUE 4: device-side termination +
        # deep chunk pipelining). Occupancy/config are gauges sampled at
        # scrape; the waste/chunk counters are cumulative scheduler totals
        # mirrored through ``observe_pipeline`` (delta-inc so restarts of
        # the scrape path don't double-count); fetch latencies arrive as
        # drained per-chunk samples.
        self.pipe_occupancy = Gauge(
            "decode_pipe_occupancy",
            "Speculative decode chunks currently in flight",
            registry=r,
        )
        self.pipe_depth = Gauge(
            "decode_pipe_depth",
            "Configured CHUNK_PIPE_DEPTH",
            registry=r,
        )
        self.device_active_slots = Gauge(
            "decode_device_active_slots",
            "Live slots reported by the last consumed chunk's n_alive",
            registry=r,
        )
        self.wasted_decode_steps = Counter(
            "wasted_decode_steps_total",
            "Decode steps executed for already-terminated slots "
            "(~0 with DEVICE_TERMINATION=true)",
            registry=r,
        )
        self.decode_chunks = Counter(
            "decode_chunks_total",
            "Decode chunk pipeline events",
            ["event"],  # dispatch | consume | prune
            registry=r,
        )
        self.chunk_fetch = Histogram(
            "chunk_fetch_seconds",
            "Blocking device->host fetch latency per consumed chunk",
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                     0.25, 0.5, 1.0, 2.5),
            registry=r,
        )
        # Last-seen cumulative totals for the delta-inc mirror.
        self._pipe_seen = {"wasted": 0, "dispatch": 0, "consume": 0,
                           "prune": 0}

        # Failure-containment metrics (overload shedding / breaker /
        # degraded fallback)
        self.queue_rejections = Counter(
            "queue_rejections_total",
            "Requests shed by overload protection",
            ["layer"],  # http (inflight cap) | engine (admission queue)
            registry=r,
        )
        self.breaker_state = Gauge(
            "breaker_state",
            "Circuit breaker state (0=closed, 1=half-open, 2=open)",
            registry=r,
        )
        self.degraded_responses = Counter(
            "degraded_responses_total",
            "Responses served by the rule-based fallback engine",
            registry=r,
        )

        # Blast-radius containment (ISSUE 5, the inner ring): engine
        # resets by cause, terminal quarantines by reason, device health
        # trips, and tokens regenerated by reset-and-replay. Cumulative
        # totals live on the engine supervisor; scrapes delta-mirror them
        # like the pipeline counters (observe_containment).
        self.engine_resets = Counter(
            "engine_resets_total",
            "Decode-state reset-and-replay cycles",
            ["cause"],  # slot_health | scheduler_error | scheduler_death
            registry=r,
        )
        self.quarantined_requests = Counter(
            "quarantined_requests_total",
            "Requests terminally quarantined by culprit isolation",
            ["reason"],  # slot_health | step_poison
            registry=r,
        )
        self.replayed_tokens = Counter(
            "replayed_tokens_total",
            "Already-generated tokens re-spliced and replayed across "
            "engine resets (innocent-victim recovery)",
            registry=r,
        )
        self.slot_health_trips = Counter(
            "slot_health_trips_total",
            "Per-slot device health-word trips (NaN/Inf logits, "
            "out-of-range token ids) caught in the decode chunk",
            registry=r,
        )
        self._containment_seen = {"resets": {}, "quarantined": {},
                                  "health_trips": 0, "replayed_tokens": 0}

        # Engine fleet (engine/fleet.py, FLEET_SIZE > 1): replica counts
        # by lifecycle state, per-replica occupancy/breaker gauges (the
        # ``replica`` label is the replica index — cardinality bounded
        # by FLEET_SIZE), and the migration/hedge/drain/eject/rejoin
        # counters, delta-mirrored from fleet.stats() like the pipeline
        # and containment totals.
        self.fleet_replicas = Gauge(
            "fleet_replicas",
            "Fleet replicas by lifecycle state",
            ["state"],  # active | draining | ejected
            registry=r,
        )
        self.fleet_replica_occupancy = Gauge(
            "fleet_replica_occupancy",
            "Active decode slots per fleet replica",
            ["replica"],
            registry=r,
        )
        self.fleet_replica_inflight = Gauge(
            "fleet_replica_inflight",
            "Fleet requests currently dispatched to each replica",
            ["replica"],
            registry=r,
        )
        self.fleet_replica_breaker = Gauge(
            "fleet_replica_breaker_state",
            "Per-replica circuit breaker (0=closed, 1=half-open, 2=open)",
            ["replica"],
            registry=r,
        )
        self.fleet_migrations = Counter(
            "fleet_migrations_total",
            "Requests migrated across replicas (crash failover + drains)",
            registry=r,
        )
        self.fleet_migrated_tokens = Counter(
            "fleet_migrated_tokens_total",
            "Generated tokens carried across replica migrations",
            registry=r,
        )
        self.fleet_hedges = Counter(
            "fleet_hedges_total",
            "Hedged re-dispatches fired past FLEET_HEDGE_MS",
            registry=r,
        )
        self.fleet_drains = Counter(
            "fleet_drains_total",
            "Voluntary replica drains started",
            registry=r,
        )
        self.fleet_ejects = Counter(
            "fleet_ejects_total",
            "Replicas ejected from rotation (evictions)",
            registry=r,
        )
        self.fleet_rejoins = Counter(
            "fleet_rejoins_total",
            "Replicas restarted and returned to rotation",
            registry=r,
        )
        self._fleet_seen = {"migrations": 0, "migrated_tokens": 0,
                            "hedges": 0, "drains": 0, "ejects": 0,
                            "rejoins": 0}

        # QoS ring (ISSUE 7, engine/qos.py): per-lane queue depth and
        # slot occupancy gauges (the ``lane`` label is the closed
        # three-lane set — cardinality bounded by construction; tenants
        # are deliberately NEVER labels), the brownout level, and the
        # preemption/expiry/displacement counters, delta-mirrored from
        # stats()["qos"] like the pipeline/containment totals.
        self.qos_queue_depth = Gauge(
            "qos_queue_depth",
            "Requests waiting for a decode slot, by priority lane",
            ["lane"],
            registry=r,
        )
        self.qos_lane_occupancy = Gauge(
            "qos_lane_occupancy",
            "Decode slots held, by priority lane",
            ["lane"],
            registry=r,
        )
        self.qos_brownout_level = Gauge(
            "qos_brownout_level",
            "AIMD brownout level (0=none, 1=background trimmed, "
            "2=batch trimmed too)",
            registry=r,
        )
        self.preemptions = Counter(
            "qos_preemptions_total",
            "Running requests preempted out of their slot for a "
            "starved higher lane (export/replay path)",
            registry=r,
        )
        self.preempted_tokens = Counter(
            "qos_preempted_tokens_total",
            "Generated tokens carried across preempt-and-replay",
            registry=r,
        )
        self.queue_expired = Counter(
            "queue_expired_total",
            "Queued requests purged at scan time because their deadline "
            "passed (they no longer occupy MAX_QUEUE_DEPTH)",
            registry=r,
        )
        self.queue_displaced = Counter(
            "queue_displaced_total",
            "Queued requests displaced from a full queue in favour of a "
            "quieter tenant's arrival (shed prefers the flooding tenant)",
            registry=r,
        )
        self._qos_seen = {"preemptions": 0, "preempted_tokens": 0,
                          "expired": 0, "displaced": 0}

        # Goodput ledger (ISSUE 8, obs/ledger.py): every device decode
        # step classified delivered | replayed | preempted | hedge_loser
        # | wasted_masked | quarantine_burn, per priority lane. Both
        # label sets are closed (three lanes, six classes) so
        # cardinality is bounded by construction; tenants are
        # deliberately NEVER labels — the per-tenant breakdown lives
        # behind /debug/ledger only. Delta-mirrored from
        # stats()["ledger"] like the pipeline/containment totals.
        self.goodput_steps = Counter(
            "goodput_steps_total",
            "Device decode steps by accounting class and lane "
            "(delivered = goodput; the rest are waste classes)",
            ["lane", "class"],
            registry=r,
        )
        self.goodput_ratio = Gauge(
            "goodput_ratio",
            "Delivered fraction of all accounted device steps, by lane",
            ["lane"],
            registry=r,
        )
        self._ledger_seen: dict = {}

        # SLO burn-rate engine (ISSUE 8, obs/slo.py): multi-window
        # error-budget burn for TTFT and queue wait per lane. ``slo``
        # and ``lane`` are closed sets; ``window`` values come from
        # SLO_WINDOWS, validated to at most obs.slo.MAX_WINDOWS at boot.
        self.slo_burn_rate = Gauge(
            "slo_burn_rate",
            "Error-budget burn rate over the window (1.0 = spending "
            "exactly at the objective's sustainable rate)",
            ["slo", "lane", "window"],
            registry=r,
        )
        self.slo_budget_remaining = Gauge(
            "slo_error_budget_remaining",
            "Unspent fraction of the window's error budget (floor 0)",
            ["slo", "lane", "window"],
            registry=r,
        )
        self.slo_breaches = Counter(
            "slo_breaches_total",
            "Latency samples that breached their SLO target",
            ["slo", "lane"],
            registry=r,
        )
        self._slo_seen: dict = {}

        # Grammar-constrained decoding (ISSUE 11, constrain/): tokens
        # delivered by forced-run fast-forward splices vs sampled under
        # the device-side mask, and FSM dead ends by cause (``cause``
        # is a closed small set: decode | admission). Delta-mirrored
        # from stats()["grammar"] like the pipeline totals.
        self.grammar_forced_tokens = Counter(
            "grammar_forced_tokens_total",
            "Tokens delivered by forced-run fast-forward splices "
            "(single-successor FSM chains written as one suffix "
            "prefill instead of decoded token-by-token)",
            registry=r,
        )
        self.grammar_masked_steps = Counter(
            "grammar_masked_steps_total",
            "Decode steps sampled under the grammar's device-side "
            "logit mask",
            registry=r,
        )
        self.grammar_dead_ends = Counter(
            "grammar_dead_end_total",
            "Slots frozen in a grammar dead end (no legal token from "
            "the current FSM state)",
            ["cause"],
            registry=r,
        )
        self._grammar_seen = {"forced": 0, "masked": 0, "dead": {}}

        # Speculative decoding (ISSUE 12, engine/batcher.py): draft
        # proposals vs verifier acceptances, and the derived acceptance
        # ratio — the first-class signal of whether the 2B is actually
        # buying the 7B extra tokens per weight read. Delta-mirrored
        # from stats()["spec"] like the grammar totals; the ratio is a
        # gauge set from the cumulative counters at scrape time.
        self.spec_drafted_tokens = Counter(
            "spec_drafted_tokens_total",
            "Draft-model token proposals submitted to the verifier",
            registry=r,
        )
        self.spec_accepted_tokens = Counter(
            "spec_accepted_tokens_total",
            "Draft proposals the target model's verify step accepted "
            "(each one is a transcript token that cost no extra "
            "target forward)",
            registry=r,
        )
        self.spec_acceptance_ratio = Gauge(
            "spec_acceptance_ratio",
            "Cumulative accepted/drafted ratio of speculative decoding",
            registry=r,
        )
        self._spec_seen = {"drafted": 0, "accepted": 0}

        # Zero-downtime weight rollout (ISSUE 13, engine/rollout.py):
        # the state machine's current state (encoded by index into the
        # closed ROLLOUT_STATES set), replicas by serving weights
        # version (cardinality bounded per scrape — stale version
        # labels are zeroed, and at most FLEET_SIZE+1 versions can be
        # live at once), and automatic rollbacks by cause (closed
        # ROLLBACK_CAUSES set), delta-mirrored from the controller's
        # cumulative totals like every other subsystem.
        self.rollout_state = Gauge(
            "rollout_state",
            "Weight-rollout state machine position (0=idle, 1=draining, "
            "2=swapping, 3=warming, 4=observing, 5=promoting, "
            "6=rolling_back, 7=rolled_back, 8=complete, 9=failed)",
            registry=r,
        )
        self.rollout_replicas = Gauge(
            "rollout_replicas",
            "Fleet replicas by the checkpoint version they serve",
            ["version"],
            registry=r,
        )
        self.rollout_rollbacks = Counter(
            "rollout_rollbacks_total",
            "Automatic weight-rollout rollbacks",
            ["cause"],
            registry=r,
        )
        self._rollout_seen: dict = {}
        self._rollout_versions_seen: set = set()

        # Perf-regression sentinel (ISSUE 15, obs/steptime.py): per-
        # (phase, bucket) step-time quantiles set at scrape time from
        # the engine's bounded digests. ``phase`` is the closed
        # obs.STEP_PHASES set; ``bucket`` values come from the engine's
        # KV/prefill bucket ladders — cardinality bounded by config,
        # like the SLO windows. The breach-trip counter delta-mirrors
        # the sentinel's edge-triggered total.
        self.step_time = Gauge(
            "step_time_seconds",
            "Per-chunk device step time quantiles by phase and bucket "
            "(p50 | p95 | p99 over the sentinel's trailing window)",
            ["phase", "bucket", "quantile"],
            registry=r,
        )
        self.step_tokens_per_sec = Gauge(
            "step_tokens_per_sec",
            "Trailing tokens/sec produced at this (phase, bucket) rung",
            ["phase", "bucket"],
            registry=r,
        )
        self.steptime_trips = Counter(
            "steptime_breach_trips_total",
            "Step-time sentinel breach transitions (p99 crossed the "
            "baseline envelope; edge-triggered, not per scrape)",
            registry=r,
        )
        self._steptime_seen = 0

        # Incident capture (ISSUE 15, obs/incidents.py): bundles
        # captured vs suppressed-by-cooldown, by trigger (the closed
        # obs.incidents.TRIGGERS set).
        self.incidents_captured = Counter(
            "incidents_captured_total",
            "Incident bundles assembled into the /debug/incidents ring",
            ["trigger"],
            registry=r,
        )
        self.incidents_suppressed = Counter(
            "incidents_suppressed_total",
            "Trigger firings swallowed by the per-trigger cooldown "
            "(counted, never captured — bounds capture overhead)",
            ["trigger"],
            registry=r,
        )
        self._incidents_seen = {"captured": {}, "suppressed": {}}

        # Request-lifecycle phase attribution (obs/trace.py): where a
        # request's wall time went. The ``phase`` label is drawn from the
        # fixed obs.PHASES allowlist — cardinality is bounded by
        # construction, a span with any other name is never observed here.
        self.request_phase = Histogram(
            "request_phase_seconds",
            "Per-request time spent in each lifecycle phase",
            ["phase"],
            buckets=_PHASE_BUCKETS,
            registry=r,
        )

    def observe_pipeline(self, stats: dict) -> None:
        """Mirror the batcher's decode-pipeline stats into Prometheus at
        scrape time: gauges set directly, cumulative scheduler totals
        turned into counter increments (the engine owns the running
        total; a scrape only publishes the delta since the last one), and
        drained chunk-fetch samples observed into the histogram."""
        self.pipe_occupancy.set(stats.get("pipe_inflight", 0))
        self.pipe_depth.set(stats.get("pipe_depth", 0))
        self.device_active_slots.set(stats.get("device_active_slots", 0))
        wasted = stats.get("wasted_decode_steps", 0)
        if wasted > self._pipe_seen["wasted"]:
            self.wasted_decode_steps.inc(wasted - self._pipe_seen["wasted"])
            self._pipe_seen["wasted"] = wasted
        for event, key in (("dispatch", "chunks_dispatched"),
                           ("consume", "chunks_consumed"),
                           ("prune", "chunks_pruned")):
            total = stats.get(key, 0)
            if total > self._pipe_seen[event]:
                self.decode_chunks.labels(event=event).inc(
                    total - self._pipe_seen[event])
                self._pipe_seen[event] = total
        for s in stats.get("chunk_fetch_secs", ()):
            self.chunk_fetch.observe(s)

    def observe_kv_pool(self, pool: dict) -> None:
        """Mirror the engine's KV-pool stats (stats()["kv_pool"]) into
        Prometheus at scrape time — block-state gauges set directly,
        cumulative sharing/COW/radix totals delta-inc'd like the
        pipeline/containment mirrors."""
        for state in ("free", "live", "cached"):
            self.kv_pool_blocks.labels(state=state).set(pool.get(state, 0))
        # ISSUE 19: single-chip engines surface the attention regime on
        # the pool body (sharding_health is None without a mesh) — the
        # mesh path sets the same gauge from observe_sharding.
        self._set_attention_regime(pool.get("attention_regime"))
        seen = self._kv_pool_seen
        radix = pool.get("radix") or {}
        for key, counter, total in (
                ("shared", self.kv_blocks_shared,
                 pool.get("shared_mapped_total", 0)),
                ("cow", self.kv_cow_copies,
                 pool.get("cow_copies_total", 0)),
                ("hit", self.radix_hit_tokens, radix.get("hit_tokens", 0)),
                ("miss", self.radix_miss_tokens,
                 radix.get("miss_tokens", 0))):
            if total > seen[key]:
                counter.inc(total - seen[key])
                seen[key] = total
        # Two-tier host tier (ISSUE 20): absent when HOST_KV_BLOCKS=0 —
        # the gauges/counters simply never move.
        host = pool.get("host_tier")
        if host:
            self.kv_host_blocks.labels(state="used").set(
                host.get("used", 0))
            self.kv_host_blocks.labels(state="free").set(
                host.get("free", 0))
            fails = host.get("onload_fail_total") or {}
            for key, counter, total in (
                    ("demoted", self.kv_blocks_demoted,
                     host.get("demoted_total", 0)),
                    ("onloaded", self.kv_blocks_onloaded,
                     host.get("onloaded_total", 0)),
                    ("dropped", self.kv_host_dropped,
                     host.get("dropped_total", 0)),
                    ("fail_corrupt",
                     self.kv_onload_fail.labels(cause="corrupt"),
                     fails.get("corrupt", 0)),
                    ("fail_exhausted",
                     self.kv_onload_fail.labels(cause="exhausted"),
                     fails.get("exhausted", 0))):
                if total > seen[key]:
                    counter.inc(total - seen[key])
                    seen[key] = total

    def observe_latent_attention(self, lat: dict) -> None:
        """Mirror /health.latent_attention at scrape time: the row's bytes
        a gauge, the cumulative rows delta-inc'd like the pool's."""
        self.latent_row_bytes.set(lat.get("row_bytes", 0))
        for kind in ("decode_rows", "latent_rows_read",
                     "window_rows_absorbed", "window_rows_expanded"):
            total = lat.get(kind, 0)
            if total > self._latent_seen.get(kind, 0):
                self.latent_rows.labels(kind=kind).inc(
                    total - self._latent_seen.get(kind, 0))
                self._latent_seen[kind] = total

    def observe_state_cache(self, ssm: dict) -> None:
        """Mirror /health.ssm (stats()["ssm"]) at scrape time: gauges set
        directly, cumulative totals delta-inc'd like the pool's."""
        self.state_snapshots.labels(state="held").set(
            ssm.get("snapshots_held", 0))
        self.state_snapshots.labels(state="capacity").set(
            ssm.get("capacity", 0))
        self.state_snapshots.labels(state="pinned").set(
            ssm.get("snapshots_pinned", 0))
        self.state_snapshots.labels(state="restore_depth_peak").set(
            ssm.get("restore_depth_peak", 0))
        counters = [(e, self.state_cache_events.labels(event=e), e)
                    for e in ("snapshots_taken", "snapshots_evicted",
                              "snapshots_skipped", "restores")]
        counters += [
            ("usable", self.state_prefix_tokens.labels(outcome="usable"),
             "prefix_tokens_usable"),
            ("recomputed",
             self.state_prefix_tokens.labels(outcome="recomputed"),
             "prefix_tokens_recomputed"),
            ("bytes", self.state_bytes_moved, "state_bytes_moved")]
        for key, counter, field in counters:
            total = ssm.get(field, 0)
            if total > self._state_seen.get(key, 0):
                counter.inc(total - self._state_seen.get(key, 0))
                self._state_seen[key] = total

    def observe_sharding(self, sharding: dict) -> None:
        """Mirror the engine's sharding view (stats()["sharding"],
        ISSUE 14) into Prometheus at scrape time — plain gauges (all
        three are config-derived states, not cumulative totals)."""
        self.mesh_devices.set(sharding.get("devices", 0))
        self.sharding_residual_fraction.set(
            sharding.get("residual_tp_fraction", 0.0))
        self.kv_pool_mesh_fallback.set(
            1 if sharding.get("kv_pool_mesh_fallback") else 0)
        self.spec_draft_sharded.set(
            1 if sharding.get("draft_sharded") else 0)
        self.spec_draft_kv_fallback.set(
            1 if sharding.get("draft_kv_fallback") else 0)
        self._set_attention_regime(sharding.get("attention_regime"))

    def _set_attention_regime(self, active) -> None:
        if not active:
            return
        for regime in REGIMES:
            self.decode_attention_regime.labels(regime=regime).set(
                1 if regime == active else 0)

    def observe_containment(self, stats: dict) -> None:
        """Delta-mirror the engine supervisor's containment totals
        (stats()["containment"]) into the labelled Prometheus counters —
        same scrape-time pattern as ``observe_pipeline``."""
        c = stats.get("containment")
        if not c:
            return
        seen = self._containment_seen
        for cause, total in c.get("resets", {}).items():
            prev = seen["resets"].get(cause, 0)
            if total > prev:
                self.engine_resets.labels(cause=cause).inc(total - prev)
                seen["resets"][cause] = total
        for reason, total in c.get("quarantined", {}).items():
            prev = seen["quarantined"].get(reason, 0)
            if total > prev:
                self.quarantined_requests.labels(reason=reason).inc(
                    total - prev)
                seen["quarantined"][reason] = total
        for key, counter in (("health_trips", self.slot_health_trips),
                             ("replayed_tokens", self.replayed_tokens)):
            total = c.get(key, 0)
            if total > seen[key]:
                counter.inc(total - seen[key])
                seen[key] = total

    #: breaker-state encoding for the per-replica gauge (kept inline —
    #: importing server.breaker here would be a layering inversion).
    _BREAKER_CODES = {"closed": 0, "half-open": 1, "open": 2}

    def observe_fleet(self, fleet: dict) -> None:
        """Mirror the fleet rollup (stats()["fleet"]) into Prometheus at
        scrape time — gauges set directly, cumulative fleet counters
        delta-inc'd like the pipeline/containment totals."""
        for state in ("active", "draining", "ejected"):
            self.fleet_replicas.labels(state=state).set(
                fleet.get(state, 0))
        for rep in fleet.get("replicas", ()):
            label = str(rep.get("replica", "?"))
            self.fleet_replica_occupancy.labels(replica=label).set(
                rep.get("occupancy", 0))
            self.fleet_replica_inflight.labels(replica=label).set(
                rep.get("inflight", 0))
            self.fleet_replica_breaker.labels(replica=label).set(
                self._BREAKER_CODES.get(rep.get("breaker"), 0))
        seen = self._fleet_seen
        for key, counter in (("migrations", self.fleet_migrations),
                             ("migrated_tokens", self.fleet_migrated_tokens),
                             ("hedges", self.fleet_hedges),
                             ("drains", self.fleet_drains),
                             ("ejects", self.fleet_ejects),
                             ("rejoins", self.fleet_rejoins)):
            total = fleet.get(key, 0)
            if total > seen[key]:
                counter.inc(total - seen[key])
                seen[key] = total

    def observe_qos(self, qos: dict) -> None:
        """Mirror the engine's QoS stats (stats()["qos"]) into
        Prometheus at scrape time — gauges set directly, cumulative
        totals delta-inc'd like the pipeline/containment mirrors."""
        for lane, n in (qos.get("lane_depth") or {}).items():
            self.qos_queue_depth.labels(lane=lane).set(n)
        for lane, n in (qos.get("lane_occupancy") or {}).items():
            self.qos_lane_occupancy.labels(lane=lane).set(n)
        self.qos_brownout_level.set(qos.get("brownout_level", 0))
        seen = self._qos_seen
        for key, counter in (("preemptions", self.preemptions),
                             ("preempted_tokens", self.preempted_tokens),
                             ("expired", self.queue_expired),
                             ("displaced", self.queue_displaced)):
            total = qos.get(key, 0)
            if total > seen[key]:
                counter.inc(total - seen[key])
                seen[key] = total

    def observe_ledger(self, ledger: dict) -> None:
        """Mirror the goodput ledger's lane table (stats()["ledger"])
        into Prometheus at scrape time — per-(lane, class) cumulative
        totals delta-inc'd, the per-lane goodput ratio set directly."""
        from ..obs.ledger import LEDGER_CLASSES

        for lane, row in (ledger.get("lanes") or {}).items():
            seen = self._ledger_seen.setdefault(lane, {})
            for cls in LEDGER_CLASSES:
                total = row.get(cls, 0)
                prev = seen.get(cls, 0)
                if total > prev:
                    # positional labels: "class" is a Python keyword.
                    self.goodput_steps.labels(lane, cls).inc(total - prev)
                    seen[cls] = total
            lane_total = row.get("total", 0)
            if lane_total:
                self.goodput_ratio.labels(lane=lane).set(
                    row.get("delivered", 0) / lane_total)

    def observe_grammar(self, grammar: dict) -> None:
        """Delta-mirror the engine's grammar totals
        (stats()["grammar"]) into Prometheus at scrape time — same
        pattern as the pipeline/containment mirrors."""
        seen = self._grammar_seen
        for key, counter, total in (
                ("forced", self.grammar_forced_tokens,
                 grammar.get("forced_tokens_total", 0)),
                ("masked", self.grammar_masked_steps,
                 grammar.get("masked_steps_total", 0))):
            if total > seen[key]:
                counter.inc(total - seen[key])
                seen[key] = total
        for cause, total in (grammar.get("dead_ends_total") or {}).items():
            prev = seen["dead"].get(cause, 0)
            if total > prev:
                self.grammar_dead_ends.labels(cause=cause).inc(
                    total - prev)
                seen["dead"][cause] = total

    def observe_spec(self, spec: dict) -> None:
        """Delta-mirror the engine's speculative-decode totals
        (stats()["spec"]) into Prometheus at scrape time — counters
        delta-inc'd like the grammar mirror, the acceptance ratio set
        as a gauge from the cumulative totals."""
        seen = self._spec_seen
        for key, counter, total in (
                ("drafted", self.spec_drafted_tokens,
                 spec.get("drafted_tokens_total", 0)),
                ("accepted", self.spec_accepted_tokens,
                 spec.get("accepted_tokens_total", 0))):
            if total > seen[key]:
                counter.inc(total - seen[key])
                seen[key] = total
        drafted = spec.get("drafted_tokens_total", 0)
        if drafted:
            self.spec_acceptance_ratio.set(
                spec.get("accepted_tokens_total", 0) / drafted)

    def observe_rollout(self, rollout: dict) -> None:
        """Mirror the rollout controller's health view into Prometheus
        at scrape time — state gauge set by index, per-version replica
        counts set (stale version labels zeroed so a completed rollout
        doesn't leave the old version reading 1 forever), rollback
        causes delta-inc'd."""
        from ..engine.rollout import ROLLOUT_STATES

        try:
            code = ROLLOUT_STATES.index(rollout.get("state", "idle"))
        except ValueError:   # pragma: no cover - future state
            code = 0
        self.rollout_state.set(code)
        versions: dict = {}
        for v in (rollout.get("replica_versions") or {}).values():
            if v:
                versions[v] = versions.get(v, 0) + 1
        for v, n in versions.items():
            self.rollout_replicas.labels(version=v).set(n)
            self._rollout_versions_seen.add(v)
        for v in self._rollout_versions_seen - set(versions):
            self.rollout_replicas.labels(version=v).set(0)
        for cause, total in (rollout.get("rollbacks_total")
                             or {}).items():
            prev = self._rollout_seen.get(cause, 0)
            if total > prev:
                self.rollout_rollbacks.labels(cause=cause).inc(
                    total - prev)
                self._rollout_seen[cause] = total

    def observe_steptime(self, st: dict) -> None:
        """Mirror the step-time sentinel snapshot (stats()["steptime"])
        into Prometheus at scrape time — quantile/rate gauges set
        directly, the edge-triggered trip total delta-inc'd."""
        for d in (st.get("digests") or {}).values():
            phase = str(d.get("phase", "?"))
            bucket = str(d.get("bucket", "?"))
            for q, key in (("p50", "p50_ms"), ("p95", "p95_ms"),
                           ("p99", "p99_ms")):
                self.step_time.labels(
                    phase=phase, bucket=bucket, quantile=q).set(
                    float(d.get(key, 0.0)) / 1000.0)
            self.step_tokens_per_sec.labels(
                phase=phase, bucket=bucket).set(d.get("tok_s", 0.0))
        total = int(st.get("trips_total", 0))
        if total > self._steptime_seen:
            self.steptime_trips.inc(total - self._steptime_seen)
            self._steptime_seen = total

    def observe_incidents(self, snap: dict) -> None:
        """Delta-mirror the incident manager's captured/suppressed
        totals (by trigger) into Prometheus at scrape time."""
        seen = self._incidents_seen
        for key, counter in (("captured", self.incidents_captured),
                             ("suppressed", self.incidents_suppressed)):
            for trigger, total in (snap.get(f"{key}_total")
                                   or {}).items():
                prev = seen[key].get(trigger, 0)
                if total > prev:
                    counter.labels(trigger=trigger).inc(total - prev)
                    seen[key][trigger] = total

    def observe_slo(self, slo: dict) -> None:
        """Mirror the SLO burn snapshot (stats()["slo"]) into
        Prometheus: per-window burn/budget gauges set directly,
        cumulative breach counts delta-inc'd."""
        for name, body in (slo.get("slos") or {}).items():
            for lane, row in (body.get("lanes") or {}).items():
                for window, win in (row.get("windows") or {}).items():
                    self.slo_burn_rate.labels(
                        slo=name, lane=lane, window=window).set(
                        win.get("burn_rate", 0.0))
                    self.slo_budget_remaining.labels(
                        slo=name, lane=lane, window=window).set(
                        win.get("budget_remaining", 1.0))
                total = row.get("breaches_total", 0)
                prev = self._slo_seen.get((name, lane), 0)
                if total > prev:
                    self.slo_breaches.labels(slo=name, lane=lane).inc(
                        total - prev)
                    self._slo_seen[(name, lane)] = total

    def render(self) -> bytes:
        return generate_latest(self.registry)
