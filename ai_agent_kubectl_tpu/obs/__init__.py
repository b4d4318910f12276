"""Observability subsystem: request-lifecycle tracing, flight recorder,
and on-demand TPU profiling.

Zero-dependency (stdlib only) by design — the trace context is touched on
the serving hot path and from the batch scheduler thread, so it must never
import jax, aiohttp, or prometheus_client. Three pieces:

- ``obs.trace`` — request ID + span API with monotonic timestamps. The
  active trace travels via a ``contextvars.ContextVar`` through the async
  serving path (middleware → cache → breaker → engine submit → executor)
  and by explicit reference through the batch scheduler's admission queue
  (``_Request.trace``), whose worker thread annotates it lock-safely. The
  engine's side is stamped where the work happens: ``RequestSpans`` (a
  request's phases, ``prefill`` with children), ``SchedSpans`` (the
  scheduler thread's ``sched/*`` intervals and its wall time by state)
  and ``SpanStats`` (the totals ``/health.spans`` serves).
- ``obs.recorder`` — ring-buffer flight recorder keeping the full span
  timeline of the last N finished requests (including shed / degraded /
  errored ones), served by ``/debug/requests[/{id}]``.
- ``obs.profiler`` — on-demand ``jax.profiler`` device-trace capture for
  ``POST /debug/profile`` (token-gated), so a TPU trace can be grabbed
  from a live server without restarting it.
- ``obs.ledger`` — the goodput ledger: every device decode step a
  request cost, classified ``delivered | replayed | preempted |
  hedge_loser | wasted_masked | quarantine_burn`` per lane (and per
  hashed tenant behind ``/debug/ledger`` only), with a conservation
  invariant the chaos suite asserts.
- ``obs.slo`` — multi-window (5m/1h) error-budget burn rates for TTFT
  and queue wait per lane, exported as ``slo_*`` gauges and a ``/health``
  section, and consumable by the QoS brownout controller.
- ``obs.steptime`` — the perf-regression sentinel's digests: per-chunk
  step time keyed by (phase, bucket), p50/p95/p99 gauges, trailing
  tok/s per rung, and online breach detection against a boot-loaded
  baseline envelope (``PERF_BASELINES``) or a self-calibrated one.
- ``obs.incidents`` — anomaly-triggered incident capture: a firing
  trigger (step-time breach, burn spike, quarantine/dead-end spike,
  pool exhaustion, breaker open) assembles a bounded evidence bundle
  into a ring behind ``/debug/incidents``, with per-trigger cooldowns.
"""

from .incidents import TRIGGERS, IncidentManager, current_incident_id
from .ledger import (LEDGER_CLASSES, WASTE_CLASSES, GoodputLedger,
                     hash_tenant)
from .recorder import FlightRecorder
from .slo import SLO_QUEUE_WAIT, SLO_TTFT, SloEngine, parse_slo_windows
from .steptime import (PHASE_DECODE, PHASE_PREFILL, PHASE_SPEC_VERIFY,
                       STEP_PHASES, StepTimeSentinel, load_baselines,
                       prefill_bucket)
from .trace import (PHASES, Trace, current_trace, new_request_id,
                    sanitize_request_id, trace_event, use_trace)

__all__ = [
    "PHASES",
    "LEDGER_CLASSES",
    "WASTE_CLASSES",
    "PHASE_DECODE",
    "PHASE_PREFILL",
    "PHASE_SPEC_VERIFY",
    "SLO_QUEUE_WAIT",
    "SLO_TTFT",
    "STEP_PHASES",
    "TRIGGERS",
    "FlightRecorder",
    "GoodputLedger",
    "IncidentManager",
    "SloEngine",
    "StepTimeSentinel",
    "Trace",
    "current_incident_id",
    "current_trace",
    "hash_tenant",
    "load_baselines",
    "new_request_id",
    "parse_slo_windows",
    "prefill_bucket",
    "sanitize_request_id",
    "trace_event",
    "use_trace",
]
