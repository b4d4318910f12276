"""Serving-path attribution probe.

Two measurements of the REAL engine (QUANT / KV_QUANT / prefix cache /
scheduler included) outside the benchmark's cells:

1. **Decode-chunk device ceiling**: chained dispatches of the engine's own
   compiled batch-chunk programs, per KV-ladder bucket — the marginal
   ms/step with host round trips amortized away, and the tok/s ceiling
   the scheduler is chasing.
2. **Burst attribution**: N concurrent requests through ``generate()``,
   reporting group-admission counts and per-request queue/prefill/decode
   spans — how much of wall-clock is ramp vs decode (this is the probe
   that exposed the round-4 admission stagger and validated the
   burst-ramp fix).

Plus an HTTP mode (``--url``) that probes a *running server* instead of
building an engine: it fires N requests, prints each response's
``Server-Timing`` phase breakdown (the obs/trace.py span timeline), and
ends with a p50/p95/p99 per-phase summary table. Both modes end with the
percentile table.

Usage (on a TPU host; defaults reproduce the 7B north-star config):
    python tools/probe_serving.py
    python tools/probe_serving.py --model gemma-2b-it --dtype bfloat16 \
        --quant "" --kv-quant "" --bs 64 --max-seq 1024
    python tools/probe_serving.py --url http://localhost:8000 --requests 32
"""

from __future__ import annotations

import argparse
import asyncio
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def log(msg: str) -> None:
    print(msg, flush=True)


def percentile(values: List[float], p: float) -> float:
    """Nearest-rank percentile on a sorted copy; good enough for a probe."""
    if not values:
        return 0.0
    vs = sorted(values)
    idx = min(len(vs) - 1, max(0, int(round(p / 100.0 * (len(vs) - 1)))))
    return vs[idx]


def parse_server_timing(header: str) -> Dict[str, float]:
    """``queue_wait;dur=1.20, decode;dur=48.01`` → {phase: ms}."""
    out: Dict[str, float] = {}
    for part in header.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, rest = part.partition(";")
        for attr in rest.split(";"):
            k, _, v = attr.strip().partition("=")
            if k == "dur":
                try:
                    out[name.strip()] = float(v)
                except ValueError:
                    pass
    return out


def print_phase_summary(samples: Dict[str, List[float]]) -> None:
    """p50/p95/p99 per-phase table over every collected request."""
    if not samples:
        log("probe[summary]: no phase samples collected")
        return
    n = max(len(v) for v in samples.values())
    log(f"probe[summary]: per-phase latency over {n} requests (ms)")
    log(f"  {'phase':<12} {'p50':>9} {'p95':>9} {'p99':>9} {'max':>9}")
    for phase, vals in samples.items():
        log(f"  {phase:<12} {percentile(vals, 50):>9.1f} "
            f"{percentile(vals, 95):>9.1f} {percentile(vals, 99):>9.1f} "
            f"{max(vals):>9.1f}")


def parse_prom_gauges(text: str) -> Dict[str, float]:
    """Minimal Prometheus exposition parse: unlabelled samples only (the
    pipeline gauges/counters the probe prints are all unlabelled)."""
    out: Dict[str, float] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        if not name:
            continue
        try:
            out[name.strip()] = float(value)
        except ValueError:
            pass
    return out


async def print_pipeline_summary(session, base_url: str, headers) -> None:
    """Wasted-chunk rate + pipe-depth occupancy from /metrics (ISSUE 4):
    how much of the decode pipeline's speculative work was thrown away,
    and how full the inflight window actually runs."""
    try:
        async with session.get(base_url + "/metrics",
                               headers=headers) as resp:
            gauges = parse_prom_gauges(await resp.text())
    except Exception as e:  # pragma: no cover - network-dependent
        log(f"probe[pipeline]: /metrics unreachable ({e})")
        return
    consumed = gauges.get('decode_chunks_total{event="consume"}', 0.0)
    wasted = gauges.get("wasted_decode_steps_total", 0.0)
    depth = gauges.get("decode_pipe_depth", 0.0)
    occ = gauges.get("decode_pipe_occupancy", 0.0)
    if not depth and "engine_batch_occupancy" not in gauges:
        log("probe[pipeline]: no decode-pipeline metrics exposed "
            "(engine without the chunked scheduler?)")
        return
    log("probe[pipeline]: decode pipeline")
    log(f"  pipe depth (configured)     {depth:>8.0f}")
    log(f"  pipe occupancy (now)        {occ:>8.0f}")
    log(f"  device live slots (n_alive) "
        f"{gauges.get('decode_device_active_slots', 0.0):>8.0f}")
    log(f"  wasted decode steps total   {wasted:>8.0f}")
    if consumed:
        log(f"  wasted steps / consumed chunk {wasted / consumed:>8.2f}")
    print_containment_summary(gauges)
    print_attention_regime(gauges)
    print_mesh_summary(gauges)
    print_kv_pool_summary(gauges)
    print_grammar_summary(gauges)
    print_fleet_summary(gauges)
    print_rollout_summary(gauges)
    print_qos_summary(gauges)
    print_goodput_summary(gauges)
    print_spec_summary(gauges)
    print_slo_summary(gauges)
    print_steptime_summary(gauges)


def _sum_labelled(gauges: Dict[str, float], name: str) -> Dict[str, float]:
    """All samples of a labelled counter: {'cause="x"': v, ...} summed by
    the (single) label value; the bare name matches unlabelled series."""
    out: Dict[str, float] = {}
    for key, v in gauges.items():
        if key == name:
            out[""] = v
        elif key.startswith(name + "{"):
            out[key[len(name) + 1:-1]] = v
    return out


def print_containment_summary(gauges: Dict[str, float]) -> None:
    """Reset/quarantine counters (ISSUE 5 inner ring) from the same
    /metrics scrape: how often the engine reset-and-replayed, why, how
    many requests were terminally quarantined, and how many
    already-generated tokens were regenerated for innocent victims."""
    resets = _sum_labelled(gauges, "engine_resets_total")
    quar = _sum_labelled(gauges, "quarantined_requests_total")
    trips = gauges.get("slot_health_trips_total")
    if trips is None and not resets and not quar:
        return      # engine without the containment subsystem
    log("probe[containment]: blast-radius containment")
    log(f"  engine resets total         {sum(resets.values()):>8.0f}"
        + (f"  ({', '.join(f'{k}={v:.0f}' for k, v in resets.items())})"
           if resets else ""))
    log(f"  quarantined requests total  {sum(quar.values()):>8.0f}"
        + (f"  ({', '.join(f'{k}={v:.0f}' for k, v in quar.items())})"
           if quar else ""))
    log(f"  slot health trips total     {trips or 0:>8.0f}")
    log(f"  replayed tokens total       "
        f"{gauges.get('replayed_tokens_total', 0.0):>8.0f}")


def print_attention_regime(gauges: Dict[str, float]) -> None:
    """Which attention path is actually serving decode: the enum gauge
    ``decode_attention_regime{regime=...}`` carries 1 on exactly one
    label — ragged (one kernel over the block pool), gather (the pool
    read through a dense gather: int8 KV, KV heads that don't divide
    tp, or no TPU), or dense (no block pool at all). /health carries
    the reason beside the regime."""
    regimes = _sum_labelled(gauges, "decode_attention_regime")
    active = [k.split("=")[-1].strip('"') for k, v in regimes.items()
              if v >= 1.0]
    if not active:
        return      # engine predating the regime gauge
    note = {"ragged": "one kernel for prefill/decode/verify",
            "gather": "block pool, KV gathered densely",
            "dense": "no block pool (dense KV ladder)"}
    log("probe[attention]: decode attention regime")
    for r in active:
        log(f"  regime                      {r:>8}  ({note.get(r, '?')})")


def print_mesh_summary(gauges: Dict[str, float]) -> None:
    """Tensor-parallel serving (ISSUE 14) from the same /metrics
    scrape: mesh size, the residual TP fraction the active policy
    achieves (1.0 = every residual-path tensor batch-sharded), and whether
    a requested KV pool silently fell back to the dense ladder."""
    devices = gauges.get("mesh_devices", 0.0)
    if not devices:
        return      # single-device serving (no mesh)
    frac = gauges.get("sharding_residual_fraction", 0.0)
    fallback = gauges.get("kv_pool_mesh_fallback", 0.0)
    log("probe[mesh]: tensor-parallel serving")
    log(f"  mesh devices                {devices:>8.0f}")
    log(f"  residual TP fraction (f)    {frac:>8.2f}")
    log(f"  kv pool mesh fallback       "
        f"{'YES (dense ladder!)' if fallback else 'no':>8}")
    # Spec×TP (ISSUE 18): whether the draft world rides this mesh
    # sharded, and whether its KV serves replicated (gather fallback).
    log(f"  draft sharded               "
        f"{'yes' if gauges.get('spec_draft_sharded') else 'no':>8}")
    log(f"  draft kv fallback           "
        f"{'YES (gathered!)' if gauges.get('spec_draft_kv_fallback') else 'no':>8}")


def print_kv_pool_summary(gauges: Dict[str, float]) -> None:
    """Block-paged KV pool + radix sharing (ISSUE 10) from the same
    /metrics scrape: pool occupancy by block state, sharing/COW totals,
    and the radix hit rate (tokens served from cached prefixes vs
    prefilled)."""
    states = _sum_labelled(gauges, "kv_pool_blocks")
    if not states:
        return      # dense-KV engine (KV_POOL=false / mesh / no batcher)
    total = sum(states.values())
    log("probe[kv_pool]: block-paged KV pool")
    log(f"  pool blocks total           {total:>8.0f}"
        + (f"  ({', '.join(f'{k}={v:.0f}' for k, v in sorted(states.items()))})"
           if states else ""))
    if total:
        free = states.get('state="free"', 0.0)
        log(f"  pool occupancy              {(total - free) / total:>8.1%}")
    log(f"  shared block mappings total "
        f"{gauges.get('kv_blocks_shared_total', 0.0):>8.0f}")
    log(f"  copy-on-write copies total  "
        f"{gauges.get('kv_cow_copies_total', 0.0):>8.0f}")
    hit = gauges.get("radix_hit_tokens_total", 0.0)
    miss = gauges.get("radix_miss_tokens_total", 0.0)
    log(f"  radix hit tokens total      {hit:>8.0f}")
    log(f"  radix miss tokens total     {miss:>8.0f}")
    if hit + miss:
        log(f"  radix hit rate              {hit / (hit + miss):>8.1%}")
    # Two-tier host offload (ISSUE 20): occupancy of the host-RAM block
    # store and how often a demoted chain came back (onloads / demotes).
    host = _sum_labelled(gauges, "kv_host_blocks")
    if host:
        h_total = sum(host.values())
        h_used = host.get('state="used"', 0.0)
        log(f"  host tier blocks total      {h_total:>8.0f}")
        if h_total:
            log(f"  host tier occupancy         {h_used / h_total:>8.1%}")
        demoted = gauges.get("kv_blocks_demoted_total", 0.0)
        onloaded = gauges.get("kv_blocks_onloaded_total", 0.0)
        log(f"  blocks demoted total        {demoted:>8.0f}")
        log(f"  blocks onloaded total       {onloaded:>8.0f}")
        if demoted:
            log(f"  onload hit rate             {onloaded / demoted:>8.1%}")


def print_grammar_summary(gauges: Dict[str, float]) -> None:
    """Grammar-constrained decoding (ISSUE 11) from the same /metrics
    scrape: forced vs masked token totals and the forced-token ratio —
    the fraction of generated tokens delivered by forced-run
    fast-forward splices instead of decode steps (the decode-step cut
    the subsystem exists for)."""
    forced = gauges.get("grammar_forced_tokens_total", 0.0)
    masked = gauges.get("grammar_masked_steps_total", 0.0)
    dead = _sum_labelled(gauges, "grammar_dead_end_total")
    if not (forced or masked or dead):
        return      # GRAMMAR_DECODE off
    log("probe[grammar]: grammar-constrained decode")
    log(f"  forced tokens total         {forced:>8.0f}")
    log(f"  masked decode steps total   {masked:>8.0f}")
    if forced + masked:
        log(f"  forced-token ratio          "
            f"{forced / (forced + masked):>8.1%}")
    for k, v in sorted(dead.items()):
        log(f"  dead ends {k:<17} {v:>8.0f}")


def print_fleet_summary(gauges: Dict[str, float]) -> None:
    """Engine-fleet counters (FLEET_SIZE > 1) from the same /metrics
    scrape: per-replica occupancy and breaker state, migration/eviction
    totals, and the hedge rate (hedges per consumed request-equivalent
    — how often the latency budget forced a second dispatch)."""
    states = _sum_labelled(gauges, "fleet_replicas")
    if not states:
        return      # single-engine deployment (no fleet layer)
    occ = _sum_labelled(gauges, "fleet_replica_occupancy")
    inflight = _sum_labelled(gauges, "fleet_replica_inflight")
    brk = _sum_labelled(gauges, "fleet_replica_breaker_state")
    brk_names = {0: "closed", 1: "half-open", 2: "open"}
    log("probe[fleet]: engine fleet")
    log("  replicas by state           "
        + ", ".join(f"{k.split('=')[-1].strip(chr(34))}={v:.0f}"
                    for k, v in sorted(states.items())))
    for key in sorted(occ):
        rep = key.split("=")[-1].strip('"')
        b = brk.get(key, 0.0)
        log(f"  replica {rep}: occupancy={occ[key]:.0f} "
            f"inflight={inflight.get(key, 0.0):.0f} "
            f"breaker={brk_names.get(int(b), '?')}")
    migrations = gauges.get("fleet_migrations_total", 0.0)
    hedges = gauges.get("fleet_hedges_total", 0.0)
    log(f"  migrations total            {migrations:>8.0f}"
        f"  ({gauges.get('fleet_migrated_tokens_total', 0.0):.0f} tokens "
        "carried)")
    log(f"  evictions (ejects) total    "
        f"{gauges.get('fleet_ejects_total', 0.0):>8.0f}"
        f"  (drains={gauges.get('fleet_drains_total', 0.0):.0f}, "
        f"rejoins={gauges.get('fleet_rejoins_total', 0.0):.0f})")
    consumed = gauges.get('decode_chunks_total{event="consume"}', 0.0)
    rate = f"  ({hedges / consumed:.4f}/chunk)" if consumed else ""
    log(f"  hedged dispatches total     {hedges:>8.0f}{rate}")


#: rollout_state gauge encoding (engine/rollout.py ROLLOUT_STATES).
_ROLLOUT_STATES = ("idle", "draining", "swapping", "warming", "observing",
                   "promoting", "rolling_back", "rolled_back", "complete",
                   "failed")


def print_rollout_summary(gauges: Dict[str, float]) -> None:
    """Weight-rollout view (ISSUE 13) from the same /metrics scrape:
    the state machine position, the per-version replica table (which
    checkpoint each part of the fleet serves), and rollbacks by cause
    — the zero-downtime-deploy dashboard next to the fleet view."""
    versions = _sum_labelled(gauges, "rollout_replicas")
    state = gauges.get("rollout_state")
    if state is None and not versions:
        return      # engine without weight-rollout support
    name = (_ROLLOUT_STATES[int(state)]
            if state is not None and 0 <= int(state) < len(_ROLLOUT_STATES)
            else "?")
    log("probe[rollout]: weight rollout")
    log(f"  state                       {name:>12}")
    for key in sorted(versions):
        ver = key.split("=")[-1].strip('"')
        if versions[key] > 0:
            log(f"  version {ver:<18} replicas={versions[key]:.0f}")
    rollbacks = _sum_labelled(gauges, "rollout_rollbacks_total")
    total = sum(rollbacks.values())
    causes = ", ".join(
        f"{k.split('=')[-1].strip(chr(34))}={v:.0f}"
        for k, v in sorted(rollbacks.items()) if v > 0)
    log(f"  rollbacks total             {total:>8.0f}"
        + (f"  ({causes})" if causes else ""))


def print_qos_summary(gauges: Dict[str, float]) -> None:
    """QoS ring (ISSUE 7) from the same /metrics scrape: per-lane queue
    depth and slot occupancy, preemption/expiry/displacement totals,
    and the active brownout level — the fairness view next to the
    throughput view."""
    depth = _sum_labelled(gauges, "qos_queue_depth")
    occ = _sum_labelled(gauges, "qos_lane_occupancy")
    if not depth and not occ:
        return      # engine without the QoS scheduler
    log("probe[qos]: QoS ring")
    for key in sorted(depth):
        lane = key.split("=")[-1].strip('"')
        log(f"  lane {lane:<12} queued={depth[key]:.0f} "
            f"slots={occ.get(key, 0.0):.0f}")
    level = gauges.get("qos_brownout_level", 0.0)
    level_name = {0: "none", 1: "background trimmed",
                  2: "batch trimmed"}.get(int(level), "?")
    log(f"  brownout level              {level:>8.0f}  ({level_name})")
    log(f"  preemptions total           "
        f"{gauges.get('qos_preemptions_total', 0.0):>8.0f}"
        f"  ({gauges.get('qos_preempted_tokens_total', 0.0):.0f} tokens "
        "carried)")
    log(f"  queue expired total         "
        f"{gauges.get('queue_expired_total', 0.0):>8.0f}")
    log(f"  queue displaced total       "
        f"{gauges.get('queue_displaced_total', 0.0):>8.0f}")


def _parse_labels(labelstr: str) -> Dict[str, str]:
    """``lane="interactive",class="delivered"`` → {lane: ..., class: ...}
    (the two-label series the goodput/slo summaries read)."""
    out: Dict[str, str] = {}
    for part in labelstr.split(","):
        k, _, v = part.partition("=")
        if k:
            out[k.strip()] = v.strip().strip('"')
    return out


#: goodput table column order — delivered first, then the waste classes.
_LEDGER_CLASSES = ("delivered", "replayed", "preempted", "hedge_loser",
                   "wasted_masked", "quarantine_burn", "draft_rejected")


def print_goodput_summary(gauges: Dict[str, float]) -> None:
    """Goodput ledger (ISSUE 8) from the same /metrics scrape: per-lane
    delivered vs waste breakdown and the goodput percentage — of every
    device step the engine burned, how many became client bytes."""
    steps = _sum_labelled(gauges, "goodput_steps_total")
    if not steps:
        return      # engine without the telemetry plane
    lanes: Dict[str, Dict[str, float]] = {}
    for labels, v in steps.items():
        d = _parse_labels(labels)
        lane = d.get("lane", "?")
        lanes.setdefault(lane, {})[d.get("class", "?")] = v
    log("probe[goodput]: goodput ledger (device steps by class)")
    header = "  " + f"{'lane':<12}" + "".join(
        f"{cls:>16}" for cls in _LEDGER_CLASSES) + f"{'goodput%':>10}"
    log(header)
    for lane in sorted(lanes):
        row = lanes[lane]
        total = sum(row.get(cls, 0.0) for cls in _LEDGER_CLASSES)
        pct = 100.0 * row.get("delivered", 0.0) / total if total else 0.0
        log("  " + f"{lane:<12}" + "".join(
            f"{row.get(cls, 0.0):>16.0f}" for cls in _LEDGER_CLASSES)
            + f"{pct:>9.1f}%")


def print_spec_summary(gauges: Dict[str, float]) -> None:
    """Speculative decoding (ISSUE 12) from the same /metrics scrape:
    the acceptance table next to the goodput table — drafted vs
    accepted proposals and the cumulative acceptance ratio (how many
    transcript tokens each 7B weight read is actually buying)."""
    drafted = gauges.get("spec_drafted_tokens_total")
    if drafted is None:
        return      # SPEC_DECODE off / engine without the subsystem
    accepted = gauges.get("spec_accepted_tokens_total", 0.0)
    ratio = gauges.get("spec_acceptance_ratio",
                       accepted / drafted if drafted else 0.0)
    log("probe[spec]: speculative decoding acceptance")
    log(f"  {'drafted':>12} {'accepted':>12} {'rejected':>12} "
        f"{'acceptance':>12}")
    log(f"  {drafted:>12.0f} {accepted:>12.0f} "
        f"{drafted - accepted:>12.0f} {ratio:>11.1%}"
        + ("  [draft sharded]"
           if gauges.get("spec_draft_sharded") else "")
        + ("  [draft KV GATHERED]"
           if gauges.get("spec_draft_kv_fallback") else ""))


def print_slo_summary(gauges: Dict[str, float]) -> None:
    """SLO burn rates (ISSUE 8): per-(slo, lane, window) error-budget
    burn and remaining budget — burn 1.0 spends the budget exactly at
    the objective's sustainable rate, above it the pager gets closer."""
    burn = _sum_labelled(gauges, "slo_burn_rate")
    if not burn:
        return      # engine without the telemetry plane
    remaining = _sum_labelled(gauges, "slo_error_budget_remaining")
    breaches = _sum_labelled(gauges, "slo_breaches_total")
    log("probe[slo]: error-budget burn rates")
    log(f"  {'slo':<12} {'lane':<12} {'window':>7} {'burn':>8} "
        f"{'budget left':>12}")
    for labels in sorted(burn):
        d = _parse_labels(labels)
        log(f"  {d.get('slo', '?'):<12} {d.get('lane', '?'):<12} "
            f"{d.get('window', '?'):>7} {burn[labels]:>8.2f} "
            f"{remaining.get(labels, 1.0):>11.0%}")
    for labels in sorted(breaches):
        d = _parse_labels(labels)
        log(f"  breaches {d.get('slo', '?')}/{d.get('lane', '?')}: "
            f"{breaches[labels]:.0f}")


def print_steptime_summary(gauges: Dict[str, float]) -> None:
    """Step-time sentinel (ISSUE 15) from the same /metrics scrape:
    per-(phase, bucket) p50/p95/p99 and the per-rung trailing tok/s —
    the regression view next to the throughput view."""
    times = _sum_labelled(gauges, "step_time_seconds")
    if not times:
        return      # engine without the sentinel
    rates = _sum_labelled(gauges, "step_tokens_per_sec")
    rows: Dict[tuple, Dict[str, float]] = {}
    for labels, v in times.items():
        d = _parse_labels(labels)
        key = (d.get("phase", "?"), d.get("bucket", "?"))
        rows.setdefault(key, {})[d.get("quantile", "?")] = v * 1000.0
    log("probe[steptime]: step-time sentinel (ms)")
    log(f"  {'phase':<12} {'bucket':>7} {'p50':>9} {'p95':>9} "
        f"{'p99':>9} {'tok/s':>9}")
    for (phase, bucket) in sorted(rows):
        row = rows[(phase, bucket)]
        rate = rates.get(f'bucket="{bucket}",phase="{phase}"',
                         rates.get(f'phase="{phase}",bucket="{bucket}"',
                                   0.0))
        log(f"  {phase:<12} {bucket:>7} {row.get('p50', 0.0):>9.2f} "
            f"{row.get('p95', 0.0):>9.2f} {row.get('p99', 0.0):>9.2f} "
            f"{rate:>9.0f}")
    trips = gauges.get("steptime_breach_trips_total", 0.0)
    log(f"  breach trips total          {trips:>8.0f}")
    captured = _sum_labelled(gauges, "incidents_captured_total")
    if captured:
        log("  incidents captured          "
            + ", ".join(f"{k.split('=')[-1].strip(chr(34))}={v:.0f}"
                        for k, v in sorted(captured.items())))


def watch_deltas(prev: Dict[str, float], cur: Dict[str, float],
                 dt: float) -> Dict[str, object]:
    """One --watch interval's delta rates from two /metrics scrapes:
    tok/s (token-counter delta), goodput%% (delivered vs total ledger
    steps this interval), spec acceptance (accepted vs drafted this
    interval), and the current decode step-time p95 (a gauge — no
    delta). Pure function so the triage math is unit-testable."""
    def delta(name: str) -> float:
        return max(0.0, cur.get(name, 0.0) - prev.get(name, 0.0))

    tok_s = delta("engine_tokens_generated_total") / dt if dt > 0 else 0.0
    d_total = d_delivered = 0.0
    for labels, v in _sum_labelled(cur, "goodput_steps_total").items():
        dv = max(0.0, v - _sum_labelled(prev, "goodput_steps_total")
                 .get(labels, 0.0))
        d_total += dv
        if _parse_labels(labels).get("class") == "delivered":
            d_delivered += dv
    goodput = (100.0 * d_delivered / d_total) if d_total else None
    d_drafted = delta("spec_drafted_tokens_total")
    d_accepted = delta("spec_accepted_tokens_total")
    acceptance = (d_accepted / d_drafted) if d_drafted else None
    p95 = None
    for labels, v in _sum_labelled(cur, "step_time_seconds").items():
        d = _parse_labels(labels)
        if d.get("phase") in ("decode", "spec_verify") \
                and d.get("quantile") == "p95":
            p95 = max(p95 or 0.0, v * 1000.0)
    return {"tok_s": tok_s, "goodput_pct": goodput,
            "acceptance": acceptance, "step_p95_ms": p95,
            "trips": delta("steptime_breach_trips_total"),
            "incidents": sum(
                max(0.0, v - _sum_labelled(prev,
                                           "incidents_captured_total")
                    .get(k, 0.0))
                for k, v in _sum_labelled(
                    cur, "incidents_captured_total").items())}


async def watch_loop(session, base_url: str, headers, interval: float,
                     rounds: int) -> None:
    """--watch N: re-scrape /metrics every N seconds and print one
    delta-rate line per interval — live incident triage without a
    Prometheus server in the loop. rounds=0 runs until interrupted."""
    log(f"probe[watch]: scraping {base_url}/metrics every "
        f"{interval:.1f}s (Ctrl-C to stop)")
    log(f"  {'t':>6} {'tok/s':>9} {'goodput':>9} {'accept':>8} "
        f"{'step p95':>10} {'trips':>6} {'incid':>6}")
    prev = None
    t_prev = t0 = time.monotonic()
    n = 0
    while rounds <= 0 or n < rounds:
        await asyncio.sleep(interval)
        # Count every ATTEMPT: an unreachable server must not turn a
        # bounded --watch-rounds run into an infinite loop. The first
        # successful scrape only establishes the baseline (rounds=N
        # means N scrapes, N-1 delta lines).
        n += 1
        try:
            async with session.get(base_url + "/metrics",
                                   headers=headers) as resp:
                cur = parse_prom_gauges(await resp.text())
        except Exception as e:  # pragma: no cover - network-dependent
            log(f"probe[watch]: /metrics unreachable ({e})")
            continue
        now = time.monotonic()
        if prev is not None:
            row = watch_deltas(prev, cur, now - t_prev)
            acc = row["acceptance"]
            gp = row["goodput_pct"]
            p95 = row["step_p95_ms"]
            log(f"  {now - t0:>5.0f}s {row['tok_s']:>9.1f} "
                f"{(f'{gp:.1f}%' if gp is not None else '-'):>9} "
                f"{(f'{acc:.0%}' if acc is not None else '-'):>8} "
                f"{(f'{p95:.2f}ms' if p95 is not None else '-'):>10} "
                f"{row['trips']:>6.0f} {row['incidents']:>6.0f}")
        prev, t_prev = cur, now


async def http_probe(args) -> None:
    """Drive a live server: per-request Server-Timing phases + summary."""
    import aiohttp

    base = args.url.rstrip("/")
    url = base + "/kubectl-command"
    headers = {}
    if args.api_key:
        headers["X-API-Key"] = args.api_key
    if args.watch:
        import aiohttp as _aiohttp

        async with _aiohttp.ClientSession() as session:
            await watch_loop(session, base, headers, args.watch,
                             args.watch_rounds)
        return
    samples: Dict[str, List[float]] = defaultdict(list)
    sem = asyncio.Semaphore(args.concurrency)

    async def one(session: "aiohttp.ClientSession", i: int) -> None:
        query = f"list pods in namespace probe-{i}"
        async with sem:
            t0 = time.monotonic()
            async with session.post(url, json={"query": query},
                                    headers=headers) as resp:
                await resp.read()
                wall = (time.monotonic() - t0) * 1000.0
                rid = resp.headers.get("X-Request-ID", "-")
                timing = parse_server_timing(
                    resp.headers.get("Server-Timing", ""))
                for phase, ms in timing.items():
                    samples[phase].append(ms)
                samples["wall"].append(wall)
                phases = " ".join(f"{k}={v:.1f}ms"
                                  for k, v in timing.items())
                log(f"probe[http {i:>3}]: {resp.status} rid={rid} "
                    f"wall={wall:.1f}ms  {phases or '(no Server-Timing)'}")

    async with aiohttp.ClientSession() as session:
        await asyncio.gather(*[one(session, i)
                               for i in range(args.requests)])
        print_phase_summary(samples)
        await print_pipeline_summary(session, base, headers)


async def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gemma-7b-it")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--quant", default="int8")
    ap.add_argument("--kv-quant", default="int8")
    ap.add_argument("--bs", type=int, default=48)
    ap.add_argument("--max-seq", type=int, default=192)
    ap.add_argument("--chunk-len", type=int, default=16)
    ap.add_argument("--max-tokens", type=int, default=64)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--reps", type=int, default=10,
                    help="chained chunk dispatches per ceiling sample")
    ap.add_argument("--pipe-depth", type=int, default=None,
                    help="override CHUNK_PIPE_DEPTH for A/B runs")
    ap.add_argument("--url", default=None,
                    help="probe a RUNNING server over HTTP instead of "
                         "building an engine (reads Server-Timing phases)")
    ap.add_argument("--requests", type=int, default=32,
                    help="HTTP mode: number of requests to fire")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="HTTP mode: concurrent requests in flight")
    ap.add_argument("--api-key", default=None,
                    help="HTTP mode: X-API-Key value")
    ap.add_argument("--watch", type=float, default=None,
                    help="HTTP mode: instead of firing requests, "
                         "re-scrape /metrics every N seconds and print "
                         "delta rates (tok/s, goodput, acceptance, "
                         "step-time p95) for live incident triage")
    ap.add_argument("--watch-rounds", type=int, default=0,
                    help="stop --watch after this many scrapes (the "
                         "first establishes the baseline, so N scrapes "
                         "print N-1 delta lines; 0 = until interrupted)")
    args = ap.parse_args()

    if args.url:
        await http_probe(args)
        return

    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    from ai_agent_kubectl_tpu.engine.tokenizer import HFTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    cfg = get_config(args.model)
    tok = HFTokenizer(
        Path(__file__).resolve().parent.parent / "ai_agent_kubectl_tpu"
        / "assets" / "tokenizer-k8s.json",
        cfg.bos_id, cfg.eos_ids, cfg.pad_id)
    buckets = tuple(b for b in (64, 128, 256, 512)
                    if b <= args.max_seq) or (args.max_seq,)
    extra = ({"chunk_pipe_depth": args.pipe_depth}
             if args.pipe_depth is not None else {})
    eng = BatchedJaxEngine(
        cfg, tokenizer=tok, dtype=args.dtype, quant=args.quant,
        kv_quant=args.kv_quant, max_seq_len=args.max_seq,
        prefill_buckets=buckets, batch_size=args.bs,
        chunk_len=args.chunk_len, **extra)
    t0 = time.monotonic()
    await eng.start()
    log(f"probe: engine ready in {time.monotonic() - t0:.0f}s "
        f"(model={cfg.name} bs={args.bs} quant={args.quant or 'bf16'} "
        f"kv={args.kv_quant or eng.dtype.__name__} "
        f"kv_buckets={eng._kv_buckets})")

    # ---- burst attribution (before the ceiling probe donates state) ----
    samples: Dict[str, List[float]] = defaultdict(list)
    for r in range(args.rounds):
        g0 = eng._group_admitted
        t0 = time.monotonic()
        rs = await asyncio.gather(*[
            eng.generate(render_prompt(f"list pods in ns probe-{r}-{i}"),
                         max_tokens=args.max_tokens, temperature=0.0)
            for i in range(args.bs)])
        dt = time.monotonic() - t0
        tot = sum(x.completion_tokens for x in rs)
        mid = len(rs) // 2
        qs = sorted(x.queue_ms for x in rs)
        pf = sorted(x.prefill_ms for x in rs)
        dm = sorted(x.decode_ms for x in rs)
        for x in rs:
            samples["queue_wait"].append(x.queue_ms)
            samples["prefill"].append(x.prefill_ms)
            samples["decode"].append(x.decode_ms)
            samples["detokenize"].append(x.detok_ms)
        log(f"probe[burst {r}]: {tot} tok in {dt:.2f}s = {tot/dt:.0f} tok/s"
            f"  groups={eng._group_admitted - g0}"
            f"  queue p50={qs[mid]:.0f}ms"
            f"  admit-wait p0/p50/p100={pf[0]:.0f}/{pf[mid]:.0f}/{pf[-1]:.0f}ms"
            f"  decode p50={dm[mid]:.0f}ms")
    print_phase_summary(samples)

    # ---- decode-chunk ceiling (stops the scheduler, drives programs) ----
    await eng.stop()
    cache, tokd, posd, temps = eng._cache, eng._tok_d, eng._pos_d, eng._temps_d
    seeds = eng._seeds_d
    no_corrupt = eng._no_corrupt_d
    # Every slot force-live with an unreachable budget: the ceiling wants
    # all lanes decoding for the whole chained run, never terminating.
    # active/ngen are donated carries — feed fresh all-live state every
    # dispatch so a stray sampled EOS can't progressively park lanes and
    # flatter the ceiling (it can still freeze a lane mid-chunk, which is
    # the same variance a real all-live batch has).
    force = jnp.ones((args.bs,), jnp.bool_)
    budget = jnp.full((args.bs,), 1 << 30, jnp.int32)

    def all_live():
        return jnp.ones((args.bs,), jnp.bool_), jnp.zeros((args.bs,),
                                                          jnp.int32)

    for kv_b in eng._kv_buckets:
        fn = eng._batch_chunk_fns[kv_b]
        active, ngen = all_live()
        packed, tokd, posd, cache, _, _ = fn(
            eng.params, tokd, posd, cache, seeds, temps, force, active, ngen,
            budget, no_corrupt)
        jax.block_until_ready(packed)
        t0 = time.monotonic()
        for _ in range(args.reps):
            active, ngen = all_live()
            packed, tokd, posd, cache, _, _ = fn(
                eng.params, tokd, posd, cache, seeds, temps, force, active,
                ngen, budget, no_corrupt)
        jax.block_until_ready(packed)
        dt = (time.monotonic() - t0) / args.reps
        per_step = dt / eng.chunk_len * 1000
        log(f"probe[ceiling]: kv_bucket={kv_b}: chunk={dt*1000:.1f}ms"
            f" -> {per_step:.2f} ms/step"
            f" -> {args.bs / per_step * 1000:.0f} tok/s device ceiling")


if __name__ == "__main__":
    asyncio.run(main())
