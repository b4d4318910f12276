"""Perf-regression sentinel (ISSUE 15): step-time digests and anomaly-
triggered incident capture.

The standing invariants:

- Step-time digests are bounded, keyed by the closed (phase, bucket)
  sets, judged against a baseline envelope (PERF_BASELINES file or
  self-calibration), and breach edge-triggered — a sustained regression
  is one trip, not one per scrape.
- THE DRILL: an injected chunk-path delay (testing/faults.py delay
  mode) trips the step-time trigger on the fake engine and an incident
  bundle appears at /debug/incidents carrying the flight-recorder
  snapshot, the chunk ring, and the ledger/SLO/health sections; the
  per-trigger cooldown provably bounds capture count under a sustained
  fault.
- The fleet merges per-replica digests and attributes breaches to the
  straggling replica; the rollout gate's optional step-time verdict
  rolls a slow canary back.
- Every /debug/* route shares one token-gate contract: 401 without the
  API key, 403 without the debug token, 404 only for genuinely
  unsupported/unknown resources.
"""

import asyncio
import importlib.util
import json
import logging
import os
import time
from pathlib import Path

import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine, FakeEngine
from ai_agent_kubectl_tpu.obs.incidents import (TRIGGER_BREAKER,
                                                TRIGGER_BURN,
                                                TRIGGER_POOL,
                                                TRIGGER_QUARANTINE,
                                                TRIGGER_STEPTIME,
                                                IncidentManager,
                                                current_incident_id)
from ai_agent_kubectl_tpu.obs.steptime import (PHASE_DECODE,
                                               PHASE_PREFILL,
                                               StepTimeSentinel,
                                               canary_vs_stable,
                                               load_baselines,
                                               merge_snapshots,
                                               prefill_bucket)
from ai_agent_kubectl_tpu.testing.faults import FaultInjector

REPO = Path(__file__).resolve().parent.parent


def _load_tool(name: str, rel: str):
    spec = importlib.util.spec_from_file_location(name, REPO / rel)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**over):
    from ai_agent_kubectl_tpu.config import ServiceConfig

    defaults = dict(engine="fake", model_name="fake", llm_timeout=5.0,
                    rate_limit="10000/minute", sentinel_eval_secs=0.0)
    defaults.update(over)
    return ServiceConfig(**defaults)


async def _make_client(cfg, engine):
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    app = create_app(cfg, engine,
                     executor=CommandExecutor(timeout=cfg.execution_timeout))
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


# ---------------------------------------------------------------------------
# StepTimeSentinel units
# ---------------------------------------------------------------------------


def test_sentinel_digests_quantiles_and_step_normalization():
    s = StepTimeSentinel(min_samples=4, factor=2.0)
    # seconds / steps => ms per step: 0.16 s over 16 steps = 10 ms.
    for _ in range(8):
        s.note("decode", 64, 0.16, steps=16, tokens=64)
    snap = s.snapshot()
    d = snap["digests"]["decode/64"]
    assert d["count"] == 8 and abs(d["p50_ms"] - 10.0) < 1e-6
    assert d["p99_ms"] >= d["p50_ms"]
    assert d["tok_s"] > 0          # trailing rate saw the tokens
    assert d["baseline_source"] == "calibrated"
    assert snap["breaches"] == [] and snap["trips_total"] == 0
    with pytest.raises(ValueError):
        s.note("warp", 64, 0.1)
    # Disabled sentinels record nothing.
    off = StepTimeSentinel(enabled=False)
    off.note("decode", 64, 0.1)
    assert off.snapshot()["digests"] == {}


def test_sentinel_file_baseline_breach_and_edge_trips():
    s = StepTimeSentinel(min_samples=4, factor=2.0, min_breach_ms=1.0,
                         baselines={"decode": {"64": 10.0,
                                               "default": 20.0}})
    for _ in range(6):
        s.note("decode", 64, 0.012, steps=1)   # 12 ms < 2x10
    snap = s.snapshot()
    assert snap["digests"]["decode/64"]["baseline_source"] == "file"
    assert snap["breaches"] == []
    for _ in range(6):
        s.note("decode", 64, 0.050, steps=1)   # 50 ms > 2x10, +40 ms
    snap = s.snapshot()
    assert [b["phase"] for b in snap["breaches"]] == ["decode"]
    assert snap["trips_total"] == 1
    # Edge-triggered: a second look at the same sustained breach is the
    # SAME trip, not a new one.
    assert s.snapshot()["trips_total"] == 1
    # The default entry covers unlisted buckets.
    for _ in range(6):
        s.note("decode", 128, 0.001, steps=1)
    assert s.snapshot()["digests"]["decode/128"]["baseline_ms"] == 20.0


def test_sentinel_breach_floor_suppresses_jitter():
    """μs-scale digests (host-side fakes) must not trip on scheduler
    jitter: factor x nothing is still nothing."""
    s = StepTimeSentinel(min_samples=4, factor=2.0, min_breach_ms=1.0)
    for _ in range(6):
        s.note("prefill", 64, 0.00002, steps=1)    # 0.02 ms baseline
    for _ in range(6):
        s.note("prefill", 64, 0.00020, steps=1)    # 10x, but only +0.18ms
    assert s.snapshot()["breaches"] == []


def test_load_baselines_validation(tmp_path):
    good = tmp_path / "b.json"
    good.write_text(json.dumps(
        {"step_time_ms": {"decode": {"default": 23.5, "192": 43.0}}}))
    table = load_baselines(str(good))
    assert table["decode"]["192"] == 43.0
    for bad in ({}, {"step_time_ms": {}},
                {"step_time_ms": {"warp": {"default": 1}}},
                {"step_time_ms": {"decode": {"default": -1}}},
                {"step_time_ms": {"decode": 5}}):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps(bad))
        with pytest.raises(ValueError):
            load_baselines(str(p))


def test_prefill_bucket_bounds_label_cardinality():
    assert prefill_bucket(3) == 64
    assert prefill_bucket(100) == 128
    assert prefill_bucket(10_000) == 1024    # clamps to the last bucket
    assert prefill_bucket(70, buckets=(64, 256)) == 256


def test_merge_snapshots_attributes_straggler_replica():
    fast = StepTimeSentinel(min_samples=4)
    slow = StepTimeSentinel(min_samples=4)
    for _ in range(8):
        fast.note("decode", 4, 0.0001, steps=1, tokens=4)
        slow.note("decode", 4, 0.0001, steps=1, tokens=4)
    for _ in range(8):
        slow.note("decode", 4, 0.050, steps=1, tokens=4)
    merged = merge_snapshots([fast.snapshot(), slow.snapshot()])
    assert merged["breaches"] and all(
        b["replica"] == 1 for b in merged["breaches"])
    d = merged["digests"]["decode/4"]
    assert d["worst_replica"] == 1 and d["count"] == 24
    assert merged["replicas"][0]["breaches"] == []


def test_canary_vs_stable_ratio():
    canary = {"digests": {
        "decode/64": {"phase": "decode", "bucket": 64, "count": 20,
                      "p95_ms": 30.0},
        "prefill/64": {"phase": "prefill", "bucket": 64, "count": 20,
                       "p95_ms": 500.0}}}
    stable = [{"digests": {"decode/64": {
        "phase": "decode", "bucket": 64, "count": 20, "p95_ms": 10.0}}}]
    cmp = canary_vs_stable(canary, stable)
    assert cmp["key"] == "decode/64" and abs(cmp["ratio"] - 3.0) < 1e-6
    # prefill never judged; no comparable decode key => no verdict.
    assert canary_vs_stable(canary, [{"digests": {}}]) is None
    assert canary_vs_stable(None, stable) is None


# ---------------------------------------------------------------------------
# IncidentManager units
# ---------------------------------------------------------------------------


def _steptime_breach_view():
    return {"steptime": {"breaches": [{"phase": "decode", "bucket": 4,
                                       "p99_ms": 50.0}],
                         "trips_total": 1},
            "breaker": "closed", "quarantined_total": 0}


def test_incident_cooldown_bounds_capture():
    im = IncidentManager(ring=8, cooldown_secs=60.0)
    views = _steptime_breach_view()
    assert len(im.evaluate(views, lambda: {"x": 1})) == 1
    # Sustained breach inside the cooldown: counted suppressed, NOTHING
    # assembled — capture overhead is bounded by construction.
    for _ in range(5):
        assert im.evaluate(views, lambda: {"x": 1}) == []
    snap = im.snapshot()
    assert snap["captured_total"] == {TRIGGER_STEPTIME: 1}
    assert snap["suppressed_total"][TRIGGER_STEPTIME] == 5
    # Past the cooldown the same trigger may capture again.
    im2 = IncidentManager(ring=8, cooldown_secs=0.0)
    im2.evaluate(views, lambda: {})
    assert len(im2.evaluate(views, lambda: {})) == 1


def test_incident_spike_triggers_baseline_first():
    im = IncidentManager(cooldown_secs=0.0)
    # First evaluation only BASELINES cumulative counters: pre-existing
    # quarantines are history, not an incident.
    out = im.evaluate({"breaker": "closed", "quarantined_total": 5},
                      lambda: {})
    assert out == []
    out = im.evaluate({"breaker": "closed", "quarantined_total": 7},
                      lambda: {})
    assert [b["trigger"] for b in out] == [TRIGGER_QUARANTINE]
    assert out[0]["detail"]["new_quarantines"] == 2
    # Pool starvation delta fires; an unchanged total doesn't.
    im.evaluate({"breaker": "closed", "quarantined_total": 7,
                 "kv_pool": {"starved_slots_total": 1}}, lambda: {})
    out = im.evaluate({"breaker": "closed", "quarantined_total": 7,
                       "kv_pool": {"starved_slots_total": 3}}, lambda: {})
    assert [b["trigger"] for b in out] == [TRIGGER_POOL]


def test_incident_breaker_edge_and_burn_threshold():
    im = IncidentManager(cooldown_secs=0.0, burn_threshold=2.0)
    base = {"quarantined_total": 0}
    out = im.evaluate(dict(base, breaker="open"), lambda: {})
    assert [b["trigger"] for b in out] == [TRIGGER_BREAKER]
    # Still open: edge-triggered, no second capture.
    assert im.evaluate(dict(base, breaker="open"), lambda: {}) == []
    # Re-open after a close fires again.
    im.evaluate(dict(base, breaker="closed"), lambda: {})
    assert len(im.evaluate(dict(base, breaker="open"), lambda: {})) == 1
    slo = {"windows": ["5m"], "slos": {"ttft": {"lanes": {
        "interactive": {"windows": {"5m": {"total": 10, "breaching": 1,
                                           "burn_rate": 5.0}}}}}}}
    out = im.evaluate(dict(base, breaker="closed", slo=slo), lambda: {})
    assert [b["trigger"] for b in out] == [TRIGGER_BURN]
    # Threshold 0 disables the burn trigger entirely.
    im0 = IncidentManager(cooldown_secs=0.0, burn_threshold=0.0)
    assert im0.evaluate(dict(base, breaker="closed", slo=slo),
                        lambda: {}) == []
    with pytest.raises(ValueError):
        im.maybe_capture("mystery", {}, lambda: {})


def test_incident_ring_bound_and_log_stamp():
    im = IncidentManager(ring=2, cooldown_secs=0.0, stamp_secs=30.0)
    ids = []
    for i in range(3):
        b = im.maybe_capture(TRIGGER_STEPTIME, {"i": i}, lambda: {})
        ids.append(b["id"])
    assert len(im.list()) == 2                  # oldest evicted
    assert im.get(ids[0]) is None and im.get(ids[2]) is not None
    assert im.list()[0]["id"] == ids[2]         # newest first
    # The log-join stamp: the active window names the newest incident,
    # and a LOG_FORMAT=json line emitted inside it carries the id.
    assert current_incident_id() == ids[2]
    from ai_agent_kubectl_tpu.logging_setup import (JsonFormatter,
                                                    RequestIdFilter)

    record = logging.LogRecord("t", logging.WARNING, __file__, 1,
                               "incident drill line", (), None)
    RequestIdFilter().filter(record)
    line = json.loads(JsonFormatter().format(record))
    assert line["incident_id"] == ids[2]


# ---------------------------------------------------------------------------
# Engine-level drill (fake engine, tier-1)
# ---------------------------------------------------------------------------


async def test_fake_engine_sentinel_phases_and_stats():
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2,
                            sentinel_min_samples=5)
    await eng.start()
    try:
        for i in range(6):
            await eng.generate(f"steady traffic {i}", max_tokens=16)
        snap = eng.steptime_health()
        phases = {d["phase"] for d in snap["digests"].values()}
        assert PHASE_DECODE in phases and PHASE_PREFILL in phases
        assert eng.stats()["steptime"]["digests"]
    finally:
        await eng.stop()


async def test_spec_chunks_key_spec_verify_phase():
    eng = FakeChunkedEngine(batch_size=2, chunk_len=6, spec_decode=True,
                            spec_draft_k=2, sentinel_min_samples=4)
    await eng.start()
    try:
        for i in range(6):
            await eng.generate(f"spec traffic {i}", max_tokens=16)
        phases = {d["phase"]
                  for d in eng.steptime_health()["digests"].values()}
        assert "spec_verify" in phases and "decode" not in phases
    finally:
        await eng.stop()


#: fixed-length scripted stream for the drill tests: every request
#: decodes the same chunk count, so sample counts are deterministic.
def _steady_stream(_prompt):
    return [9] * 30 + [2]


#: the drill's timing scheme: calibrate the envelope against a small
#: INJECTED delay (ms-scale, so host scheduling jitter is noise on the
#: baseline instead of a breach), then stretch it ~8x for the fault.
_WARM_DELAY = 0.006
_FAULT_DELAY = 0.05


async def test_chunk_delay_fault_trips_sentinel():
    """The engine half of the acceptance drill: a delay-mode fault on
    the chunk path stretches dispatch intervals; the self-calibrated
    envelope breaches and counts one trip."""
    inj = FaultInjector()
    inj.set("chunk", "delay", _WARM_DELAY)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2,
                            sentinel_min_samples=6, faults=inj,
                            stream_fn=_steady_stream)
    await eng.start()
    try:
        for i in range(6):
            await eng.generate(f"warm {i}", max_tokens=12)
        snap = eng.steptime_health()
        assert [b for b in snap["breaches"]
                if b["phase"] == PHASE_DECODE] == []
        inj.set("chunk", "delay", _FAULT_DELAY)
        for i in range(3):
            await eng.generate(f"slow {i}", max_tokens=12)
        snap = eng.steptime_health()
        decode = [b for b in snap["breaches"]
                  if b["phase"] == PHASE_DECODE]
        assert decode, f"no decode breach in {snap['breaches']}"
        assert snap["trips_total"] >= 1
        assert decode[0]["p99_ms"] > 2.0 * decode[0]["baseline_ms"]
    finally:
        inj.clear()
        await eng.stop()


# ---------------------------------------------------------------------------
# HTTP end-to-end: the sentinel drill, the watcher, metrics, gates
# ---------------------------------------------------------------------------


def _dump_bundle(bundle: dict) -> None:
    """CI satellite: chaos-smoke failures upload /debug/incidents
    bundles as workflow artifacts — tests write every fetched bundle
    into INCIDENT_DUMP_DIR when the env var is set."""
    dump = os.environ.get("INCIDENT_DUMP_DIR")
    if not dump:
        return
    os.makedirs(dump, exist_ok=True)
    with open(os.path.join(dump, f"{bundle['id']}.json"), "w") as f:
        json.dump(bundle, f, indent=2, default=repr)


async def test_http_incident_drill_bundle_and_cooldown():
    """THE acceptance drill: injected chunk slowdown → step-time
    trigger → an incident bundle at /debug/incidents with the
    flight-recorder, chunk-ring, ledger and health evidence; the
    cooldown bounds captures under the sustained fault."""
    inj = FaultInjector()
    inj.set("chunk", "delay", _WARM_DELAY)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2,
                            sentinel_min_samples=6, faults=inj,
                            stream_fn=_steady_stream)
    client = await _make_client(
        _cfg(incident_cooldown_secs=60.0), eng)
    svc = client.server.app["service"]
    try:
        # Warm traffic THROUGH HTTP so the flight recorder holds real
        # request timelines (the fake's token-stream output fails the
        # kubectl safety parse — a 422 is still engine traffic and
        # still recorded, which is the point of the recorder).
        for i in range(7):
            await client.post("/kubectl-command",
                              json={"query": f"list warm pods {i}"})
        r = await client.get("/debug/incidents")
        assert r.status == 200
        body = await r.json()
        assert body["incidents"] == []     # healthy: nothing captured
        inj.set("chunk", "delay", _FAULT_DELAY)
        for i in range(3):
            await client.post("/kubectl-command",
                              json={"query": f"list slow pods {i}"})
        body = await (await client.get("/debug/incidents")).json()
        assert body["captured_total"].get(TRIGGER_STEPTIME) == 1
        assert len(body["incidents"]) == 1
        iid = body["incidents"][0]["id"]
        bundle = await (await client.get(f"/debug/incidents/{iid}")).json()
        _dump_bundle(bundle)
        # The evidence the acceptance bar names: flight recorder, chunk
        # ring, ledger + SLO + health sections, config fingerprint,
        # weights version, and the triggering breach detail.
        assert bundle["trigger"] == TRIGGER_STEPTIME
        assert bundle["detail"]["breaches"]
        assert len(bundle["flight_recorder"]) > 0
        assert bundle["chunks"]["0"], "chunk ring missing"
        assert bundle["ledger"]["conservation"]["balanced"]
        assert bundle["slo"] is not None
        assert bundle["steptime"]["breaches"]
        assert bundle["kv_pool"] is not None
        assert bundle["config_fingerprint"] and bundle["weights_version"]
        # Cooldown provably bounds capture under the SUSTAINED fault:
        # more slow traffic + more evaluations capture nothing new.
        for i in range(2):
            await client.post("/kubectl-command",
                              json={"query": f"still slow {i}"})
            body = await (await client.get("/debug/incidents")).json()
        assert body["captured_total"].get(TRIGGER_STEPTIME) == 1
        assert body["suppressed_total"].get(TRIGGER_STEPTIME, 0) >= 1
        assert len(body["incidents"]) == 1
        # The incident id joined the log stamp window.
        assert current_incident_id() == iid
        assert svc.incidents.snapshot()["last_incident_id"] == iid
        # 404 for an unknown bundle id.
        assert (await client.get("/debug/incidents/inc-nope")).status == 404
    finally:
        inj.clear()
        await client.close()


async def test_background_watcher_captures_without_scrapes():
    """SENTINEL_EVAL_SECS > 0 arms the background watcher: the trigger
    fires and the bundle lands with nobody polling any endpoint."""
    inj = FaultInjector()
    inj.set("chunk", "delay", _WARM_DELAY)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2,
                            sentinel_min_samples=6, faults=inj,
                            stream_fn=_steady_stream)
    client = await _make_client(
        _cfg(sentinel_eval_secs=0.05, incident_cooldown_secs=60.0), eng)
    svc = client.server.app["service"]
    try:
        for i in range(6):
            await eng.generate(f"warm {i}", max_tokens=12)
        await asyncio.sleep(0.12)          # watcher baselines, healthy
        assert svc.incidents.snapshot()["captured_total"] == {}
        inj.set("chunk", "delay", _FAULT_DELAY)
        for i in range(3):
            await eng.generate(f"slow {i}", max_tokens=12)
        deadline = time.monotonic() + 3.0
        while time.monotonic() < deadline:
            if svc.incidents.snapshot()["captured_total"].get(
                    TRIGGER_STEPTIME):
                break
            await asyncio.sleep(0.05)
        assert svc.incidents.snapshot()["captured_total"].get(
            TRIGGER_STEPTIME) == 1
    finally:
        inj.clear()
        await client.close()


async def test_metrics_and_health_surfaces():
    inj = FaultInjector()
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2,
                            sentinel_min_samples=5, faults=inj)
    client = await _make_client(_cfg(), eng)
    try:
        for i in range(7):
            await eng.generate(f"traffic {i}", max_tokens=16)
        text = await (await client.get("/metrics")).text()
        assert 'step_time_seconds{' in text
        assert 'quantile="p99"' in text
        assert "step_tokens_per_sec{" in text
        assert "steptime_breach_trips_total" in text
        health = await (await client.get("/health")).json()
        assert health["steptime"]["digests"]
        assert health["incidents"]["ring_size"] == 8
        # Trip the sentinel; the trip counter and the incident counter
        # both surface on the next scrape.
        inj.set("chunk", "delay", 0.03)
        for i in range(4):
            await eng.generate(f"slow {i}", max_tokens=16)
        text = await (await client.get("/metrics")).text()
        assert "steptime_breach_trips_total 0.0" not in text.replace(
            "_created", "_CREATED")
        assert 'incidents_captured_total{trigger="steptime_breach"}' \
            in text
    finally:
        inj.clear()
        await client.close()


# ---------------------------------------------------------------------------
# Token-gate matrix over every /debug/* route (satellite)
# ---------------------------------------------------------------------------

_DEBUG_ROUTES = [
    ("GET", "/debug/requests"),
    ("GET", "/debug/requests/some-id"),
    ("GET", "/debug/chunks"),
    ("GET", "/debug/ledger"),
    ("GET", "/debug/incidents"),
    ("GET", "/debug/incidents/some-id"),
    ("POST", "/debug/profile?seconds=0.1"),
    ("POST", "/debug/trace?seconds=0.1"),
]


@pytest.mark.parametrize("method,path", _DEBUG_ROUTES,
                         ids=[p.split("?")[0] for _, p in _DEBUG_ROUTES])
async def test_debug_token_gate_matrix(method, path):
    """One contract for every debug surface: 401 without the API key,
    403 with the key but a bad/missing debug token, and with both —
    anything but an auth status (200/404/409 are the route's own
    business)."""
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2)
    client = await _make_client(
        _cfg(api_auth_key="api-key-1", debug_token="debug-token-1"), eng)
    try:
        req = getattr(client, method.lower())
        assert (await req(path)).status == 401
        assert (await req(path, headers={
            "X-API-Key": "api-key-1"})).status == 403
        assert (await req(path, headers={
            "X-API-Key": "api-key-1",
            "X-Debug-Token": "wrong"})).status == 403
        r = await req(path, headers={"X-API-Key": "api-key-1",
                                     "X-Debug-Token": "debug-token-1"})
        assert r.status not in (401, 403)
    finally:
        await client.close()


async def test_debug_unsupported_consistency():
    """404-when-unsupported: /debug/ledger 404s on an engine without a
    ledger, while service-level surfaces (incidents, requests, chunks)
    answer 200 with empty bodies — absence of a subsystem is a 404,
    absence of DATA is an empty 200."""
    client = await _make_client(_cfg(), FakeEngine())
    try:
        assert (await client.get("/debug/ledger")).status == 404
        r = await client.get("/debug/incidents")
        assert r.status == 200
        assert (await r.json())["incidents"] == []
        assert (await client.get("/debug/requests")).status == 200
        assert (await client.get("/debug/chunks")).status == 200
        assert (await client.get("/debug/requests/nope")).status == 404
        assert (await client.get("/debug/incidents/nope")).status == 404
    finally:
        await client.close()


# ---------------------------------------------------------------------------
# Fleet: straggler attribution + rollout step-time gate
# ---------------------------------------------------------------------------


async def test_fleet_attributes_incident_to_faulted_replica():
    """Fleet half of the acceptance drill: replica 0 carries an
    r0-scoped chunk delay; the merged steptime view breaches with
    replica attribution, and the incident detail names it."""
    from ai_agent_kubectl_tpu.engine.fleet import EngineFleet

    # This drill judges a wall-clock breach on a CPU that the suite's
    # other workers share, and the clean replica must STAY clean: so the
    # margins are ones that load cannot erase. Envelope: 20 ms injected
    # per 2-step chunk = 10 ms/step; breach: factor 10 = 100 ms/step,
    # which a stall would have to add 200 ms to ONE chunk cycle of the
    # clean replica to reach; fault: 300 ms per chunk = 150 ms/step on
    # replica 0, which load can only lengthen.
    warm_delay, fault_delay, factor = 0.02, 0.3, 10.0
    inj = FaultInjector()
    inj.set("chunk", "delay", warm_delay)
    reps = [FakeChunkedEngine(batch_size=2, chunk_len=2,
                              sentinel_min_samples=6,
                              sentinel_factor=factor,
                              faults=inj.for_replica(i),
                              stream_fn=_steady_stream)
            for i in range(2)]
    fleet = EngineFleet(reps, affinity=False)
    await fleet.start()
    try:
        # Drive each replica directly: the merge/attribution is what is
        # under test, not the router.
        for i in range(6):
            for rep in reps:
                await rep.generate(f"warm {i}", max_tokens=12)
        # Re-arming the chunk point replica-scoped: ONLY replica 0
        # stalls now (its sibling just gets faster — a downside breach
        # never fires, only the upper tail does).
        inj.set("chunk", "delay", fault_delay, replica=0)
        for rep in reps:
            await rep.generate("slow", max_tokens=12)
        snap = fleet.steptime_health()
        decode = [b for b in snap["breaches"]
                  if b["phase"] == PHASE_DECODE]
        assert decode and all(b["replica"] == 0 for b in decode)
        assert not snap["replicas"][1]["breaches"]
        # The incident trigger sees the attributed breaches verbatim.
        im = IncidentManager(cooldown_secs=0.0)
        out = im.evaluate({"steptime": snap, "breaker": "closed",
                           "quarantined_total": 0}, lambda: {})
        steptime = [b for b in out if b["trigger"] == TRIGGER_STEPTIME]
        assert steptime and any(
            br.get("replica") == 0
            for br in steptime[0]["detail"]["breaches"])
    finally:
        inj.clear()
        await fleet.stop()


async def test_rollout_gate_steptime_verdict():
    """ROLLOUT_STEPTIME_GATE: a canary whose decode p95 runs a multiple
    of stable's rolls back with cause steptime_gate; gate off (0) never
    judges step time."""
    from ai_agent_kubectl_tpu.engine.fleet import EngineFleet
    from ai_agent_kubectl_tpu.engine.rollout import (CAUSE_STEPTIME_GATE,
                                                     ROLLBACK_CAUSES,
                                                     RolloutController)

    assert CAUSE_STEPTIME_GATE in ROLLBACK_CAUSES
    reps = [FakeChunkedEngine(batch_size=2, chunk_len=2)
            for _ in range(2)]
    fleet = EngineFleet(reps, affinity=False)
    await fleet.start()
    try:
        slow = {"digests": {"decode/4": {
            "phase": "decode", "bucket": 4, "count": 20, "p95_ms": 9.0}}}
        fast = {"digests": {"decode/4": {
            "phase": "decode", "bucket": 4, "count": 20, "p95_ms": 3.0}}}
        reps[0].steptime_health = lambda: slow
        reps[1].steptime_health = lambda: fast
        ctrl = RolloutController(fleet, steptime_gate=2.0)
        ctrl.canary_idx = 0
        gate = ctrl._evaluate_gate(ctrl._gate_baseline())
        assert gate["breach"] and gate["cause"] == CAUSE_STEPTIME_GATE
        assert abs(gate["steptime"]["ratio"] - 3.0) < 1e-6
        off = RolloutController(fleet, steptime_gate=0.0)
        off.canary_idx = 0
        gate = off._evaluate_gate(off._gate_baseline())
        assert not gate["breach"]
    finally:
        await fleet.stop()


def test_probe_watch_deltas():
    probe = _load_tool("probe_mod", "tools/probe_serving.py")
    prev = {"engine_tokens_generated_total": 100.0,
            'goodput_steps_total{class="delivered",lane="interactive"}':
                80.0,
            'goodput_steps_total{class="wasted_masked",'
            'lane="interactive"}': 20.0,
            "spec_drafted_tokens_total": 10.0,
            "spec_accepted_tokens_total": 5.0}
    cur = {"engine_tokens_generated_total": 300.0,
           'goodput_steps_total{class="delivered",lane="interactive"}':
               170.0,
           'goodput_steps_total{class="wasted_masked",'
           'lane="interactive"}': 30.0,
           "spec_drafted_tokens_total": 30.0,
           "spec_accepted_tokens_total": 20.0,
           'step_time_seconds{bucket="4",phase="decode",'
           'quantile="p95"}': 0.012,
           "steptime_breach_trips_total": 1.0}
    row = probe.watch_deltas(prev, cur, dt=2.0)
    assert row["tok_s"] == 100.0
    assert abs(row["goodput_pct"] - 90.0) < 1e-6
    assert abs(row["acceptance"] - 0.75) < 1e-6
    assert abs(row["step_p95_ms"] - 12.0) < 1e-6
    assert row["trips"] == 1.0


def test_config_sentinel_validation(tmp_path):
    from ai_agent_kubectl_tpu.config import ServiceConfig

    table = tmp_path / "baselines.json"
    table.write_text(json.dumps(
        {"step_time_ms": {"decode": {"default": 23.5, "192": 43.0}}}))

    for bad in (dict(sentinel_window=4), dict(sentinel_factor=0.9),
                dict(sentinel_min_samples=0), dict(sentinel_eval_secs=-1),
                dict(incident_ring=0), dict(incident_cooldown_secs=-1),
                dict(incident_burn_threshold=-0.1),
                dict(incident_profile_secs=31.0),
                dict(rollout_steptime_gate=0.5),
                dict(perf_baselines="/does/not/exist.json")):
        with pytest.raises(ValueError):
            ServiceConfig(engine="fake", model_name="fake", **bad)
    cfg = ServiceConfig(engine="fake", model_name="fake",
                        perf_baselines=str(table),
                        rollout_steptime_gate=1.5)
    assert cfg.sentinel_enable
