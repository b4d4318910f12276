"""QoS ring (ISSUE 7): tenant/lane classification, fair-share WDRR
admission, preemptive decode via export/replay, brownout AIMD, and the
tenant-flood drill.

The fairness invariants, on the queue alone, the FakeChunkedEngine (the
deterministic numpy twin), the fleet router, the HTTP surface, and the
real BatchedJaxEngine on CPU:

- WDRR serves a saturated queue weights-proportionally per round, round-
  robins tenants within a lane, and never starves anyone.
- A tenant past its in-queue cap is shed with TenantOverloaded (429);
  at global depth the shed prefers the flooding tenant (displacement).
- Expired-deadline requests are purged at scan time and counted, not
  left occupying MAX_QUEUE_DEPTH.
- A preempted request replays BYTE-IDENTICALLY (fake and jax engines;
  on jax at temperature 0 and 0.9 — the seeded-replay contract), and
  preempt-budget exhaustion leaves the victim running.
- A two-tenant flood keeps the quiet tenant's queue wait bounded.
"""

import asyncio
import queue as _queue
import threading
import time
import types

import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine, _FakeReq
from ai_agent_kubectl_tpu.engine.protocol import (EngineOverloaded,
                                                  GenerationTimeout,
                                                  TenantOverloaded)
from ai_agent_kubectl_tpu.engine.qos import (LANE_BACKGROUND, LANE_BATCH,
                                             LANE_INTERACTIVE,
                                             BrownoutController, QoSContext,
                                             QoSQueue, classify,
                                             parse_lane_weights,
                                             parse_tenant_tiers, use_qos)
from ai_agent_kubectl_tpu.testing.faults import FaultInjector

# ---------------------------------------------------------------------------
# Classification + spec parsing
# ---------------------------------------------------------------------------


def test_classify_tenant_key_and_lane_clamp():
    tiers = {"key-batch": "batch", "10.0.0.9": "background"}
    # API key wins over client IP as the tenant key.
    ctx = classify("key-batch", "1.2.3.4", None, tiers)
    assert ctx.tenant == "key-batch" and ctx.lane == "batch"
    # X-Priority may lower below the tier...
    ctx = classify("key-batch", None, "background", tiers)
    assert ctx.lane == "background"
    # ...but never raise above it.
    ctx = classify("key-batch", None, "interactive", tiers)
    assert ctx.lane == "batch"
    # No API key → client IP keys the tenant; unknown tenants get the
    # default lane; garbage X-Priority is ignored.
    ctx = classify(None, "10.0.0.9", "turbo", tiers)
    assert ctx.tenant == "10.0.0.9" and ctx.lane == "background"
    ctx = classify(None, "8.8.8.8", None, tiers)
    assert ctx.tenant == "8.8.8.8" and ctx.lane == "interactive"
    assert classify(None, None, None, {}).tenant == "anon"


def test_spec_parsers_validate():
    assert parse_tenant_tiers("a:interactive, b:batch")["b"] == "batch"
    assert parse_lane_weights("interactive:9")["interactive"] == 9
    assert parse_lane_weights("")["batch"] == 4      # defaults survive
    with pytest.raises(ValueError):
        parse_tenant_tiers("a:turbo")
    with pytest.raises(ValueError):
        parse_lane_weights("interactive:0")
    with pytest.raises(ValueError):
        parse_lane_weights("warp:3")


# ---------------------------------------------------------------------------
# QoSQueue policy units
# ---------------------------------------------------------------------------


def _req(tenant="anon", lane=LANE_INTERACTIVE, deadline=None, name=""):
    return types.SimpleNamespace(
        tenant=tenant, lane=lane, deadline=deadline,
        cancel=threading.Event(), preempt_t0=None, name=name,
        t_enqueue=0.0)


def test_wdrr_shares_and_intra_round_priority():
    q = QoSQueue(weights={"interactive": 8, "batch": 4, "background": 1})
    for i in range(20):
        q.put(_req(lane=LANE_INTERACTIVE, name=f"i{i}"))
        q.put(_req(lane=LANE_BATCH, name=f"b{i}"))
        q.put(_req(lane=LANE_BACKGROUND, name=f"g{i}"))
    # One full round over a saturated queue: 8 interactive, 4 batch,
    # 1 background — interactive's credit spends first within the round.
    round1 = [q.get_nowait().lane for _ in range(13)]
    assert round1.count(LANE_INTERACTIVE) == 8
    assert round1.count(LANE_BATCH) == 4
    assert round1.count(LANE_BACKGROUND) == 1
    assert round1[0] == LANE_INTERACTIVE
    # Shares hold over further rounds: nobody starves.
    round2 = [q.get_nowait().lane for _ in range(13)]
    assert round2.count(LANE_BACKGROUND) == 1


def test_tenants_round_robin_within_a_lane():
    q = QoSQueue()
    for i in range(3):
        q.put(_req(tenant="A", name=f"A{i}"))
        q.put(_req(tenant="B", name=f"B{i}"))
    order = [q.get_nowait().name for _ in range(6)]
    # Alternating tenants, FIFO within each tenant.
    assert order == ["A0", "B0", "A1", "B1", "A2", "B2"]


def test_tenant_cap_sheds_the_flooder_with_429():
    q = QoSQueue(tenant_cap=2)
    q.put(_req(tenant="flood"))
    q.put(_req(tenant="flood"))
    with pytest.raises(TenantOverloaded) as ei:
        q.put(_req(tenant="flood"))
    assert ei.value.tenant == "flood"
    assert "2/2" in str(ei.value)
    # Other tenants are untouched by the flooder's cap.
    assert q.put(_req(tenant="quiet")) == []
    assert q.qsize() == 3


def test_full_queue_displacement_prefers_flooding_tenant():
    q = QoSQueue(max_depth=4)
    for i in range(4):
        q.put(_req(tenant="flood", lane=LANE_BACKGROUND, name=f"f{i}"))
    # The flooding tenant's own arrival at a full queue: classic shed.
    with pytest.raises(EngineOverloaded) as ei:
        q.put(_req(tenant="flood", lane=LANE_BACKGROUND))
    assert "admission queue full (4/4)" in str(ei.value)
    # A quiet tenant's arrival displaces the flooder's NEWEST request.
    displaced = q.put(_req(tenant="quiet", name="q0"))
    assert [d.name for d in displaced] == ["f3"]
    assert q.qsize() == 4
    # A background arrival never displaces higher-lane work.
    q2 = QoSQueue(max_depth=2)
    q2.put(_req(tenant="flood", lane=LANE_INTERACTIVE))
    q2.put(_req(tenant="flood", lane=LANE_INTERACTIVE))
    with pytest.raises(EngineOverloaded):
        q2.put(_req(tenant="quiet", lane=LANE_BACKGROUND))


def test_displacement_never_evicts_an_already_admitted_request():
    """A preempted victim (or any resume-carrying requeue) may already
    have streamed tokens to its client — displacement must skip it even
    when its tenant dominates the queue."""
    q = QoSQueue(max_depth=2)
    protected = _req(tenant="flood", lane=LANE_BACKGROUND, name="victim")
    protected.preempt_count = 1
    q.put(_req(tenant="flood", lane=LANE_BACKGROUND, name="fresh"))
    q.requeue_head(protected)
    # The flooder's newest DISPLACEABLE entry is "fresh", not the victim.
    displaced = q.put(_req(tenant="quiet", name="q0"))
    assert [d.name for d in displaced] == ["fresh"]
    # Only protected entries left for the dominant tenant: shed instead.
    q3 = QoSQueue(max_depth=2)
    for nm in ("v1", "v2"):
        r = _req(tenant="flood", lane=LANE_BACKGROUND, name=nm)
        r.preempt_count = 1
        q3.requeue_head(r)
    with pytest.raises(EngineOverloaded):
        q3.put(_req(tenant="quiet", name="q1"))


def test_expired_requests_purged_at_scan_not_at_pop():
    expired = []
    q = QoSQueue(max_depth=3, on_expire=expired.append)
    past = time.monotonic() - 1.0
    for i in range(3):
        q.put(_req(deadline=past, name=f"dead{i}"))
    assert q.qsize() == 3
    # A put at capacity purges the dead instead of shedding the living.
    assert q.put(_req(name="live")) == []
    assert q.expired_total == 3
    assert len(expired) == 3
    assert q.get_nowait().name == "live"
    # A preempted victim's paused time extends its effective deadline.
    victim = _req(deadline=time.monotonic() - 0.5, name="v")
    victim.preempt_t0 = time.monotonic() - 2.0   # paused longer than over
    q.put(victim)
    q._purge_locked(time.monotonic(), force=True)
    assert q.qsize() == 1        # still alive: pause credited


def test_requeue_head_and_min_lane():
    q = QoSQueue()
    q.put(_req(tenant="T", lane=LANE_BACKGROUND, name="first"))
    q.put(_req(tenant="T", lane=LANE_BACKGROUND, name="second"))
    victim = _req(tenant="T", lane=LANE_BACKGROUND, name="victim")
    q.requeue_head(victim)
    # min_lane pins the pop to the starved lane and above.
    with pytest.raises(_queue.Empty):
        q.get_nowait(min_lane=LANE_INTERACTIVE)
    assert q.get_nowait().name == "victim"     # head of its tenant queue
    assert q.get_nowait(exclude_lanes=()).name == "first"


def test_starved_lane_judges_enqueue_time():
    q = QoSQueue()
    r = _req(lane=LANE_INTERACTIVE)
    q.put(r)
    now = time.monotonic()
    assert q.starved_lane(now, 10.0) is None
    assert q.starved_lane(now + 11.0, 10.0) == LANE_INTERACTIVE
    # A brownout-capped lane is excluded: a freed slot couldn't admit it.
    assert q.starved_lane(now + 11.0, 10.0,
                          exclude=(LANE_INTERACTIVE,)) is None
    # A requeued victim's fresh stamp at the head must not mask an
    # older starving request queued behind it (whole-deque scan).
    q2 = QoSQueue()
    old = _req(tenant="T", lane=LANE_BATCH, name="old")
    q2.put(old)
    old.t_enqueue -= 20.0
    fresh = _req(tenant="T", lane=LANE_BATCH, name="fresh")
    q2.requeue_head(fresh)
    assert q2.starved_lane(time.monotonic(), 10.0) == LANE_BATCH


def test_brownout_aimd_background_sheds_first_batch_recovers_first():
    b = BrownoutController(slo_ms=100.0, eval_interval_secs=0.0)
    assert b.level == 0
    now = time.monotonic()
    b.note_queue_wait(LANE_INTERACTIVE, 500.0, now=now)
    assert b.maybe_eval(now)
    assert b.level == 1 and b.shares[LANE_BACKGROUND] == 0.5
    # Keep breaching: background floors, then batch starts shedding.
    for _ in range(4):
        b.note_queue_wait(LANE_INTERACTIVE, 500.0, now=now)
        b.maybe_eval(now)
    assert b.shares[LANE_BACKGROUND] == b.FLOOR
    assert b.level == 2 and b.shares[LANE_BATCH] < 1.0
    # Caps floor at one slot — brownout never zeroes a lane.
    assert b.lane_cap(LANE_BACKGROUND, 8) >= 1
    assert b.lane_cap(LANE_INTERACTIVE, 8) == 8
    # Recovery (idle window = healthy): batch restores fully FIRST.
    later = now + 60.0
    while b.shares[LANE_BATCH] < 1.0:
        assert b.maybe_eval(later)
        assert b.shares[LANE_BACKGROUND] == b.FLOOR
    while b.level:
        b.maybe_eval(later)
    assert b.shares == {LANE_BACKGROUND: 1.0, LANE_BATCH: 1.0}
    # Disabled controller never trims.
    off = BrownoutController(slo_ms=0.0)
    off.note_queue_wait(LANE_INTERACTIVE, 1e9)
    assert not off.maybe_eval() and off.level == 0


# ---------------------------------------------------------------------------
# FakeChunkedEngine: preemption mechanics (deterministic manual ticking)
# ---------------------------------------------------------------------------


def _fake_req(eng, prompt, *, lane, tenant, max_tokens=50, stream=None):
    return _FakeReq(
        prompt=prompt, max_tokens=max_tokens, deadline=None,
        out_queue=asyncio.Queue(), cancel=asyncio.Event(),
        stream=list(stream if stream is not None
                    else eng.stream_fn(prompt)),
        tenant=tenant, lane=lane, t_submit=time.monotonic())


def _drain_text(req):
    ids = []
    while True:
        try:
            event, payload = req.out_queue.get_nowait()
        except asyncio.QueueEmpty:
            return ids, None
        if event == "token":
            ids.append(payload)
        elif event == "done":
            return ids, payload
        elif event == "error":
            raise payload


def test_fake_preempt_exports_and_replays_byte_identical():
    stream = [10 + i for i in range(40)] + [2]
    eng = FakeChunkedEngine(batch_size=1, chunk_len=4,
                            preempt_wait_ms=1.0, preempt_budget=2)
    bg = _fake_req(eng, "bulk job", lane=LANE_BACKGROUND, tenant="bulk",
                   stream=stream, max_tokens=60)
    eng._queue.put(bg)
    eng._admit_pending()
    assert eng._slots[0] is not None
    for _ in range(4):           # decode a few chunks
        eng._tick()
    emitted_before = list(eng._slots[0].emitted)
    assert len(emitted_before) >= 2
    inter = _fake_req(eng, "quick question", lane=LANE_INTERACTIVE,
                      tenant="quiet", max_tokens=4,
                      stream=[7, 8, 9, 2])
    eng._queue.put(inter)
    time.sleep(0.005)            # exceed PREEMPT_WAIT_MS
    assert eng._maybe_preempt() is True
    assert eng._slots[0] is None
    assert bg.resume_ids == emitted_before
    assert bg.preempt_count == 1
    # The victim sits at the HEAD of its tenant queue; the freed slot
    # goes to the starved interactive lane first.
    eng._admit_pending()
    assert eng._slots[0].req is inter
    for _ in range(400):
        eng._tick()
        if all(s is None for s in eng._slots) and not eng._queue:
            break
    pieces_bg, done_bg = _drain_text(bg)
    _, done_int = _drain_text(inter)
    assert done_int is not None and done_bg is not None
    assert eng.stats()["qos"]["preemptions"] == 1
    # BYTE-IDENTITY: the preempted run's concatenated stream equals an
    # uncontended run of the same scripted request.
    ref_eng = FakeChunkedEngine(batch_size=1, chunk_len=4)
    ref = _fake_req(ref_eng, "bulk job", lane=LANE_BACKGROUND,
                    tenant="bulk", stream=stream, max_tokens=60)
    ref_eng._queue.put(ref)
    ref_eng._admit_pending()
    for _ in range(400):
        ref_eng._tick()
        if all(s is None for s in ref_eng._slots):
            break
    ref_pieces, ref_done = _drain_text(ref)
    assert "".join(pieces_bg) == "".join(ref_pieces)
    assert done_bg.text == ref_done.text


def test_fake_preempt_budget_exhaustion_leaves_victim_running():
    eng = FakeChunkedEngine(batch_size=1, chunk_len=4,
                            preempt_wait_ms=1.0, preempt_budget=0)
    bg = _fake_req(eng, "bulk", lane=LANE_BACKGROUND, tenant="bulk",
                   stream=[9] * 50 + [2], max_tokens=60)
    eng._queue.put(bg)
    eng._admit_pending()
    inter = _fake_req(eng, "quick", lane=LANE_INTERACTIVE, tenant="q")
    eng._queue.put(inter)
    time.sleep(0.005)
    # Budget spent (0): no victim is eligible — the slot keeps decoding.
    assert eng._maybe_preempt() is False
    assert eng._slots[0] is not None and eng._slots[0].req is bg
    assert eng.stats()["qos"]["preemptions"] == 0


async def test_fake_two_tenant_flood_quiet_tenant_bounded():
    """Fairness acceptance on the fake: one tenant floods background
    work; a quiet tenant's interactive requests are admitted promptly
    (WDRR + preemption), and the flood still fully drains (no
    starvation)."""
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4,
                            preempt_wait_ms=5.0, preempt_budget=2,
                            stream_fn=lambda p: [11] * 60 + [2])
    await eng.start()
    try:
        t0 = time.monotonic()
        with use_qos(QoSContext(tenant="flood", lane=LANE_BACKGROUND)):
            flood = [asyncio.create_task(
                eng.generate(f"bulk {i}", max_tokens=60))
                for i in range(10)]
        await asyncio.sleep(0.02)     # flood occupies both slots
        with use_qos(QoSContext(tenant="quiet", lane=LANE_INTERACTIVE)):
            tq0 = time.monotonic()
            r = await eng.generate("quick", max_tokens=4)
        quiet_wall = time.monotonic() - tq0
        assert r.finish_reason in ("stop", "length")
        flood_results = await asyncio.gather(*flood)
        flood_wall = time.monotonic() - t0
        # The quiet tenant did not wait out the flood's full drain.
        assert quiet_wall < max(0.25, flood_wall / 3)
        # ...and the flood was merely delayed, never starved.
        assert all(fr.completion_tokens == 60 for fr in flood_results)
        # (Whether WDRR alone or a preemption admitted the quiet tenant
        # is timing-dependent on the fake's instant decode; the
        # preemption mechanics are asserted deterministically above.)
    finally:
        await eng.stop()


async def test_fake_tenant_flood_drill_one_shot():
    inj = FaultInjector.from_spec("tenant:flood:5")
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, faults=inj)
    await eng.start()
    try:
        r = await eng.generate("real request", max_tokens=4)
        assert r.finish_reason in ("stop", "length")
        assert inj.fired("tenant") == 1
        # One-shot: a second submission injects nothing more.
        await eng.generate("another", max_tokens=4)
        assert inj.fired("tenant") == 1
        # The burst was real decode work under the synthetic tenant; let
        # it drain and verify it flowed through the queue stats.
        for _ in range(500):
            if not eng._queue and all(s is None for s in eng._slots):
                break
            await asyncio.sleep(0.01)
        assert not eng._queue
    finally:
        await eng.stop()


def test_flood_drill_spec_validation():
    with pytest.raises(ValueError):
        FaultInjector.from_spec("tenant:flood")        # unsized
    with pytest.raises(ValueError):
        FaultInjector.from_spec("admit:flood:3")       # wrong point
    inj = FaultInjector.from_spec("tenant:flood:7")
    assert inj.has_any("tenant")
    assert inj.tenant_flood() == 7
    assert inj.tenant_flood() == 0                     # disarmed


def test_queue_expired_visible_in_engine_stats():
    eng = FakeChunkedEngine(batch_size=1)
    dead = _fake_req(eng, "late", lane=LANE_INTERACTIVE, tenant="t")
    dead.deadline = time.monotonic() - 1.0
    eng._queue.put(dead)
    eng._queue._purge_locked(time.monotonic(), force=True)
    assert eng.stats()["qos"]["expired"] == 1
    assert eng.qos_health()["queue_expired_total"] == 1
    with pytest.raises(GenerationTimeout):
        _drain_text(dead)


# ---------------------------------------------------------------------------
# Fleet: lane-aware routing + the FLEET_SIZE=2 flood smoke (CI step)
# ---------------------------------------------------------------------------


async def test_fleet_routes_interactive_to_preemptible_replica():
    from ai_agent_kubectl_tpu.engine.fleet import EngineFleet

    class _Eng:
        ready = True

        def __init__(self, lanes):
            self._lanes = lanes
            self._slots = [object()] * sum(lanes.values())

        def lane_occupancy(self):
            return dict(self._lanes)

    # Replica 0: 3 slots of preemptible background. Replica 1: 2 slots
    # of interactive. Raw occupancy prefers replica 1; lane-aware
    # routing knows replica 0 is effectively idle for interactive.
    fleet = EngineFleet([_Eng({"background": 3}),
                         _Eng({"interactive": 2})], affinity=False)
    assert fleet._route("p", lane=LANE_INTERACTIVE).idx == 0
    # For background arrivals every slot contends: replica 1 is lighter.
    assert fleet._route("p", lane=LANE_BACKGROUND).idx == 1
    # Lane-blind routing (direct engine calls) keeps the old key.
    assert fleet._route("p").idx == 1


async def test_fleet_flood_drill_keeps_interactive_probe_bounded():
    """The CI tenant-flood chaos smoke (ISSUE 7 satellite): FLEET_SIZE=2
    fake replicas, a tenant:flood:12 drill armed through the shared
    injector, then an interactive probe — admitted promptly despite the
    burst, and the fleet /health rollup exposes the QoS state."""
    from ai_agent_kubectl_tpu.engine.fleet import EngineFleet

    inj = FaultInjector.from_spec("tenant:flood:12")
    reps = [FakeChunkedEngine(batch_size=2, chunk_len=4,
                              preempt_wait_ms=5.0,
                              stream_fn=lambda p: [9] * 40 + [2],
                              faults=inj.for_replica(i))
            for i in range(2)]
    fleet = EngineFleet(reps, affinity=False)
    await fleet.start()
    try:
        with use_qos(QoSContext(tenant="probe", lane=LANE_INTERACTIVE)):
            t0 = time.monotonic()
            r = await fleet.generate("interactive probe", max_tokens=4)
            probe_wall = time.monotonic() - t0
        assert r.finish_reason in ("stop", "length")
        assert inj.fired("tenant") == 1
        # Bounded: the probe never waited out 12 × 40-token burst.
        assert probe_wall < 2.0
        qh = fleet.qos_health()
        assert "lanes" in qh and "brownout_level" in qh
        # Let the burst drain so stop() is clean, then check aggregation.
        for _ in range(1000):
            if all(not rep._queue and all(s is None for s in rep._slots)
                   for rep in reps):
                break
            await asyncio.sleep(0.01)
        stats = fleet.stats()
        assert "qos" in stats and "lane_depth" in stats["qos"]
    finally:
        await fleet.stop()


# ---------------------------------------------------------------------------
# HTTP surface: 429 mapping, classification clamp, /health + /metrics
# ---------------------------------------------------------------------------


async def _make_client(cfg, engine):
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    app = create_app(cfg, engine,
                     executor=CommandExecutor(timeout=cfg.execution_timeout))
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


def _cfg(**over):
    from ai_agent_kubectl_tpu.config import ServiceConfig

    defaults = dict(engine="fake", model_name="fake", llm_timeout=2.0,
                    rate_limit="1000/minute")
    defaults.update(over)
    return ServiceConfig(**defaults)


async def test_http_tenant_overloaded_maps_to_429():
    from ai_agent_kubectl_tpu.engine.fake import FakeEngine

    engine = FakeEngine()
    client = await _make_client(_cfg(), engine)
    try:
        engine.fail_with = TenantOverloaded(
            "tenant queue cap reached (3/3 queued for tenant 'x')",
            retry_after=7.0, tenant="x", lane="interactive")
        resp = await client.post("/kubectl-command",
                                 json={"query": "list the pods"})
        assert resp.status == 429
        assert resp.headers["Retry-After"] == "7"
        body = await resp.json()
        assert "Tenant over queue quota" in body["detail"]
        assert "tenant queue cap" in body["detail"]
    finally:
        await client.close()


async def test_http_classification_clamped_by_tier():
    from ai_agent_kubectl_tpu.engine.fake import FakeEngine
    from ai_agent_kubectl_tpu.engine.qos import current_qos

    class _Probe(FakeEngine):
        def __init__(self):
            super().__init__()
            self.seen = []

        async def generate(self, prompt, **kw):
            self.seen.append(current_qos())
            return await super().generate(prompt, **kw)

    engine = _Probe()
    client = await _make_client(
        _cfg(tenant_tiers="bulk-key:batch"), engine)
    try:
        # Tier clamps an X-Priority above it...
        await client.post("/kubectl-command",
                          json={"query": "list pods one"},
                          headers={"X-API-Key": "bulk-key",
                                   "X-Priority": "interactive"})
        # ...but allows self-demotion below it.
        await client.post("/kubectl-command",
                          json={"query": "list pods two"},
                          headers={"X-API-Key": "bulk-key",
                                   "X-Priority": "background"})
        # No key: client IP keys the tenant at the default lane.
        await client.post("/kubectl-command",
                          json={"query": "list pods three"})
        # An UNREGISTERED key must not mint a fresh tenant (spoof
        # resistance): it buckets by client IP like keyless traffic.
        await client.post("/kubectl-command",
                          json={"query": "list pods four"},
                          headers={"X-API-Key": "spoofed-random-key"})
        lanes = [c.lane for c in engine.seen]
        assert lanes == ["batch", "background", "interactive",
                         "interactive"]
        assert engine.seen[0].tenant == "bulk-key"
        assert engine.seen[2].tenant not in ("bulk-key", "")
        assert engine.seen[3].tenant == engine.seen[2].tenant
    finally:
        await client.close()


async def test_http_health_and_metrics_expose_qos():
    eng = FakeChunkedEngine(batch_size=2)
    client = await _make_client(_cfg(), eng)
    try:
        health = await (await client.get("/health")).json()
        assert health["qos"]["lanes"] == {
            "background": 0, "batch": 0, "interactive": 0}
        assert health["qos"]["brownout_level"] == 0
        assert "preemptions_last_60s" in health["qos"]
        text = await (await client.get("/metrics")).text()
        assert 'qos_queue_depth{lane="interactive"}' in text
        assert "qos_brownout_level" in text
        assert "queue_expired_total" in text
        assert "qos_preemptions_total" in text
    finally:
        await client.close()


# ---------------------------------------------------------------------------
# BatchedJaxEngine on CPU: the real preempt-and-replay, byte-identical
# ---------------------------------------------------------------------------

JAX_KW = dict(dtype="float32", max_seq_len=64, prefill_buckets=(16,),
              prefix_cache=False,
              batch_size=2, chunk_len=4, chunk_pipe_depth=2)

#: (prompt, temperature, seed) — two greedy + two sampled background
#: requests, so byte-parity across preemption also proves the seeded
#: RNG re-alignment at temperature > 0, plus one interactive probe.
BG_REQS = [("bulk a ", 0.0, 101), ("bulk b ", 0.9, 202),
           ("bulk c ", 0.9, 303), ("bulk d ", 0.0, 404)]
PROBE = ("quick q ", 0.0, 505)


def _mk_jax_engine(**over):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    kw = dict(JAX_KW)
    kw.update(over)
    return BatchedJaxEngine(get_config("toy-8m"), tokenizer=ByteTokenizer(),
                            **kw)


@pytest.fixture(scope="module")
def jax_qos_baseline():
    """Uncontended transcripts for every request (preemption off)."""
    eng = _mk_jax_engine(preempt_wait_ms=0.0)
    asyncio.run(eng.start())

    async def run():
        out = {}
        for p, t, s in BG_REQS + [PROBE]:
            r = await eng.generate(p, max_tokens=40, temperature=t, seed=s)
            out[p] = r.text
        return out

    try:
        base = asyncio.run(run())
    finally:
        asyncio.run(eng.stop())
    return base


async def test_jax_preempted_victim_replays_byte_identical(
        jax_qos_baseline):
    """THE acceptance criterion on the real engine: with both slots busy
    on background work, an interactive arrival preempts the cheapest
    victim within PREEMPT_WAIT_MS + one chunk, and every transcript —
    preempted victims included, at temperature 0 AND 0.9 — is
    byte-identical to the uncontended run. The victim's trace shows the
    preempt/resume slot handoff."""
    from ai_agent_kubectl_tpu.obs.trace import Trace, use_trace

    eng = _mk_jax_engine(preempt_wait_ms=15.0, preempt_budget=2)
    await eng.start()
    traces = {}

    async def run_bg(p, t, s):
        tr = Trace(f"qos-{p.strip()}", "POST", "/t")
        traces[p] = tr
        with use_trace(tr):
            with use_qos(QoSContext(tenant="bulk", lane=LANE_BACKGROUND)):
                return await eng.generate(p, max_tokens=40,
                                          temperature=t, seed=s)

    try:
        bg_tasks = [asyncio.create_task(run_bg(p, t, s))
                    for p, t, s in BG_REQS]
        # Both slots seated AND past their first consumed token: a
        # victim preempted at zero generated tokens legitimately
        # re-admits as FRESH (no "replayed into slot" event — the
        # documented zero-token path), so the handoff assertion below
        # needs every candidate victim to have something to carry.
        for _ in range(800):
            await asyncio.sleep(0.005)
            if all(s is not None and len(s.detok.ids) > 0
                   for s in eng._slots):
                break
        else:
            pytest.fail("background never filled the slots")
        p, t, s = PROBE
        with use_qos(QoSContext(tenant="quiet", lane=LANE_INTERACTIVE)):
            probe = await eng.generate(p, max_tokens=8,
                                       temperature=t, seed=s)
        bg = await asyncio.gather(*bg_tasks)
        qos = eng.stats()["qos"]
        assert qos["preemptions"] >= 1
        # Byte-identity for every participant (greedy AND sampled).
        assert probe.text == jax_qos_baseline[PROBE[0]][:len(probe.text)]
        for (pp, _, _), r in zip(BG_REQS, bg):
            assert r.text == jax_qos_baseline[pp], \
                f"transcript changed across preemption for {pp!r}"
        # The trace shows the preempt → resume slot handoff.
        events = [m for tr in traces.values()
                  for (_, m, _) in tr._events]
        assert any("preempted out of slot" in m for m in events)
        assert any("replayed into slot" in m for m in events)
        assert any("resuming after" in m for m in events)
    finally:
        await eng.stop()


async def test_jax_direct_calls_default_lane_unchanged():
    """No QoS context → one interactive anon bucket: plain engine calls
    behave exactly as before the ring existed (and never preempt)."""
    eng = _mk_jax_engine(preempt_wait_ms=15.0)
    await eng.start()
    try:
        rs = await asyncio.gather(*[
            eng.generate(p, max_tokens=8, temperature=0.0, seed=s)
            for p, _, s in BG_REQS])
        assert all(r.completion_tokens > 0 for r in rs)
        assert eng.stats()["qos"]["preemptions"] == 0
        assert eng.stats()["qos"]["lane_occupancy"]["interactive"] == 0
    finally:
        await eng.stop()
