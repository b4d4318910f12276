"""Chaos suite: the fault-injection harness (testing/faults.py) driving the
failure-containment subsystem end-to-end — bounded admission + load
shedding, circuit breaker + rule-based degradation, and the
watchdog-hang/recovery loop — against the real HTTP app (ISSUE 1
acceptance criteria a/b/c)."""

import asyncio
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_agent_kubectl_tpu.config import ServiceConfig
from ai_agent_kubectl_tpu.engine.fake import FakeEngine
from ai_agent_kubectl_tpu.engine.fallback import FallbackEngine, rule_command
from ai_agent_kubectl_tpu.engine.protocol import (EngineOverloaded,
                                                  EngineUnavailable)
from ai_agent_kubectl_tpu.server.app import create_app
from ai_agent_kubectl_tpu.server.breaker import CircuitBreaker
from ai_agent_kubectl_tpu.testing.faults import (ChaosEngine, FaultInjector,
                                                 InjectedFault)


def make_cfg(**over):
    defaults = dict(engine="fake", model_name="fake", llm_timeout=5.0,
                    rate_limit="10000/minute")
    defaults.update(over)
    return ServiceConfig(**defaults)


async def make_client(cfg, engine):
    app = create_app(cfg, engine)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


def toy_batched(**over):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    kw = dict(dtype="float32", max_seq_len=128, prefill_buckets=(64,),
              batch_size=2, chunk_len=4, prefix_cache=False)
    kw.update(over)
    return BatchedJaxEngine(get_config("toy-8m"), **kw)


# ---------------------------------------------------------------- harness


def test_fault_spec_parsing():
    inj = FaultInjector.from_spec("admit:error:0.5,chunk:hang,generate:delay:2.0")
    assert inj.has("admit") and inj.has("chunk") and inj.has("generate")
    assert inj._faults["admit"].mode == "error"
    assert inj._faults["admit"].rate == 0.5
    assert inj._faults["chunk"].mode == "hang"
    assert inj._faults["generate"].mode == "delay"
    assert inj._faults["generate"].arg == 2.0
    assert FaultInjector.from_spec("") is None
    assert FaultInjector.from_spec("   ") is None
    with pytest.raises(ValueError):
        FaultInjector.from_spec("admit")             # no mode
    with pytest.raises(ValueError):
        FaultInjector.from_spec("admit:explode")     # unknown mode
    with pytest.raises(ValueError):
        FaultInjector.from_spec("admit:error:1.5")   # rate out of range
    with pytest.raises(ValueError):
        FaultInjector.from_spec("generate:delay")    # delay needs seconds


async def test_fault_injector_modes():
    inj = FaultInjector(seed=0)
    # error fires and counts
    inj.set("generate", "error")
    with pytest.raises(InjectedFault):
        await inj.acheck("generate")
    with pytest.raises(InjectedFault):
        inj.check("generate")
    assert inj.fired("generate") == 2
    # rate 0 never fires
    inj.set("generate", "error", 0.0)
    for _ in range(20):
        await inj.acheck("generate")
    assert inj.fired("generate") == 2
    # delay sleeps roughly the configured time
    inj.set("generate", "delay", 0.05)
    t0 = time.monotonic()
    await inj.acheck("generate")
    assert time.monotonic() - t0 >= 0.04
    # hang blocks until its max, or until released
    inj.set("generate", "hang", 0.1)
    t0 = time.monotonic()
    await inj.acheck("generate")
    assert time.monotonic() - t0 >= 0.08
    inj.set("generate", "hang", 30.0)
    inj.release("generate")          # disarms: next check is a no-op
    t0 = time.monotonic()
    await inj.acheck("generate")
    assert time.monotonic() - t0 < 0.05
    # unarmed points are free
    inj.clear()
    inj.check("anything")


async def test_chaos_engine_wraps_transparently():
    faults = FaultInjector()
    inner = FakeEngine()
    eng = ChaosEngine(inner, faults)
    await eng.start()
    assert eng.ready and eng.name == "fake"
    r = await eng.generate("User Request: list pods\nKubectl Command:")
    assert r.text == "kubectl get pods"
    faults.set("generate", "error")
    with pytest.raises(InjectedFault):
        await eng.generate("User Request: list pods\nKubectl Command:")
    assert inner.calls == 1          # fault fired before the inner engine
    faults.clear()
    pieces = [p async for p in eng.generate_stream(
        "User Request: list pods\nKubectl Command:")]
    assert "".join(pieces) == "kubectl get pods"
    await eng.stop()


def test_factory_wraps_generate_faults():
    from ai_agent_kubectl_tpu.server.factory import build_engine

    cfg = make_cfg(fault_points="generate:error:1.0")
    eng = build_engine(cfg)
    assert isinstance(eng, ChaosEngine)
    # engine-internal points on an engine that can never fire them must
    # refuse to boot, not run a silently inert drill
    cfg2 = make_cfg(fault_points="admit:error:1.0")    # ENGINE=fake
    with pytest.raises(ValueError):
        build_engine(cfg2)
    # ...but are fine on the continuous-batching engine (no wrapper needed)
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine

    cfg3 = make_cfg(engine="jax", model_name="toy-8m", decode_batch_size=4,
                    fault_points="admit:error:1.0")
    assert isinstance(build_engine(cfg3), BatchedJaxEngine)


def test_factory_refuses_to_boot_on_malformed_fault_spec():
    """A typo'd FAULT_POINTS must crash startup, not degrade-start into a
    503 outage that masquerades as the drill's result."""
    from ai_agent_kubectl_tpu.server.factory import build_engine

    with pytest.raises(ValueError):
        build_engine(make_cfg(fault_points="generat:error:1.0"))


def test_factory_shares_one_injector_across_layers():
    """admit/chunk (batcher-internal) and generate (ChaosEngine) points
    must live on ONE injector so fired()/release()/clear() see them all."""
    from ai_agent_kubectl_tpu.server.factory import build_engine

    cfg = make_cfg(engine="jax", model_name="toy-8m", decode_batch_size=4,
                   fault_points="admit:error:1.0,generate:error:1.0")
    eng = build_engine(cfg)
    assert isinstance(eng, ChaosEngine)
    assert eng.inner.faults is eng.faults


# ---------------------------------------------------------------- breaker


def test_breaker_state_machine():
    clock = [0.0]
    b = CircuitBreaker(threshold=2, window_secs=10.0, recovery_secs=5.0,
                       timer=lambda: clock[0])
    assert b.state == "closed" and b.begin() is not None
    b.record_failure()
    assert b.state == "closed"
    b.record_failure()
    assert b.state == "open" and b.opens == 1
    assert b.begin() is None
    clock[0] = 4.9
    assert b.begin() is None
    # recovery elapsed: half-open admits exactly one probe
    clock[0] = 5.1
    assert b.state == "half-open"
    assert b.begin() is not None
    assert b.begin() is None
    # failed probe re-opens and restarts the recovery clock
    b.record_failure()
    assert b.state == "open"
    clock[0] = 10.3
    assert b.state == "half-open" and b.begin() is not None
    b.record_success()
    assert b.state == "closed" and b.begin() is not None
    # rolling window: old failures age out instead of accumulating forever
    b.record_failure()
    clock[0] = 25.0
    b.record_failure()
    assert b.state == "closed"
    assert b.recent_failures == 1


def test_breaker_disabled_never_opens():
    b = CircuitBreaker(threshold=0)
    for _ in range(50):
        b.record_failure()
    assert b.state == "closed" and b.begin() is not None


def test_fallback_engine_rules():
    assert rule_command("list all pods") == "kubectl get pods"
    assert rule_command("scale deployment web to 5") == \
        "kubectl scale deployment web --replicas=5"
    assert rule_command("what is the meaning of life") == "kubectl get all"


async def test_fallback_engine_is_read_only():
    """The degraded path must never mint a mutating command from a blind
    keyword match: "why did X delete pod web-1" degrades to the safe
    catch-all, not to kubectl delete."""
    eng = FallbackEngine()
    r = await eng.generate(
        "User Request: why did the autoscaler delete pod web-1\n"
        "Kubectl Command:")
    assert r.text == "kubectl get all"
    r = await eng.generate(
        "User Request: scale deployment web to 0\nKubectl Command:")
    assert r.text == "kubectl get all"
    # read-only rules still answer
    r = await eng.generate(
        "User Request: describe pod web-1\nKubectl Command:")
    assert r.text == "kubectl describe pod web-1"


def test_breaker_opens_under_partial_failure():
    """Interleaved successes must not reset the rolling failure window —
    a 50%-failing engine (one bad shard) still opens the breaker."""
    clock = [0.0]
    b = CircuitBreaker(threshold=3, window_secs=10.0, recovery_secs=5.0,
                       timer=lambda: clock[0])
    for i in range(3):
        b.record_failure()
        assert b.state == ("open" if i == 2 else "closed")
        if i < 2:
            b.record_success()
        clock[0] += 1.0
    assert b.state == "open"


# ------------------------------------------- (a) overload shedding, HTTP cap


async def test_http_inflight_cap_sheds_fast():
    """A burst beyond MAX_INFLIGHT_REQUESTS is shed with an immediate 503 +
    Retry-After while the admitted requests complete normally."""
    engine = FakeEngine(delay=0.5)
    client = await make_client(make_cfg(max_inflight_requests=2), engine)
    try:
        async def timed(i):
            t0 = time.monotonic()
            resp = await client.post("/kubectl-command",
                                     json={"query": f"describe pod web-{i}"})
            body = await resp.json() if resp.status in (200, 503) else None
            return resp.status, time.monotonic() - t0, resp.headers, body

        results = await asyncio.gather(*[timed(i) for i in range(8)])
        shed = [r for r in results if r[0] == 503]
        served = [r for r in results if r[0] == 200]
        assert len(served) == 2 and len(shed) == 6
        for status, elapsed, headers, _body in shed:
            assert "Retry-After" in headers
            assert int(headers["Retry-After"]) >= 1
            # shed target is <100 ms; allow slack for loaded CI hosts
            assert elapsed < 1.0
        for _status, _elapsed, _headers, body in served:
            assert body["kubectl_command"].startswith("kubectl")
            assert body["degraded"] is False
        text = await (await client.get("/metrics")).text()
        assert 'queue_rejections_total{layer="http"} 6.0' in text
    finally:
        await client.close()


# --------------------------------------- (a) overload shedding, engine queue


async def test_queue_overflow_sheds_with_retry_after():
    """4× the batcher's admission capacity: the overflow is shed at submit
    time with 503 + Retry-After (instead of queueing until a 60 s 504)
    and every admitted request completes."""
    eng = toy_batched(batch_size=1, max_queue_depth=2)
    cfg = make_cfg(engine="jax", model_name="toy-8m", max_new_tokens=16,
                   max_inflight_requests=0, llm_timeout=30.0)
    client = await make_client(cfg, eng)
    try:
        async def timed(i):
            t0 = time.monotonic()
            resp = await client.post("/kubectl-command",
                                     json={"query": f"describe pod x{i}"})
            body = await resp.json()
            return resp.status, time.monotonic() - t0, resp.headers, body

        # capacity ≈ 1 decoding slot + 2 queued; 12 requests = 4× that.
        # The random-init toy model can emit text the safety validator
        # rejects (422) — that still means the request was ADMITTED and
        # generation COMPLETED, which is what this test is about.
        results = await asyncio.gather(*[timed(i) for i in range(12)])
        shed = [r for r in results if r[0] == 503]
        served = [r for r in results if r[0] in (200, 422)]
        assert len(shed) + len(served) == 12
        assert shed, "a 4x-capacity burst must shed something"
        assert served, "admitted requests must still be served"
        for _status, elapsed, headers, body in shed:
            assert "Retry-After" in headers
            assert int(headers["Retry-After"]) >= 1
            assert "overloaded" in body["detail"].lower()
            assert elapsed < 1.0       # shed fast, not after a timeout
        for status, _elapsed, _headers, body in served:
            if status == 200:
                assert body["kubectl_command"]
        stats = eng.stats()
        assert stats["queue_rejections"] == len(shed)
        assert stats["max_queue_depth"] == 2
        text = await (await client.get("/metrics")).text()
        assert f'queue_rejections_total{{layer="engine"}} {float(len(shed))}' in text
    finally:
        await client.close()


def test_retry_after_hint_tracks_drain_rate():
    eng = toy_batched()
    # no drain history: flat default
    assert eng.retry_after_hint() == 5.0
    # 11 finishes over the last second → ~10 req/s drain rate
    now = time.monotonic()
    eng._finish_times.extend(now - 1.0 + i * 0.1 for i in range(11))
    assert eng.retry_after_hint(extra_depth=20) == pytest.approx(2.0, rel=0.2)
    assert eng.retry_after_hint(extra_depth=1) == 1.0          # floor
    assert eng.retry_after_hint(extra_depth=100_000) == 60.0   # ceiling
    # stale history (idle gap) must not dilute the rate into a huge
    # Retry-After: old timestamps age out and the default returns
    eng._finish_times.clear()
    eng._finish_times.extend(now - 3600.0 + i * 0.1 for i in range(11))
    assert eng.retry_after_hint(extra_depth=20) == 5.0


# ------------------------------- (b) breaker + degraded rule-based fallback


async def test_breaker_fallback_degraded_then_recovery():
    """With DEGRADED_FALLBACK=true, engine failures open the breaker and
    /kubectl-command keeps answering 200 with degraded rule-based
    commands (never 503); once the engine heals, a half-open probe
    re-closes the breaker and real generation resumes."""
    faults = FaultInjector()
    inner = FakeEngine()
    engine = ChaosEngine(inner, faults)
    cfg = make_cfg(degraded_fallback=True, breaker_threshold=2,
                   breaker_window_secs=30.0, breaker_recovery_secs=1.0)
    client = await make_client(cfg, engine)
    try:
        faults.set("generate", "error")
        for i in range(5):
            resp = await client.post(
                "/kubectl-command", json={"query": f"list pods batch {i}"})
            assert resp.status == 200, "degraded mode must never 503"
            body = await resp.json()
            assert body["degraded"] is True
            assert body["kubectl_command"] == "kubectl get pods"
            assert body["engine_metadata"]["engine"] == "fallback-rules"
        # the breaker opened after `threshold` failures and stopped
        # hitting the engine — not all 5 requests fired the fault
        assert faults.fired("generate") <= 3
        assert inner.calls == 0

        resp = await client.get("/health")
        assert resp.status == 200            # engine process is alive
        health = await resp.json()
        assert health["status"] == "degraded"
        assert health["breaker"] == "open"
        assert health["degraded_fallback"] is True

        text = await (await client.get("/metrics")).text()
        assert "degraded_responses_total 5.0" in text
        assert "breaker_state 2.0" in text

        # engine heals; after recovery_secs the half-open probe succeeds
        faults.clear()
        await asyncio.sleep(1.05)
        resp = await client.post("/kubectl-command",
                                 json={"query": "list pods recovered"})
        body = await resp.json()
        assert resp.status == 200
        assert body["degraded"] is False
        assert body["engine_metadata"]["engine"] == "fake"
        assert inner.calls == 1
        health = await (await client.get("/health")).json()
        assert health["breaker"] == "closed" and health["status"] == "healthy"
    finally:
        await client.close()


async def test_stream_degraded_event_when_breaker_open():
    faults = FaultInjector()
    engine = ChaosEngine(FakeEngine(), faults)
    cfg = make_cfg(degraded_fallback=True, breaker_threshold=1,
                   breaker_recovery_secs=60.0)
    client = await make_client(cfg, engine)
    try:
        faults.set("generate", "error")
        resp = await client.post("/kubectl-command/stream",
                                 json={"query": "show deployments now"})
        assert resp.status == 200
        text = await resp.text()
        assert "event: degraded" in text
        assert "event: done" in text
        assert "kubectl get deployments" in text
    finally:
        await client.close()


async def test_breaker_open_without_fallback_fails_fast():
    """No DEGRADED_FALLBACK: an open breaker fails new requests instantly
    (503) instead of letting each one ride the failing engine."""
    faults = FaultInjector()
    inner = FakeEngine()
    engine = ChaosEngine(inner, faults)
    cfg = make_cfg(breaker_threshold=2, breaker_recovery_secs=60.0)
    client = await make_client(cfg, engine)
    try:
        faults.set("generate", "error")
        for i in range(5):
            resp = await client.post(
                "/kubectl-command", json={"query": f"get nodes round {i}"})
            assert resp.status == 503
        assert faults.fired("generate") == 2   # breaker short-circuited 3
        health = await (await client.get("/health")).json()
        assert health["breaker"] == "open"
        assert health["degraded_fallback"] is False
    finally:
        await client.close()


# --------------------- (c) hung dispatch → watchdog → breaker → recovery


async def test_hung_chunk_trips_watchdog_breaker_and_recovers():
    """An injected hung chunk dispatch blocks the scheduler thread like a
    hung device; the watchdog fails in-flight waiters promptly, /health
    flips to degraded with the breaker state visible, and once the hang
    is released recovery re-closes the breaker end-to-end."""
    faults = FaultInjector()
    eng = toy_batched(batch_size=2, watchdog_secs=1.0, faults=faults)
    cfg = make_cfg(engine="jax", model_name="toy-8m", max_new_tokens=16,
                   llm_timeout=30.0, breaker_threshold=1,
                   breaker_recovery_secs=0.1)
    client = await make_client(cfg, eng)
    try:
        # warmup: generation completes (422 = random-init toy output
        # failed the safety validator after a full generation — engine OK)
        resp = await client.post("/kubectl-command",
                                 json={"query": "list pods warmup"})
        assert resp.status in (200, 422)

        faults.set("chunk", "hang", 30.0)
        t0 = time.monotonic()
        resp = await client.post("/kubectl-command",
                                 json={"query": "describe pod hung-one"})
        elapsed = time.monotonic() - t0
        assert resp.status == 503
        # failed by the watchdog (~1-2 s), not by the 30 s llm_timeout
        assert elapsed < 10.0

        resp = await client.get("/health")
        assert resp.status == 503
        health = await resp.json()
        assert health["status"] == "degraded"
        assert health["engine_ready"] is False
        assert health["breaker"] == "open"

        # release the hang: the scheduler resumes, the watchdog re-marks
        # the engine ready on its next progress check
        faults.release("chunk")
        for _ in range(100):
            resp = await client.get("/health")
            if resp.status == 200:
                break
            await asyncio.sleep(0.1)
        else:
            pytest.fail("engine did not recover after the hang was released")

        # breaker half-open by now; the next request is the probe that
        # re-closes it and real generation resumes (breaker success is
        # recorded before output parsing, so a 422 still closes it)
        resp = await client.post("/kubectl-command",
                                 json={"query": "list pods after recovery"})
        assert resp.status in (200, 422)
        if resp.status == 200:
            assert (await resp.json())["degraded"] is False
        health = await (await client.get("/health")).json()
        assert health["breaker"] == "closed" and health["status"] == "healthy"
    finally:
        await client.close()


# ----------------------------------------- engine-level containment paths


async def test_admission_fault_fails_only_that_request():
    """An admission failure (e.g. scratch-cache OOM) errors the one
    request, not the engine: readiness holds and the next request works."""
    faults = FaultInjector()
    eng = toy_batched(faults=faults)
    await eng.start()
    try:
        faults.set("admit", "error")
        with pytest.raises(EngineUnavailable):
            await eng.generate("list pods", max_tokens=4, temperature=0.0)
        assert eng.ready
        faults.clear()
        r = await eng.generate("list pods", max_tokens=4, temperature=0.0)
        assert r.completion_tokens > 0
    finally:
        await eng.stop()


async def test_mid_drain_abort_with_hung_chunk():
    """stop(drain_secs) while a chunk dispatch hangs: the drain deadline
    passes and the in-flight request is aborted with EngineUnavailable
    instead of blocking shutdown forever."""
    faults = FaultInjector()
    eng = toy_batched(faults=faults)
    await eng.start()
    faults.set("chunk", "hang", 1.0)    # max 1 s per dispatch
    task = asyncio.create_task(
        eng.generate("describe pod slow-drain", max_tokens=100,
                     temperature=0.0))
    await asyncio.sleep(0.2)            # admitted; dispatch now hanging
    await eng.stop(drain_secs=0.2)
    with pytest.raises(EngineUnavailable):
        await task


async def test_engine_overload_raises_typed_error():
    """Direct engine API: submissions beyond max_queue_depth raise
    EngineOverloaded (with a retry_after) while queued work completes."""
    eng = toy_batched(batch_size=1, max_queue_depth=1)
    await eng.start()
    try:
        tasks = [
            asyncio.create_task(
                eng.generate(f"get pods chunk {i}", max_tokens=12,
                             temperature=0.0))
            for i in range(10)
        ]
        results = await asyncio.gather(*tasks, return_exceptions=True)
        shed = [r for r in results if isinstance(r, EngineOverloaded)]
        ok = [r for r in results if not isinstance(r, BaseException)]
        assert len(shed) + len(ok) == 10
        assert shed and ok
        assert all(r.retry_after >= 0 for r in shed)
        assert all(r.completion_tokens > 0 for r in ok)
    finally:
        await eng.stop()


# ------------------------------------------- review regressions (PR 1 fixes)


def test_breaker_release_probe_unwedges_half_open():
    clock = [0.0]
    b = CircuitBreaker(threshold=1, window_secs=10.0, recovery_secs=1.0,
                       timer=lambda: clock[0])
    b.record_failure()
    clock[0] = 1.5
    assert b.state == "half-open" and b.begin() is not None
    # probe slot taken; an undecided outcome must return it
    assert b.begin() is None
    b.release_probe()
    assert b.begin() is not None
    # and release_probe is a safe no-op when closed
    b.record_success()
    b.release_probe()
    assert b.state == "closed" and b.begin() is not None


async def test_cancelled_probe_does_not_wedge_breaker():
    """A half-open probe whose client disconnects (handler task cancelled)
    or that gets shed as overload must release the probe slot — otherwise
    the breaker stays half-open rejecting everyone forever."""
    from ai_agent_kubectl_tpu.server.app import Service

    cfg = make_cfg(breaker_threshold=1, breaker_recovery_secs=0.0)
    engine = FakeEngine()
    await engine.start()
    svc = Service(cfg, engine)
    svc.breaker.record_failure()              # open; recovery 0 → half-open
    assert svc.breaker.state == "half-open"

    async def hang():
        await asyncio.sleep(30)

    task = asyncio.create_task(svc.run_engine(hang))
    await asyncio.sleep(0.05)                 # probe slot taken
    assert svc.breaker._probe_inflight
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    assert svc.breaker._probe_inflight is False

    async def shed():
        raise EngineOverloaded("queue full", retry_after=2.0)

    with pytest.raises(EngineOverloaded):     # overload ≠ engine outcome
        await svc.run_engine(shed)
    assert svc.breaker._probe_inflight is False
    probe = svc.breaker.begin()               # next probe still admitted
    assert probe is not None
    svc.breaker.release_probe(probe)


async def test_chaos_engine_forwards_retry_after_hint():
    faults = FaultInjector()
    eng = ChaosEngine(toy_batched(), faults)
    assert eng.retry_after_hint() == 5.0      # inner batcher's cold default
    assert ChaosEngine(FakeEngine(), faults).retry_after_hint() == 1.0


async def test_stream_degraded_unsafe_rule_yields_error_event():
    """A rule template interpolating an unsafe capture ("logs of web;id")
    on the degraded path must produce an in-band error event, not an
    unhandled handler exception that truncates the stream."""
    faults = FaultInjector()
    engine = ChaosEngine(FakeEngine(), faults)
    cfg = make_cfg(degraded_fallback=True, breaker_threshold=1,
                   breaker_recovery_secs=60.0)
    client = await make_client(cfg, engine)
    try:
        faults.set("generate", "error")
        resp = await client.post("/kubectl-command/stream",
                                 json={"query": "show logs of web;id"})
        assert resp.status == 200
        text = await resp.text()
        assert "event: error" in text
        assert "event: done" not in text
    finally:
        await client.close()


def test_breaker_fences_stragglers_from_before_open():
    """An engine call admitted while CLOSED can outlive a whole
    closed→open→half-open cycle (llm_timeout 60 s vs recovery 15 s). Its
    late outcome carries a stale epoch token and must neither clobber the
    in-flight probe slot nor close the open breaker."""
    clock = [0.0]
    b = CircuitBreaker(threshold=1, window_secs=10.0, recovery_secs=5.0,
                       timer=lambda: clock[0])
    straggler = b.begin()                 # admitted while closed
    assert straggler is not None
    b.record_failure()                    # another call opens the breaker
    assert b.state == "open"
    clock[0] = 6.0
    probe = b.begin()                     # the half-open probe
    assert probe is not None
    # late failure from the pre-open call: probe slot must survive and
    # the recovery clock must not restart
    b.record_failure(straggler)
    assert b._probe_inflight
    assert b.state == "half-open"
    # late success from the pre-open call: must NOT close an open breaker
    b.record_success(straggler)
    assert b.state == "half-open"
    # only the probe's own outcome decides
    b.record_success(probe)
    assert b.state == "closed"


async def test_negative_inflight_cap_means_unlimited():
    """MAX_INFLIGHT_REQUESTS=-1 (a common 'unlimited' spelling) must not
    shed 100% of traffic."""
    client = await make_client(make_cfg(max_inflight_requests=-1),
                               FakeEngine())
    try:
        resp = await client.post("/kubectl-command",
                                 json={"query": "list all pods"})
        assert resp.status == 200
    finally:
        await client.close()


async def test_coalesced_waiters_count_one_engine_shed():
    """N identical concurrent queries coalesce onto ONE single-flight
    engine call; when that call is shed, queue_rejections_total must
    count 1 (the actual engine shed), not N."""
    class SheddingEngine(FakeEngine):
        async def generate(self, prompt, **kw):
            self.calls += 1
            await asyncio.sleep(0.1)      # let the waiters pile up
            raise EngineOverloaded("queue full", retry_after=2.0)

    engine = SheddingEngine()
    client = await make_client(make_cfg(), engine)
    try:
        resps = await asyncio.gather(*[
            client.post("/kubectl-command", json={"query": "list all pods"})
            for _ in range(5)
        ])
        assert all(r.status == 503 for r in resps)
        assert all("Retry-After" in r.headers for r in resps)
        assert engine.calls == 1
        text = await (await client.get("/metrics")).text()
        assert 'queue_rejections_total{layer="engine"} 1.0' in text
    finally:
        await client.close()


def test_breaker_window_zero_disables():
    """BREAKER_WINDOW_SECS=0 follows the sibling knobs' '0 disables'
    convention instead of crashing the server at construction."""
    b = CircuitBreaker(threshold=5, window_secs=0.0, recovery_secs=-1.0)
    for _ in range(20):
        b.record_failure()
    assert b.state == "closed" and b.begin() is not None


def test_fault_spec_rejects_unknown_point():
    """A typo'd FAULT_POINTS entry must fail at startup, not silently arm
    nothing and let a game-day drill run against a healthy engine."""
    with pytest.raises(ValueError):
        FaultInjector.from_spec("generat:error:1.0")


def test_fault_spec_rejects_negative_arg():
    with pytest.raises(ValueError):
        FaultInjector.from_spec("chunk:delay:-5")
    with pytest.raises(ValueError):
        FaultInjector.from_spec("chunk:hang:-1")


async def test_startup_unreadiness_does_not_open_breaker():
    """'Engine not started' rejections during a restart's warm-up must not
    open the breaker — that would extend the outage past the model load by
    up to recovery_secs on every restart under live traffic."""
    from ai_agent_kubectl_tpu.server.app import Service

    cfg = make_cfg(breaker_threshold=1, breaker_recovery_secs=60.0)
    engine = FakeEngine()            # not started: ready is False
    svc = Service(cfg, engine)
    for _ in range(3):
        with pytest.raises(EngineUnavailable):
            await svc.run_engine(lambda: engine.generate("list pods"))
    assert svc.breaker.state == "closed"
    await engine.start()
    r = await svc.run_engine(lambda: engine.generate(
        "User Request: list pods\nKubectl Command:"))
    assert r.text == "kubectl get pods"
    assert svc.breaker.state == "closed"


def test_fault_spec_rejects_duplicate_points():
    with pytest.raises(ValueError):
        FaultInjector.from_spec("generate:error:0.5,generate:delay:2.0")


async def test_rearming_hang_releases_old_waiter():
    """set() over an armed hang must unblock anything waiting on the old
    fault — otherwise a drill adjustment orphans the scheduler thread for
    the old hang's full max_secs."""
    inj = FaultInjector()
    inj.set("chunk", "hang", 30.0)
    waited = []

    async def wait_old():
        t0 = time.monotonic()
        await inj.acheck("chunk")          # blocks on fault A's event
        waited.append(time.monotonic() - t0)

    task = asyncio.create_task(wait_old())
    await asyncio.sleep(0.05)
    inj.set("chunk", "hang", 5.0)          # re-arm: must release fault A
    await asyncio.wait_for(task, timeout=2.0)
    assert waited and waited[0] < 1.0
    inj.clear()
