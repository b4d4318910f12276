"""Engine fleet (ISSUE 6): replicated engines, health-aware routing,
zero-downtime drains, and cross-replica replay failover.

The fleet matrix, mostly on FakeChunkedEngine replicas (milliseconds,
same portable-state contract the jax batcher speaks) plus a lean
BatchedJaxEngine failover test and the full bs=48 acceptance chaos test
(slow-marked):

- routing: least-loaded, skips draining/ejected/open-breaker replicas,
  prefix affinity keeps agent-loop turns on the replica holding their KV;
- migration: hard-kill a replica mid-decode → the request re-splices
  onto a healthy replica from (prompt, generated-prefix, seed) and the
  client's stream continues BYTE-IDENTICAL to an undisturbed run;
- drain → eject → rejoin: a voluntary cycle drops nothing and /health
  ends green;
- hedged re-dispatch past FLEET_HEDGE_MS, overload rerouting, terminal
  quarantine (never migrated), migration budgets;
- replica-scoped drills (r0:scheduler:die) through one shared injector.
"""

import asyncio
import zlib

import pytest

from ai_agent_kubectl_tpu.config import ServiceConfig
from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine, FakeEngine
from ai_agent_kubectl_tpu.engine.fleet import (REPLICA_ACTIVE,
                                               REPLICA_DRAINING,
                                               REPLICA_EJECTED, EngineFleet,
                                               PrefixAffinity)
from ai_agent_kubectl_tpu.engine.protocol import (EngineOverloaded,
                                                  EngineUnavailable,
                                                  RequestQuarantined)
from ai_agent_kubectl_tpu.server.ratelimit import client_key
from ai_agent_kubectl_tpu.testing.faults import FaultInjector

# ---------------------------------------------------------------------------
# Router units: affinity map + client keying + routable filtering
# ---------------------------------------------------------------------------


def test_prefix_affinity_longest_match_and_eviction():
    aff = PrefixAffinity(maxsize=3)
    aff.record("sys prompt + turn1", 0)
    aff.record("sys prompt + turn1 + answer1", 1)
    # Turn 2 extends turn 1 + answer: the LONGEST recorded prefix wins.
    assert aff.lookup("sys prompt + turn1 + answer1 + turn2") == 1
    assert aff.lookup("sys prompt + turn1 plus other stuff") == 0
    assert aff.lookup("unrelated prompt") is None
    # LRU eviction keeps the map bounded.
    aff.record("aaaa", 0)
    aff.record("bbbb", 1)  # evicts the oldest entry
    assert len(aff._map) == 3
    # forget_replica drops every entry pointing at a gone replica.
    aff.forget_replica(1)
    assert aff.lookup("bbbb") is None


def test_client_key_proxy_modes():
    # Untrusted: the raw peer IP is authoritative, XFF is ignored.
    assert client_key("10.0.0.9", "1.1.1.1, 2.2.2.2", False) == "10.0.0.9"
    # Trusted (behind a fronting router tier): leftmost untrusted hop.
    assert client_key("10.0.0.9", "1.1.1.1, 2.2.2.2", True) == "1.1.1.1"
    assert client_key("10.0.0.9", " 3.3.3.3 ", True) == "3.3.3.3"
    # Degenerate headers fall back to the peer.
    assert client_key("10.0.0.9", " , ", True) == "10.0.0.9"
    assert client_key(None, None, True) == "unknown"


async def make_fleet(n=2, fleet_kw=None, **ekw):
    ekw.setdefault("chunk_len", 2)
    fleet = EngineFleet([FakeChunkedEngine(**ekw) for _ in range(n)],
                        **(fleet_kw or {}))
    await fleet.start()
    return fleet


async def baseline_text(prompt, max_tokens=100, **ekw):
    ekw.setdefault("chunk_len", 2)
    eng = FakeChunkedEngine(**ekw)
    await eng.start()
    try:
        return (await eng.generate(prompt, max_tokens=max_tokens)).text
    finally:
        await eng.stop()


def long_stream(prompt):
    """120-token deterministic stream — long enough to kill/drain a
    replica mid-decode with plenty of continuation left."""
    h = zlib.crc32(prompt.encode())
    return [10 + (h + 7 * i) % 200 for i in range(120)] + [2]


async def test_route_skips_unhealthy_and_prefers_least_loaded():
    fleet = await make_fleet(3)
    try:
        r0, r1, r2 = fleet.replicas
        r0.inflight, r1.inflight, r2.inflight = 5, 1, 3
        assert fleet._route("x").idx == 1
        r1.state = REPLICA_DRAINING
        assert fleet._route("x").idx == 2
        r2.state = REPLICA_EJECTED
        assert fleet._route("x").idx == 0
        # An open per-replica breaker takes the last candidate out too.
        for _ in range(5):
            r0.breaker.record_failure()
        assert fleet._route("x") is None
    finally:
        await fleet.stop()


async def test_route_affinity_with_slack_override():
    fleet = await make_fleet(2)
    try:
        r0, r1 = fleet.replicas
        fleet.affinity.record("session alpha", 1)
        r1.inflight = fleet.AFFINITY_SLACK  # within slack: affinity wins
        assert fleet._route("session alpha + next turn").idx == 1
        r1.inflight = fleet.AFFINITY_SLACK + 1  # hot spot: load wins
        assert fleet._route("session alpha + next turn").idx == 0
    finally:
        await fleet.stop()


# ---------------------------------------------------------------------------
# Serving parity + cross-replica migration
# ---------------------------------------------------------------------------


async def test_fleet_serves_byte_identical_to_single_engine():
    fleet = await make_fleet(2)
    try:
        for prompt in ("list pods please", "get nodes now", "top pods"):
            want = await baseline_text(prompt, max_tokens=32)
            got = await fleet.generate(prompt, max_tokens=32)
            assert got.text == want
            pieces = []
            async for p in fleet.generate_stream(prompt, max_tokens=32):
                pieces.append(p)
            assert "".join(pieces) == want
    finally:
        await fleet.stop()


async def test_migration_mid_stream_byte_identical():
    """THE failover contract: a client holding an open stream when its
    replica is hard-killed mid-decode sees a seamless, byte-identical
    continuation — the request re-splices from (prompt, prefix, seed)
    onto the healthy replica."""
    kw = dict(stream_fn=long_stream)
    fleet = await make_fleet(2, **kw)
    try:
        want = await baseline_text("migrate me", max_tokens=100, **kw)
        pieces = []
        async for p in fleet.generate_stream("migrate me", max_tokens=100):
            pieces.append(p)
            if len(pieces) == 3:
                victim = next(r for r in fleet.replicas if r.flights)
                asyncio.create_task(victim.engine.stop())
        assert "".join(pieces) == want
        assert fleet._migrations == 1
        assert fleet._migrated_tokens > 0
        h = fleet.fleet_health()
        assert h["migrations"] == 1
    finally:
        await fleet.stop()


async def test_migration_non_streaming_generate():
    kw = dict(stream_fn=long_stream)
    fleet = await make_fleet(2, **kw)
    try:
        want = await baseline_text("kill my replica", max_tokens=80, **kw)
        task = asyncio.create_task(
            fleet.generate("kill my replica", max_tokens=80))
        for _ in range(500):
            await asyncio.sleep(0.001)
            victims = [r for r in fleet.replicas if r.flights]
            if victims and victims[0].occupancy():
                asyncio.create_task(victims[0].engine.stop())
                break
        result = await task
        assert result.text == want
        assert fleet._migrations >= 1
    finally:
        await fleet.stop()


async def test_drain_eject_rejoin_cycle_drops_nothing():
    kw = dict(stream_fn=long_stream)
    fleet = await make_fleet(2, **kw)
    try:
        want = await baseline_text("drain me", max_tokens=100, **kw)
        pieces, started = [], []
        async for p in fleet.generate_stream("drain me", max_tokens=100):
            pieces.append(p)
            if len(pieces) == 3:
                victim = next(r for r in fleet.replicas if r.flights)
                started.append(
                    (victim.idx, asyncio.create_task(fleet.drain(victim.idx))))
        assert "".join(pieces) == want      # migrated, byte-identical
        idx, task = started[0]
        await task
        h = fleet.fleet_health()
        assert h["drains"] == 1 and h["migrations"] >= 1
        assert fleet.replicas[idx].state == REPLICA_EJECTED
        assert fleet.replicas[idx].eject_cause == "drain"
        assert fleet.ready                  # the sibling keeps serving
        await fleet.rejoin(idx)
        h = fleet.fleet_health()
        assert h["active"] == 2 and h["rejoins"] == 1
        assert fleet.replicas[idx].breaker.state == "closed"
        # The rejoined replica serves again (byte-identical as ever).
        got = await fleet.generate("drain me", max_tokens=100)
        assert got.text == want
    finally:
        await fleet.stop()


async def test_monitor_ejects_dead_replica_and_auto_rejoins():
    fleet = await make_fleet(2, fleet_kw=dict(rejoin_secs=0.05))
    try:
        victim = fleet.replicas[0]
        await victim.engine.stop()          # engine.ready drops
        for _ in range(200):
            await asyncio.sleep(0.01)
            if victim.state == REPLICA_EJECTED:
                break
        assert victim.eject_cause == "not_ready"
        assert fleet._ejects == 1
        for _ in range(300):                # auto-rejoin restarts it
            await asyncio.sleep(0.01)
            if victim.state == REPLICA_ACTIVE:
                break
        assert victim.state == REPLICA_ACTIVE
        assert fleet._rejoins == 1
        assert (await fleet.generate("alive again", max_tokens=8)).text
    finally:
        await fleet.stop()


# ---------------------------------------------------------------------------
# Hedging, overload rerouting, terminal errors, budgets
# ---------------------------------------------------------------------------


class SlowStartEngine(FakeChunkedEngine):
    """First event delayed — the hedge trigger scenario."""

    def __init__(self, delay=0.3, **kw):
        super().__init__(**kw)
        self._delay = delay

    async def stream_events(self, *a, **kw):
        await asyncio.sleep(self._delay)
        async for ev in super().stream_events(*a, **kw):
            yield ev


class StallThenEndEngine(FakeChunkedEngine):
    """Stalls past the hedge budget, then closes its stream WITHOUT a
    done event — the contract breach the relay must survive when a
    hedge branch is already racing."""

    def __init__(self, delay=0.1, **kw):
        super().__init__(**kw)
        self._delay = delay

    async def stream_events(self, *a, **kw):
        await asyncio.sleep(self._delay)
        return
        yield  # pragma: no cover


class SheddingEngine(FakeChunkedEngine):
    """Every submission sheds — the overload-reroute scenario."""

    async def stream_events(self, *a, **kw):
        raise EngineOverloaded("admission queue full (fake)",
                               retry_after=2.0)
        yield  # pragma: no cover


class DyingEngine(FakeChunkedEngine):
    """Emits one token then fails — the migration-budget scenario."""

    async def stream_events(self, prompt, **kw):
        agen = super().stream_events(prompt, **kw)
        async for ev in agen:
            yield ev
            break
        await agen.aclose()
        raise EngineUnavailable("replica died mid-request (fake)")


class QuarantiningEngine(FakeChunkedEngine):
    async def stream_events(self, *a, **kw):
        raise RequestQuarantined("request poisons decode steps (fake)")
        yield  # pragma: no cover


async def test_hedge_fires_on_stall_and_wins_byte_identical():
    fleet = EngineFleet([SlowStartEngine(chunk_len=2),
                         FakeChunkedEngine(chunk_len=2)],
                        hedge_ms=40, affinity=False)
    await fleet.start()
    try:
        want = await baseline_text("hedge me please", max_tokens=32)
        got = await fleet.generate("hedge me please", max_tokens=32)
        assert got.text == want
        assert fleet._hedges == 1 and fleet._hedge_wins == 1
        assert fleet.fleet_health()["hedges"] == 1
        # No replica breaker tripped: a hedge is latency insurance, not
        # a failure verdict.
        assert all(r.breaker.state == "closed" for r in fleet.replicas)
    finally:
        await fleet.stop()


async def test_overload_reroutes_then_propagates_fleet_priced():
    fleet = EngineFleet([SheddingEngine(chunk_len=2),
                         FakeChunkedEngine(chunk_len=2)], affinity=False)
    await fleet.start()
    try:
        # One replica shedding is a routing signal: served elsewhere.
        fleet.replicas[1].inflight = 10     # force the shedder first
        want = await baseline_text("busy fleet", max_tokens=16)
        got = await fleet.generate("busy fleet", max_tokens=16)
        assert got.text == want
        assert fleet._migrations == 0       # reroute, not a migration
    finally:
        await fleet.stop()
    fleet2 = EngineFleet([SheddingEngine(chunk_len=2),
                          SheddingEngine(chunk_len=2)], affinity=False)
    await fleet2.start()
    try:
        with pytest.raises(EngineOverloaded) as ei:
            await fleet2.generate("busy fleet", max_tokens=16)
        assert ei.value.retry_after >= 1.0  # fleet-wide re-priced hint
        assert all(r.breaker.state == "closed" for r in fleet2.replicas)
    finally:
        await fleet2.stop()


async def test_quarantine_is_terminal_never_migrated():
    fleet = EngineFleet([QuarantiningEngine(chunk_len=2),
                         FakeChunkedEngine(chunk_len=2)], affinity=False)
    await fleet.start()
    try:
        fleet.replicas[1].inflight = 10     # route to the quarantiner
        with pytest.raises(RequestQuarantined):
            await fleet.generate("poisonous request", max_tokens=16)
        assert fleet._migrations == 0       # 410 must not hop replicas
    finally:
        await fleet.stop()


async def test_drain_without_target_finishes_in_place():
    """Draining the LAST routable replica must not nudge its in-flight
    requests into 'no healthy replica' errors — they finish in place
    within the drain budget (same semantics as whole-fleet stop())."""
    kw = dict(stream_fn=long_stream, chunk_len=2)
    fleet = await make_fleet(2, **kw)
    try:
        want = await baseline_text("last one standing", max_tokens=60, **kw)
        fleet.eject(1, cause="manual")      # no healthy sibling remains
        pieces, drain_task = [], None
        async for p in fleet.generate_stream("last one standing",
                                             max_tokens=60):
            pieces.append(p)
            if len(pieces) == 3:
                drain_task = asyncio.create_task(fleet.drain(0))
        assert "".join(pieces) == want      # finished in place, intact
        assert fleet._migrations == 0
        await drain_task
        assert fleet.replicas[0].state == REPLICA_EJECTED
    finally:
        await fleet.stop()


async def test_hedge_survives_primary_stream_ending_without_done():
    """A primary whose stream closes without a done event (contract
    breach) while a hedge branch is racing: the hedge wins — the breach
    is not escalated into a migration that would cancel it."""
    fleet = EngineFleet([StallThenEndEngine(delay=0.1, chunk_len=2),
                         SlowStartEngine(delay=0.2, chunk_len=2)],
                        hedge_ms=30, affinity=False)
    await fleet.start()
    try:
        fleet.replicas[1].inflight = 10     # force the breacher first
        want = await baseline_text("contract breach", max_tokens=16)
        got = await fleet.generate("contract breach", max_tokens=16)
        assert got.text == want
        assert fleet._hedges == 1
        assert fleet._migrations == 0       # hedge won; no migration
    finally:
        await fleet.stop()


class NudgeThenDieEngine(FakeChunkedEngine):
    """Emits one token, then fails with the eject nudge ALREADY set on
    its flights — the monitor's eject racing the engine error when a
    replica dies. The relay must treat that as ONE migration, not an
    error-migration followed by a spurious stale-nudge migration
    aborting the fresh dispatch on the healthy sibling."""

    replica_ref = None                      # set by the test post-build

    async def stream_events(self, prompt, **kw):
        agen = super().stream_events(prompt, **kw)
        async for ev in agen:
            yield ev
            break
        await agen.aclose()
        for fl in list(self.replica_ref.flights):
            fl.migrate.set()
        raise EngineUnavailable("replica died mid-request (fake)")


async def test_stale_eject_nudge_after_error_counts_one_migration():
    kw = dict(stream_fn=long_stream, chunk_len=2)
    eng0 = NudgeThenDieEngine(**kw)
    fleet = EngineFleet([eng0, FakeChunkedEngine(**kw)],
                        migration_budget=1, affinity=False)
    eng0.replica_ref = fleet.replicas[0]
    await fleet.start()
    try:
        fleet.replicas[1].inflight = 10     # route to the dying one first
        want = await baseline_text("race the nudge", max_tokens=40, **kw)
        got = await fleet.generate("race the nudge", max_tokens=40)
        assert got.text == want             # byte-identical despite race
        assert fleet._migrations == 1       # ONE migration, budget intact
    finally:
        await fleet.stop()


async def test_migration_budget_exhausted_raises():
    fleet = EngineFleet([DyingEngine(chunk_len=2),
                         DyingEngine(chunk_len=2)],
                        migration_budget=1, affinity=False)
    await fleet.start()
    try:
        with pytest.raises(EngineUnavailable):
            await fleet.generate("doomed", max_tokens=16)
        assert fleet._migrations == 1       # budget spent, then propagate
    finally:
        await fleet.stop()


# ---------------------------------------------------------------------------
# Replica-scoped drills + the CI fleet chaos smoke
# ---------------------------------------------------------------------------


def test_replica_scoped_fault_specs():
    inj = FaultInjector.from_spec("r1:scheduler:die,r0:decode:poison_step")
    v0, v1 = inj.for_replica(0), inj.for_replica(1)
    assert not v0.has("scheduler") and v1.has("scheduler")
    assert v0.has("decode") and not v1.has("decode")
    # The die only fires through replica 1's view.
    v0.check_scheduler_die()                # no-op
    with pytest.raises(BaseException):
        v1.check_scheduler_die()
    assert inj.fired("scheduler") == 1
    # Unscoped faults fire through every view.
    inj2 = FaultInjector.from_spec("admit:error")
    assert inj2.for_replica(0).has("admit") and inj2.for_replica(3).has("admit")
    assert "r1:scheduler:die" in FaultInjector.from_spec(
        "r1:scheduler:die").describe()
    with pytest.raises(ValueError):
        FaultInjector.from_spec("r1:")


async def test_fleet_chaos_scheduler_die_and_poison_zero_dropped():
    """The CI fleet chaos smoke: FLEET_SIZE=2 with scheduler:die AND
    decode:poison_step drills aimed at replica 0 through one shared
    injector. Zero requests dropped; the only losses are quarantines
    (the poison target's own 410); every other transcript byte-identical
    to an undisturbed run."""
    inj = FaultInjector.from_spec("r0:decode:poison_step")
    inj.target_substr = "victim"
    engines = [FakeChunkedEngine(batch_size=8, chunk_len=2,
                                 faults=inj.for_replica(i))
               for i in range(2)]
    fleet = EngineFleet(engines, affinity=False)
    await fleet.start()
    try:
        prompts = [f"pod chaos {i}" for i in range(20)] + ["victim pod"]
        want = {}
        for p in prompts:
            if p != "victim pod":
                want[p] = await baseline_text(p, max_tokens=24)
        results = await asyncio.gather(
            *(fleet.generate(p, max_tokens=24) for p in prompts),
            return_exceptions=True)
        dropped = [p for p, r in zip(prompts, results)
                   if isinstance(r, BaseException)
                   and not isinstance(r, RequestQuarantined)]
        assert dropped == []                # zero dropped requests
        for p, r in zip(prompts, results):
            if p == "victim pod":
                # The injected poison follows the victim; it must end as
                # a quarantine (its own 410), never a fleet-wide error.
                assert isinstance(r, RequestQuarantined), r
            else:
                assert r.text == want[p], f"{p!r} transcript changed"
        # Now the scheduler:die drill against replica 0 mid-traffic.
        inj.set("scheduler", "die", replica=0)
        results2 = await asyncio.gather(
            *(fleet.generate(p, max_tokens=24)
              for p in prompts if p != "victim pod"),
            return_exceptions=True)
        assert not [r for r in results2 if isinstance(r, BaseException)]
        assert inj.fired("scheduler") <= 1  # scoped: replica 1 untouched
    finally:
        await fleet.stop()


# ---------------------------------------------------------------------------
# HTTP surface: /health fleet section, Retry-After, metrics, factory
# ---------------------------------------------------------------------------


def make_cfg(**over):
    defaults = dict(engine="fake", model_name="toy-8m", llm_timeout=5.0)
    defaults.update(over)
    return ServiceConfig(**defaults)


async def make_client(cfg, engine):
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.server.app import create_app
    app = create_app(cfg, engine)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def test_health_and_metrics_expose_fleet():
    fleet = EngineFleet([FakeEngine(), FakeEngine()])
    client = await make_client(make_cfg(), fleet)
    try:
        body = await (await client.get("/health")).json()
        f = body["fleet"]
        assert f["size"] == 2 and f["active"] == 2
        assert len(f["replicas"]) == 2
        for rep in f["replicas"]:
            assert rep["state"] == "active"
            assert rep["breaker"] == "closed"
            assert "occupancy" in rep and "last_reset" in rep
        # Generate through the fleet (generic-engine adapter path), then
        # check the metrics mirror.
        resp = await client.post("/kubectl-command",
                                 json={"query": "list all pods"})
        assert resp.status == 200
        assert (await resp.json())["kubectl_command"] == "kubectl get pods"
        text = await (await client.get("/metrics")).text()
        assert 'fleet_replicas{state="active"} 2.0' in text
        assert 'fleet_replica_occupancy{replica="0"}' in text
        assert "fleet_migrations_total" in text
        assert "fleet_hedges_total" in text
        # Drain a replica → counters move, health stays green (sibling).
        await fleet.drain(0, drain_secs=0.2)
        resp = await client.get("/health")
        assert resp.status == 200
        body = await resp.json()
        assert body["fleet"]["ejected"] == 1
        text = await (await client.get("/metrics")).text()
        assert "fleet_drains_total 1.0" in text
    finally:
        await client.close()


async def test_health_503_carries_fleet_priced_retry_after():
    fleet = EngineFleet([FakeEngine(), FakeEngine()])
    client = await make_client(make_cfg(), fleet)
    try:
        await fleet.stop()                  # whole fleet down
        resp = await client.get("/health")
        assert resp.status == 503
        assert int(resp.headers["Retry-After"]) >= 1
    finally:
        await client.close()


async def test_stream_disconnect_mid_drain_still_fills_cache():
    """Mid-drain client disconnect: the shared single-flight generation
    migrates off the draining replica, completes, and fills the response
    cache — the next request is served from_cache with no new engine
    work."""
    engines = [FakeEngine(delay=0.4), FakeEngine(delay=0.4)]
    fleet = EngineFleet(engines)
    client = await make_client(make_cfg(), fleet)
    try:
        resp = await client.post("/kubectl-command/stream",
                                 json={"query": "list all pods"})
        assert resp.status == 200
        # Drain whichever replica took the flight, then drop the client.
        victim = next((r for r in fleet.replicas if r.flights),
                      fleet.replicas[0])
        drain = asyncio.ensure_future(fleet.drain(victim.idx,
                                                  drain_secs=1.0))
        await asyncio.sleep(0.05)
        resp.close()                        # disconnect mid-stream
        await drain
        svc = client.app["service"]
        for _ in range(100):
            if len(svc.cache.cache) == 1:
                break
            await asyncio.sleep(0.05)
        resp2 = await client.post("/kubectl-command",
                                  json={"query": "list all pods"})
        body = await resp2.json()
        assert body["from_cache"] is True
        assert body["kubectl_command"] == "kubectl get pods"
    finally:
        await client.close()


def test_factory_builds_fleet_and_rejects_openai_fleet():
    from ai_agent_kubectl_tpu.server.factory import build_engine

    eng = build_engine(make_cfg(fleet_size=2))
    assert isinstance(eng, EngineFleet)
    assert len(eng.replicas) == 2
    with pytest.raises(ValueError):
        build_engine(make_cfg(engine="openai", fleet_size=2))
    # Replica-scoped drill specs flow through the factory to per-replica
    # views of ONE injector.
    eng2 = build_engine(make_cfg(engine="jax", decode_batch_size=4,
                                 fleet_size=2,
                                 fault_points="r0:scheduler:die"))
    assert isinstance(eng2, EngineFleet)
    f0 = eng2.replicas[0].engine.faults
    f1 = eng2.replicas[1].engine.faults
    assert f0.has("scheduler") and not f1.has("scheduler")
    assert f0.inner is f1.inner             # one shared ledger
    # A scoped drill naming a replica the fleet doesn't have is a typo,
    # not chaos — refuse to boot (same rule as unknown points).
    with pytest.raises(ValueError):
        build_engine(make_cfg(engine="jax", decode_batch_size=4,
                              fleet_size=2,
                              fault_points="r5:scheduler:die"))
    # FLEET_SIZE=1: the single engine IS replica 0 — an r0: drill stays
    # live through the scoped view instead of going silently inert.
    eng3 = build_engine(make_cfg(engine="jax", decode_batch_size=4,
                                 fault_points="r0:scheduler:die"))
    assert eng3.faults.has("scheduler")
    # Replica-scoped generate faults can never fire (the ChaosEngine
    # wrapper sits above the fleet, replica-blind): refuse to boot.
    with pytest.raises(ValueError):
        build_engine(make_cfg(fleet_size=2,
                              fault_points="r0:generate:error"))


# ---------------------------------------------------------------------------
# BatchedJaxEngine failover — the real cross-replica re-splice end to end
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402  (jax section)

from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine  # noqa: E402
from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer  # noqa: E402
from ai_agent_kubectl_tpu.models.config import get_config  # noqa: E402

#: lean geometry — two engine starts must stay cheap on the tier-1 CPU
#: gate; the full bs=48 acceptance geometry lives in the slow test below.
JAX_LEAN_KW = dict(dtype="float32", max_seq_len=64, prefill_buckets=(16,),
                   prefix_cache=False,
                   batch_size=4, chunk_len=4, chunk_pipe_depth=3)


def _jax_fleet(n=2, **kw):
    merged = dict(JAX_LEAN_KW, **kw)
    return EngineFleet(
        [BatchedJaxEngine(get_config("toy-8m"), tokenizer=ByteTokenizer(),
                          **merged) for _ in range(n)],
        affinity=False)


async def _stream_with_kill(fleet, prompt, *, seed, temperature,
                            max_tokens=40, kill_after=2):
    """Collect a stream, hard-killing the serving replica after
    ``kill_after`` pieces. Returns (text, killed_idx)."""
    pieces, killed = [], []
    async for p in fleet.generate_stream(prompt, max_tokens=max_tokens,
                                         temperature=temperature,
                                         seed=seed, timeout=120):
        pieces.append(p)
        if len(pieces) == kill_after and not killed:
            victim = next(r for r in fleet.replicas if r.flights)
            killed.append(victim.idx)
            asyncio.create_task(victim.engine.stop())
    return "".join(pieces), (killed[0] if killed else None)


async def test_jax_fleet_failover_stream_byte_identical():
    """Cross-replica replay failover on the REAL engine: an SSE client
    whose replica is hard-killed mid-decode sees a byte-identical
    continuation — the request re-splices on the sibling replica from
    (prompt, generated-prefix, seed) via the PR 5 replay path, at
    temperature 0 AND 0.9 (seeded-RNG parity across engines)."""
    fleet = _jax_fleet()
    await fleet.start()
    try:
        cases = [("pod alpha ", 0.0, 101), ("pod beta ", 0.9, 202)]
        # Undisturbed fleet baselines first (deterministic per seed —
        # identical weights on every replica, PRNGKey(engine seed)).
        want = {}
        for prompt, temp, seed in cases:
            r = await fleet.generate(prompt, max_tokens=40,
                                     temperature=temp, seed=seed,
                                     timeout=120)
            want[prompt] = r.text
        for i, (prompt, temp, seed) in enumerate(cases):
            got, killed = await _stream_with_kill(
                fleet, prompt, seed=seed, temperature=temp)
            assert got == want[prompt], (
                f"failover transcript changed for {prompt!r}")
            assert killed is not None
            assert fleet._migrations >= 1
            if i < len(cases) - 1:
                # Rejoin the killed replica so the next case has a
                # healthy sibling to migrate onto (the cycle itself);
                # skipped after the last case — an engine restart costs
                # ~10 s of tier-1 budget and proves nothing new.
                await fleet.rejoin(killed)
        # The monitor's eject of the last-killed replica is debounced;
        # the migrated stream can finish first (pool-mode failover is a
        # block re-map, not a re-prefill), so poll briefly instead of
        # assuming the eject already landed.
        for _ in range(600):
            h = fleet.fleet_health()
            if h["active"] == 1:
                break
            await asyncio.sleep(0.01)
        assert h["active"] == 1 and h["rejoins"] == 1
        assert h["migrations"] >= 2 and h["migrated_tokens"] > 0
    finally:
        await fleet.stop()


# The FULL acceptance chaos test (ISSUE 6): FLEET_SIZE=2 at the bs=48
# depth-3 acceptance geometry with ~50 requests in flight fleet-wide —
# two bs=48 engine starts plus a full drain→eject→rejoin cycle, so it
# runs outside the tier-1 CPU budget (same rule as the other
# engine-start-heavy extras).
JAX_ACC_KW = dict(dtype="float32", max_seq_len=64, prefill_buckets=(16,),
                  prefix_cache=False,
                  batch_size=48, chunk_len=4, chunk_pipe_depth=3)
N_ACC = 50


def _acc_requests():
    # (prompt, temperature, seed): greedy bulk + sampled (temp 0.9)
    # every 13th, mirroring the PR 5 acceptance shape.
    return [(f"pod f{i} ", 0.9 if i % 13 == 3 else 0.0, 2000 + i)
            for i in range(N_ACC)]


@pytest.mark.slow
async def test_jax_fleet_acceptance_kill_drain_rejoin_bs48():
    """THE acceptance criterion: FLEET_SIZE=2, bs=48, depth-3 pipeline;
    hard-kill one replica mid-decode with ~50 requests in flight
    fleet-wide → every request that was on the dead replica completes
    via migration with a transcript byte-identical to an undisturbed run
    (temp 0 and 0.9), zero requests dropped; a full drain→eject→rejoin
    cycle then leaves /health green with the fleet's migration counters
    matching the flight-recorder's per-request migration events."""
    from ai_agent_kubectl_tpu.obs import Trace, use_trace

    fleet = _jax_fleet(2, **JAX_ACC_KW)
    await fleet.start()
    try:
        reqs = _acc_requests()
        # Undisturbed fleet run = the byte-identity reference.
        base = await asyncio.gather(
            *(fleet.generate(p, max_tokens=8, temperature=t, seed=s,
                             timeout=300)
              for p, t, s in reqs))
        want = {p: r.text for (p, _, _), r in zip(reqs, base)}

        # Chaos run: per-request traces stand in for the flight recorder
        # (same Trace objects /debug/requests serves).
        traces = {p: Trace("t-" + p.strip(), "POST", "/kubectl-command")
                  for p, _, _ in reqs}

        async def one(p, t, s):
            with use_trace(traces[p]):
                return await fleet.generate(p, max_tokens=8, temperature=t,
                                            seed=s, timeout=300)

        tasks = [asyncio.create_task(one(p, t, s)) for p, t, s in reqs]
        # Wait until both replicas are genuinely decoding, then hard-kill
        # whichever holds more in-flight requests.
        victim = None
        for _ in range(3000):
            await asyncio.sleep(0.01)
            busy = [r for r in fleet.replicas if r.occupancy() >= 4]
            if busy:
                victim = max(busy, key=lambda r: len(r.flights))
                break
        assert victim is not None, "fleet never reached mid-decode state"
        await victim.engine.stop()      # hard kill mid-decode
        results = await asyncio.gather(*tasks, return_exceptions=True)
        errs = [r for r in results if isinstance(r, BaseException)]
        assert errs == [], f"dropped requests: {errs[:3]}"
        for (p, _, _), r in zip(reqs, results):
            assert r.text == want[p], f"transcript changed for {p!r}"
        assert fleet._migrations >= 1
        # Migration counters match the per-request migration events the
        # flight recorder would serve.
        # (both migration flavors count: crash-failover events read
        # "fleet: replica N failed mid-request ...; migrating with ...",
        # eject/drain nudges read "fleet: migrating off replica N ...")
        trace_migrations = sum(
            1 for tr in traces.values() for _, msg, _meta in tr._events
            if msg.startswith("fleet:") and "migrat" in msg)
        assert trace_migrations == fleet._migrations

        # Full drain→eject→rejoin cycle on the OTHER (healthy) replica
        # with fresh traffic in flight.
        survivor = next(r for r in fleet.replicas
                        if r.idx != victim.idx)
        await fleet.rejoin(victim.idx)
        tasks2 = [asyncio.create_task(
            fleet.generate(p, max_tokens=8, temperature=t, seed=s,
                           timeout=300))
            for p, t, s in reqs[:12]]
        await asyncio.sleep(0.3)
        await fleet.drain(survivor.idx)
        results2 = await asyncio.gather(*tasks2, return_exceptions=True)
        assert not [r for r in results2 if isinstance(r, BaseException)]
        for (p, _, _), r in zip(reqs[:12], results2):
            assert r.text == want[p]
        await fleet.rejoin(survivor.idx)
        h = fleet.fleet_health()
        assert h["active"] == 2 and h["ejected"] == 0   # /health green
        assert h["drains"] == 1 and h["rejoins"] >= 2
    finally:
        await fleet.stop()


async def test_eject_cause_names_reset_budget_exhaustion():
    """Fleet escalation of the containment policy: an engine whose
    supervisor recently denied a reset (budget spent) is ejected with an
    attributable cause — replace-the-replica, not a transient flap."""
    import time as _time

    fleet = await make_fleet(2)
    try:
        victim = fleet.replicas[0]
        victim.engine.supervisor.last_denial_wall = _time.time()
        await victim.engine.stop()
        for _ in range(200):
            await asyncio.sleep(0.01)
            if victim.state == REPLICA_EJECTED:
                break
        assert victim.eject_cause == "reset_budget_exhausted"
        assert victim.engine.supervisor.stats()["budget_denials"] == 0
    finally:
        await fleet.stop()
