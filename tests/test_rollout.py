"""Zero-downtime weight rollout (ISSUE 13): versioned checkpoints,
canary replicas, the SLO-burn promotion gate, and automatic rollback.

The rollout matrix, mostly on FakeChunkedEngine fleets (milliseconds,
same swap/version contract the jax batcher speaks) plus a lean
BatchedJaxEngine warm-swap test and a slow-marked jax fleet acceptance:

- versioned checkpoints: content-fingerprint versions, per-replica
  version table in fleet_health, the fleet-stable facade version;
- version-pinned failover: an established stream NEVER crosses onto
  other weights (same-version sibling resume is byte-identical; no
  sibling → a clean error, never a silent cross-version splice); a
  fresh request replays from scratch on the new version;
- canary steering: the share accumulator sends the canary exactly its
  bounded fraction of fresh traffic;
- the state machine: drain → swap → warmup → rejoin → observe →
  promote-or-rollback, with rollbacks for burn-gate breach, swap:fail
  (replica stays ejected, cause swap_failed), checkpoint:corrupt
  (prior weights restored), and operator abort;
- FLEET_SIZE=1 degenerate: last-replica in-place swap (in-flight
  finishes, new arrivals shed with a priced 503, zero drops);
- warm program reuse on the real engine: a swap re-executes the SAME
  jitted programs (no re-trace) and a rollback is byte-identical;
- HTTP: POST/GET /admin/rollout + abort (token-gated), X-Model-Version,
  /health rollout + fleet version sections, rollout_* metrics.
"""

import asyncio
import os
import time

import pytest

from ai_agent_kubectl_tpu.config import ServiceConfig
from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.fleet import EngineFleet
from ai_agent_kubectl_tpu.engine.protocol import (EngineOverloaded,
                                                  EngineUnavailable)
from ai_agent_kubectl_tpu.engine.rollout import (CAUSE_ABORTED,
                                                 CAUSE_BURN_GATE,
                                                 CAUSE_CHECKPOINT_CORRUPT,
                                                 CAUSE_SWAP_FAILED,
                                                 STATE_COMPLETE,
                                                 STATE_OBSERVING,
                                                 STATE_ROLLED_BACK,
                                                 CheckpointCorrupt,
                                                 RolloutController,
                                                 RolloutError, SwapFailed,
                                                 checkpoint_version,
                                                 fast_burn_from_snapshot)
from ai_agent_kubectl_tpu.obs.slo import SLO_TTFT
from ai_agent_kubectl_tpu.testing.faults import FaultInjector


def _throttle_dispatch(rep, min_interval: float) -> None:
    """Rate-limit a fake replica's chunk dispatches so a long decode
    spans real wall time (the fake otherwise finishes in microseconds,
    leaving nothing in flight to drain or migrate)."""
    real = rep._dispatch_chunk
    last = [0.0]

    def throttled():
        now = time.monotonic()
        if now - last[0] < min_interval:
            return
        last[0] = now
        real()

    rep._dispatch_chunk = throttled


async def make_fleet(n=2, fleet_kw=None, **ekw):
    ekw.setdefault("chunk_len", 2)
    fleet = EngineFleet([FakeChunkedEngine(**ekw) for _ in range(n)],
                        **(fleet_kw or {}))
    await fleet.start()
    return fleet


def make_controller(fleet, **kw):
    kw.setdefault("canary_share", 0.25)
    kw.setdefault("observe_secs", 0.2)
    kw.setdefault("burn_gate", 2.0)
    kw.setdefault("drain_secs", 1.0)
    return RolloutController(fleet, **kw)


async def wait_idle(ctl, timeout=10.0):
    deadline = time.monotonic() + timeout
    while ctl.active and time.monotonic() < deadline:
        await asyncio.sleep(0.01)
    assert not ctl.active, f"rollout stuck in {ctl.state}"


async def baseline_text(prompt, max_tokens=64, **ekw):
    ekw.setdefault("chunk_len", 2)
    eng = FakeChunkedEngine(**ekw)
    await eng.start()
    try:
        return (await eng.generate(prompt, max_tokens=max_tokens)).text
    finally:
        await eng.stop()


# ---------------------------------------------------------------------------
# Versioned checkpoints + config + fault-point units
# ---------------------------------------------------------------------------


def test_checkpoint_version_fingerprints_path_and_content(tmp_path):
    # Deterministic per path — the dev/toy contract ("the same name
    # always means the same weights").
    assert checkpoint_version("/nope/a") == checkpoint_version("/nope/a")
    assert checkpoint_version("/nope/a") != checkpoint_version("/nope/b")
    # A real directory fingerprints its file manifest: replacing a
    # shard in place changes the version even at the same path.
    d = tmp_path / "ckpt"
    d.mkdir()
    (d / "model-00001.safetensors").write_bytes(b"x" * 64)
    v1 = checkpoint_version(str(d))
    (d / "model-00002.safetensors").write_bytes(b"y" * 64)
    v2 = checkpoint_version(str(d))
    assert v1 != v2
    assert len(v1) == 12


def test_config_rollout_knobs_validated():
    for bad in ({"rollout_canary_share": 0.0},
                {"rollout_canary_share": 0.6},
                {"rollout_canary_share": -0.1},
                {"rollout_observe_secs": -1.0},
                {"rollout_burn_gate": 0.5}):
        with pytest.raises(ValueError):
            ServiceConfig(**bad)
    os.environ["ROLLOUT_CANARY_SHARE"] = "0.2"
    os.environ["ROLLOUT_OBSERVE_SECS"] = "12"
    os.environ["ROLLOUT_BURN_GATE"] = "3"
    try:
        cfg = ServiceConfig.from_env(env_file=None)
        assert cfg.rollout_canary_share == 0.2
        assert cfg.rollout_observe_secs == 12.0
        assert cfg.rollout_burn_gate == 3.0
    finally:
        for k in ("ROLLOUT_CANARY_SHARE", "ROLLOUT_OBSERVE_SECS",
                  "ROLLOUT_BURN_GATE"):
            os.environ.pop(k, None)


def test_fault_points_swap_fail_and_checkpoint_corrupt():
    inj = FaultInjector.from_spec("swap:fail,checkpoint:corrupt")
    # One-shot: fires exactly once each, then disarms.
    assert inj.swap_fail() and not inj.swap_fail()
    assert inj.checkpoint_corrupt() and not inj.checkpoint_corrupt()
    assert inj.fired("swap") == 1 and inj.fired("checkpoint") == 1
    # Replica scoping: an r1-scoped drill is invisible to replica 0.
    inj = FaultInjector.from_spec("r1:swap:fail")
    assert not inj.for_replica(0).swap_fail()
    assert inj.for_replica(1).swap_fail()
    # Mode/point cross-validation: typos refuse to boot.
    for bad in ("swap:die", "checkpoint:fail", "decode:corrupt",
                "admit:fail"):
        with pytest.raises(ValueError):
            FaultInjector.from_spec(bad)


def test_fast_burn_from_snapshot_shapes():
    assert fast_burn_from_snapshot(None) is None
    assert fast_burn_from_snapshot({}) is None
    snap = {"windows": ["5m", "1h"], "slos": {"ttft": {"lanes": {
        "interactive": {"windows": {
            "5m": {"total": 10, "breaching": 5, "burn_rate": 50.0},
            "1h": {"total": 10, "breaching": 5, "burn_rate": 50.0},
        }}}}}}
    assert fast_burn_from_snapshot(snap) == 50.0
    # No samples in the fast window → None (not healthy, not breaching).
    snap["slos"]["ttft"]["lanes"]["interactive"]["windows"]["5m"] = {
        "total": 0, "breaching": 0, "burn_rate": 0.0}
    assert fast_burn_from_snapshot(snap) is None


# ---------------------------------------------------------------------------
# Engine swap units (fake)
# ---------------------------------------------------------------------------


async def test_fake_swap_requires_drained_engine_and_is_atomic():
    eng = FakeChunkedEngine(chunk_len=2)
    await eng.start()
    try:
        with pytest.raises(RolloutError):
            eng.swap_weights("/tmp/ckpt-v2")
    finally:
        await eng.stop()
    # Corrupt checkpoint: atomic — version (and therefore bytes) keep
    # serving the prior weights.
    inj = FaultInjector.from_spec("checkpoint:corrupt")
    eng.faults = inj
    with pytest.raises(CheckpointCorrupt):
        eng.swap_weights("/tmp/ckpt-v2")
    assert eng.weights_version == "fake-0"
    # A successful swap changes the version and the scripted "weights".
    v2 = eng.swap_weights("/tmp/ckpt-v2")
    assert eng.weights_version == v2 == checkpoint_version("/tmp/ckpt-v2")
    await eng.start()
    try:
        t2 = (await eng.generate("get pods", max_tokens=32)).text
    finally:
        await eng.stop()
    t1 = await baseline_text("get pods", max_tokens=32)
    assert t2 != t1
    # Swap BACK (a rollback): byte-identical restoration.
    eng.swap_weights(eng.checkpoint_path, version="fake-0")
    await eng.start()
    try:
        t1b = (await eng.generate("get pods", max_tokens=32)).text
    finally:
        await eng.stop()
    assert t1b == t1


async def test_fake_swap_fail_kills_the_replica():
    eng = FakeChunkedEngine(chunk_len=2,
                            faults=FaultInjector.from_spec("swap:fail"))
    with pytest.raises(SwapFailed):
        eng.swap_weights("/tmp/ckpt-v2")
    # Mid-swap death leaves no servable weights behind.
    assert eng.weights_version == ""


# ---------------------------------------------------------------------------
# Fleet: version surfaces, pinned routing, canary steering
# ---------------------------------------------------------------------------


async def test_fleet_version_table_and_facade():
    fleet = await make_fleet(2)
    try:
        fh = fleet.fleet_health()
        assert fh["weights_version"] == "fake-0"
        assert fh["versions"] == {"fake-0": 2}
        assert all(rep["weights_version"] == "fake-0"
                   for rep in fh["replicas"])
        assert fh["canary"] is None
        # Swap replica 1 to v2: the table splits, the facade stays on
        # the (tied) stable version deterministically.
        await fleet.drain(1)
        fleet.replicas[1].engine.swap_weights("/x/v2", version="v2")
        await fleet.rejoin(1)
        fh = fleet.fleet_health()
        assert fh["versions"] == {"fake-0": 1, "v2": 1}
        assert fleet.replicas[1].weights_version() == "v2"
        # stats() carries the per-replica version too.
        stats = fleet.stats()
        vers = {r["replica"]: r["weights_version"]
                for r in stats["fleet"]["replicas"]}
        assert vers == {0: "fake-0", 1: "v2"}
    finally:
        await fleet.stop()


async def test_route_version_filter_and_canary_accumulator():
    fleet = await make_fleet(3, fleet_kw={"affinity": False})
    try:
        fleet.replicas[2].engine.weights_version = "v2"
        # Version pin: only same-version replicas are candidates.
        assert fleet._route("q", version="v2").idx == 2
        assert fleet._route("q", version="fake-0").idx in (0, 1)
        assert fleet._route("q", version="v3") is None
        # Canary steering: share 0.25 → exactly every 4th fresh pick.
        fleet.set_canary(2, 0.25)
        picks = [fleet._route(f"q{i}").idx for i in range(20)]
        assert picks.count(2) == 5
        # Pinned traffic ignores the canary steering entirely.
        assert fleet._route("q", version="v2").idx == 2
        fleet.clear_canary()
        # Steering off: the idle-fleet tie-break (lowest idx) is back —
        # no accumulator sends anything to replica 2 anymore.
        assert all(fleet._route("q").idx == 0 for _ in range(8))
    finally:
        await fleet.stop()


async def test_canary_share_bounded_end_to_end():
    fleet = await make_fleet(2, fleet_kw={"affinity": False})
    try:
        fleet.set_canary(1, 0.25)
        for i in range(20):
            await fleet.generate(f"query number {i}", max_tokens=4)
        canary = fleet.replicas[1].dispatches
        assert canary == 5, f"canary got {canary}/20 at share 0.25"
    finally:
        await fleet.stop()


async def test_established_stream_never_splices_across_versions():
    """Hard-kill the replica serving an established stream while the
    only sibling runs DIFFERENT weights: the stream fails cleanly (the
    client keeps its bytes) rather than resuming on the wrong weights."""
    fleet = await make_fleet(2, fleet_kw={"affinity": False},
                             max_seq_len=512)
    try:
        for rep in fleet.replicas:
            _throttle_dispatch(rep.engine, 0.02)
        await fleet.drain(1)
        fleet.replicas[1].engine.swap_weights("/x/v2", version="v2")
        await fleet.rejoin(1)

        got = []
        with pytest.raises(EngineUnavailable) as ei:
            async for piece in fleet.generate_stream(
                    "a long running query", max_tokens=200):
                got.append(piece)
                if len(got) == 3:
                    # Hard-kill the serving replica (replica 0 — the
                    # only fake-0 one) mid-decode.
                    asyncio.get_running_loop().create_task(
                        fleet.replicas[0].engine.stop())
        assert "no replica serves weights" in str(ei.value)
        assert len(got) >= 3   # delivered bytes were kept, not replaced
    finally:
        await fleet.stop()


async def test_fresh_request_replays_on_new_version_as_fresh():
    """A replica that dies BEFORE any event lets the request re-route
    freely: it replays from scratch on the new-version sibling as a
    fresh request (not a splice)."""

    class DiesAtSubmit(FakeChunkedEngine):
        async def stream_events(self, prompt, **kw):
            raise EngineUnavailable("replica dead at submit")
            yield  # pragma: no cover

    dead = DiesAtSubmit(chunk_len=2)
    alive = FakeChunkedEngine(chunk_len=2, weights_version="v2")
    fleet = EngineFleet([dead, alive], affinity=False)
    await fleet.start()
    try:
        # Force the first route onto the dead replica by loading the
        # live one.
        fleet.replicas[1].inflight = 5
        result = await fleet.generate("some user query", max_tokens=32)
        fleet.replicas[1].inflight -= 5
        assert result.weights_version == "v2"
        ref = FakeChunkedEngine(chunk_len=2, weights_version="v2")
        await ref.start()
        try:
            expect = (await ref.generate("some user query",
                                         max_tokens=32)).text
        finally:
            await ref.stop()
        assert result.text == expect   # v2's own transcript, from scratch
    finally:
        await fleet.stop()


async def test_same_version_migration_still_byte_identical():
    """The pre-rollout contract survives the version filter: killing a
    replica mid-decode resumes byte-identically on a SAME-version
    sibling."""
    base = await baseline_text("migrating stream query", max_tokens=60,
                               max_seq_len=512)
    fleet = await make_fleet(2, fleet_kw={"affinity": False},
                             max_seq_len=512)
    try:
        for rep in fleet.replicas:
            _throttle_dispatch(rep.engine, 0.02)
        got = []
        killed = []
        async for piece in fleet.generate_stream(
                "migrating stream query", max_tokens=60):
            got.append(piece)
            if len(got) == 3 and not killed:
                killed.append(True)
                serving = max(fleet.replicas, key=lambda r: r.inflight)
                asyncio.get_running_loop().create_task(
                    serving.engine.stop())
        assert "".join(got) == base
    finally:
        await fleet.stop()


async def test_drain_finishes_in_place_without_same_version_sibling():
    """Draining the last replica on a version lets its in-flight work
    finish in place (nudging it would abort into unroutable
    migrations) — the promote phase's correctness under live traffic."""
    base = await baseline_text("finish in place query", max_tokens=40,
                               max_seq_len=512)
    fleet = await make_fleet(2, fleet_kw={"affinity": False},
                             max_seq_len=512)
    try:
        for rep in fleet.replicas:
            _throttle_dispatch(rep.engine, 0.01)
        await fleet.drain(1)
        fleet.replicas[1].engine.swap_weights("/x/v2", version="v2")
        await fleet.rejoin(1)

        task = asyncio.create_task(fleet.generate(
            "finish in place query", max_tokens=40))
        while not fleet.replicas[0].flights:
            await asyncio.sleep(0.005)
        # Drain the ONLY fake-0 replica while it serves the stream.
        await fleet.drain(0, drain_secs=5.0)
        result = await task
        assert result.text == base          # finished in place, zero drops
        assert result.weights_version == "fake-0"
    finally:
        await fleet.stop()


# ---------------------------------------------------------------------------
# The rollout state machine
# ---------------------------------------------------------------------------


async def test_rollout_happy_path_promotes_whole_fleet():
    fleet = await make_fleet(2)
    ctl = make_controller(fleet, observe_secs=0.2)
    try:
        before = (await fleet.generate("get pods", max_tokens=24)).text
        status = await ctl.start_rollout("/tmp/ckpt-v2")
        v2 = status["target_version"]
        assert status["state"] in ("draining", "swapping", "warming",
                                   "observing")
        await wait_idle(ctl)
        assert ctl.state == STATE_COMPLETE
        assert set(ctl.replica_versions().values()) == {v2}
        assert fleet.weights_version == v2
        after = (await fleet.generate("get pods", max_tokens=24)).text
        assert after != before              # new weights, new bytes
        # The timeline narrates drain→swap→rejoin→promote per replica.
        kinds = [e["type"] for e in ctl.events]
        for k in ("drain", "swap", "warmup", "rejoin", "observe",
                  "promote", "rollout_complete"):
            assert k in kinds
        assert ctl.rollouts_completed == 1
        # Canary steering is off again after promotion.
        assert fleet._canary_idx is None
    finally:
        await fleet.stop()


async def test_rollout_conflict_and_same_version_refused():
    fleet = await make_fleet(2)
    ctl = make_controller(fleet, observe_secs=0.5)
    try:
        await ctl.start_rollout("/tmp/ckpt-v2")
        with pytest.raises(RolloutError):
            await ctl.start_rollout("/tmp/ckpt-v3")
        await wait_idle(ctl)
        with pytest.raises(RolloutError):   # already serving that version
            await ctl.start_rollout("/tmp/ckpt-v2")
    finally:
        await fleet.stop()


async def test_rollout_burn_breach_rolls_back_chaos_smoke():
    """The CI 'Rollout chaos smoke': FLEET_SIZE=2, canary with an
    injected SLO-burn breach → automatic rollback, prior bytes restored,
    rollback cause counted, ledger books balanced."""
    fleet = await make_fleet(2, slo_ttft_ms=10.0)
    ctl = make_controller(fleet, observe_secs=2.0)
    try:
        before = (await fleet.generate("get pods", max_tokens=24)).text
        # Healthy stable cohort baseline.
        for rep in fleet.replicas:
            for _ in range(30):
                rep.engine._slo.note(SLO_TTFT, "interactive", 1.0)
        await ctl.start_rollout("/tmp/ckpt-v2")
        deadline = time.monotonic() + 5.0
        while ctl.state != STATE_OBSERVING:
            assert ctl.active and time.monotonic() < deadline
            await asyncio.sleep(0.01)
        assert fleet._canary_idx == ctl.canary_idx
        # The canary burns: every TTFT sample breaches its target.
        canary = fleet.replicas[ctl.canary_idx]
        for _ in range(50):
            canary.engine._slo.note(SLO_TTFT, "interactive", 500.0)
        await wait_idle(ctl)
        assert ctl.state == STATE_ROLLED_BACK
        assert ctl.last_rollback_cause == CAUSE_BURN_GATE
        assert ctl.rollbacks == {CAUSE_BURN_GATE: 1}
        assert ctl.last_gate and ctl.last_gate["cause"] == CAUSE_BURN_GATE
        # Prior weights restored, byte-identically; books balanced.
        assert set(ctl.replica_versions().values()) == {"fake-0"}
        after = (await fleet.generate("get pods", max_tokens=24)).text
        assert after == before
        assert fleet.ledger_snapshot()["conservation"]["balanced"]
        assert fleet._canary_idx is None
    finally:
        await fleet.stop()


async def test_rollout_swap_fail_replica_stays_ejected():
    inj = FaultInjector.from_spec("r0:swap:fail")
    fleet = EngineFleet(
        [FakeChunkedEngine(chunk_len=2, faults=inj.for_replica(i))
         for i in range(2)])
    await fleet.start()
    ctl = make_controller(fleet)
    try:
        await ctl.start_rollout("/tmp/ckpt-v2")
        await wait_idle(ctl)
        assert ctl.state == STATE_ROLLED_BACK
        assert ctl.last_rollback_cause == CAUSE_SWAP_FAILED
        # The mid-swap corpse stays ejected, attributably — no blind
        # resurrection with unknown weights.
        assert fleet.replicas[0].state == "ejected"
        assert fleet.replicas[0].eject_cause == "swap_failed"
        # The fleet keeps serving on the sibling's prior weights.
        r = await fleet.generate("get pods", max_tokens=8)
        assert r.weights_version == "fake-0"
    finally:
        await fleet.stop()


async def test_rollout_checkpoint_corrupt_restores_prior():
    inj = FaultInjector.from_spec("checkpoint:corrupt")
    fleet = EngineFleet(
        [FakeChunkedEngine(chunk_len=2, faults=inj.for_replica(i))
         for i in range(2)])
    await fleet.start()
    ctl = make_controller(fleet)
    try:
        await ctl.start_rollout("/tmp/ckpt-v2")
        await wait_idle(ctl)
        assert ctl.state == STATE_ROLLED_BACK
        assert ctl.last_rollback_cause == CAUSE_CHECKPOINT_CORRUPT
        # Atomic load rejection: every replica active on prior weights.
        assert set(ctl.replica_versions().values()) == {"fake-0"}
        assert all(rep.state == "active" for rep in fleet.replicas)
    finally:
        await fleet.stop()


async def test_rollout_abort_rolls_back():
    fleet = await make_fleet(2)
    ctl = make_controller(fleet, observe_secs=30.0)
    try:
        await ctl.start_rollout("/tmp/ckpt-v2")
        deadline = time.monotonic() + 5.0
        while ctl.state != STATE_OBSERVING:
            assert ctl.active and time.monotonic() < deadline
            await asyncio.sleep(0.01)
        status = await ctl.abort()
        assert status["state"] == STATE_ROLLED_BACK
        assert ctl.last_rollback_cause == CAUSE_ABORTED
        assert set(ctl.replica_versions().values()) == {"fake-0"}
        with pytest.raises(RolloutError):   # nothing left to abort
            await ctl.abort()
    finally:
        await fleet.stop()


async def test_single_replica_inplace_swap_zero_drops():
    """FLEET_SIZE=1 degenerate rollout: the last replica swaps in
    place — in-flight work finishes within the drain budget (zero
    established streams dropped), new arrivals shed with a PRICED 503,
    and the canary gate is skipped (no stable cohort)."""
    base = await baseline_text("long in flight query", max_tokens=40,
                               max_seq_len=512)
    fleet = await make_fleet(1, max_seq_len=512)
    ctl = make_controller(fleet, drain_secs=5.0)
    try:
        _throttle_dispatch(fleet.replicas[0].engine, 0.01)
        task = asyncio.create_task(fleet.generate(
            "long in flight query", max_tokens=40))
        while not fleet.replicas[0].flights:
            await asyncio.sleep(0.005)
        await ctl.start_rollout("/tmp/ckpt-v2")
        # While the swap window is open, fresh arrivals are shed with a
        # priced Retry-After (not a bare 503).
        shed = None
        deadline = time.monotonic() + 5.0
        while ctl.active and time.monotonic() < deadline:
            try:
                await fleet.generate("fresh arrival", max_tokens=4)
            except EngineOverloaded as e:
                shed = e
                break
            except EngineUnavailable:
                pass
            await asyncio.sleep(0.005)
        result = await task                  # the established stream...
        assert result.text == base           # ...finished untouched
        await wait_idle(ctl)
        assert ctl.state == STATE_COMPLETE
        assert shed is not None and shed.retry_after > 0
        note = next(e for e in ctl.events if e["type"] == "promote")
        assert "single replica" in note.get("note", "")
        r2 = await fleet.generate("long in flight query", max_tokens=40)
        assert r2.weights_version == ctl.target_version
        assert r2.text != base
    finally:
        await fleet.stop()


async def test_version_pinned_migration_during_rollout_kill():
    """The ISSUE 13 satellite: hard-kill a replica mid-decode DURING a
    rollout. The stream either resumes byte-identically on a
    same-version sibling, or — when none exists — fails cleanly; never
    a cross-version splice. With a 3-replica fleet two stable replicas
    remain, so the resume is byte-identical."""
    base = await baseline_text("kill during rollout query",
                               max_tokens=60, max_seq_len=512)
    fleet = await make_fleet(3, fleet_kw={"affinity": False},
                             max_seq_len=512)
    ctl = make_controller(fleet, observe_secs=3.0, canary_share=0.01)
    try:
        for rep in fleet.replicas:
            _throttle_dispatch(rep.engine, 0.02)
        await ctl.start_rollout("/tmp/ckpt-v2")
        deadline = time.monotonic() + 5.0
        while ctl.state != STATE_OBSERVING:
            assert ctl.active and time.monotonic() < deadline
            await asyncio.sleep(0.01)
        # A stable-cohort stream (share 0.01 → first fresh pick is
        # stable), killed mid-decode: must resume on the OTHER stable
        # replica byte-identically.
        got = []
        killed = []
        async for piece in fleet.generate_stream(
                "kill during rollout query", max_tokens=60):
            got.append(piece)
            if len(got) == 3 and not killed:
                killed.append(True)
                serving = max(
                    (r for r in fleet.replicas
                     if r.idx != ctl.canary_idx),
                    key=lambda r: r.inflight)
                asyncio.get_running_loop().create_task(
                    serving.engine.stop())
        assert "".join(got) == base
        await ctl.abort()
        await wait_idle(ctl)
    finally:
        await fleet.stop()


# ---------------------------------------------------------------------------
# HTTP surface: /admin/rollout, X-Model-Version, /health, /metrics
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
    defaults = dict(engine="fake", model_name="fake", llm_timeout=5.0,
                    rate_limit="10000/minute",
                    rollout_observe_secs=0.2)
    defaults.update(over)
    return ServiceConfig(**defaults)


async def test_http_rollout_lifecycle_and_surfaces():
    fleet = EngineFleet([FakeChunkedEngine(chunk_len=2)
                         for _ in range(2)])
    client = await _make_client(_cfg(), fleet)
    try:
        # X-Model-Version rides every response (the stable version) —
        # asserted on /health since the fake-chunked token streams are
        # not safety-valid kubectl commands.
        resp = await client.get("/health")
        assert resp.status == 200
        assert resp.headers.get("X-Model-Version") == "fake-0"
        # /health: rollout idle + per-replica version table.
        health = await (await client.get("/health")).json()
        assert health["rollout"]["state"] == "idle"
        assert health["rollout"]["replica_versions"] == {
            "0": "fake-0", "1": "fake-0"}
        assert health["fleet"]["versions"] == {"fake-0": 2}
        # Pre-rollout scrape: registers the fake-0 version series (so
        # the post-rollout scrape must ZERO it, not leak it forever).
        text = await (await client.get("/metrics")).text()
        assert 'rollout_replicas{version="fake-0"} 2.0' in text
        assert "rollout_state 0.0" in text              # idle
        # Start a rollout over HTTP.
        resp = await client.post("/admin/rollout",
                                 json={"checkpoint": "/tmp/ckpt-v2"})
        assert resp.status == 202
        started = await resp.json()
        v2 = started["target_version"]
        # Conflict while in flight.
        resp = await client.post("/admin/rollout",
                                 json={"checkpoint": "/tmp/ckpt-v3"})
        assert resp.status == 409
        svc = client.app["service"]
        deadline = time.monotonic() + 10.0
        while svc.rollout.active and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        status = await (await client.get("/admin/rollout")).json()
        assert status["state"] == "complete"
        assert status["stable_version"] == v2
        # The new stable version is echoed on responses now.
        resp = await client.get("/health")
        assert resp.headers.get("X-Model-Version") == v2
        # /metrics: rollout gauges + version table.
        text = await (await client.get("/metrics")).text()
        assert "rollout_state 8.0" in text          # complete
        assert f'rollout_replicas{{version="{v2}"}} 2.0' in text
        assert 'rollout_replicas{version="fake-0"} 0.0' in text
        # Abort with nothing in flight → 409.
        resp = await client.post("/admin/rollout/abort")
        assert resp.status == 409
        # Bad bodies → 400.
        resp = await client.post("/admin/rollout", json={})
        assert resp.status == 400
    finally:
        await client.close()


async def test_http_rollout_token_gate_and_rollback_metric():
    inj = FaultInjector.from_spec("checkpoint:corrupt")
    fleet = EngineFleet(
        [FakeChunkedEngine(chunk_len=2, faults=inj.for_replica(i))
         for i in range(2)])
    client = await _make_client(_cfg(debug_token="s3cret"), fleet)
    try:
        # Token-gated like the debug surfaces.
        assert (await client.post(
            "/admin/rollout",
            json={"checkpoint": "/tmp/x"})).status == 403
        assert (await client.get("/admin/rollout")).status == 403
        ok = {"X-Debug-Token": "s3cret"}
        resp = await client.post("/admin/rollout",
                                 json={"checkpoint": "/tmp/ckpt-v2"},
                                 headers=ok)
        assert resp.status == 202
        svc = client.app["service"]
        deadline = time.monotonic() + 10.0
        while svc.rollout.active and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        status = await (await client.get("/admin/rollout",
                                         headers=ok)).json()
        assert status["state"] == "rolled_back"
        assert status["last_rollback_cause"] == "checkpoint_corrupt"
        text = await (await client.get("/metrics")).text()
        assert ('rollout_rollbacks_total{cause="checkpoint_corrupt"} 1.0'
                in text)
        health = await (await client.get("/health")).json()
        assert health["rollout"]["rollbacks_total"] == {
            "checkpoint_corrupt": 1}
    finally:
        await client.close()


async def test_http_rollout_404_without_swap_support():
    from ai_agent_kubectl_tpu.engine.fake import FakeEngine

    client = await _make_client(_cfg(), FakeEngine())
    try:
        assert (await client.post(
            "/admin/rollout",
            json={"checkpoint": "/tmp/x"})).status == 404
        assert (await client.get("/admin/rollout")).status == 404
        health = await (await client.get("/health")).json()
        assert health["rollout"] is None
        # The rule-table engine still stamps a version header.
        resp = await client.post("/kubectl-command",
                                 json={"query": "list the pods"})
        assert resp.headers.get("X-Model-Version") == "fake-rules-0"
    finally:
        await client.close()


# ---------------------------------------------------------------------------
# Real engine: warm program reuse across a swap
# ---------------------------------------------------------------------------


async def test_jax_swap_reuses_warm_programs_and_changes_bytes():
    """The tentpole's perf clause on the REAL engine: a swap keeps the
    jitted program objects AND their trace caches (no re-trace ⇒ no
    multi-second first-request compile), changes the transcript (the
    weights really swapped), and a rollback restores it byte-for-byte."""
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    eng = BatchedJaxEngine(
        get_config("toy-8m"), dtype="float32", max_seq_len=256,
        prefill_buckets=(64,), batch_size=2, chunk_len=4,
        prefix_cache=False)
    await eng.start()
    try:
        v1 = eng.weights_version
        assert v1 and eng.checkpoint_path.startswith("dev:")
        t1 = (await eng.generate("get pods", max_tokens=8)).text
        fn_ids = {b: id(f) for b, f in eng._batch_chunk_fns.items()}
        cache_sizes = {b: f._cache_size()
                       for b, f in eng._batch_chunk_fns.items()}
        prefill_ids = {k: id(f) for k, f in eng._prefill_fns.items()}

        # swap on a RUNNING engine is refused (drain first).
        with pytest.raises(RolloutError):
            eng.swap_weights("/tmp/x")
        await eng.stop()
        v2 = eng.swap_weights("/tmp/dev-ckpt-v2")
        assert v2 != v1
        await eng.start()
        t2 = (await eng.generate("get pods", max_tokens=8)).text
        # Warm reuse: same jitted objects, same trace-cache sizes (a
        # re-trace would grow _cache_size), same prefill programs.
        assert {b: id(f) for b, f in eng._batch_chunk_fns.items()} \
            == fn_ids
        assert {b: f._cache_size()
                for b, f in eng._batch_chunk_fns.items()} == cache_sizes
        assert {k: id(f) for k, f in eng._prefill_fns.items()} \
            == prefill_ids
        assert (await eng.generate("get pods", max_tokens=8)).weights_version == v2
        assert t2 != t1                      # genuinely different weights
        # Rollback: the dev sentinel re-derives the EXACT original init.
        await eng.stop()
        assert eng.swap_weights("dev:toy-8m:seed=0:quant=",
                                version=v1) == v1
        await eng.start()
        t1b = (await eng.generate("get pods", max_tokens=8)).text
        assert t1b == t1
    finally:
        await eng.stop()


async def test_jax_swap_rejects_wrong_geometry():
    """A checkpoint whose tree doesn't match the serving model is a
    CheckpointCorrupt at load — the serving tree is untouched."""
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import init_params

    import jax

    eng = BatchedJaxEngine(
        get_config("toy-8m"), dtype="float32", max_seq_len=256,
        prefill_buckets=(64,), batch_size=2, chunk_len=4,
        prefix_cache=False)
    await eng.start()
    v1 = eng.weights_version
    t1 = (await eng.generate("get pods", max_tokens=6)).text
    await eng.stop()
    try:
        wrong = init_params(jax.random.PRNGKey(7),
                            get_config("toy-moe"), dtype="float32")
        orig = eng._load_swap_params
        eng._load_swap_params = lambda path: wrong
        try:
            with pytest.raises(CheckpointCorrupt):
                eng.swap_weights("/tmp/wrong-model")
        finally:
            eng._load_swap_params = orig
        assert eng.weights_version == v1
        await eng.start()
        assert (await eng.generate("get pods", max_tokens=6)).text == t1
    finally:
        await eng.stop()


@pytest.mark.slow
async def test_jax_fleet_rolling_swap_acceptance():
    """Slow acceptance (jax): FLEET_SIZE=2 rolling swap under live
    traffic — zero dropped requests, the canary phase steers a bounded
    share, and post-promotion both replicas serve the new version with
    the documented byte change."""
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    def mk():
        return BatchedJaxEngine(
            get_config("toy-8m"), dtype="float32", max_seq_len=256,
            prefill_buckets=(64,), batch_size=2, chunk_len=4,
            prefix_cache=False)

    fleet = EngineFleet([mk(), mk()], affinity=False)
    await fleet.start()
    ctl = RolloutController(fleet, canary_share=0.25, observe_secs=0.5,
                            burn_gate=2.0, drain_secs=10.0)
    try:
        v1 = fleet.weights_version
        before = (await fleet.generate("get pods", max_tokens=8)).text
        errors = []
        done = []

        async def client_loop(i):
            for j in range(6):
                try:
                    r = await fleet.generate(f"query {i}",
                                             max_tokens=6)
                    done.append(r)
                except Exception as e:   # noqa: BLE001 - counted
                    errors.append(e)
                await asyncio.sleep(0.02)

        tasks = [asyncio.create_task(client_loop(i)) for i in range(3)]
        await ctl.start_rollout("/tmp/jax-ckpt-v2")
        await wait_idle(ctl, timeout=120.0)
        await asyncio.gather(*tasks)
        assert not errors, f"dropped requests during rollout: {errors[:3]}"
        assert ctl.state == STATE_COMPLETE
        v2 = ctl.target_version
        assert v2 != v1
        assert set(ctl.replica_versions().values()) == {v2}
        after = (await fleet.generate("get pods", max_tokens=8)).text
        assert after != before
    finally:
        await fleet.stop()
