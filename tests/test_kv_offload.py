"""Two-tier KV pool: host-RAM block offload (ISSUE 20).

The standing invariants:

- A returning session whose radix chain was demoted to host RAM gets a
  transcript BYTE-IDENTICAL to a cold re-prefill — on the fake engine
  and the real jax batcher, at temperature 0 and seeded 0.9 — while the
  radix hit counters show the onload (not a re-prefill) served it.
- ``onload:corrupt`` (testing/faults.py): the demote-time CRC32 catches
  the corrupt page, the tainted host subtree drops, and the SAME request
  completes byte-identically via ordinary suffix prefill — zero failed
  requests, books exact-balanced across BOTH tiers.
- ``offload:fail`` leaves the device tier exactly where HOST_KV_BLOCKS=0
  would: a broken host tier degrades to the single-tier behaviour.
- A containment reset rebuilds BOTH tiers empty (host payloads were
  captured from the condemned device world) with cumulative counters
  carried forward.
- Sessions are first-class: the turn-N TTFT SLO is judged only for
  radix-warm re-admissions of a declared session, per-session token
  budgets demote over-budget sessions to the background lane, and a
  demote/onload churn spike files a ``host_tier_thrash`` incident.
"""

import asyncio

import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.kv_pool import BlockPool, HostBlockStore
from ai_agent_kubectl_tpu.engine.protocol import RequestQuarantined
from ai_agent_kubectl_tpu.engine.qos import (LANE_BACKGROUND,
                                             LANE_INTERACTIVE, QoSContext,
                                             SessionBudgets, classify,
                                             use_qos)
from ai_agent_kubectl_tpu.engine.radix_cache import RadixCache
from ai_agent_kubectl_tpu.obs.incidents import TRIGGER_HOST_THRASH
from ai_agent_kubectl_tpu.testing.faults import FaultInjector


# ---------------------------------------------------------------- helpers

def _holders(eng) -> dict:
    """Expected per-device-block holder counts (slots + parked + radix
    edges) — what BlockPool.check verifies the refcounts against."""
    holders: dict = {}
    for slot in list(eng._slots) + list(eng._parked):
        if slot is None:
            continue
        for b in slot.blocks:
            holders[b] = holders.get(b, 0) + 1
    if eng._radix is not None:
        for b, n in eng._radix._held.items():
            holders[b] = holders.get(b, 0) + n
    return holders


def _assert_no_leak(eng) -> None:
    """THE invariant, extended across the second tier: device refcounts
    balance exactly AND every resident host page is held by exactly one
    radix node (no leak, no double-free, in either tier)."""
    cached = (eng._radix.cached_blocks() if eng._radix is not None
              else set())
    st = eng._pool.stats(cached)
    assert st.live == 0, f"live blocks leaked: {st}"
    host = getattr(eng, "_host_store", None)
    hh = (eng._radix.host_holders()
          if host is not None and eng._radix is not None else None)
    eng._pool.check(_holders(eng), host=host, host_holders=hh)


# -------------------------------------------------------- host store units

def test_host_store_put_get_verify_free_and_check():
    import numpy as np

    store = HostBlockStore(2)
    a = store.put(np.arange(8, dtype=np.int64))
    assert store.used == 1 and store.demoted_total == 1
    assert store.verify(a, store.get(a))
    # A flipped byte fails the demote-time checksum.
    bad = store.get(a).copy()
    bad[0] ^= 0xFF
    assert not store.verify(a, bad)
    b = store.put(np.arange(4, dtype=np.int64))
    with pytest.raises(RuntimeError):
        store.put(np.arange(2, dtype=np.int64))   # full: demote makes room
    store.check({a: 1, b: 1})
    with pytest.raises(AssertionError):
        store.check({a: 1})                        # resident but unheld
    store.free(a)
    with pytest.raises(RuntimeError):
        store.free(a)                              # double free
    with pytest.raises(RuntimeError):
        store.get(a)                               # use-after-free
    store.free(b)
    store.check({})
    with pytest.raises(ValueError):
        store.note_onload_fail("gamma-ray")        # closed cause set


def test_radix_demote_promote_round_trip_balances_both_tiers():
    """Device→host→device for a 3-page chain: demotion frees every
    device block (NOT counted as eviction — the pages survive), the
    match transparently promotes with the checksum verified, and the
    exact-balance check holds across both tiers at every step."""
    pool = BlockPool(16, 4)
    store = HostBlockStore(8)
    rad = RadixCache(pool, max_blocks=8, host_store=store)
    ids = list(range(12))
    blocks = pool.alloc(3)
    rad.insert(ids, blocks)
    pool.decref(blocks)
    assert rad.evict_for(16)
    assert pool.free_count == 16
    assert store.used == 3 and store.demoted_total == 3
    assert rad.host_resident_blocks() == 3
    assert rad.evicted_blocks_total == 0          # demotes are not drops
    pool.check({}, host=store, host_holders=rad.host_holders())
    mr = rad.match(ids + [99])
    assert mr.n_tokens == 12                      # onload served the hit
    assert store.onloaded_total == 3 and store.used == 0
    pool.decref(mr.blocks)
    pool.check({b: 1 for b in rad.cached_blocks()},
               host=store, host_holders=rad.host_holders())
    rad.clear()
    pool.check({}, host=store, host_holders=rad.host_holders())


def test_host_lru_spans_both_tiers():
    """The LRU clock is one clock: a full store drops its stalest host
    leaf for a warmer incoming demote, and an incoming page colder than
    everything resident is discarded instead of displacing it."""
    pool = BlockPool(16, 4)
    store = HostBlockStore(1)
    rad = RadixCache(pool, max_blocks=8, host_store=store)
    a = pool.alloc(1)
    rad.insert([1, 2, 3, 4], a)
    pool.decref(a)
    b = pool.alloc(1)
    rad.insert([5, 6, 7, 8], b)                   # younger chain
    pool.decref(b)
    assert rad.evict_for(16)
    # Capacity 1: the older chain demoted first, then the younger demote
    # displaced it (older-than-incoming ⇒ victim).
    assert store.used == 1 and store.demoted_total == 2
    assert store.dropped_total == 1
    # Touch the resident page (bumps its LRU stamp), then demote a chain
    # that is COLDER than it: the incoming page is discarded, the warm
    # resident survives.
    mr = rad.match([5, 6, 7, 8, 9])
    assert mr.n_tokens == 4 and store.onloaded_total == 1
    pool.decref(mr.blocks)
    c = pool.alloc(1)
    rad.insert([9, 9, 9, 9], c)
    pool.decref(c)
    # Age the new chain below the resident one by re-touching the warm
    # chain afterwards, then evict.
    mr2 = rad.match([5, 6, 7, 8])
    pool.decref(mr2.blocks)
    dropped0 = store.dropped_total
    assert rad.evict_for(16)
    assert store.used == 1                        # warm page still resident
    assert store.dropped_total > dropped0         # cold incoming discarded
    mr3 = rad.match([5, 6, 7, 8, 0])
    assert mr3.n_tokens == 4                      # and it still promotes
    pool.decref(mr3.blocks)
    rad.clear()
    pool.check({}, host=store, host_holders=rad.host_holders())


def test_radix_onload_corrupt_purges_subtree_and_falls_back():
    inj = FaultInjector()
    pool = BlockPool(16, 4)
    store = HostBlockStore(8)
    rad = RadixCache(pool, max_blocks=8, host_store=store, faults=inj)
    ids = list(range(8))
    blocks = pool.alloc(2)
    rad.insert(ids, blocks)
    pool.decref(blocks)
    assert rad.evict_for(16) and store.used == 2
    inj.set("onload", "corrupt")
    mr = rad.match(ids + [42])
    # The corrupt first page ends the match at zero — the caller
    # prefills the whole suffix — and the tainted subtree is gone.
    assert mr.n_tokens == 0 and not mr.blocks
    assert store.onload_fail_total["corrupt"] == 1
    assert store.used == 0 and rad.host_resident_blocks() == 0
    pool.check({}, host=store, host_holders=rad.host_holders())
    # One-shot: the next demote→promote round trip works again.
    b2 = pool.alloc(2)
    rad.insert(ids, b2)
    pool.decref(b2)
    assert rad.evict_for(16)
    mr2 = rad.match(ids + [42])
    assert mr2.n_tokens == 8
    pool.decref(mr2.blocks)


def test_radix_offload_fail_degrades_to_single_tier():
    """``offload:fail`` on the only demotable page: the device tier ends
    exactly where a HOST_KV_BLOCKS=0 cache does after identical
    traffic — same free count, same node count, empty host store."""
    inj = FaultInjector()
    inj.set("offload", "fail")
    pool = BlockPool(8, 4)
    store = HostBlockStore(4)
    rad = RadixCache(pool, max_blocks=4, host_store=store, faults=inj)
    pool0 = BlockPool(8, 4)
    rad0 = RadixCache(pool0, max_blocks=4)        # the single-tier twin
    for p, r in ((pool, rad), (pool0, rad0)):
        b = p.alloc(2)
        r.insert([1, 2, 3, 4, 5, 6], b)           # 1 full page + tail
        p.decref(b)
        assert r.evict_for(8)
    assert store.used == 0 and store.demoted_total == 0
    assert store.offload_fail_total == 1
    assert pool.free_count == pool0.free_count == 8
    assert rad.node_count() == rad0.node_count() == 0
    assert rad.evicted_blocks_total == rad0.evicted_blocks_total
    pool.check({}, host=store, host_holders=rad.host_holders())


# ------------------------------------------------------------- qos units

def test_session_budgets_charge_demote_and_lru_eviction():
    sb = SessionBudgets(10, max_sessions=2)
    sb.charge("t/a", 6)
    assert not sb.over("t/a")
    assert sb.lane_for("t/a", LANE_INTERACTIVE) == LANE_INTERACTIVE
    sb.charge("t/a", 5)
    assert sb.over("t/a")
    assert sb.lane_for("t/a", LANE_INTERACTIVE) == LANE_BACKGROUND
    # Already-background requests pass through uncounted.
    assert sb.lane_for("t/a", LANE_BACKGROUND) == LANE_BACKGROUND
    assert sb.demoted_total == 1
    # Bounded LRU: the coldest session's tally drops — the benign
    # failure mode (a forgotten session regains priority).
    sb.charge("t/b", 1)
    sb.charge("t/c", 1)
    assert sb.evicted_total == 1 and not sb.over("t/a")
    snap = sb.snapshot()
    assert snap["sessions_tracked"] == 2 and snap["enabled"]
    # budget_tokens <= 0 disables the whole mechanism.
    off = SessionBudgets(0)
    off.charge("t/x", 10 ** 9)
    assert not off.over("t/x")
    assert off.lane_for("t/x", LANE_INTERACTIVE) == LANE_INTERACTIVE


def test_classify_namespaces_sessions_under_tenant():
    """One client can never spend another tenant's budget by guessing
    its session string: the raw X-Session-ID is namespaced."""
    a = classify("key-a", None, None, {}, session="agent-7")
    b = classify("key-b", None, None, {}, session="agent-7")
    assert a.session == "key-a/agent-7" and b.session == "key-b/agent-7"
    assert a.session != b.session
    assert classify("key-a", None, None, {}).session == ""
    assert classify("key-a", None, None, {}, session="  ").session == ""


# ------------------------------------------------- fake engine (CI smoke)

async def test_fake_demoted_session_returns_byte_identical():
    """THE tentpole acceptance on the fake engine: turn 2 of a session
    whose chain was demoted to host RAM is byte-identical to a cold
    re-prefill (temperature 0 AND seeded 0.9), while the hit counters
    show the ONLOAD served it."""
    cold = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4,
                            host_kv_blocks=32)
    await cold.start()
    await eng.start()
    history = "alpha beta gamma delta epsilon zeta eta theta question"
    for temp, seed in ((0.0, None), (0.9, 123)):
        r1 = await eng.generate(history, max_tokens=8,
                                temperature=temp, seed=seed)
        chain_ids = len(eng._prompt_token_ids(history))
        assert eng._radix.cached_block_count() > 0
        assert eng._radix.evict_for(eng._pool.n_blocks)
        assert eng._host_store.used > 0          # the chain went to host
        assert eng._radix.cached_block_count() == 0
        h2 = history + " " + r1.text + " next"
        hits0 = eng._radix.hit_tokens_total
        on0 = eng._host_store.onloaded_total
        r2 = await eng.generate(h2, max_tokens=8,
                                temperature=temp, seed=seed)
        rc = await cold.generate(h2, max_tokens=8,
                                 temperature=temp, seed=seed)
        assert r2.text == rc.text, (temp, seed)
        assert eng._host_store.onloaded_total > on0
        # The onload-served pages count as radix hits: the re-sent
        # history was a re-map, not a re-prefill.
        assert eng._radix.hit_tokens_total - hits0 >= chain_ids - 2
        history = h2
    _assert_no_leak(eng)
    await eng.stop()
    await cold.stop()


async def test_fake_onload_corrupt_falls_back_to_prefill_zero_failures():
    """The corruption drill end-to-end: the returning request completes
    byte-identically through the prefill fallback — no exception, no
    degraded transcript — and the books balance across both tiers."""
    inj = FaultInjector()
    cold = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4,
                            host_kv_blocks=32, faults=inj)
    await cold.start()
    await eng.start()
    history = "one two three four five six seven eight query"
    r1 = await eng.generate(history, max_tokens=8)
    assert eng._radix.evict_for(eng._pool.n_blocks)
    assert eng._host_store.used > 0
    inj.set("onload", "corrupt")
    h2 = history + " " + r1.text + " next"
    r2 = await eng.generate(h2, max_tokens=8)
    rc = await cold.generate(h2, max_tokens=8)
    assert r2.text == rc.text                    # byte-identical fallback
    assert r2.finish_reason == rc.finish_reason
    assert not r2.degraded                       # a hit became a prefill,
    #                                              not a degraded result
    assert eng._host_store.onload_fail_total["corrupt"] == 1
    assert eng._host_store.used == 0             # tainted subtree purged
    _assert_no_leak(eng)
    await eng.stop()
    await cold.stop()


async def test_fake_offload_fail_matches_host_off_engine():
    """``offload:fail`` through the engine: the device tier ends
    identical to a HOST_KV_BLOCKS=0 engine run through the same traffic
    and eviction."""
    inj = FaultInjector()
    eng = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4,
                            host_kv_blocks=8, faults=inj)
    off = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4)
    await eng.start()
    await off.start()
    prompt = "aa bb cc dd"                       # 1 full page + tail chain
    await eng.generate(prompt, max_tokens=2)
    await off.generate(prompt, max_tokens=2)
    inj.set("offload", "fail")
    assert eng._radix.evict_for(eng._pool.n_blocks)
    assert off._radix.evict_for(off._pool.n_blocks)
    assert eng._host_store.used == 0
    assert eng._host_store.offload_fail_total == 1
    assert eng._pool.free_count == off._pool.free_count
    assert eng._radix.node_count() == off._radix.node_count() == 0
    _assert_no_leak(eng)
    await eng.stop()
    await off.stop()


async def test_fake_containment_reset_rebuilds_both_tiers():
    """A scheduler death condemns the host tier too (its payloads were
    captured from the poisoned device world): after the supervisor
    reset, BOTH tiers are empty and the cumulative counters carried."""
    inj = FaultInjector()
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4,
                            host_kv_blocks=32, faults=inj)
    await eng.start()
    await eng.generate("warm chain aa bb cc dd ee", max_tokens=6)
    assert eng._radix.evict_for(eng._pool.n_blocks)
    store0 = eng._host_store
    d0 = store0.demoted_total
    assert store0.used > 0 and d0 > 0
    inj.set("scheduler", "die")
    rs = await asyncio.gather(
        *[eng.generate(f"die drill {i}", max_tokens=6) for i in range(3)])
    assert all(r.completion_tokens > 0 for r in rs)
    assert eng.supervisor.stats()["resets"].get("scheduler_death", 0) >= 1
    assert eng._host_store is not store0         # both tiers rebuilt
    assert eng._host_store.used == 0
    assert eng._host_store.demoted_total >= d0   # counters carried
    _assert_no_leak(eng)
    await eng.stop()


async def test_fake_session_budget_demotes_returning_turns():
    """Delivered tokens charge the namespaced session at finish; once
    over budget, the next turn classifies into the background lane."""
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4,
                            session_token_budget=2)
    await eng.start()
    ctx = QoSContext(tenant="acme", lane=LANE_INTERACTIVE,
                     session="acme/agent-1")
    with use_qos(ctx):
        await eng.generate("first turn spends the budget", max_tokens=8)
        assert eng._session_budgets.over("acme/agent-1")
        await eng.generate("second turn is demoted", max_tokens=4)
    snap = eng.qos_health()["session_budgets"]
    assert snap["enabled"] and snap["sessions_over_budget"] >= 1
    assert snap["demoted_total"] >= 1
    # A different session under the same tenant is unaffected.
    assert not eng._session_budgets.over("acme/agent-2")
    await eng.stop()


async def test_fake_starvation_marks_result_degraded():
    """Starvation-truncation is surfaced to the CLIENT: the result that
    was silently cut short carries ``degraded`` (and finish 'length'),
    a healthy run does not."""
    eng = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4,
                            kv_pool_blocks=3, radix_cache=False,
                            max_seq_len=64)
    await eng.start()
    r = await eng.generate("a b", max_tokens=60)
    assert r.finish_reason == "length" and r.degraded
    _assert_no_leak(eng)
    await eng.stop()
    ok = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4)
    await ok.start()
    r2 = await ok.generate("a b", max_tokens=4)
    assert not r2.degraded
    await ok.stop()


# --------------------------------------------------------- HTTP (ISSUE 20)

async def test_http_session_slo_host_tier_surfaces_and_thrash_incident():
    """The service plane end-to-end: /health grows the host_tier
    subsection, /metrics the host-tier gauges/counters, the turn-N TTFT
    SLO is judged ONLY for the radix-warm re-admission of a declared
    session, and a demote/onload churn spike files a
    ``host_tier_thrash`` incident at /debug/incidents."""
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    cfg = ServiceConfig(engine="fake", model_name="fake", llm_timeout=5.0,
                        rate_limit="10000/minute", sentinel_eval_secs=0.0,
                        incident_cooldown_secs=0.0,
                        incident_thrash_min_blocks=1)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4,
                            host_kv_blocks=32,
                            slo_session_ttft_ms=60_000.0)
    app = create_app(cfg, eng, executor=CommandExecutor(timeout=1.0))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await eng.start()
        hdr = {"X-Session-ID": "agent-1"}
        q = {"query": "list all pods in the staging namespace right now"}
        await client.post("/kubectl-command", json=q, headers=hdr)
        # Turn 1 is COLD — never judged by the session-TTFT SLO.
        lanes = eng.slo_health()["slos"]["session_ttft"]["lanes"]
        assert sum(r["samples_total"] for r in lanes.values()) == 0
        # Baseline the incident counters, then demote the session's
        # chain and return to it: demote + onload both spike.
        body = await (await client.get("/debug/incidents")).json()
        assert body["incidents"] == []
        assert eng._radix.evict_for(eng._pool.n_blocks)
        assert eng._host_store.used > 0
        await client.post("/kubectl-command", json=q, headers=hdr)
        assert eng._host_store.onloaded_total > 0
        # The radix-warm re-admission of the declared session IS judged.
        lanes = eng.slo_health()["slos"]["session_ttft"]["lanes"]
        assert sum(r["samples_total"] for r in lanes.values()) == 1
        # Thrash trigger: both deltas reached the (test-sized) floor.
        body = await (await client.get("/debug/incidents")).json()
        assert body["captured_total"].get(TRIGGER_HOST_THRASH) == 1
        inc = [i for i in body["incidents"]
               if i["trigger"] == TRIGGER_HOST_THRASH]
        assert inc, body["incidents"]
        # /health: the kv_pool section grew the host_tier subsection.
        h = await (await client.get("/health")).json()
        host = h["kv_pool"]["host_tier"]
        assert host["capacity"] == 32
        assert host["demoted_total"] >= 1 and host["onloaded_total"] >= 1
        # /metrics: host-tier gauges + delta-mirrored counters.
        m = await (await client.get("/metrics")).text()
        assert 'kv_host_blocks{state="used"}' in m
        assert 'kv_host_blocks{state="free"}' in m
        assert "kv_blocks_demoted_total" in m
        assert "kv_blocks_onloaded_total" in m
        assert 'kv_onload_fail_total{cause="corrupt"}' in m
        _assert_no_leak(eng)
    finally:
        await eng.stop()
        await client.close()


# --------------------------------------------------- jax engine (tier-1)

def _mk_jax(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    defaults = dict(dtype="float32", max_seq_len=192,
                    prefill_buckets=(32, 64), prefix_cache=False,
                    batch_size=4, chunk_len=4)
    defaults.update(kw)
    return BatchedJaxEngine(get_config("toy-8m"), tokenizer=ByteTokenizer(),
                            **defaults)


async def test_jax_demoted_chain_returns_byte_identical():
    """THE acceptance criterion on the real engine: after the session's
    chain is demoted (REAL device KV travels to host RAM and back), the
    returning turn's transcript is byte-identical to the dense cold
    re-prefill at temperature 0 AND seeded 0.9, and the onload served
    it."""
    warm = _mk_jax(kv_pool_page=16, host_kv_blocks=16)
    cold = _mk_jax(kv_pool=False)
    await warm.start()
    cold.tokenizer = warm.tokenizer
    await cold.start()
    try:
        for temp, seed in ((0.0, 0), (0.9, 77)):
            prompt = (f"inspect deployment rollout status verbose {seed} "
                      f"across the staging cluster now")
            r1 = await warm.generate(prompt, max_tokens=12,
                                     temperature=temp, seed=seed)
            assert warm._radix.cached_block_count() > 0
            assert warm._radix.evict_for(warm._pool.n_blocks)
            assert warm._host_store.used > 0
            assert warm._radix.cached_block_count() == 0
            h2 = prompt + r1.text + " and then?"
            on0 = warm._host_store.onloaded_total
            hits0 = warm._radix.hit_tokens_total
            r2 = await warm.generate(h2, max_tokens=12,
                                     temperature=temp, seed=seed)
            rc = await cold.generate(h2, max_tokens=12,
                                     temperature=temp, seed=seed)
            assert r2.text == rc.text, (temp, seed)
            assert warm._host_store.onloaded_total > on0
            # The prompt prefix (its bytes round-trip exactly) was
            # served by promoted pages, not a re-prefill.
            assert (warm._radix.hit_tokens_total - hits0
                    >= (len(prompt) // 16) * 16)
        _assert_no_leak(warm)
    finally:
        await asyncio.gather(warm.stop(), cold.stop())


async def test_jax_containment_reset_rebuilds_both_tiers():
    """decode:nan containment with a populated host tier: the reset
    rebuilds BOTH tiers empty (the host payloads were gathered from the
    poisoned device world), counters carry, books balance."""
    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison target"
    eng = _mk_jax(kv_pool_page=16, host_kv_blocks=16, faults=inj)
    await eng.start()
    try:
        await eng.generate("warm this chain before the poison lands",
                           max_tokens=8, temperature=0.0)
        assert eng._radix.evict_for(eng._pool.n_blocks)
        store0 = eng._host_store
        d0 = store0.demoted_total
        assert store0.used > 0 and d0 > 0
        with pytest.raises(RequestQuarantined):
            await eng.generate("poison target x", max_tokens=8,
                               temperature=0.0)
        assert eng._host_store is not store0
        assert eng._host_store.used == 0
        assert eng._host_store.demoted_total >= d0
        _assert_no_leak(eng)
    finally:
        await eng.stop()
