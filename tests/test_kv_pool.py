"""Block-paged KV pool + radix-tree prefix sharing (ISSUE 10).

Allocator/radix units, copy-on-write and eviction semantics, the
block-leak invariant after the PR 5/7 chaos recovery matrix (fake
engine — the SAME BlockPool/RadixCache/map_prefix code the jax batcher
runs), the oversubscribed prefix-sharing smoke (CI step), and
pool-vs-dense byte-identity on the REAL engine at temperature 0 and
0.9 including multi-turn incremental prefill."""

import asyncio
import time

import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine, _FakeReq
from ai_agent_kubectl_tpu.engine.kv_pool import (BlockPool, PoolExhausted,
                                                 alloc_with_evict,
                                                 map_prefix, pages_for)
from ai_agent_kubectl_tpu.engine.protocol import RequestQuarantined
from ai_agent_kubectl_tpu.engine.qos import (LANE_BACKGROUND,
                                             LANE_INTERACTIVE)
from ai_agent_kubectl_tpu.engine.radix_cache import RadixCache
from ai_agent_kubectl_tpu.testing.faults import FaultInjector


# ---------------------------------------------------------------- helpers

def _holders(eng) -> dict:
    """Expected per-block holder counts: live slots' tables + parked
    slots + the radix tree's edges — what BlockPool.check verifies the
    refcounts against EXACTLY."""
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
    """THE invariant: every non-cached block is back on the free list,
    refcounts balance exactly — no leak, no double-free."""
    cached = (eng._radix.cached_blocks() if eng._radix is not None
              else set())
    st = eng._pool.stats(cached)
    assert st.live == 0, f"live blocks leaked: {st}"
    assert st.free + st.cached == st.n_blocks, st
    eng._pool.check(_holders(eng))


async def _drain(eng, n_ticks=2000):
    for _ in range(n_ticks):
        eng._tick()
        if (all(s is None for s in eng._slots) and not eng._inflight
                and not eng._queue and not eng._parked):
            return
        await asyncio.sleep(0)
    raise AssertionError("fake engine did not drain")


# ------------------------------------------------------------- pool units

def test_block_pool_alloc_refcount_free():
    pool = BlockPool(8, 4)
    a = pool.alloc(3)
    assert len(a) == 3 and pool.free_count == 5
    pool.incref(a)                       # second holder
    assert pool.decref(a) == []          # first holder drops: none freed
    assert pool.decref(a) == a           # second drops: all freed
    assert pool.free_count == 8
    with pytest.raises(RuntimeError):
        pool.decref([a[0]])              # double free is a hard error
    with pytest.raises(RuntimeError):
        pool.incref([a[0]])              # use-after-free is a hard error
    with pytest.raises(PoolExhausted):
        pool.alloc(9)
    pool.check({})


def test_pages_for_and_pool_check_detects_imbalance():
    assert pages_for(0, 16) == 0
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2
    pool = BlockPool(4, 16)
    kept = pool.alloc(1)
    with pytest.raises(AssertionError):
        pool.check({})                   # holder books don't balance
    pool.check({kept[0]: 1})


# ------------------------------------------------------------ radix units

def test_radix_insert_match_share_and_cow():
    pool = BlockPool(32, 4)
    rad = RadixCache(pool, max_blocks=16)
    ids = list(range(11))                # 2 full pages + 3-row tail
    blocks = pool.alloc(3)
    assert rad.insert(ids, blocks) == 3
    pool.decref(blocks)                  # owner leaves: chain is cached
    st = pool.stats(rad.cached_blocks())
    assert st.cached == 3 and st.live == 0

    # A second request sharing the prefix: full blocks map shared, the
    # partial tail is marked for copy-on-write, refs are the caller's.
    mr = rad.match(list(range(11)) + [99])
    assert mr.n_tokens == 11
    assert mr.blocks == blocks[:2]
    assert mr.tail_block == blocks[2] and mr.tail_rows == 3
    assert pool.ref(blocks[0]) == 2      # tree + caller
    cow = pool.alloc(1)                  # the private copy target
    pool.decref([mr.tail_block])         # caller done with the source
    pool.note_cow()
    assert pool.cow_copies_total == 1
    assert pool.shared_mapped_total == 2
    pool.decref(mr.blocks + cow)
    _ = pool.stats(rad.cached_blocks())
    rad.clear()
    assert pool.free_count == 32
    pool.check({})


def test_radix_match_divergent_tail_and_miss_counters():
    pool = BlockPool(16, 4)
    rad = RadixCache(pool, max_blocks=8)
    blocks = pool.alloc(2)
    rad.insert([1, 2, 3, 4, 5, 6], blocks)      # 1 full page + 2-row tail
    pool.decref(blocks)
    # Diverges inside the tail: only the common row matches.
    mr = rad.match([1, 2, 3, 4, 5, 99, 100])
    assert mr.n_tokens == 5 and mr.tail_rows == 1
    pool.decref(mr.blocks + [mr.tail_block])
    # Diverges inside the first page: nothing matches.
    mr2 = rad.match([1, 2, 99, 4])
    assert mr2.n_tokens == 0 and not mr2.blocks and mr2.tail_block is None
    assert rad.miss_tokens_total >= 4


def test_radix_lru_eviction_is_refcount_aware():
    pool = BlockPool(16, 4)
    rad = RadixCache(pool, max_blocks=2)         # tiny budget
    b1 = pool.alloc(2)
    rad.insert([1, 2, 3, 4, 5, 6, 7, 8], b1)     # 2 full pages
    # A live slot still maps b1's first block when the budget evicts it.
    pool.incref([b1[0]])
    pool.decref(b1)                              # inserter leaves
    b2 = pool.alloc(2)
    rad.insert([9, 10, 11, 12, 13, 14, 15, 16], b2)
    pool.decref(b2)
    assert rad.cached_block_count() <= 2
    # The evicted-but-live block survived at refcount 1 (the slot's) —
    # eviction dropped only the CACHED state, never yanked live KV.
    assert pool.ref(b1[0]) == 1
    pool.decref([b1[0]])
    rad.clear()
    pool.check({})


def test_map_prefix_admission_leaves_last_token_and_releases_on_failure():
    pool = BlockPool(4, 4)
    rad = RadixCache(pool, max_blocks=4)
    blocks = pool.alloc(2)
    rad.insert([1, 2, 3, 4, 5, 6, 7], blocks)    # 1 full page + 3-row tail
    pool.decref(blocks)
    # match_all=False: the LAST token must prefill (its logits seed the
    # first sample), so an exact-chain prompt matches at most n-1 — here
    # the full page shares and the 3-row tail copy-on-writes.
    got, m = map_prefix(pool, rad, [1, 2, 3, 4, 5, 6, 7, 8])
    assert m == 7 and len(got) == 2      # 1 shared full page + COW'd tail
    assert pool.cow_copies_total == 1
    pool.decref(got)
    # Exhaustion mid-build releases every ref it took (pool of 4: 2
    # cached + a 9-page ask can never fit, even after eviction).
    with pytest.raises(PoolExhausted):
        map_prefix(pool, rad, list(range(100)), match_all=True)
    st = pool.stats(rad.cached_blocks())
    assert st.live == 0


def test_alloc_with_evict_reclaims_cached_blocks():
    pool = BlockPool(4, 4)
    rad = RadixCache(pool, max_blocks=4)
    blocks = pool.alloc(4)
    rad.insert(list(range(16)), blocks)
    pool.decref(blocks)                  # all 4 blocks now cached
    assert pool.free_count == 0
    got = alloc_with_evict(pool, rad, 3)  # eviction frees LRU leaves
    assert got is not None and len(got) == 3
    pool.decref(got)


# ----------------------------------------------- fake engine (CI smoke)

async def test_fake_two_sessions_share_prompt_blocks_byte_identical():
    """The CI prefix-sharing smoke, part 1: concurrent sessions sharing
    a prompt prefix at a pool so small the dense layout (batch x
    pages-per-slot) could not allocate — shared-block count > 0 and
    transcripts byte-identical to the dense-KV fake."""
    prompt = "one two three four five six seven eight nine ten query"
    dense = FakeChunkedEngine(batch_size=4, chunk_len=4, kv_pool=False)
    await dense.start()
    want = (await dense.generate(prompt, max_tokens=10)).text
    await dense.stop()

    # 4 slots x 17 max pages would want 68 blocks dense; 24 suffices
    # BECAUSE the prompt blocks share.
    eng = FakeChunkedEngine(batch_size=4, chunk_len=4, kv_pool_page=4,
                            kv_pool_blocks=24, max_seq_len=64)
    await eng.start()
    rs = await asyncio.gather(
        *[eng.generate(prompt, max_tokens=10) for _ in range(8)])
    assert all(r.text == want for r in rs)
    assert eng._pool.shared_mapped_total > 0
    _assert_no_leak(eng)
    await eng.stop()


async def test_fake_multi_turn_radix_hits_cover_history():
    """CI smoke, part 2: a 3-turn loop re-sending its whole history —
    turn 2+ must radix-hit at least the history length (incremental
    prefill), byte-identical to the dense fake."""
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4)
    dense = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool=False)
    await eng.start()
    await dense.start()
    history = "alpha beta gamma delta question"
    for turn in range(3):
        hits0 = eng._radix.hit_tokens_total
        hist_ids = len(eng._prompt_token_ids(history))
        r = await eng.generate(history, max_tokens=8)
        rd = await dense.generate(history, max_tokens=8)
        assert r.text == rd.text
        if turn > 0:
            hits = eng._radix.hit_tokens_total - hits0
            # history = prior prompt + full completion + one new word;
            # the cached chain covers everything but the completion's
            # final id and the new word — incremental prefill over the
            # whole re-sent history (the acceptance criterion:
            # radix_hit_tokens >= history length, chain-coverage form).
            assert hits >= hist_ids - 2, (turn, hits, hist_ids)
        history = history + " " + r.text + " next"
    _assert_no_leak(eng)
    await eng.stop()
    await dense.stop()


async def test_fake_preempt_resume_remaps_cached_chain():
    """Preemptive decode over the pool: the victim's chain is cached at
    preemption and its resume RE-MAPS those blocks (radix hit covering
    prompt + generated prefix) instead of re-prefilling — and the books
    still balance."""
    stream = [10 + i for i in range(30)] + [2]
    eng = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4,
                            preempt_wait_ms=1.0, preempt_budget=2)
    bg = _FakeReq(prompt="bulk job one", max_tokens=40, deadline=None,
                  out_queue=asyncio.Queue(), cancel=asyncio.Event(),
                  stream=list(stream), tenant="bulk",
                  lane=LANE_BACKGROUND, t_submit=time.monotonic(),
                  prompt_ids=FakeChunkedEngine._prompt_token_ids(
                      "bulk job one"))
    eng._queue.put(bg)
    eng._admit_pending()
    for _ in range(4):
        eng._tick()
    inter = _FakeReq(prompt="quick", max_tokens=2, deadline=None,
                     out_queue=asyncio.Queue(), cancel=asyncio.Event(),
                     stream=[7, 8, 2], tenant="quiet",
                     lane=LANE_INTERACTIVE, t_submit=time.monotonic(),
                     prompt_ids=FakeChunkedEngine._prompt_token_ids(
                         "quick"))
    eng._queue.put(inter)
    time.sleep(0.005)
    assert eng._maybe_preempt() is True
    g = len(bg.resume_ids)
    assert g >= 2
    # The preempted chain is CACHED (prompt + emitted[:-1]).
    chain_len = len(bg.prompt_ids) + g - 1
    assert eng._radix.cached_block_count() >= pages_for(chain_len, 4)
    hits0 = eng._radix.hit_tokens_total
    for _ in range(600):
        eng._tick()
        if all(s is None for s in eng._slots) and not eng._queue:
            break
        await asyncio.sleep(0)
    # Resume radix-matched the whole replay basis — a block-table
    # re-map, not a re-prefill.
    assert eng._radix.hit_tokens_total - hits0 >= chain_len
    _assert_no_leak(eng)


async def test_fake_leak_invariant_after_chaos_matrix():
    """THE block-leak invariant (tier-1, CI smoke part 3): after the
    PR 5/7 chaos recovery matrix — a targeted decode:nan quarantine, a
    scheduler:die restart, and preempt→resume traffic — every non-cached
    block returns to the free list; refcounts balance exactly against
    the computed holder set (no leak, no double-free)."""
    # Phase 1: decode:nan quarantine — the target 410s, victims replay.
    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison me"
    eng = FakeChunkedEngine(batch_size=4, chunk_len=4, kv_pool_page=4,
                            faults=inj)
    await eng.start()
    prompts = ["poison me now", "innocent one", "innocent two",
               "innocent three", "queued four", "queued five"]
    results = await asyncio.gather(
        *[eng.generate(p, max_tokens=10) for p in prompts],
        return_exceptions=True)
    quarantined = [r for r in results if isinstance(r, BaseException)]
    assert len(quarantined) == 1
    assert isinstance(quarantined[0], RequestQuarantined)
    _assert_no_leak(eng)
    await eng.stop()

    # Phase 2: scheduler:die mid-traffic — supervisor restarts, pool
    # world rebuilds, replays complete, books balance.
    inj2 = FaultInjector()
    inj2.set("scheduler", "die")
    eng2 = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4,
                             faults=inj2)
    await eng2.start()
    rs = await asyncio.gather(
        *[eng2.generate(f"die drill {i}", max_tokens=8) for i in range(4)])
    assert all(r.completion_tokens > 0 for r in rs)
    assert eng2.supervisor.stats()["resets"].get("scheduler_death", 0) >= 1
    _assert_no_leak(eng2)
    await eng2.stop()

    # Phase 3: preempt→resume under contention (manual ticking above
    # covers mechanics; here the async loop drives it end to end).
    eng3 = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4,
                             preempt_wait_ms=1.0, preempt_budget=2)
    await eng3.start()
    from ai_agent_kubectl_tpu.engine.qos import QoSContext, use_qos

    async def bg_job():
        with use_qos(QoSContext(tenant="bulk", lane=LANE_BACKGROUND)):
            return await eng3.generate("long background job",
                                       max_tokens=30)

    async def probe():
        await asyncio.sleep(0.02)
        with use_qos(QoSContext(tenant="quiet", lane=LANE_INTERACTIVE)):
            return await eng3.generate("quick probe", max_tokens=3)

    rbg, rpr = await asyncio.gather(bg_job(), probe())
    assert rbg.completion_tokens > 0 and rpr.completion_tokens > 0
    _assert_no_leak(eng3)
    await eng3.stop()


async def test_fake_pool_starvation_truncates_never_corrupts():
    """A genuinely-out pool (no radix to evict) truncates the slot at
    its current length with finish 'length' — and frees its blocks."""
    eng = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4,
                            kv_pool_blocks=3, radix_cache=False,
                            max_seq_len=64)
    await eng.start()
    r = await eng.generate("a b", max_tokens=60)   # wants ~16 blocks
    assert r.finish_reason == "length"
    assert 0 < r.completion_tokens < 60
    assert eng._pool_starved >= 1
    _assert_no_leak(eng)
    await eng.stop()


async def test_fake_oversubscribed_pool_admits_past_dense_capacity():
    """Oversubscription is the point: with blocks for ~1.5 dense slots,
    8 short concurrent requests all complete correctly (blocks cycle
    through the free list as requests finish; the dense layout would
    need 8 full regions up front)."""
    dense_pages_per_slot = pages_for(64 + 4, 4)        # max_seq + chunk
    eng = FakeChunkedEngine(batch_size=8, chunk_len=4, kv_pool_page=4,
                            kv_pool_blocks=3 * dense_pages_per_slot // 2,
                            radix_cache=False, max_seq_len=64)
    dense = FakeChunkedEngine(batch_size=8, chunk_len=4, kv_pool=False)
    await eng.start()
    await dense.start()
    prompts = [f"short req {i}" for i in range(8)]
    rs = await asyncio.gather(
        *[eng.generate(p, max_tokens=6) for p in prompts])
    ds = await asyncio.gather(
        *[dense.generate(p, max_tokens=6) for p in prompts])
    assert [r.text for r in rs] == [d.text for d in ds]
    _assert_no_leak(eng)
    await eng.stop()
    await dense.stop()


async def test_fake_kv_pool_stats_and_health_surface():
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4)
    await eng.start()
    await eng.generate("surface check", max_tokens=6)
    st = eng.stats()["kv_pool"]
    assert st["n_blocks"] == eng._pool.n_blocks
    assert st["free"] + st["live"] + st["cached"] == st["n_blocks"]
    assert st["radix"]["insertions"] >= 1
    assert eng.kv_pool_health() == st
    # Dense fake reports no pool section.
    off = FakeChunkedEngine(kv_pool=False)
    assert off.kv_pool_health() is None
    assert off.stats()["kv_pool"] is None
    await eng.stop()


async def test_health_and_metrics_expose_kv_pool():
    """/health carries the kv_pool section and /metrics the
    kv_pool_blocks{state} gauges + sharing/radix counters (delta-mirror
    from stats()['kv_pool'])."""
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    cfg = ServiceConfig(engine="fake", model_name="fake", llm_timeout=5.0)
    engine = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4)
    app = create_app(cfg, engine,
                     executor=CommandExecutor(timeout=1.0))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await engine.start()
        prompt = "list all pods in the staging namespace please right now"
        await engine.generate(prompt, max_tokens=6)
        await engine.generate(prompt, max_tokens=6)
        h = await client.get("/health")
        body = await h.json()
        assert body["kv_pool"] is not None
        assert body["kv_pool"]["n_blocks"] == engine._pool.n_blocks
        assert body["kv_pool"]["radix"]["hit_tokens"] > 0
        m = await client.get("/metrics")
        text = await m.text()
        assert 'kv_pool_blocks{state="free"}' in text
        assert "radix_hit_tokens_total" in text
        assert "kv_blocks_shared_total" in text
        assert "kv_cow_copies_total" in text
    finally:
        await engine.stop()
        await client.close()


def test_config_validates_pool_knobs():
    from ai_agent_kubectl_tpu.config import ServiceConfig

    with pytest.raises(ValueError):
        ServiceConfig(kv_pool_page=24)       # does not divide 128
    with pytest.raises(ValueError):
        ServiceConfig(kv_pool_page=0)
    with pytest.raises(ValueError):
        ServiceConfig(kv_pool_blocks=-1)
    with pytest.raises(ValueError):
        ServiceConfig(radix_lru_blocks=-1)
    cfg = ServiceConfig(kv_pool_page=64, kv_pool_blocks=256,
                        radix_lru_blocks=32)
    assert cfg.kv_pool and cfg.radix_cache


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


async def test_jax_pool_vs_dense_byte_identity_and_sharing():
    """THE acceptance criterion on the real engine: pool transcripts are
    byte-identical to the dense ladder at temperature 0 AND 0.9 (seeded
    sampling), concurrent admissions sharing a prompt prefix share
    blocks, a repeated prompt radix-hits, and the books balance after
    the traffic drains."""
    pool = _mk_jax(kv_pool_page=16)
    dense = _mk_jax(kv_pool=False)
    await pool.start()
    dense.tokenizer = pool.tokenizer
    await dense.start()
    try:
        cases = [("list pods", 0.0, 11), ("get deployments wide", 0.9, 22),
                 ("scale web to three", 0.9, 33)]
        for prompt, temp, seed in cases:
            rp = await pool.generate(prompt, max_tokens=16,
                                     temperature=temp, seed=seed)
            rd = await dense.generate(prompt, max_tokens=16,
                                      temperature=temp, seed=seed)
            assert rp.text == rd.text, (prompt, temp)
        # Repetition then concurrency: the first request caches its
        # chain; three concurrent repeats all radix-share it (full
        # blocks shared, tail COW'd) with identical transcripts.
        first = await pool.generate("repeat exactly this", max_tokens=10,
                                    temperature=0.0)
        rs = await asyncio.gather(*[
            pool.generate("repeat exactly this", max_tokens=10,
                          temperature=0.0) for _ in range(3)])
        assert len({r.text for r in rs} | {first.text}) == 1
        st = pool.stats()["kv_pool"]
        assert st["radix"]["hit_tokens"] > 0
        assert st["shared_mapped_total"] + st["cow_copies_total"] > 0
        # Books balance: nothing live once traffic drained.
        _assert_no_leak(pool)
    finally:
        await asyncio.gather(pool.stop(), dense.stop())


async def test_jax_multi_turn_incremental_prefill():
    """Turn 2 of an agent loop re-sending its history prefills only the
    unmatched suffix: radix_hit_tokens grows by >= the history length,
    and the transcript equals the dense path's."""
    pool = _mk_jax(kv_pool_page=16)
    dense = _mk_jax(kv_pool=False)
    await pool.start()
    dense.tokenizer = pool.tokenizer
    await dense.start()
    try:
        history = "turn one: list pods"
        for turn in range(2):
            hits0 = pool._radix.hit_tokens_total
            hist_ids = len(pool.tokenizer.encode(history))
            rp = await pool.generate(history, max_tokens=10,
                                     temperature=0.0)
            rd = await dense.generate(history, max_tokens=10,
                                      temperature=0.0)
            assert rp.text == rd.text
            if turn > 0:
                hits = pool._radix.hit_tokens_total - hits0
                # The toy model emits non-UTF8 garbage whose text form
                # does not round-trip through the byte tokenizer, so
                # the guaranteed match floor here is the turn-1 prompt
                # (the re-sent portion that DOES round-trip) — the fake
                # engine's suite asserts the full history-length claim
                # with its round-trip token encoding.
                assert hits >= turn1_ids - 1, (hits, turn1_ids, hist_ids)
                assert rp.prefix_cache_hit
            else:
                turn1_ids = hist_ids
            history = history + rp.text + " and then?"
        _assert_no_leak(pool)
    finally:
        await asyncio.gather(pool.stop(), dense.stop())


async def test_jax_containment_reset_rebuilds_pool_no_leak():
    """A decode:nan quarantine mid-batch (pool mode): the target 410s,
    victims replay byte-identically into FRESH blocks (the reset
    rebuilt the allocator world), and the books balance after."""
    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison target"
    base_eng = _mk_jax(kv_pool_page=16)
    await base_eng.start()
    prompts = ["poison target x", "bystander a", "bystander b"]
    base = {}
    for p in prompts[1:]:
        base[p] = (await base_eng.generate(p, max_tokens=8,
                                           temperature=0.0)).text
    await base_eng.stop()

    eng = _mk_jax(kv_pool_page=16, faults=inj)
    await eng.start()
    try:
        results = await asyncio.gather(
            *[eng.generate(p, max_tokens=8, temperature=0.0)
              for p in prompts],
            return_exceptions=True)
        assert isinstance(results[0], RequestQuarantined)
        for p, r in zip(prompts[1:], results[1:]):
            assert r.text == base[p], f"victim {p!r} transcript changed"
        _assert_no_leak(eng)
    finally:
        await eng.stop()
