"""The recurrent-state cache beside the block pool (ISSUE 33): StateStore and
the radix tree's snapshot rule as units, the fake engine's mirror of the
bookkeeping (a state of no bytes), and the real engine on ``toy-hybrid-moe``
(CPU, float32): a request seated from a restored snapshot answers as one
prefilled from token 0, a match deeper than the last snapshot recomputes
exactly the tokens past it, eviction spares what a live slot descends from,
preempt and replay of a sequence with state, /health.ssm, the two spans, the
Prometheus series, and the refusals at start."""

import asyncio
import time

import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine, _FakeReq
from ai_agent_kubectl_tpu.engine.kv_pool import (BlockPool, StateStore,
                                                 map_prefix, release_state,
                                                 state_cuts, take_snapshot)
from ai_agent_kubectl_tpu.engine.qos import (LANE_BACKGROUND,
                                             LANE_INTERACTIVE, QoSContext,
                                             use_qos)
from ai_agent_kubectl_tpu.engine.radix_cache import RadixCache

PAGE = 4


def world(capacity=4, n_blocks=64, slots=3, **store_kw):
    pool = BlockPool(n_blocks, PAGE)
    store = StateStore(capacity, slots, state_bytes=100, **store_kw)
    return pool, store, RadixCache(pool, max_blocks=n_blocks, state_store=store)


def admit(pool, store, radix, slot, ids):
    """One admission as both engines run it: map, snapshot at the policy's
    edges. Returns (blocks, tokens the prefill skipped)."""
    blocks, m = map_prefix(pool, radix, ids, state=store, slot=slot)
    for edge in state_cuts(store, slot, len(ids), PAGE, m):
        take_snapshot(store, radix, slot, ids, edge)
    return blocks, m


def finish(pool, store, radix, slot, ids, blocks):
    radix.insert(ids, blocks)
    release_state(store, radix, slot, ids, True)
    pool.decref(blocks)
    store.check()


# ------------------------------------------------------------------- units

def test_match_is_usable_only_as_deep_as_the_last_snapshot():
    pool, store, radix = world()
    a = list(range(100, 122))                 # 22 tokens: last whole block ends at 20
    blocks, m = admit(pool, store, radix, 0, a)
    # its last whole block's edge and the edge before it (ISSUE 40)
    assert m == 0 and [e for e, _ in store.pending(0)] == [16, 20]
    finish(pool, store, radix, 0, a, blocks)
    assert store.stats()["snapshots_held"] == 2 and not store.pending(0)
    hit0, miss0 = radix.hit_tokens_total, radix.miss_tokens_total
    # the same 22 tokens grown by a turn: K/V matches 5 blocks + 1 tail row,
    # the state only the 5 blocks
    b = a + list(range(300, 310))
    blocks, m = admit(pool, store, radix, 1, b)
    assert m == 20 and store.restores == 1
    assert (store.prefix_tokens_matched, store.prefix_tokens_usable,
            store.prefix_tokens_recomputed) == (22, 20, 2)
    # hit_tokens counts only tokens whose prefill was skipped
    assert radix.hit_tokens_total - hit0 == 20
    assert radix.miss_tokens_total - miss0 == len(b) - 1 - 20
    finish(pool, store, radix, 1, b, blocks)
    # a sequence that shares 3 blocks with them and then leaves: K/V matched 12
    # tokens, no snapshot on that path, everything recomputed. The node there
    # has one child (a's own chain): nobody branched from it yet, no snapshot
    c = a[:12] + list(range(500, 512))
    blocks, m = admit(pool, store, radix, 2, c)
    assert m == 0 and store.branch_edge(2) == 0
    assert store.prefix_tokens_recomputed == 2 + 12
    assert [e for e, _ in store.pending(2)] == [16, 20]
    finish(pool, store, radix, 2, c, blocks)
    # the next one finds a node two sequences branch from: it recomputes the 12
    # too and leaves the snapshot there, which the one after restores
    d = a[:12] + list(range(700, 709))
    blocks, m = admit(pool, store, radix, 0, d)
    assert m == 0 and store.branch_edge(0) == 12
    assert [e for e, _ in store.pending(0)] == [16, 20]        # 12 went on the tree
    finish(pool, store, radix, 0, d, blocks)
    e = a[:12] + list(range(800, 809))
    blocks, m = admit(pool, store, radix, 1, e)
    assert m == 12 and store.restores == 2
    finish(pool, store, radix, 1, e, blocks)


def test_eviction_is_lru_among_snapshots_no_live_slot_descends_from():
    calls = []
    pool, store, radix = world(
        capacity=4, snapshot_fn=lambda s, h: calls.append(("snap", s, h)),
        restore_fn=lambda s, h: calls.append(("restore", s, h)),
        zero_fn=lambda s: calls.append(("zero", s)))
    a, b, c = (list(range(k, k + 10)) for k in (100, 200, 300))
    for slot, ids in ((0, a), (1, b)):
        blocks, _ = admit(pool, store, radix, slot, ids)
        finish(pool, store, radix, slot, ids, blocks)
    # two snapshots a prompt: its last block edge and the one before it
    assert store.held == 4 and [k for k, *_ in calls] == ["zero", "snap", "snap"] * 2
    # a live slot descends from a's snapshots (the OLDER ones): c's snapshots
    # must evict b's
    live, m = admit(pool, store, radix, 0, a + [1, 2, 3, 4, 5])
    assert m == 8 and calls[-2][0] == "restore"
    assert store.restore_depth_peak == 3              # a's deepest: older than b's two
    held_by_a = calls[-2][2]
    blocks, _ = admit(pool, store, radix, 1, c)
    assert store.snapshots_evicted == 2
    finish(pool, store, radix, 1, c, blocks)
    _, m_b = map_prefix(pool, radix, b + [9], state=store, slot=2)
    assert m_b == 0                                   # b's snapshot is gone
    assert held_by_a in store._node                   # a's is not
    # every snapshot pinned: a new one is skipped, not forced
    pool2, store2, radix2 = world(capacity=1)
    blocks, _ = admit(pool2, store2, radix2, 0, a)
    assert store2.held == 1 and store2.snapshots_skipped == 1      # a's second cut
    assert store2.take(1, 8) is None and store2.snapshots_skipped == 2
    store2.check()


def test_a_node_that_loses_its_block_loses_its_snapshot():
    pool, store, radix = world(n_blocks=8)
    a = list(range(100, 110))
    blocks, _ = admit(pool, store, radix, 0, a)
    finish(pool, store, radix, 0, a, blocks)
    assert store.held == 2
    assert radix.evict_for(8)                 # the pool wants everything back
    assert store.held == 0 and store.snapshots_evicted == 2
    store.check()
    radix.clear()
    assert radix.stats()["snapshots"] == 0


# -------------------------------------------------------------- fake mirror

def _req(prompt, stream, lane=LANE_INTERACTIVE, max_tokens=8):
    return _FakeReq(prompt=prompt, max_tokens=max_tokens, deadline=None,
                    out_queue=asyncio.Queue(), cancel=asyncio.Event(),
                    stream=list(stream), tenant="t", lane=lane,
                    t_submit=time.monotonic(),
                    prompt_ids=FakeChunkedEngine._prompt_token_ids(prompt))


async def _drain(eng, n_ticks=2000):
    for _ in range(n_ticks):
        eng._tick()
        if (all(s is None for s in eng._slots) and not eng._inflight
                and not eng._queue and not eng._parked):
            return
        await asyncio.sleep(0)
    raise AssertionError("fake engine did not drain")


async def test_fake_mirrors_the_bookkeeping_over_agent_sessions():
    """Two agents over one preamble, three turns each, through the fake (no
    bytes, the batcher's StateStore / map_prefix / state_cuts verbatim): every
    turn leaves its prompt-end snapshot, later turns restore their own, the
    books balance."""
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=4,
                            state_snapshots=6)
    pre = " ".join(f"t{100 + i}" for i in range(24))
    hist = {a: pre + f" t{900 + a}" for a in (0, 1)}
    for turn in range(3):
        for a in (0, 1):
            hist[a] += " " + " ".join(f"t{1000 * (a + 1) + 10 * turn + j}" for j in range(9))
            eng._queue.put(_req(hist[a], [5, 6, 7, 2]))
            eng._admit_pending()
            await _drain(eng)
            eng._state.check()
    st = eng.stats()["ssm"]
    assert st["restores"] >= 4 and st["snapshots_taken"] >= 6
    assert st["prefix_tokens_usable"] <= st["prefix_tokens_matched"]
    assert st["prefix_tokens_matched"] == (st["prefix_tokens_usable"]
                                           + st["prefix_tokens_recomputed"])
    assert st["snapshots_held"] <= 6 and st["held_peak"] <= 6
    assert eng._pool.stats(eng._radix.cached_blocks()).live == 0
    assert FakeChunkedEngine(batch_size=1).stats()["ssm"] is None


async def test_fake_preempt_and_resume_release_and_reseat_the_state():
    eng = FakeChunkedEngine(batch_size=1, chunk_len=4, kv_pool_page=4,
                            preempt_wait_ms=1.0, preempt_budget=2,
                            state_snapshots=4)
    bg = _req(" ".join(f"t{200 + i}" for i in range(14)),
              [10 + i for i in range(30)] + [2], LANE_BACKGROUND, 40)
    eng._queue.put(bg)
    eng._admit_pending()
    for _ in range(4):
        eng._tick()
    eng._queue.put(_req("quick", [7, 8, 2], max_tokens=2))
    time.sleep(0.005)
    assert eng._maybe_preempt() is True
    eng._state.check()
    r0 = eng._state.restores
    await _drain(eng)
    # the resume restored the victim's prompt-end snapshot (12 of its tokens)
    assert eng._state.restores > r0
    eng._state.check()
    assert not any(eng._state._slot_pins[0])


# -------------------------------------------------------------- real engine

def _mk(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    defaults = dict(dtype="float32", max_seq_len=320, prefill_buckets=(16, 64),
                    prefix_cache=False, batch_size=2, chunk_len=4,
                    kv_pool_page=16, state_snapshots=8, kv_pool_blocks=96,
                    radix_lru_blocks=64)
    model = kw.pop("model", "toy-hybrid-moe")
    defaults.update(kw)
    return BatchedJaxEngine(get_config(model), tokenizer=ByteTokenizer(), **defaults)


PREAMBLE = "cluster context: " + "node pool alpha beta gamma delta " * 3      # 116 chars
TURNS = ["agent one asks about pods in kube-system;  ",     # 160 tokens with the preamble
         "tool says twelve pods are ready; ",
         "tool says one pod is crash looping now; "]


# (longer than two pages of 16: a prompt's two cuts then stand past the
# preamble's end, and the snapshot there is the branch rule's to leave)
AGENT_TWO = "agent two starts here and lists every deployment; "
AGENT_THREE = "agent three is here and wants the node status; "
AGENT_FOUR = "agent four came too and asks about services; "


@pytest.fixture(scope="module")
def from_token_zero():
    """Every prompt of the session answered by an engine with no radix tree:
    each prefilled from token 0."""
    eng = _mk(radix_cache=False)
    asyncio.run(eng.start())

    async def run():
        out, hist = {}, PREAMBLE
        for t in TURNS + [AGENT_TWO]:
            prompt = (PREAMBLE + t) if t.startswith("agent two") else (hist + t)
            out[prompt] = (await eng.generate(prompt, max_tokens=10, temperature=0.0)).text
            hist = prompt if not t.startswith("agent two") else hist
        return out

    try:
        return asyncio.run(run())
    finally:
        asyncio.run(eng.stop())


@pytest.mark.parametrize("force_ragged", [False, True], ids=["gather", "ragged-staged"])
async def test_restored_snapshot_answers_as_a_prefill_from_token_zero(from_token_zero,
                                                                      force_ragged):
    """(Through the CPU's gather regime, whose admissions prefill whole, and
    through the chip's ragged regime, interpreted, whose admissions stage the
    prompt's last partial block into the next chunk's window.) Turns 2 and 3 of a session are seated from the snapshot the turn before
    left at its prompt's last block edge and prefill only what follows; a second
    agent over the same preamble recomputes it once and leaves the snapshot.
    Every answer equals the engine's that prefilled from token 0; the counters
    say what was matched, usable and recomputed; the spans and series exist."""
    from ai_agent_kubectl_tpu.server.metrics import Metrics

    eng = _mk(force_ragged=force_ragged)
    await eng.start()
    try:
        assert eng.kv_pool_health()["attention_regime"] == ("ragged" if force_ragged else "gather")
        st0 = eng.stats()["ssm"]          # the start's warm-up answer is in it
        assert st0["capacity"] == 8
        hist, usable = PREAMBLE, []
        for t in TURNS:
            before = eng.family_health()["ssm"]
            r = await eng.generate(hist + t, max_tokens=10, temperature=0.0)
            assert r.text == from_token_zero[hist + t], t
            after = eng.family_health()["ssm"]
            usable.append(after["prefix_tokens_usable"] - before["prefix_tokens_usable"])
            hist += t
        n1 = len(eng.tokenizer.encode(PREAMBLE + TURNS[0]))     # bytes and a BOS
        # turn 1 found nothing; turn 2 restored turn 1's prompt-end snapshot,
        # turn 3 turn 2's: each the last whole block of the prompt before
        assert usable[0] == 0
        assert usable[1] == (n1 - 1) // 16 * 16
        assert usable[2] == (n1 + len(TURNS[1]) - 1) // 16 * 16
        st = eng.family_health()["ssm"]
        # two snapshots a turn, at its prompt's last block edge and the edge
        # before it: where turn 2 left turn 1's chain the node had one child,
        # and nobody else comes that way
        assert st["restores"] - st0["restores"] == 2
        assert st["snapshots_taken"] - st0["snapshots_taken"] == 6
        # matched K/V past the snapshot (the rest of the last prompt and its
        # answer's rows) was recomputed, and counted so
        assert st["prefix_tokens_recomputed"] > 0
        assert st["prefix_tokens_matched"] == (st["prefix_tokens_usable"]
                                               + st["prefix_tokens_recomputed"])
        radix = eng.kv_pool_health()["radix"]
        assert radix["hit_tokens"] == st["prefix_tokens_usable"]
        # a second agent: K/V matches the preamble, no snapshot there yet
        r = await eng.generate(PREAMBLE + AGENT_TWO, max_tokens=10,
                               temperature=0.0)
        assert r.text == from_token_zero[PREAMBLE + AGENT_TWO]
        st2 = eng.family_health()["ssm"]
        edge = len(eng.tokenizer.encode(PREAMBLE)) // 16 * 16
        assert st2["prefix_tokens_recomputed"] - st["prefix_tokens_recomputed"] >= edge
        assert st2["snapshots_taken"] == st["snapshots_taken"] + 2    # its prompt's end
        # a third finds the preamble's end a node two sequences branch from:
        # it recomputes the preamble once more and leaves the snapshot there
        await eng.generate(PREAMBLE + AGENT_THREE, max_tokens=4, temperature=0.0)
        st3 = eng.family_health()["ssm"]
        assert st3["prefix_tokens_recomputed"] - st2["prefix_tokens_recomputed"] >= edge
        # the branch edge + its prompt's two
        assert st3["snapshots_taken"] == st2["snapshots_taken"] + 3
        # which a fourth restores
        await eng.generate(PREAMBLE + AGENT_FOUR, max_tokens=4, temperature=0.0)
        st3, st2 = eng.family_health()["ssm"], st3
        assert st3["prefix_tokens_usable"] - st2["prefix_tokens_usable"] == edge
        assert st3["layer_passes"]["ssm"] == st3["forward_passes"] * 2
        # the other slots' rows of the decode passes: the state-space layers'
        # step kernel passed them over (ISSUE 49), in both layers, and no more
        # rows than the chunk programs' passes had
        steps = st3["forward_passes"] - st3["eager_prefill_passes"]
        assert 0 < st3["decode_rows_still"] <= steps * st3["live_rows"] * 2
        assert st3["decode_rows_still"] % 2 == 0
        # and of the prologue windows, the window kernel's (ISSUE 54; under
        # ``gather`` a prompt prefills in eager pieces, whose words no chunk
        # brings to the host)
        moved, still = st3["window_rows_moved"], st3["window_rows_still"]
        assert moved % 2 == 0 and still % 2 == 0
        assert (moved > 0 and still > 0) if force_ragged else moved == still == 0
        eng._state.check()
        spans = eng.spans_health()
        assert spans["sched/state_restore"]["count"] == st3["restores"]
        assert spans["sched/state_snapshot"]["count"] == st3["snapshots_taken"]
        m = Metrics()
        m.observe_state_cache(eng.stats()["ssm"])
        text = m.render().decode()
        assert 'state_cache_events_total{event="restores"} %.1f' % st3["restores"] in text
        assert "state_prefix_tokens_total" in text and "state_bytes_moved_total" in text
        assert 'state_snapshots{state="capacity"}' in text
        assert ('state_snapshots{state="restore_depth_peak"} %.1f' % st3["restore_depth_peak"]
                in text) and st3["restore_depth_peak"] >= 1
    finally:
        await eng.stop()


async def test_preempt_and_replay_of_a_sequence_with_state(from_token_zero):
    """A background request is preempted mid-answer for an interactive one; its
    resume is seated from the nearest snapshot (its prompt's last block edge),
    replays what it had generated, and ends with the transcript of an
    uncontended run."""
    prompt = PREAMBLE + TURNS[0]
    base = _mk(batch_size=1, preempt_wait_ms=0.0)
    await base.start()
    want = (await base.generate(prompt, max_tokens=40, temperature=0.9, seed=7)).text
    await base.stop()
    eng = _mk(batch_size=1, preempt_wait_ms=15.0, preempt_budget=2)
    await eng.start()
    try:
        async def bulk():
            with use_qos(QoSContext(tenant="bulk", lane=LANE_BACKGROUND)):
                return await eng.generate(prompt, max_tokens=40, temperature=0.9, seed=7)

        task = asyncio.create_task(bulk())
        for _ in range(2000):
            await asyncio.sleep(0.005)
            if eng._slots[0] is not None and len(eng._slots[0].detok.ids) > 2:
                break
        with use_qos(QoSContext(tenant="quiet", lane=LANE_INTERACTIVE)):
            await eng.generate("quick question ", max_tokens=4, temperature=0.0)
        got = await task
        assert eng.stats()["qos"]["preemptions"] >= 1
        assert got.text == want
        assert eng.family_health()["ssm"]["restores"] >= 1
        eng._state.check()
    finally:
        await eng.stop()


@pytest.mark.parametrize("model", ["toy-hybrid-moe", "toy-linear-hybrid"])
async def test_forced_run_splice_never_feeds_the_state_a_token_twice(model):
    """Grammar fast-forward on against off, with chunks in flight: K/V rows are
    written by position, so a forced run spliced over chunks the device has
    already run only rewrites them; a recurrent state would take the run's
    tokens a second time (a Mamba-2 state and the gated delta rule's matrix
    alike). A state-keeping model splices only while none of the
    slot's decode chunks is in flight (the masked chunks force the same tokens),
    and the transcripts are the same byte for byte."""
    on = _mk(model=model, grammar_decode=True, grammar_forced_run_min=1, chunk_len=1)
    off = _mk(model=model, grammar_decode=True, grammar_forced_run_min=10 ** 6,
              chunk_len=1)
    seen = []
    splice = on._grammar_fast_forward

    def watched(idx, slot):
        before = on._grammar_ff_splices
        inflight = slot.decode_chunks_inflight
        run = on._grammar.forced_run(slot.gs, 64)[0] if slot.req.gpid >= 0 else []
        splice(idx, slot)
        seen.append((inflight, len(run), on._grammar_ff_splices - before))

    on._grammar_fast_forward = watched
    await on.start()
    await off.start()
    try:
        for prompt, temp, seed in [("list pods", 0.0, 3), ("restart web", 0.9, 99),
                                   ("scale the api", 0.9, 5), ("get svc", 0.9, 123)]:
            a = await on.generate(prompt, max_tokens=32, temperature=temp, seed=seed)
            b = await off.generate(prompt, max_tokens=32, temperature=temp, seed=seed)
            assert a.text == b.text, (prompt, temp)
        # the case arose: a forced run longer than the chunks in flight cover came
        # up, and was left to them; the admission's run was spliced
        assert any(n > 0 and run - n >= 1 for n, run, _ in seen)
        assert all(did == 0 for n, _, did in seen if n > 0)
        assert on.grammar_health()["fast_forward_splices_total"] >= 4
        assert off.grammar_health()["fast_forward_splices_total"] == 0
        on._state.check()
    finally:
        await asyncio.gather(on.stop(), off.stop())


async def test_family_is_refused_where_it_cannot_be_served():
    with pytest.raises(ValueError, match="keeps a recurrent state.*dense per-slot"):
        await _mk(kv_pool=False).start()
    with pytest.raises(ValueError, match="keeps a recurrent state.*SPEC_DECODE"):
        await _mk(spec_decode=True, spec_draft_model="toy-hybrid-moe").start()
    # every other model has no such cache
    plain = _mk(model="toy-8m")
    await plain.start()
    try:
        assert plain.stats()["ssm"] is None and plain._state is None
    finally:
        await plain.stop()


def test_a_live_slot_pins_the_deepest_two_snapshots_of_its_path():
    """ISSUE 45 (ROADMAP R8 c): a session's fourth turn descends from six
    snapshots; it pins the one it was seated from and the one before it, and
    the earlier turns' are the LRU's to evict while it is still live."""
    pool, store, radix = world(capacity=8, slots=2)
    ids = list(range(100, 110))
    for turn in range(3):
        blocks, _ = admit(pool, store, radix, 0, ids)
        finish(pool, store, radix, 0, ids, blocks)
        ids = ids + list(range(1000 * (turn + 1), 1000 * (turn + 1) + 9))
    assert store.held == 6
    live, m = admit(pool, store, radix, 0, ids)
    mr_path = sorted(store._edge[h] for h in store._node)
    assert m == 24 and mr_path == [4, 8, 12, 16, 20, 24]
    pinned = sorted(store._edge[h] for h in store._pins)
    assert pinned == [20, 24, 32, 36]           # two of the path, the two it took
    # another sequence's snapshots evict the session's EARLIER turns', not those
    other = list(range(500, 514))
    blocks, _ = admit(pool, store, radix, 1, other)
    assert store.snapshots_evicted == 2 and store.snapshots_skipped == 0
    assert sorted(store._edge[h] for h in store._node if store._edge[h] < 20) == [12, 16]
    finish(pool, store, radix, 1, other, blocks)
    finish(pool, store, radix, 0, ids, live)
