"""Speculative decoding (ISSUE 12): 2B drafts, 7B verifies inside the
packed chunk.

The acceptance bar IS byte-identity: exact-match verification samples
every position from the TARGET's own logits under the per-request seed
stream, so the transcript can never depend on the drafts — spec-on
output equals spec-off output at any k, including k=0. The fake's
two-model twin (a deterministic draft-miss oracle over the scripted
stream) runs the accept/reject machinery, the packed v3 lanes, the
draft_rejected ledger billing, and the draft:die degradation in
milliseconds; the jax tests at the bottom pin the real engine's parity
claims at temp 0 AND seeded 0.9, with a genuinely-disagreeing draft
model (different random init) and with an identical one (acceptance
actually fires).
"""

import asyncio

import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.protocol import (
    pack_chunk, packed_chunk_size, unpack_chunk)
from ai_agent_kubectl_tpu.obs.ledger import (CLASS_DRAFT_REJECTED,
                                             LEDGER_CLASSES)
from ai_agent_kubectl_tpu.testing.faults import FaultInjector


# ------------------------------------------------- packed contract (v3)


def test_packed_chunk_v3_roundtrip():
    """The two spec lanes ride the packed buffer only when asked for,
    travel together, and round-trip exactly."""
    n, c = 3, 4
    toks = np.arange(n * c, dtype=np.int32).reshape(n, c)
    done = np.array([True, False, True])
    lengths = np.array([4, 2, 1], np.int32)
    health = np.array([0, 0, 2], np.int32)
    drafted = np.array([6, 3, 0], np.int32)
    accepted = np.array([5, 0, 0], np.int32)
    buf = pack_chunk(toks, done, lengths, 1, health=health,
                     drafted=drafted, accepted=accepted)
    assert buf.shape == (packed_chunk_size(n, c, spec=True),)
    res = unpack_chunk(buf, n, c, spec=True)
    assert (res.tokens == toks).all()
    assert (res.done == done).all()
    assert (res.lengths == lengths).all()
    assert (res.health == health).all()
    assert (res.drafted == drafted).all()
    assert (res.accepted == accepted).all()
    assert res.n_alive == 1
    # Plain buffers stay plain (and are smaller).
    plain = pack_chunk(toks, done, lengths, 1, health=health)
    assert plain.shape == (packed_chunk_size(n, c),)
    assert unpack_chunk(plain, n, c).drafted is None
    # The lanes travel together or not at all.
    with pytest.raises(ValueError):
        pack_chunk(toks, done, lengths, 1, drafted=drafted)
    # A spec buffer read with the wrong layout fails loudly.
    with pytest.raises(ValueError):
        unpack_chunk(buf, n, c)


def test_draft_rejected_is_a_ledger_class():
    assert CLASS_DRAFT_REJECTED in LEDGER_CLASSES
    assert LEDGER_CLASSES[0] == "delivered"   # goodput first, always


# ------------------------------------------------------ fake 2-model twin


def mk_fake(**kw):
    kw.setdefault("spec_decode", True)
    kw.setdefault("spec_draft_k", 3)
    kw.setdefault("spec_fake_miss", 3)
    return FakeChunkedEngine(**kw)


async def test_fake_spec_on_off_byte_identity():
    """Spec on vs off transcripts are byte-identical across prompt
    shapes and draft depths — including k > chunk_len, where one verify
    window is wider than a plain chunk."""
    for k, chunk_len in ((1, 4), (3, 4), (8, 4)):
        on = mk_fake(spec_draft_k=k, chunk_len=chunk_len)
        off = FakeChunkedEngine(chunk_len=chunk_len)
        await on.start()
        await off.start()
        try:
            for prompt in ("list pods", "scale web to 3",
                           "describe node abc", "x"):
                a = await on.generate(prompt, max_tokens=20)
                b = await off.generate(prompt, max_tokens=20)
                assert a.text == b.text, (k, chunk_len, prompt)
                assert a.finish_reason == b.finish_reason
        finally:
            await asyncio.gather(on.stop(), off.stop())


async def test_fake_acceptance_accounting_and_ledger():
    """Acceptance counters and the draft_rejected waste class: with the
    miss oracle every ~3rd draft is wrong, so acceptance lands strictly
    between 0 and 1, rejected == drafted - accepted lands in the
    ledger, and conservation still balances exactly."""
    eng = mk_fake(spec_fake_miss=3)
    await eng.start()
    try:
        for i in range(4):
            await eng.generate(f"query number {i}", max_tokens=24)
        h = eng.spec_health()
        assert h["enabled"] and h["active"]
        assert h["drafted_tokens_total"] > 0
        assert 0 < h["accepted_tokens_total"] < h["drafted_tokens_total"]
        assert 0.0 < h["acceptance_ratio"] < 1.0
        snap = eng.ledger_snapshot()
        assert snap["classes"][CLASS_DRAFT_REJECTED] == (
            h["drafted_tokens_total"] - h["accepted_tokens_total"])
        assert snap["conservation"]["balanced"]
    finally:
        await eng.stop()


async def test_fake_perfect_draft_accepts_everything():
    """spec_fake_miss=0 = an oracle draft: every proposal with a live
    position accepts — only the terminal window's overhang (drafts past
    EOS/budget, which had nothing left to buy) bills as rejected — and
    transcripts are still the scripted stream."""
    on = mk_fake(spec_fake_miss=0)
    off = FakeChunkedEngine()
    await on.start()
    await off.start()
    try:
        a = await on.generate("perfect draft", max_tokens=20)
        b = await off.generate("perfect draft", max_tokens=20)
        assert a.text == b.text
        h = on.spec_health()
        assert h["acceptance_ratio"] >= 0.85
        assert on.ledger_snapshot()["classes"][CLASS_DRAFT_REJECTED] == (
            h["drafted_tokens_total"] - h["accepted_tokens_total"])
    finally:
        await asyncio.gather(on.stop(), off.stop())


def _assert_books(eng: FakeChunkedEngine) -> None:
    """Pool balance: holder count = slot tables + radix references (the
    kv-pool suite's leak invariant, re-run after spec verify/rollback
    traffic)."""
    holders: dict = {}
    for slot in list(eng._slots) + list(eng._parked):
        if slot is not None:
            for b in slot.blocks:
                holders[b] = holders.get(b, 0) + 1
    if eng._radix is not None:
        for b, n in eng._radix._held.items():
            holders[b] = holders.get(b, 0) + n
    eng._pool.check(holders)


async def test_fake_books_balance_under_decode_nan_mid_verify():
    """A decode:nan drill lands MID-VERIFY (the health trip fires inside
    a speculative chunk): the target quarantines, innocents replay
    byte-identically, the pool books check exactly after rollback, and
    the ledger — draft_rejected included — still balances."""
    from ai_agent_kubectl_tpu.engine.protocol import RequestQuarantined

    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison"
    eng = mk_fake(batch_size=4, chunk_len=4, kv_pool_page=4, faults=inj,
                  quarantine_retry_budget=0)
    ref = FakeChunkedEngine(batch_size=4, chunk_len=4, kv_pool_page=4)
    await eng.start()
    await ref.start()
    try:
        async def one(prompt, expect_quarantine=False):
            try:
                r = await eng.generate(prompt, max_tokens=24)
                assert not expect_quarantine
                return r.text
            except RequestQuarantined:
                assert expect_quarantine
                return None

        results = await asyncio.gather(
            one("poison me", expect_quarantine=True),
            one("innocent a"), one("innocent b"), one("innocent c"))
        for prompt, text in zip(("innocent a", "innocent b",
                                 "innocent c"), results[1:]):
            r = await ref.generate(prompt, max_tokens=24)
            assert text == r.text, prompt   # replay byte-identity
        for _ in range(200):
            if all(s is None for s in eng._slots) and not eng._queue:
                break
            await asyncio.sleep(0.01)
        _assert_books(eng)
        assert eng.ledger.conservation()["balanced"]
        assert eng.stats()["containment"]["quarantined"]
    finally:
        await asyncio.gather(eng.stop(), ref.stop())


async def test_fake_draft_die_degrades_to_plain_decode():
    """draft:die mid-serving: the engine flips to plain decode without
    failing anything — the in-flight request completes byte-identical
    to spec-off, later requests keep serving, and /health shows the
    degradation."""
    inj = FaultInjector()
    inj.set("draft", "die")
    eng = mk_fake(faults=inj)
    off = FakeChunkedEngine()
    await eng.start()
    await off.start()
    try:
        a = await eng.generate("during the drill", max_tokens=24)
        b = await off.generate("during the drill", max_tokens=24)
        assert a.text == b.text
        assert inj.fired("draft") == 1
        h = eng.spec_health()
        assert h["enabled"] and not h["active"]
        assert h["degraded_total"] == 1
        # Still serving — just plain decode now (no new drafting).
        drafted0 = h["drafted_tokens_total"]
        c = await eng.generate("after the drill", max_tokens=24)
        d = await off.generate("after the drill", max_tokens=24)
        assert c.text == d.text
        assert eng.spec_health()["drafted_tokens_total"] == drafted0
    finally:
        await asyncio.gather(eng.stop(), off.stop())


async def test_fake_spec_composes_with_grammar():
    """Grammar + spec together: transcripts equal the grammar-only
    engine's (the verify fold runs the same per-position grammar
    stepping), output stays in-grammar, and the books balance."""
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    ids = tok.encode("kubectl get pods -n kube-system", add_bos=False) \
        + [tok.eos_ids[0]]
    sf = lambda prompt: list(ids)   # noqa: E731
    on = mk_fake(grammar_decode=True, grammar_forced_run_min=2,
                 stream_fn=sf)
    off = FakeChunkedEngine(grammar_decode=True, grammar_forced_run_min=2,
                            stream_fn=sf)
    await on.start()
    await off.start()
    try:
        a = await on.generate("q", max_tokens=64)
        b = await off.generate("q", max_tokens=64)
        assert a.text == b.text == "kubectl get pods -n kube-system"
        _assert_books(on)
    finally:
        await asyncio.gather(on.stop(), off.stop())


# ------------------------------------------------- validation + surfaces


def test_engine_constructors_validate_spec_knobs():
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    with pytest.raises(ValueError):
        FakeChunkedEngine(spec_decode=True, device_termination=False)
    with pytest.raises(ValueError):
        FakeChunkedEngine(spec_decode=True, spec_draft_k=0)
    with pytest.raises(ValueError):
        BatchedJaxEngine(get_config("toy-8m"), spec_decode=True,
                         device_termination=False)
    with pytest.raises(ValueError):
        BatchedJaxEngine(get_config("toy-8m"), spec_decode=True,
                         spec_draft_k=0)


def test_config_validates_spec_knobs():
    from ai_agent_kubectl_tpu.config import ServiceConfig

    with pytest.raises(ValueError):
        ServiceConfig(spec_decode=True, device_termination=False)
    with pytest.raises(ValueError):
        ServiceConfig(spec_decode=True, spec_draft_k=0)
    with pytest.raises(ValueError):
        ServiceConfig(spec_decode=True, spec_draft_model="no-such-model")
    with pytest.raises(ValueError):
        # toy-8m (vocab 512) cannot be drafted by gemma-2b (vocab 256k).
        ServiceConfig(spec_decode=True, model_name="toy-8m",
                      spec_draft_model="gemma-2b-it")
    cfg = ServiceConfig(spec_decode=True, model_name="gemma-7b-it",
                        spec_draft_model="gemma-2b-it", spec_draft_k=8)
    assert cfg.spec_draft_k == 8
    # Off by default, and off means no constraint coupling.
    assert not ServiceConfig().spec_decode


async def test_health_and_metrics_expose_spec():
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    cfg = ServiceConfig(engine="fake", model_name="fake")
    engine = mk_fake()
    app = create_app(cfg, engine, executor=CommandExecutor(timeout=1.0))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await engine.start()
        await engine.generate("q", max_tokens=24)
        h = await client.get("/health")
        body = await h.json()
        assert body["spec"] is not None
        assert body["spec"]["k"] == 3
        assert body["spec"]["active"] is True
        assert body["spec"]["drafted_tokens_total"] > 0
        assert body["spec"]["acceptance_ratio"] is not None
        m = await client.get("/metrics")
        text = await m.text()
        assert "spec_drafted_tokens_total" in text
        assert "spec_accepted_tokens_total" in text
        assert "spec_acceptance_ratio" in text
        assert 'class="draft_rejected"' in text
        # No spec section on a spec-off engine.
        off = FakeChunkedEngine()
        assert off.spec_health() is None
        assert off.stats()["spec"] is None
    finally:
        await engine.stop()
        await client.close()


def test_draft_die_fault_spec_parses():
    inj = FaultInjector.from_spec("draft:die")
    assert inj.has("draft")
    assert inj.draft_die() is True
    assert inj.draft_die() is False      # one-shot
    assert inj.fired("draft") == 1
    with pytest.raises(ValueError):
        FaultInjector.from_spec("draft:nan")    # die is the only mode
    # Replica-scoped drills stay scoped (fleet view plumbing).
    inj2 = FaultInjector.from_spec("r1:draft:die")
    assert not inj2.for_replica(0).draft_die()
    assert inj2.for_replica(1).draft_die()


# ------------------------------------------------------------ jax engine


def _mk_jax(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    defaults = dict(dtype="float32", max_seq_len=192,
                    prefill_buckets=(32, 64), prefix_cache=False,
                    batch_size=4, chunk_len=4)
    defaults.update(kw)
    return BatchedJaxEngine(get_config("toy-8m"), **defaults)


def _jax_books(eng) -> None:
    holders: dict = {}
    for slot in list(eng._slots) + list(eng._parked):
        if slot is not None and slot.blocks:
            for b in slot.blocks:
                holders[b] = holders.get(b, 0) + 1
    if eng._radix is not None:
        for b, n in eng._radix._held.items():
            holders[b] = holders.get(b, 0) + n
    eng._pool.check(holders)


async def test_jax_spec_on_off_byte_identity():
    """THE acceptance test: a draft model that genuinely disagrees with
    the target (different random init) changes NOTHING about the
    transcript — byte-identical to spec-off at temp 0 AND seeded 0.9,
    across k — while the acceptance counters record the disagreement
    and the pool books stay balanced."""
    off = _mk_jax()
    await off.start()
    engines = [off]
    try:
        for k in (2, 4):
            on = _mk_jax(spec_decode=True, spec_draft_k=k,
                         spec_draft_model="toy-8m", spec_draft_seed=1234)
            on.tokenizer = off.tokenizer
            await on.start()
            engines.append(on)
            for prompt, temp, seed in [("list pods", 0.0, 7),
                                       ("scale web", 0.9, 123),
                                       ("get svc please", 0.9, 5)]:
                a = await on.generate(prompt, max_tokens=24,
                                      temperature=temp, seed=seed)
                b = await off.generate(prompt, max_tokens=24,
                                       temperature=temp, seed=seed)
                assert a.text == b.text, (k, prompt, temp)
            h = on.spec_health()
            assert h["drafted_tokens_total"] > 0
            _jax_books(on)
            assert on.ledger_snapshot()["conservation"]["balanced"]
    finally:
        await asyncio.gather(*[e.stop() for e in engines])


async def test_jax_spec_identical_draft_accepts():
    """With draft == target weights the greedy path must actually
    ACCEPT (the multiplicative win exists): acceptance well above zero
    at temp 0, and the transcript still byte-identical to spec-off."""
    on = _mk_jax(spec_decode=True, spec_draft_k=3, chunk_len=8,
                 spec_draft_model="toy-8m", spec_draft_seed=0)
    off = _mk_jax(chunk_len=8)
    await on.start()
    off.tokenizer = on.tokenizer
    await off.start()
    try:
        for prompt in ("list pods", "get nodes"):
            a = await on.generate(prompt, max_tokens=24, temperature=0.0)
            b = await off.generate(prompt, max_tokens=24, temperature=0.0)
            assert a.text == b.text, prompt
        h = on.spec_health()
        assert h["accepted_tokens_total"] > 0
        # Random-toy logits are near-ties, so cross-layout ULPs cost a
        # few argmax flips; a real draft/target pair does better. The
        # bar here is "the accept path fires", not a rate claim.
        assert h["acceptance_ratio"] > 0.3
    finally:
        await asyncio.gather(on.stop(), off.stop())


async def test_jax_draft_die_degrades_and_replays_clean():
    """draft:die on the real engine: serving continues as plain decode
    (byte-identical — nothing ever depended on the drafts), the spec
    section reports the degradation, and later traffic still works."""
    inj = FaultInjector()
    inj.set("draft", "die")
    on = _mk_jax(spec_decode=True, spec_draft_k=2,
                 spec_draft_model="toy-8m", spec_draft_seed=99,
                 faults=inj)
    off = _mk_jax()
    await on.start()
    off.tokenizer = on.tokenizer
    await off.start()
    try:
        a = await on.generate("during drill", max_tokens=20,
                              temperature=0.9, seed=3)
        b = await off.generate("during drill", max_tokens=20,
                               temperature=0.9, seed=3)
        assert a.text == b.text
        assert inj.fired("draft") == 1
        h = on.spec_health()
        assert not h["active"] and h["degraded_total"] == 1
        c = await on.generate("after drill", max_tokens=12,
                              temperature=0.0)
        d = await off.generate("after drill", max_tokens=12,
                               temperature=0.0)
        assert c.text == d.text
    finally:
        await asyncio.gather(on.stop(), off.stop())


async def test_jax_spec_containment_replay_byte_identity():
    """decode:nan mid-verify on the real engine: the targeted request
    quarantines, innocents replay — through the draft-cache re-prefill
    path — and finish byte-identical to an undisturbed spec-off run;
    books and ledger balance after the storm."""
    from ai_agent_kubectl_tpu.engine.protocol import RequestQuarantined

    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison"
    on = _mk_jax(spec_decode=True, spec_draft_k=2,
                 spec_draft_model="toy-8m", spec_draft_seed=7,
                 faults=inj, quarantine_retry_budget=0)
    off = _mk_jax()
    await on.start()
    off.tokenizer = on.tokenizer
    await off.start()
    try:
        async def one(prompt, temp, seed, expect_quarantine=False):
            try:
                r = await on.generate(prompt, max_tokens=16,
                                      temperature=temp, seed=seed)
                assert not expect_quarantine
                return r.text
            except RequestQuarantined:
                assert expect_quarantine
                return None

        texts = await asyncio.gather(
            one("poison me", 0.0, 1, expect_quarantine=True),
            one("innocent a", 0.0, 2), one("innocent b", 0.9, 3))
        for (prompt, temp, seed), text in zip(
                [("innocent a", 0.0, 2), ("innocent b", 0.9, 3)],
                texts[1:]):
            r = await off.generate(prompt, max_tokens=16,
                                   temperature=temp, seed=seed)
            assert text == r.text, prompt
        _jax_books(on)
        assert on.ledger_snapshot()["conservation"]["balanced"]
    finally:
        await asyncio.gather(on.stop(), off.stop())
