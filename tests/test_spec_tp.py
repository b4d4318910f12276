"""Speculative decoding under the TP mesh (ISSUE 18).

The acceptance spine: the draft world is mesh-native — draft params and
draft KV ride the serving mesh through the same f≈1 sharding policy as
the target (KV-head-sharded when the heads divide the ``model`` axis,
loudly gathered when they don't) — and NOTHING about the transcript may
show it. Spec-on-mesh equals spec-off-single-chip byte-for-byte at
temp 0 and seeded 0.9, at k∈{2,4}, on the 8-virtual-device CPU mesh
(conftest forces the device count). Around it: the draft:die flip on a
mesh degrades with ZERO recompiles (both program sets were compiled at
warmup) and zero failed requests, decode:nan mid-verify under tp
quarantines only the poisoned request while innocents replay
byte-identical and the books balance, the ``draft_sharded`` /
``draft_kv_fallback`` health fields and their fleet OR-rollup, the
step-time sentinel's spec_verify digests keyed under the mesh with
worst-replica merge attribution.
"""

import asyncio

import pytest

from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.obs.steptime import (PHASE_SPEC_VERIFY,
                                               StepTimeSentinel,
                                               merge_snapshots)
from ai_agent_kubectl_tpu.testing.faults import FaultInjector

PROMPTS = ["list pods", "get nodes -o wide", "describe deployment web"]
TEMPS = [0.0, 0.9, 0.9]
SEEDS = [7, 123, 5]


def _mk(mesh_shape: str = "", **over) -> BatchedJaxEngine:
    kw = dict(
        tokenizer=ByteTokenizer(),
        dtype="float32",
        max_seq_len=128,
        prefill_buckets=(32, 64),
        attn_impl="dense",
        prefix_cache=False,
        mesh_shape=mesh_shape,
        batch_size=4,
        chunk_len=4,
    )
    kw.update(over)
    return BatchedJaxEngine(get_config("toy-8m"), **kw)


def _mk_spec(mesh_shape: str, k: int = 2, **over) -> BatchedJaxEngine:
    return _mk(mesh_shape, spec_decode=True, spec_draft_k=k,
               spec_draft_model="toy-8m", spec_draft_seed=1234, **over)


def _books(eng) -> None:
    holders: dict = {}
    for slot in list(eng._slots) + list(eng._parked):
        if slot is not None and slot.blocks:
            for b in slot.blocks:
                holders[b] = holders.get(b, 0) + 1
    if eng._radix is not None:
        for b, n in eng._radix._held.items():
            holders[b] = holders.get(b, 0) + n
    eng._pool.check(holders)


async def _serve(eng) -> list:
    outs = await asyncio.gather(*[
        eng.generate(p, max_tokens=16, temperature=t, seed=s)
        for p, t, s in zip(PROMPTS, TEMPS, SEEDS)
    ])
    return [r.text for r in outs]


# --------------------------------------------- byte-identity on the mesh
#
# The engine-building tests are slow-marked: each one compiles BOTH the
# plain and spec program sets against a virtual mesh (~20-70 s apiece on
# the CPU backend), and the tier-1 gate (-m 'not slow') runs close to
# its wall-clock budget already. The CI "Spec x TP parity smoke" step
# runs this file with NO marker filter, so every one of them still
# gates every CI run.


@pytest.mark.slow
async def test_spec_tp_byte_identity_and_health_flags():
    """THE acceptance test: spec-on under a tp mesh vs spec-off on a
    single device — one comparison pins both claims (mesh-vs-single AND
    spec-on-vs-off) at temp 0 and seeded 0.9. tp=2 shards the toy
    draft's 2 KV heads (no fallback); tp=8 can't divide them, so the
    draft KV gathers — loudly flagged, still byte-identical."""
    off = _mk()
    await off.start()
    engines = [off]
    try:
        ref = await _serve(off)
        for mesh, k, want_fallback in (("tp=2", 2, False),
                                       ("tp=2", 4, False),
                                       ("tp=8", 4, True)):
            on = _mk_spec(mesh, k)
            on.tokenizer = off.tokenizer
            await on.start()
            engines.append(on)
            assert on._use_spec, (mesh, k)
            sh = on.sharding_health()
            assert sh["draft_sharded"] is True
            assert sh["draft_kv_fallback"] is want_fallback, (mesh, k)
            h0 = on.spec_health()
            assert h0["draft_sharded"] is True
            assert h0["draft_kv_fallback"] is want_fallback
            # The draft cache is genuinely placed on the mesh.
            devs = int(mesh.split("=")[1])
            assert len(on._draft_cache.k.sharding.device_set) == devs
            assert await _serve(on) == ref, (mesh, k)
            h = on.spec_health()
            assert h["drafted_tokens_total"] > 0, (mesh, k)
            _books(on)
            assert on.ledger_snapshot()["conservation"]["balanced"]
    finally:
        await asyncio.gather(*[e.stop() for e in engines])


@pytest.mark.slow
async def test_spec_tp_sentinel_keys_spec_verify_under_mesh():
    """The step-time sentinel keys spec chunks as spec_verify (not
    decode) while serving under the mesh — the digest the PR-15 gate
    and the PERF_BASELINES spec_verify envelope watch."""
    eng = _mk_spec("tp=2", 2)
    await eng.start()
    try:
        for _ in range(3):          # keep the pipe busy enough to sample
            await _serve(eng)
        digests = eng.steptime_health()["digests"]
        spec_keys = [key for key in digests
                     if key.startswith("spec_verify/")]
        assert spec_keys, digests.keys()
        assert digests[spec_keys[0]]["count"] > 0
    finally:
        await eng.stop()


def test_merge_snapshots_attributes_spec_verify_straggler():
    """Worst-replica merge attribution applies to spec_verify digests
    exactly as to decode: the straggler's replica index lands on the
    merged digest and on every breach."""
    fast = StepTimeSentinel(min_samples=4)
    slow = StepTimeSentinel(min_samples=4)
    for _ in range(8):
        fast.note(PHASE_SPEC_VERIFY, 128, 0.0001, steps=1, tokens=4)
        slow.note(PHASE_SPEC_VERIFY, 128, 0.0001, steps=1, tokens=4)
    for _ in range(8):
        slow.note(PHASE_SPEC_VERIFY, 128, 0.050, steps=1, tokens=4)
    merged = merge_snapshots([fast.snapshot(), slow.snapshot()])
    d = merged["digests"]["spec_verify/128"]
    assert d["worst_replica"] == 1 and d["count"] == 24
    assert merged["breaches"] and all(
        b["replica"] == 1 and b["phase"] == "spec_verify"
        for b in merged["breaches"])


# ------------------------------------------------- faults under the mesh


@pytest.mark.slow
async def test_spec_tp_draft_die_zero_recompiles_zero_failures():
    """draft:die while serving on a tp=2 mesh: the flip to plain decode
    reuses the program set compiled at warmup — the jitted-fn dicts are
    untouched, no request fails, and transcripts before/after stay
    byte-identical to a single-device spec-off engine."""
    inj = FaultInjector()
    inj.set("draft", "die")
    on = _mk_spec("tp=2", 2, faults=inj)
    off = _mk()
    await on.start()
    off.tokenizer = on.tokenizer
    await off.start()
    try:
        # Warmup compiled BOTH program sets; snapshot their identities.
        spec_fns = dict(on._spec_chunk_fns)
        plain_fns = dict(on._batch_chunk_fns)
        assert spec_fns and plain_fns

        a = await on.generate("during drill", max_tokens=20,
                              temperature=0.9, seed=3)
        b = await off.generate("during drill", max_tokens=20,
                               temperature=0.9, seed=3)
        assert a.text == b.text
        assert inj.fired("draft") == 1
        h = on.spec_health()
        assert not h["active"] and h["degraded_total"] == 1
        assert h["draft_sharded"] is True   # sharding survives the flip

        c = await on.generate("after drill", max_tokens=12,
                              temperature=0.0)
        d = await off.generate("after drill", max_tokens=12,
                               temperature=0.0)
        assert c.text == d.text

        # Zero recompiles: same keys, same jitted-fn objects.
        assert on._spec_chunk_fns.keys() == spec_fns.keys()
        assert on._batch_chunk_fns.keys() == plain_fns.keys()
        assert all(on._spec_chunk_fns[key] is fn
                   for key, fn in spec_fns.items())
        assert all(on._batch_chunk_fns[key] is fn
                   for key, fn in plain_fns.items())
    finally:
        await asyncio.gather(on.stop(), off.stop())


@pytest.mark.slow
async def test_spec_tp_nan_containment_replay_byte_identity():
    """decode:nan mid-verify under tp=2: the poisoned request
    quarantines, innocents replay — through the sharded draft-cache
    re-prefill path — and finish byte-identical to an undisturbed
    single-device spec-off run; books and ledger balance after."""
    from ai_agent_kubectl_tpu.engine.protocol import RequestQuarantined

    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison"
    on = _mk_spec("tp=2", 2, faults=inj, quarantine_retry_budget=0)
    off = _mk()
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
        _books(on)
        assert on.ledger_snapshot()["conservation"]["balanced"]
    finally:
        await asyncio.gather(on.stop(), off.stop())


# --------------------------------------------------------- fleet rollup


def test_fleet_ors_draft_kv_fallback():
    """ANY replica serving the draft KV gathered must surface at the
    fleet level — same rule as the pool's loud fallback — on BOTH the
    sharding and spec rollups."""
    from ai_agent_kubectl_tpu.engine.fleet import EngineFleet

    class _Eng:
        def __init__(self, fallback):
            self._f = fallback

        def sharding_health(self):
            return {"devices": 8, "pool_sharded": True,
                    "kv_pool_mesh_fallback": False,
                    "draft_sharded": True,
                    "draft_kv_fallback": self._f}

        def spec_health(self):
            return {"enabled": True, "active": True,
                    "drafted_tokens_total": 10,
                    "accepted_tokens_total": 5,
                    "draft_sharded": True,
                    "draft_kv_fallback": self._f}

    class _Rep:
        def __init__(self, eng):
            self.engine = eng

    fleet = EngineFleet.__new__(EngineFleet)
    fleet.replicas = [_Rep(_Eng(False)), _Rep(_Eng(True))]
    assert fleet.sharding_health()["draft_kv_fallback"] is True
    assert fleet.spec_health()["draft_kv_fallback"] is True

    fleet.replicas = [_Rep(_Eng(False)), _Rep(_Eng(False))]
    assert fleet.sharding_health()["draft_kv_fallback"] is False
    assert fleet.spec_health()["draft_kv_fallback"] is False
