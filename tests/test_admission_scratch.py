"""Suffix-depth admission scratch (ISSUE 3): group admissions prefill
into kv_limit-deep scratch (not S_alloc), capped by ADMIT_SCRATCH_MB and
serialized against the background warm — and must stay byte-identical to
the single-admission path."""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.ops.quant import QuantKV, kv_set_slots


# ------------------------------------------------- kv_set_slots depth-aware

def test_kv_set_slots_shallow_src_writes_prefix_only():
    """A src shallower on the sequence axis writes exactly its depth; the
    destination's tail and other slots are untouched; OOB rows drop."""
    rng = np.random.default_rng(0)
    dst = jnp.asarray(rng.normal(size=(2, 4, 8, 3, 5)).astype(np.float32))
    src = jnp.asarray(rng.normal(size=(2, 2, 5, 3, 5)).astype(np.float32))
    slots = jnp.asarray([1, 4], jnp.int32)          # slot 4 is OOB -> drop
    out = np.asarray(kv_set_slots(dst, src, slots))

    expect = np.asarray(dst).copy()
    expect[:, 1, :5] = np.asarray(src)[:, 0]
    np.testing.assert_array_equal(out, expect)
    # Stale tail beyond src depth survives (never read by the causal mask).
    np.testing.assert_array_equal(out[:, 1, 5:], np.asarray(dst)[:, 1, 5:])


def test_kv_set_slots_shallow_quantkv():
    """QuantKV leaves (int8 payload [..., hd] + scale [..., heads]) both
    follow the sequence-axis prefix write."""
    rng = np.random.default_rng(1)
    dst = QuantKV(
        q=jnp.asarray(rng.integers(-127, 127, (2, 3, 8, 2, 4), np.int8)),
        s=jnp.asarray(rng.normal(size=(2, 3, 8, 2)).astype(np.float32)))
    src = QuantKV(
        q=jnp.asarray(rng.integers(-127, 127, (2, 1, 6, 2, 4), np.int8)),
        s=jnp.asarray(rng.normal(size=(2, 1, 6, 2)).astype(np.float32)))
    out = kv_set_slots(dst, src, jnp.asarray([2], jnp.int32))
    np.testing.assert_array_equal(np.asarray(out.q)[:, 2, :6],
                                  np.asarray(src.q)[:, 0])
    np.testing.assert_array_equal(np.asarray(out.s)[:, 2, :6],
                                  np.asarray(src.s)[:, 0])
    np.testing.assert_array_equal(np.asarray(out.q)[:, 0],
                                  np.asarray(dst.q)[:, 0])
    np.testing.assert_array_equal(np.asarray(out.s)[:, 2, 6:],
                                  np.asarray(dst.s)[:, 2, 6:])


def test_kv_set_slots_full_depth_unchanged():
    """Equal-depth src keeps the original full-slot semantics."""
    dst = jnp.zeros((1, 2, 4, 1, 2))
    src = jnp.ones((1, 1, 4, 1, 2))
    out = np.asarray(kv_set_slots(dst, src, jnp.asarray([0], jnp.int32)))
    np.testing.assert_array_equal(out[:, 0], np.ones((1, 4, 1, 2)))
    np.testing.assert_array_equal(out[:, 1], np.zeros((1, 4, 1, 2)))


# --------------------------------------------------- scratch budget capping

def _mk(**kw):
    # Buckets chosen for tier-1 speed: the byte-tokenized system prompt
    # (273 tokens) fits ONE 512 prefill (no chunked prefix build), and
    # 512-bucket suffixes exceed max_seq so the background warm has no
    # extra suffix shapes to compile; the group path runs on bucket 64
    # (kv_limit 384 — warmed eagerly at startup).
    defaults = dict(
        dtype="float32",
        max_seq_len=512,
        prefill_buckets=(64, 512),
        batch_size=4,
        chunk_len=4,
        # The dense group-admission scratch is what this suite tests;
        # pool mode retires that machinery (suffixes prefill straight
        # into blocks — ISSUE 10, covered by tests/test_kv_pool.py).
        kv_pool=False,
    )
    defaults.update(kw)
    return BatchedJaxEngine(get_config("toy-8m"), **defaults)


def test_admit_scratch_budget_caps_kpads():
    """Cap math without engine starts: a tiny ADMIT_SCRATCH_MB disables
    group sizes whose scratch rows exceed it; 0 keeps every structural
    kpad (no caps map at all)."""
    eng = _mk(admit_scratch_mb=0)
    eng._cap_admit_kpads([128, 384])
    assert eng._admit_kpad_caps == {}            # 0 = uncapped
    assert eng.admit_kpads_for(384) == eng.admit_kpads

    tiny = _mk(admit_scratch_mb=1)               # rows are ~100s of KB
    tiny._cap_admit_kpads([128, 384])
    for depth, cap in tiny._admit_kpad_caps.items():
        assert cap * tiny._scratch_row_bytes(depth) <= 1_000_000
    assert tiny.admit_kpads_for(384) <= tiny.admit_kpads


@pytest.mark.slow
async def test_tiny_scratch_budget_still_serves():
    """With a budget that forbids every group size, bursts fall back to
    single admissions and still serve. (slow-marked: one extra engine
    start; the fallback path itself is also exercised whenever the warm
    thread holds the scratch lock in the parity test.)"""
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    eng = _mk(admit_scratch_mb=1)
    await eng.start()
    try:
        assert eng._prefix is not None
        rs = await asyncio.gather(*[
            eng.generate(render_prompt(f"get pods {i}"), max_tokens=4,
                         temperature=0.0) for i in range(4)])
        assert all(r.completion_tokens > 0 for r in rs)
    finally:
        await eng.stop()


def test_scratch_row_bytes_geometry():
    """The budget math matches the actual scratch allocation, int8 KV and
    model dtype."""
    eng = _mk()
    cfg = eng.model_cfg
    depth = 100
    assert eng._scratch_row_bytes(depth) == (
        2 * cfg.n_layers * depth * cfg.n_kv_heads * cfg.head_dim * 4)
    eng8 = _mk(kv_quant="int8")
    assert eng8._scratch_row_bytes(depth) == (
        2 * cfg.n_layers * depth * cfg.n_kv_heads * (cfg.head_dim + 4))


# ---------------------------------------------- group-vs-single parity (e2e)

async def test_group_admission_parity_with_singles(monkeypatch):
    """Group admissions through the SHRUNKEN suffix-depth scratch must
    produce the same greedy tokens as the single-admission path, and the
    KV-pool gauges must be unchanged by the scratch change (ISSUE 3
    satellite). Two engines, same seed/config: one with the group path,
    one with it structurally disabled. int8 KV on purpose — QuantKV's
    scale leaf takes the depth-aware write too (the plain-dtype path is
    pinned by the unit tests above and the suffix-depth spy below)."""
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    grouped = _mk(kv_quant="int8")
    single = _mk(kv_quant="int8")
    single.ADMIT_KPADS = ()          # instance override: no group path
    await grouped.start()
    await single.start()
    try:
        assert grouped._prefix is not None and single._prefix is not None
        # Let the background admission warm finish: it holds the scratch
        # lock (groups would fall back to singles) and the test needs the
        # group path to actually run.
        grouped._batch_warm_thread.join(120.0)
        # Spy on scratch allocations: the group path must allocate at
        # kv_limit depth, never S_alloc — the whole point of ISSUE 3.
        depths = []
        orig = grouped._new_cache

        def spy(batch, max_seq=None):
            depths.append((batch, max_seq))
            return orig(batch, max_seq)

        monkeypatch.setattr(grouped, "_new_cache", spy)
        prompts = [render_prompt(f"list pods in namespace team-{i}")
                   for i in range(4)]
        g0 = grouped._group_admitted
        res_g = await asyncio.gather(*[
            grouped.generate(p, max_tokens=12, temperature=0.0)
            for p in prompts])
        res_s = await asyncio.gather(*[
            single.generate(p, max_tokens=12, temperature=0.0)
            for p in prompts])
        assert grouped._group_admitted > g0, \
            "burst did not exercise the group-admission path"
        assert single._group_admitted == 0
        assert all(r.prefix_cache_hit for r in res_g + res_s)
        assert [r.text for r in res_g] == [r.text for r in res_s]
        group_allocs = [d for b, d in depths if b > 1]
        assert group_allocs, "no group-admission scratch was allocated"
        assert all(d is not None and d < grouped._S_alloc
                   for d in group_allocs)
        # KV-pool accounting is about SLOTS, not scratch: identical gauges.
        sg, ss = grouped.stats(), single.stats()
        assert sg["kv_pages_total"] == ss["kv_pages_total"]
        assert sg["kv_pages_used"] == ss["kv_pages_used"] == 0  # all freed
    finally:
        await grouped.stop()
        await single.stop()
