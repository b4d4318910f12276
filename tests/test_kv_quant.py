"""int8 KV cache (KV_QUANT=int8, ops/quant.py::QuantKV).

The reference has no KV cache at all (the forward pass is a remote call,
/root/reference/app.py:184); int8 KV is a build-side capacity lever — it
halves the decode KV pool, which is what caps batch size on HBM-bound
single-chip 7B serving. Tests: quantization error
bounds, cache structure, and greedy serving parity against the
full-precision KV path on the toy model.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import KVCache
from ai_agent_kubectl_tpu.ops.quant import (QuantKV, kv_dequantize,
                                            kv_quantize, kv_tokens)


def test_kv_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 2, 64),
                          dtype=jnp.float32)
    q = kv_quantize(x)
    assert q.q.dtype == jnp.int8 and q.q.shape == x.shape
    assert q.s.shape == x.shape[:-1]
    back = kv_dequantize(q, jnp.float32)
    # Symmetric int8 over each head vector: error <= amax/254 per element.
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    assert np.all(np.abs(np.asarray(back) - np.asarray(x))
                  <= amax / 254.0 + 1e-7)


def test_kv_quantize_zero_vector_is_exact():
    x = jnp.zeros((2, 3, 1, 8), jnp.float32)
    q = kv_quantize(x)
    assert np.all(np.asarray(q.q) == 0)
    assert np.all(np.asarray(kv_dequantize(q)) == 0)


def test_zeros_builds_quantkv_structure():
    cfg = get_config("toy-8m")
    cache = KVCache.zeros(cfg, batch=3, max_seq=32, kv_quant="int8")
    assert isinstance(cache.k, QuantKV) and isinstance(cache.v, QuantKV)
    assert cache.k.q.shape == (cfg.n_layers, 3, 32, cfg.n_kv_heads,
                               cfg.head_dim)
    assert cache.k.s.shape == cache.k.q.shape[:-1]
    assert cache.max_seq == 32
    assert kv_tokens(cache.k) == 32
    # Plain-dtype cache unchanged by the new knob's default.
    plain = KVCache.zeros(cfg, batch=3, max_seq=32)
    assert not isinstance(plain.k, QuantKV)


@pytest.fixture(scope="module")
def engines():
    """Batched engines with and without int8 KV, same seed/config —
    includes the prefix-cache splice path (byte-tokenized system prompt
    is chunk-prefilled, then spliced per admission)."""
    made = {}
    for kvq in ("", "int8"):
        eng = BatchedJaxEngine(
            get_config("toy-8m"),
            dtype="float32",
            kv_quant=kvq,
            max_seq_len=512,
            prefill_buckets=(64, 128, 256, 512),
            batch_size=4,
            chunk_len=4,
        )
        asyncio.run(eng.start())
        made[kvq] = eng
    yield made
    for eng in made.values():
        asyncio.run(eng.stop())


async def test_greedy_parity_full_precision_vs_int8_kv(engines):
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    prompts = [render_prompt(f"list pods in namespace team-{i}")
               for i in range(6)]
    full = await asyncio.gather(*[
        engines[""].generate(p, max_tokens=16, temperature=0.0)
        for p in prompts])
    quant = await asyncio.gather(*[
        engines["int8"].generate(p, max_tokens=16, temperature=0.0)
        for p in prompts])
    # Both paths serve from the prefix cache (splice exercises the
    # QuantKV tree helpers); greedy decode on the toy model survives the
    # <1% KV quantization error bit-exactly.
    assert all(r.prefix_cache_hit for r in full + quant)
    assert [r.text for r in full] == [r.text for r in quant]


async def test_int8_kv_pool_serves_gather_and_says_why():
    """The ragged kernel reads bf16 KV: an int8 pool serves the
    ``gather`` regime even where the kernel was asked for, and /health
    names the condition."""
    eng = BatchedJaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        kv_quant="int8",
        force_ragged=True,
        max_seq_len=128,
        prefill_buckets=(64,),
        batch_size=2,
        chunk_len=4,
        prefix_cache=False,
    )
    await eng.start()
    try:
        assert eng._decode_impl == "dense" and not eng._use_ragged
        health = eng.kv_pool_health()
        assert health["attention_regime"] == "gather"
        assert "KV_QUANT=int8" in health["attention_regime_reason"]
        # no kernel, so nothing of it was resolved (ISSUE 30)
        assert health["attention_pages_per_step"] is None
        assert health["attention_decode_grid_steps"] is None
        assert health["attention_stream_depth"] is None     # ISSUE 32
        r = await eng.generate("get pods -o wide", max_tokens=8,
                               temperature=0.0)
        assert r.completion_tokens > 0
    finally:
        await eng.stop()


async def test_int8_kv_serves_under_mesh_with_parity(engines):
    """int8 KV composes with data/model mesh axes: QuantKV shards via
    shard_cache (payload [L,B,S,KV,hd] spec; scales the same minus hd)
    and greedy serving matches the single-device int8-KV engine."""
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt

    eng = BatchedJaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        kv_quant="int8",
        mesh_shape="data:2,model:2",
        max_seq_len=512,
        prefill_buckets=(64, 128, 256, 512),
        batch_size=4,
        chunk_len=4,
    )
    await eng.start()
    try:
        assert eng.kv_quant == "int8"
        assert isinstance(eng._cache.k, QuantKV)
        prompts = [render_prompt(f"get pods in ns mesh-{i}") for i in range(3)]
        mesh_out = await asyncio.gather(*[
            eng.generate(p, max_tokens=12, temperature=0.0) for p in prompts])
        single_out = await asyncio.gather(*[
            engines["int8"].generate(p, max_tokens=12, temperature=0.0)
            for p in prompts])
        assert [r.text for r in mesh_out] == [r.text for r in single_out]
    finally:
        await eng.stop()


def test_int8_kv_stays_enabled_under_pipe_mesh():
    """Round 5 closed the int8-KV x pipe composition gap (VERDICT r4
    item 2): a pipe mesh now serves a QuantKV cache instead of silently
    falling back to full-precision KV. (Greedy parity is pinned by
    tests/test_mesh_serving.py::test_batched_serving_pp_tp_int8_kv_parity.)"""
    eng = BatchedJaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        kv_quant="int8",
        mesh_shape="pipe:2,model:2",
        max_seq_len=128,
        prefill_buckets=(64,),
        batch_size=4,
        chunk_len=4,
        prefix_cache=False,
    )
    asyncio.run(eng.start())
    try:
        assert eng.kv_quant == "int8"
        assert isinstance(eng._cache.k, QuantKV)
    finally:
        asyncio.run(eng.stop())
