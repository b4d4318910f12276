"""Paged decode attention parity vs dense (SURVEY.md §2.2 row 2): the
kernel runs in interpret mode on CPU and must match dense_attention for
ragged per-slot lengths, GQA and MQA, and page-boundary edge cases."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.ops.attention import dense_attention
from ai_agent_kubectl_tpu.ops.paged_attention import paged_decode_attention


def _dense_ref(q, k, v, positions):
    """dense_attention over full caches with the decode causal mask."""
    N, H, hd = q.shape
    S = k.shape[1]
    kv_pos = jnp.arange(S)[None, None, :]
    mask = kv_pos <= positions[:, None, None]          # [N, 1, S]
    return dense_attention(q[:, None], k, v, mask)[:, 0]


def _rand(N, S, H, KV, hd, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (N, H, hd), dtype)
    k = jax.random.normal(ks[1], (N, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (N, S, KV, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("kv_heads", [1, 2])   # MQA and GQA
def test_paged_pool_block_table_matches_dense(kv_heads):
    """Block-table variant (ISSUE 10): slots read scattered pool blocks
    by table indirection; shared blocks (one block in two tables) and
    sentinel entries beyond the live span must not change the math vs
    dense attention over the gathered per-slot view."""
    from ai_agent_kubectl_tpu.ops.paged_attention import (
        paged_decode_attention_pool)

    N, n_blocks, page, H, hd = 3, 10, 16, 4, 64
    KV = kv_heads
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (N, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (n_blocks, page, KV, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (n_blocks, page, KV, hd), jnp.float32)
    # Slot 0 and 1 SHARE block 7 as their first page (radix sharing);
    # dead pages carry the sentinel (n_blocks), which must clamp.
    tables = jnp.asarray([[7, 2, 9, 10], [7, 5, 10, 10], [0, 1, 3, 4]],
                         jnp.int32)
    positions = jnp.asarray([40, 17, 63], jnp.int32)
    out = paged_decode_attention_pool(q, kp, vp, positions, tables,
                                      page_size=page, interpret=True)
    # Reference: gather each slot's pages densely, mask causally.
    idx = jnp.clip(tables, 0, n_blocks - 1)
    kg = kp[idx].reshape(N, 4 * page, KV, hd)
    vg = vp[idx].reshape(N, 4 * page, KV, hd)
    ref = _dense_ref(q, kg, vg, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel", ["contiguous", "block_table"])
def test_paged_stacked_cache_layer_index_equals_layer_slice(kernel):
    """ISSUE 25: both single-query kernels read a layer of the STACKED
    cache through their index maps — the call with the [L, ...] cache and
    a layer index equals the call on ``cache[layer]`` bit for bit, for a
    first, middle and last layer (every other layer is NaN)."""
    from ai_agent_kubectl_tpu.ops.paged_attention import (
        paged_decode_attention_pool)

    L, N, S, H, KV, hd, page = 3, 3, 64, 4, 2, 64, 16
    q, k, v = _rand(N, S, H, KV, hd, seed=3)
    positions = jnp.asarray([40, 17, 63], jnp.int32)
    tables = jnp.asarray([[7, 2, 9, 12], [7, 5, 12, 12], [0, 1, 3, 4]],
                         jnp.int32)
    if kernel == "block_table":
        k = k.reshape(N * S // page, page, KV, hd)
        v = v.reshape(N * S // page, page, KV, hd)

    def call(kk, vv, *layer):
        if kernel == "block_table":
            return paged_decode_attention_pool(
                q, kk, vv, positions, tables, *layer, page_size=page,
                interpret=True)
        return paged_decode_attention(q, kk, vv, positions, *layer,
                                      page_size=page, interpret=True)

    sliced = np.asarray(call(k, v))
    for layer in range(L):
        nan = jnp.full_like(k, jnp.nan)
        sk = jnp.stack([k if i == layer else nan for i in range(L)])
        sv = jnp.stack([v if i == layer else nan for i in range(L)])
        np.testing.assert_array_equal(
            np.asarray(call(sk, sv, jnp.int32(layer))), sliced)
    with pytest.raises(ValueError, match="takes a layer index"):
        call(jnp.stack([k, k]), jnp.stack([v, v]))


@pytest.mark.parametrize("kv_heads", [1, 2])   # MQA and GQA
def test_paged_matches_dense_ragged(kv_heads):
    N, S, H, hd, page = 4, 128, 4, 64, 16
    q, k, v = _rand(N, S, H, kv_heads, hd)
    # Ragged lengths incl. page-boundary edges: 0 (single live token),
    # exactly page-1, exactly page, mid-cache.
    positions = jnp.asarray([0, 15, 16, 77], jnp.int32)
    out = paged_decode_attention(q, k, v, positions, page_size=page,
                                 interpret=True)
    ref = _dense_ref(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_full_cache_and_last_page():
    N, S, H, KV, hd, page = 2, 64, 4, 2, 64, 16
    q, k, v = _rand(N, S, H, KV, hd, seed=1)
    positions = jnp.asarray([S - 1, S - page], jnp.int32)
    out = paged_decode_attention(q, k, v, positions, page_size=page,
                                 interpret=True)
    ref = _dense_ref(q, k, v, positions)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_rejects_unaligned_cache():
    q, k, v = _rand(2, 60, 4, 2, 64)
    with pytest.raises(ValueError, match="divisible"):
        paged_decode_attention(q, k, v, jnp.zeros((2,), jnp.int32),
                               page_size=16, interpret=True)


def test_paged_bf16_inputs():
    N, S, H, KV, hd, page = 2, 64, 4, 1, 128, 16
    q, k, v = _rand(N, S, H, KV, hd, seed=2, dtype=jnp.bfloat16)
    positions = jnp.asarray([33, 5], jnp.int32)
    out = paged_decode_attention(q, k, v, positions, page_size=page,
                                 interpret=True)
    ref = _dense_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), positions)
    np.testing.assert_allclose(np.asarray(out).astype(np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


async def test_batched_engine_paged_decode_parity():
    """The continuous-batching engine serving with DECODE_ATTN=paged
    (interpret mode on CPU) produces exactly the dense-decode outputs, and
    its slot caches pad to page multiples."""
    import asyncio

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    def mk(decode_attn):
        return BatchedJaxEngine(
            get_config("toy-8m"), tokenizer=ByteTokenizer(), dtype="float32",
            max_seq_len=64, prefill_buckets=(32,), prefix_cache=False,
            batch_size=2, chunk_len=4, kv_page_size=16,
            decode_attn=decode_attn)

    texts = {}
    for impl in ("dense", "paged"):
        eng = mk(impl)
        await eng.start()
        try:
            assert eng._decode_impl == impl
            rs = await asyncio.gather(*[
                eng.generate(p, max_tokens=6, temperature=0.0)
                for p in ("list pods", "get nodes wide")
            ])
            texts[impl] = [r.text for r in rs]
            if impl == "paged":
                assert eng._cache.k.shape[2] % 16 == 0
        finally:
            await eng.stop()
    assert texts["paged"] == texts["dense"]
