"""Model-layer tests (SURVEY.md §4 numerics row): shapes, causality,
cache-consistency (prefill vs incremental decode parity), GQA, MoE,
tokenizer round-trips, RoPE offset correctness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import KVCache, forward, init_params
from ai_agent_kubectl_tpu.ops.attention import causal_mask, dense_attention
from ai_agent_kubectl_tpu.ops.rope import apply_rope


@pytest.fixture(scope="module")
def toy():
    cfg = get_config("toy-8m")
    # float32 params: parity tests check the algorithm, not bf16 rounding.
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def test_forward_shapes(toy):
    cfg, params = toy
    B, S, CAP = 2, 16, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    cache = KVCache.zeros(cfg, B, CAP, dtype=jnp.float32)
    logits, cache = forward(params, cfg, tokens, positions, cache, kv_limit=S)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert cache.k.shape == (cfg.n_layers, B, CAP, cfg.n_kv_heads, cfg.head_dim)
    assert np.all(np.asarray(cache.lengths) == S)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_causality(toy):
    # Changing a future token must not change past logits.
    cfg, params = toy
    B, S = 1, 12
    key = jax.random.PRNGKey(2)
    tokens = jax.random.randint(key, (B, S), 3, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    cache = KVCache.zeros(cfg, B, S, dtype=jnp.float32)
    logits1, _ = forward(params, cfg, tokens, positions, cache, kv_limit=S)
    tokens2 = tokens.at[0, -1].set((tokens[0, -1] + 7) % cfg.vocab_size)
    logits2, _ = forward(params, cfg, tokens2, positions, cache, kv_limit=S)
    np.testing.assert_allclose(
        np.asarray(logits1[0, :-1]), np.asarray(logits2[0, :-1]), rtol=1e-5, atol=1e-5
    )
    assert not np.allclose(np.asarray(logits1[0, -1]), np.asarray(logits2[0, -1]))


def test_prefill_decode_parity(toy):
    # Full-sequence forward == prefill(first part) + token-by-token decode.
    cfg, params = toy
    B, S, CAP = 1, 10, 16
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 3, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    full_logits, _ = forward(
        params, cfg, tokens, positions, KVCache.zeros(cfg, B, CAP, dtype=jnp.float32), kv_limit=CAP
    )

    split = 6
    cache = KVCache.zeros(cfg, B, CAP, dtype=jnp.float32)
    pre_logits, cache = forward(
        params, cfg, tokens[:, :split], positions[:, :split], cache, kv_limit=CAP
    )
    np.testing.assert_allclose(
        np.asarray(full_logits[:, :split]), np.asarray(pre_logits),
        rtol=1e-4, atol=1e-4,
    )
    for i in range(split, S):
        step_logits, cache = forward(
            params, cfg, tokens[:, i:i + 1], positions[:, i:i + 1], cache,
            kv_limit=CAP,
        )
        np.testing.assert_allclose(
            np.asarray(full_logits[:, i]), np.asarray(step_logits[:, 0]),
            rtol=1e-4, atol=1e-4,
        )


def test_padded_prefill_matches_exact(toy):
    # Bucketed padding (static shapes) must not change valid-token logits.
    cfg, params = toy
    B, S, PAD, CAP = 1, 7, 12, 16
    tokens = jax.random.randint(jax.random.PRNGKey(4), (B, S), 3, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    exact, _ = forward(
        params, cfg, tokens, positions, KVCache.zeros(cfg, B, CAP, dtype=jnp.float32), kv_limit=CAP
    )
    padded_tokens = jnp.pad(tokens, ((0, 0), (0, PAD - S)))
    padded_positions = jnp.broadcast_to(jnp.arange(PAD), (B, PAD))
    padded, _ = forward(
        params, cfg, padded_tokens, padded_positions,
        KVCache.zeros(cfg, B, CAP, dtype=jnp.float32), kv_limit=CAP,
    )
    np.testing.assert_allclose(
        np.asarray(exact), np.asarray(padded[:, :S]), rtol=1e-4, atol=1e-4
    )


def test_moe_forward_and_mixing():
    cfg = get_config("toy-moe")
    params = init_params(jax.random.PRNGKey(5), cfg)
    B, S = 2, 8
    tokens = jax.random.randint(jax.random.PRNGKey(6), (B, S), 3, cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    logits, _ = forward(
        params, cfg, tokens, positions, KVCache.zeros(cfg, B, S, dtype=jnp.float32), kv_limit=S
    )
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.all(np.isfinite(np.asarray(logits)))


def test_router_topk_weights_sum_to_one():
    from ai_agent_kubectl_tpu.parallel.moe import router_weights

    cfg = get_config("toy-moe")
    logits = jax.random.normal(jax.random.PRNGKey(7), (3, 5, cfg.n_experts))
    mix, idx = router_weights(cfg, logits)
    s = np.asarray(mix.sum(axis=-1))
    np.testing.assert_allclose(s, np.ones_like(s), rtol=1e-5)
    # Exactly k nonzero entries per token
    nz = np.asarray((mix > 0).sum(axis=-1))
    assert np.all(nz == cfg.experts_per_token)


def test_rope_relative_positions():
    # RoPE: attention scores depend only on relative position, so shifting
    # both q and k positions by a constant must not change q·k.
    q = jax.random.normal(jax.random.PRNGKey(8), (1, 4, 2, 64))
    k = jax.random.normal(jax.random.PRNGKey(9), (1, 4, 2, 64))
    pos = jnp.arange(4)[None, :]
    q1, k1 = apply_rope(q, pos), apply_rope(k, pos)
    q2, k2 = apply_rope(q, pos + 100), apply_rope(k, pos + 100)
    s1 = jnp.einsum("bqhd,bkhd->bhqk", q1, k1)
    s2 = jnp.einsum("bqhd,bkhd->bhqk", q2, k2)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


def test_gqa_matches_mha_when_heads_equal():
    # dense_attention with n_kv == n_heads must equal plain attention.
    B, S, H, D = 1, 6, 4, 16
    q = jax.random.normal(jax.random.PRNGKey(10), (B, S, H, D))
    k = jax.random.normal(jax.random.PRNGKey(11), (B, S, H, D))
    v = jax.random.normal(jax.random.PRNGKey(12), (B, S, H, D))
    mask = causal_mask(S, S)
    out = dense_attention(q, k, v, jnp.broadcast_to(mask, (B, S, S)))
    # manual
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (D ** 0.5)
    logits = jnp.where(mask[:, None], logits, -1e30)
    ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_byte_tokenizer_roundtrip():
    tok = ByteTokenizer()
    text = "kubectl get pods -n kube-system — ünïcode ✓"
    ids = tok.encode(text)
    assert ids[0] == tok.bos_id
    assert tok.decode(ids) == text


def test_param_count_sanity():
    assert 1e6 < get_config("toy-8m").param_count() < 2e7
    assert 1.5e9 < get_config("gemma-2b-it").param_count() < 3.5e9
    assert 6e9 < get_config("llama-3-8b-instruct").param_count() < 9e9
    assert 4e10 < get_config("mixtral-8x7b-instruct").param_count() < 5.2e10
    assert 6e10 < get_config("llama-3-70b-instruct").param_count() < 8e10


# ------------------------------------------- the cache rides the layer scan
#
# ISSUE 25: as the layer scan's xs/ys the stacked cache was sliced out a
# layer at a time and written back into a second stacked buffer — on the
# chip, three moves of the whole KV pool every forward pass. These pin the
# structure that removed them, in every cache layout.

def _forward_case(cfg, mode):
    """(cache, forward kwargs) of one cache layout at toy size."""
    B, page, pages = 2, 8, 4
    if mode == "dense":
        return KVCache.zeros(cfg, B, pages * page, dtype=jnp.float32), {}
    if mode == "dense_int8":
        return KVCache.zeros(cfg, B, pages * page, kv_quant="int8"), {}
    n_blocks = 256
    pool = (cfg.n_layers, n_blocks, page, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32),
                    v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((n_blocks,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    return cache, dict(
        block_tables=tables, page_size=page,
        attn_impl="ragged" if mode == "pool_ragged" else "dense")


@pytest.mark.parametrize(
    "mode", ["pool_ragged", "pool_gather", "dense", "dense_int8"])
def test_layer_scan_carries_the_cache(toy, mode):
    """The layer scan has every cache leaf among its CARRIES and nothing
    cache-shaped among its xs or ys (what it scans over is the layer
    parameters and the layer index)."""
    cfg, params = toy
    cache, kw = _forward_case(cfg, mode)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.full((2, 1), 5, jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, c: forward(p, cfg, tok, pos, c, kv_limit=32, **kw)
    )(params, cache).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"
             and e.params["length"] == cfg.n_layers]
    assert len(scans) == 1, "one layer scan"
    eqn = scans[0]
    n_const, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]

    def shapes(vs):
        return [tuple(v.aval.shape) for v in vs]

    carries = shapes(eqn.invars[n_const:n_const + n_carry])
    xs = shapes(eqn.invars[n_const + n_carry:])
    ys = shapes(eqn.outvars[n_carry:])
    leaves = [tuple(x.shape)
              for x in jax.tree_util.tree_leaves((cache.k, cache.v))]
    for leaf in leaves:
        assert carries.count(leaf) >= leaves.count(leaf), (leaf, carries)
        assert leaf not in xs and leaf not in ys, (leaf, xs, ys)
    assert ys == [], f"the layer scan stacks nothing: {ys}"
    assert (cfg.n_layers,) in xs, "the layer index is scanned over"


def test_donated_pool_forward_needs_less_than_one_pool_of_temporaries(toy):
    """With the pool donated, a pool-mode forward's temporaries stay below
    one pool's bytes: nothing holds a second copy of it. (As the scan's
    xs/ys the pool cost at least one more of itself. The gather path is
    the one measured: this backend runs the Pallas kernel interpreted,
    which copies its operands — the compiled kernel's side of this is
    PERF.md's AOT table.)"""
    cfg, params = toy
    cache, kw = _forward_case(cfg, "pool_gather")
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.full((2, 1), 5, jnp.int32)
    compiled = jax.jit(
        lambda p, c: forward(p, cfg, tok, pos, c, kv_limit=32, **kw),
        donate_argnums=(1,)).lower(params, cache).compile()
    mem = compiled.memory_analysis()
    if mem is None or not hasattr(mem, "temp_size_in_bytes"):
        pytest.skip("this backend reports no memory analysis")
    pool_bytes = cache.k.nbytes + cache.v.nbytes
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is donated"
    assert mem.temp_size_in_bytes < pool_bytes, (
        mem.temp_size_in_bytes, pool_bytes)
