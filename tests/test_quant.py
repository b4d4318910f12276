"""Weight-only int8 quantization (SURVEY.md §2.2 optional row): accuracy
bounds, matmul-epilogue equivalence, sharded-tree placement, and the
engine serving with QUANT=int8."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.ops.quant import (
    QuantInt8, QuantInt8W8A8, dequantize, qmatmul, qmatmul_heads,
    quantize_int8, quantize_params_int8,
)


def test_quantize_roundtrip_error_bound():
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 32), jnp.float32)
    qw = quantize_int8(w)
    assert qw.q.dtype == jnp.int8
    assert qw.scale.shape == (4, 1, 32)   # per-(layer, out-channel)
    deq = dequantize(qw, jnp.float32)
    # Symmetric 8-bit: error bounded by half a quantization step.
    step = np.asarray(qw.scale)
    assert np.all(np.abs(np.asarray(deq) - np.asarray(w)) <= step / 2 + 1e-7)


def test_qmatmul_matches_dequant_matmul():
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, 64), jnp.float32)
    qw = quantize_int8(w)
    out = qmatmul(x, qw)
    ref = x @ dequantize(qw, jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # Plain weights pass through untouched.
    np.testing.assert_allclose(np.asarray(qmatmul(x, w)), np.asarray(x @ w),
                               rtol=1e-6)


@pytest.mark.parametrize("kind", ["plain", "int8", "w8a8"])
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_qmatmul_heads_is_the_reshape_bit_for_bit(kind, jitted):
    """The head split kept outside the dot (ISSUE 43) changes no value: the
    same dot, the same float32 epilogue, the same cast, then a reshape."""
    heads, hd = 4, 8
    w = jax.random.normal(jax.random.PRNGKey(3), (64, heads * hd), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 64),
                          jnp.float32).astype(jnp.bfloat16)
    if kind == "plain":
        w = w.astype(jnp.bfloat16)
    else:
        qw = quantize_int8(w)
        w = qw if kind == "int8" else QuantInt8W8A8(q=qw.q, scale=qw.scale)
    split = lambda x, w: qmatmul_heads(x, w, heads, hd)
    plain = lambda x, w: qmatmul(x, w).reshape(2, 5, heads, hd)
    if jitted:
        split, plain = jax.jit(split), jax.jit(plain)
    out, ref = split(x, w), plain(x, w)
    assert out.shape == (2, 5, heads, hd) and out.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(ref.astype(jnp.float32)))


def test_quantize_params_covers_moe_and_skips_small_leaves():
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import init_params

    params = init_params(jax.random.PRNGKey(0), get_config("toy-moe"),
                         dtype=jnp.float32)
    qp = quantize_params_int8(params)
    assert isinstance(qp["layers"]["wq"], QuantInt8)
    # MoE expert weights (rank 4) quantize with per-(layer, expert,
    # out-channel) scales (VERDICT r4 item 3).
    assert isinstance(qp["layers"]["w_gate"], QuantInt8)
    assert qp["layers"]["w_gate"].scale.shape[-2] == 1
    # The router, embedding, and norms stay full precision.
    assert not isinstance(qp["layers"]["router"], QuantInt8)
    assert not isinstance(qp["embed"], QuantInt8)
    assert not isinstance(qp["layers"]["attn_norm"], QuantInt8)


def test_quantized_forward_close_to_dequantized_reference():
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import (
        KVCache, forward, init_params,
    )

    cfg = get_config("toy-8m")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    qp = quantize_params_int8(params)
    deq = jax.tree_util.tree_map(
        lambda x: dequantize(x, jnp.float32) if isinstance(x, QuantInt8) else x,
        qp, is_leaf=lambda x: isinstance(x, QuantInt8))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(8), (1, 8)).astype(jnp.int32)

    lq, _ = forward(qp, cfg, tokens, positions, KVCache.zeros(cfg, 1, 16,
                                                              jnp.float32))
    lr, _ = forward(deq, cfg, tokens, positions, KVCache.zeros(cfg, 1, 16,
                                                               jnp.float32))
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lr),
                               rtol=2e-4, atol=2e-4)


def test_w8a8_qmatmul_close_to_weight_only():
    """QuantInt8W8A8 (per-token activation quant + s8×s8 MXU dot) stays
    within ~1% of the weight-only dequant reference. Measured a speed
    no-op on the 7B geometry (earlier chip run, not re-measured) — kept as a library
    option."""
    from ai_agent_kubectl_tpu.ops.quant import QuantInt8W8A8, to_w8a8

    w = jax.random.normal(jax.random.PRNGKey(5), (64, 32), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 64), jnp.float32)
    qw = quantize_int8(w)
    out = qmatmul(x, QuantInt8W8A8(q=qw.q, scale=qw.scale))
    ref = x @ dequantize(qw, jnp.float32)
    rel = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    assert rel < 0.02, rel

    # to_w8a8 re-tags layer projections only; embed/head stay weight-only.
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import init_params

    params = quantize_params_int8(
        init_params(jax.random.PRNGKey(0), get_config("toy-8m"),
                    dtype=jnp.float32),
        quantize_embed=True)
    p88 = to_w8a8(params)
    assert isinstance(p88["layers"]["wq"], QuantInt8W8A8)
    assert isinstance(p88["embed"], QuantInt8)
    assert isinstance(p88["lm_head"], QuantInt8)

    # shard_params must treat the W8A8 leaf like QuantInt8 (tree-structure
    # mismatch regression: its tree_map descended into the node).
    from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
    from ai_agent_kubectl_tpu.parallel.sharding import shard_params

    mesh = build_mesh(MeshConfig.parse("data:2,model:2"),
                      devices=jax.devices()[:4])
    sp = shard_params(p88, mesh, get_config("toy-8m"))
    assert isinstance(sp["layers"]["wq"], QuantInt8W8A8)


def test_embed_quant_roundtrip_and_tied_head():
    from ai_agent_kubectl_tpu.ops.quant import (
        embed_lookup, quantize_embed_int8, tied_head,
    )

    emb = jax.random.normal(jax.random.PRNGKey(3), (128, 32), jnp.float32)
    qe = quantize_embed_int8(emb, chunk=50)      # exercise chunking
    assert qe.q.shape == emb.shape and qe.scale.shape == (128, 1)
    # Per-row error bound: half a step of that row's scale.
    deq = np.asarray(qe.q, np.float32) * np.asarray(qe.scale)
    assert np.all(np.abs(deq - np.asarray(emb))
                  <= np.asarray(qe.scale) / 2 + 1e-7)

    toks = jnp.asarray([[3, 77, 126]], jnp.int32)
    looked = embed_lookup(qe, toks, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(looked), deq[np.asarray(toks)[0]][None],
                               rtol=1e-6)

    h = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 32), jnp.float32)
    logits = tied_head(h, qe)
    ref = h @ jnp.asarray(deq).T
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_tied_embed_quantized_forward_close():
    """Gemma-style tied/scaled embeddings with the per-row int8 embedding:
    logits stay close to the dequantized-reference forward."""
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import (
        KVCache, forward, init_params,
    )
    from ai_agent_kubectl_tpu.ops.quant import embed_lookup

    cfg = get_config("toy-8m", tie_embeddings=True, embed_scale=True)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    qp = quantize_params_int8(params, quantize_embed=True)
    assert isinstance(qp["embed"], QuantInt8)
    deq = dict(qp)
    deq["embed"] = embed_lookup(qp["embed"], jnp.arange(cfg.vocab_size),
                                dtype=jnp.float32)
    deq = jax.tree_util.tree_map(
        lambda x: dequantize(x, jnp.float32) if isinstance(x, QuantInt8) else x,
        deq, is_leaf=lambda x: isinstance(x, QuantInt8))

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    positions = jnp.broadcast_to(jnp.arange(8), (1, 8)).astype(jnp.int32)
    lq, _ = forward(qp, cfg, tokens, positions, KVCache.zeros(cfg, 1, 16,
                                                              jnp.float32))
    lr, _ = forward(deq, cfg, tokens, positions, KVCache.zeros(cfg, 1, 16,
                                                               jnp.float32))
    np.testing.assert_allclose(np.asarray(lq), np.asarray(lr),
                               rtol=2e-4, atol=2e-4)


async def test_int8_embed_serves_under_mesh_with_parity():
    """quant=int8 now quantizes the embedding under a mesh too: the
    vocab-sharded QuantInt8 gather + tied_head epilogue must serve with
    greedy parity against the single-device int8 engine (tied and untied
    covered via the two toy configs)."""
    import asyncio as _a

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    for overrides in ({}, {"tie_embeddings": True, "embed_scale": True}):
        cfg = get_config("toy-8m", **overrides)
        outs = {}
        for mesh_shape in ("", "data:2,model:2"):
            eng = BatchedJaxEngine(
                cfg, dtype="float32", quant="int8", mesh_shape=mesh_shape,
                max_seq_len=128, prefill_buckets=(64,), batch_size=2,
                chunk_len=4, prefix_cache=False,
            )
            await eng.start()
            try:
                from ai_agent_kubectl_tpu.ops.quant import QuantInt8
                assert isinstance(eng.params["embed"], QuantInt8)
                rs = await _a.gather(*[
                    eng.generate(f"get pods -n team-{i}", max_tokens=8,
                                 temperature=0.0)
                    for i in range(3)])
                outs[mesh_shape] = [r.text for r in rs]
            finally:
                await eng.stop()
        assert outs[""] == outs["data:2,model:2"], overrides


def test_quantized_params_shard_over_tp_mesh():
    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import (
        KVCache, forward, init_params,
    )
    from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
    from ai_agent_kubectl_tpu.parallel.sharding import shard_cache, shard_params

    cfg = get_config("toy-8m")
    params = quantize_params_int8(
        init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32))
    mesh = build_mesh(MeshConfig.parse("tp=8"))
    sp = shard_params(params, mesh, cfg)
    wq = sp["layers"]["wq"]
    assert wq.q.addressable_shards[0].data.shape[-1] == wq.q.shape[-1] // 8
    assert wq.scale.addressable_shards[0].data.shape[-1] == \
        wq.scale.shape[-1] // 8

    cache = shard_cache(KVCache.zeros(cfg, 1, 16, jnp.float32), mesh, cfg)
    tokens = jnp.zeros((1, 4), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(4), (1, 4)).astype(jnp.int32)
    logits, _ = jax.jit(lambda p, t, pos, c: forward(p, cfg, t, pos, c))(
        sp, tokens, positions, cache)
    assert logits.shape == (1, 4, cfg.vocab_size)


async def test_engine_serves_with_int8_quant():
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    eng = BatchedJaxEngine(
        get_config("toy-8m"), tokenizer=ByteTokenizer(), dtype="float32",
        quant="int8", max_seq_len=128, prefill_buckets=(32, 64),
        prefix_cache=False, batch_size=2, chunk_len=4)
    await eng.start()
    try:
        assert isinstance(eng.params["layers"]["wq"], QuantInt8)
        r = await eng.generate("list pods", max_tokens=6, temperature=0.0)
        assert r.completion_tokens >= 1
        assert r.finish_reason in ("length", "stop")
    finally:
        await eng.stop()


def test_random_params_int8_matches_quantized_init_structure():
    """random_params_int8 (the no-materialization bench init) must produce
    the exact tree structure/shapes/dtypes of quantize_params_int8 over a
    real init — serving programs then compile identically to a real int8
    checkpoint."""
    import jax

    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import init_params
    from ai_agent_kubectl_tpu.ops.quant import (
        quantize_params_int8,
        random_params_int8,
    )

    cfg = get_config("toy-8m")
    key = jax.random.PRNGKey(0)
    ref = jax.eval_shape(
        lambda k: quantize_params_int8(init_params(k, cfg, dtype=jnp.bfloat16)),
        key,
    )
    got = jax.eval_shape(
        lambda k: random_params_int8(k, cfg, dtype=jnp.bfloat16), key
    )
    ref_l, ref_t = jax.tree_util.tree_flatten_with_path(ref)
    got_l, got_t = jax.tree_util.tree_flatten_with_path(got)
    assert ref_t == got_t
    for (pr, r), (pg, g) in zip(ref_l, got_l):
        assert pr == pg
        assert r.shape == g.shape and r.dtype == g.dtype, (pr, r, g)
