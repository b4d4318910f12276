"""int4 weight quantization tests (ops/quant4.py): pack/unpack format,
matmul parity (XLA fallback vs f32 reference vs interpret-mode Pallas
kernel), param-tree structure, and the int4-vs-int8 logit-delta numerics
the VERDICT r4 item 1 asked to quantify. The compiled-kernel parity test
lives in tests/test_tpu_kernels.py (TPU-gated)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.ops.quant4 import (
    QuantInt4, dequantize_int4, int4_supported, qmatmul4,
    qmatmul4_interpret, quantize_int4, quantize_params_int4,
    random_params_int4, unpack_int4)

#: a toy geometry whose every projection tiles the int4 kernel format
#: (dims % 512; block halves fill the 128 lanes)
INT4_TOY = dict(dim=512, n_heads=4, head_dim=128, n_kv_heads=2,
                mlp_hidden=512)


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32) * 0.05


def test_pack_unpack_roundtrip():
    w = _rand(jax.random.PRNGKey(0), (512, 512))
    qw = quantize_int4(w)
    assert qw.q.shape == (512, 256) and qw.q.dtype == jnp.int8
    assert qw.scale.shape == (1, 512) and qw.scale.dtype == jnp.float32
    vals = unpack_int4(qw)
    assert vals.shape == (512, 512)
    v = np.asarray(vals)
    assert v.min() >= -7 and v.max() <= 7
    # Quantization error bound: |w - deq| <= scale/2 per element.
    deq = np.asarray(dequantize_int4(qw, jnp.float32))
    bound = np.repeat(np.asarray(qw.scale), 512, axis=0) / 2 + 1e-7
    assert (np.abs(deq - np.asarray(w)) <= bound).all()


def test_groupwise_scales_differ_per_group():
    # Two groups with very different magnitudes must get different scales
    # (the group-wise property that bounds int4 error).
    w = np.ones((1024, 512), np.float32) * 0.01
    w[512:] *= 100.0
    qw = quantize_int4(jnp.asarray(w))
    s = np.asarray(qw.scale)
    assert s.shape == (2, 512)
    assert (s[1] > s[0] * 50).all()


def test_matmul_parity_vs_f32_reference():
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    w = _rand(k1, (512, 1024))
    x = _rand(k2, (8, 512))
    qw = quantize_int4(w)
    y = qmatmul4(x, qw)
    ref = x @ np.asarray(dequantize_int4(qw, jnp.float32))
    # Same quantized weights: only dot order/precision differs.
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-2, atol=2e-3)
    # Characterize the error vs the ORIGINAL weight. For i.i.d. gaussian
    # weights (the incompressible worst case — no structure for the 15
    # levels to exploit) per-matmul max rel error lands ~0.15-0.2;
    # trained-network tolerance comes from the argmax/softmax at the end,
    # which the logit-delta test below checks on a real forward pass.
    full = np.asarray(x) @ np.asarray(w)
    rel = np.abs(np.asarray(y) - full).max() / (np.abs(full).max() + 1e-9)
    assert rel < 0.3, f"int4 matmul rel err {rel}"


def test_interpret_kernel_matches_fallback():
    """The Pallas kernel (interpret mode) and the XLA fallback compute the
    same group-scaled math — this is the parity that licenses trusting
    the compiled kernel on TPU (plus the TPU-gated test)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    w = _rand(k1, (1024, 512))
    x = _rand(k2, (24, 1024))          # T=24: exercises row padding to 8s
    qw = quantize_int4(w)
    y_kernel = qmatmul4_interpret(x, qw)
    y_fallback = qmatmul4(x, qw)       # CPU -> XLA fallback
    np.testing.assert_allclose(np.asarray(y_kernel),
                               np.asarray(y_fallback),
                               rtol=1e-3, atol=1e-4)


def test_stacked_leaf_scan_slicing():
    """Stacked [L, in, out] leaves slice per layer under lax.scan exactly
    like QuantInt8 (the transformer's layer loop contract)."""
    w = _rand(jax.random.PRNGKey(3), (3, 512, 512))
    qw = quantize_int4(w)
    x = _rand(jax.random.PRNGKey(4), (4, 512))

    def body(h, lw):
        return qmatmul4(h, lw), ()

    out, _ = jax.lax.scan(body, x, qw)
    ref = x
    for i in range(3):
        ref = qmatmul4(ref, QuantInt4(q=qw.q[i], scale=qw.scale[i]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-4)


def test_int4_supported_gates():
    assert int4_supported(512, 512)
    assert int4_supported(24576, 3072)
    assert not int4_supported(256, 512)       # in % group
    assert not int4_supported(512, 640)       # out % block
    assert not int4_supported(512, 128256)    # llama vocab head


def test_param_tree_structure_and_fallbacks():
    """quantize_params_int4: tileable projections -> QuantInt4, the
    non-tileable toy-8m dims -> QuantInt8; random_params_int4 builds the
    same tree structure/shapes/dtypes directly."""
    from ai_agent_kubectl_tpu.models.transformer import init_params
    from ai_agent_kubectl_tpu.ops.quant import QuantInt8

    cfg = get_config("toy-8m", **INT4_TOY)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    q = quantize_params_int4(params, quantize_embed=True)
    for key in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        assert isinstance(q["layers"][key], QuantInt4), key
    assert isinstance(q["lm_head"], QuantInt4)
    assert isinstance(q["embed"], QuantInt8)  # embedding stays per-row int8

    r = random_params_int4(jax.random.PRNGKey(0), cfg, dtype=jnp.float32,
                           quantize_embed=True)
    flat_q = jax.tree_util.tree_flatten_with_path(q)[0]
    flat_r = jax.tree_util.tree_flatten_with_path(r)[0]
    assert len(flat_q) == len(flat_r)
    for (pq, lq), (pr, lr) in zip(flat_q, flat_r):
        assert pq == pr
        assert lq.shape == lr.shape and lq.dtype == lr.dtype, pq

    # Mixed trees: toy-8m's 704-wide MLP can't tile (704 = 128 * 5.5) ->
    # int8 fallback; its 256-dim attention projections pick the smaller
    # (256, 256) format.
    cfg8 = get_config("toy-8m")
    p8 = init_params(jax.random.PRNGKey(0), cfg8, dtype=jnp.float32)
    q8 = quantize_params_int4(p8)
    assert isinstance(q8["layers"]["w_gate"], QuantInt8)
    assert isinstance(q8["layers"]["w_down"], QuantInt8)
    assert isinstance(q8["layers"]["wq"], QuantInt4)
    assert (q8["layers"]["wq"].group_in,
            q8["layers"]["wq"].block_out) == (256, 256)


def test_forward_logit_delta_int4_vs_int8_vs_full():
    """The numerics VERDICT r4 asked for: quantify the int4 logit error
    against int8 and full precision on a real forward pass. Group-wise
    int4 must stay within a small multiple of int8's error."""
    from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                         init_params)
    from ai_agent_kubectl_tpu.ops.quant import quantize_params_int8

    cfg = get_config("toy-8m", **INT4_TOY)
    params = init_params(jax.random.PRNGKey(5), cfg, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 16), 0,
                                cfg.vocab_size)
    positions = jnp.arange(16)[None, :]

    def run(p):
        cache = KVCache.zeros(cfg, 1, 32, dtype=jnp.float32)
        logits, _ = forward(p, cfg, tokens, positions, cache, kv_limit=32)
        return np.asarray(logits)

    full = run(params)
    l8 = run(quantize_params_int8(params))
    l4 = run(quantize_params_int4(params))
    scale = np.abs(full).max()
    err8 = np.abs(l8 - full).max() / scale
    err4 = np.abs(l4 - full).max() / scale
    # Measured on this worst case (i.i.d. gaussian init — no structure
    # for 15 levels to exploit, and error compounds through all 4 layers
    # + head): err8 ~0.019, err4 ~0.37 with group-512 scales (group 128
    # measured 0.31 — group size barely moves gaussian absmax, which is
    # why 512 stays the default; trained checkpoints, the real target,
    # are the favorable case for weight-only int4). The asserts pin the
    # measured envelope so a packing/scale regression shows up as an
    # order-of-magnitude jump, not a flaky threshold.
    assert err8 < 0.05, f"int8 logit rel err {err8}"
    assert err4 < 0.5, f"int4 logit rel err {err4}"


async def test_engine_serves_int4_end_to_end():
    """QUANT=int4 through the real batched serving path (CPU: the XLA
    fallback computes the same math the kernel runs on TPU)."""
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine

    cfg = get_config("toy-8m", **INT4_TOY)
    eng = BatchedJaxEngine(
        cfg, dtype="float32", quant="int4", max_seq_len=128,
        prefill_buckets=(64,), batch_size=2, chunk_len=4,
        prefix_cache=False,
    )
    await eng.start()
    try:
        r = await eng.generate("list the pods", max_tokens=6,
                               temperature=0.0)
        assert r.completion_tokens > 0
        r2 = await eng.generate("list the pods", max_tokens=6,
                                temperature=0.0)
        assert r.text == r2.text      # greedy determinism under int4
    finally:
        await eng.stop()
