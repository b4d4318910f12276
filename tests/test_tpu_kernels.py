"""TPU-gated compiled-kernel parity tests (VERDICT r2 weak #7).

The regular suite runs the Pallas kernels in interpret mode on CPU, which
hides Mosaic tiling/layout regressions; these tests run the COMPILED
kernels on a real chip against the dense reference.

Run on the bench chip:  RUN_TPU_TESTS=1 python -m pytest tests/test_tpu_kernels.py -q
(Skipped everywhere else.)
"""

import os

import pytest

_on_tpu = False
if os.environ.get("RUN_TPU_TESTS") == "1":
    import jax

    _on_tpu = jax.default_backend() == "tpu"

pytestmark = pytest.mark.skipif(
    not _on_tpu,
    reason="TPU-only: set RUN_TPU_TESTS=1 on a TPU host",
)


def _rand(shape, seed, dtype):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def test_compiled_flash_matches_dense_prefill_shapes():
    """Compiled Mosaic flash kernel vs dense on real bucket shapes
    (suffix prefill b64 @ kv384 and full-bucket 256) — catches tiling
    regressions the interpreter hides."""
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops.attention import dense_attention
    from ai_agent_kubectl_tpu.ops.flash_attention import flash_attention_cached

    for (S, KVLEN, off) in ((64, 384, 273), (256, 256, 0)):
        B, H, KV, hd = 2, 8, 1, 256
        q = _rand((B, S, H, hd), 0, jnp.bfloat16)
        k = _rand((B, KVLEN, KV, hd), 1, jnp.bfloat16)
        v = _rand((B, KVLEN, KV, hd), 2, jnp.bfloat16)
        positions = jnp.broadcast_to(off + jnp.arange(S), (B, S)).astype(
            jnp.int32)

        out = flash_attention_cached(q, k, v, positions, interpret=False)

        kv_pos = jnp.arange(KVLEN)[None, None, :]
        mask = kv_pos <= positions[:, :, None]
        ref = dense_attention(q, k, v, mask)
        np.testing.assert_allclose(
            np.asarray(out).astype(np.float32),
            np.asarray(ref).astype(np.float32), rtol=3e-2, atol=3e-2)


def test_quant_attention_reads_int8_kv_without_materializing():
    """The r5 serving contract for KV_QUANT=int8
    (ops/attention.py::dense_attention_quant): the int8 payload feeds the
    attention dots directly — scales commute onto scores/probs — so no
    ENTRY-level instruction may materialize a full-precision copy of the
    context, and the outputs must match dequantize-then-attend."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops.attention import (dense_attention,
                                                    dense_attention_quant)
    from ai_agent_kubectl_tpu.ops.quant import kv_dequantize, kv_quantize

    B, S, KV, hd, H = 48, 192, 16, 256, 16
    k = kv_quantize(_rand((B, S, KV, hd), 10, jnp.float32))
    v = kv_quantize(_rand((B, S, KV, hd), 11, jnp.float32))
    q = _rand((B, 1, H, hd), 12, jnp.bfloat16)
    positions = jnp.full((B, 1), S - 1, jnp.int32)
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]

    fn = jax.jit(lambda q, kq, ks, vq, vs, m:
                 dense_attention_quant(q, kq, ks, vq, vs, m))
    out = fn(q, k.q, k.s, v.q, v.s, mask)
    ref = dense_attention(q, kv_dequantize(k, q.dtype),
                          kv_dequantize(v, q.dtype), mask)
    np.testing.assert_allclose(
        np.asarray(out).astype(np.float32),
        np.asarray(ref).astype(np.float32), rtol=3e-2, atol=3e-2)

    hlo = fn.lower(q, k.q, k.s, v.q, v.s, mask).compile().as_text()
    entry = hlo.split("ENTRY")[-1]
    materialized = [
        line.strip() for line in entry.splitlines()
        if (f"= bf16[{B},{S},{KV},{hd}]" in line
            or f"= f32[{B},{S},{KV},{hd}]" in line)
        and "parameter" not in line
    ]
    assert not materialized, (
        "quant attention materialized a full-precision context copy:\n"
        + "\n".join(materialized)
    )


def test_compiled_int4_kernel_matches_xla_fallback():
    """The compiled packed-nibble Pallas matmul (ops/quant4.py) must
    compute the XLA fallback's group-wise math on the chip — the parity
    that licenses QUANT=int4 as a served feature."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops.quant4 import (_xla_int4_matmul,
                                                 qmatmul4, quantize_int4)

    w = _rand((1024, 512), 20, jnp.float32) * 0.05
    x = _rand((48, 1024), 21, jnp.bfloat16)
    qw = quantize_int4(jnp.asarray(w))
    out = jax.jit(qmatmul4)(x, qw)          # compiled Pallas on TPU
    ref = _xla_int4_matmul(x, qw)
    np.testing.assert_allclose(
        np.asarray(out).astype(np.float32),
        np.asarray(ref).astype(np.float32), rtol=2e-2, atol=2e-2)


def test_int8_convert_fuses_into_weight_read():
    """The int8→bf16 convert in qmatmul must fuse into the dot's weight
    read — a materialized bf16 copy of the weight in the ENTRY computation
    would forfeit the whole bandwidth win (ADVICE r3, ops/quant.py). The
    check: no ENTRY-level instruction in the compiled HLO produces a bf16
    tensor of the full weight shape."""
    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.ops.quant import qmatmul, quantize_int8

    IN, OUT, B = 2048, 4096, 32
    w = quantize_int8(_rand((IN, OUT), 7, jnp.float32))
    x = _rand((B, IN), 8, jnp.bfloat16)

    hlo = jax.jit(qmatmul).lower(x, w).compile().as_text()
    entry = hlo.split("ENTRY")[-1]
    materialized = [
        line.strip() for line in entry.splitlines()
        if f"= bf16[{IN},{OUT}]" in line and "parameter" not in line
    ]
    assert not materialized, (
        "int8 weight convert materialized a full bf16 weight copy:\n"
        + "\n".join(materialized)
    )


# ------------------------------------------- block-pool kernels (PR 21)
#
# The kernel that reads the shared block pool through per-slot tables —
# it carries the whole pool serving path on TPU — at the geometry
# chip_smoke.py serves: Llama-3-8B heads (H 32, KV 8, hd 128), the page
# floor a TPU engine serves (64), the per-slot table of
# MAX_SEQ_LEN=1024, and the same model's tp=4 shard (H 8, KV 2).

_POOL_GEOMETRIES = ((32, 8), (8, 2), (32, 4))      # (H, KV); Keye's last
_POOL_HD, _POOL_PAGE, _POOL_PAGES = 128, 64, 17
#: every window width the engine warms a ragged program for: decode (1),
#: a spec verify window (k+1 = 4), and the default PREFILL_BUCKETS.
_WARMED_WIDTHS = (1, 4, 64, 128, 256, 512, 1024)


def _pool_case(H, KV, W, spans, seed, pages=_POOL_PAGES):
    """A block pool plus per-slot tables (``pages`` wide) for ``spans`` =
    [(position, q_len), ...]: every slot maps exactly the pages its live rows need
    and the sentinel elsewhere, slot 1 shares slot 0's first block (a
    radix-shared prefix page), and the block the sentinel clamps to is
    NaN in the pool the kernel reads — a fetch that escaped the
    dead-page clamp poisons the output. Returns the kernel operands and
    a NaN-free copy of the pool for the reference."""
    import jax.numpy as jnp
    import numpy as np

    N = len(spans)
    n_blocks = N * pages + 1
    k = _rand((n_blocks, _POOL_PAGE, KV, _POOL_HD), seed, jnp.bfloat16)
    v = _rand((n_blocks, _POOL_PAGE, KV, _POOL_HD), seed + 1, jnp.bfloat16)
    q = _rand((N, W, H, _POOL_HD), seed + 2, jnp.bfloat16)
    tables = np.full((N, pages), n_blocks + 5, np.int32)
    for n, (pos, q_len) in enumerate(spans):
        live = -(-(pos + max(q_len, 1)) // _POOL_PAGE)
        tables[n, :live] = n * pages + np.arange(live)
    tables[1, 0] = tables[0, 0]
    dead = n_blocks - 1
    clean = (k.at[dead].set(0), v.at[dead].set(0))
    poisoned = (k.at[dead].set(jnp.nan), v.at[dead].set(jnp.nan))
    positions = jnp.asarray([s[0] for s in spans], jnp.int32)
    q_lens = jnp.asarray([s[1] for s in spans], jnp.int32)
    return q, poisoned, clean, q_lens, positions, jnp.asarray(tables)


def _gather_reference(q, k, v, q_lens, positions, tables):
    """The dense gather path (models/transformer.py::_pool_gather +
    dense_attention, causal-in-window mask) in float32."""
    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.models.transformer import _pool_gather
    from ai_agent_kubectl_tpu.ops.attention import dense_attention

    W, pages = q.shape[1], tables.shape[1]
    kv_len = pages * _POOL_PAGE
    cols = jnp.arange(W)[None, :, None]
    kv_pos = jnp.arange(kv_len)[None, None, :]
    mask = jnp.logical_and(kv_pos <= positions[:, None, None] + cols,
                           cols < q_lens[:, None, None])
    with jax.default_matmul_precision("highest"):
        return dense_attention(
            q.astype(jnp.float32),
            _pool_gather(k, tables, pages).astype(jnp.float32),
            _pool_gather(v, tables, pages).astype(jnp.float32),
            mask)


@pytest.mark.parametrize("H,KV", _POOL_GEOMETRIES)
@pytest.mark.parametrize("W", _WARMED_WIDTHS)
def test_compiled_ragged_pool_matches_gather(H, KV, W):
    """Compiled ragged kernel vs the dense gather reference, one call
    carrying a decode row, a spec verify window, a fresh full-span
    prefill, a frozen slot, a partial span behind a shared prefix page
    (its last query tile half empty) and a full span at an unaligned
    offset — at every window width the engine warms."""
    import numpy as np

    from ai_agent_kubectl_tpu.ops.ragged_attention import \
        ragged_attention_pool

    spans = [(700, 1), (333, min(W, 4)), (0, W), (700, 0),
             (_POOL_PAGE, W - W // 3), (37, W)]
    q, (k, v), clean, q_lens, positions, tables = _pool_case(
        H, KV, W, spans, seed=30)
    out = np.asarray(ragged_attention_pool(
        q, k, v, q_lens, positions, tables, page_size=_POOL_PAGE,
        interpret=False)).astype(np.float32)
    assert np.isfinite(out).all(), "a dead page leaked into the output"
    ref = np.asarray(_gather_reference(q, *clean, q_lens, positions,
                                       tables))
    for n, (_pos, q_len) in enumerate(spans):
        np.testing.assert_allclose(
            out[n, :q_len], ref[n, :q_len], rtol=3e-2, atol=3e-2,
            err_msg=f"slot {n} (q_len={q_len}, W={W})")
        assert not out[n, q_len:].any(), f"slot {n}: padding rows not zero"


@pytest.mark.parametrize("W", (1, 64, 1024))
def test_compiled_ragged_stacked_pool_layer_equals_layer_slice(W):
    """ISSUE 25: the COMPILED kernel handed the stacked pool and a layer
    index (as the layer scan hands it the carried pool) returns, bit for
    bit, what it returns for that layer alone — for the first, a middle
    and the last layer of a stack whose other layers are NaN."""
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops.ragged_attention import \
        ragged_attention_pool

    H, KV = _POOL_GEOMETRIES[0]
    spans = [(700, 1), (333, min(W, 4)), (0, W), (700, 0)]
    q, (k, v), _clean, q_lens, positions, tables = _pool_case(
        H, KV, W, spans, seed=50)
    alone = np.asarray(ragged_attention_pool(
        q, k, v, q_lens, positions, tables, page_size=_POOL_PAGE,
        interpret=False).astype(jnp.float32))
    L = 3
    for layer in range(L):
        nan = jnp.full_like(k, jnp.nan)
        sk = jnp.stack([k if i == layer else nan for i in range(L)])
        sv = jnp.stack([v if i == layer else nan for i in range(L)])
        out = np.asarray(ragged_attention_pool(
            q, sk, sv, q_lens, positions, tables, jnp.int32(layer),
            page_size=_POOL_PAGE, interpret=False).astype(jnp.float32))
        np.testing.assert_array_equal(out, alone)


@pytest.mark.parametrize("H,KV", _POOL_GEOMETRIES)
@pytest.mark.parametrize("pages", (_POOL_PAGES, 64, 257))
def test_compiled_ragged_pool_decode_batch_matches_gather(H, KV, pages):
    """Compiled ragged kernel at q_len = 1 vs the dense gather reference
    over a full batch of ragged positions (first row of a sequence, page
    edges, the last row the table can hold), at a table the page block
    does not divide (17) and at MAX_SEQ_LEN 4096's width (64); and at the
    long-log cell's 257-page table with 100-245 live pages a slot (13-31
    full blocks: the ring's buffers reused all the way)."""
    import numpy as np

    from ai_agent_kubectl_tpu.ops.ragged_attention import \
        ragged_attention_pool

    rng = np.random.RandomState(1)
    if pages == 257:
        spans = [(int(p), 1) for p in rng.randint(
            100 * _POOL_PAGE, 245 * _POOL_PAGE, 16)]
    else:
        edge = [0, _POOL_PAGE - 1, _POOL_PAGE, pages * _POOL_PAGE - 1]
        spans = [(int(p), 1) for p in edge + list(
            rng.randint(0, pages * _POOL_PAGE, 32 - len(edge)))]
    q, (k, v), clean, q_lens, positions, tables = _pool_case(
        H, KV, 1, spans, seed=40, pages=pages)
    out = np.asarray(ragged_attention_pool(
        q, k, v, q_lens, positions, tables, page_size=_POOL_PAGE,
        interpret=False)).astype(np.float32)
    assert np.isfinite(out).all(), "a dead page leaked into the output"
    ref = np.asarray(_gather_reference(q, *clean, q_lens, positions,
                                       tables))
    np.testing.assert_allclose(out, ref, rtol=3e-2, atol=3e-2)


def test_compiled_gated_delta_step_matches_the_jnp_step_and_skips_still_rows():
    """ops/gated_delta.py's step kernel COMPILED, at olmo-hybrid-7b's head
    geometry, on plane 1 of a three-plane leaf under jit with the leaf donated:
    the rows that move equal the ``jnp`` step to float32 rounding; the rows
    that do not (first, between and last; and every row) keep their state bit
    for bit, through grid steps that name another row's block again, which the
    interpreter does not model: and the other planes are untouched."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    B, H, dk, dv = 8, 30, 96, 192
    r = np.random.default_rng(0)
    q = GD.l2_normalize(r.normal(size=(B, 1, H, dk)), dk ** -0.5)
    k = GD.l2_normalize(r.normal(size=(B, 1, H, dk)))
    v = jnp.asarray(r.normal(size=(B, 1, H, dv)), jnp.float32)
    leaf0 = r.normal(size=(3, B, dk, H * dv)).astype(np.float32)
    step = jax.jit(GD.gated_delta_step_kernel, static_argnums=8, donate_argnums=5)
    for moves, hb in (("11111111", 0), ("01101100", 0), ("00000000", 0),
                      ("10000001", 30), ("00010000", 2)):
        live = np.asarray([c == "1" for c in moves])
        g = jnp.asarray(np.where(live[:, None, None], -r.uniform(1e-3, 0.7, (B, 1, H)), 0.0),
                        jnp.float32)
        beta = jnp.asarray(np.where(live[:, None, None], r.uniform(0.1, 2.0, (B, 1, H)), 0.0),
                           jnp.float32)
        want_o, want_S = GD.gated_delta_step(q, k, v, g, beta, jnp.asarray(leaf0[1]))
        o, out = step(q, k, v, g, beta, jnp.asarray(leaf0), jnp.asarray(1, jnp.int32), None, hb)
        o, out = np.asarray(o), np.asarray(out)
        np.testing.assert_allclose(o[live], np.asarray(want_o)[live], rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(out[1][live], np.asarray(want_S)[live], rtol=2e-5, atol=2e-6)
        assert not o[~live].any(), moves
        np.testing.assert_array_equal(out[1][~live], leaf0[1][~live])
        np.testing.assert_array_equal(out[::2], leaf0[::2])


def test_compiled_window_scan_matches_the_float64_recurrence():
    """ops/gated_delta.py::gated_delta_scan COMPILED (ISSUE 47: its
    unit-triangular inverse by blocks, every product through the MXU at the
    highest precision), at olmo-hybrid-7b's head sizes and four heads, against
    tests/test_linear_attention.py's float64 recurrence: random keys over rows
    of 150, 64 and no tokens (the last keeps its state bit for bit) at that
    file's tolerance (the chip reads 8e-7), and a chunk of nearly equal keys
    written at ``beta`` 1.8-2.0 at twice what the chip reads there: an atol of
    5.8e-5 at rtol 2e-4 by blocks as by ``solve_triangular`` before (the
    MXU's six-pass float32 products are the error on the chip, not how ``T``
    is made; the CPU's exact float32 reads 3.1e-5)."""
    import jax
    import numpy as np
    from test_linear_attention import recurrence, scan_inputs, worst_case_inputs

    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    sizes = dict(H=4, dk=96, dv=192)
    for a, q_lens, atol in (
            (scan_inputs(0, 3, 150, [150, 64, 0], **sizes), [150, 64, 0], 2e-5),
            (worst_case_inputs(0, 2, 64, **sizes), [64, 64], 1.2e-4)):
        want_o, want_S = recurrence(**a)
        o, S1 = jax.jit(GD.gated_delta_scan)(*a.values())
        for b, n in enumerate(q_lens):
            np.testing.assert_allclose(np.asarray(o)[b, :n], want_o[b, :n],
                                       rtol=2e-4, atol=atol)
            if n == 0:
                np.testing.assert_array_equal(np.asarray(S1)[b], np.asarray(a["S0"])[b])
        np.testing.assert_allclose(np.asarray(S1), want_S, rtol=2e-4, atol=atol)


def test_compiled_step_and_scan_take_sixteen_key_heads_for_thirty_two_value_heads():
    """ISSUE 55's geometry COMPILED (qwen3-next-80b-a3b-instruct-l12: 16 key heads
    for 32 value heads of 128 x 128, one lane tile a head, [128, 2,048] blocks of
    16 heads): the step kernel on plane 1 of a leaf with still rows between moving
    ones against the ``jnp`` step given q and k REPEATED, and the chunked scan over
    rows of 300, 64 and no tokens (its ``K K^T`` and ``Q K^T`` made once a key
    head) against tests/test_linear_attention.py's float64 recurrence given the
    same."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from test_linear_attention import recurrence, scan_inputs

    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    B, Hk, H, dk, dv = 16, 16, 32, 128, 128
    assert GD._block_heads(H, dk, dv, 4) == 16
    r = np.random.default_rng(0)
    q = GD.l2_normalize(r.normal(size=(B, 1, Hk, dk)), dk ** -0.5)
    k = GD.l2_normalize(r.normal(size=(B, 1, Hk, dk)))
    v = jnp.asarray(r.normal(size=(B, 1, H, dv)), jnp.float32)
    leaf0 = r.normal(size=(3, B, dk, H * dv)).astype(np.float32)
    live = np.asarray([c == "1" for c in "0110110011111101"])
    g = jnp.asarray(np.where(live[:, None, None], -r.uniform(1e-3, 0.7, (B, 1, H)), 0.0),
                    jnp.float32)
    beta = jnp.asarray(np.where(live[:, None, None], r.uniform(0.1, 1.0, (B, 1, H)), 0.0),
                       jnp.float32)
    want_o, want_S = GD.gated_delta_step(jnp.repeat(q, 2, axis=2), jnp.repeat(k, 2, axis=2),
                                         v, g, beta, jnp.asarray(leaf0[1]))
    step = jax.jit(GD.gated_delta_step_kernel, static_argnums=8, donate_argnums=5)
    o, out = step(q, k, v, g, beta, jnp.asarray(leaf0), jnp.asarray(1, jnp.int32), None, 0)
    o, out = np.asarray(o), np.asarray(out)
    np.testing.assert_allclose(o[live], np.asarray(want_o)[live], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(out[1][live], np.asarray(want_S)[live], rtol=2e-5, atol=2e-6)
    assert not o[~live].any()
    np.testing.assert_array_equal(out[1][~live], leaf0[1][~live])
    np.testing.assert_array_equal(out[::2], leaf0[::2])

    a = scan_inputs(1, 3, 300, [300, 64, 0], H=8, dk=dk, dv=dv)
    a["q"], a["k"] = a["q"][:, :, ::2], a["k"][:, :, ::2]
    want_o, want_S = recurrence(**dict(a, q=np.repeat(a["q"], 2, axis=2),
                                       k=np.repeat(a["k"], 2, axis=2)))
    o, S1 = jax.jit(GD.gated_delta_scan)(*a.values())
    worst = 0.0
    for b, n in enumerate([300, 64, 0]):
        np.testing.assert_allclose(np.asarray(o)[b, :n], want_o[b, :n], rtol=2e-4, atol=1.2e-4)
        worst = max(worst, float(np.abs(np.asarray(o)[b, :n] - want_o[b, :n]).max(initial=0)))
    np.testing.assert_allclose(np.asarray(S1), want_S, rtol=2e-4, atol=1.2e-4)
    np.testing.assert_array_equal(np.asarray(S1)[2], np.asarray(a["S0"])[2])
    _record("step_and_scan_16_key_heads_for_32", scan_worst_abs=worst)


@pytest.mark.parametrize("sizes", ["qwen3next", "olmo"])
def test_compiled_gated_delta_window_matches_the_scan_and_the_float64_recurrence(sizes):
    """ops/gated_delta_window.py's kernel COMPILED (ISSUE 56) at the two
    configurations' own head sizes (32 value heads over 16 key heads of 128 x 128,
    a key head's two value heads a pair; 30 over 30 of 96 x 192, a 192-wide head
    with its neighbour), on plane 1 of a three-plane leaf: rows of 300, 64, 1 and
    no tokens in one 320-wide window (five chunks; the last keeps its state bit
    for bit, outputs past ``q_len`` are zeros, the other planes untouched) against
    the float64 recurrence and against the compiled ``gated_delta_scan``, and a
    chunk of nearly equal keys written at ``beta`` 1.8-2.0 at the tolerance the
    scan has there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from test_linear_attention import recurrence, scan_inputs, worst_case_inputs

    from ai_agent_kubectl_tpu.ops import gated_delta as GD
    from ai_agent_kubectl_tpu.ops import gated_delta_window as GW

    H, Hk, dk, dv = (32, 16, 128, 128) if sizes == "qwen3next" else (30, 30, 96, 192)
    r, q_lens = H // Hk, [300, 64, 1, 0]
    a = scan_inputs(2, 4, 320, q_lens, H=H, dk=dk, dv=dv)
    a["q"], a["k"] = a["q"][:, :, ::r], a["k"][:, :, ::r]
    leaf0 = np.random.default_rng(3).normal(size=(3,) + a.pop("S0").shape).astype(np.float32)
    want_o, want_S = recurrence(**dict(a, q=np.repeat(a["q"], r, axis=2),
                                       k=np.repeat(a["k"], r, axis=2)), S0=leaf0[1])
    scan_o, scan_S = jax.jit(GD.gated_delta_scan)(*a.values(), jnp.asarray(leaf0[1]))
    window = jax.jit(GW.gated_delta_window, donate_argnums=5)
    o, out = window(*a.values(), jnp.asarray(leaf0), jnp.asarray(1, jnp.int32),
                    jnp.asarray(q_lens, jnp.int32))
    o, out = np.asarray(o), np.asarray(out)
    worst = {"o": 0.0, "o_scan": 0.0}
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(o[b, :n], want_o[b, :n], rtol=2e-4, atol=1.2e-4)
        worst["o"] = max(worst["o"], float(np.abs(o[b, :n] - want_o[b, :n]).max(initial=0)))
        worst["o_scan"] = max(worst["o_scan"], float(
            np.abs(np.asarray(scan_o)[b, :n] - want_o[b, :n]).max(initial=0)))
        assert not o[b, n:].any()
    np.testing.assert_allclose(out[1], want_S, rtol=2e-4, atol=1.2e-4)
    np.testing.assert_array_equal(out[1, 3], leaf0[1, 3])
    np.testing.assert_array_equal(out[::2], leaf0[::2])
    w = worst_case_inputs(0, 2, 64, H=4, dk=dk, dv=dv)
    hard_o, hard_S = recurrence(**w)
    hard = w.pop("S0")[None]
    o2, out2 = jax.jit(GW.gated_delta_window)(*w.values(), hard, 0)
    np.testing.assert_allclose(np.asarray(o2), hard_o, rtol=2e-4, atol=1.2e-4)
    np.testing.assert_allclose(np.asarray(out2[0]), hard_S, rtol=2e-4, atol=1.2e-4)
    _record("gated_delta_window", sizes=sizes, worst_abs=worst["o"],
            scan_worst_abs=worst["o_scan"],
            state_worst_abs=float(np.abs(out[1] - want_S).max()),
            hard_worst_abs=float(np.abs(np.asarray(o2) - hard_o).max()))


def _record(name, **readings):
    """What the chip read, beside the verdict: chiprun_out/kernel_parity.jsonl."""
    import json
    from pathlib import Path

    out = Path(__file__).resolve().parent.parent / "chiprun_out"
    out.mkdir(exist_ok=True)
    with open(out / "kernel_parity.jsonl", "a") as f:
        f.write(json.dumps({"test": name, **readings}) + "\n")


def test_compiled_step_kernel_takes_a_decay_a_key_channel():
    """ops/gated_delta.py's step kernel COMPILED through its ``channel`` branch
    (ISSUE 48: ``g`` [B, 1, H, 128], a decay a KEY CHANNEL, multiplying the rows
    of a head's tile), at ling-3.0-flash-vl-l12's geometry (16 rows, 32 heads
    of 128 x 128) on plane 1 of a three-plane leaf under jit with the leaf
    donated, three tokens running: the rows that move equal the ``jnp`` step
    to float32 rounding and tests/test_kda_latent.py's float64 recurrence at
    that file's tolerance, a row with EVERY channel at the floor of -5 among
    them; the rows that do not keep their state bit for bit and read zeros;
    the other planes are untouched."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from test_kda_latent import recurrence, scan_inputs

    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    B, H, dk, dv, T = 16, 32, 128, 128, 3
    moves = "1011111111101101"
    live = np.asarray([c == "1" for c in moves])
    a = scan_inputs(48, B, T, [T if m else 0 for m in live], H=H, dk=dk, dv=dv)
    a["g"] = a["g"].at[2].set(-5.0)
    want_o, want_S = recurrence(**a)
    r = np.random.default_rng(1)
    leaf0 = r.normal(size=(3, B, dk, H * dv)).astype(np.float32)
    leaf0[1] = np.asarray(a["S0"])
    step = jax.jit(GD.gated_delta_step_kernel, static_argnums=8, donate_argnums=5)
    leaf, S, worst = jnp.asarray(leaf0), a["S0"], [0.0, 0.0]
    for t in range(T):
        one = [a[n][:, t:t + 1] for n in ("q", "k", "v", "g", "beta")]
        o_j, S = GD.channel_decay_step(*one, S)
        o_k, leaf = step(*one, leaf, jnp.asarray(1, jnp.int32), None, 0)
        o_k = np.asarray(o_k)
        worst[0] = max(worst[0], float(np.abs(o_k[live] - np.asarray(o_j)[live]).max()))
        worst[1] = max(worst[1], float(np.abs(o_k[live, 0] - want_o[live, t]).max()))
        np.testing.assert_allclose(o_k[live], np.asarray(o_j)[live], rtol=2e-5, atol=2e-6)
        np.testing.assert_allclose(o_k[live, 0], want_o[live, t], rtol=2e-4, atol=2e-5)
        assert not o_k[~live].any()
    out = np.asarray(leaf)
    _record("step_kernel_channel", o_against_jnp=worst[0], o_against_float64=worst[1],
            state_against_float64=float(np.abs(out[1] - want_S).max()))
    np.testing.assert_allclose(out[1][live], want_S[live], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(out[1][~live], leaf0[1][~live])
    np.testing.assert_array_equal(out[::2], leaf0[::2])


@pytest.mark.parametrize("at_floor", (0.5, 1.0))
def test_compiled_channel_decay_scan_matches_the_float64_recurrence(at_floor):
    """ops/gated_delta.py::channel_decay_scan COMPILED at the window the cell
    runs (16 rows of 512, taken four at a time; heads of 128 x 128, four of
    them) from an initial state, rows of unequal lengths across the 64-row
    chunks and 16-row blocks, one empty (its state kept bit for bit), with
    half or ALL of the channels at the floor of -5: against
    tests/test_kda_latent.py's float64 recurrence. The MXU's six-pass float32
    products are the error here (``gated_delta_scan`` reads 5.8e-5 on its
    worst chunk); the CPU's exact float32 meets 2e-5."""
    import jax
    import numpy as np
    from test_kda_latent import recurrence, scan_inputs

    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    q_lens = [512, 500, 452, 131, 70, 3, 0, 64, 65, 16, 17, 480, 256, 300, 1, 511]
    a = scan_inputs(7, 16, 512, q_lens, at_floor=at_floor, H=4, dk=128, dv=128)
    assert float(a["g"].min()) == -5.0
    want_o, want_S = recurrence(**a)
    o, S1 = jax.jit(GD.channel_decay_scan)(*a.values())
    o, S1 = np.asarray(o), np.asarray(S1)
    assert np.isfinite(o).all() and np.isfinite(S1).all()
    _record("channel_decay_scan", at_floor=at_floor,
            o=max(float(np.abs(o[b, :n] - want_o[b, :n]).max()) for b, n in enumerate(q_lens) if n),
            state=float(np.abs(S1 - want_S).max()))
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(o[b, :n], want_o[b, :n], rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(S1, want_S, rtol=2e-4, atol=1e-4)
    np.testing.assert_array_equal(S1[6], np.asarray(a["S0"])[6])


@pytest.mark.parametrize("G,chunk", [(8, 128), (1, 256)], ids=["nemotron", "granite"])
def test_compiled_ssd_window_matches_the_scan_and_the_float64_recurrence(G, chunk):
    """ops/ssd_scan.py's window kernel COMPILED (ISSUE 54) at both configurations'
    geometry (64 heads of 64 x 128 in 8 groups or in ONE), on plane 1 of a
    three-plane leaf under jit with the leaf donated: a 16 x 512 window whose rows
    end across the chunks' edges (one whole, one empty between two that move, one
    of a single token) and an eager 1 x 512 piece, from a non-zero state. The rows
    that brought tokens equal ``ssd_scan`` at the configuration's own chunk to the
    MXU's six-pass float32 rounding, and one row's first 40 tokens of four heads
    the float64 recurrence; a row that brought none keeps its state bit for bit
    (through grid steps that name another row's block again, which the interpreter
    does not model), the other planes are untouched, outputs past ``q_len`` are
    zeros."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    H, P, N = 64, 64, 128
    r = np.random.default_rng(1)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    A, D = f32(-r.uniform(0.5, 16.0, H)), f32(r.normal(size=H))
    window = jax.jit(S.ssd_window, static_argnums=8, donate_argnums=6)
    for B, q_lens in ((16, [512, 500, 0, 131, 70, 3, 0, 64, 129, 128, 127, 480, 256, 300, 1, 0]),
                      (16, [0] * 16), (1, [437])):
        W = 512
        q = np.asarray(q_lens)
        live = q > 0
        x = f32(r.normal(size=(B, W, H, P)))
        dt = f32(np.where(np.arange(W)[None, :, None] < q[:, None, None],
                          r.uniform(0.001, 0.1, (B, W, H)), 0.0))
        Bm, Cm = f32(r.normal(size=(B, W, G, N))), f32(r.normal(size=(B, W, G, N)))
        leaf0 = r.normal(size=(3, B, H, P, N)).astype(np.float32)
        want_y, want_h = S.ssd_scan(x, dt, A, Bm, Cm, D, jnp.asarray(leaf0[1]), chunk)
        for given in (jnp.asarray(q, jnp.int32), None):
            y, out = window(x, dt, A, Bm, Cm, D, jnp.asarray(leaf0),
                            jnp.asarray(1, jnp.int32), chunk, given)
            y, out = np.asarray(y), np.asarray(out)
            assert np.isfinite(y).all() and np.isfinite(out).all()
            for b, n in enumerate(q):
                np.testing.assert_allclose(y[b, :n], np.asarray(want_y)[b, :n],
                                           rtol=2e-4, atol=2e-3)
                assert not y[b, n:].any()
            np.testing.assert_allclose(out[1][live], np.asarray(want_h)[live],
                                       rtol=2e-4, atol=2e-4)
            np.testing.assert_array_equal(out[1][~live], leaf0[1][~live])
            np.testing.assert_array_equal(out[::2], leaf0[::2])
        if live.any():
            _record("ssd_window", groups=G, rows=B,
                    y_against_scan=max(float(np.abs(y[b, :n] - np.asarray(want_y)[b, :n]).max())
                                       for b, n in enumerate(q) if n),
                    state_against_scan=float(np.abs(out[1][live] - np.asarray(want_h)[live]).max()))
    # one row's head of the window against the recurrence in float64
    from test_hybrid_model import recurrence
    heads = slice(0, 64, 16)
    groups = np.arange(64)[heads] // (64 // G)
    a64 = [np.asarray(a, np.float64) for a in
           (x[:1, :40, heads], dt[:1, :40, heads], A[heads], Bm[:1, :40, groups],
            Cm[:1, :40, groups], D[heads], leaf0[1][:1, heads])]
    want64 = np.stack([recurrence(a64[0][:, :, i:i + 1], a64[1][:, :, i:i + 1], a64[2][i:i + 1],
                                  a64[3][:, :, i:i + 1], a64[4][:, :, i:i + 1], a64[5][i:i + 1],
                                  a64[6][:, i:i + 1])[0][0, :, 0] for i in range(4)], axis=1)
    y1, _ = window(x[:1], dt[:1], A, Bm[:1], Cm[:1], D, jnp.asarray(leaf0[:, :1]),
                   jnp.asarray(1, jnp.int32), chunk, None)
    np.testing.assert_allclose(np.asarray(y1)[0, :40, heads], want64, rtol=2e-4, atol=2e-3)


def test_compiled_ssd_step_matches_the_jnp_step_and_skips_still_rows():
    """ops/ssd_scan.py's step kernel COMPILED (ISSUE 49), at nemotron-3-nano-30b-
    a3b's geometry (16 rows, 64 heads of 64 x 128 in 8 groups), on plane 1 of a
    three-plane leaf under jit with the leaf donated: the rows that move equal
    ``ssd_step`` to float32 rounding (the output a lane sum of 128 products
    against the MXU's six-pass einsum); the rows that do not (first, between and
    last; and every row) keep their state bit for bit, through grid steps that
    name another row's block again, which the interpreter does not model, and
    read zeros; the other planes are untouched; bf16 inputs, as the mixer hands
    them, give a bf16 output within an ulp."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    B, H, P, G, N = 16, 64, 64, 8, 128
    r = np.random.default_rng(0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(r.normal(size=(B, 1, H, P)))
    Bm, Cm = f32(r.normal(size=(B, 1, G, N))), f32(r.normal(size=(B, 1, G, N)))
    A, D = f32(-r.uniform(0.5, 4.0, H)), f32(r.normal(size=H))
    leaf0 = r.normal(size=(3, B, H, P, N)).astype(np.float32)
    step = jax.jit(S.ssd_step_kernel, static_argnums=9, donate_argnums=6)
    worst = [0.0, 0.0]
    for moves, hb in (("1" * 16, 0), ("0110110011110101", 0), ("0" * 16, 0),
                      ("1000000000000001", 64), ("0000000100000000", 8)):
        live = np.asarray([c == "1" for c in moves])
        dt = f32(np.where(live[:, None, None], r.uniform(0.01, 0.5, (B, 1, H)), 0.0))
        want_y, want_h = S.ssd_step(x, dt, A, Bm, Cm, D, jnp.asarray(leaf0[1]))
        y, out = step(x, dt, A, Bm, Cm, D, jnp.asarray(leaf0), jnp.asarray(1, jnp.int32),
                      None, hb)
        y, out, want_y, want_h = (np.asarray(a) for a in (y, out, want_y, want_h))
        if live.any():
            worst[0] = max(worst[0], float(np.abs(y[live] - want_y[live]).max()))
            worst[1] = max(worst[1], float(np.abs(out[1][live] - want_h[live]).max()))
        np.testing.assert_allclose(y[live], want_y[live], rtol=2e-5, atol=2e-4)
        np.testing.assert_allclose(out[1][live], want_h[live], rtol=2e-5, atol=2e-6)
        assert not y[~live].any(), moves
        np.testing.assert_array_equal(out[1][~live], leaf0[1][~live])
        np.testing.assert_array_equal(out[::2], leaf0[::2])
    _record("ssd_step_kernel", y_against_jnp=worst[0], state_against_jnp=worst[1])
    bf16 = lambda a: a.astype(jnp.bfloat16)
    y16, _ = step(bf16(x), dt, A, bf16(Bm), bf16(Cm), D, jnp.asarray(leaf0), 1, None, 0)
    want16, _ = S.ssd_step(bf16(x), dt, A, bf16(Bm), bf16(Cm), D, jnp.asarray(leaf0[1]))
    assert y16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(y16.astype(jnp.float32))[live],
                               np.asarray(want16.astype(jnp.float32))[live], rtol=1e-2, atol=1e-2)
