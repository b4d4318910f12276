"""Attention layers of two kinds in one model (ISSUE 40; ``toy-sliding-moe``,
CPU, float32): the program against the plain reference's full forward
(benchmark/configs/laguna-s-2.1-l12.reference.py) with prefill in several
windows then decode through the pool — sequences that cross the span many
times, wrap the ring and cross a page edge mid-span, on both attention paths,
through the packed window and over the seeded int8 tree. The family's other
tests are tests/test_sliding_attention.py; these are the long ones, and under
``--dist loadfile`` a file is one worker's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.transformer import KVCache, forward
from test_sliding_attention import CFG, PAGE, SIZES, params, ref  # noqa: F401


def _through_the_pool(cfg, params, toks, lens, windows, attn_impl, packed=False):
    """Prefill ``toks`` [B, T] (row b has ``lens[b]`` tokens) in ``windows``,
    then decode to the longest row's end, through a pool with no sliding leaf
    given: every position's logits, and the cache as the last call left it."""
    B, T = toks.shape
    pages = -(-T // PAGE)
    pool = (cfg.n_of("*"), B * pages, PAGE, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32),
                    span_rows=jnp.zeros((4,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    got, pos = [[] for _ in range(B)], 0

    def call(tok, positions, cache, mask, q_lens, **kw):
        with jax.default_matmul_precision("highest"):
            return forward(params, cfg, jnp.asarray(tok), jnp.asarray(positions), cache,
                           kv_limit=pages * PAGE, attn_impl=attn_impl,
                           token_mask=jnp.asarray(mask), write_mask=jnp.asarray(mask),
                           block_tables=tables, q_lens=jnp.asarray(q_lens), **kw)

    for W in windows:
        ql = np.array([max(0, min(W, n - pos)) for n in lens], np.int32)
        cols = np.arange(W)[None, :]
        win = np.zeros((B, W), np.int32)
        for b in range(B):
            win[b, :ql[b]] = toks[b, pos:pos + ql[b]]
        positions = (pos + np.broadcast_to(cols, (B, W))).astype(np.int32)
        if packed:
            # the chunk program's entry: only each slot's last valid row's logits
            logits, cache = call(win, positions, cache, cols < ql[:, None], ql,
                                 logits_at=jnp.asarray(np.maximum(ql, 1) - 1),
                                 packed_rows=B * W)
            for b in range(B):
                got[b].append((pos + ql[b] - 1, np.asarray(logits[b, 0])) if ql[b] else None)
        else:
            logits, cache = call(win, positions, cache, cols < ql[:, None], ql)
            for b in range(B):
                got[b].append(np.asarray(logits[b, :ql[b]]))
        pos += W
    for s in range(pos, max(lens)):
        live = np.array([s < n for n in lens])
        logits, cache = call(toks[:, s:s + 1], np.full((B, 1), s, np.int32), cache,
                             live[:, None], live.astype(np.int32))
        for b in range(B):
            if live[b] and not packed:
                got[b].append(np.asarray(logits[b, :1]))
    return got, cache


@pytest.fixture(scope="module")
def sequences(params):
    """Two sequences of 150 and 97 tokens and the reference's logits for them:
    six spans (24) long, past the ring (span + the widest window, in pages of
    8: 88 rows), with windows that start mid-page."""
    toks = np.random.default_rng(0).integers(3, 500, size=(2, 150)).astype(np.int32)
    weights = ref.weights_from_program(params, CFG.n_layers)
    want = [np.asarray(ref.forward(SIZES, weights, jnp.asarray(toks[b]))[0])
            for b in range(2)]
    return toks, [150, 97], want


@pytest.mark.parametrize("attn_impl", ["dense", "ragged"])
def test_program_matches_the_reference_through_windows_and_decode(params, sequences,
                                                                  attn_impl):
    """Windows of 64, 40, 12 and 20 (the third ends mid-page, so the fourth
    starts there) then decode steps, no sliding leaf given: ``forward`` makes
    one for the call's own window and is handed it back. Every position's
    logits equal the reference's full forward; the rings wrapped; the counts
    beside the mask say the sliding layers' decode queries saw the span and
    the full layers' the whole context."""
    toks, lens, want = sequences
    got, cache = _through_the_pool(CFG, params, toks, lens, (64, 40, 12, 20), attn_impl)
    for b, n in enumerate(lens):
        np.testing.assert_allclose(np.concatenate(got[b]), want[b][:n], atol=2e-5)
    assert cache.sk.shape == (CFG.n_of("S"), 2, 88, CFG.n_kv_heads, CFG.head_dim)
    assert cache.k.shape[0] == CFG.n_of("*") == 3      # the pool: full layers only
    rows_s, keys_s, rows_f, keys_f = (int(n) for n in cache.span_rows)
    steps = 150 - 136                                  # decode steps, row 0 alone
    assert rows_s == steps * CFG.n_of("S") and rows_f == steps * CFG.n_of("*")
    assert keys_s == rows_s * CFG.sliding_window
    assert keys_f == CFG.n_of("*") * sum(range(137, 151))


def test_packed_window_rows_match_the_reference(params, sequences):
    """The chunk program's entry (the window's valid rows packed, ISSUE 39):
    each slot's last valid row of every window."""
    toks, lens, want = sequences
    got, _ = _through_the_pool(CFG, params, toks, lens, (64, 40, 32), "ragged", packed=True)
    for b in range(2):
        for entry in filter(None, got[b]):
            at, logits = entry
            np.testing.assert_allclose(logits, want[b][at], atol=2e-5)


def test_seeded_int8_weights_match_the_reference(sequences):
    """The seeded generator's tree (int8 projections of both kinds and of the
    dense layer, bf16 gates of unit variance) through the program, float32
    activations, against the reference over the same dequantised weights."""
    from ai_agent_kubectl_tpu.ops.quant import QuantInt8, random_params_int8

    q = random_params_int8(jax.random.PRNGKey(3), CFG, dtype=jnp.float32,
                           quantize_embed=True)
    for name in ("sw_wq", "sw_wo", "dense_up", "wq"):
        assert isinstance(q["layers"][name], QuantInt8), name
    assert not isinstance(q["layers"]["sw_wg"], QuantInt8)
    gate = np.asarray(q["layers"]["wg"], np.float32)
    assert 0.5 < gate.std() * CFG.dim ** 0.5 < 1.5        # unit-variance gate logits
    toks, lens, _ = sequences
    got, _ = _through_the_pool(CFG, q, toks[:1, :70], [70], (40, 24), "dense")
    want = np.asarray(ref.forward(SIZES, ref.weights_from_program(q, CFG.n_layers),
                                  jnp.asarray(toks[0, :70]))[0])
    np.testing.assert_allclose(np.concatenate(got[0]), want, atol=2e-4)
