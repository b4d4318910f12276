"""The mixed admission window's valid rows, packed (ISSUE 39).

``forward(..., packed_rows=W + N)`` carries the window's valid rows through
everything that works a row at a time and hands the mixers the [N, W] window
they always saw. Held here against the [N, W] form (the parent's prologue) on
one tiny configuration a family: the same logits at each slot's last valid
row and the same rows written to every cache leaf, with one and two staged
suffixes beside riders, dead slots and an empty window; the engines'
staging rule and their transcripts are in the second half.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                     init_params,
                                                     state_zeros, window_rows)

PAGE = 16
FAMILIES = ["toy-8m", "toy-moe", "toy-sparse-moe", "toy-hybrid-moe",
            "toy-mla-moe"]


# --------------------------------------------------------------- the rows


def test_rows_are_the_valid_columns_in_slot_order_then_padding():
    q = jnp.asarray([3, 0, 1, 2], jnp.int32)
    pos = jnp.asarray([[10, 11, 12, 13], [0, 1, 2, 3], [7, 8, 9, 10],
                       [20, 21, 22, 23]], jnp.int32)
    win = window_rows(q, pos, 8)
    assert win.valid[0].tolist() == [True] * 6 + [False] * 2
    assert win.slot[:6].tolist() == [0, 0, 0, 2, 3, 3]
    assert win.col[:6].tolist() == [0, 1, 2, 0, 0, 1]
    assert win.pos[0, :6].tolist() == [10, 11, 12, 7, 20, 21]
    # every valid (slot, column) finds its row again, and back
    x = jnp.arange(16.0).reshape(4, 4)
    packed = win.pack(x)
    assert packed.shape == (1, 8)
    again = win.unpack(packed)
    for n, length in enumerate(q.tolist()):
        assert again[n, :length].tolist() == x[n, :length].tolist()


def test_an_empty_window_packs_to_padding_alone():
    win = window_rows(jnp.zeros((3,), jnp.int32), jnp.zeros((3, 4), jnp.int32), 7)
    assert not bool(win.valid.any())
    assert win.pack(jnp.ones((3, 4))).shape == (1, 7)


# ------------------------------------------------ packed against [N, W]


def pool_for(cfg, n_slots, pages):
    """A block pool with every leaf the configuration's engine keeps."""
    n_blocks = n_slots * pages
    lengths = jnp.zeros((n_blocks,), jnp.int32)
    if cfg.latent:
        return KVCache(
            k=None, v=None, lengths=lengths,
            lat=jnp.zeros((cfg.n_layers, n_blocks, PAGE // 2, 2 * cfg.latent_row),
                          jnp.float32),
            lat_rows=jnp.zeros((2,), jnp.int32),
            experts_read=jnp.zeros((), jnp.int32))
    layers = cfg.n_of("*") if cfg.layer_kinds else cfg.n_layers
    pool = (layers, n_blocks, PAGE, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=lengths)
    if cfg.selects_keys:
        cache = dataclasses.replace(
            cache, ik=jnp.zeros(pool[:3] + (cfg.index_key_width,), jnp.float32),
            sel_rows=jnp.zeros((2,), jnp.int32))
    if cfg.keeps_state:
        ssm, conv = state_zeros(cfg, n_slots, jnp.float32)
        cache = dataclasses.replace(cache, ssm=ssm, conv=conv)
    if cfg.is_moe and cfg.grouped_experts:
        cache = dataclasses.replace(cache, experts_read=jnp.zeros((), jnp.int32))
    return cache


def one_window(cfg, params, cache, tables, toks, start, q_lens, width, impl,
               packed_rows=None):
    q = np.asarray(q_lens, np.int32)
    cols = np.arange(width)[None, :]
    pos = (np.asarray(start, np.int32)[:, None] + cols).astype(np.int32)
    live = jnp.asarray(cols < q[:, None])
    return forward(params, cfg, jnp.asarray(toks), jnp.asarray(pos), cache,
                   kv_limit=tables.shape[1] * PAGE, attn_impl=impl,
                   token_mask=live, write_mask=live, block_tables=tables,
                   q_lens=jnp.asarray(q), logits_at=jnp.asarray(np.maximum(q, 1) - 1),
                   packed_rows=packed_rows)


#: q_lens of the mixed window over 6 slots that hold 20, 0, 5, 33, 9 and 40
#: tokens: staged suffixes (> 1) beside riders (1) and dead slots (0).
CASES = {
    "one staged suffix beside riders": [1, 24, 1, 1, 0, 1],
    "two staged suffixes": [1, 19, 1, 13, 1, 0],
    "a suffix as wide as the window": [0, 32, 0, 1, 1, 0],
    "riders alone": [1, 0, 1, 1, 1, 1],
    "nobody": [0, 0, 0, 0, 0, 0],
}
CONTEXT = [20, 0, 5, 33, 9, 40]
WIDTH = 32


@pytest.fixture(scope="module", params=FAMILIES)
def family(request):
    """(cfg, params, a pool in which every slot has its context, tables, toks)."""
    cfg = get_config(request.param)
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    n, pages = len(CONTEXT), 6
    tables = jnp.arange(n * pages, dtype=jnp.int32).reshape(n, pages)
    toks = np.random.default_rng(5).integers(1, cfg.vocab_size, (n, 96)).astype(np.int32)
    w0 = 48
    first = np.zeros((n, w0), np.int32)
    for b, c in enumerate(CONTEXT):
        first[b, :c] = toks[b, :c]
    _, cache = one_window(cfg, params, pool_for(cfg, n, pages), tables, first,
                          [0] * n, CONTEXT, w0, "dense")
    return cfg, params, cache, tables, toks


@pytest.mark.parametrize("case", list(CASES))
def test_packed_window_equals_the_slot_by_width_window(family, case):
    cfg, params, cache, tables, toks = family
    q = CASES[case]
    window = np.zeros((len(q), WIDTH), np.int32)
    for b, (c, n) in enumerate(zip(CONTEXT, q)):
        window[b, :n] = toks[b, c:c + n]
    with jax.default_matmul_precision("highest"):
        want, want_cache = one_window(cfg, params, cache, tables, window,
                                      CONTEXT, q, WIDTH, "dense")
        got, got_cache = one_window(cfg, params, cache, tables, window,
                                    CONTEXT, q, WIDTH, "dense",
                                    packed_rows=WIDTH + len(q))
    assert got.shape == want.shape == (len(q), 1, cfg.vocab_size)
    live = np.asarray(q) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    for name in ("k", "v", "ik", "lat", "ssm", "conv"):
        a, b = getattr(got_cache, name), getattr(want_cache, name)
        assert (a is None) == (b is None), name
        if a is not None:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                       atol=2e-5, err_msg=name)
    for name in ("experts_read", "sel_rows", "lat_rows"):
        a, b = getattr(got_cache, name), getattr(want_cache, name)
        if a is not None:
            assert np.asarray(a).tolist() == np.asarray(b).tolist(), name


def test_packed_window_through_the_interpreted_kernel():
    """The ragged regime's own call: the kernel is handed the same [N, W]
    queries whether the residual was packed or not."""
    cfg = get_config("toy-8m")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    n, pages = 4, 4
    tables = jnp.arange(n * pages, dtype=jnp.int32).reshape(n, pages)
    toks = np.random.default_rng(6).integers(1, cfg.vocab_size, (n, 64)).astype(np.int32)
    context, q = [12, 0, 30, 7], [1, 20, 0, 9]
    first = np.zeros((n, 32), np.int32)
    for b, c in enumerate(context):
        first[b, :c] = toks[b, :c]
    _, cache = one_window(cfg, params, pool_for(cfg, n, pages), tables, first,
                          [0] * n, context, 32, "dense")
    window = np.zeros((n, 32), np.int32)
    for b, (c, m) in enumerate(zip(context, q)):
        window[b, :m] = toks[b, c:c + m]
    want, want_cache = one_window(cfg, params, cache, tables, window, context,
                                  q, 32, "ragged")
    got, got_cache = one_window(cfg, params, cache, tables, window, context, q,
                                32, "ragged", packed_rows=32 + n)
    live = np.asarray(q) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_cache.k), np.asarray(want_cache.k),
                               rtol=2e-5, atol=2e-5)


def test_packed_entry_needs_the_window_it_packs():
    cfg = get_config("toy-8m")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    cache = KVCache.zeros(cfg, 2, 32, dtype=jnp.float32)
    with pytest.raises(ValueError, match="packed_rows"):
        forward(params, cfg, jnp.zeros((2, 8), jnp.int32),
                jnp.zeros((2, 8), jnp.int32), cache, packed_rows=10)
