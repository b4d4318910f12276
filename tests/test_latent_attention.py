"""Latent attention (MLA) over a compressed paged cache and a chip's share of
the experts (ISSUE 38), on ``toy-mla-moe`` (CPU, float32, seeded weights): the
program through the block pool against the plain reference's full forward, on
logits — a ragged window of unequal prompts, decode, a second window over a
filled pool — on both attention paths and both MoE paths; the absorbed form
against the expanded one; YaRN, interleaved pairs and the query scale across
the (scaled-down) trained-position boundary; the pair-row leaf and its write;
the four shares of the experts adding up to the uncut layer; and the real
engine: a prefix mapped from the radix tree, copy-on-write, a block demoted to
the host tier and promoted, /health and the Prometheus series, the refusals."""

import asyncio
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                     init_params)
from ai_agent_kubectl_tpu.ops.latent_attention import (absorbed_attention,
                                                       expanded_attention,
                                                       write_rows)
from ai_agent_kubectl_tpu.ops.quant import random_params_int8
from ai_agent_kubectl_tpu.ops.ragged_attention import (latent_attention_pool,
                                                       latent_pack,
                                                       latent_query,
                                                       latent_unpack)
from ai_agent_kubectl_tpu.ops.rope import (apply_rope_scaled, query_scale,
                                           yarn_frequencies, yarn_mscale)

ROOT = Path(__file__).resolve().parent.parent
CFG = get_config("toy-mla-moe")
PAGE = 16
#: the reference's view of the toy: the configuration file's key names
SIZES = dict(num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=16, v_head_dim=32, rms_norm_eps=1e-6, rope_theta=10000.0,
             factor=8.0, original_max_position_embeddings=64, beta_fast=32.0,
             beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0, rope_interleave=True,
             llama_4_scaling_beta=0.1, num_experts_per_tok=2,
             routed_scaling_factor=1.0, first_routed_expert=0)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "mistral4_reference",
        ROOT / "benchmark/configs/mistral-small-4-119b-2603-l9.reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


def latent_pool(cfg, n_blocks, dtype=jnp.float32, **extra):
    return KVCache(k=None, v=None, lengths=jnp.zeros((n_blocks,), jnp.int32),
                   lat=jnp.zeros((cfg.n_layers, n_blocks, PAGE // 2, 2 * cfg.latent_row),
                                 dtype),
                   lat_rows=jnp.zeros((2,), jnp.int32),
                   experts_read=jnp.zeros((), jnp.int32), **extra)


def through_the_pool(cfg, params, toks, windows, impl="dense", moe_impl="auto",
                     cache=None, tables=None):
    """``windows``: a list of per-row query counts, one entry a pass (a 1
    everywhere is a decode step). Returns (every valid position's logits a
    row, the cache)."""
    B = toks.shape[0]
    pages = -(-toks.shape[1] // PAGE)
    if cache is None:
        cache = latent_pool(cfg, B * pages)
        tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    done, got = np.zeros(B, np.int32), [[] for _ in range(B)]
    with jax.default_matmul_precision("highest"):
        for q in windows:
            q = np.asarray(q, np.int32)
            w = int(q.max())
            tok = np.zeros((B, w), np.int32)
            for b in range(B):
                tok[b, :q[b]] = toks[b, done[b]:done[b] + q[b]]
            pos = (done[:, None] + np.arange(w)[None, :]).astype(np.int32)
            live = jnp.asarray(np.arange(w)[None, :] < q[:, None])
            logits, cache = forward(
                params, cfg, jnp.asarray(tok), jnp.asarray(pos), cache,
                kv_limit=pages * PAGE, attn_impl=impl, token_mask=live, write_mask=live,
                block_tables=tables, q_lens=jnp.asarray(q), moe_impl=moe_impl)
            for b in range(B):
                got[b].append(np.asarray(logits[b, :q[b]]))
            done += q
    return [np.concatenate(g) for g in got], cache


def worst(ref, weights, toks, got, sizes=SIZES):
    errs = []
    for b, have in enumerate(got):
        want, _ = ref.forward(sizes, weights, jnp.asarray(toks[b, :len(have)]))
        errs.append(float(np.abs(have - np.asarray(want)).max()))
    return max(errs)


# ------------------------------------------------- program against reference

@pytest.mark.parametrize("impl,moe_impl", [("dense", "auto"), ("ragged", "auto"),
                                           ("dense", "dense")])
def test_program_equals_the_reference_over_windows_and_decode(ref, params, impl, moe_impl):
    """Two prompts of unequal length in one ragged window that crosses the
    trained-position boundary (64: YaRN's frequencies and the query scale are
    both in the logits), decode steps, then a SECOND window over the filled
    pool beside a row that only decodes (a mixed window), then decode again:
    every position's logits are the reference's full forward's."""
    toks = np.random.default_rng(1).integers(3, 500, size=(2, 176), dtype=np.int32)
    windows = [[150, 37], [1, 1], [1, 1], [20, 1], [1, 1]]
    got, cache = through_the_pool(CFG, params, toks, windows, impl, moe_impl)
    assert worst(ref, ref.weights_from_program(params, CFG.n_layers), toks, got) < 2e-5
    # the device's own count: 4 passes with decode rows (the mixed window has
    # one), each through both layers
    queries, rows = (int(n) for n in cache.lat_rows)
    assert queries == 2 * (2 + 2 + 1 + 2)
    assert rows == 2 * ((151 + 38) + (152 + 39) + 40 + (173 + 41))
    assert cache.k is None and cache.v is None and cache.ik is None


def test_seeded_int8_weights_agree_with_the_reference(ref):
    """The benchmark's weights: int8 projections and experts, the key/value
    up-projection kept in bf16's place (absorbed, it is contracted over its
    output channels); the reference dequantises the same tree."""
    from ai_agent_kubectl_tpu.ops.quant import QuantInt8

    qp = random_params_int8(jax.random.PRNGKey(5), CFG, dtype=jnp.float32,
                            quantize_embed=True)
    layers = qp["layers"]
    assert all(isinstance(layers[k], QuantInt8)
               for k in ("w_dq", "w_uq", "w_dkv", "wo", "w_up", "shared_up"))
    assert not isinstance(layers["w_ukv"], QuantInt8)
    assert layers["router"].shape == (2, 128, 16) and layers["w_up"].q.shape[:2] == (2, 4)
    toks = np.random.default_rng(2).integers(3, 500, size=(2, 96), dtype=np.int32)
    got, _ = through_the_pool(CFG, qp, toks, [[80, 33], [1, 1], [1, 1]])
    assert worst(ref, ref.weights_from_program(qp, CFG.n_layers), toks, got) < 2e-4


def test_a_caller_without_the_leaf_gets_it_made_and_its_k_v_back(params):
    """benchmark/refcheck.py builds K and V pools and no other leaf: forward
    makes a zero latent leaf on the K pool's block geometry, returns it, and
    hands K and V back untouched."""
    toks = np.random.default_rng(3).integers(3, 500, size=(2, 48), dtype=np.int32)
    want, _ = through_the_pool(CFG, params, toks, [[40, 9], [1, 1]])
    kv = jnp.full((CFG.n_layers, 6, PAGE, CFG.n_kv_heads, CFG.head_dim), 7.0, jnp.float32)
    cache = KVCache(k=kv, v=kv, lengths=jnp.zeros((6,), jnp.int32))
    tables = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    got, out = through_the_pool(CFG, params, toks, [[40, 9], [1, 1]], cache=cache,
                                tables=tables)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-6)
    assert out.lat.shape == (CFG.n_layers, 6, PAGE // 2, 2 * CFG.latent_row)
    np.testing.assert_array_equal(np.asarray(out.k), np.asarray(kv))


# ------------------------------------------------------------ the two forms

def test_absorbed_equals_expanded():
    rng = np.random.default_rng(4)
    B, S, K, H, C, N, R, V = 2, 5, 23, 4, 32, 16, 8, 24
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q_nope, q_r, c, kr = f(B, S, H, N), f(B, S, H, R), f(B, K, C), f(B, K, R)
    w_uk, w_uv = f(C, H, N) * C ** -0.5, f(C, H, V) * C ** -0.5
    mask = jnp.asarray(np.arange(K)[None, None, :] <= (K - S + np.arange(S))[None, :, None])
    mask = jnp.broadcast_to(mask, (B, S, K))
    with jax.default_matmul_precision("highest"):
        want = expanded_attention(q_nope, q_r, c, kr, w_uk, w_uv, mask)
        q_c = jnp.einsum("bshn,chn->bshc", q_nope, w_uk)
        o_c = absorbed_attention(q_c, q_r, c, kr, mask)
        got = jnp.einsum("bshc,chv->bshv", o_c, w_uv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_kernel_is_the_absorbed_form_over_the_pair_row_leaf():
    """Interpreted: a prefill tile, a decode row and a frozen slot, through a
    shuffled block table, against the dense absorbed form over the same rows."""
    rng = np.random.default_rng(6)
    L, nb, C, R, H, N, W, pages = 2, 14, 32, 8, 4, 4, 8, 3
    c = jnp.asarray(rng.standard_normal((L, nb, PAGE, C)), jnp.float32)
    kr = jnp.asarray(rng.standard_normal((L, nb, PAGE, R)), jnp.float32)
    leaf = latent_pack(c, kr)
    assert leaf.shape == (L, nb, PAGE // 2, 2 * (C + R))
    back = latent_unpack(leaf, C)
    np.testing.assert_array_equal(np.asarray(back[0]), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(back[1]), np.asarray(kr))
    q_c = jnp.asarray(rng.standard_normal((N, W, H, C)) * 0.3, jnp.float32)
    q_r = jnp.asarray(rng.standard_normal((N, W, H, R)) * 0.3, jnp.float32)
    tables = jnp.asarray(rng.permutation(nb)[:N * pages].reshape(N, pages), jnp.int32)
    q_lens = jnp.asarray([8, 1, 0, 5], jnp.int32)
    pos = jnp.asarray([30, 41, 9, 0], jnp.int32)
    got = latent_attention_pool(latent_query(q_c, q_r), leaf, q_lens, pos, tables,
                                jnp.asarray(1), v_lanes=C, page_size=PAGE)
    ctx = lambda a: a[1][tables].reshape(N, pages * PAGE, -1)
    mask = (jnp.arange(pages * PAGE)[None, None, :]
            <= (pos[:, None] + jnp.arange(W)[None, :])[:, :, None])
    with jax.default_matmul_precision("highest"):
        want = absorbed_attention(q_c, q_r, ctx(c), ctx(kr), mask)
    for n in range(N):
        ql = int(q_lens[n])
        np.testing.assert_allclose(np.asarray(got)[n, :ql], np.asarray(want)[n, :ql],
                                   atol=2e-5)
        assert not np.asarray(got)[n, ql:].any()


@pytest.mark.parametrize("start,count", [(0, 1), (5, 1), (4, 6), (5, 6), (5, 7), (15, 3)])
def test_a_window_write_keeps_each_rows_other_half(start, count):
    """A leaf row holds two tokens: a write of an odd-aligned or odd-long
    window, or of one decode token, replaces its tokens' halves and nobody
    else's; rows past ``q_len`` and unmapped pages write nothing."""
    rng = np.random.default_rng(start * 31 + count)
    L, nb, C, R, S = 2, 4, 8, 4, 8
    old_c = jnp.asarray(rng.standard_normal((L, nb, PAGE, C)), jnp.float32)
    old_kr = jnp.asarray(rng.standard_normal((L, nb, PAGE, R)), jnp.float32)
    leaf = latent_pack(old_c, old_kr)
    new_c = jnp.asarray(rng.standard_normal((1, S, C)), jnp.float32)
    new_kr = jnp.asarray(rng.standard_normal((1, S, R)), jnp.float32)
    positions = (start + np.arange(S))[None].astype(np.int32)
    block = np.asarray([2, 0, 9])[positions // PAGE]             # page 2 is unmapped
    flat = np.where((np.arange(S)[None] < count) & (block < nb),
                    block * PAGE + positions % PAGE, nb * PAGE)
    out = write_rows(leaf, jnp.asarray(flat), jnp.asarray(positions), new_c, new_kr,
                     jnp.asarray(1))
    got_c, got_kr = (np.asarray(a) for a in latent_unpack(out, C))
    want_c, want_kr = np.array(old_c), np.array(old_kr)
    for j in range(count):
        p = start + j
        blk = [2, 0, 9][p // PAGE]
        if blk < nb:
            want_c[1, blk, p % PAGE], want_kr[1, blk, p % PAGE] = new_c[0, j], new_kr[0, j]
    np.testing.assert_array_equal(got_c, want_c)
    np.testing.assert_array_equal(got_kr, want_kr)


# ------------------------------------------------------------------ rotary

def test_yarn_frequencies_keep_fast_pairs_and_divide_slow_ones():
    inv = yarn_frequencies(64, 10000.0, 128.0, 8192, 32.0, 1.0)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # the ramp runs over pairs 12 (32 turns in 8,192 positions) to 25 (one)
    np.testing.assert_allclose(inv[:13], plain[:13], rtol=1e-6)
    np.testing.assert_allclose(inv[25:], plain[25:] / 128.0, rtol=1e-6)
    assert np.all(np.diff(inv) < 0) and np.all(inv <= plain * (1 + 1e-6))
    np.testing.assert_allclose(yarn_frequencies(64, 10000.0, 1.0, 8192, 32, 1), plain,
                               rtol=1e-6)
    assert abs(yarn_mscale(128.0, 1.0) - 1.4852030) < 1e-6 and yarn_mscale(1.0, 1.0) == 1.0


def test_interleaved_rotation_gives_the_pairwise_products(ref):
    """Pairs (2i, 2i+1), the result left de-interleaved: a rotated query's
    product with a rotated key is the pair-by-pair complex rotation's, and
    depends on the positions' difference alone; the reference's own rotation
    is the same numbers."""
    rng = np.random.default_rng(8)
    q, k = rng.standard_normal((2, 1, 3, 1, 16))
    inv = yarn_frequencies(16, 10000.0, 8.0, 64, 32.0, 1.0)
    rot = lambda x, p: np.asarray(apply_rope_scaled(
        jnp.asarray(x, jnp.float32), jnp.full((1, 3), p, jnp.int32), inv, True))
    qc, kc = q[..., 0::2] + 1j * q[..., 1::2], k[..., 0::2] + 1j * k[..., 1::2]
    want = np.real(np.sum(qc * np.exp(1j * 70 * inv) * np.conj(kc * np.exp(1j * 3 * inv)), -1))
    np.testing.assert_allclose(np.sum(rot(q, 70) * rot(k, 3), -1), want, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.sum(rot(q, 170) * rot(k, 103), -1), want, rtol=2e-4, atol=2e-4)
    mine = rot(q, 70)[0, :, 0]
    theirs = np.asarray(ref.rope(SIZES, jnp.asarray(q[0, :, 0], jnp.float32),
                                 jnp.full((3,), 70)))
    np.testing.assert_allclose(mine, theirs, atol=1e-6)


def test_the_query_scale_is_one_inside_the_trained_positions_and_steps_past_them():
    g = np.asarray(query_scale(jnp.asarray([0, 63, 64, 127, 128, 640]), 0.1, 64))
    np.testing.assert_allclose(g, 1 + 0.1 * np.log1p([0, 0, 1, 1, 2, 10]), rtol=1e-6)


def test_the_boundary_shows_in_the_logits(ref, params):
    """A program that forgot the query scale (or YaRN) is not the reference
    past the boundary: the comparison above has power there."""
    toks = np.random.default_rng(9).integers(3, 500, size=(1, 112), dtype=np.int32)
    got, _ = through_the_pool(CFG, params, toks, [[100]])
    weights = ref.weights_from_program(params, CFG.n_layers)
    for key, off in (("llama_4_scaling_beta", 0.0), ("factor", 1.0)):
        other, _ = ref.forward(dict(SIZES, **{key: off}), weights, jnp.asarray(toks[0, :100]))
        err = np.abs(got[0] - np.asarray(other)).max(axis=1)
        assert err[64:].max() > 1e-3, key
        if key == "llama_4_scaling_beta":
            assert err[:64].max() < 2e-5


# --------------------------------------------------------- a chip's share

def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(ref):
    """The router scores 16 experts; four trees hold 4 each (experts 0-3, 4-7,
    8-11, 12-15) of the SAME uncut model. Each share's layer output minus the
    part every chip computes whole (the residual and the shared expert) is its
    experts' part of the routed sum: the four add up to the uncut layer's
    routed sum, in the program (both MoE paths) and in the reference."""
    from ai_agent_kubectl_tpu.models.transformer import _dense_mlp, _moe_mlp

    whole_cfg = dataclasses.replace(CFG, n_experts=16, router_width=0, n_layers=1)
    whole = init_params(jax.random.PRNGKey(11), whole_cfg, dtype=jnp.float32)
    lp = {k: v[0] for k, v in whole["layers"].items()}
    x = jnp.asarray(np.random.default_rng(12).standard_normal((2, 24, CFG.dim)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _ = _moe_mlp(whole_cfg, lp, x, moe_impl="dense")
        shared = _dense_mlp(whole_cfg, lp, x, "shared_")
        for moe_impl in ("auto", "dense"):
            parts, read = [], 0
            for first in (0, 4, 8, 12):
                cfg = dataclasses.replace(CFG, n_layers=1, first_expert=first)
                cut = {k: (v[first:first + 4] if k in ("w_gate", "w_up", "w_down") else v)
                       for k, v in lp.items()}
                y, n = _moe_mlp(cfg, cut, x, moe_impl=moe_impl)
                parts.append(y)
                read += int(n.get("experts_read", 0))
            np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut), atol=2e-5)
            assert moe_impl == "dense" or 4 <= read <= 16
        # the reference, told the same shares: routed parts + the shared
        # expert counted once
        total = 0.0
        for first in (0, 4, 8, 12):
            cut = {k: (v[:, first:first + 4] if k in ("w_gate", "w_up", "w_down") else v)
                   for k, v in whole["layers"].items()}
            lw = ref.weights_from_program({**whole, "layers": cut}, 1)["layers"][0]
            y, _ = ref.experts(dict(SIZES, first_routed_expert=first), lw, x[0])
            total = total + y - np.asarray(shared[0])
        np.testing.assert_allclose(np.asarray(total + shared[0]),
                                   np.asarray(uncut[0] + shared[0]), atol=2e-5)


def test_the_share_rides_the_configuration():
    assert CFG.experts_scored == 16 and CFG.n_experts == 4 and CFG.grouped_experts
    assert get_config("toy-sparse-moe").experts_scored == 16
    assert CFG.latent and CFG.latent_row == 48 and not get_config("toy-8m").latent
    from ai_agent_kubectl_tpu.parallel.moe import grouped_kernel_shape
    # a quarter of the pairs are expected here: 512 tokens x 2 picks / 4 over
    # 4 experts are 64 rows an expert, not 256
    assert grouped_kernel_shape(CFG, 512)["tile_rows"] == 80


# ------------------------------------------------------------- the engine

LOG = "pod web-1 crashed with OOMKilled at 12:03; " * 3     # 129 byte tokens


def _engine(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    defaults = dict(dtype="float32", max_seq_len=256, prefill_buckets=(32, 64),
                    prefix_cache=False, batch_size=4, chunk_len=4, kv_pool_page=16)
    defaults.update(kw)
    return BatchedJaxEngine(CFG, tokenizer=ByteTokenizer(), **defaults)


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


async def _asks(eng, n):
    await eng.start()
    try:
        outs = [await eng.generate(LOG + "why?", max_tokens=12, temperature=0.0, seed=1)
                for _ in range(n)]
        _books(eng)
        return [o.text for o in outs], eng.stats(), np.asarray(eng._cache.lat)
    finally:
        await eng.stop()


@pytest.mark.parametrize("force_ragged", [False, True], ids=["gather", "ragged"])
def test_a_second_ask_maps_the_cached_log_from_the_latent_leaf(force_ragged):
    """The radix tree shares the latent leaf's blocks (the same tables): the
    second ask prefix-hits the log, prefills its last tokens over the first
    ask's rows and says what an unshared run says. The pool holds the
    compressed row and nothing else."""
    from ai_agent_kubectl_tpu.server.metrics import Metrics

    shared, st, lat = asyncio.run(_asks(_engine(force_ragged=force_ragged), 2))
    alone, st0, _ = asyncio.run(_asks(_engine(force_ragged=force_ragged,
                                              radix_cache=False), 2))
    assert shared[0] == shared[1] == alone[0] == alone[1]
    pool = st["kv_pool"]
    assert pool["attention_regime"] == ("ragged" if force_ragged else "gather")
    assert pool["radix"]["hit_tokens"] >= 128
    assert pool["bytes_per_token"] == CFG.n_layers * CFG.latent_row * 4 == 384
    assert np.abs(lat).max() > 0
    la = st["latent_attention"]
    assert la["row_bytes"] == 384 and la["layers"] == 2 and la["window_rows_expanded"] == 0
    assert la["window_rows_absorbed"] < st0["latent_attention"]["window_rows_absorbed"]
    # 11 decode queries an ask, each over ~135-146 cached rows in both layers
    assert la["decode_rows"] >= 22
    per_row = la["latent_rows_read"] / la["decode_rows"] / la["layers"]
    assert 100 < per_row < 150
    moe = st["moe"]
    assert (moe["experts_held"], moe["first_expert"], moe["router_width"]) == (4, 0, 16)
    assert 0 < moe["experts_read"] / moe["layer_passes"] <= 4
    m = Metrics()
    m.observe_latent_attention(la)
    text = m.render().decode()
    assert "latent_cache_row_bytes 384.0" in text
    assert 'latent_attention_rows_total{kind="latent_rows_read"} %.1f' % la[
        "latent_rows_read"] in text


def test_copy_on_write_and_the_host_tier_carry_the_leaf():
    async def go():
        eng = _engine(host_kv_blocks=4)
        await eng.start()
        try:
            rng = np.random.default_rng(3)
            eng._cache = dataclasses.replace(
                eng._cache, lat=jnp.asarray(rng.standard_normal(eng._cache.lat.shape),
                                            eng._cache.lat.dtype))
            before = [np.asarray(a) for a in latent_unpack(eng._cache.lat, CFG.kv_lora_rank)]
            eng._run_cow(2, 5, 7)           # 7 tokens of block 2 -> block 5 (kv_splice)
            after = [np.asarray(a) for a in latent_unpack(eng._cache.lat, CFG.kv_lora_rank)]
            for a, b in zip(after, before):
                np.testing.assert_array_equal(a[:, 5, :7], b[:, 2, :7])
                # the 8th token rides with its partner; the rest is untouched
                np.testing.assert_array_equal(a[:, 5, 8:], b[:, 5, 8:])
            payload = eng._pool_offload_block(5)
            assert payload.nbytes == CFG.n_layers * PAGE * CFG.latent_row * 4
            eng._pool_onload_block(9, payload)
            np.testing.assert_array_equal(np.asarray(eng._cache.lat)[:, 9],
                                          np.asarray(eng._cache.lat)[:, 5])
        finally:
            await eng.stop()

    asyncio.run(go())


def test_a_demoted_block_is_promoted_and_answers_the_same():
    """The radix tree's LRU demotes the log's blocks to the host tier under
    pressure from other prompts; a re-ask onloads them (checksummed) and says
    what it said."""
    async def go():
        eng = _engine(host_kv_blocks=32, radix_lru_blocks=10, kv_pool_blocks=40,
                      batch_size=2)
        await eng.start()
        try:
            first = await eng.generate(LOG + "why?", max_tokens=8, temperature=0.0, seed=1)
            for i in range(3):
                await eng.generate(f"other {i} " + "x y z " * 20, max_tokens=4,
                                   temperature=0.0, seed=1)
            host = eng.kv_pool_health()["host_tier"]
            assert host["demoted_total"] > 0
            again = await eng.generate(LOG + "why?", max_tokens=8, temperature=0.0, seed=1)
            assert again.text == first.text
            assert eng.kv_pool_health()["host_tier"]["onloaded_total"] > 0
            _books(eng)
        finally:
            await eng.stop()

    asyncio.run(go())


def test_an_engine_without_the_pool_refuses_at_start():
    async def go():
        with pytest.raises(ValueError, match="keeps a latent cache .* the dense per-slot KV"):
            await _engine(kv_pool=False).start()

    asyncio.run(go())


def test_forward_without_block_tables_says_why(params):
    cache = KVCache.zeros(CFG, 1, 32, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="served through the pool alone"):
        forward(params, CFG, jnp.zeros((1, 4), jnp.int32),
                jnp.arange(4, dtype=jnp.int32)[None], cache)
