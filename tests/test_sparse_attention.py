"""Learned top-k key selection inside paged attention, the index-key pool leaf
and the grouped expert GEMM (ISSUE 31), on the CPU at toy size: the program
through the block pool (interpreted kernel, ``toy-sparse-moe``, seeded weights)
against the benchmark's plain reference for keye-vl-2.0-30b-a3b-l8, loaded by
path as benchmark/refcheck.py loads it."""

import asyncio
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                     init_params)
from ai_agent_kubectl_tpu.ops.quant import (quantize_int8, quantize_params_int8,
                                            random_params_int8)
from ai_agent_kubectl_tpu.ops.ragged_attention import ragged_attention_pool
from ai_agent_kubectl_tpu.ops.rope import apply_rope
from ai_agent_kubectl_tpu.ops.sparse_select import (index_scores,
                                                    window_selection)
from ai_agent_kubectl_tpu.parallel.moe import dense_moe, grouped_moe

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import refcheck  # noqa: E402

CFG = get_config("toy-sparse-moe")          # index_topk 48
REFERENCE = "benchmark/configs/keye-vl-2.0-30b-a3b-l8.reference.py"
SIZES = {"num_attention_heads": CFG.n_heads, "num_key_value_heads": CFG.n_kv_heads,
         "head_dim": CFG.head_dim, "rope_theta": CFG.rope_theta,
         "rms_norm_eps": CFG.rms_eps, "num_experts_per_tok": CFG.experts_per_token,
         "indexer_num_heads": CFG.index_heads, "indexer_head_dim": CFG.index_head_dim,
         "topk": CFG.index_topk, "q_chunk_size": 32}
PAGE, STEPS = 16, 3


def through_the_pool(cfg, params, toks, lens, window, impl, with_leaf=True):
    """Every position's logits: one ragged window over the prompts, then STEPS
    single-token steps through the cache (refcheck.run's program side). Without
    the leaf the pool is built as refcheck.py builds it, K and V alone."""
    B = len(lens)
    pages = -(-(window + STEPS) // PAGE)
    pool = (cfg.n_layers, B * pages, PAGE, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32))
    if with_leaf and cfg.selects_keys:
        cache = dataclasses.replace(cache, ik=jnp.zeros(
            pool[:3] + (cfg.index_key_width,), jnp.float32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)

    @jax.jit
    def step(params, tok, pos, cache, wmask, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * PAGE,
                       attn_impl=impl, token_mask=wmask, write_mask=wmask,
                       block_tables=tables, q_lens=q_lens)

    cols = np.arange(window)[None, :]
    q_lens = np.asarray(lens, np.int32)
    win = np.zeros((B, window), np.int32)
    for b, n in enumerate(lens):
        win[b, :n] = toks[b, :n]
    logits, cache = step(params, jnp.asarray(win),
                         jnp.asarray(np.broadcast_to(cols, (B, window)).astype(np.int32)),
                         cache, jnp.asarray(cols < q_lens[:, None]), jnp.asarray(q_lens))
    got = [[np.asarray(logits[b, :n])] for b, n in enumerate(lens)]
    for s in range(STEPS):
        tok = np.stack([toks[b, n + s] for b, n in enumerate(lens)])[:, None]
        logits, cache = step(params, jnp.asarray(tok),
                             jnp.asarray((q_lens + s)[:, None].astype(np.int32)), cache,
                             jnp.ones((B, 1), bool), jnp.ones((B,), jnp.int32))
        for b in range(B):
            got[b].append(np.asarray(logits[b, :1]))
    return [np.concatenate(g, axis=0) for g in got], cache


@pytest.fixture(scope="module")
def seeded():
    params = random_params_int8(jax.random.PRNGKey(31), CFG, dtype=jnp.float32,
                                quantize_embed=True)
    return params, refcheck.load_reference(REFERENCE).weights_from_program(
        params, CFG.n_layers)


def tokens(lens, seed=5):
    return np.random.default_rng(seed).integers(
        3, CFG.vocab_size, size=(len(lens), max(lens) + STEPS), dtype=np.int32)


def worst(ref, sizes, weights, toks, lens, got):
    out = 0.0
    for b, n in enumerate(lens):
        want, _ = ref.forward(sizes, weights, jnp.asarray(toks[b]))
        want = np.asarray(want)[:n + STEPS]
        out = max(out, float(np.abs(got[b] - want).max() / want.std()))
    return out


@pytest.mark.parametrize("impl,with_leaf", [("ragged", True), ("ragged", False),
                                            ("dense", True)],
                         ids=["ragged", "ragged_leaf_made_by_forward", "gather"])
def test_program_matches_the_reference_on_both_sides_of_topk(seeded, impl, with_leaf):
    """Prompts of 100 and 37 tokens around index_topk = 48: the longer one's
    window rows and decode rows select, the shorter one's keep every key. A
    pool built without the leaf (benchmark/refcheck.py builds K and V alone) is
    given a zero one by ``forward`` and gets it back."""
    params, weights = seeded
    ref = refcheck.load_reference(REFERENCE)
    lens = (100, 37)
    toks = tokens(lens)
    got, cache = through_the_pool(CFG, params, toks, lens, 128, impl, with_leaf)
    assert cache.ik.shape == cache.k.shape[:3] + (CFG.index_key_width,)
    assert float(jnp.abs(cache.ik).max()) > 0
    assert worst(ref, SIZES, weights, toks, lens, got) < 1e-4
    # the comparison has power over the mechanism: a reference that attends to
    # every key is far off on the long prompt, and only there
    every = dict(SIZES, topk=10 ** 6)
    assert worst(ref, every, weights, toks[:1], lens[:1], got[:1]) > 0.1
    assert worst(ref, every, weights, toks[1:], lens[1:], got[1:]) < 1e-4


def test_selection_is_the_dense_ragged_path_bit_for_bit_up_to_topk(seeded):
    """While no context passes index_topk every key is selected and the dense
    ragged kernel serves: the logits are those of the same weights with the
    selector off, bit for bit (kv_limit is past topk, so the selecting
    program is compiled and takes its dense branch at run time)."""
    params, _ = seeded
    lens = (40, 17)
    toks = tokens(lens, seed=9)
    sel, _ = through_the_pool(CFG, params, toks, lens, 64, "ragged")
    off = dataclasses.replace(CFG, index_topk=0)
    ref, _ = through_the_pool(off, params, toks, lens, 64, "ragged")
    for a, b in zip(sel, ref):
        np.testing.assert_array_equal(a, b)


def test_kernel_sel_of_every_causal_key_changes_no_bit():
    rng = np.random.default_rng(0)
    N, W, H, KV, hd, pages = 2, 8, 4, 2, 64, 3
    q = jnp.asarray(rng.standard_normal((N, W, H, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((N * pages, PAGE, KV, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((N * pages, PAGE, KV, hd)), jnp.float32)
    tables = jnp.arange(N * pages, dtype=jnp.int32).reshape(N, pages)
    q_lens, pos = jnp.asarray([8, 3], jnp.int32), jnp.asarray([20, 5], jnp.int32)
    plain = ragged_attention_pool(q, k, v, q_lens, pos, tables, page_size=PAGE)
    ones = jnp.ones((N, W, pages * PAGE), bool)
    np.testing.assert_array_equal(
        plain, ragged_attention_pool(q, k, v, q_lens, pos, tables, sel=ones, page_size=PAGE))
    # and a mask that drops keys is a different result, equal to the dense one
    keep = jnp.asarray(rng.random((N, W, pages * PAGE)) < 0.5).at[:, :, 0].set(True)
    got = ragged_attention_pool(q, k, v, q_lens, pos, tables, sel=keep, page_size=PAGE)
    assert float(jnp.abs(got - plain).max()) > 1e-3
    kk = k.reshape(N, pages * PAGE, KV, hd).repeat(H // KV, axis=2)
    vv = v.reshape(N, pages * PAGE, KV, hd).repeat(H // KV, axis=2)
    s = jnp.einsum("nwhd,nshd->nhws", q, kk) * hd ** -0.5
    causal = (jnp.arange(pages * PAGE)[None, None, :]
              <= (pos[:, None] + jnp.arange(W)[None, :])[:, :, None])
    s = jnp.where(jnp.logical_and(causal, keep)[:, None], s, -jnp.inf)
    want = jnp.einsum("nhws,nshd->nwhd", jax.nn.softmax(s, axis=-1), vv)
    for n in range(N):
        np.testing.assert_allclose(got[n, :int(q_lens[n])], want[n, :int(q_lens[n])],
                                   atol=2e-5)


@pytest.mark.parametrize("k", [1, 3, 5, 8])
def test_exact_topk_with_ties_to_the_lower_key(k):
    """Against a sort: scores with many exact ties (and a row with fewer live
    keys than k)."""
    rng = np.random.default_rng(k)
    scores = rng.integers(-2, 3, size=(2, 4, 12)).astype(np.float32)
    scores[0, 1, 5:] = -np.inf                  # 5 live keys
    scores[1, 2, :] = 0.0                       # all tied
    want = np.zeros(scores.shape, bool)
    for i in np.ndindex(scores.shape[:2]):
        order = sorted(range(12), key=lambda s: (-scores[i][s], s))[:k]
        want[i][[s for s in order if scores[i][s] > -np.inf]] = True
    np.testing.assert_array_equal(window_selection(jnp.asarray(scores), k), want)
    # a decode step is a window of one row
    np.testing.assert_array_equal(window_selection(jnp.asarray(scores[:, 1:2]), k), want[:, 1:2])


def test_index_scores_in_chunks_are_the_scores_whole(monkeypatch):
    from ai_agent_kubectl_tpu.ops import sparse_select

    rng = np.random.default_rng(2)
    qi = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    wi = jnp.asarray(rng.standard_normal((2, 8, 4)), jnp.float32)
    ik = jnp.asarray(rng.standard_normal((2, 24, 16)), jnp.float32)
    pos = jnp.asarray(10 + np.arange(8)[None, :].repeat(2, 0), jnp.int32)
    whole = index_scores(qi, wi, ik, pos)
    monkeypatch.setattr(sparse_select, "_SCORE_TILE_BYTES", 2 * 4 * 24 * 4 * 2)   # 2 rows
    np.testing.assert_allclose(index_scores(qi, wi, ik, pos), whole, rtol=1e-6)
    assert bool(jnp.all(whole[:, 0, 11:] == -jnp.inf)) and bool(jnp.isfinite(whole[0, 0, 10]))


def test_sectioned_mrope_with_equal_streams_is_ops_rope():
    ref = refcheck.load_reference(REFERENCE)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((9, 3, 128)), jnp.float32)
    pos = jnp.arange(100, 109)
    got = ref.mrope(x, jnp.stack([pos, pos, pos]), 1e7, [16, 24, 24])
    want = apply_rope(x[None], pos[None], 1e7)[0]
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(ref.rope(x, pos, 1e7), want, atol=2e-5)
    # and the streams do matter: another height moves the middle section alone
    other = ref.mrope(x, jnp.stack([pos, pos + 7, pos]), 1e7, [16, 24, 24])
    moved = np.abs(np.asarray(other - got)).max(axis=(0, 1))[:64]
    assert moved[:16].max() == 0 and moved[40:].max() == 0 and moved[16:40].max() > 0


@pytest.mark.parametrize("name", ["toy-moe", "toy-sparse-moe"])
@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
def test_grouped_expert_path_equals_dense_moe(name, quant):
    """Tokens grouped by expert through the Pallas kernel against every expert
    evaluated for every token: a masked token reads no expert and returns
    zeros, experts nobody picked are never counted, and the count is right."""
    cfg = get_config(name)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    if quant:
        params = quantize_params_int8(params)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.dim), jnp.float32)
    mask = jnp.ones((2, 3)).at[1, 2].set(0)
    want = np.array(dense_moe(cfg, lp, x))
    got, n_read = jax.jit(lambda lp, x, m: grouped_moe(cfg, lp, x, m))(lp, x, mask)
    got = np.asarray(got)
    assert np.abs(got[1, 2]).max() == 0
    want[1, 2] = 0
    np.testing.assert_allclose(got, want, atol=5e-6)
    logits = np.asarray((x.reshape(-1, cfg.dim) @ lp["router"]))[:5]      # the live tokens
    picked = {int(e) for row in logits for e in np.argsort(-row)[:cfg.experts_per_token]}
    assert int(n_read) == len(picked) <= cfg.n_experts
    if name == "toy-sparse-moe":
        assert len(picked) < cfg.n_experts          # some expert is empty


# ------------- the grouped kernel at made-up expert shapes and group sizes
# (ISSUE 34: a tile's rows follow the group; the kernel's body is the parent's)

def _expert_case(D, F, activation, quant, dtype=jnp.float32, E=16, k=2, seed=0):
    """(cfg, one layer's seeded leaves) of E experts D x F."""
    cfg = dataclasses.replace(get_config("toy-sparse-moe"), name="made-up-experts",
                              dim=D, mlp_hidden=F, activation=activation,
                              n_experts=E, experts_per_token=k)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def leaf(key, i, o):
        w = jax.random.normal(key, (E, i, o), jnp.float32) * i ** -0.5
        return quantize_int8(w) if quant else w.astype(dtype)

    lp = {"router": jax.random.normal(keys[0], (D, E), jnp.float32).astype(dtype),
          "w_up": leaf(keys[1], D, F), "w_down": leaf(keys[2], F, D)}
    if cfg.gated_mlp:
        lp["w_gate"] = leaf(keys[3], D, F)
    return cfg, lp


WIDTH_CASES = {
    # D, F, activation: D 128 hands the up blocks over as [F, D] where F is no
    # multiple of 128 (up_t), D 64 and an F of whole lanes as [D, F]
    "gated-29x8": (64, 232, "silu"),
    "gated-29x16": (64, 464, "silu"),
    "gated-29x16-up-as-F-by-D": (128, 464, "silu"),
    "gated-gelu-three-lane-tiles": (64, 384, "gelu"),
    "relu2-29x8-up-as-F-by-D": (128, 232, "relu2"),
    "relu2-29x16": (64, 464, "relu2"),
    "relu2-two-lane-tiles-up-as-D-by-F": (128, 256, "relu2"),
}


@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("case", list(WIDTH_CASES))
def test_grouped_kernel_at_odd_inner_widths_equals_dense_moe(case, quant):
    """Inner widths that are no multiple of a lane tile (29 x 8, 29 x 16) and
    ones that are, gated and two-matrix, the up blocks either way round:
    against every expert evaluated for every token, a masked row zeros."""
    D, F, activation = WIDTH_CASES[case]
    cfg, lp = _expert_case(D, F, activation, quant)
    assert (F % 128 != 0 and D % 128 == 0) == ("F-by-D" in case)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, D), jnp.float32)
    mask = jnp.ones((2, 5)).at[0, 3].set(0)
    want = np.array(dense_moe(cfg, lp, x))
    want[0, 3] = 0
    got, n_read = jax.jit(lambda lp, x, m: grouped_moe(cfg, lp, x, m))(lp, x, mask)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    assert 1 <= int(n_read) <= cfg.n_experts


@pytest.mark.parametrize("tokens,tiles", [(24, 2), (40, 3)], ids=["two-tiles", "three-tiles"])
@pytest.mark.parametrize("activation", ["silu", "relu2"])
def test_an_expert_over_consecutive_tiles_of_bf16_rows(tokens, tiles, activation):
    """Every token picks expert 3 (a rigged router column), so its group spans
    ``tiles`` consecutive 16-row tiles that name the same blocks; int8 experts
    under bf16 rows, as served."""
    from ai_agent_kubectl_tpu.parallel.moe import _group_tile
    D, F = 128, 232
    cfg, lp = _expert_case(D, F, activation, True, jnp.bfloat16)
    lp["router"] = lp["router"].at[0, 3].set(40.0)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, tokens, D), jnp.float32)
    x = x.at[:, :, 0].set(2.0).astype(jnp.bfloat16)
    assert _group_tile(tokens * 2, cfg.n_experts) == 16 and -(-tokens // 16) == tiles
    got, n_read = jax.jit(lambda lp, x: grouped_moe(cfg, lp, x))(lp, x)
    want = np.asarray(dense_moe(cfg, lp, x), np.float32)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=0.03,
                               atol=0.03 * np.abs(want).max())
    assert 2 <= int(n_read) <= cfg.n_experts


@pytest.mark.parametrize("activation", ["silu", "relu2"])
@pytest.mark.parametrize("rows", [16, 32, 48, 80])
def test_a_rows_result_does_not_depend_on_its_tiles_rows(rows, activation, monkeypatch):
    """What ISSUE 34 changes is how many rows share a tile, never a row's
    arithmetic: the same 48 tokens through tiles of 16 (the parent's choice
    for this call), 32, 48 and 80 rows differ by under 2e-6 of the largest result
    (on the chip the MXU sums a row's products in one order whatever the
    tile; the CPU's blocked dot may not)."""
    from ai_agent_kubectl_tpu.parallel import moe
    cfg, lp = _expert_case(128, 232, activation, True)
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 16, 128), jnp.float32)
    mask = jnp.ones((3, 16)).at[2, 9:].set(0)
    run = lambda: np.asarray(jax.jit(                                   # noqa: E731
        lambda lp, x, m: grouped_moe(cfg, lp, x, m))(lp, x, mask)[0])
    assert moe._group_tile(48 * 2, cfg.n_experts) == 16
    parent = run()
    monkeypatch.setattr(moe, "_GROUP_TILE_MIN", rows)
    assert moe._group_tile(48 * 2, cfg.n_experts) == rows
    assert np.abs(run() - parent).max() <= 2e-6 * np.abs(parent).max()


@pytest.mark.parametrize("activation", ["silu", "relu2"])
def test_a_pass_with_no_live_tile_returns_zeros(activation):
    """Every row masked: no group has a row, no tile is live, no expert is read
    and the result is zeros."""
    cfg, lp = _expert_case(128, 232, activation, True)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 128), jnp.float32)
    got, n_read = jax.jit(lambda lp, x, m: grouped_moe(cfg, lp, x, m))(
        lp, x, jnp.zeros((2, 4)))
    assert int(n_read) == 0 and np.abs(np.asarray(got)).max() == 0


@pytest.mark.parametrize("pairs,experts,rows,steps", [
    (16 * 6, 128, 16, 97), (16 * 8, 128, 16, 129), (11 * 6, 128, 16, 67),   # decode passes
    (512 * 6, 128, 32, 221), (512 * 8, 128, 48, 211),             # an eager 512-wide piece
    (16 * 64 * 6, 128, 64, 223), (16 * 64 * 8, 128, 80, 229),    # the 64-wide window
    (12, 16, 16, 13), (10 ** 6, 8, 256, 3915)])
def test_a_tiles_rows_follow_the_group_and_the_grid_the_pairs(pairs, experts, rows, steps):
    """What holds most groups whole (mean + its root, in whole sublane tiles),
    from 16 at decode to 256: 24 rows an expert get 32-row tiles, not two of 16. The
    grid is the most tiles the pairs can light and one dead one; no split of
    the pairs over the experts lights more."""
    from ai_agent_kubectl_tpu.parallel.moe import _grid_tiles, _group_tile
    assert _group_tile(pairs, experts) == rows
    assert _grid_tiles(pairs, rows, experts) == steps
    r = np.random.default_rng(pairs)
    for _ in range(50):
        sizes = r.multinomial(pairs, r.dirichlet(np.full(experts, r.choice([0.05, 1, 20]))))
        assert (-(-sizes // rows)).sum() < steps
    spread = np.full(experts, pairs // experts)          # every group one row over whole tiles
    spread[: pairs - spread.sum()] += 1
    assert (-(-spread // rows)).sum() < steps


@pytest.mark.parametrize("geometry", ["nemotron30b", "keye30b"])
def test_the_kernel_timing_tool_rehearses_every_form(geometry, tmp_path):
    """tools/time_grouped_kernel.py (ISSUE 34) takes the kernel apart by patching
    what its body calls while it is traced; its rehearsal runs every form on tiny
    shapes through the interpreter, so a change to the kernel's signature or to the
    tile rule shows here and not on the chip."""
    import importlib.util
    import json
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" / "time_grouped_kernel.py"
    spec = importlib.util.spec_from_file_location("time_grouped_kernel", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    out = tmp_path / "lines.jsonl"
    dot = jnp.dot
    assert tool.main(["--rehearse", "--geometry", geometry, "--shape", "decode",
                      "eager-512-tail", "--form", *tool._FORMS, "--out", str(out)]) == 0
    assert jnp.dot is dot                        # the stream form's patch is undone
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert [x.get("error") for x in lines] == [None] * (2 * len(tool._FORMS))
    assert all(x["rehearsal"] and x["us_per_call"] > 0 for x in lines)
    decode = lines[0]
    assert decode["tile_rows"] == 16 and decode["resolved"]["grid_steps"] == decode["grid_steps"]
    assert 1 <= decode["live_experts"] <= decode["live_tiles"] < decode["grid_steps"]


def test_the_path_is_chosen_by_one_static_rule():
    assert get_config("toy-sparse-moe").grouped_experts
    assert not get_config("toy-moe").grouped_experts
    assert not get_config("mixtral-8x7b-instruct").grouped_experts
    assert not get_config("toy-8m").grouped_experts


# ------------------------------------------------------------- the engine

LOG = "pod web-1 crashed with OOMKilled at 12:03; " * 3     # 129 byte tokens > topk


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
        return [o.text for o in outs], eng.stats(), np.asarray(eng._cache.ik)
    finally:
        await eng.stop()


@pytest.mark.parametrize("force_ragged", [False, True], ids=["gather", "ragged"])
def test_a_second_ask_maps_the_cached_log_and_its_index_keys(force_ragged):
    """The radix tree shares the index-key leaf with K and V (same blocks, same
    table): the second ask prefix-hits the log, selects from the first ask's
    index keys, and says what an unshared run says."""
    shared, st, ik = asyncio.run(_asks(_engine(force_ragged=force_ragged), 2))
    alone, st0, _ = asyncio.run(_asks(_engine(force_ragged=force_ragged,
                                              radix_cache=False), 2))
    assert shared[0] == shared[1] == alone[0] == alone[1]
    assert st["kv_pool"]["radix"]["hit_tokens"] >= 128 and np.abs(ik).max() > 0
    sel = st["sparse_attention"]
    assert sel["window_rows"] < st0["sparse_attention"]["window_rows"]     # the hit
    assert 0 < sel["decode_rows_selected"] < sel["decode_rows_live"]
    # 11 decode rows an ask, each keeping index_topk of ~140 live keys (the
    # engine's own short warm-up generation adds a few rows that keep all)
    assert 0 <= sel["decode_rows_selected"] - 2 * 11 * CFG.index_topk < CFG.index_topk
    assert sel["forward_passes"] > 0
    assert st["moe"]["layer_passes"] > 0
    assert 1 <= st["moe"]["experts_read"] / st["moe"]["layer_passes"] <= CFG.n_experts
    # ISSUE 34: what the grouped kernel resolves from shapes, beside the counters
    kern = st["moe"]["kernel"]
    assert kern["decode"]["tile_rows"] == 16 and kern["decode"]["grid_steps"] >= 2
    assert kern["widest_window"]["tile_rows"] >= kern["eager_piece"]["tile_rows"] >= 16
    regime = st["kv_pool"]
    assert regime["attention_regime"] == ("ragged" if force_ragged else "gather")
    assert regime["attention_selects_keys"]["index_topk"] == CFG.index_topk


def test_copy_on_write_and_the_host_tier_carry_the_leaf():
    async def go():
        eng = _engine(host_kv_blocks=4)
        await eng.start()
        try:
            rng = np.random.default_rng(3)
            eng._cache = dataclasses.replace(
                eng._cache, ik=jnp.asarray(rng.standard_normal(eng._cache.ik.shape),
                                           eng._cache.ik.dtype))
            before = np.asarray(eng._cache.ik)
            eng._run_cow(2, 5, 7)           # 7 rows of block 2 -> block 5 (kv_splice)
            after = np.asarray(eng._cache.ik)
            np.testing.assert_array_equal(after[:, 5, :7], before[:, 2, :7])
            np.testing.assert_array_equal(after[:, 5, 7:], before[:, 5, 7:])
            payload = eng._pool_offload_block(5)
            per_block = sum(int(np.prod(leaf.shape)) // leaf.shape[1] * leaf.dtype.itemsize
                            for leaf in (eng._cache.k, eng._cache.v, eng._cache.ik))
            assert payload.nbytes == per_block
            eng._pool_onload_block(9, payload)
            np.testing.assert_array_equal(np.asarray(eng._cache.ik)[:, 9], after[:, 5])
            np.testing.assert_array_equal(np.asarray(eng._cache.k)[:, 9],
                                          np.asarray(eng._cache.k)[:, 5])
        finally:
            await eng.stop()

    asyncio.run(go())


def test_an_engine_without_the_pool_refuses_at_start():
    async def go():
        eng = _engine(kv_pool=False)
        with pytest.raises(ValueError, match="selects its keys .* the dense per-slot KV"):
            await eng.start()

    asyncio.run(go())


# ------------------------------ the selector's own count (REVIEW, PR 31)

def _decode_counts(monkeypatch, lens, broken):
    """``sel_rows`` after a window and STEPS decode steps over ``lens``."""
    from ai_agent_kubectl_tpu.ops import sparse_select

    if broken:      # a selector that keeps every causal key
        monkeypatch.setattr(sparse_select, "window_selection",
                            lambda scores, k: scores > -jnp.inf)
    params = random_params_int8(jax.random.PRNGKey(31), CFG, dtype=jnp.float32,
                                quantize_embed=True)
    B, window = len(lens), 128
    pages = -(-(window + STEPS) // PAGE)
    pool = (CFG.n_layers, B * pages, PAGE, CFG.n_kv_heads, CFG.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32),
                    ik=jnp.zeros(pool[:3] + (CFG.index_key_width,), jnp.float32),
                    sel_rows=jnp.zeros((2,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    toks = tokens(lens)

    def step(tok, pos, cache, wmask, q_lens):
        return forward(params, CFG, tok, pos, cache, kv_limit=pages * PAGE,
                       attn_impl="dense", token_mask=wmask, write_mask=wmask,
                       block_tables=tables, q_lens=q_lens)[1]

    cols = np.arange(window)[None, :]
    q_lens = np.asarray(lens, np.int32)
    win = np.zeros((B, window), np.int32)
    for b, n in enumerate(lens):
        win[b, :n] = toks[b, :n]
    cache = step(jnp.asarray(win), jnp.asarray(np.broadcast_to(cols, (B, window)).astype(np.int32)),
                 cache, jnp.asarray(cols < q_lens[:, None]), jnp.asarray(q_lens))
    after_window = np.asarray(cache.sel_rows)
    live = jnp.asarray([True] + [False] * (B - 1))      # slot 0 alone decodes
    for s in range(STEPS):
        tok = np.stack([toks[b, n + s] for b, n in enumerate(lens)])[:, None]
        cache = step(jnp.asarray(tok), jnp.asarray((q_lens + s)[:, None].astype(np.int32)),
                     cache, live[:, None], jnp.ones((B,), jnp.int32))
    return after_window, np.asarray(cache.sel_rows)


@pytest.mark.parametrize("broken", [False, True], ids=["selector", "keeps_every_key"])
def test_the_device_counts_what_decode_queries_saw_and_kept(monkeypatch, broken):
    """``KVCache.sel_rows`` is read off the mask the kernel is handed, in every
    layer: window rows are not decode rows, a dead slot's query is not counted,
    a live decode query past index_topk keeps exactly index_topk of its keys,
    and a selector that keeps every key shows as a share of 100%."""
    n = 100
    after_window, (live, kept) = _decode_counts(monkeypatch, (n, 80), broken)
    assert after_window.tolist() == [0, 0]
    assert live == CFG.n_layers * sum(n + s + 1 for s in range(STEPS))
    assert kept == (live if broken else CFG.n_layers * STEPS * CFG.index_topk)


def test_a_decode_query_with_few_keys_keeps_them_all(monkeypatch):
    """At most index_topk keys: the dense branch serves and counts every key."""
    _, (live, kept) = _decode_counts(monkeypatch, (20, 30), False)
    assert live == kept == CFG.n_layers * sum(20 + s + 1 for s in range(STEPS))


@pytest.mark.parametrize("spec,moe,sel", [(False, False, True), (False, True, True),
                                          (True, True, True), (True, False, False)],
                         ids=["sel", "moe_sel", "spec_moe_sel", "spec"])
def test_the_packed_chunk_carries_the_optional_lanes(spec, moe, sel):
    from ai_agent_kubectl_tpu.engine.protocol import (pack_chunk, packed_chunk_size,
                                                      unpack_chunk)

    n, ct = 3, 4
    lanes = dict(drafted=np.arange(n), accepted=np.arange(n)) if spec else {}
    buf = pack_chunk(np.arange(n * ct).reshape(n, ct), np.zeros(n, bool), np.full(n, ct), 2,
                     experts_read=77 if moe else None,
                     sel_rows=np.asarray([900, 48]) if sel else None, **lanes)
    assert buf.shape == (packed_chunk_size(n, ct, spec=spec, moe=moe, sel=sel),)
    res = unpack_chunk(buf, n, ct, spec=spec, moe=moe, sel=sel)
    assert res.n_alive == 2 and res.tokens.shape == (n, ct)
    assert res.experts_read == (77 if moe else None)
    assert res.sel_rows == ((900, 48) if sel else None)
    with pytest.raises(ValueError, match="packed chunk buffer"):
        unpack_chunk(buf, n, ct, spec=spec, moe=moe, sel=not sel)


@pytest.mark.parametrize("impl", ["ragged", "dense"], ids=["ragged", "gather"])
def test_a_mixed_window_selects_for_the_rows_that_ride(seeded, impl):
    """The chunk program's prologue: one slot rides at q_len 1 (its decode
    step, 101 keys before it), one brings a 20-row window (keys 61-80), one is
    dead. Only the riding rows are scored and selected (a window's rows for the
    slot that brings one, the first row for the others), and both live slots
    read what the reference reads."""
    params, weights = seeded
    ref = refcheck.load_reference(REFERENCE)
    lens, W = (100, 60, 0), 32
    toks = np.random.default_rng(9).integers(3, CFG.vocab_size, size=(3, 128), dtype=np.int32)
    B, pages = 3, 128 // PAGE
    pool = (CFG.n_layers, B * pages, PAGE, CFG.n_kv_heads, CFG.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32),
                    ik=jnp.zeros(pool[:3] + (CFG.index_key_width,), jnp.float32),
                    sel_rows=jnp.zeros((2,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)

    def step(tok, start, q_lens):
        cols = np.arange(tok.shape[1])[None, :]
        wmask = jnp.asarray(cols < np.asarray(q_lens)[:, None])
        return forward(params, CFG, jnp.asarray(tok), jnp.asarray(np.asarray(start)[:, None] + cols),
                       cache, kv_limit=pages * PAGE, attn_impl=impl, token_mask=wmask,
                       write_mask=wmask, block_tables=tables,
                       q_lens=jnp.asarray(q_lens, jnp.int32))

    _, cache = step(toks[:, :100], (0, 0, 0), lens)
    cache = dataclasses.replace(cache, sel_rows=jnp.zeros((2,), jnp.int32))
    mixed = np.zeros((B, W), np.int32)
    mixed[0, 0], mixed[1, :20] = toks[0, 100], toks[1, 60:80]
    logits, cache = step(mixed, (100, 60, 0), (1, 20, 0))
    want0, _ = ref.forward(SIZES, weights, jnp.asarray(toks[0, :101]))
    want1, _ = ref.forward(SIZES, weights, jnp.asarray(toks[1, :80]))
    std = float(np.asarray(want0).std())
    assert np.abs(np.asarray(logits[0, 0]) - np.asarray(want0)[100]).max() / std < 1e-4
    assert np.abs(np.asarray(logits[1, :20]) - np.asarray(want1)[60:80]).max() / std < 1e-4
    # the one decode row: 101 keys before it in each layer, index_topk kept
    assert np.asarray(cache.sel_rows).tolist() == [CFG.n_layers * 101,
                                                  CFG.n_layers * CFG.index_topk]


@pytest.mark.parametrize("suffix,rides", [(0, 0), (40, 40), (512, 512), (513, 64), (11000, 64)])
def test_a_suffix_past_the_widest_window_rides_the_narrowest(suffix, rides):
    """The scheduler's model-blind rule (REVIEW, PR 31): what the widest window
    holds rides whole, as in every chat cell; a longer suffix's head is eager
    anyway and a window costs every slot its width."""
    from ai_agent_kubectl_tpu.engine.batcher import staged_suffix_len

    assert staged_suffix_len(suffix, (64, 128, 256, 512)) == rides
