"""Attention layers of two kinds in one model (ISSUE 40; ``toy-sliding-moe``,
CPU, float32; the program against the plain reference's full forward is
tests/test_reference_logits_sliding.py, a file of its own so that another worker
takes it): the ragged kernel's lower bound at 6 and 9 query heads a KV head with the bound
falling mid-page; the rotary rule a kind; the share of the experts; the engine:
a re-ask seated from a snapshot of the sliding state answers as a cold prefill,
a match past every held snapshot recomputes and counts it, an evicted snapshot,
/health, the refusals; the fake's mirror; the packed chunk's four-word lane."""

import asyncio
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.kv_pool import (span_window_counts, state_cuts,
                                                 StateStore)
from ai_agent_kubectl_tpu.engine.protocol import pack_chunk, unpack_chunk
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                     init_params)
from ai_agent_kubectl_tpu.ops.ragged_attention import (ragged_attention_pool,
                                                       ring_tables)

ROOT = Path(__file__).resolve().parent.parent
CFG = get_config("toy-sliding-moe")
PAGE = 8


def _reference():
    spec = importlib.util.spec_from_file_location(
        "laguna_reference", ROOT / "benchmark/configs/laguna-s-2.1-l12.reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def sizes_of(cfg) -> dict:
    """The configuration file's keys for ``cfg`` (what the reference reads)."""
    return dict(
        num_attention_heads=cfg.n_heads, num_attention_heads_sliding=cfg.sliding_n_heads,
        num_key_value_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        sliding_window=cfg.sliding_window, rope_theta=cfg.rope_theta,
        sliding_rope_theta=cfg.sliding_rope_theta, partial_rotary_factor=cfg.rope_partial,
        sliding_partial_rotary_factor=cfg.sliding_rope_partial, factor=cfg.rope_factor,
        original_max_position_embeddings=cfg.rope_original_max, beta_fast=cfg.rope_beta_fast,
        beta_slow=cfg.rope_beta_slow, attention_factor=cfg.rope_attention_factor,
        num_experts_per_tok=cfg.experts_per_token, first_routed_expert=cfg.first_expert,
        moe_routed_scaling_factor=cfg.router_scale, rms_norm_eps=cfg.rms_eps)


SIZES = sizes_of(CFG)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)


@pytest.mark.parametrize("G,W,window", [(6, 1, 20), (9, 1, 20), (9, 5, 13), (6, 24, 20)],
                         ids=["decode-6", "decode-9", "verify-9", "window-6"])
def test_the_kernels_lower_bound_at_groups_of_6_and_9(G, W, window):
    """The interpreted kernel with ``window`` against plain attention under
    the banded mask: 6 and 9 query heads a KV head (the flat and the
    transposed form), a span that starts and ends mid-page (page 8), contexts
    many pages past it, a frozen slot, a sequence's ring read through
    ``ring_tables`` and the same rows read through a real table."""
    KV, hd, page, n_pages = 2, 32, 8, 12
    H, N = G * KV, 3
    rng = np.random.default_rng(G * 31 + W)
    pos = np.array([61, 3, 40], np.int32)
    q_lens = np.array([W, min(W, 2), 0], np.int32)
    q = jnp.asarray(rng.standard_normal((N, W, H, hd)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((N, n_pages * page, KV, hd)), jnp.float32)
    vals = jnp.asarray(rng.standard_normal((N, n_pages * page, KV, hd)), jnp.float32)
    # (a) a real pool: slot n's page p is block n * n_pages + p
    pool = lambda a: a.reshape(N * n_pages, page, KV, hd)
    tables = jnp.arange(N * n_pages, dtype=jnp.int32).reshape(N, n_pages)
    got = ragged_attention_pool(q, pool(keys), pool(vals), jnp.asarray(q_lens),
                                jnp.asarray(pos), tables, page_size=page, window=window,
                                interpret=True)
    # (b) rings of 6 pages (48 rows >= window + W - 1): position p at row p % 48
    ring = 48
    at = (np.arange(n_pages * page) % ring)
    newest = np.zeros((N, ring), np.int64)
    for n in range(N):
        last = pos[n] + max(int(q_lens[n]), 1) - 1
        for p in range(last + 1):
            newest[n, p % ring] = p
    rk = jnp.stack([keys[n][newest[n]] for n in range(N)])
    rv = jnp.stack([vals[n][newest[n]] for n in range(N)])
    as_pool = lambda a: a.reshape(N * (ring // page), page, KV, hd)
    got_ring = ragged_attention_pool(
        q, as_pool(rk), as_pool(rv), jnp.asarray(q_lens), jnp.asarray(pos),
        ring_tables(N, ring // page, n_pages), page_size=page, window=window,
        interpret=True)
    assert at.max() == ring - 1
    for n in range(N):
        for j in range(int(q_lens[n])):
            t = pos[n] + j
            lo = max(0, t - window + 1)
            k = jnp.repeat(keys[n, lo:t + 1], G, axis=1)           # [s, H, hd]
            v = jnp.repeat(vals[n, lo:t + 1], G, axis=1)
            s = jnp.einsum("hd,shd->hs", q[n, j], k) * hd ** -0.5
            want = jnp.einsum("hs,shd->hd", jax.nn.softmax(s, axis=-1), v)
            np.testing.assert_allclose(got[n, j], want, atol=2e-5)
            np.testing.assert_allclose(got_ring[n, j], want, atol=2e-5)
    assert not np.asarray(got[2]).any()                # the frozen slot's rows


def test_a_window_wider_than_the_ring_is_refused(params):
    """The engine's ring holds the span and its widest bucket; a wider window
    would overwrite rows its own first queries read."""
    from ai_agent_kubectl_tpu.models.transformer import sliding_zeros

    pool = (3, 8, PAGE, CFG.n_kv_heads, CFG.head_dim)
    sk, sv = sliding_zeros(CFG, 1, 40, jnp.float32)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((8,), jnp.int32), sk=sk, sv=sv)
    with pytest.raises(ValueError, match="needs a ring"):
        forward(params, CFG, jnp.zeros((1, 32), jnp.int32),
                jnp.arange(32, dtype=jnp.int32)[None], cache, kv_limit=64,
                block_tables=jnp.arange(8, dtype=jnp.int32)[None])


def test_the_dense_ladder_refuses_the_family(params):
    """Without block tables (``attn_impl`` dense over per-slot K/V) a sliding
    layer would attend to everything: refused with a sentence, not served."""
    cache = KVCache.zeros(CFG, 1, 64, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="sliding-attention layers"):
        forward(params, CFG, jnp.zeros((1, 8), jnp.int32),
                jnp.arange(8, dtype=jnp.int32)[None], cache)


def test_each_kind_has_its_own_rotary_rule_and_heads():
    """The full kind rotates half its lanes with YaRN's frequencies times the
    attention factor, the sliding kind all of them plainly; a configuration
    without the fields keeps ``apply_rope`` bit for bit."""
    from ai_agent_kubectl_tpu.models.transformer import _rotary_rule
    from ai_agent_kubectl_tpu.ops.rope import apply_rope

    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 5, 2, 32)), jnp.float32)
    pos = jnp.asarray([[0, 1, 70, 300, 1000]], jnp.int32)
    full, sliding = _rotary_rule(CFG, "*")(x, pos), _rotary_rule(CFG, "S")(x, pos)
    np.testing.assert_array_equal(full[..., 16:], x[..., 16:])      # unrotated lanes
    np.testing.assert_allclose(sliding, apply_rope(x, pos, 10000.0), atol=1e-6)
    # position 0 turns nothing: the rotated lanes carry the factor alone
    np.testing.assert_allclose(full[0, 0, :, :16], x[0, 0, :, :16] * 1.2, rtol=1e-6)
    assert not np.allclose(full[0, 2, :, :16], 1.2 * apply_rope(
        x[..., :16], pos, CFG.rope_theta)[0, 2])                    # YaRN's frequencies
    plain = get_config("toy-8m")
    np.testing.assert_array_equal(_rotary_rule(plain, "*")(x, pos),
                                  apply_rope(x, pos, plain.rope_theta))
    assert (CFG.heads_of("*"), CFG.heads_of("S")) == (4, 6)
    assert CFG.layer_kinds == tuple("*DSE*ESESE*E") and CFG.slides and CFG.keeps_state
    assert CFG.state_bytes() == 3 * 2 * 2 * 24 * 2 * 32


def test_the_shares_add_up_to_the_uncut_layer(params):
    """THE SHARE TEST: the router scores 16 experts; four trees hold 4 each of
    the SAME uncut model. Each share's expert mixer minus what every chip
    computes alike (the residual and the shared expert) is its experts' part
    of the routed sum; the four add up to the uncut mixer's, in the program and
    in the reference. Attention, the gate and the dense layer are whole on
    every chip and counted once."""
    from ai_agent_kubectl_tpu.models.transformer import _expert_mixer

    whole_cfg = dataclasses.replace(CFG, n_experts=16, router_width=0)
    whole = init_params(jax.random.PRNGKey(11), whole_cfg, dtype=jnp.float32)["layers"]
    x = jnp.asarray(np.random.default_rng(12).standard_normal((2, 24, CFG.dim)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut, _ = _expert_mixer(whole_cfg, whole, 1, x, None, None, "dense")
        cut_of = lambda first: {k: (v[:, first:first + 4] if k in ("w_gate", "w_up", "w_down")
                                    else v) for k, v in whole.items()}
        alike = None
        for moe_impl in ("auto", "dense"):
            parts = []
            for first in (0, 4, 8, 12):
                cfg = dataclasses.replace(CFG, first_expert=first)
                y, _ = _expert_mixer(cfg, cut_of(first), 1, x, None, None, moe_impl)
                parts.append(y)
            # residual + shared expert: what a share with no pick at all gives
            lw = ref.weights_from_program(
                {"embed": jnp.zeros((1, 1)), "final_norm": jnp.zeros((1,)),
                 "lm_head": jnp.zeros((1, 1)), "layers": cut_of(0)}, CFG.n_layers)
            expert_lw = [l for l in lw["layers"] if l["kind"] == "E"][1]
            xh = ref.rms_norm(x[0], expert_lw["norm"], CFG.rms_eps)
            alike = x[0] + ref.dense_mlp(expert_lw, xh, "shared_")
            total = sum(p[0] - alike for p in parts) + alike
            np.testing.assert_allclose(total, uncut[0], atol=3e-5)
        # the reference, told the same shares
        total = alike
        for first in (0, 4, 8, 12):
            lw = ref.weights_from_program(
                {"embed": jnp.zeros((1, 1)), "final_norm": jnp.zeros((1,)),
                 "lm_head": jnp.zeros((1, 1)), "layers": cut_of(first)}, CFG.n_layers)
            expert_lw = [l for l in lw["layers"] if l["kind"] == "E"][1]
            xh = ref.rms_norm(x[0], expert_lw["norm"], CFG.rms_eps)
            y, _ = ref.experts(dict(SIZES, first_routed_expert=first), expert_lw, xh)
            total = total + y - ref.dense_mlp(expert_lw, xh, "shared_")
        np.testing.assert_allclose(total, uncut[0], atol=3e-5)


# ------------------------------------------------------- the rule and the lane

def test_the_cut_rule_leaves_a_snapshot_a_page_before_the_last_block_edge():
    """``state_cuts``: the prompt's last whole block AND the edge before it, so
    that a prompt which diverges inside the last page still finds a state."""
    store = StateStore(4, 1)
    assert state_cuts(store, 0, 241, 16, 0) == [224, 240]
    assert state_cuts(store, 0, 241, 16, 224) == [240]      # seated past the first
    assert state_cuts(store, 0, 20, 16, 0) == [16]          # no edge under a page
    assert state_cuts(store, 0, 12, 16, 0) == []


def test_span_window_counts_apply_the_span():
    c = span_window_counts(0, 100, 24)
    assert c == {"window_rows": 100, "window_pairs_full": 5050,
                 "window_pairs_sliding": 300 + 24 * 76}
    late = span_window_counts(90, 100, 24)
    assert late["window_pairs_sliding"] == 240 and late["window_pairs_full"] == 955


@pytest.mark.parametrize("moe", [False, True])
def test_the_packed_chunk_carries_four_attention_words(moe):
    toks = np.arange(8, dtype=np.int32).reshape(2, 4)
    z = np.zeros(2, np.int32)
    buf = pack_chunk(toks, z, z, 2, sel_rows=(7, 8, 9, 10),
                     experts_read=5 if moe else None)
    res = unpack_chunk(buf, 2, 4, moe=moe, sel=4)
    assert res.sel_rows == (7, 8, 9, 10) and res.n_alive == 2
    assert res.experts_read == (5 if moe else None)
    two = unpack_chunk(pack_chunk(toks, z, z, 2, sel_rows=(3, 4)), 2, 4, sel=True)
    assert two.sel_rows == (3, 4)
    with pytest.raises(ValueError, match="sel=2"):
        unpack_chunk(buf, 2, 4, moe=moe, sel=2)


# ------------------------------------------------------------------ the engine

LOG = "pod web-1 crashed with OOMKilled at 12:03; " * 5          # 215 byte tokens
ASKS = ["why crashed?", "which pod??", "when was it?"]           # under a page of 16


def _engine(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    defaults = dict(dtype="float32", max_seq_len=384, prefill_buckets=(16, 64),
                    prefix_cache=False, batch_size=2, chunk_len=4, kv_pool_page=16,
                    state_snapshots=8, kv_pool_blocks=96, radix_lru_blocks=64)
    defaults.update(kw)
    return BatchedJaxEngine(CFG, tokenizer=ByteTokenizer(), **defaults)


async def _ask(eng, prompts):
    """Each prompt's answer and what it moved in /health.ssm."""
    await eng.start()
    try:
        texts, deltas = [], []
        for prompt in prompts:
            before = eng.family_health()["ssm"]
            texts.append((await eng.generate(prompt, max_tokens=12, temperature=0.0,
                                             seed=1)).text)
            after = eng.family_health()["ssm"]
            deltas.append({k: after[k] - before[k] for k in (
                "prefix_tokens_matched", "prefix_tokens_usable",
                "prefix_tokens_recomputed", "snapshots_taken", "snapshots_evicted",
                "restores")})
        eng._state.check()
        return texts, deltas, eng.stats()
    finally:
        await eng.stop()


@pytest.fixture(scope="module")
def cold():
    """Every ask answered by an engine with no radix tree: a cold prefill."""
    return asyncio.run(_ask(_engine(radix_cache=False), [LOG + q for q in ASKS]))[0]


@pytest.mark.parametrize("force_ragged", [False, True], ids=["gather", "ragged-staged"])
def test_a_re_ask_is_seated_from_the_sliding_states_snapshot(cold, force_ragged):
    """A second and third question about the same log diverge from the first
    inside its last page: they match the log's blocks up to the edge before
    the first ask's last whole block, find the snapshot the cut rule left
    there, restore the sliding layers' last rows into their slot's ring and
    prefill only what follows — and say what a cold prefill says. The pool
    keeps the full layers' rows alone; the counters say what the kinds read."""
    texts, deltas, st = asyncio.run(_ask(_engine(force_ragged=force_ragged),
                                         [LOG + q for q in ASKS]))
    assert texts == cold
    n = 1 + len(LOG) + len(ASKS[0])                      # a BOS and bytes
    edge = (n - 1) // 16 * 16 - 16
    assert deltas[0]["prefix_tokens_matched"] == 0 and deltas[0]["snapshots_taken"] == 2
    for d in deltas[1:]:
        assert d["prefix_tokens_usable"] == d["prefix_tokens_matched"] == edge
        assert d["restores"] == 1 and d["prefix_tokens_recomputed"] == 0
    pool = st["kv_pool"]
    assert pool["attention_regime"] == ("ragged" if force_ragged else "gather")
    assert pool["radix"]["hit_tokens"] == 2 * edge
    # 3 full layers x K and V x 2 KV heads x 32 x 4 B: the sliding layers keep
    # nothing in the pool
    assert pool["bytes_per_token"] == 3 * 2 * 2 * 32 * 4
    sl = st["sliding_attention"]
    assert sl["span"] == 24 and sl["ring_rows"] == 96 and sl["snapshot_rows"] == 24
    assert sl["layers_sliding"] == sl["layers_full"] == 3
    assert sl["decode_rows_sliding"] == sl["decode_rows_full"] > 0
    assert sl["sliding_keys_read"] <= 24 * sl["decode_rows_sliding"]
    assert sl["full_keys_read"] > 8 * sl["sliding_keys_read"]
    assert sl["window_pairs_sliding"] < sl["window_pairs_full"] and sl["window_rows"] > n
    assert st["ssm"]["state_bytes"] == CFG.state_bytes()          # as bf16 keeps it
    assert st["ssm"]["layer_passes"]["sliding"] == st["ssm"]["forward_passes"] * 3


def test_a_match_past_every_held_snapshot_recomputes_and_counts_it(cold):
    """A prompt that shares only the log's first half matches K/V blocks no
    snapshot stands on (the first ask left its two near its end): nothing is
    usable, every matched token is recomputed and counted so. Then a store of
    two: another log's snapshots evict the first log's, and a re-ask about the
    first log matches its K/V, finds no state on its path and recomputes the
    log — with a cold prefill's answer both times."""
    half = [LOG + ASKS[0], LOG[:100] + " and then what happened to it?"]
    texts, deltas, _ = asyncio.run(_ask(_engine(), half))
    assert texts[0] == cold[0]
    assert deltas[1]["prefix_tokens_matched"] == 96
    assert deltas[1]["prefix_tokens_usable"] == 0 and deltas[1]["restores"] == 0
    assert deltas[1]["prefix_tokens_recomputed"] == 96

    other = "node pool beta drained at 09:41 by the autoscaler; " * 4
    texts, deltas, _ = asyncio.run(_ask(_engine(state_snapshots=2),
                                        [LOG + ASKS[0], other + "why?", LOG + ASKS[1]]))
    assert texts[0] == cold[0] and texts[2] == cold[1]
    assert deltas[1]["snapshots_evicted"] == 2          # the first log's two
    re_ask = deltas[2]
    assert re_ask["prefix_tokens_matched"] == re_ask["prefix_tokens_recomputed"] == 208
    assert re_ask["prefix_tokens_usable"] == 0 and re_ask["restores"] == 0


def test_an_engine_without_the_pool_refuses_at_start():
    """The messages: tests/test_families.py's matrix."""
    with pytest.raises(ValueError, match="sliding-attention state"):
        asyncio.run(_engine(kv_pool=False).start())


async def test_the_fake_mirrors_the_rule_and_the_counters():
    """The fake scheduler runs ``state_cuts`` verbatim over a state of no
    bytes: a re-ask that diverges inside the last page is seated from the
    snapshot a page before the first ask's last block edge; its
    /health.sliding_attention applies the span."""
    import time

    from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine, _FakeReq
    from ai_agent_kubectl_tpu.engine.qos import LANE_INTERACTIVE

    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, kv_pool_page=16, max_seq_len=512,
                            state_snapshots=8, sliding_window=24)

    async def ask(prompt):
        ids = FakeChunkedEngine._prompt_token_ids(prompt)
        eng._queue.put(_FakeReq(
            prompt=prompt, max_tokens=6, deadline=None, out_queue=asyncio.Queue(),
            cancel=asyncio.Event(), stream=[5, 6, 7, 8, 9, 2], tenant="t",
            lane=LANE_INTERACTIVE, t_submit=time.monotonic(), prompt_ids=ids))
        eng._admit_pending()
        for _ in range(2000):
            eng._tick()
            if (all(s is None for s in eng._slots) and not eng._inflight
                    and not eng._queue and not eng._parked):
                break
            await asyncio.sleep(0)
        eng._state.check()
        return len(ids)

    log = " ".join(f"t{100 + i}" for i in range(216))
    n = await ask(log + " t901 t902 t903")
    before = eng.family_health()["ssm"]
    await ask(log + " t911 t912 t913")
    after = eng.family_health()["ssm"]
    # 216 shared tokens: the match ends at block edge 208, where one
    # of the first ask's two snapshots (at 192 and at 208) stands
    assert n == 219 and state_cuts(eng._state, 0, n, 16, 0) == [192, 208]
    assert after["prefix_tokens_usable"] - before["prefix_tokens_usable"] == 208
    # (the 8 matched rows of the partial block past it are recomputed)
    assert after["prefix_tokens_recomputed"] - before["prefix_tokens_recomputed"] == 8
    sl = eng.stats()["sliding_attention"]
    assert sl["span"] == 24 and sl["window_rows"] > n
    assert sl["sliding_keys_read"] == 24 * sl["decode_rows_sliding"] > 0
    assert sl["full_keys_read"] > 5 * sl["sliding_keys_read"]
    assert FakeChunkedEngine(batch_size=1).stats()["sliding_attention"] is None
