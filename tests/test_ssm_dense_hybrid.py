"""Mamba-2 layers beside attention layers of 64-wide heads, a dense MLP behind
each, a scanned period, four muP scalars and a tied head (ISSUE 53), on the CPU
at toy size (``toy-ssm-dense``) against the benchmark's plain reference for
granite-4.0-h-micro, loaded by path as benchmark/refcheck.py loads it."""

import asyncio
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models import transformer
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import (KVCache, _scan_period,
                                                     forward, init_params)
from ai_agent_kubectl_tpu.ops.attention import dense_attention
from ai_agent_kubectl_tpu.ops.ragged_attention import (lane_heads, pair_queries,
                                                       ragged_attention_pool,
                                                       ragged_supported,
                                                       unpair_outputs)

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import refcheck  # noqa: E402

CFG = get_config("toy-ssm-dense")
REFERENCE = "benchmark/configs/granite-4.0-h-micro.reference.py"
#: the toy under the source's own names, as modelmap.sizes hands a file's
SIZES = {"num_attention_heads": CFG.n_heads, "num_key_value_heads": CFG.n_kv_heads,
         "head_dim": CFG.head_dim, "rms_norm_eps": CFG.rms_eps,
         "mamba_n_heads": CFG.ssm_heads, "mamba_d_head": CFG.ssm_head_dim,
         "mamba_n_groups": CFG.ssm_groups, "mamba_d_state": CFG.ssm_state,
         "mamba_d_conv": CFG.ssm_conv,
         "embedding_multiplier": CFG.embed_multiplier,
         "residual_multiplier": CFG.residual_multiplier,
         "attention_multiplier": CFG.attention_multiplier,
         "logits_scaling": CFG.logits_scaling}
PAGE, STEPS = 16, 2
#: max |logit - reference| at a position over the reference logits' standard
#: deviation, float32 weights and activations on both sides: what is left is the
#: chunked scan's and the kernels' order of summation
TOLERANCE_REL = 2e-3
TOKS = np.random.default_rng(5).integers(3, 500, size=(2, 80), dtype=np.int32)
#: the second window starts from a carried state; a row crosses the scan's
#: 16-token chunk edges and one brings fewer tokens than the convolution's taps
WINDOWS = [[40, 3], [17, 30]]


@pytest.fixture(scope="module")
def ref():
    return refcheck.load_reference(REFERENCE)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), CFG, dtype=jnp.float32)


def through_the_pool(params, impl="ragged", lanes=2):
    """The serving path: ``WINDOWS`` as ragged windows through the pool and the
    state (each from what the one before left), then STEPS decode steps. The
    pool is the engine's (``KVCache.pool_zeros``), ``lanes`` KV heads a 128-lane
    row as the compiled kernel's pool has them."""
    B = TOKS.shape[0]
    W = max(max(w) for w in WINDOWS)
    pages = -(-(sum(max(w) for w in WINDOWS) + STEPS) // PAGE)
    cache = KVCache.pool_zeros(CFG, n_blocks=B * pages, page=PAGE, slots=B,
                               dtype=jnp.float32, lane_heads=lanes)
    assert cache.k.shape == (2, B * pages, PAGE, 2 // lanes, 64 * lanes)
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)

    @jax.jit
    def step(params, tok, pos, cache, wmask, q_lens):
        return forward(params, CFG, tok, pos, cache, kv_limit=pages * PAGE,
                       attn_impl=impl, token_mask=wmask, write_mask=wmask,
                       block_tables=tables, q_lens=q_lens)

    done = np.zeros(B, np.int32)
    got = [[] for _ in range(B)]
    for q in WINDOWS + [[1] * B] * STEPS:
        q = np.asarray(q, np.int32)
        w = W if q.max() > 1 else 1
        tok = np.zeros((B, w), np.int32)
        for b in range(B):
            tok[b, :q[b]] = TOKS[b, done[b]:done[b] + q[b]]
        pos = (done[:, None] + np.arange(w)[None, :]).astype(np.int32)
        logits, cache = step(params, jnp.asarray(tok), jnp.asarray(pos), cache,
                             jnp.asarray(np.arange(w)[None, :] < q[:, None]),
                             jnp.asarray(q))
        for b in range(B):
            got[b].append(np.asarray(logits[b, :q[b]]))
        done += q
    return [np.concatenate(g) for g in got], cache


def rel_err(got, want):
    want = np.asarray(want)
    return float(np.abs(got - want).max(axis=1).max() / want.std())


def wanted(ref, params):
    weights = ref.weights_from_program(params, CFG.n_layers)
    out = []
    for b in range(2):
        n = sum(w[b] for w in WINDOWS) + STEPS
        want, aux = ref.forward(SIZES, weights, jnp.asarray(TOKS[b, :n]))
        assert aux == {}
        out.append(np.asarray(want))
    return out


@pytest.fixture(scope="module")
def program(params):
    """The chip's path: the ragged kernel (interpreted) over a pool of two KV
    heads a lane tile, the scanned period."""
    return through_the_pool(params)


def test_program_equals_the_reference_through_pool_state_and_decode(ref, params,
                                                                    program):
    """Two ragged windows of unequal rows and decode steps, the multipliers all
    away from 1: every position's logits against the plain reference."""
    got, cache = program
    assert _scan_period(CFG.layer_kinds) == 10 and CFG.n_of("M") == 8
    assert cache.ssm.shape == (8, 2, 8, 16, 32) and cache.ssm.dtype == jnp.float32
    assert cache.conv.shape == (8, 2, 3, CFG.ssm_conv_dim)
    for got_b, want in zip(got, wanted(ref, params)):
        assert rel_err(got_b, want) < TOLERANCE_REL


@pytest.mark.parametrize("multiplier", ["embedding_multiplier", "residual_multiplier",
                                        "attention_multiplier", "logits_scaling"])
def test_the_tolerance_fails_a_reference_without_one_multiplier(ref, params, program,
                                                                multiplier, monkeypatch):
    """Each of the four scalars, read as absent by the reference alone (1; the
    attention's as head_dim ** -0.5), moves the logits by far more than the
    tolerance: the program applies every one."""
    monkeypatch.setattr(ref, "LEAVE_OUT", frozenset({multiplier}))
    worst = max(rel_err(g, w) for g, w in zip(program[0], wanted(ref, params)))
    assert worst > 10 * TOLERANCE_REL, (multiplier, worst)


def test_the_scanned_period_equals_the_same_mixers_unrolled(params, program,
                                                            monkeypatch):
    """One period of ten mixers as the body of a scan over its two repeats,
    every stack and both state planes addressed by a traced ordinal, against
    the twenty mixers unrolled: the logits, and every plane of the state."""
    monkeypatch.setattr(transformer, "_scan_period", lambda kinds: 0)
    got, cache = through_the_pool(params)
    for a, b in zip(got, program[0]):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5)
    for name in ("ssm", "conv", "k", "v"):
        np.testing.assert_allclose(np.asarray(getattr(cache, name)),
                                   np.asarray(getattr(program[1], name)),
                                   rtol=0, atol=2e-5)
    # (a pattern with an expert or a sliding layer keeps its unrolled program)
    monkeypatch.undo()
    assert _scan_period(tuple("MEMEM*EMEMEM*")) == 0
    assert _scan_period(tuple("*DSE*ESESE*E")) == 0
    assert _scan_period(tuple("MDMDMDMDMD*DMDMDMDMD" * 4)) == 20


def test_heads_paired_in_a_lane_tile_change_nothing(params, program):
    """The same model through the gather path and through the kernel over a
    pool of a head a row: the pairing moves no logit and the leaves hold the
    same rows in the same order."""
    for impl, lanes in (("dense", 1), ("ragged", 1)):
        got, cache = through_the_pool(params, impl=impl, lanes=lanes)
        for a, b in zip(got, program[0]):
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(cache.k).reshape(program[1].k.shape),
            np.asarray(program[1].k), rtol=0, atol=2e-6)


@pytest.mark.parametrize("W,q_lens,positions", [(24, [24, 9, 0], [40, 0, 7]),
                                                (1, [1, 1, 1], [63, 5, 0])],
                         ids=["window", "decode"])
def test_heads_of_64_through_the_kernel_equal_dense_attention(W, q_lens, positions):
    """32 query heads over 8 KV heads of 64 (GQA 4:1) against a pool whose rows
    hold 4 heads of 128 lanes: the interpreted kernel on paired queries, at the
    model's own softmax scale, equals dense attention over the 64-wide heads."""
    H, KV, hd, page, pages, N = 32, 8, 64, 16, 4, 3
    assert lane_heads(hd, KV) == 2 and lane_heads(128, 8) == 1
    assert lane_heads(64, 1) == 0 and lane_heads(96, 8) == 0
    assert ragged_supported(64, 64, 1, 8) and not ragged_supported(64, 64, 1, 1)
    r = np.random.default_rng(W)
    k, v = (jnp.asarray(r.normal(size=(N * pages, page, KV, hd)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(r.normal(size=(N, W, H, hd)), jnp.float32)
    tables = jnp.arange(N * pages, dtype=jnp.int32).reshape(N, pages)
    ql, pos = jnp.asarray(q_lens, jnp.int32), jnp.asarray(positions, jnp.int32)
    paired = lambda a: a.reshape(N * pages, page, KV // 2, 2 * hd)
    got = unpair_outputs(ragged_attention_pool(
        pair_queries(q, KV, 2), paired(k), paired(v), ql, pos, tables,
        page_size=page, scale=1 / 64), KV, 2)
    ctx = lambda a: a.reshape(N, pages * page, KV, hd)
    at = pos[:, None] + jnp.arange(W)[None, :]
    mask = jnp.arange(pages * page)[None, None, :] <= at[:, :, None]
    want = dense_attention(q, ctx(k), ctx(v), mask, scale=1 / 64)
    for n, n_q in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(got)[n, :n_q], np.asarray(want)[n, :n_q],
                                   rtol=2e-5, atol=2e-5)
    # at head_dim ** -0.5 the scores are eight times as large: not the same
    other = dense_attention(q, ctx(k), ctx(v), mask)
    assert np.abs(np.asarray(other)[0, 0] - np.asarray(want)[0, 0]).max() > 0.05


def test_a_tpu_resolves_ragged_for_heads_of_64_on_one_device():
    """``ragged_supported`` says true for 64 where the KV heads pair up, and the
    one place that decides the regime follows it: ragged on a TPU alone, the
    gather over a model axis (the pairing runs on one device) and for a lone
    KV head of 64, which no form serves."""
    from ai_agent_kubectl_tpu.engine.regime import resolve_attention_regime

    ask = lambda cfg, mesh=None: resolve_attention_regime(
        cfg, backend="tpu", mesh_shape=mesh, kv_quant="", kv_pool=True,
        device_termination=True, pool_page=64)[0]
    assert ask(CFG) == "ragged"
    assert ask(CFG, {"model": 2}) == "gather"
    assert ask(dataclasses.replace(CFG, n_kv_heads=1)) == "gather"
    assert ask(get_config("toy-hybrid-moe")) == "gather"      # two KV heads of 32


# ---------------------------------------------------------------- the engine

def _mk(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    defaults = dict(dtype="float32", max_seq_len=320, prefill_buckets=(16, 64),
                    prefix_cache=False, batch_size=2, chunk_len=4,
                    kv_pool_page=16, state_snapshots=4, kv_pool_blocks=96,
                    radix_lru_blocks=64)
    defaults.update(kw)
    return BatchedJaxEngine(CFG, tokenizer=ByteTokenizer(), **defaults)


PREAMBLE = "cluster context: " + "node pool alpha beta gamma delta " * 3
TURNS = ["agent one asks about pods in kube-system;  ",
         "tool says twelve pods are ready; ",
         "tool says one pod is crash looping now; "]
OTHERS = ["agent two starts here and lists every deployment; ",
          "agent three is here and wants the node status; "]


def _session(eng):
    """Agent one's three turns with two other agents' prompts between the
    second and the third: a store of four snapshots evicts on the way."""
    async def run():
        await eng.start()
        try:
            out, hist = {}, PREAMBLE
            for i, t in enumerate(TURNS):
                if i == 2:
                    for o in OTHERS:
                        out[o] = (await eng.generate(PREAMBLE + o, max_tokens=6,
                                                     temperature=0.0)).text
                hist += t
                out[hist] = (await eng.generate(hist, max_tokens=8,
                                                temperature=0.0)).text
            return out, eng.family_health(), eng.kv_pool_health()
        finally:
            await eng.stop()

    return asyncio.run(run())


def test_a_session_snapshotted_evicted_and_restored_answers_as_from_token_zero():
    """A pattern of two mixers a layer with Mamba-2 layers through StateStore:
    turns seated from the snapshot the turn before left (eight planes of state
    and convolution tail a sequence), other agents' prompts evicting from a
    store of four, every answer the engine's that prefilled from token 0; the
    books balance and /health says what was resolved at start."""
    want, _, _ = _session(_mk(radix_cache=False))
    eng = _mk()
    got, health, pool = _session(eng)
    assert got == want
    st = health["ssm"]
    assert st["restores"] >= 2 and st["prefix_tokens_usable"] > 0
    assert st["state_bytes"] == CFG.state_bytes() == 8 * (4 * 8 * 16 * 32 + 2 * 3 * 192)
    assert st["held_peak"] == st["capacity"] == 4 and st["snapshots_taken"] > 4
    assert st["layer_passes"]["ssm"] == st["forward_passes"] * 8
    assert st["layer_passes"]["dense_mlp"] == st["forward_passes"] * 10
    assert st["layer_passes"]["attention"] == st["forward_passes"] * 2
    assert st["decode_rows_still"] > 0 and st["decode_rows_still"] % 8 == 0
    # (the window kernel's three words, ISSUE 54, count a chunk program's
    # prologue windows: under ``gather`` a prompt prefills in eager pieces)
    assert {st[k] for k in ("window_rows_moved", "window_rows_still",
                            "window_chunks_skipped")} == {0}
    # the CPU serves the gather regime: a head a row of the pool's lanes
    assert pool["attention_regime"] == "gather" and pool["attention_lane_heads"] == 1
    eng._state.check()
