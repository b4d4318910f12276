"""Kimi delta attention (the delta rule with a decay for every key channel)
beside latent attention in ONE layer pattern, under a group-limited sigmoid
router over a chip's share of the experts (ISSUE 48), on the CPU at toy size
(``toy-kda-mla-moe``: two KDA layers to one latent layer, a leading dense
layer, 4 held of 16 experts in 4 groups of which 2 stay) against the
benchmark's plain reference for ling-3.0-flash-vl-l12, loaded by path as
benchmark/refcheck.py loads it."""

import asyncio
import dataclasses
import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                     init_params)
from ai_agent_kubectl_tpu.ops import gated_delta as GD
from ai_agent_kubectl_tpu.ops.quant import random_params_int8

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import refcheck  # noqa: E402

CFG = get_config("toy-kda-mla-moe")
FILE = "benchmark/configs/ling-3.0-flash-vl-l12.json"
REFERENCE = "benchmark/configs/ling-3.0-flash-vl-l12.reference.py"
#: the toy's sizes under the source's names; its order is two KDA layers to a
#: latent one behind ONE dense layer (the published: five to one behind two)
SIZES = {"num_attention_heads": CFG.n_heads, "rms_norm_eps": CFG.rms_eps,
         "linear_num_value_heads": CFG.lin_value_heads,
         "linear_key_head_dim": CFG.lin_key_dim,
         "linear_value_head_dim": CFG.lin_value_dim,
         "short_conv_kernel_size": CFG.lin_conv,
         "kda_lower_bound": CFG.lin_decay_floor, "kv_lora_rank": CFG.kv_lora_rank,
         "qk_nope_head_dim": CFG.qk_nope_head_dim,
         "qk_rope_head_dim": CFG.qk_rope_head_dim, "v_head_dim": CFG.v_head_dim,
         "rope_theta": CFG.rope_theta, "num_experts_per_tok": CFG.experts_per_token,
         "n_group": CFG.n_group, "topk_group": CFG.topk_group,
         "routed_scaling_factor": CFG.router_scale, "first_routed_expert": 0,
         "layer_group_size": 3, "first_k_dense_replace": 1}
PAGE, STEPS = 16, 3
#: max |logit - reference| at a position over the reference logits' standard
#: deviation, float32 weights and activations on both sides
TOLERANCE_REL = 2e-3


@pytest.fixture(scope="module")
def ref():
    return refcheck.load_reference(REFERENCE)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), CFG, dtype=jnp.float32)


# ------------------------------------------------------------- the recurrence

def recurrence(q, k, v, g, beta, S0):
    """Token by token in numpy float64: S' = Diag(alpha) S; u = beta (v - S'^T
    k); S = S' + k u^T; o = S^T q. Shapes as ``channel_decay_scan``'s."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    Sh = np.moveaxis(np.asarray(S0, np.float64).reshape(B, dk, H, dv), 2, 1).copy()
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    out = np.zeros((B, S, H, dv))
    for t in range(S):
        Sh = np.exp(g[:, t])[..., None] * Sh
        u = beta[:, t][..., None] * (v[:, t] - np.einsum("bhkv,bhk->bhv", Sh, k[:, t]))
        Sh = Sh + np.einsum("bhk,bhv->bhkv", k[:, t], u)
        out[:, t] = np.einsum("bhkv,bhk->bhv", Sh, q[:, t])
    return out, np.moveaxis(Sh, 1, 2).reshape(B, dk, H * dv)


def scan_inputs(seed, B, S, q_lens, floor=-5.0, at_floor=0.5, H=4, dk=24, dv=40):
    """Decays in (floor, 0): a share ``at_floor`` of the channels sit at the
    bound itself (sigmoid saturated: g == floor exactly in float32), the rest
    spread down to nothing."""
    r = np.random.default_rng(seed)
    live = (np.arange(S)[None, :] < np.asarray(q_lens)[:, None])[..., None]
    arg = np.where(r.uniform(size=(B, S, H, dk)) < at_floor, 40.0,
                   r.normal(size=(B, S, H, dk)) * 4 - 3)
    g = floor / (1.0 + np.exp(-arg))
    return dict(
        q=GD.l2_normalize(r.normal(size=(B, S, H, dk)), dk ** -0.5),
        k=GD.l2_normalize(r.normal(size=(B, S, H, dk))),
        v=jnp.asarray(r.normal(size=(B, S, H, dv)), jnp.float32),
        g=jnp.asarray(np.where(live[..., None], g, 0.0), jnp.float32),
        beta=jnp.asarray(np.where(live, r.uniform(0.0, 1.0, (B, S, H)), 0.0), jnp.float32),
        S0=jnp.asarray(r.normal(size=(B, dk, H * dv)), jnp.float32))


@pytest.mark.parametrize("S,chunk,at_floor", [
    (150, 64, 0.5), (150, 64, 1.0), (150, 16, 1.0), (200, 32, 0.9), (64, 64, 0.5),
    (37, 64, 0.5), (5, 64, 1.0), (70, 48, 0.0)])
def test_the_chunked_scan_equals_the_recurrence_with_decays_at_the_floor(S, chunk, at_floor):
    """channel_decay_scan from an INITIAL state, rows padded past unequal
    q_lens: the recurrence's outputs at every real token and its state at each
    row's q_len, over more than one 64-row chunk with half, nine tenths or ALL
    of the channels at the floor of -5 (64 rows at the floor are a decay of
    e^-320: factored over the chunk it overflows; over 16-row blocks against
    the block's last row nothing passes e^75). No overflow, no NaN."""
    q_lens = [S, max(1, S // 4), 0]
    a = scan_inputs(S, 3, S, q_lens, at_floor=at_floor)
    assert at_floor == 0.0 or float(a["g"].min()) == -5.0
    want_o, want_S = recurrence(**a)
    o, S1 = jax.jit(GD.channel_decay_scan, static_argnums=6)(*a.values(), chunk)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S1)).all()
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(o)[b, :n], want_o[b, :n], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S1), want_S, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(S1)[2], np.asarray(a["S0"])[2])
    assert S1.dtype == jnp.float32 and S1.shape == a["S0"].shape


@pytest.mark.parametrize("S", [130, 64, 9])
def test_with_a_heads_decays_all_equal_it_is_the_gated_delta_rule(S):
    """The rule with the decay a scalar a head (ops/gated_delta.py's, olmo-
    hybrid-7b's) is this one with the head's 24 channels equal, beta up to 2."""
    r = np.random.default_rng(S)
    a = scan_inputs(S, 2, S, [S, S // 2])
    g = jnp.asarray(np.where(np.asarray(a["beta"]) > 0, -r.uniform(1e-3, 0.7, (2, S, 4)), 0.0),
                    jnp.float32)
    a["beta"] = 2 * a["beta"]
    want = GD.gated_delta_scan(a["q"], a["k"], a["v"], g, a["beta"], a["S0"])
    got = GD.channel_decay_scan(a["q"], a["k"], a["v"],
                                jnp.broadcast_to(g[..., None], a["q"].shape), a["beta"], a["S0"])
    for w, h in zip(want, got):
        np.testing.assert_allclose(np.asarray(h), np.asarray(w), rtol=2e-5, atol=2e-6)


def test_a_chunk_that_is_not_whole_blocks_is_refused():
    a = scan_inputs(0, 1, 100, [100])
    with pytest.raises(ValueError, match="whole blocks of 16"):
        GD.channel_decay_scan(*a.values(), 24)
    with pytest.raises(ValueError, match="lin_decay_floor in \\[-5.5, 0\\), not -8.0"):
        dataclasses.replace(CFG, lin_decay_floor=-8.0).layer_kinds


@pytest.mark.parametrize("H,dk,dv,block_heads", [(4, 24, 40, 0), (4, 16, 128, 2),
                                                 (6, 8, 64, 2), (2, 128, 128, 1)])
def test_the_step_kernel_takes_a_decay_a_key_channel(H, dk, dv, block_heads):
    """The Pallas step kernel (interpreted) on the whole leaf against the
    ``jnp`` step and the float64 recurrence, three tokens running: a live row,
    a padded one (its state untouched) and one with every channel at the
    floor; the decay multiplies the ROWS of a head's tile."""
    a = scan_inputs(H + dk, 3, 3, [3, 0, 3], H=H, dk=dk, dv=dv)
    a["g"] = a["g"].at[2].set(-5.0)
    want_o, want_S = recurrence(**a)
    leaf = jnp.stack([jnp.zeros_like(a["S0"]), a["S0"]])
    S = a["S0"]
    for t in range(3):
        one = [a[n][:, t:t + 1] for n in ("q", "k", "v", "g", "beta")]
        o_j, S = GD.channel_decay_step(*one, S)
        o_k, leaf = GD.gated_delta_step_kernel(*one, leaf, 1, block_heads=block_heads)
        np.testing.assert_allclose(np.asarray(o_j)[[0, 2], 0], want_o[[0, 2], t],
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(o_k)[[0, 2]], np.asarray(o_j)[[0, 2]],
                                   rtol=2e-5, atol=2e-6)
        assert not np.asarray(o_k)[1].any()     # a row that does not move: zeros
    np.testing.assert_allclose(np.asarray(leaf[1]), want_S, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(leaf[1, 1]), np.asarray(a["S0"][1]))
    np.testing.assert_array_equal(np.asarray(leaf[0]), 0.0)


# ----------------------------------------------------------------- the router

def test_the_group_limited_choice_by_hand():
    """16 experts in 4 groups of which 2 stay: a group's score is the sum of
    its two largest score + bias; the picks come from the staying groups alone,
    though the largest score of all sits in a group that does not stay; the
    weights are the picked scores WITHOUT the bias, normalised, times 2.5."""
    from ai_agent_kubectl_tpu.parallel.moe import top_k_routing

    s = np.full((1, 16), 0.10)
    s[0, [0, 1]] = 0.60, 0.55           # group 0: 1.15
    s[0, 4] = 0.95                      # group 1: 0.95 + 0.10 = 1.05 (the largest expert)
    s[0, [8, 9]] = 0.50, 0.58           # group 2: 1.08
    bias = np.zeros(16)
    bias[9] = 0.05                      # group 2: 1.13; the bias picks, it does not weigh
    logits = jnp.asarray(np.log(s / (1 - s)), jnp.float32)
    w, idx = top_k_routing(CFG, logits, jnp.asarray(bias, jnp.float32))
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 9]
    order = np.argsort(np.asarray(idx)[0])
    np.testing.assert_allclose(np.asarray(w)[0][order],
                               2.5 * np.array([0.60, 0.58]) / 1.18, rtol=1e-5)
    free = dataclasses.replace(CFG, n_group=1, topk_group=1)
    assert sorted(np.asarray(top_k_routing(free, logits, jnp.asarray(
        bias, jnp.float32))[1])[0].tolist()) == [4, 9]


def test_the_four_shares_of_a_layers_experts_add_up_to_the_uncut_layer(ref):
    """The router scores 16 experts under the group limit; four trees hold 4
    each (experts 0-3, 4-7, 8-11, 12-15: a router group each) of the SAME uncut
    model. Each share's routed part, the shared expert counted once, adds up
    to the uncut layer's, in the program (both MoE paths, the picks counted)
    and in the reference."""
    from ai_agent_kubectl_tpu.models.transformer import _dense_mlp, _expert_mixer

    whole_cfg = dataclasses.replace(CFG, n_experts=16, router_width=0)
    whole = init_params(jax.random.PRNGKey(11), whole_cfg, dtype=jnp.float32)["layers"]
    x = jnp.asarray(np.random.default_rng(12).standard_normal((2, 24, CFG.dim)), jnp.float32)
    cut_of = lambda first: {k: (v[:, first:first + 4] if k in ("w_gate", "w_up", "w_down") else v)
                            for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        uncut, _ = _expert_mixer(whole_cfg, whole, 1, x, None, None, "dense")
        normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.rms_eps)
        shared = _dense_mlp(CFG, {k: v[1] for k, v in whole.items()
                                  if k.startswith("shared_")}, normed, "shared_")
        for moe_impl in ("auto", "dense"):
            parts, picks = [], np.zeros(2, np.int64)
            for first in (0, 4, 8, 12):
                cfg = dataclasses.replace(CFG, first_expert=first)
                y, n = _expert_mixer(cfg, cut_of(first), 1, x, None, None, moe_impl)
                parts.append(y - x - shared)
                picks += np.asarray(n.get("expert_picks", (0, 0)))
            np.testing.assert_allclose(np.asarray(sum(parts) + x + shared),
                                       np.asarray(uncut), atol=2e-5)
            # every token's 2 picks were made four times and landed once
            assert moe_impl == "dense" or picks.tolist() == [4 * 48 * 2, 48 * 2]
        total = 0.0
        for first in (0, 4, 8, 12):
            lw = {n: np.asarray(whole[n][1]) for n in ref.LEAVES["experts"]}
            lw.update({n: {"q": cut_of(first)[n][1], "scale": jnp.ones((4, 1, whole[n].shape[-1]))}
                       for n in ("w_gate", "w_up", "w_down")})
            y, _ = ref.experts(dict(SIZES, first_routed_expert=first), lw, normed[0])
            total = total + y - np.asarray(shared[0])
        np.testing.assert_allclose(np.asarray(total + shared[0] + x[0]), np.asarray(uncut[0]),
                                   atol=2e-5)


# ------------------------------------------------- the model and its reference

def through_the_pool(cfg, params, toks, windows, impl="dense", packed=False, cut=None):
    """Ragged windows then STEPS decode steps through ``forward`` over the block
    pool, as benchmark/refcheck.py builds it: K and V pools of ``cfg.n_layers``
    rows (they ride untouched), no latent and no state leaf given. ``cut`` (a
    window's index): the state leaves are taken out after that window, the
    live rows zeroed and put back, as a snapshot, an eviction and a restore do
    while the latent rows stay in their blocks."""
    B = toks.shape[0]
    W = max(max(w) for w in windows)
    pages = -(-(sum(max(w) for w in windows) + STEPS) // PAGE)
    pool = (cfg.n_layers, B * pages, PAGE, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)

    @jax.jit
    def step(params, tok, pos, cache, wmask, q_lens):
        extra = {}
        if packed and tok.shape[1] > 1:
            extra = dict(packed_rows=B * tok.shape[1], logits_at=jnp.maximum(q_lens - 1, 0))
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * PAGE,
                       attn_impl=impl, token_mask=wmask, write_mask=wmask,
                       block_tables=tables, q_lens=q_lens, **extra)

    done = np.zeros(B, np.int32)
    got = [[] for _ in range(B)]
    for i, q in enumerate(windows + [[1] * B] * STEPS):
        q = np.asarray(q, np.int32)
        w = W if q.max() > 1 else 1
        tok = np.zeros((B, w), np.int32)
        for b in range(B):
            tok[b, :q[b]] = toks[b, done[b]:done[b] + q[b]]
        pos = done[:, None] + np.arange(w)[None, :]
        logits, cache = step(params, jnp.asarray(tok), jnp.asarray(pos.astype(np.int32)),
                             cache, jnp.asarray(np.arange(w)[None, :] < q[:, None]),
                             jnp.asarray(q))
        if cut == i:
            saved = {n: np.asarray(getattr(cache, n)) for n in ("lin", "lconv")}
            cache = dataclasses.replace(
                cache, **{n: jnp.zeros_like(getattr(cache, n)) for n in saved})
            cache = dataclasses.replace(cache, **{n: jnp.asarray(a) for n, a in saved.items()})
        for b in range(B):
            got[b].append(np.asarray(logits[b, -1:] if packed and w > 1 else logits[b, :q[b]]))
        done += q
    return [np.concatenate(g) for g in got], cache


def rel_err(got, want):
    want = np.asarray(want)
    return float(np.abs(got - want).max(axis=1).max() / want.std())


TOKS = np.random.default_rng(5).integers(3, 500, size=(2, 300), dtype=np.int32)
#: rows cross the scan's 64-token chunks and 16-row blocks unevenly, one sits a
#: window out, one brings fewer tokens than the convolution's taps
WINDOWS = [[150, 3], [70, 0], [17, 130]]


def wanted(ref, params, sizes=SIZES):
    weights = ref.weights_from_program(params, CFG.n_layers, sizes)
    out = []
    for b in range(2):
        n = sum(w[b] for w in WINDOWS) + STEPS
        want, aux = ref.forward(sizes, weights, jnp.asarray(TOKS[b, :n]))
        assert set(aux) == {"clear_score", "steadiness", "position"}
        out.append(np.asarray(want))
    return out


@pytest.fixture(scope="module")
def program_logits(params):
    return through_the_pool(CFG, params, TOKS, WINDOWS)


def test_program_equals_the_reference_over_several_windows_and_decode(ref, params,
                                                                      program_logits):
    """Three ragged windows of unequal rows and decode steps through the latent
    pool and the carried state: every position's logits against the plain
    reference (token-by-token recurrence, EXPANDED latent attention). The
    caller's K and V pools come back untouched; the latent leaf has a plane a
    LATENT layer."""
    got, cache = program_logits
    H, dk, dv = CFG.lin_value_heads, CFG.lin_key_dim, CFG.lin_value_dim
    assert cache.lin.shape == (4, 2, dk, H * dv) and cache.lin.dtype == jnp.float32
    assert cache.lconv.shape == (4, 2, CFG.lin_conv - 1, CFG.lin_conv_dim)
    assert cache.lat.shape == (2, cache.k.shape[1], PAGE // 2, 2 * CFG.latent_row)
    assert not np.asarray(cache.k).any() and not np.asarray(cache.v).any()
    for got_b, want in zip(got, wanted(ref, params)):
        assert rel_err(got_b, want) < TOLERANCE_REL


def test_a_state_taken_out_and_put_back_continues_as_if_uninterrupted(params, program_logits):
    again, _ = through_the_pool(CFG, params, TOKS, WINDOWS, cut=1)
    for a, b in zip(again, program_logits[0]):
        np.testing.assert_array_equal(a, b)


#: a term of the layer equations -> (what to find in the reference's source,
#: what to put in its place)
LEFT_OUT = {
    "the decay a key channel": (
        "alpha = jnp.exp(g) ",
        "alpha = jnp.exp(jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)) "),
    "the lower bound": ('cfg["kda_lower_bound"] * jax.nn.sigmoid(', "-1.0 * jax.nn.sigmoid("),
    "beta": ("u = b_t[:, None] * (v_t - ", "u = (v_t - "),
    "the output gate": (" * jax.nn.sigmoid(z.reshape(T, H, dv))", ""),
    "the head gate": (' * jax.nn.sigmoid(n @ lw["wg"])[:, :, None]', ""),
    "the latent norm": ('rms_norm(cr[:, :C], lw["dkv_norm"], cfg["rms_norm_eps"])', "cr[:, :C]"),
    "the rotary embedding": ("q_rope = rope(cfg, q[..., N:], pos)", "q_rope = q[..., N:]"),
    "the group limit": ("    if G > 1:", "    if False:"),
    "the bias": ('choice = s + lw["router_bias"][None, :]', "choice = s"),
    "the 2.5": (' * cfg["routed_scaling_factor"]', ""),
    "the shared expert": ("return out + shared, margin", "return out, margin"),
}


@pytest.mark.parametrize("term", list(LEFT_OUT))
def test_the_tolerance_fails_a_reference_with_a_term_left_out(params, program_logits, term):
    """The comparison has power over every term of the layer equations: the
    reference's own source with ONE term taken out disagrees with the program by
    far more than the tolerance."""
    find, put = LEFT_OUT[term]
    source = (ROOT / REFERENCE).read_text()
    assert source.count(find) == 1, term
    crippled = types.ModuleType("crippled_reference")
    exec(compile(source.replace(find, put), f"<{term}>", "exec"), crippled.__dict__)
    worst = max(rel_err(g, w) for g, w in zip(program_logits[0], wanted(crippled, params)))
    assert worst > 10 * TOLERANCE_REL, (term, worst)


def test_packed_window_rows_and_the_ragged_kernels_match_the_reference(ref, params):
    """The chip's path: the window's valid rows packed, the latent kernel and
    the step kernel interpreted."""
    got, _ = through_the_pool(CFG, params, TOKS, WINDOWS, impl="ragged", packed=True)
    want = wanted(ref, params)
    ends = np.cumsum([[w[b] for w in WINDOWS] for b in range(2)], axis=1)
    for b in range(2):
        rows = [e - 1 for e, w in zip(ends[b], WINDOWS) if w[b]] + \
            list(range(ends[b][-1], ends[b][-1] + STEPS))
        live = [i for i, w in enumerate(WINDOWS) if w[b]] + [3, 4, 5]
        assert rel_err(got[b][live], want[b][rows]) < TOLERANCE_REL


def test_seeded_int8_weights_agree_with_the_reference(ref):
    """The benchmark's pair: random_params_int8's tree against the reference on
    its dequantised weights. W_f is an int8 projection at a quarter of the
    others' scale; a head's channels at rest remember from 2 tokens to over a
    thousand."""
    q = random_params_int8(jax.random.PRNGKey(11), CFG, dtype=jnp.float32, quantize_embed=True)
    layers = q["layers"]
    assert layers["lin_wf"].q.dtype == jnp.int8 and layers["wq"].q.dtype == jnp.int8
    assert layers["w_ukv"].dtype == jnp.float32 and layers["lin_wb"].dtype == jnp.float32
    assert float(layers["lin_wf"].scale.max()) == pytest.approx(
        0.25 * float(layers["lin_in"].scale.max()))
    assert layers["router_bias"].shape == (5, 16) and layers["lin_f_bias"].shape == (4, 4, 24)
    rest = np.exp(np.asarray(layers["lin_A_log"][0], np.float64))[:, None] \
        * np.asarray(layers["lin_f_bias"][0], np.float64)
    np.testing.assert_allclose(rest.min(axis=1), -9.0, rtol=1e-5)
    np.testing.assert_allclose(rest.max(axis=1), -2.0, rtol=1e-5)
    memory = 1.0 / (5.0 / (1.0 + np.exp(-rest)))            # tokens to 1/e at rest
    assert memory.min() < 2 and memory.max() > 1500
    got, _ = through_the_pool(CFG, q, TOKS, WINDOWS)
    for got_b, want in zip(got, wanted(ref, q)):
        assert rel_err(got_b, want) < TOLERANCE_REL


def test_the_configuration_says_what_it_keeps():
    assert CFG.layer_kinds == tuple("LDLE*ELELE*E")
    assert CFG.has_linear and CFG.latent and CFG.keeps_state
    assert (CFG.n_of("L"), CFG.n_of("*"), CFG.n_of("D"), CFG.n_of("E")) == (4, 2, 1, 5)
    assert CFG.state_bytes() == 4 * (4 * 24 * 160 + 2 * 3 * 352)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))))
    assert CFG.param_count() == n
    with pytest.raises(NotImplementedError, match="query LoRA"):
        mla = get_config("toy-mla-moe")
        uniform = dataclasses.replace(mla, q_lora_rank=0)
        forward(init_params(jax.random.PRNGKey(0), mla), uniform,
                jnp.zeros((1, 4), jnp.int32), jnp.arange(4, dtype=jnp.int32)[None],
                KVCache.zeros(uniform, 1, 16))


def test_param_count_and_state_bytes_equal_the_files_sizing():
    """The configuration file's sizes through benchmark/modelmap.py give the
    ModelConfig the server registers; its own count of parameters and of a
    sequence's state are the file's ``sizing``, and the parts are the
    issue's arithmetic."""
    from modelmap import key_map, model_config, sizes

    file = json.loads((ROOT / FILE).read_text())
    cfg = model_config(file["name"], sizes(file), key_map(file))
    assert cfg.layer_kinds == tuple("LDLDLELELE*E" + "LELELELELE*E")
    assert (cfg.n_of("L"), cfg.n_of("*"), cfg.n_of("D"), cfg.n_of("E")) == (10, 2, 2, 10)
    sizing = file["sizing"]
    assert cfg.param_count() == sizing["param_count"] == 9_215_490_112
    assert cfg.state_bytes() == sizing["state_bytes_per_sequence"] == 10 * (
        32 * 128 * 128 * 4 + 3 * 12288 * 2) == 21_708_800
    assert sizing["cache_bytes_per_token"] == 2 * cfg.latent_row * 2 == 2304
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))))
    assert n == cfg.param_count()
    whole = dataclasses.replace(cfg, n_layers=42, n_experts=512, router_width=0,
                                layer_pattern="LDLDLELELE*E" + "LELELELELE*E" * 6)
    assert 124.0e9 < whole.param_count() < 125.0e9
    assert file["reduced"].keys() == {"num_hidden_layers", "num_experts"}
    assert (cfg.n_group, cfg.topk_group, cfg.router, cfg.router_scale) == (
        8, 4, "sigmoid_bias", 2.5)
    assert (cfg.lin_channel_decay, cfg.lin_decay_floor, cfg.lin_out_gate) == (
        True, -5, "sigmoid")
    assert cfg.q_lora_rank == 0 and cfg.attn_gate == "head_wise" and cfg.latent_row == 576


# ------------------------------------------------------------------ the engine

def _mk(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    defaults = dict(dtype="float32", max_seq_len=320, prefill_buckets=(16, 64),
                    prefix_cache=False, batch_size=2, chunk_len=4, kv_pool_page=16,
                    state_snapshots=8, kv_pool_blocks=96, radix_lru_blocks=64)
    defaults.update(kw)
    return BatchedJaxEngine(CFG, tokenizer=ByteTokenizer(), **defaults)


LOG = "kubectl logs web-1: " + "GET /healthz 200 3ms; POST /orders 503 upstream; " * 3
ASKS = ["why do orders fail?  ", "which pod is it?  ", "since when?  "]


@pytest.fixture(scope="module")
def from_token_zero():
    """Every ask about the log answered by an engine with no radix tree: each
    prefilled from token 0."""
    eng = _mk(radix_cache=False)

    async def run():
        await eng.start()
        try:
            return {a: (await eng.generate(LOG + a, max_tokens=10, temperature=0.0)).text
                    for a in ASKS}
        finally:
            await eng.stop()

    return asyncio.run(run())


async def test_a_re_ask_seated_from_snapshot_and_shared_latent_rows(from_token_zero):
    """(Through the chip's ragged regime, interpreted.) The second and third
    asks about one log are seated from the snapshot at the log's last block
    edge (the KDA layers' state, through StateStore) AND the shared latent
    blocks of the same radix chain, and prefill only what follows; every
    answer equals the engine's that prefilled from token 0. /health carries
    both kinds' sections, and the counts are a hand count's."""
    eng = _mk(force_ragged=True)
    await eng.start()
    try:
        for a in ASKS:
            r = await eng.generate(LOG + a, max_tokens=10, temperature=0.0)
            assert r.text == from_token_zero[a], a
        health = eng.family_health()
        st, lin, lat, moe = (health[s] for s in ("ssm", "linear_attention",
                                                 "latent_attention", "moe"))
        assert st["restores"] >= 2 and st["prefix_tokens_usable"] > 0
        assert eng.kv_pool_health()["radix"]["hit_tokens"] >= 2 * (len(LOG) // 16) * 16
        assert st["state_bytes"] == CFG.state_bytes() == lin["state_bytes_per_sequence"]
        assert (lin["layers_linear"], lin["layers_full"], lat["layers"]) == (4, 2, 2)
        # every decode row through the 4 KDA layers and the 2 latent ones
        assert lin["decode_rows_linear"] == 4 * lat["decode_rows"] > 0
        assert lin["decode_rows_full"] == 0       # its * layers are latent: counted there
        assert lat["latent_rows_read"] > 2 * lat["decode_rows"] * len(LOG)
        assert lat["row_bytes"] == 2 * CFG.latent_row * 4       # float32 here
        assert lin["decode_rows_still"] > 0 and lin["decode_rows_still"] % 4 == 0
        assert lin["window_rows_linear"] > 0 and lin["chunks_scanned"] > 0
        # the router: 2 picks a live row a layer in the 5 expert layers; a
        # quarter of the experts are held
        assert moe["picks"] % (2 * 5) == 0 and 0 < moe["picks_held"] < moe["picks"]
        assert (moe["n_group"], moe["topk_group"], moe["experts_held"],
                moe["router_width"]) == (4, 2, 4, 16)
        eng._state.check()
    finally:
        await eng.stop()


async def test_the_union_of_both_kinds_obstacles_refuses_the_model():
    with pytest.raises(ValueError, match="keeps a linear-attention state.*dense per-slot"):
        await _mk(kv_pool=False).start()
    with pytest.raises(ValueError, match="keeps a linear-attention state.*SPEC_DECODE"):
        await _mk(spec_decode=True, spec_draft_model="toy-kda-mla-moe").start()
    with pytest.raises(ValueError, match="keeps a latent cache.*KV_QUANT=int8"):
        await _mk(kv_quant="int8").start()
