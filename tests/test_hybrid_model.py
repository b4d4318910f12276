"""One mixer a layer (ISSUE 33), on the CPU at toy size: Mamba-2 layers, two-
matrix relu^2 experts under a sigmoid router with a selection bias beside a
shared expert, and attention without a rotary embedding (``toy-hybrid-moe``,
seeded weights) against the benchmark's plain reference for
nemotron-3-nano-30b-a3b-l13, loaded by path as benchmark/refcheck.py loads it."""

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
from ai_agent_kubectl_tpu.ops.quant import random_params_int8
from ai_agent_kubectl_tpu.ops.ssd_scan import causal_conv, ssd_scan, ssd_step
from ai_agent_kubectl_tpu.parallel.moe import dense_moe, grouped_moe

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import refcheck  # noqa: E402

CFG = get_config("toy-hybrid-moe")
REFERENCE = "benchmark/configs/nemotron-3-nano-30b-a3b-l13.reference.py"
SIZES = {"num_attention_heads": CFG.n_heads, "num_key_value_heads": CFG.n_kv_heads,
         "head_dim": CFG.head_dim, "rms_norm_eps": CFG.rms_eps,
         "num_experts_per_tok": CFG.experts_per_token,
         "routed_scaling_factor": CFG.router_scale,
         "mamba_num_heads": CFG.ssm_heads, "mamba_head_dim": CFG.ssm_head_dim,
         "n_groups": CFG.ssm_groups, "ssm_state_size": CFG.ssm_state,
         "conv_kernel": CFG.ssm_conv}
PAGE, STEPS = 16, 3


@pytest.fixture(scope="module")
def ref():
    return refcheck.load_reference(REFERENCE)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), CFG, dtype=jnp.float32)


def recurrence(x, dt, A, Bm, Cm, D, h0):
    """The step-by-step recurrence in numpy float64, one row at a time."""
    B, S, H, P = x.shape
    G = Bm.shape[2]
    y = np.zeros((B, S, H, P))
    h = np.array(h0, np.float64)
    for b in range(B):
        for t in range(S):
            for hh in range(H):
                g = hh // (H // G)
                h[b, hh] = (np.exp(dt[b, t, hh] * A[hh]) * h[b, hh]
                            + dt[b, t, hh] * np.outer(x[b, t, hh], Bm[b, t, g]))
                y[b, t, hh] = h[b, hh] @ Cm[b, t, g] + D[hh] * x[b, t, hh]
    return y, h


def scan_inputs(seed, B, S, H=4, P=8, G=2, N=16):
    r = np.random.default_rng(seed)
    return dict(x=r.normal(size=(B, S, H, P)), dt=r.uniform(0.01, 0.5, (B, S, H)),
                A=-r.uniform(0.5, 4.0, H), Bm=r.normal(size=(B, S, G, N)),
                Cm=r.normal(size=(B, S, G, N)), D=r.normal(size=H),
                h0=r.normal(size=(B, H, P, N)))


@pytest.mark.parametrize("S,chunk", [(37, 8), (16, 16), (5, 64), (1, 8)])
def test_chunked_scan_equals_the_recurrence_from_a_state_with_padding(S, chunk):
    """ssd_scan from an INITIAL state, rows padded past unequal q_lens (dt 0):
    outputs equal the recurrence's at every real token and the state returned
    is the state at each row's q_len, padding having moved nothing."""
    a = scan_inputs(S, 3, S)
    q_lens = np.array([S, max(1, S // 2), 0])
    a["dt"] = a["dt"] * (np.arange(S)[None, :, None] < q_lens[:, None, None])
    f = {k: jnp.asarray(v, jnp.float32) for k, v in a.items()}
    y, h = ssd_scan(f["x"], f["dt"], f["A"], f["Bm"], f["Cm"], f["D"], f["h0"], chunk)
    for b, n in enumerate(q_lens):
        want_y, want_h = recurrence(a["x"][b:b + 1, :n], a["dt"][b:b + 1, :n], a["A"],
                                    a["Bm"][b:b + 1, :n], a["Cm"][b:b + 1, :n],
                                    a["D"], a["h0"][b:b + 1])
        np.testing.assert_allclose(np.asarray(y)[b, :n], want_y[0], rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(h)[b], want_h[0], rtol=2e-4, atol=2e-4)
    assert h.dtype == jnp.float32


def test_single_step_update_equals_a_window_of_one():
    a = {k: jnp.asarray(v, jnp.float32) for k, v in scan_inputs(9, 2, 1).items()}
    y1, h1 = ssd_step(a["x"], a["dt"], a["A"], a["Bm"], a["Cm"], a["D"], a["h0"])
    want_y, want_h = recurrence(*(np.asarray(a[k], np.float64) for k in
                                  ("x", "dt", "A", "Bm", "Cm", "D", "h0")))
    np.testing.assert_allclose(np.asarray(y1), want_y, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), want_h, rtol=2e-4, atol=2e-4)


def test_convolution_carries_its_tail_across_windows():
    """A sequence cut into windows of unequal length convolves as one piece, and
    a row of q_len 0 keeps its tail."""
    r = np.random.default_rng(1)
    K, C, T = 4, 6, 11
    x = jnp.asarray(r.normal(size=(1, T, C)), jnp.float32)
    w, b = jnp.asarray(r.normal(size=(K, C)), jnp.float32), jnp.asarray(r.normal(size=C), jnp.float32)
    zero = jnp.zeros((1, K - 1, C), jnp.float32)
    whole, tail = causal_conv(x, zero, w, b, jnp.array([T]))
    y1, t1 = causal_conv(jnp.pad(x[:, :2], ((0, 0), (0, 3), (0, 0))), zero, w, b, jnp.array([2]))
    y2, t2 = causal_conv(x[:, 2:], t1, w, b, jnp.array([T - 2]))
    np.testing.assert_allclose(np.concatenate([y1[:, :2], y2], 1), whole, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(t2, tail, rtol=0, atol=0)
    _, kept = causal_conv(x[:, :4], t1, w, b, jnp.array([0]))
    np.testing.assert_allclose(kept, t1, rtol=0, atol=0)


def through_the_pool(cfg, params, toks, windows, impl="dense", moe_impl="auto"):
    """Every position's logits through the block pool: ``windows`` is a list of
    per-row q_lens, one ragged window each (rows of unequal length, each window
    continuing from the state and the K/V the one before left), then STEPS
    single-token steps. The pool is built as refcheck.py builds it: K and V
    alone with ``cfg.n_layers`` rows, no state leaf."""
    B = toks.shape[0]
    W = max(max(w) for w in windows)
    pages = -(-(sum(max(w) for w in windows) + STEPS) // PAGE)
    pool = (cfg.n_layers, B * pages, PAGE, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)

    @jax.jit
    def step(params, tok, pos, cache, wmask, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * PAGE,
                       attn_impl=impl, token_mask=wmask, write_mask=wmask,
                       block_tables=tables, q_lens=q_lens, moe_impl=moe_impl)

    done = np.zeros(B, np.int32)
    got = [[] for _ in range(B)]
    for q in windows + [[1] * B] * STEPS:
        q = np.asarray(q, np.int32)
        w = W if q.max() > 1 else 1
        tok = np.zeros((B, w), np.int32)
        for b in range(B):
            tok[b, :q[b]] = toks[b, done[b]:done[b] + q[b]]
        pos = done[:, None] + np.arange(w)[None, :]
        logits, cache = step(params, jnp.asarray(tok), jnp.asarray(pos.astype(np.int32)),
                             cache, jnp.asarray(np.arange(w)[None, :] < q[:, None]),
                             jnp.asarray(q))
        for b in range(B):
            got[b].append(np.asarray(logits[b, :q[b]]))
        done += q
    return [np.concatenate(g) for g in got], cache


@pytest.mark.parametrize("moe_impl", ["auto", "dense"])
def test_program_equals_the_reference_over_several_windows_and_decode(ref, params, moe_impl):
    """Three ragged windows of unequal rows (the second and third start from a
    carried state, one row sits a window out) and decode steps: every position's
    logits against the plain reference's token-by-token recurrence."""
    toks = np.random.default_rng(5).integers(3, 500, size=(2, 80), dtype=np.int32)
    windows = [[20, 9], [13, 0], [7, 22]]
    got, cache = through_the_pool(CFG, params, toks, windows, moe_impl=moe_impl)
    assert cache.ssm.shape == (2, 2, CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state)
    assert cache.ssm.dtype == jnp.float32
    weights = ref.weights_from_program(params, CFG.n_layers)
    for b in range(2):
        n = sum(w[b] for w in windows) + STEPS
        want, aux = ref.forward(SIZES, weights, jnp.asarray(toks[b, :n]))
        np.testing.assert_allclose(got[b], np.asarray(want), rtol=2e-3, atol=2e-3)
        assert aux["clear_score"].shape == (n,)


def test_seeded_int8_weights_agree_with_the_reference(ref):
    """The benchmark's pair: random_params_int8's tree (bf16 activations) against
    the reference on its dequantised weights, as refcheck.run compares them."""
    cfg = CFG
    q = random_params_int8(jax.random.PRNGKey(11), cfg, dtype=jnp.bfloat16,
                           quantize_embed=True)
    toks = np.random.default_rng(2).integers(3, 500, size=(2, 60), dtype=np.int32)
    got, _ = through_the_pool(cfg, q, toks, [[30, 17], [10, 12]])
    weights = ref.weights_from_program(q, cfg.n_layers)
    for b, n in enumerate((43, 32)):
        want, _ = ref.forward(SIZES, weights, jnp.asarray(toks[b, :n]))
        err = np.abs(got[b] - np.asarray(want)).max(axis=1) / float(np.asarray(want).std())
        assert np.median(err) < 0.08, np.median(err)


def expert_layer(params, j=0):
    from ai_agent_kubectl_tpu.models.transformer import EXPERT_LAYER_LEAVES, _at
    return {k: _at(params["layers"][k], j) for k in EXPERT_LAYER_LEAVES
            if k in params["layers"]}


@pytest.mark.parametrize("path", ["dense", "grouped"])
def test_two_matrix_experts_sigmoid_router_and_shared_expert(ref, params, path):
    """relu(x Wu)^2 Wd experts of width 72 (not a multiple of 128), top-2 of
    sigmoid scores + bias renormalised and scaled, plus the shared expert:
    each path against the reference's loop over the experts."""
    assert CFG.mlp_hidden % 128 and not CFG.gated_mlp
    lp = expert_layer(params)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 9, CFG.dim)), jnp.float32)
    mask = jnp.ones((2, 9), jnp.float32).at[1, 6:].set(0)
    if path == "dense":
        y = dense_moe(CFG, lp, x)
    else:
        y, n_read = grouped_moe(CFG, lp, x, mask)
        assert 1 <= int(n_read) <= CFG.n_experts
    from ai_agent_kubectl_tpu.models.transformer import _dense_mlp
    y = y + _dense_mlp(CFG, lp, x, "shared_")
    lw = {k: np.asarray(v) for k, v in lp.items() if k.startswith(("router", "shared"))}
    for name in ("w_up", "w_down"):
        lw[name] = {"q": lp[name], "scale": jnp.ones((CFG.n_experts, 1, lp[name].shape[2]))}
    with jax.default_matmul_precision("highest"):
        want, margin = ref.experts(SIZES, lw, x.reshape(18, CFG.dim))
    live = np.asarray(mask).reshape(18) > 0
    np.testing.assert_allclose(np.asarray(y).reshape(18, -1)[live], np.asarray(want)[live],
                               rtol=2e-4, atol=2e-4)
    assert margin.shape == (18,)


@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("F,masked", [(232, 0), (464, 5), (384, 5), (464, 18)],
                         ids=["29x8", "29x16-rows-masked", "three-lane-tiles",
                              "every-row-masked"])
def test_two_matrix_experts_of_other_widths_against_the_reference(ref, F, masked, quant):
    """ISSUE 34: relu^2 experts of inner widths 29 x 8 and 29 x 16 (the up block
    handed over as [F, D], as Nemotron's 29 x 64 is) and 3 x 128 (as [D, F])
    through the grouped kernel under the sigmoid router with its selection bias:
    against the reference's loop over whole experts, live rows one by one; masked
    rows read no expert and a pass with none live returns zeros."""
    from ai_agent_kubectl_tpu.ops.quant import QuantInt8, quantize_int8
    cfg = dataclasses.replace(CFG, name="made-up-relu2", mlp_hidden=F)
    D, E = cfg.dim, cfg.n_experts
    r = np.random.default_rng(F)
    up = jnp.asarray(r.normal(size=(E, D, F)) * D ** -0.5, jnp.float32)
    down = jnp.asarray(r.normal(size=(E, F, D)) * F ** -0.5, jnp.float32)
    lp = {"router": jnp.asarray(r.normal(size=(D, E)), jnp.float32),
          "router_bias": jnp.asarray(r.normal(size=E) * 0.1, jnp.float32),
          "w_up": quantize_int8(up) if quant else up,
          "w_down": quantize_int8(down) if quant else down}
    x = jnp.asarray(r.normal(size=(2, 9, D)), jnp.float32)
    mask = jnp.ones((18,), jnp.float32).at[18 - masked:].set(0).reshape(2, 9)
    y, n_read = jax.jit(lambda lp, x, m: grouped_moe(cfg, lp, x, m))(lp, x, mask)
    y = np.asarray(y).reshape(18, D)
    live = np.asarray(mask).reshape(18) > 0
    assert np.abs(y[~live]).max(initial=0) == 0
    if masked == 18:
        assert int(n_read) == 0
        return
    lw = {"router": lp["router"], "router_bias": lp["router_bias"],
          "shared_up": jnp.zeros((D, 8)), "shared_down": jnp.zeros((8, D))}
    for name in ("w_up", "w_down"):
        w = lp[name]
        lw[name] = ({"q": w.q, "scale": w.scale} if isinstance(w, QuantInt8) else
                    {"q": w, "scale": jnp.ones((E, 1, w.shape[2]))})
    with jax.default_matmul_precision("highest"):
        want, _ = ref.experts(SIZES, lw, x.reshape(18, D))
    np.testing.assert_allclose(y[live], np.asarray(want)[live], rtol=2e-4, atol=2e-4)
    assert 1 <= int(n_read) <= E


def test_selection_bias_picks_but_does_not_weigh(params):
    """A large bias on one expert makes every token pick it; its weight is still
    its own sigmoid score over the picked scores' sum."""
    from ai_agent_kubectl_tpu.parallel.moe import router_logits, top_k_routing
    lp = expert_layer(params)
    x = jnp.asarray(np.random.default_rng(8).normal(size=(5, CFG.dim)), jnp.float32)
    logits = router_logits(CFG, lp, x)
    bias = jnp.zeros((CFG.n_experts,)).at[3].set(10.0)
    w, idx = top_k_routing(CFG, logits, bias)
    assert (np.asarray(idx) == 3).any(axis=1).all()
    s = np.asarray(jax.nn.sigmoid(logits))
    picked = np.take_along_axis(s, np.asarray(idx), axis=1)
    np.testing.assert_allclose(np.asarray(w), picked / picked.sum(1, keepdims=True) * 2.5,
                               rtol=1e-5)


def test_no_rotary_embedding_and_spare_pool_rows(params):
    """Attention takes no positions: shifting every position of a from-scratch
    window changes no logit; and the K/V pool is addressed by the attention
    layer's ordinal, so rows beyond the two attention layers stay zero."""
    toks = np.random.default_rng(4).integers(3, 500, size=(1, 12), dtype=np.int32)
    got, cache = through_the_pool(CFG, params, toks, [[9]])
    assert float(jnp.abs(cache.k[:CFG.n_of("*")]).max()) > 0
    assert float(jnp.abs(cache.k[CFG.n_of("*"):]).max()) == 0
    dense = KVCache.zeros(CFG, 1, 64, dtype=jnp.float32)
    assert dense.k.shape[0] == CFG.n_of("*")
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    a, _ = forward(params, CFG, jnp.asarray(toks), pos, dense)
    np.testing.assert_allclose(np.asarray(a)[0], got[0], rtol=2e-3, atol=2e-3)


def test_a_mesh_is_refused_with_its_message(params):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1] * 2).reshape(2), ("model",)) \
        if len(jax.devices()) < 2 else Mesh(np.array(jax.devices()[:2]), ("model",))
    toks = jnp.zeros((1, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match="one mixer a layer"):
        forward(params, CFG, toks, jnp.arange(4, dtype=jnp.int32)[None],
                KVCache.zeros(CFG, 1, 16, dtype=jnp.float32), mesh=mesh)


def test_pattern_longer_than_depth_means_its_first_layers():
    cut = dataclasses.replace(CFG, n_layers=4, layer_pattern="ME*ME*ME*")
    assert cut.layer_kinds == ("M", "E", "*", "M")
    assert (cut.n_of("M"), cut.n_of("E"), cut.n_of("*")) == (2, 1, 1)
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, n_layers=7).layer_kinds
    assert CFG.state_bytes() == 2 * (4 * 8 * 16 * 32 + 2 * 3 * 256)
    assert get_config("toy-8m").layer_kinds == () and not get_config("toy-8m").keeps_state


def test_a_bf16_state_drifts_where_a_float32_state_does_not(monkeypatch):
    """Why the recurrent state is float32 (ops/ssd_scan.py::STATE_DTYPE), shown
    where the benchmark's comparison cannot show it (900-token prompts and 3
    decode steps round a state too rarely to rise above bf16 activations): a
    slow head (decay 0.999 a step) decoded 1,500 steps, its state rounded at
    every one, ends several times further from the float64 recurrence in bf16."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    r = np.random.default_rng(0)
    T, H, P, N = 1500, 1, 4, 8
    a = dict(x=r.normal(size=(1, T, H, P)), dt=np.full((1, T, H), 1e-3), A=-np.ones(H),
             Bm=r.normal(size=(1, T, 1, N)), Cm=r.normal(size=(1, T, 1, N)), D=np.zeros(H),
             h0=r.normal(size=(1, H, P, N)))
    want_y, _ = recurrence(**a)

    def decoded(dtype):
        monkeypatch.setattr(S, "STATE_DTYPE", dtype)
        f = {k: jnp.asarray(v, jnp.float32) for k, v in a.items()}

        def step(h, t):
            y, h = S.ssd_step(f["x"][:, t][:, None], f["dt"][:, t][:, None], f["A"],
                              f["Bm"][:, t][:, None], f["Cm"][:, t][:, None], f["D"], h)
            return h, y[:, 0]

        _, ys = jax.lax.scan(step, f["h0"].astype(dtype), jnp.arange(T))
        return np.abs(np.asarray(ys)[-200:, 0] - want_y[0, -200:]).mean()

    err32, err16 = decoded(jnp.float32), decoded(jnp.bfloat16)
    assert err16 > 20 * err32, (err16, err32)


def test_a_large_seeded_leaf_filled_in_place_holds_the_same_values(monkeypatch):
    """ops/quant.py fills a leaf of 2 GiB or more slice by slice into one buffer
    (three times the leaf at once otherwise: 16.68 GB of a 16.9 GB chip at the
    benchmark's cut); the values are the stacked form's, key for key."""
    from ai_agent_kubectl_tpu.ops import quant

    stacked = random_params_int8(jax.random.PRNGKey(4), CFG, dtype=jnp.bfloat16)
    monkeypatch.setattr(quant, "_IN_PLACE_BYTES", 1)
    in_place = random_params_int8(jax.random.PRNGKey(4), CFG, dtype=jnp.bfloat16)
    for a, b in zip(jax.tree_util.tree_leaves(stacked), jax.tree_util.tree_leaves(in_place)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a), np.asarray(b))


# ------ linear attention's decode step as a kernel (ISSUE 46; the model is
# ------ tests/test_linear_attention.py's, ``toy-linear-hybrid``)

def _plane_step(q, k, v, g, beta, state, layer, moves=None):
    """``gated_delta_step_kernel``'s contract by the plain ``jnp`` step."""
    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    o, S = GD.gated_delta_step(q, k, v, g, beta,
                               jax.lax.dynamic_index_in_dim(state, layer, 0, False))
    return o, jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)


def test_decode_logits_through_the_step_kernel_equal_the_jnp_steps(monkeypatch):
    """``forward`` at S == 1 takes ops/gated_delta.py's kernel (interpreted here)
    on the whole state leaf inside the scan over periods: after a 37-token window
    (the chunked scan, untouched), 12 decode steps' logits and the state they
    leave equal those of the ``jnp`` step from and to a sliced plane, one row of
    the two dead from the fifth step on (its state stays as it was)."""
    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    cfg = get_config("toy-linear-hybrid")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    toks = np.random.default_rng(9).integers(3, 500, size=(2, 64), dtype=np.int32)

    def decoded(step_fn):
        monkeypatch.setattr(GD, "gated_delta_step_kernel", step_fn)
        run = jax.jit(lambda tok, pos, cache, mask: forward(
            params, cfg, tok, pos, cache, token_mask=mask, write_mask=mask))
        cache = KVCache.zeros(cfg, 2, 64, dtype=jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(37, dtype=jnp.int32), (2, 37))
        _, cache = run(jnp.asarray(toks[:, :37]), pos, cache, jnp.ones((2, 37), bool))
        out = []
        for t in range(37, 49):
            live = jnp.asarray([[True], [t < 41]])
            logits, cache = run(jnp.asarray(toks[:, t:t + 1]),
                                jnp.full((2, 1), t, jnp.int32), cache, live)
            out.append(np.asarray(logits[:, 0]))
            if t == 40:
                at_its_death = np.asarray(cache.lin[:, 1])
        np.testing.assert_array_equal(np.asarray(cache.lin[:, 1]), at_its_death)
        return np.stack(out), np.asarray(cache.lin)

    kernel = GD.gated_delta_step_kernel
    want, want_state = decoded(_plane_step)
    got, state = decoded(kernel)
    assert state.dtype == np.float32 and state.shape == (6, 2, 24, 4 * 40)
    np.testing.assert_allclose(got[:, 0], want[:, 0], rtol=2e-4, atol=2e-4 * want.std())
    np.testing.assert_allclose(got[:4, 1], want[:4, 1], rtol=2e-4, atol=2e-4 * want.std())
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-5)


def test_the_step_kernels_float32_state_drifts_no_more_than_the_jnp_steps():
    """The slow head of tests/test_linear_attention.py's drift test (decay 0.999
    a step, 1,500 decode steps) through the kernel: as near the float64
    recurrence as the ``jnp`` step's float32 state ends."""
    from ai_agent_kubectl_tpu.ops import gated_delta as GD

    T, dk, dv = 1500, 8, 8
    r = np.random.default_rng(0)
    a = dict(q=GD.l2_normalize(r.normal(size=(1, T, 1, dk)), dk ** -0.5),
             k=GD.l2_normalize(r.normal(size=(1, T, 1, dk))),
             v=jnp.asarray(r.normal(size=(1, T, 1, dv)), jnp.float32),
             g=jnp.full((1, T, 1), -1e-3, jnp.float32),
             beta=jnp.full((1, T, 1), 0.5, jnp.float32))
    S0 = r.normal(size=(1, dk, dv))
    f = {n: np.asarray(x, np.float64) for n, x in a.items()}
    S, want = S0[0].copy(), np.zeros((T, dv))
    for t in range(T):
        k, al = f["k"][0, t, 0], np.exp(f["g"][0, t, 0])
        u = f["beta"][0, t, 0] * (f["v"][0, t, 0] - al * S.T @ k)
        S = al * S + np.outer(k, u)
        want[t] = S.T @ f["q"][0, t, 0]

    def decoded(step_fn):
        def step(leaf, t):
            o, leaf = step_fn(*(jax.lax.dynamic_slice_in_dim(a[n], t, 1, 1) for n in a),
                              leaf, jnp.asarray(0, jnp.int32))
            return leaf, o[0, 0, 0]

        _, os = jax.lax.scan(step, jnp.asarray(S0, jnp.float32)[None], jnp.arange(T))
        return np.abs(np.asarray(os)[-200:] - want[-200:]).mean()

    err_kernel, err_jnp = decoded(GD.gated_delta_step_kernel), decoded(_plane_step)
    assert err_kernel < 1.5 * err_jnp + 1e-7, (err_kernel, err_jnp)


# ------ Mamba-2's decode step as a kernel (ISSUE 49; ops/ssd_scan.py::
# ------ ssd_step_kernel, interpreted here, against ``ssd_step``)

def _plane_ssd_step(x, dt, A, Bm, Cm, D, state, layer, moves=None):
    """``ssd_step_kernel``'s contract by the plain ``jnp`` step, from and to a
    sliced plane (what ``_ssm_mixer`` ran at S == 1 before the kernel)."""
    y, h = ssd_step(x, dt, A, Bm, Cm, D,
                    jax.lax.dynamic_index_in_dim(state, layer, 0, False))
    return y, jax.lax.dynamic_update_index_in_dim(state, h, layer, 0)


def step_inputs(seed, rows, H, P, G, N, planes=3):
    """One token a row (``scan_inputs``, float32) and a leaf of ``planes``
    planes."""
    a = {k: jnp.asarray(v, jnp.float32)
         for k, v in scan_inputs(seed, rows, 1, H=H, P=P, G=G, N=N).items()}
    h0 = a.pop("h0")
    return a, jnp.stack([h0 * (j + 1) for j in range(planes)])


@pytest.mark.parametrize("H,P,G,N,block_heads", [
    (64, 64, 8, 128, 0), (64, 64, 8, 128, 8), (64, 64, 8, 128, 64), (4, 8, 2, 16, 0),
    (4, 8, 2, 16, 2)],
    ids=["published", "published-8-heads", "published-64-heads", "small", "small-2-heads"])
def test_the_ssd_step_kernel_equals_the_jnp_step_on_live_padded_and_dead_rows(H, P, G, N,
                                                                              block_heads):
    """On plane 1 of a three-plane leaf against ``ssd_step`` on that plane, three
    rows: row 0 live, row 1 padded (a real token's x, B and C with dt = 0), row 2 a
    dead slot (zeros). The live row's output and state equal to float32 rounding
    (the kernel sums a head's 128 products along the lanes, the einsum in another
    order), a padded and a dead row's state bit for bit its input (the kernel
    neither reads nor writes it) and their outputs zeros, the other planes
    untouched, the leaf float32, the output in x's dtype."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    assert S._block_heads(64, 8, 64 * 128 * 4) == 32 and S._block_heads(4, 2, 8 * 16 * 4) == 4
    a, leaf = step_inputs(H, 3, H, P, G, N)
    a["dt"] = a["dt"].at[1:].set(0.0)
    for n in ("x", "Bm", "Cm"):
        a[n] = a[n].at[2].set(0.0)
    want_y, want_h = ssd_step(*a.values(), leaf[1])
    y, out = jax.jit(S.ssd_step_kernel, static_argnums=9)(
        *a.values(), leaf, jnp.asarray(1, jnp.int32), None, block_heads)
    np.testing.assert_allclose(np.asarray(y)[0], np.asarray(want_y)[0], rtol=1e-5, atol=1e-5)
    assert not np.asarray(y)[1:].any()          # a row that does not move reads nothing
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want_h), rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(out[1, 0]) - np.asarray(leaf[1, 0])).max() > 0.1
    np.testing.assert_array_equal(np.asarray(out[1, 1:]), np.asarray(leaf[1, 1:]))
    np.testing.assert_array_equal(np.asarray(out[::2]), np.asarray(leaf[::2]))
    assert out.dtype == jnp.float32 and out.shape == leaf.shape
    y16, _ = S.ssd_step_kernel(a["x"].astype(jnp.bfloat16), *list(a.values())[1:], leaf, 1)
    assert y.dtype == jnp.float32 and y.shape == (3, 1, H, P) and y16.dtype == jnp.bfloat16


@pytest.mark.parametrize("moves", ["0000", "0010", "1111", "1000", "0001", "0101"],
                         ids=["none", "one", "all", "first", "last", "alternating"])
@pytest.mark.parametrize("given", [False, True], ids=["from-dt", "moves-given"])
def test_the_ssd_step_kernel_visits_only_the_rows_that_move(moves, given):
    """Whichever rows move (read off ``dt != 0``, or told by ``moves``): their
    outputs and state equal the ``jnp`` step's, every other row's state is bit for
    bit its input and its output zeros: the kernel takes the moving rows in its
    grid's first steps and gives the others no block of their own."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    live = np.asarray([c == "1" for c in moves])
    a, leaf = step_inputs(3, 4, 4, 8, 2, 16, planes=2)
    a["dt"] = a["dt"] * live[:, None, None]
    want_y, want_h = ssd_step(*a.values(), leaf[1])
    y, out = jax.jit(S.ssd_step_kernel, static_argnums=9)(
        *a.values(), leaf, jnp.asarray(1, jnp.int32), jnp.asarray(live) if given else None, 2)
    np.testing.assert_allclose(np.asarray(y)[live], np.asarray(want_y)[live],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1])[live], np.asarray(want_h)[live],
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(y)[~live].any()
    np.testing.assert_array_equal(np.asarray(out[1])[~live], np.asarray(leaf[1])[~live])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))


def test_the_ssd_step_kernel_over_every_plane_in_turn_equals_the_recurrence():
    """As ``_patterned_layers`` runs it, and as a scan over periods would: the
    whole leaf carried, 24 tokens a plane, the plane a Python int (Nemotron's
    unrolled layers) or a traced index; every plane ends where the float64
    recurrence from its own initial state does, and a call on one plane leaves the
    others bit for bit."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    T, planes = 24, 3
    raw = scan_inputs(11, 2, T)
    a = {k: jnp.asarray(v, jnp.float32) for k, v in raw.items()}
    h0 = a.pop("h0")
    leaf = jnp.stack([h0 * (j + 1) for j in range(planes)])
    token = lambda t: [jax.lax.dynamic_slice_in_dim(a[n], t, 1, 1) if a[n].ndim > 1
                       else a[n] for n in ("x", "dt", "A", "Bm", "Cm", "D")]

    @jax.jit
    def traced(leaf):
        def step(leaf, t):
            def layer(leaf, j):
                y, leaf = S.ssd_step_kernel(*token(t), leaf, j)
                return leaf, y[:, 0]
            return jax.lax.scan(layer, leaf, jnp.arange(planes, dtype=jnp.int32))
        return jax.lax.scan(step, leaf, jnp.arange(T))

    @jax.jit
    def unrolled(leaf):
        def step(leaf, t):
            ys = []
            for j in range(planes):
                y, leaf = S.ssd_step_kernel(*token(t), leaf, j)
                ys.append(y[:, 0])
            return leaf, jnp.stack(ys)
        return jax.lax.scan(step, leaf, jnp.arange(T))

    out, y = traced(leaf)                                   # y [T, planes, B, H, P]
    out_u, y_u = unrolled(leaf)
    # (to rounding: the CPU's compiler contracts the small programs around the
    # call to fused multiply-adds in the one and not in the other)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_u), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_u), rtol=1e-4, atol=1e-5)
    for j in range(planes):
        want_y, want_h = recurrence(**{**raw, "h0": raw["h0"] * (j + 1)})
        np.testing.assert_allclose(np.asarray(y)[:, j].swapaxes(0, 1), want_y,
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(out[j]), want_h, rtol=2e-4, atol=2e-4)
        _, one = S.ssd_step_kernel(*token(0), leaf, j)
        others = [i for i in range(planes) if i != j]
        np.testing.assert_array_equal(np.asarray(one)[others], np.asarray(leaf)[others])
        assert np.abs(np.asarray(one[j]) - np.asarray(leaf[j])).max() > 1e-3


def test_decode_logits_through_the_ssd_step_kernel_equal_the_jnp_steps(params, monkeypatch):
    """``forward`` at S == 1 takes ops/ssd_scan.py's kernel (interpreted here) on
    the whole ``ssm`` leaf: after a 37-token window (the window kernel, the same
    on both sides), 12 decode steps' logits and the state they leave
    equal those of ``ssd_step`` patched in from and to a sliced plane, one row of
    the two dead from the fifth step on: its state stays bit for bit as it was,
    which ``ssd_step`` shows too, and its logits, which nothing samples, are
    the only ones the two differ in (the kernel reads a dead row's state not at
    all, so its mixer output is zeros and not ``C . h + D x``)."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    toks = np.random.default_rng(9).integers(3, 500, size=(2, 64), dtype=np.int32)

    def decoded(step_fn):
        monkeypatch.setattr(S, "ssd_step_kernel", step_fn)
        run = jax.jit(lambda tok, pos, cache, mask: forward(
            params, CFG, tok, pos, cache, token_mask=mask, write_mask=mask))
        cache = KVCache.zeros(CFG, 2, 64, dtype=jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(37, dtype=jnp.int32), (2, 37))
        _, cache = run(jnp.asarray(toks[:, :37]), pos, cache, jnp.ones((2, 37), bool))
        out = []
        for t in range(37, 49):
            live = jnp.asarray([[True], [t < 41]])
            logits, cache = run(jnp.asarray(toks[:, t:t + 1]),
                                jnp.full((2, 1), t, jnp.int32), cache, live)
            out.append(np.asarray(logits[:, 0]))
            if t == 40:
                at_its_death = np.asarray(cache.ssm[:, 1])
        np.testing.assert_array_equal(np.asarray(cache.ssm[:, 1]), at_its_death)
        return np.stack(out), np.asarray(cache.ssm)

    kernel = S.ssd_step_kernel
    want, want_state = decoded(_plane_ssd_step)
    got, state = decoded(kernel)
    assert state.dtype == np.float32 and state.shape == (CFG.n_of("M"), 2, 8, 16, 32)
    np.testing.assert_allclose(got[:, 0], want[:, 0], rtol=2e-4, atol=2e-4 * want.std())
    np.testing.assert_allclose(got[:4, 1], want[:4, 1], rtol=2e-4, atol=2e-4 * want.std())
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-5)


def test_the_ssd_step_kernels_float32_state_drifts_no_more_than_the_jnp_steps():
    """The slow head of the drift test above (decay 0.999 a step, 1,500 decode
    steps) through the kernel: as near the float64 recurrence as ``ssd_step``'s
    float32 state ends."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    r = np.random.default_rng(0)
    T, H, P, N = 1500, 1, 4, 8
    a = dict(x=r.normal(size=(1, T, H, P)), dt=np.full((1, T, H), 1e-3), A=-np.ones(H),
             Bm=r.normal(size=(1, T, 1, N)), Cm=r.normal(size=(1, T, 1, N)), D=np.zeros(H),
             h0=r.normal(size=(1, H, P, N)))
    want_y, _ = recurrence(**a)
    f = {k: jnp.asarray(v, jnp.float32) for k, v in a.items()}

    def decoded(step_fn):
        def step(leaf, t):
            y, leaf = step_fn(f["x"][:, t][:, None], f["dt"][:, t][:, None], f["A"],
                              f["Bm"][:, t][:, None], f["Cm"][:, t][:, None], f["D"],
                              leaf, jnp.asarray(0, jnp.int32))
            return leaf, y[:, 0]

        _, ys = jax.lax.scan(step, f["h0"][None], jnp.arange(T))
        return np.abs(np.asarray(ys)[-200:, 0] - want_y[0, -200:]).mean()

    err_kernel, err_jnp = decoded(S.ssd_step_kernel), decoded(_plane_ssd_step)
    assert err_kernel < 1.5 * err_jnp + 1e-7, (err_kernel, err_jnp)


# ------ a Mamba-2 window as a kernel (ISSUE 54; ops/ssd_scan.py::ssd_window,
# ------ interpreted here, against ``ssd_scan`` from and to a sliced plane)

#: id -> (S, chunk, H, P, G, N, q_lens): one group and several; a window that is
#: no multiple of its chunk; rows that end inside a chunk, on its edge and before
#: the window's last chunks; a row that brought nothing; one chunk for the window
WINDOWS = {
    "several-groups-ragged": (37, 8, 4, 8, 2, 16, [37, 18, 0, 3]),
    "one-group-ragged": (37, 8, 4, 8, 1, 16, [5, 37, 16, 0]),
    "a-group-a-head": (24, 8, 4, 8, 4, 16, [24, 0, 9, 8]),
    "one-chunk": (16, 16, 4, 8, 2, 16, [16, 1, 0, 7]),
    "window-under-its-chunk": (5, 64, 4, 8, 2, 16, [5, 0, 2, 4]),
    "sixteen-heads-two-tiles": (40, 16, 16, 8, 2, 16, [40, 0, 17, 32]),
    "all-rows-dead": (24, 8, 4, 8, 2, 16, [0, 0, 0, 0]),
}


def window_inputs(name, planes=3):
    """``scan_inputs`` in float32 with ``dt`` zeroed past each row's q_len (a
    dead row: zeros all over), and a leaf of ``planes`` non-zero planes."""
    S_, chunk, H, P, G, N, q = WINDOWS[name]
    q = np.asarray(q)
    raw = scan_inputs(len(name), len(q), S_, H=H, P=P, G=G, N=N)
    raw["dt"] = raw["dt"] * (np.arange(S_)[None, :, None] < q[:, None, None])
    for n in ("x", "Bm", "Cm"):
        raw[n] = raw[n] * (q > 0).reshape((-1,) + (1,) * (raw[n].ndim - 1))
    a = {k: jnp.asarray(v, jnp.float32) for k, v in raw.items()}
    h0 = a.pop("h0")
    return a, jnp.stack([h0 * (j + 1) for j in range(planes)]), q, chunk


@pytest.mark.parametrize("given", [False, True], ids=["from-dt", "q-lens-given"])
@pytest.mark.parametrize("name", list(WINDOWS))
def test_the_ssd_window_kernel_equals_the_scan_from_a_state(name, given):
    """On plane 1 (a traced index) of a three-plane leaf against ``ssd_scan``
    from that plane: every real token's output and the state at each row's
    ``q_len`` equal to float32 rounding, a row that brought nothing keeps its
    state bit for bit (the kernel neither reads nor writes it), outputs past a
    row's ``q_len`` are zeros, the other planes untouched, the leaf float32, the
    output in x's dtype; ``q_lens`` read off ``dt`` or told."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    a, leaf, q, chunk = window_inputs(name)
    want_y, want_h = ssd_scan(*a.values(), leaf[1], chunk)
    y, out = jax.jit(S.ssd_window, static_argnums=8)(
        *a.values(), leaf, jnp.asarray(1, jnp.int32), chunk,
        jnp.asarray(q, jnp.int32) if given else None)
    for b, n in enumerate(q):
        np.testing.assert_allclose(np.asarray(y)[b, :n], np.asarray(want_y)[b, :n],
                                   rtol=1e-5, atol=1e-5)
        assert not np.asarray(y)[b, n:].any()
    live = q > 0
    np.testing.assert_allclose(np.asarray(out[1])[live], np.asarray(want_h)[live],
                               rtol=1e-6, atol=1e-6)
    if live.any():
        assert np.abs(np.asarray(out[1])[live] - np.asarray(leaf[1])[live]).max() > 0.1
    np.testing.assert_array_equal(np.asarray(out[1])[~live], np.asarray(leaf[1])[~live])
    np.testing.assert_array_equal(np.asarray(out[::2]), np.asarray(leaf[::2]))
    assert out.dtype == jnp.float32 and out.shape == leaf.shape
    assert y.dtype == jnp.float32 and y.shape == a["x"].shape
    if name == "one-chunk" and given:
        y16, _ = S.ssd_window(a["x"].astype(jnp.bfloat16), *list(a.values())[1:], leaf, 1,
                              chunk)
        assert y16.dtype == jnp.bfloat16


@pytest.mark.parametrize("moves", ["0000", "0010", "1111", "1000", "0001", "0101"],
                         ids=["none", "one", "all", "first", "last", "alternating"])
def test_the_ssd_window_kernel_visits_only_the_rows_that_move(moves):
    """Whichever rows brought tokens (21 of a 24-wide window in chunks of 8):
    their outputs and state equal the scan's, every other row's state is bit for
    bit its input though its x, B and C are a real token's (``dt`` alone is 0),
    and its outputs zeros: the kernel takes the moving rows in its grid's first
    steps and gives the others no block of their own."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    live = np.asarray([c == "1" for c in moves])
    raw = scan_inputs(3, 4, 24)
    raw["dt"] = raw["dt"] * (live[:, None] & (np.arange(24) < 21)[None, :])[..., None]
    a = {k: jnp.asarray(v, jnp.float32) for k, v in raw.items()}
    h0 = a.pop("h0")
    leaf = jnp.stack([h0, h0 * 2])
    want_y, want_h = ssd_scan(*a.values(), leaf[1], 8)
    y, out = jax.jit(S.ssd_window, static_argnums=8)(*a.values(), leaf,
                                                     jnp.asarray(1, jnp.int32), 8)
    np.testing.assert_allclose(np.asarray(y)[live, :21], np.asarray(want_y)[live, :21],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1])[live], np.asarray(want_h)[live],
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(y)[~live].any() and not np.asarray(y)[:, 21:].any()
    np.testing.assert_array_equal(np.asarray(out[1])[~live], np.asarray(leaf[1])[~live])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))


def test_the_ssd_window_kernel_over_every_plane_in_turn_equals_the_recurrence():
    """As ``_patterned_layers`` runs it: the whole leaf carried through three
    windows of a 40-token sequence (17, 16 and 7 tokens in windows 24 wide, chunks
    of 8), the plane a traced index inside a scan over planes (Granite's scanned
    period) or a Python int (Nemotron's unrolled layers); every plane ends where
    the float64 recurrence from its own initial state does."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    T, W, planes, cuts = 40, 24, 3, [(0, 17), (17, 33), (33, 40)]
    raw = scan_inputs(11, 2, T)
    a = {k: jnp.asarray(v, jnp.float32) for k, v in raw.items()}
    h0 = a.pop("h0")
    leaf = jnp.stack([h0 * (j + 1) for j in range(planes)])

    def piece(lo, hi):
        pad = lambda v: jnp.pad(v[:, lo:hi], ((0, 0), (0, W - (hi - lo)))
                                + ((0, 0),) * (v.ndim - 2))
        return [pad(a[n]) if a[n].ndim > 1 else a[n]
                for n in ("x", "dt", "A", "Bm", "Cm", "D")]

    @jax.jit
    def traced(leaf):
        ys = []
        for lo, hi in cuts:
            def layer(leaf, j, args=piece(lo, hi)):
                y, leaf = S.ssd_window(*args, leaf, j, 8)
                return leaf, y[:, :hi - lo]
            leaf, y = jax.lax.scan(layer, leaf, jnp.arange(planes, dtype=jnp.int32))
            ys.append(y)
        return leaf, jnp.concatenate(ys, axis=2)            # [planes, B, T, H, P]

    @jax.jit
    def unrolled(leaf):
        ys = []
        for lo, hi in cuts:
            got = []
            for j in range(planes):
                y, leaf = S.ssd_window(*piece(lo, hi), leaf, j, 8)
                got.append(y[:, :hi - lo])
            ys.append(jnp.stack(got))
        return leaf, jnp.concatenate(ys, axis=2)

    out, y = traced(leaf)
    out_u, y_u = unrolled(leaf)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_u), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_u), rtol=1e-4, atol=1e-5)
    for j in range(planes):
        want_y, want_h = recurrence(**{**raw, "h0": raw["h0"] * (j + 1)})
        np.testing.assert_allclose(np.asarray(y)[j], want_y, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(out[j]), want_h, rtol=2e-4, atol=2e-4)


def test_window_logits_through_the_window_kernel_equal_the_scans(params, monkeypatch):
    """``forward`` at S > 1 takes ops/ssd_scan.py's window kernel (interpreted
    here) on the whole ``ssm`` leaf: two ragged windows' logits (one row sits the
    second out: its state stays bit for bit) and the state they leave equal those
    of ``ssd_scan`` patched in from and to a sliced plane, and the pass counts
    what the kernel passed over (``KVCache.ssm_window``)."""
    from ai_agent_kubectl_tpu.ops import ssd_scan as S

    toks = np.random.default_rng(9).integers(3, 500, size=(2, 64), dtype=np.int32)

    def plane_scan(x, dt, A, Bm, Cm, D, state, layer, chunk, q_lens=None):
        y, h = ssd_scan(x, dt, A, Bm, Cm, D,
                        jax.lax.dynamic_index_in_dim(state, layer, 0, False), chunk)
        return y, jax.lax.dynamic_update_index_in_dim(state, h, layer, 0)

    def windows(window_fn):
        monkeypatch.setattr(S, "ssd_window", window_fn)
        run = jax.jit(lambda tok, pos, cache, mask, q: forward(
            params, CFG, tok, pos, cache, token_mask=mask, write_mask=mask, q_lens=q))
        cache = dataclasses.replace(KVCache.zeros(CFG, 2, 64, dtype=jnp.float32),
                                    ssm_window=jnp.zeros((3,), jnp.int32))
        out, done = [], np.zeros(2, np.int32)
        for q in ([24, 11], [20, 0]):
            q = np.asarray(q, np.int32)
            tok = np.zeros((2, 24), np.int32)
            for b in range(2):
                tok[b, :q[b]] = toks[b, done[b]:done[b] + q[b]]
            pos = (done[:, None] + np.arange(24)[None, :]).astype(np.int32)
            before = np.asarray(cache.ssm) if cache.ssm is not None else None
            logits, cache = run(jnp.asarray(tok), jnp.asarray(pos), cache,
                                jnp.asarray(np.arange(24)[None, :] < q[:, None]),
                                jnp.asarray(q))
            out += [np.asarray(logits[b, :q[b]]) for b in range(2)]
            done += q
        np.testing.assert_array_equal(np.asarray(cache.ssm)[:, 1], before[:, 1])
        return np.concatenate(out), np.asarray(cache.ssm), np.asarray(cache.ssm_window)

    kernel = S.ssd_window
    want, want_state, _ = windows(plane_scan)
    got, state, counted = windows(kernel)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4 * want.std())
    np.testing.assert_allclose(state, want_state, rtol=2e-4, atol=2e-5)
    # two state-space layers: 2 + 1 rows moved, 0 + 1 sat a window out; chunks of
    # ``ssm_chunk`` past a moving row's q_len
    Q = S.window_chunk(24, CFG.ssm_chunk)
    skipped = sum(-(-24 // Q) - -(-n // Q) for n in (24, 11, 20))
    assert list(counted) == [2 * 3, 2 * 1, 2 * skipped]
    assert list(S.window_counts(jnp.asarray([37, 16, 0, 17]), 37, 8)) == [3, 1, 0 + 3 + 2]
