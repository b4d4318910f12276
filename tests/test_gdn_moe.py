"""The gated delta rule with fewer key heads than value heads beside gated
attention and a chip's share of the experts under a gated shared expert (ISSUE
55), on the CPU at toy size (``toy-gdn-moe``: two key heads for four value
heads, ``LELELE*E`` twice, an elementwise gate out of ``W_q``, a (1 + w) norm a
head on q and k, a quarter of the lanes rotated, 4 held of 16 scored) against
the benchmark's plain reference for qwen3-next-80b-a3b-instruct-l12, loaded by
path as benchmark/refcheck.py loads it."""

import dataclasses
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import init_params
from ai_agent_kubectl_tpu.ops.quant import random_params_int8

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import refcheck  # noqa: E402
from test_linear_attention import rel_err, through_the_pool  # noqa: E402

#: one period of the toy's two (the rehearsal runs both): the programs compile
#: in half the time and every kind of layer is there
CFG = dataclasses.replace(get_config("toy-gdn-moe"), n_layers=4)
REFERENCE = "benchmark/configs/qwen3-next-80b-a3b-instruct-l12.reference.py"
SIZES = {"num_attention_heads": CFG.n_heads, "num_key_value_heads": CFG.n_kv_heads,
         "head_dim": CFG.head_dim, "rms_norm_eps": CFG.rms_eps,
         "rope_theta": CFG.rope_theta, "partial_rotary_factor": CFG.rope_partial,
         "linear_num_key_heads": CFG.lin_key_heads,
         "linear_num_value_heads": CFG.lin_value_heads,
         "linear_key_head_dim": CFG.lin_key_dim,
         "linear_value_head_dim": CFG.lin_value_dim,
         "linear_conv_kernel_dim": CFG.lin_conv,
         "num_experts_per_tok": CFG.experts_per_token,
         "first_routed_expert": CFG.first_expert, "full_attention_interval": 4}
STEPS = 3
#: as tests/test_linear_attention.py's: float32 on both sides
TOLERANCE_REL = 2e-3

TOKS = np.random.default_rng(5).integers(3, 500, size=(2, 300), dtype=np.int32)
#: the second and third windows start from a carried state, one row sits a
#: window out; rows cross the scan's 64-token chunk edges and one brings fewer
#: tokens than the convolution's taps
WINDOWS = [[150, 3], [70, 0], [17, 130]]


@pytest.fixture(scope="module")
def ref():
    return refcheck.load_reference(REFERENCE)


@pytest.fixture(scope="module")
def params():
    """float32, with the zero-started gains of the (1 + w) norms and the plain
    ones moved off their start, so that a norm taken for the other kind shows."""
    p = init_params(jax.random.PRNGKey(3), CFG, dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 16))
    move = lambda a: a + 0.3 * jax.random.normal(next(keys), a.shape, jnp.float32)
    p["layers"] = {n: move(a) if n.endswith("norm") else a for n, a in p["layers"].items()}
    p["final_norm"] = move(p["final_norm"])
    return p


def wanted(ref, params, forward=None):
    weights = ref.weights_from_program(params, CFG.n_layers, SIZES)
    out = []
    for b in range(2):
        n = sum(w[b] for w in WINDOWS) + STEPS
        want, aux = (forward or ref.forward)(SIZES, weights, jnp.asarray(TOKS[b, :n]))
        assert set(aux) == {"clear_score", "margin", "position"}
        assert aux["margin"].shape == (n, CFG.n_of("E"))
        out.append(np.asarray(want))
    return out


@pytest.fixture(scope="module")
def program_logits(params):
    return through_the_pool(CFG, params, TOKS, WINDOWS)


def test_program_equals_the_reference_over_several_windows_and_decode(ref, params,
                                                                      program_logits):
    """Three ragged windows of unequal rows and decode steps by the cache: every
    position's logits against the plain reference (the recurrence token by
    token on a state a VALUE head, key head h // 2; both attention kinds; the
    state crossing chunk edges and windows)."""
    got, cache = program_logits
    H, dk, dv = CFG.lin_value_heads, CFG.lin_key_dim, CFG.lin_value_dim
    assert cache.lin.shape == (3, 2, dk, H * dv) and cache.lin.dtype == jnp.float32
    assert CFG.lin_conv_dim == 2 * CFG.lin_key_heads * dk + H * dv == 256
    assert cache.lconv.shape == (3, 2, CFG.lin_conv - 1, CFG.lin_conv_dim)
    for got_b, want in zip(got, wanted(ref, params)):
        assert rel_err(got_b, want) < TOLERANCE_REL


#: a term of the model -> (what to find in the reference's source, what to put
#: in its place)
LEFT_OUT = {
    "the key head a value head reads": (
        "(jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))",
        "(jnp.tile(a, (1, Hv // Hk, 1)) for a in (q, k))"),
    "the attention's gate": (" * gate).reshape(T, H * hd)", ").reshape(T, H * hd)"),
    "the gate's place in a head's columns": (
        "q = rope(cfg, norm(qg[..., :hd], lw[\"q_norm\"], eps), pos)\n"
        "    gate = jax.nn.sigmoid(qg[..., hd:])",
        "q = rope(cfg, norm(qg[..., hd:], lw[\"q_norm\"], eps), pos)\n"
        "    gate = jax.nn.sigmoid(qg[..., :hd])"),
    "the 1 in a head's (1 + w) norm": (
        'k = rope(cfg, norm((n @ lw["wk"]).reshape(T, KV, hd), lw["k_norm"], eps), pos)',
        'k = rope(cfg, norm((n @ lw["wk"]).reshape(T, KV, hd), lw["k_norm"] - 1.0, eps), pos)'),
    "the rotated share of the lanes": ('cfg.get("partial_rotary_factor", 1.0)', "1.0"),
    "the shared expert's gate": (
        'jax.nn.sigmoid(n @ lw["shared_expert_gate"]) * shared', "shared"),
    "the plain gain of the gated head norm": (
        'o * lw["lin_gate_norm"][None, None, :]', 'o * (1.0 + lw["lin_gate_norm"][None, None, :])'),
}


@pytest.mark.parametrize("term", list(LEFT_OUT))
def test_the_tolerance_fails_a_reference_with_a_term_left_out(params, program_logits, term):
    """The comparison has power over what this family adds: the reference's own
    source with ONE term altered disagrees with the program by far more than
    the tolerance."""
    find, put = LEFT_OUT[term]
    source = (ROOT / REFERENCE).read_text()
    assert source.count(find) == 1, term
    crippled = types.ModuleType("crippled_reference")
    exec(compile(source.replace(find, put), f"<{term}>", "exec"), crippled.__dict__)
    worst = max(rel_err(g, w) for g, w in
                zip(program_logits[0], wanted(crippled, params, crippled.forward)))
    assert worst > 10 * TOLERANCE_REL, (term, worst)


def test_packed_window_rows_and_the_ragged_kernel_match_the_reference(ref, params):
    """The chip's path: the window's valid rows packed (the gate's logits are
    unpacked beside q) and the paged attention kernel, interpreted."""
    got, _ = through_the_pool(CFG, params, TOKS, WINDOWS, impl="ragged", packed=True)
    want = wanted(ref, params)
    ends = np.cumsum([[w[b] for w in WINDOWS] for b in range(2)], axis=1)
    for b in range(2):
        rows = [e - 1 for e, w in zip(ends[b], WINDOWS) if w[b]] + \
            list(range(ends[b][-1], ends[b][-1] + STEPS))
        live = [i for i, w in enumerate(WINDOWS) if w[b]] + [3, 4, 5]
        assert rel_err(got[b][live], want[b][rows]) < TOLERANCE_REL


def test_seeded_int8_weights_agree_with_the_reference(ref):
    """The weights the benchmark serves (ops/quant.py::random_params_int8): the
    (1 + w) gains start at 0 and a head's q/k gain is the seeded 2 in all, the
    gated head norm's gain is a plain 1, and program and reference agree to
    bf16's rounding."""
    params = random_params_int8(jax.random.PRNGKey(9), CFG, dtype=jnp.bfloat16,
                                quantize_embed=True)
    layers = params["layers"]
    assert not np.asarray(layers["lin_norm"], np.float32).any()
    assert (np.asarray(layers["lin_gate_norm"], np.float32) == 1).all()
    assert (np.asarray(layers["q_norm"], np.float32) == 1).all()     # 1 + 1 = 2
    assert layers["wq"].q.shape == (1, CFG.dim, CFG.n_heads * 2 * CFG.head_dim)
    assert layers["shared_expert_gate"].shape == (4, CFG.dim, 1) and "wg" not in layers
    got, _ = through_the_pool(CFG, params, TOKS[:, :80], [[60, 3]])
    weights = ref.weights_from_program(params, CFG.n_layers, SIZES)
    for b, n in enumerate((63, 6)):
        want, _ = ref.forward(SIZES, weights, jnp.asarray(TOKS[b, :n]))
        assert rel_err(got[b], np.asarray(want)) < 0.25


def test_the_four_shares_of_a_layers_experts_add_up_to_the_uncut_layer(ref):
    """The router scores 16 experts; four trees hold 4 each (``first_expert`` 0,
    4, 8, 12) of the SAME uncut model. Each share's routed part, the GATED shared
    expert counted once, adds up to the uncut layer's, in the program (both MoE
    paths, the picks counted) and in the reference."""
    from ai_agent_kubectl_tpu.models.transformer import _expert_mixer, _shared_expert

    whole_cfg = dataclasses.replace(CFG, n_experts=16, router_width=0)
    whole = init_params(jax.random.PRNGKey(11), whole_cfg, dtype=jnp.float32)["layers"]
    x = jnp.asarray(np.random.default_rng(12).standard_normal((2, 24, CFG.dim)), jnp.float32)
    cut_of = lambda first: {k: (v[:, first:first + 4] if k in ("w_gate", "w_up", "w_down") else v)
                            for k, v in whole.items()}
    with jax.default_matmul_precision("highest"):
        uncut, _ = _expert_mixer(whole_cfg, whole, 1, x, None, None, "dense")
        normed = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.rms_eps) * (
            1.0 + whole["mlp_norm"][1])
        shared = _shared_expert(CFG, {k: v[1] for k, v in whole.items()
                                      if k.startswith("shared_")}, normed)
        ungated = _shared_expert(dataclasses.replace(CFG, shared_expert_gate=False),
                                 {k: v[1] for k, v in whole.items()
                                  if k.startswith("shared_")}, normed)
        assert float(jnp.abs(shared - ungated).max()) > 0.05
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


@pytest.mark.parametrize("keys,values,decay,ok", [
    (2, 4, False, True), (2, 6, False, True), (4, 4, True, True),
    (4, 6, False, False), (0, 4, False, False), (2, 4, True, False)],
    ids=["2-for-4", "2-for-6", "equal-channel-decay", "4-for-6", "no-key-heads",
         "2-for-4-channel-decay"])
def test_layer_kinds_refuses_only_what_is_not_built(keys, values, decay, ok):
    """A key head serves a whole number of value heads; a decay a key channel
    needs one for one. Anything else is refused by name."""
    cfg = dataclasses.replace(CFG, lin_key_heads=keys, lin_value_heads=values,
                              lin_channel_decay=decay, lin_decay_floor=-5.0 if decay else 0.0)
    if ok:
        assert cfg.layer_kinds.count("L") == 3
        return
    with pytest.raises(ValueError, match="lin_value_heads .* over lin_key_heads"):
        cfg.layer_kinds


def test_the_configuration_says_what_it_keeps_and_counts():
    """Two caches (K/V in the two attention layers, a matrix state in the six
    linear ones), the picks counted, the leaves' count by ``param_count``."""
    from ai_agent_kubectl_tpu.models import families

    assert [k.name for k in families.kinds_of(CFG)] == [
        "experts", "linear", "linear_window", "expert_share"]
    # (a decay a key channel runs no window kernel: Ling's toy keeps its lane)
    assert "linear_window" not in [
        k.name for k in families.kinds_of(get_config("toy-kda-mla-moe"))]
    assert CFG.counts_picks and CFG.gate_elementwise and not CFG.gate_per_head
    assert not get_config("toy-sliding-moe").counts_picks      # the programs it had
    assert get_config("toy-kda-mla-moe").counts_picks
    dk, dv, H = CFG.lin_key_dim, CFG.lin_value_dim, CFG.lin_value_heads
    assert CFG.state_bytes() == 3 * (4 * dk * H * dv + 2 * 3 * CFG.lin_conv_dim)
    params = init_params(jax.random.PRNGKey(0), CFG, dtype=jnp.float32)
    leaves = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert leaves == CFG.param_count()
    said = families._linear_section(CFG, {"dev": [0] * 6}, {"forward_passes": 0})
    assert (said["key_heads"], said["value_heads"]) == (2, 4)


LOG = "kubectl logs web-1: " + "GET /healthz 200 3ms; POST /orders 503 upstream; " * 3
ASK = "why do orders fail?  "


async def test_a_re_ask_is_seated_from_the_snapshot_and_the_shared_blocks():
    """The normal path (BatchedJaxEngine, pool, radix tree, StateStore; the
    chip's ragged regime, interpreted): the same ask about one log twice, then
    another. The first is prefilled from token 0; the others are seated from
    the delta-rule layers' snapshot at the log's last block edge AND the
    attention layer's shared K/V blocks of the same radix chain, and the
    repeated ask reads the answer it read from token 0. /health carries the two
    head counts and the picks."""
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    eng = BatchedJaxEngine(
        CFG, tokenizer=ByteTokenizer(), dtype="float32", max_seq_len=320,
        prefill_buckets=(16, 64), prefix_cache=False, batch_size=2, chunk_len=4,
        kv_pool_page=16, state_snapshots=8, kv_pool_blocks=96, radix_lru_blocks=64,
        force_ragged=True)
    await eng.start()
    try:
        texts = [(await eng.generate(LOG + ask, max_tokens=10, temperature=0.0)).text
                 for ask in (ASK, ASK, "which pod is it?  ")]
        assert texts[0] == texts[1] and len(texts[2]) > 0
        health = eng.family_health()
        st, lin, moe = (health[s] for s in ("ssm", "linear_attention", "moe"))
        assert st["restores"] >= 2 and st["prefix_tokens_usable"] > 0
        assert eng.kv_pool_health()["radix"]["hit_tokens"] >= 2 * (len(LOG) // 16) * 16
        assert st["state_bytes"] == CFG.state_bytes() == lin["state_bytes_per_sequence"]
        assert (lin["layers_linear"], lin["layers_full"], lin["key_heads"],
                lin["value_heads"]) == (3, 1, 2, 4)
        # every decode row through the 3 delta-rule layers and the 1 of attention
        assert lin["decode_rows_linear"] == 3 * lin["decode_rows_full"] > 0
        assert lin["full_keys_read"] > lin["decode_rows_full"] * len(LOG)
        assert lin["window_rows_linear"] > 0 and lin["chunks_scanned"] > 0
        # the router: 2 picks a live row a layer in the 4 expert layers, a
        # quarter of the 16 scored held here
        assert moe["picks"] % (2 * 4) == 0 and 0 < moe["picks_held"] < moe["picks"]
        assert (moe["experts_held"], moe["router_width"]) == (4, 16)
        eng._state.check()
    finally:
        await eng.stop()
