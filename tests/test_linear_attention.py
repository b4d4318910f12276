"""Linear attention by the gated delta rule beside full attention (ISSUE 45),
on the CPU at toy size (``toy-linear-hybrid``: three linear layers to one full
one, each followed by a dense MLP, a sublayer's output normed and not its
input, QK-norm over the whole projection, no rotary embedding) against the
benchmark's plain reference for olmo-hybrid-7b, loaded by path as
benchmark/refcheck.py loads it."""

import asyncio
import dataclasses
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
from ai_agent_kubectl_tpu.ops import gated_delta_window as GW
from ai_agent_kubectl_tpu.ops.quant import random_params_int8

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import refcheck  # noqa: E402

CFG = get_config("toy-linear-hybrid")
REFERENCE = "benchmark/configs/olmo-hybrid-7b.reference.py"
SIZES = {"num_attention_heads": CFG.n_heads, "num_key_value_heads": CFG.n_kv_heads,
         "head_dim": CFG.head_dim, "rms_norm_eps": CFG.rms_eps,
         "linear_num_value_heads": CFG.lin_value_heads,
         "linear_key_head_dim": CFG.lin_key_dim,
         "linear_value_head_dim": CFG.lin_value_dim,
         "linear_conv_kernel_dim": CFG.lin_conv,
         "linear_allow_neg_eigval": CFG.lin_neg_eigval}
PAGE, STEPS = 16, 3
#: max |logit - reference| at a position over the reference logits' standard
#: deviation, float32 weights and activations on both sides: what is left is the
#: chunked form's and the kernels' order of summation
TOLERANCE_REL = 2e-3


@pytest.fixture(scope="module")
def ref():
    return refcheck.load_reference(REFERENCE)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(3), CFG, dtype=jnp.float32)


# ------------------------------------------------------------- the recurrence

def recurrence(q, k, v, g, beta, S0):
    """Token by token in numpy float64. Shapes as ``gated_delta_scan``'s."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    Sh = np.moveaxis(np.asarray(S0, np.float64).reshape(B, dk, H, dv), 2, 1).copy()
    q, k, v, g, beta = (np.asarray(a, np.float64) for a in (q, k, v, g, beta))
    out = np.zeros((B, S, H, dv))
    for t in range(S):
        a = np.exp(g[:, t])[..., None]
        u = beta[:, t][..., None] * (v[:, t] - a * np.einsum("bhkv,bhk->bhv", Sh, k[:, t]))
        Sh = a[..., None] * Sh + np.einsum("bhk,bhv->bhkv", k[:, t], u)
        out[:, t] = np.einsum("bhkv,bhk->bhv", Sh, q[:, t])
    return out, np.moveaxis(Sh, 1, 2).reshape(B, dk, H * dv)


def scan_inputs(seed, B, S, q_lens, H=4, dk=24, dv=40):
    r = np.random.default_rng(seed)
    live = (np.arange(S)[None, :] < np.asarray(q_lens)[:, None])[..., None]
    return dict(
        q=GD.l2_normalize(r.normal(size=(B, S, H, dk)), dk ** -0.5),
        k=GD.l2_normalize(r.normal(size=(B, S, H, dk))),
        v=jnp.asarray(r.normal(size=(B, S, H, dv)), jnp.float32),
        g=jnp.asarray(np.where(live, -r.uniform(1e-3, 0.7, (B, S, H)), 0.0), jnp.float32),
        beta=jnp.asarray(np.where(live, r.uniform(0.0, 2.0, (B, S, H)), 0.0), jnp.float32),
        S0=jnp.asarray(r.normal(size=(B, dk, H * dv)), jnp.float32))


@pytest.mark.parametrize("S,chunk", [(150, 16), (150, 64), (64, 64), (37, 64), (5, 16),
                                     (150, 24), (40, 64)])
def test_chunked_scan_equals_the_recurrence_from_a_state_with_padding(S, chunk):
    """gated_delta_scan from an INITIAL state, rows padded past unequal q_lens
    (g and beta 0 there): outputs equal the recurrence's at every real token and
    the state returned is the state at each row's q_len. The chunk lengths (16,
    64, 64, 37, 5, 24, 40) are one, four, two and a half and a third of
    ``unit_lower_inverse``'s 16-row blocks: 24, 37 and 40 cross a block's edge
    unevenly and are padded with identity rows."""
    q_lens = [S, max(1, S // 4), 0]
    a = scan_inputs(S, 3, S, q_lens)
    want_o, want_S = recurrence(**a)
    o, S1 = jax.jit(GD.gated_delta_scan, static_argnums=6)(*a.values(), chunk)
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(o)[b, :n], want_o[b, :n], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S1), want_S, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(S1)[2], np.asarray(a["S0"])[2])
    assert S1.dtype == jnp.float32 and S1.shape == a["S0"].shape


@pytest.mark.parametrize("S,chunk", [(64, 64), (40, 64), (150, 24)])
def test_rows_that_brought_no_token_keep_their_state_bit_for_bit(S, chunk):
    """A padded row (real q, k and v, g = 0 and beta = 0 at every token) and a
    dead one (zeros) beside a live row: their ``A`` is zero, every block of
    ``unit_lower_inverse`` inverts to the identity and every merge adds zeros,
    so the state comes back as it went in, whole blocks or not."""
    a = scan_inputs(S, 3, S, [S, 0, 0])
    for n in ("q", "k", "v"):
        a[n] = a[n].at[2].set(0.0)
    o, S1 = jax.jit(GD.gated_delta_scan, static_argnums=6)(*a.values(), chunk)
    np.testing.assert_array_equal(np.asarray(S1)[1:], np.asarray(a["S0"])[1:])
    assert np.abs(np.asarray(S1)[0] - np.asarray(a["S0"])[0]).max() > 0.1
    assert np.isfinite(np.asarray(o)).all()


def worst_case_inputs(seed, B=2, S=64, H=4, dk=24, dv=40):
    """The hardest chunk the rule allows: every key (and query) within 0.05 of
    ONE unit vector a head, written at ``beta`` 1.8-2.0 with almost no decay:
    each token all but reflects the state along the one direction, and ``A`` is
    ~1.9 everywhere under its diagonal."""
    r = np.random.default_rng(seed)
    unit = GD.l2_normalize(r.normal(size=(B, 1, H, dk)))
    near = lambda: GD.l2_normalize(
        unit + 0.05 * GD.l2_normalize(r.normal(size=(B, S, H, dk))))
    a = dict(q=near() * dk ** -0.5, k=near(),
             v=jnp.asarray(r.normal(size=(B, S, H, dv)), jnp.float32),
             g=jnp.asarray(-r.uniform(1e-4, 1e-3, (B, S, H)), jnp.float32),
             beta=jnp.asarray(r.uniform(1.8, 2.0, (B, S, H)), jnp.float32),
             S0=jnp.asarray(r.normal(size=(B, dk, H * dv)), jnp.float32))
    assert np.abs(np.asarray(a["k"]) - np.asarray(unit)).max() < 0.05
    return a


@pytest.mark.parametrize("seed", [0, 2, 3])
def test_a_chunk_of_nearly_equal_keys_written_at_full_strength(seed):
    """``worst_case_inputs`` from a random state against the float64
    recurrence. The tolerance is what ``jax.scipy.linalg.solve_triangular``
    met here before ISSUE 47: at rtol 2e-4 it needed an atol of 2.3e-5 at the
    most over seeds 0-5 (outputs up to 3.7, a state up to 7.6), the inverse by
    blocks needs 3.0e-5, and 5e-5 is twice the former. The product of powers,
    ``(I - A)(I + A^2)(I + A^4)...``, is the same matrix on paper and reads
    2e+19 here (the test below): not a faster form of this, another result."""
    a = worst_case_inputs(seed)
    want_o, want_S = recurrence(**a)
    o, S1 = jax.jit(GD.gated_delta_scan)(*a.values())
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-4, atol=5e-5)
    np.testing.assert_allclose(np.asarray(S1), want_S, rtol=2e-4, atol=5e-5)


def test_the_inverse_by_blocks_holds_where_the_product_of_powers_does_not():
    """``unit_lower_inverse`` on the worst chunk's ``A`` against a float64
    inverse: a relative error of float32's rounding (3e-6 at the most), as a
    row-by-row substitution's, and an ``A`` of zeros gives the identity bit
    for bit. The log-depth form, six products of ``A``'s powers in float32,
    is off by more than the answer is large: ``A``'s powers grow like
    binomials times 2^k before they cancel."""
    a = worst_case_inputs(1)
    k, beta = np.asarray(a["k"], np.float64), np.asarray(a["beta"], np.float64)
    gamma = np.cumsum(np.asarray(a["g"], np.float64), axis=1)
    A = np.tril(np.einsum("bihk,bjhk->bhij", k, k)
                * np.moveaxis(beta, 1, 2)[..., None]
                * np.exp(np.moveaxis(gamma, 1, 2)[..., :, None]
                         - np.moveaxis(gamma, 1, 2)[..., None, :]), -1)
    want = np.linalg.inv(np.eye(64) + A)
    rel = lambda T: (np.linalg.norm(np.asarray(T, np.float64) - want)
                     / np.linalg.norm(want))
    A32 = jnp.asarray(A, jnp.float32)
    assert rel(jax.jit(GD.unit_lower_inverse)(A32)) < 1e-5
    eye = np.eye(64, dtype=np.float32)
    np.testing.assert_array_equal(
        np.asarray(GD.unit_lower_inverse(jnp.zeros((3, 64, 64)))),
        np.broadcast_to(eye, (3, 64, 64)))
    power, T = np.asarray(A32), eye - np.asarray(A32)
    for _ in range(5):                          # A^2, A^4, ... A^32
        power = power @ power
        T = T @ (eye + power)
    assert rel(T) > 1e12


def test_single_steps_equal_the_recurrence_and_a_window_of_one():
    a = scan_inputs(7, 2, 40, [40, 40])
    want_o, want_S = recurrence(**a)
    step = jax.jit(GD.gated_delta_step)
    S, outs = a["S0"], []
    for t in range(40):
        o, S = step(*(a[n][:, t:t + 1] for n in ("q", "k", "v", "g", "beta")), S)
        outs.append(np.asarray(o))
    np.testing.assert_allclose(np.concatenate(outs, 1), want_o, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(S), want_S, rtol=2e-4, atol=2e-5)
    one = [a[n][:, :1] for n in ("q", "k", "v", "g", "beta")]
    o1, S1 = GD.gated_delta_scan(*one, a["S0"], 64)
    o2, S2 = GD.gated_delta_step(*one, a["S0"])
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(S1), np.asarray(S2))


def step_inputs(seed, H, dk, dv, layers=3):
    """Three rows of one token each: row 0 live, row 1 padded (a real token's
    q, k and v with g = 0 and beta = 0), row 2 a dead slot (zeros, which
    ``l2_normalize`` leaves zero); a leaf of ``layers`` planes."""
    a = scan_inputs(seed, 3, 1, [1, 0, 0], H=H, dk=dk, dv=dv)
    for n in ("q", "k", "v"):
        a[n] = a[n].at[2].set(0.0)
    leaf = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(layers,) + a.pop("S0").shape), jnp.float32)
    return a, leaf


@pytest.mark.parametrize("H,dk,dv,block_heads", [
    (30, 96, 192, 0), (30, 96, 192, 2), (30, 96, 192, 30), (4, 24, 40, 0), (4, 24, 64, 2)],
    ids=["published", "published-2-heads", "published-30-heads", "small", "small-2-heads"])
def test_the_step_kernel_equals_the_jnp_step_on_live_padded_and_dead_rows(H, dk, dv,
                                                                          block_heads):
    """ops/gated_delta.py::gated_delta_step_kernel (interpreted here) on plane 1 of
    a three-plane leaf against ``gated_delta_step`` on that plane: outputs and
    state equal to float32 rounding (the kernel sums a head's 96 products in
    another order), a padded and a dead row's state bit for bit its input (the
    kernel neither reads nor writes it) and their outputs zeros, the other planes
    untouched, the leaf float32."""
    a, leaf = step_inputs(H, H, dk, dv)
    assert GD._block_heads(30, 96, 192, 4) == 10 and GD._block_heads(4, 24, 40, 4) == 4
    want_o, want_S = GD.gated_delta_step(*a.values(), leaf[1])
    o, out = jax.jit(GD.gated_delta_step_kernel, static_argnums=8)(
        *a.values(), leaf, jnp.asarray(1, jnp.int32), None, block_heads)
    np.testing.assert_allclose(np.asarray(o)[0], np.asarray(want_o)[0], rtol=1e-5, atol=1e-6)
    assert not np.asarray(o)[1:].any()          # a row that does not move reads nothing
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want_S), rtol=1e-5, atol=1e-6)
    assert np.abs(np.asarray(out[1, 0]) - np.asarray(leaf[1, 0])).max() > 0.1
    np.testing.assert_array_equal(np.asarray(out[1, 1:]), np.asarray(leaf[1, 1:]))
    np.testing.assert_array_equal(np.asarray(out[::2]), np.asarray(leaf[::2]))
    assert out.dtype == jnp.float32 and out.shape == leaf.shape
    assert o.dtype == jnp.float32 and o.shape == (3, 1, H, dv)


@pytest.mark.parametrize("moves", ["1111", "0000", "0101", "0010", "1000", "0111"])
def test_the_step_kernel_visits_only_the_rows_that_move(moves):
    """Whichever rows move (g, beta != 0), first, last, every or none: their
    outputs and state equal the ``jnp`` step's, and every other row's state is
    bit for bit its input and its output zeros: the kernel takes the moving
    rows in its grid's first steps and gives the others no block of their own."""
    live = np.asarray([c == "1" for c in moves])
    a = scan_inputs(3, 4, 1, live.astype(int), H=4, dk=24, dv=64)
    leaf = jnp.asarray(np.random.default_rng(4).normal(size=(2,) + a.pop("S0").shape),
                       jnp.float32)
    want_o, want_S = GD.gated_delta_step(*a.values(), leaf[1])
    o, out = jax.jit(GD.gated_delta_step_kernel, static_argnums=8)(
        *a.values(), leaf, jnp.asarray(1, jnp.int32), None, 2)
    np.testing.assert_allclose(np.asarray(o)[live], np.asarray(want_o)[live],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1])[live], np.asarray(want_S)[live],
                               rtol=1e-5, atol=1e-6)
    assert not np.asarray(o)[~live].any()
    np.testing.assert_array_equal(np.asarray(out[1])[~live], np.asarray(leaf[1])[~live])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))


def test_the_step_kernel_scanned_over_a_leafs_planes_equals_the_recurrence():
    """As ``_patterned_layers`` runs it: the whole leaf on a scan's carry, the
    plane a traced index, 40 tokens a plane; every plane ends where the float64
    recurrence from its own initial state does."""
    T, planes = 40, 3
    a = scan_inputs(11, 2, T, [T, T])
    leaf = jnp.asarray(np.random.default_rng(12).normal(
        size=(planes,) + a.pop("S0").shape), jnp.float32)

    @jax.jit
    def decode(leaf):
        def token(leaf, t):
            def layer(leaf, j):
                o, leaf = GD.gated_delta_step_kernel(
                    *(jax.lax.dynamic_slice_in_dim(a[n], t, 1, 1) for n in a), leaf, j)
                return leaf, o[:, 0]
            return jax.lax.scan(layer, leaf, jnp.arange(planes, dtype=jnp.int32))
        return jax.lax.scan(token, leaf, jnp.arange(T))

    out, o = decode(leaf)                                   # o [T, planes, B, H, dv]
    for j in range(planes):
        want_o, want_S = recurrence(**a, S0=leaf[j])
        np.testing.assert_allclose(np.asarray(o)[:, j].swapaxes(0, 1), want_o,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out[j]), want_S, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("H", [4, 6])
@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_two_key_heads_serve_four_and_six_value_heads(form, H):
    """ISSUE 55: q and k come with the KEY heads' count (2) and value head h
    reads key head h // r. The chunked scan (which makes ``K K^T`` and ``Q K^T``
    once a key head) over chunk edges from a state with padding, and the step
    kernel (interpreted; 128-wide values: one lane tile a head, as published)
    on one plane of a leaf, against the token-by-token recurrence given the
    keys and queries REPEATED; equal counts go the way they went (the same
    function, nothing repeated)."""
    r = H // 2
    if form == "scan":
        a = scan_inputs(H, 3, 150, [150, 37, 0], H=H)
        a["q"], a["k"] = a["q"][:, :, ::r], a["k"][:, :, ::r]
        for n in ("q", "k"):        # the recurrence saw head h // r's
            assert a[n].shape[2] == 2
        want_o, want_S = recurrence(**dict(
            a, q=np.repeat(a["q"], r, axis=2), k=np.repeat(a["k"], r, axis=2)))
        o, S1 = jax.jit(GD.gated_delta_scan, static_argnums=6)(*a.values(), 64)
        for b, n in enumerate([150, 37, 0]):
            np.testing.assert_allclose(np.asarray(o)[b, :n], want_o[b, :n],
                                       rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(S1), want_S, rtol=2e-4, atol=2e-5)
        return
    a, leaf = step_inputs(H, H, 16, 128)
    a["q"], a["k"] = a["q"][:, :, ::r], a["k"][:, :, ::r]
    full = dict(a, q=np.repeat(a["q"], r, axis=2), k=np.repeat(a["k"], r, axis=2))
    want_o, want_S = recurrence(**full, S0=leaf[1])
    o, out = jax.jit(GD.gated_delta_step_kernel, static_argnums=8)(
        *a.values(), leaf, jnp.asarray(1, jnp.int32), None, 0)
    o2, S2 = GD.gated_delta_step(*a.values(), leaf[1])
    for got_o, got_S in ((o, out[1]), (o2, S2)):
        np.testing.assert_allclose(np.asarray(got_o)[0], want_o[0], rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_S), want_S, rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out[::2]), np.asarray(leaf[::2]))


# ---------------------------------------------------------- the window kernel

def window_inputs(seed, S, q_lens, H, Hk, dk, dv, layers=3):
    """``scan_inputs`` with the KEY heads' count on q and k, and a leaf of
    ``layers`` planes in place of the one state."""
    a = scan_inputs(seed, len(q_lens), S, q_lens, H=H, dk=dk, dv=dv)
    r = H // Hk
    a["q"], a["k"] = a["q"][:, :, ::r], a["k"][:, :, ::r]
    leaf = jnp.asarray(np.random.default_rng(seed + 1).normal(
        size=(layers,) + a.pop("S0").shape), jnp.float32)
    return a, leaf


#: (value heads, key heads, d_k, d_v): r = 1 on a head of a third of a lane
#: tile (the toy's), r = 2 and r = 3 on a head that is a whole tile (as
#: qwen3-next's), r = 1 and r = 2 on a head of a tile and a half (as
#: olmo-hybrid-7b's 192)
WINDOW_HEADS = {"r1-dv40": (4, 4, 24, 40), "r2-dv128": (4, 2, 16, 128),
                "r3-dv128": (6, 2, 16, 128), "r1-dv192": (4, 4, 24, 192),
                "r2-dv192": (4, 2, 24, 192)}


@pytest.mark.parametrize("heads,S", [
    ("r1-dv40", 70), ("r1-dv40", 150), ("r2-dv128", 130), ("r3-dv128", 70),
    ("r1-dv192", 130), ("r2-dv192", 150)])
def test_the_window_kernel_equals_the_scan_from_a_carried_state(heads, S):
    """ops/gated_delta_window.py::gated_delta_window (interpreted here) on plane
    1 of a three-plane leaf against ``gated_delta_scan`` on that plane, windows
    that cross one and two chunk edges with rows of ``q_lens`` 0, 1, 33 and S
    in one window: outputs and state equal to float32 rounding (the kernel
    takes ``T`` out of the bracket and sums in another order) at every real
    token, outputs past ``q_len`` zeros, the row that brought none bit for bit
    its input (the kernel neither reads nor writes it), the other planes
    untouched, the leaf float32."""
    H, Hk, dk, dv = WINDOW_HEADS[heads]
    q_lens = [33, 0, S, 1]
    a, leaf = window_inputs(S, S, q_lens, H, Hk, dk, dv)
    assert GW.window_chunk(S) == 64 and GW._unit_heads(H, H // Hk, dv)
    want_o, want_S = GD.gated_delta_scan(*a.values(), leaf[1])
    o, out = jax.jit(GW.gated_delta_window)(
        *a.values(), leaf, jnp.asarray(1, jnp.int32), jnp.asarray(q_lens, jnp.int32))
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(o)[b, :n], np.asarray(want_o)[b, :n],
                                   rtol=1e-5, atol=2e-6)
        assert not np.asarray(o)[b, n:].any()
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want_S), rtol=1e-5, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(out[1, 1]), np.asarray(leaf[1, 1]))
    assert np.abs(np.asarray(out[1, 3]) - np.asarray(leaf[1, 3])).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(out[::2]), np.asarray(leaf[::2]))
    assert out.dtype == jnp.float32 and out.shape == leaf.shape
    assert o.dtype == jnp.float32 and o.shape == (4, S, H, dv)


@pytest.mark.parametrize("moves", ["1111", "0000", "0101", "1000"])
def test_the_window_kernel_visits_only_the_rows_that_brought_tokens(moves):
    """Whichever rows brought tokens, first, last, every or none, and with
    ``q_lens`` left for the kernel to find (a row's last ``g`` or ``beta`` that
    is not 0): their outputs and state are the scan's, every other row's state
    is bit for bit its input and its outputs zeros."""
    live = np.asarray([c == "1" for c in moves])
    q_lens = np.where(live, [70, 5, 64, 33], 0)
    a, leaf = window_inputs(5, 70, q_lens, 4, 2, 16, 128, layers=2)
    want_o, want_S = GD.gated_delta_scan(*a.values(), leaf[1])
    o, out = jax.jit(GW.gated_delta_window)(*a.values(), leaf, 1)
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(o)[b, :n], np.asarray(want_o)[b, :n],
                                   rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(out[1])[live], np.asarray(want_S)[live],
                               rtol=1e-5, atol=2e-6)
    assert not np.asarray(o)[~live].any()
    np.testing.assert_array_equal(np.asarray(out[1])[~live], np.asarray(leaf[1])[~live])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))


@pytest.mark.parametrize("S,chunk", [(16, 16), (24, 32), (40, 64)])
def test_a_window_under_a_chunk_is_one_chunk_of_whole_solve_blocks(S, chunk):
    """A 16-, 24- and 40-wide window (a toy's buckets) run as ONE chunk of 16,
    32 and 64 rows (one, two and four of the substitution's blocks: none, one
    and two merges), the rest padding; against the float64 recurrence."""
    assert GW.window_chunk(S) == chunk
    q_lens = [S, 3]
    a, leaf = window_inputs(S, S, q_lens, 4, 4, 24, 40, layers=1)
    want_o, want_S = recurrence(**a, S0=leaf[0])
    o, out = jax.jit(GW.gated_delta_window)(*a.values(), leaf, 0,
                                            jnp.asarray(q_lens, jnp.int32))
    for b, n in enumerate(q_lens):
        np.testing.assert_allclose(np.asarray(o)[b, :n], want_o[b, :n], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[0]), want_S, rtol=2e-4, atol=2e-5)


def test_the_window_kernel_scanned_over_a_leafs_planes_equals_the_recurrence():
    """As ``_patterned_layers`` runs a scanned period: the whole leaf on a
    scan's carry, the plane a traced ordinal, a 130-token window a plane; every
    plane ends where the float64 recurrence from its own initial state does."""
    S, planes, q_lens = 130, 3, [130, 70]
    a, leaf = window_inputs(21, S, q_lens, 4, 2, 16, 128, layers=planes)

    @jax.jit
    def windows(leaf):
        def layer(leaf, j):
            o, leaf = GW.gated_delta_window(*a.values(), leaf, j,
                                            jnp.asarray(q_lens, jnp.int32))
            return leaf, o
        return jax.lax.scan(layer, leaf, jnp.arange(planes, dtype=jnp.int32))

    out, o = windows(leaf)                                  # o [planes, B, S, H, dv]
    full = dict(a, q=np.repeat(a["q"], 2, axis=2), k=np.repeat(a["k"], 2, axis=2))
    for j in range(planes):
        want_o, want_S = recurrence(**full, S0=leaf[j])
        for b, n in enumerate(q_lens):
            np.testing.assert_allclose(np.asarray(o)[j, b, :n], want_o[b, :n],
                                       rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(out[j]), want_S, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("seed", [0, 3])
def test_the_window_kernel_holds_a_chunk_of_nearly_equal_keys_at_beta_2(seed):
    """``worst_case_inputs`` through the kernel, whose ``T`` is made in VMEM
    (substitution inside blocks of 16 in ``unit_lower_inverse``'s order, then
    the merges): the tolerance the scan meets, against the float64 recurrence."""
    a = worst_case_inputs(seed)
    want_o, want_S = recurrence(**a)
    leaf = a.pop("S0")[None]
    o, out = jax.jit(GW.gated_delta_window)(*a.values(), leaf, 0)
    np.testing.assert_allclose(np.asarray(o), want_o, rtol=2e-4, atol=5e-5)
    np.testing.assert_allclose(np.asarray(out[0]), want_S, rtol=2e-4, atol=5e-5)


def test_the_window_counts_are_the_rows_and_chunks_the_kernel_passes_over():
    """``window_counts``: of a 150-wide window (three chunks a row) whose rows
    brought 150, 33, 0, 1 and 64 tokens, four rows move, one stays and 0 + 2 +
    2 + 2 chunks past a moving row's ``q_len`` are passed over and one row took
    the step; rows and chunks sum to the slots and chunks the XLA scan went
    over (``chunks_scanned``)."""
    q_lens = jnp.asarray([150, 33, 0, 1, 64], jnp.int32)
    moved, still, skipped, stepped = (int(n) for n in GW.window_counts(q_lens, 150))
    assert (moved, still, skipped, stepped) == (4, 1, 6, 1)
    assert moved + still == 5 and skipped + 3 + 1 + 1 + 1 == moved * 3
    assert [int(n) for n in GW.window_counts(q_lens[2:3], 64)] == [0, 1, 0, 0]


def test_a_chunk_that_is_no_whole_block_or_heads_that_make_no_pairs_take_the_scan(monkeypatch):
    """tools/refcheck_power.py patches ``CHUNK`` to 1 to round a bf16 state at
    every token, and a block of three heads has no pair for the substitution's
    128 lanes: ``gated_delta_window`` then runs the plain scan from and to the
    plane, bit for bit."""
    a, leaf = window_inputs(9, 40, [40, 7], 3, 3, 24, 40, layers=2)
    assert not GW._unit_heads(3, 1, 40) and GW._unit_heads(10, 1, 192) == 2
    assert GW._unit_heads(16, 2, 128) == 2
    want_o, want_S = GD.gated_delta_scan(*a.values(), leaf[1])
    o, out = GW.gated_delta_window(*a.values(), leaf, 1)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(want_o))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(want_S))
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))
    monkeypatch.setattr(GD, "CHUNK", 1)
    assert GW.window_chunk(40) == 0
    b, leaf = window_inputs(9, 8, [8, 7], 4, 4, 24, 40, layers=1)
    want_o, want_S = GD.gated_delta_scan(*b.values(), leaf[0])
    o, out = GW.gated_delta_window(*b.values(), leaf, 0)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(want_S))


def test_a_token_erases_along_its_key_before_it_writes():
    """What the delta rule has that a decayed sum has not: the same key written
    twice at beta 1 holds the SECOND value, not the sum of both."""
    k = jnp.zeros((1, 2, 1, 4)).at[..., 0].set(1.0)
    v = jnp.asarray([[[[1.0, 2.0]], [[5.0, 7.0]]]])
    ones = jnp.ones((1, 2, 1))
    o, S = GD.gated_delta_scan(k, k, v, 0.0 * ones, ones, jnp.zeros((1, 4, 2)), 64)
    np.testing.assert_allclose(np.asarray(S)[0, 0], [5.0, 7.0], atol=1e-6)
    np.testing.assert_allclose(np.asarray(o)[0, 1, 0], [5.0, 7.0], atol=1e-6)


def test_a_bf16_state_drifts_where_a_float32_state_does_not(monkeypatch):
    """Why the state is float32 (ops/gated_delta.py::STATE_DTYPE): a slow head
    (decay 0.999 a step) decoded 1,500 steps, its state rounded at every one,
    ends many times further from the float64 recurrence in bf16."""
    T = 1500
    a = scan_inputs(0, 1, T, [T], H=1, dk=8, dv=8)
    a["g"] = jnp.full_like(a["g"], -1e-3)
    a["beta"] = jnp.full_like(a["beta"], 0.5)
    want, _ = recurrence(**a)

    def decoded(dtype):
        monkeypatch.setattr(GD, "STATE_DTYPE", dtype)

        def step(S, t):
            o, S = GD.gated_delta_step(*(a[n][:, t][:, None] for n in
                                         ("q", "k", "v", "g", "beta")), S)
            return S, o[:, 0]

        _, os = jax.lax.scan(step, a["S0"].astype(dtype), jnp.arange(T))
        return np.abs(np.asarray(os)[-200:, 0] - want[0, -200:]).mean()

    err32, err16 = decoded(jnp.float32), decoded(jnp.bfloat16)
    assert err16 > 20 * err32, (err16, err32)


# ---------------------------------------------------------- the whole model

def through_the_pool(cfg, params, toks, windows, impl="dense", packed=False, cut=None,
                     heads=None):
    """Every position's logits through the block pool: ``windows`` is a list of
    per-row q_lens, one ragged window each (each continuing from the state and
    the K/V the one before left), then STEPS single-token steps. The pool is
    built as refcheck.py builds it: K and V alone with ``cfg.n_layers`` rows, no
    state leaf. ``cut`` (a window's index): the state leaves are taken out of
    the cache after that window, the live rows zeroed, and put back before the
    next call, as a snapshot, an eviction and a restore do. ``heads``: KV heads a
    row of the caller's pool holds (``cfg.n_kv_heads`` as refcheck.py's)."""
    B = toks.shape[0]
    W = max(max(w) for w in windows)
    pages = -(-(sum(max(w) for w in windows) + STEPS) // PAGE)
    pool = (cfg.n_layers, B * pages, PAGE, heads or cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)

    @jax.jit
    def step(params, tok, pos, cache, wmask, q_lens):
        extra = {}
        if packed and tok.shape[1] > 1:
            extra = dict(packed_rows=B * tok.shape[1],
                         logits_at=jnp.maximum(q_lens - 1, 0))
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
            got[b].append(np.asarray(logits[b, -1:] if packed and w > 1 else
                                     logits[b, :q[b]]))
        done += q
    return [np.concatenate(g) for g in got], cache


def rel_err(got, want):
    want = np.asarray(want)
    return float(np.abs(got - want).max(axis=1).max() / want.std())


TOKS = np.random.default_rng(5).integers(3, 500, size=(2, 300), dtype=np.int32)
#: the second and third windows start from a carried state, one row sits a
#: window out; rows cross the scan's 64-token chunk edges (a window of 150 is
#: chunks of 64, 64 and 22) and one brings fewer tokens than the convolution's taps
WINDOWS = [[150, 3], [70, 0], [17, 130]]


def wanted(ref, params, sizes=SIZES, forward_fn=None):
    weights = ref.weights_from_program(params, CFG.n_layers)
    out = []
    for b in range(2):
        n = sum(w[b] for w in WINDOWS) + STEPS
        want, aux = (forward_fn or ref.forward)(sizes, weights, jnp.asarray(TOKS[b, :n]))
        assert list(aux) == ["position"] and aux["position"].shape == (n,)
        out.append(np.asarray(want))
    return out


@pytest.fixture(scope="module")
def program_logits(params):
    return through_the_pool(CFG, params, TOKS, WINDOWS)


def test_program_equals_the_reference_over_several_windows_and_decode(ref, params,
                                                                      program_logits):
    """Three ragged windows of unequal rows and decode steps: every position's
    logits against the plain reference's token-by-token recurrence."""
    got, cache = program_logits
    H, dk, dv = CFG.lin_value_heads, CFG.lin_key_dim, CFG.lin_value_dim
    assert cache.lin.shape == (6, 2, dk, H * dv) and cache.lin.dtype == jnp.float32
    assert cache.lconv.shape == (6, 2, CFG.lin_conv - 1, CFG.lin_conv_dim)
    for got_b, want in zip(got, wanted(ref, params)):
        assert rel_err(got_b, want) < TOLERANCE_REL


def test_a_state_taken_out_and_put_back_continues_as_if_uninterrupted(params, program_logits):
    """A snapshot, the live row lost to another sequence, a restore: bit for bit
    the uninterrupted run."""
    again, _ = through_the_pool(CFG, params, TOKS, WINDOWS, cut=1)
    for a, b in zip(again, program_logits[0]):
        np.testing.assert_array_equal(a, b)


#: a term of the mixer or of the block -> (what to find in the reference's
#: source, what to put in its place)
LEFT_OUT = {
    "the erase": ('v_t - a_t[:, None] * jnp.einsum("hkv,hk->hv", S, k_t)', "v_t"),
    "the decay": ("S = a_t[:, None, None] * S + ", "S = S + "),
    "the 2 in beta": ('(2.0 if cfg["linear_allow_neg_eigval"] else 1.0)', "1.0"),
    "the output gate": (' * jax.nn.silu(z.reshape(T, H, dv))', ""),
    "the output-side norm": (
        'h + rms_norm(gated_delta(cfg, lw, h), lw["lin_norm"], eps)',
        "h + gated_delta(cfg, lw, h)"),
    "the whole-projection QK-norm": (
        'rms_norm(x @ lw["wq"], lw["q_norm"], eps)', '(x @ lw["wq"])'),
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
    worst = max(rel_err(g, w) for g, w in
                zip(program_logits[0], wanted(crippled, params)))
    assert worst > 10 * TOLERANCE_REL, (term, worst)


def test_packed_window_rows_and_the_ragged_kernel_match_the_reference(ref, params):
    """The chip's path: the window's valid rows packed (the projections, the
    gated norm and the MLP run on them, the convolution and the scan on the
    window) and the paged attention kernel, interpreted."""
    got, _ = through_the_pool(CFG, params, TOKS, WINDOWS, impl="ragged", packed=True)
    want = wanted(ref, params)
    ends = np.cumsum([[w[b] for w in WINDOWS] for b in range(2)], axis=1)
    for b in range(2):
        # a packed window gives each row's LAST valid position's logits
        rows = [e - 1 for e, w in zip(ends[b], WINDOWS) if w[b]] + \
            list(range(ends[b][-1], ends[b][-1] + STEPS))
        live = [i for i, w in enumerate(WINDOWS) if w[b]] + [3, 4, 5]
        assert rel_err(got[b][live], want[b][rows]) < TOLERANCE_REL


def test_seeded_int8_weights_agree_with_the_reference(ref):
    """The benchmark's pair: random_params_int8's tree against the reference on
    its dequantised weights, as refcheck.run compares them. In float32 the two
    agree as the float32 trees do; in bf16, over ONE period as the benchmark's
    comparison runs it, by the rounding of 8 sublayers whose outputs are each
    normed to unit scale (it grows by ~0.03 of the logits' deviation a layer:
    0.03, 0.05, 0.12, 0.28 at 1, 2, 4, 8 layers of this toy). The seeded decays
    and step biases give heads that remember a few tokens and heads that
    remember thousands."""
    q = random_params_int8(jax.random.PRNGKey(11), CFG, dtype=jnp.float32,
                           quantize_embed=True)
    A = np.exp(np.asarray(q["layers"]["lin_A_log"][0], np.float64))
    dt = np.log1p(np.exp(np.asarray(q["layers"]["lin_dt_bias"][0], np.float64)))
    assert (A * dt).min() < 5e-3 and (A * dt).max() > 0.1
    assert q["layers"]["lin_wa"].dtype == jnp.float32       # a small leaf, not int8
    assert q["layers"]["lin_in"].q.dtype == jnp.int8
    got, _ = through_the_pool(CFG, q, TOKS, WINDOWS)
    for got_b, want in zip(got, wanted(ref, q)):
        assert rel_err(got_b, want) < TOLERANCE_REL
    one = dataclasses.replace(CFG, n_layers=4)
    q = random_params_int8(jax.random.PRNGKey(11), one, dtype=jnp.bfloat16,
                           quantize_embed=True)
    got, _ = through_the_pool(one, q, TOKS, WINDOWS)
    weights = ref.weights_from_program(q, 4)
    for b in range(2):
        n = sum(w[b] for w in WINDOWS) + STEPS
        want = np.asarray(ref.forward(SIZES, weights, jnp.asarray(TOKS[b, :n]))[0])
        err = np.abs(got[b] - want).max(axis=1) / float(want.std())
        assert 0.02 < np.median(err) < 0.25, np.median(err)


@pytest.mark.parametrize("impl", ["dense", "ragged"])
def test_a_callers_pool_of_the_models_own_heads_becomes_the_engines_leaf(ref, impl):
    """Ten KV heads are kept as sixteen (``ModelConfig.kv_heads_paged``: whole
    tiles of 8, the published 30 as 32). A pool built from ``n_kv_heads``, as
    benchmark/refcheck.py builds it, is padded ONCE at ``forward``'s entry and
    returned so: every layer then writes and reads the rows the engine's leaf
    has, the spare heads zeros, and the logits are those of a pool that was
    whole from the start, and the reference's. One period alone is a scan of one
    step (``_scan_period``), the program a configuration cut for the comparison
    shares with its full depth."""
    from ai_agent_kubectl_tpu.models.transformer import _scan_period

    cfg = dataclasses.replace(CFG, n_layers=4, n_heads=10, n_kv_heads=10, head_dim=16)
    assert cfg.kv_heads_paged == 16 and _scan_period(cfg.layer_kinds) == 8
    assert _scan_period(CFG.layer_kinds) == 8 and _scan_period(tuple("M*E")) == 0
    p = init_params(jax.random.PRNGKey(4), cfg, dtype=jnp.float32)
    own, cache = through_the_pool(cfg, p, TOKS, WINDOWS, impl=impl)
    whole, _ = through_the_pool(cfg, p, TOKS, WINDOWS, impl=impl, heads=16)
    assert cache.k.shape[-2] == cache.v.shape[-2] == 16
    assert not np.asarray(cache.k[..., 10:, :]).any() and np.asarray(cache.k[0]).any()
    sizes = dict(SIZES, num_attention_heads=10, num_key_value_heads=10, head_dim=16)
    weights = ref.weights_from_program(p, 4)
    for b in range(2):
        np.testing.assert_array_equal(own[b], whole[b])
        want = ref.forward(sizes, weights, jnp.asarray(TOKS[b, :own[b].shape[0]]))[0]
        assert rel_err(own[b], want) < TOLERANCE_REL


def test_no_rotary_embedding_and_the_dense_cache(params, program_logits):
    """Full attention takes no positions of its own, and the per-slot cache
    (tests and tools; the engine refuses it) runs the same layers."""
    dense = KVCache.zeros(CFG, 1, 64, dtype=jnp.float32)
    assert dense.k.shape[0] == CFG.n_of("*") == 2
    pos = jnp.arange(50, dtype=jnp.int32)[None]
    a, out = forward(params, CFG, jnp.asarray(TOKS[:1, :50]), pos, dense)
    assert rel_err(np.asarray(a)[0], program_logits[0][0][:50]) < TOLERANCE_REL
    assert out.lin is not None and out.lconv is not None


def test_the_configuration_says_what_it_keeps():
    assert CFG.layer_kinds == tuple("LDLDLD*D" * 2)
    assert CFG.has_linear and CFG.keeps_state and not CFG.has_ssm and not CFG.slides
    assert (CFG.n_of("L"), CFG.n_of("*"), CFG.n_of("D")) == (6, 2, 8)
    assert CFG.lin_key_dim % 128 and CFG.lin_value_dim % 128
    assert CFG.lin_key_dim != CFG.lin_value_dim
    # float32 [24, 4 x 40] and a bf16 tail of 3 x (2 x 96 + 160) a linear layer
    assert CFG.state_bytes() == 6 * (4 * 24 * 160 + 2 * 3 * 352)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))))
    assert CFG.param_count() == n
    # a key head serves a WHOLE number of value heads (ISSUE 55); the rest is
    # still refused by name
    assert dataclasses.replace(CFG, lin_value_heads=8).layer_kinds.count("L") == 6
    with pytest.raises(ValueError, match="lin_value_heads 6 over lin_key_heads 4"):
        dataclasses.replace(CFG, lin_value_heads=6).layer_kinds
    with pytest.raises(NotImplementedError, match="post_norm"):
        uniform = dataclasses.replace(get_config("toy-8m"), post_norm=True)
        forward(init_params(jax.random.PRNGKey(0), uniform), uniform,
                jnp.zeros((1, 4), jnp.int32), jnp.arange(4, dtype=jnp.int32)[None],
                KVCache.zeros(uniform, 1, 16))


# ------------------------------------------------------------------ the engine

def _mk(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    defaults = dict(dtype="float32", max_seq_len=320, prefill_buckets=(16, 64),
                    prefix_cache=False, batch_size=2, chunk_len=4, kv_pool_page=16,
                    state_snapshots=8, kv_pool_blocks=96, radix_lru_blocks=64)
    defaults.update(kw)
    return BatchedJaxEngine(CFG, tokenizer=ByteTokenizer(), **defaults)


PREAMBLE = "cluster context: " + "node pool alpha beta gamma delta " * 3
TURNS = ["agent one asks about pods in kube-system;  ",
         "tool says twelve pods are ready; ",
         "tool says one pod is crash looping now; "]


@pytest.fixture(scope="module")
def from_token_zero():
    """Every prompt of the session answered by an engine with no radix tree:
    each prefilled from token 0."""
    eng = _mk(radix_cache=False)

    async def run():
        await eng.start()
        try:
            out, hist = {}, PREAMBLE
            for t in TURNS:
                hist += t
                out[hist] = (await eng.generate(hist, max_tokens=10, temperature=0.0)).text
            return out
        finally:
            await eng.stop()

    return asyncio.run(run())


@pytest.mark.parametrize("force_ragged", [True], ids=["ragged-staged"])
async def test_a_session_seated_from_snapshots_answers_as_from_token_zero(from_token_zero,
                                                                        force_ragged):
    """(Through the chip's ragged regime, interpreted, whose admissions stage a
    prompt's tail into the next chunk's window; the CPU's gather regime serves
    the fixture's engine.) Turns 2 and 3 of a session are seated from the snapshot the turn before
    left (the matrix state and the convolution's tail, through StateStore) and
    prefill only what follows; every answer equals the engine's that prefilled
    from token 0. /health.linear_attention and /health.ssm are served."""
    eng = _mk(force_ragged=force_ragged)
    await eng.start()
    try:
        hist = PREAMBLE
        for t in TURNS:
            hist += t
            r = await eng.generate(hist, max_tokens=10, temperature=0.0)
            assert r.text == from_token_zero[hist], t
        health = eng.family_health()
        st, lin = health["ssm"], health["linear_attention"]
        assert st["restores"] >= 2 and st["prefix_tokens_usable"] > 0
        assert st["state_bytes"] == CFG.state_bytes() == lin["state_bytes_per_sequence"]
        assert st["layer_passes"]["linear"] == st["forward_passes"] * 6
        assert (lin["layers_linear"], lin["layers_full"]) == (6, 2)
        # three linear layers to one full one, each decode row through all
        assert lin["decode_rows_linear"] == 3 * lin["decode_rows_full"] > 0
        # the other slots' rows of those passes: the step kernel passed them over
        assert lin["decode_rows_still"] > 0 and lin["decode_rows_still"] % 6 == 0
        assert lin["full_keys_read"] > 100 * lin["decode_rows_full"] / 2
        if force_ragged:
            assert lin["window_rows_linear"] > 0 and lin["chunks_scanned"] > 0
            # what the window kernel did with those windows' slots: a slot
            # a linear layer is updated or passed over, chunk by chunk
            slots = lin["window_rows_moved"] + lin["window_rows_still"]
            assert lin["window_rows_moved"] > 0 and slots % (2 * 6) == 0
            assert lin["window_rows_stepped"] <= lin["window_rows_moved"]
            assert lin["window_chunks_skipped"] >= 0
        eng._state.check()
    finally:
        await eng.stop()


async def test_the_family_is_refused_where_it_cannot_be_served():
    with pytest.raises(ValueError, match="keeps a linear-attention state.*dense per-slot"):
        await _mk(kv_pool=False).start()
    with pytest.raises(ValueError, match="keeps a linear-attention state.*SPEC_DECODE"):
        await _mk(spec_decode=True, spec_draft_model="toy-linear-hybrid").start()
