"""The gated delta rule's recurrence for a ragged window, and its single step.

One head of a linear-attention layer keeps a matrix state ``S [d_k, d_v]``
and, for token t with a unit key ``k_t [d_k]``, a query ``q_t [d_k]``, a
value ``v_t [d_v]``, a log decay ``g_t <= 0`` (``alpha_t = exp(g_t)``) and a
write strength ``beta_t`` in (0, 2):

    u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)
    S_t = alpha_t S_{t-1} + k_t u_t^T            o_t = S_t^T q_t

which is ``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``:
every token decays the state, ERASES what the state held along its key and
writes its value there (Mamba-2's recurrence, ops/ssd_scan.py, only decays
and adds). ``gated_delta_scan`` computes a window ``[B, S]`` of it from an
initial state in chunks of C tokens. Inside a chunk, with ``gamma`` the
running sum of ``g`` and ``S_0`` the state the chunk starts with:

    A_ij = beta_i (k_i . k_j) exp(gamma_i - gamma_j)   for j < i, else 0
    T    = (I + A)^-1              (unit lower triangular: a forward
                                    substitution, here ``solve_triangular``)
    U    = T (beta * V) - T (beta * exp(gamma) * K) S_0
    O    = (exp(gamma) * Q) S_0 + tril(Q K^T * exp(gamma_i - gamma_j)) U
    S_C  = exp(gamma_C) S_0 + (exp(gamma_C - gamma) * K)^T U

``T`` and the two products it is applied to hold nothing of the state, so
every chunk's are made at once; only the three products with ``S_0`` run
chunk after chunk. Every decay is formed as ``exp(gamma_i - gamma_j)`` under
the causal mask: the factored ``exp(gamma_i) * exp(-gamma_j)`` overflows.
``gated_delta_step`` is the single-token update of a decode step. A token
with ``g = 0`` and ``beta = 0`` neither decays the state nor writes to it,
which is how padding is kept out: the caller zeroes both past a row's
``q_len`` (and on dead rows), and the state returned is the state at each
row's ``q_len``.

The state is float32 whatever the activations are (``STATE_DTYPE``;
tests/test_linear_attention.py holds the dtype and shows a bf16 state's
drift over a long decode), and it is kept ``[B, d_k, H x d_v]``, the heads
side by side along the lanes: at the published sizes (96 x 30 x 192) that
row has whole 128-lane tiles, where ``[H, d_k, d_v]`` pads every 192-wide
row to 256 and the leaf by a third. The step therefore never splits the
lane axis into heads: a head's ``S^T k`` is one product of ALL heads' keys
with the row, of which the head's own block of lanes is kept (``_own``), 30
times the arithmetic of the head-by-head product and still nothing beside
the state's bytes.

Plain ``jax.numpy``, float32 at the highest matmul precision, as
ops/ssd_scan.py: a kernel is ROADMAP's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

#: dtype of the carried state (tools/refcheck_power.py patches it to read
#: what the comparison makes of a bf16 state).
STATE_DTYPE = jnp.float32
#: tokens a chunk of ``gated_delta_scan`` (tiling only: any chunk gives the
#: recurrence's values; tools/refcheck_power.py patches it to 1 to round a bf16
#: state at every token).
CHUNK = 64

_HI = jax.lax.Precision.HIGHEST


def l2_normalize(x, scale: float = 1.0):
    """``x / ||x||_2 * scale`` over the last axis, float32 (an all-zero row,
    a padded token's, stays zero)."""
    x = x.astype(jnp.float32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
                * scale)


def _own(H: int, dv: int):
    """[H, H x dv] float32: 1 where lane l belongs to head h."""
    return (jnp.arange(H * dv)[None, :] // dv
            == jnp.arange(H)[:, None]).astype(jnp.float32)


def gated_delta_step(q, k, v, g, beta, S0):
    """One token a row. q, k [B,1,H,dk] (normalised); v [B,1,H,dv]; g, beta
    [B,1,H] (both 0 = the row does not move); S0 [B,dk,H*dv].
    Returns (o [B,1,H,dv] float32, S [B,dk,H*dv] STATE_DTYPE)."""
    B, _, H, dk = q.shape
    dv = v.shape[-1]
    own = _own(H, dv)
    lanes = lambda a: jnp.repeat(a.astype(jnp.float32), dv, axis=-1)
    S = S0.astype(jnp.float32)
    kq = jnp.concatenate([k[:, 0], q[:, 0]], axis=1).astype(jnp.float32)
    # every head's key and query against the whole row; a head keeps its
    # own lanes
    both = jnp.einsum("bhk,bkl->bhl", kq, S, precision=_HI)
    Sk = jnp.sum(both[:, :H] * own, axis=1)                     # [B, L]
    Sq = jnp.sum(both[:, H:] * own, axis=1)
    alpha, bet = lanes(jnp.exp(g[:, 0])), lanes(beta[:, 0])
    u = bet * (v[:, 0].reshape(B, H * dv).astype(jnp.float32) - alpha * Sk)
    S = alpha[:, None, :] * S + jnp.einsum(
        "bhk,bhl->bkl", k[:, 0].astype(jnp.float32), own * u[:, None, :],
        precision=_HI)
    S = S.astype(STATE_DTYPE)
    # o = S_t^T q = alpha S_{t-1}^T q + (k . q) u
    kdotq = jnp.sum(k[:, 0].astype(jnp.float32) * q[:, 0].astype(jnp.float32),
                    axis=-1)
    o = alpha * Sq + lanes(kdotq) * u
    return o.reshape(B, 1, H, dv), S


def gated_delta_scan(q, k, v, g, beta, S0, chunk: int = 0):
    """A window a row, in chunks of ``chunk`` tokens (0: ``CHUNK``). q, k [B,S,H,dk]
    (normalised); v [B,S,H,dv]; g, beta [B,S,H] (0 on padding); S0
    [B,dk,H*dv]. Returns (o [B,S,H,dv] float32, the state after the window
    [B,dk,H*dv] STATE_DTYPE)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if S == 1:
        return gated_delta_step(q, k, v, g, beta, S0)
    C = min(chunk or CHUNK, S)
    pad = -S % C
    n = (S + pad) // C

    def chunks(a):      # [B, S, H, ...] -> [B, n, H, C, ...]
        a = jnp.pad(a.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, C) + a.shape[2:]), 3, 2)

    qc, kc, vc, gc, bc = (chunks(a) for a in (q, k, v, g, beta))
    gamma = jnp.cumsum(gc, axis=-1)                             # [B,n,H,C] <= 0
    tril = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(tril, gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))                        # [B,n,H,Ci,Cj]
    kk = jnp.einsum("bnhik,bnhjk->bnhij", kc, kc, precision=_HI)
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  bc[..., None] * kk * decay, 0.0)
    eg = jnp.exp(gamma)[..., None]
    rhs = jnp.concatenate([bc[..., None] * vc, bc[..., None] * eg * kc], -1)
    X = solve_triangular(A + jnp.eye(C, dtype=jnp.float32), rhs, lower=True,
                         unit_diagonal=True)
    U0, Wm = X[..., :dv], X[..., dv:]           # T (beta V), T (beta e^g K)
    qk = jnp.einsum("bnhik,bnhjk->bnhij", qc, kc, precision=_HI) * decay
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None] * kc   # [B,n,H,C,dk]
    end = jnp.exp(gamma[..., -1])[..., None, None]              # [B,n,H,1,1]

    def body(Sh, xs):
        U0_, Wm_, qk_, qe_, to_end_, end_ = xs
        U = U0_ - jnp.einsum("bhik,bhkv->bhiv", Wm_, Sh, precision=_HI)
        O = (jnp.einsum("bhik,bhkv->bhiv", qe_, Sh, precision=_HI)
             + jnp.einsum("bhij,bhjv->bhiv", qk_, U, precision=_HI))
        Sh = end_ * Sh + jnp.einsum("bhik,bhiv->bhkv", to_end_, U,
                                    precision=_HI)
        return Sh.astype(STATE_DTYPE).astype(jnp.float32), O

    # heads apart while the chunks run; side by side again for the leaf
    Sh = jnp.moveaxis(S0.astype(jnp.float32).reshape(B, dk, H, dv), 2, 1)
    Sh, O = jax.lax.scan(
        body, Sh, tuple(jnp.moveaxis(a, 1, 0)
                        for a in (U0, Wm, qk, eg * qc, to_end, end)))
    o = jnp.moveaxis(jnp.moveaxis(O, 0, 1), 2, 3)               # [B,n,C,H,dv]
    o = o.reshape(B, S + pad, H, dv)[:, :S]
    S1 = jnp.moveaxis(Sh, 1, 2).reshape(B, dk, H * dv)
    return o, S1.astype(STATE_DTYPE)


def gated_head_norm(o, z, w, eps: float):
    """RMSNorm over each head's ``d_v`` values of ``o`` [..., H, dv], times
    the gain ``w`` [dv], THEN times ``silu(z)`` (norm first, the gate
    after: the reverse of Mamba-2's ``gated_group_norm``). float32."""
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * w.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
