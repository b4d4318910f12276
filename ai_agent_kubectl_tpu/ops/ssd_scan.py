"""Mamba-2's state-space recurrence for a ragged window, and its convolution.

One head of a Mamba-2 layer keeps a state ``h [head_dim, state]`` and, for
token t with input ``x_t [head_dim]``, step ``dt_t > 0``, ``B_t``/``C_t
[state]`` (shared by the heads of a group) and the head's ``A < 0``:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = C_t . h_t + D x_t

``ssd_scan`` computes a window ``[B, S]`` of it from an initial state in
chunks (the "state-space duality" form: inside a chunk the outputs are one
masked matrix product of decays and C.B scores, between chunks only the
state is carried), ``ssd_step`` the single-token update of a decode step.
A token with ``dt = 0`` neither decays the state nor adds to it, which is
how padding is kept out: the caller zeroes ``dt`` past a row's ``q_len``
(and on dead rows), and the state returned is the state at each row's
``q_len``. The state is float32 whatever the activations are
(``STATE_DTYPE``): rounded to bf16 at every token, a slow head's state
stops moving once an update is under half an ulp of it
(tests/test_hybrid_model.py holds the dtype and shows the drift over 1,500
decode steps). The benchmark's comparison does NOT fail a bf16 state: its
sequences are too short for that (PERF.md, Open question 26, has the
readings by context length).

``causal_conv`` is the depthwise causal convolution in front of the scan,
with the ``K - 1`` inputs before the window carried in the cache beside
the state.

Plain ``jax.numpy``: at the serving shapes the scan is 2-3% of a layer's
arithmetic beside its two projections (a 64-token chunk of 64 heads is 0.5
GFLOP against the in-projection's 57), so it runs in float32 at the
highest matmul precision and a kernel is ROADMAP's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: dtype of the carried recurrent state (tools/refcheck_power.py patches
#: it to read what the comparison makes of a bf16 state, by context length).
STATE_DTYPE = jnp.float32

_HI = jax.lax.Precision.HIGHEST


def causal_conv(xbc, tail, w, b, q_lens):
    """silu(depthwise causal conv + bias) over a window.

    xbc [B, S, C]; tail [B, K-1, C]: the K-1 inputs before the window
    (zeros at a sequence's start); w [K, C] (w[K-1] multiplies the
    current input); b [C], or None for no bias; q_lens [B]: a row's valid
    columns.
    Returns (y [B, S, C], the new tail: the last K-1 inputs up to each
    row's q_len — the old tail where q_len is 0)."""
    K = w.shape[0]
    S = xbc.shape[1]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    acc = 0.0 if b is None else b.astype(jnp.float32)[None, None, :]
    for k in range(K):
        acc = acc + (full[:, k:k + S].astype(jnp.float32)
                     * w[k].astype(jnp.float32)[None, None, :])
    idx = q_lens[:, None] + jnp.arange(K - 1, dtype=q_lens.dtype)[None, :]
    new_tail = jnp.take_along_axis(full, idx[:, :, None], axis=1)
    return jax.nn.silu(acc).astype(xbc.dtype), new_tail.astype(tail.dtype)


def _grouped(x, dt, Bm, Cm, h0):
    """Heads split into their groups: x [B,S,G,Hg,P], dt [B,S,G,Hg],
    h [B,G,Hg,P,N] (B and C are [B,S,G,N] already)."""
    B_, S, H, P = x.shape
    G = Bm.shape[2]
    return (x.astype(jnp.float32).reshape(B_, S, G, H // G, P),
            dt.astype(jnp.float32).reshape(B_, S, G, H // G),
            Bm.astype(jnp.float32), Cm.astype(jnp.float32),
            h0.astype(jnp.float32).reshape(B_, G, H // G, P, h0.shape[-1]))


def ssd_step(x, dt, A, Bm, Cm, D, h0):
    """One token a row. x [B,1,H,P]; dt [B,1,H] (0 = the row does not
    move); A, D [H]; Bm, Cm [B,1,G,N]; h0 [B,H,P,N].
    Returns (y [B,1,H,P] in x's dtype, h [B,H,P,N] STATE_DTYPE)."""
    B_, _, H, P = x.shape
    xg, dtg, Bg, Cg, h = _grouped(x, dt, Bm, Cm, h0)
    xg, dtg, Bg, Cg = xg[:, 0], dtg[:, 0], Bg[:, 0], Cg[:, 0]
    Ag = A.astype(jnp.float32).reshape(dtg.shape[1:])
    h = (h * jnp.exp(dtg * Ag)[..., None, None]
         + (dtg[..., None] * xg)[..., None] * Bg[:, :, None, None, :])
    h = h.astype(STATE_DTYPE)
    y = jnp.einsum("bghpn,bgn->bghp", h.astype(jnp.float32), Cg,
                   precision=_HI)
    y = y.reshape(B_, 1, H, P) + (D.astype(jnp.float32)[None, None, :, None]
                                  * x.astype(jnp.float32))
    return y.astype(x.dtype), h.reshape(h0.shape)


def ssd_scan(x, dt, A, Bm, Cm, D, h0, chunk: int):
    """A window a row, in chunks of ``chunk`` tokens. x [B,S,H,P]; dt
    [B,S,H] (0 on padding); A, D [H]; Bm, Cm [B,S,G,N]; h0 [B,H,P,N].
    Returns (y [B,S,H,P] in x's dtype, the state after the window
    [B,H,P,N] STATE_DTYPE)."""
    B_, S, H, P = x.shape
    if S == 1:
        return ssd_step(x, dt, A, Bm, Cm, D, h0)
    Q = min(chunk, S)
    pad = -S % Q
    xg, dtg, Bg, Cg, h = _grouped(x, dt, Bm, Cm, h0)
    if pad:
        xg, dtg, Bg, Cg = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                   (a.ndim - 2)) for a in (xg, dtg, Bg, Cg))
    n_chunks = (S + pad) // Q

    def chunks(a):          # [B, S, ...] -> [n_chunks, B, Q, ...]
        return jnp.moveaxis(a.reshape((B_, n_chunks, Q) + a.shape[2:]), 1, 0)

    Ag = A.astype(jnp.float32).reshape(dtg.shape[2:])
    causal = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]

    def body(h, xs):
        xc, dtc, Bc, Cc = xs
        cum = jnp.cumsum(dtc * Ag, axis=1)                  # [B,Q,G,Hg] <= 0
        # inside the chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i.B_j)
        # dt_j x_j
        cb = jnp.einsum("bign,bjgn->bijg", Cc, Bc, precision=_HI)
        diff = cum[:, :, None] - cum[:, None, :]            # [B,Qi,Qj,G,Hg]
        m = (jnp.exp(jnp.where(causal, diff, -jnp.inf))
             * dtc[:, None] * cb[..., None])
        y = jnp.einsum("bijgh,bjghp->bighp", m, xc, precision=_HI)
        # from the state the chunk started with
        y = y + (jnp.einsum("bign,bghpn->bighp", Cc, h.astype(jnp.float32),
                            precision=_HI) * jnp.exp(cum)[..., None])
        # the state the chunk ends with
        to_end = jnp.exp(cum[:, -1:] - cum) * dtc           # [B,Q,G,Hg]
        h = (h.astype(jnp.float32) * jnp.exp(cum[:, -1])[..., None, None]
             + jnp.einsum("bjghp,bjgn->bghpn", xc * to_end[..., None], Bc,
                          precision=_HI))
        return h.astype(STATE_DTYPE), y

    h, ys = jax.lax.scan(body, h.astype(STATE_DTYPE),
                         tuple(chunks(a) for a in (xg, dtg, Bg, Cg)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B_, S + pad, H, P)[:, :S]
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), h.reshape(h0.shape)


def gated_group_norm(y, z, w, groups: int, eps: float):
    """RMSNorm over each of ``groups`` equal slices of the last axis of
    ``y * silu(z)``, times ``w`` (Mamba-2's gated norm, gate first)."""
    g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(shape) * w.astype(jnp.float32)).astype(y.dtype)
