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

A decode step is one Pallas kernel a layer (``ssd_step_kernel``, ISSUE 49).
It takes the WHOLE leaf ``[layers, B, H, head_dim, state]``, aliased input
to output, and the layer as a prefetched scalar of its index maps, so no
plane is sliced out in front of it and none written back behind it (in
``jnp`` from ``ssm[j]`` to ``ssm.at[j].set`` the chunk loop copied the whole
live leaf twice a step, 2 x 201 MB, and went over a plane two to three
times in float32 fusions); its grid is (row, block of whole groups of
heads: 32 of the published 64, [32, 64, 128]). A head's tile [64, 128] is
read into VMEM once, decayed, added the outer product of the head's
``dt x`` (a column: eight heads' inputs are turned over at once, on the
XLU; picked out of a row's inputs by a lane compare and a sum, or brought
to the first lane by a rotation, a head's column cost as much again as its
tile's update) with its group's ``B`` (a row), written once, and ``C . h``
is a sum along its lanes, all float32 on the VPU and XLU: the state
crosses HBM once in and once out, and a row that does not move (padding,
a dead slot) not at all: the moving rows take the grid's first steps and
the others' steps name the block before them again (ops/gated_delta.py's
step kernel is the same shape around another recurrence). ``ssd_step`` is
the same step in ``jnp`` on one plane, kept as what the tests hold the
kernel to.

The window's scan is plain ``jax.numpy``: at the serving shapes it is 2-3%
of a layer's arithmetic beside its two projections (a 64-token chunk of 64
heads is 0.5 GFLOP against the in-projection's 57), so it runs in float32
at the highest matmul precision, from and to a plane sliced out of the
leaf, and a kernel for it is ROADMAP's.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .gated_delta import moving_rows_first

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


#: Bytes of the state the step kernel takes a grid step at the most: a block
#: is the most whole groups of heads under it (32 of the published 64:
#: [32, 64, 128] float32, 1 MiB, 2 steps a row; tools/time_ssd_step.py times
#: the other widths).
_STEP_BLOCK_BYTES = 2 ** 20
#: Scoped VMEM the kernel asks for: a block in and out, each double-buffered
#: by the pipeline (4 x 1 MiB), a row's inputs and a head's temporaries.
_STEP_VMEM_BYTES = 16 * 2 ** 20


def _block_heads(H: int, G: int, head_bytes: int) -> int:
    """Heads a block of the step kernel: the most whole groups that divide
    ``H`` and stay under ``_STEP_BLOCK_BYTES``; one group where none does."""
    Hg = H // G
    fits = [n for n in range(Hg, H + 1, Hg)
            if H % n == 0 and n * head_bytes <= _STEP_BLOCK_BYTES]
    return max(fits, default=Hg)


def _step_kernel(lyr_ref, order_ref, n_live_ref, x_ref, decay_ref, bc_ref,
                 s_ref, y_ref, s_out_ref, *, heads_a_group: int):
    """Grid step (i, c): heads c*hb .. c*hb + hb - 1 of the state of row
    ``order_ref[i]`` in layer ``lyr_ref[0]``, for the ``n_live_ref[0]`` rows
    that move (``order_ref`` names them first); a later step's row does not
    move, its state block is the last moving row's last one again (nothing
    is fetched, nothing written back) and its output is zeros. x_ref
    [1,H/u,u,Pp]: the row's ``dt x``, ``u`` heads a tile, a head a sublane;
    decay_ref [1,H/u,u,N]: its ``exp(dt A)``, along the lanes; bc_ref
    [1,G,2,N]: a group's ``B`` then ``C``; s_ref, s_out_ref [1,1,hb,P,N]
    (one buffer: the leaf is aliased); y_ref [1,1,P,hb]: ``C . h``, a head a
    column. ``u`` heads at a time, in a loop whose body is traced once (a
    kernel is lowered anew for every program of every start: its jaxpr's
    length is ``setup_s``): their tile of ``dt x`` is turned over once, so
    that a head's is a column (P down the sublanes, as its state's tile
    has it) to broadcast along the lanes; a head's tile [P, N] is read
    once, decayed, added the outer product with ``B`` and written once, and
    ``C . h`` is a sum along its lanes, all float32 on the VPU and XLU."""
    del lyr_ref, order_ref
    hb, P = s_ref.shape[2:4]
    u = x_ref.shape[2]
    n_live = n_live_ref[0]
    first = pl.program_id(1) * hb

    @pl.when(pl.program_id(0) >= n_live)
    def _stays():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when((n_live == 0) & (pl.program_id(0) + pl.program_id(1) == 0))
    def _none_moves():      # the one block every step names: as it came
        s_out_ref[...] = s_ref[...]

    column = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape[2:], 1)

    def heads(t, y):
        at = first // u + t
        cols, decay = x_ref[0, at].T, decay_ref[0, at]      # [Pp, u], [u, N]
        for k in range(u):
            i = t * u + k
            bc = bc_ref[0, (first + i) // heads_a_group]                # [2, N]
            state = (s_ref[0, 0, i].astype(jnp.float32) * decay[k:k + 1]
                     + cols[:P, k:k + 1] * bc[0:1]).astype(s_out_ref.dtype)
            s_out_ref[0, 0, i] = state
            y = jnp.where(column == i,
                          jnp.sum(state.astype(jnp.float32) * bc[1:2], axis=1,
                                  keepdims=True), y)
        return y

    @pl.when(pl.program_id(0) < n_live)
    def _moves():
        y_ref[0, 0] = jax.lax.fori_loop(0, hb // u, heads,
                                        jnp.zeros(y_ref.shape[2:], jnp.float32))


def ssd_step_kernel(x, dt, A, Bm, Cm, D, state, layer, moves=None,
                    block_heads: int = 0):
    """``ssd_step`` on plane ``layer`` (a Python int or a traced scalar) of
    the WHOLE state leaf ``state`` [layers, B, H, P, N], as one Pallas kernel
    that reads each moving row's plane once, updates it in VMEM and writes
    it once, in place: the leaf is aliased input to output and the layer is
    a prefetched scalar of the index maps, so no plane is sliced out in
    front of the call and none written back behind it. The grid is (row,
    block of ``block_heads`` heads; 0: ``_block_heads``). ``moves`` [B]
    bool: the rows whose token is real; absent, the rows whose ``dt`` is not
    all 0. A row that does not move (padding, a dead slot) has its state
    neither read nor written (the rows that move take the grid's first
    steps, the others' steps name the block before them again) and its
    output is zeros (``ssd_step`` gives it ``C . h + D x`` of its unmoved
    state, which nothing reads). Other arguments as ``ssd_step``'s.
    Returns (y [B,1,H,P] in x's dtype, the leaf). Off the TPU the kernel
    runs interpreted."""
    return _step_call(x, dt, A, Bm, Cm, D, state, layer, moves,
                      block_heads=block_heads,
                      interpret=jax.default_backend() != "tpu")


@partial(jax.jit, static_argnames=("block_heads", "interpret"))
def _step_call(x, dt, A, Bm, Cm, D, state, layer, moves, *, block_heads: int,
               interpret: bool):
    """``ssd_step_kernel``, under a jit of its own: a model's state-space
    layers, and every program of a start, then share ONE trace of the
    kernel, and a program lowers it once (a chunk program's lowering is
    ``setup_s``, compile-cache hit or not)."""
    B, _, H, P = x.shape
    G, N = Bm.shape[2:]
    hb = block_heads or _block_heads(H, G, P * N * state.dtype.itemsize)
    nb = H // hb
    u = math.gcd(hb, 8)                 # heads the kernel's loop takes at once
    f32 = lambda a: a.astype(jnp.float32)
    x32, dt = f32(x[:, 0]), f32(dt[:, 0])                   # [B,H,P], [B,H]
    if moves is None:
        moves = jnp.any(dt != 0, axis=-1)
    order, n_live = moving_rows_first(moves)
    # ``u`` heads a tile: ``dt x`` a head a sublane, P along the lanes (whole
    # lane tiles: the kernel turns a tile over), and the decay along N lanes
    dtx = jnp.pad(dt[..., None] * x32, ((0, 0), (0, 0), (0, -P % 128)))
    dtx = dtx.reshape(B, H // u, u, -1)
    decay = jnp.broadcast_to(jnp.exp(dt * f32(A))[..., None],
                             (B, H, N)).reshape(B, H // u, u, N)
    bc = jnp.stack([f32(Bm[:, 0]), f32(Cm[:, 0])], axis=2)          # [B,G,2,N]
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def plane(i, c, lyr, order, n_live):
        # past the rows that move: the last of them, its last block
        last = jnp.maximum(n_live[0] - 1, 0)
        return (lyr[0], order[jnp.minimum(i, last)],
                jnp.where(i < n_live[0], c, nb - 1), 0, 0)

    row = lambda i, c, lyr, order, n_live: (order[i], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, nb),
        in_specs=[pl.BlockSpec((1,) + dtx.shape[1:], row),
                  pl.BlockSpec((1,) + decay.shape[1:], row),
                  pl.BlockSpec((1, G, 2, N), row),
                  pl.BlockSpec((1, 1, hb, P, N), plane)],
        out_specs=[pl.BlockSpec((1, 1, P, hb),
                                lambda i, c, lyr, order, n_live:
                                (order[i], c, 0, 0)),
                   pl.BlockSpec((1, 1, hb, P, N), plane)],
    )
    y, state = pl.pallas_call(
        partial(_step_kernel, heads_a_group=H // G),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, nb, P, hb), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        interpret=interpret,
        name="ssd_step",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_STEP_VMEM_BYTES)}),
    )(lyr, order, n_live, dtx, decay, bc, state)
    y = jnp.swapaxes(y, 2, 3).reshape(B, H, P) + f32(D)[:, None] * x32
    y = jnp.where(moves[:, None, None], y, 0.0)[:, None]
    return y.astype(x.dtype), state


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
