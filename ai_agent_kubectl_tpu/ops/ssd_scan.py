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
the others' steps name the block before them again (ops/state_leaf.py: the
frame every kernel on a state leaf shares; this module has the two bodies
and their operands). ``ssd_step`` is
the same step in ``jnp`` on one plane, kept as what the tests hold the
kernel to.

A window (S > 1: an eager piece of one sequence, a chunk program's
prologue over every slot) is one Pallas kernel a layer too (``ssd_window``,
ISSUE 54), on the same whole aliased leaf with the layer a prefetched
scalar. Its grid is (row, block of heads as the step kernel's, chunk of
``window_chunk`` tokens: 128 of a 512-wide window), the chunks innermost:
a block of a row's state is fetched once, stays in VMEM across the row's
chunks and is written once; the rows that brought tokens take the grid's
first steps, a row that brought none has its state neither read nor
written, and a chunk wholly past a row's ``q_len`` is passed over. Inside
a chunk a head's decay-and-score tile ``M`` [Q, Q] (``exp(cum_i - cum_j)
dt_j C_i.B_j`` under the diagonal; ``cum`` the chunk's running sum of ``dt
A``, made in XLA in front of the call, a head a sublane) exists in VMEM
only, and the three products (``M x``, ``C h``, ``x^T B``) run on the MXU
in float32 at the highest precision. The outputs go straight to the
window's rows [B, S, H*P], whole lane tiles, so nothing is stacked, moved
or reshaped behind the loop. In ``jnp`` (``ssd_scan`` from ``ssm[j]`` to
``ssm.at[j].set``) a ``lax.scan`` over chunks stacked each chunk's outputs
with a ``dynamic_update_slice`` of 134 us for 4 MB (the chunk axis sat
inside the buffer's tile), went over a [B, Q, Q, H] float32 decay matrix in
HBM (16 MB a row a chunk at Q = 256) in elementwise fusions, and scanned
every slot's row and every column of a prologue, padding too: 8.6% of
granite-4.0-h-micro's device in the stacking alone (ledger, PR 53).
``ssd_scan`` is that plain form, kept as what the tests hold the kernel to.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import state_leaf

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
#: [32, 64, 128] float32, 1 MiB, 2 steps a row; tools/time_state_kernels.py
#: times the other widths).
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

    @pl.when(state_leaf.passed_over(n_live))
    def _stays():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(state_leaf.none_moves(n_live))
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
        moves = state_leaf.any_gate(dt != 0)
    order, n_live = state_leaf.moving_rows_first(moves)
    # ``u`` heads a tile: ``dt x`` a head a sublane, P along the lanes (whole
    # lane tiles: the kernel turns a tile over), and the decay along N lanes
    dtx = jnp.pad(dt[..., None] * x32, ((0, 0), (0, 0), (0, -P % 128)))
    dtx = dtx.reshape(B, H // u, u, -1)
    decay = jnp.broadcast_to(jnp.exp(dt * f32(A))[..., None],
                             (B, H, N)).reshape(B, H // u, u, N)
    bc = jnp.stack([f32(Bm[:, 0]), f32(Cm[:, 0])], axis=2)          # [B,G,2,N]
    row = lambda i, c, lyr, order, n_live: (order[i], 0, 0, 0)
    y, state = state_leaf.visit(
        partial(_step_kernel, heads_a_group=H // G), name="ssd_step",
        grid=(B, nb), layer=layer, order=order, n_live=n_live,
        at=state_leaf.step_block(nb),
        in_specs=[pl.BlockSpec((1,) + dtx.shape[1:], row),
                  pl.BlockSpec((1,) + decay.shape[1:], row),
                  pl.BlockSpec((1, G, 2, N), row)],
        out_specs=[pl.BlockSpec((1, 1, P, hb),
                                lambda i, c, lyr, order, n_live:
                                (order[i], c, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, nb, P, hb), jnp.float32)],
        leaf=state, block=(1, 1, hb, P, N),
        plane=lambda layer, row, c: (layer, row, c, 0, 0),
        vmem_limit_bytes=_STEP_VMEM_BYTES, interpret=interpret,
    )(dtx, decay, bc)
    y = jnp.swapaxes(y, 2, 3).reshape(B, H, P) + f32(D)[:, None] * x32
    y = jnp.where(moves[:, None, None], y, 0.0)[:, None]
    return y.astype(x.dtype), state


#: Scoped VMEM the window kernel asks for: a block of the state in and out
#: (2 MiB where one group is 64 heads), a chunk's inputs and outputs
#: [128, 4096], each double-buffered, and ``u`` heads' temporaries.
_WINDOW_VMEM_BYTES = 48 * 2 ** 20
#: Tokens a chunk of the window kernel at the most: a chunk's decay-and-score
#: tile is [Q, Q] a head, so its exponents and its ``M x`` product grow with
#: Q a token while the two products with the state do not; 128 fills the
#: MXU's rows and a lane tile (tools/time_state_kernels.py times the others).
_WINDOW_CHUNK = 128


def window_chunk(S: int, chunk: int) -> int:
    """Tokens a chunk the window kernel walks a window of ``S`` in, under a
    configuration's ``ssm_chunk`` ("tiling only: changes no result"): the
    narrower of it and ``_WINDOW_CHUNK``; a window under that is one chunk
    (whole sublane tiles)."""
    return min(chunk, _WINDOW_CHUNK, S + -S % 8)


def window_counts(q_lens, S: int, chunk: int):
    """int32 [3], what ``ssd_window`` does with a window of ``S`` whose rows
    brought ``q_lens`` [B] tokens: the rows whose state it updates, the rows
    it passes over (they brought none) and the chunks past a moving row's
    ``q_len`` that it passes over (``KVCache.ssm_window``)."""
    Q = window_chunk(S, chunk)
    moved = jnp.sum(q_lens > 0, dtype=jnp.int32)
    skipped = jnp.where(q_lens > 0, -(-S // Q) - _chunks_with_tokens(q_lens, Q),
                        0)
    return jnp.stack([moved, q_lens.shape[0] - moved,
                      jnp.sum(skipped, dtype=jnp.int32)])


def _chunks_with_tokens(q_lens, Q: int):
    return (q_lens + Q - 1) // Q


def _window_kernel(lyr_ref, order_ref, n_live_ref, chunks_ref, x_ref, cum_ref,
                   dt_ref, b_ref, c_ref, d_ref, s_ref, y_ref, s_out_ref,
                   cb_ref, *, heads_a_group: int):
    """Grid step (i, c, k): chunk k of heads c*hb .. c*hb + hb - 1 of row
    ``order_ref[i]``'s window, from and to its state in layer ``lyr_ref[0]``,
    for the ``n_live_ref[0]`` rows that brought tokens (``order_ref`` names
    them first) and the ``chunks_ref[row]`` chunks that hold a row's tokens;
    every other step names the blocks of the step before it again (nothing is
    fetched, nothing written back) and does nothing. The state block
    s_out_ref [1,1,hb,P,N] (one buffer with s_ref: the leaf is aliased) is
    filled from s_ref at a row's first chunk, stays in VMEM across its
    chunks and is written back once. x_ref, y_ref [1,Q,hb*P]: the chunk's
    inputs and outputs, a head P lanes, as the mixer has them; cum_ref,
    dt_ref [1,hb,Q]: the chunk's running sum of ``dt A`` (<= 0) and ``dt``, a
    head a sublane, a token a lane; b_ref, c_ref [1,Q,gb*N]: ``B`` and ``C``
    of the block's groups; d_ref [1,hb*P]: ``D``, a head's P lanes over;
    cb_ref [Q,Q]: scratch, a group's ``C . B`` scores.

    ``u`` heads at a time, in a loop whose body is traced once: their
    [u,Q] tiles of ``cum`` and ``dt`` are turned over once, so that a head's
    is a column too. A head's decay-and-score tile ``M`` [Q,Q] (``exp(cum_i
    - cum_j) dt_j C_i.B_j`` under the diagonal) is made in VMEM and
    multiplied with the head's inputs on the MXU, the heads of one lane tile
    into one lane tile (each against the tile's inputs with the other heads'
    lanes zeroed: the MXU is a lane tile wide either way); ``C h`` and ``x^T
    B`` are one product each for the ``u`` heads, whose per-head factors
    (``exp(cum_i)``; ``exp(cum_Q - cum_j) dt_j``) are columns spread over a
    head's lanes. All float32, every product at the highest precision."""
    del lyr_ref
    Q = x_ref.shape[1]
    hb, P, N = s_ref.shape[2:]
    u = math.gcd(hb, 8, heads_a_group)              # heads of one group
    lanes = min(128, u * P) if P < 128 else P       # of one store of outputs
    r = lanes // P                                  # heads that share it
    i, k = pl.program_id(0), pl.program_id(2)
    n_live = n_live_ref[0]
    first = pl.program_id(1) * hb
    dot = partial(jax.lax.dot_general, precision=_HI,
                  preferred_element_type=jnp.float32)
    f32 = lambda a: a.astype(jnp.float32)

    @pl.when(state_leaf.fetched(i, k, n_live))
    def _fetched():     # (where no row moves: the one block every step names)
        s_out_ref[...] = s_ref[...]

    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    lane = jax.lax.broadcasted_iota(jnp.int32, (Q, lanes), 1)

    def spread(cols, at):
        """[Q, lanes]: column ``at + n`` of ``cols`` over lanes n*P .. n*P +
        P - 1."""
        out = jnp.broadcast_to(cols[:, at + r - 1:at + r], (Q, lanes))
        for n in range(r - 2, -1, -1):
            out = jnp.where(lane < (n + 1) * P,
                            jnp.broadcast_to(cols[:, at + n:at + n + 1],
                                             (Q, lanes)), out)
        return out

    def heads(t, carry):
        h0 = t * u                                  # in the block
        g = (first + h0) // heads_a_group - first // heads_a_group
        Bg = f32(b_ref[0, :, pl.ds(pl.multiple_of(g * N, N), N)])     # [Q,N]
        Cg = f32(c_ref[0, :, pl.ds(pl.multiple_of(g * N, N), N)])

        @pl.when((t == 0) | ((first + h0) % heads_a_group == 0))
        def _scores():
            cb_ref[...] = dot(Cg, Bg, (((1,), (1,)), ((), ())))
        cb = cb_ref[...]
        cum, dt = cum_ref[0, pl.ds(h0, u)], dt_ref[0, pl.ds(h0, u)]   # [u,Q]
        last = cum[:, Q - 1:Q]                                        # [u,1]
        cum_c = cum.T                                                 # [Q,u]
        grow_c, to_end_c = jnp.exp(cum_c), (jnp.exp(last - cum) * dt).T
        state = s_out_ref[0, 0, pl.ds(h0, u)]                         # [u,P,N]
        state2 = f32(state).reshape(u * P, N)
        from_state = dot(Cg, state2, (((1,), (1,)), ((), ())))      # [Q,u*P]
        scaled = []
        for n in range(u // r):             # a lane tile of outputs at a time
            at = pl.multiple_of((h0 + n * r) * P, lanes)
            xs = f32(x_ref[0, :, pl.ds(at, lanes)])                  # [Q,lanes]
            y = (from_state[:, n * lanes:(n + 1) * lanes]
                 * spread(grow_c, n * r) + xs * d_ref[:, pl.ds(at, lanes)])
            for m in range(r):
                j = n * r + m
                decay = jnp.exp(jnp.where(causal, cum_c[:, j:j + 1]
                                          - cum[j:j + 1], -jnp.inf))
                own = xs if r == 1 else jnp.where(
                    (lane >= m * P) & (lane < (m + 1) * P), xs, 0.0)
                y = y + dot(decay * dt[j:j + 1] * cb, own,
                            (((1,), (0,)), ((), ())))
            y_ref[0, :, pl.ds(at, lanes)] = y.astype(y_ref.dtype)
            scaled.append(xs * spread(to_end_c, n * r))
        added = dot(jnp.concatenate(scaled, axis=1), Bg,
                    (((0,), (0,)), ((), ())))                         # [u*P,N]
        kept = jnp.exp(jnp.broadcast_to(last, (u, N)))      # the chunk's decay
        for j in range(u):
            s_out_ref[0, 0, h0 + j] = (
                f32(state[j]) * kept[j:j + 1]
                + added[j * P:(j + 1) * P]).astype(s_out_ref.dtype)
        return carry

    @pl.when((i < n_live) & (k < chunks_ref[order_ref[i]]))
    def _moves():
        jax.lax.fori_loop(0, hb // u, heads, 0)


def ssd_window(x, dt, A, Bm, Cm, D, state, layer, chunk: int, q_lens=None):
    """``ssd_scan`` from and to plane ``layer`` (a Python int or a traced
    scalar) of the WHOLE state leaf ``state`` [layers, B, H, P, N], as one
    Pallas kernel: the leaf is aliased input to output and the layer a
    prefetched scalar of the index maps, so no plane is sliced out in front
    of the call and none set back behind it. The grid is (row, block of
    ``_block_heads`` heads, chunk of ``window_chunk`` tokens), the chunks
    innermost: a block of a row's state is read once, stays in VMEM across
    the row's chunks and is written once; a chunk's [Q, Q] decay-and-score
    tiles are made in VMEM and its outputs written straight to the
    window's rows [B, S, H*P], so nothing is stacked behind the loop.
    ``q_lens`` [B]: a row's real tokens, a prefix of its columns (absent:
    up to its last ``dt`` that is not 0); ``dt`` is 0 past them. A row that
    brought none has its state neither read nor written (the rows that
    brought some take the grid's first steps) and a chunk wholly past a
    row's ``q_len`` is passed over; outputs past ``q_len`` are zeros
    (``ssd_scan`` gives them ``C . h + D x``, which nothing reads). Other
    arguments as ``ssd_scan``'s. Returns (y [B,S,H,P] in x's dtype, the
    leaf). Off the TPU the kernel runs interpreted."""
    return _window_call(x, dt, A, Bm, Cm, D, state, layer, q_lens,
                        chunk=window_chunk(x.shape[1], chunk),
                        interpret=jax.default_backend() != "tpu")


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _window_call(x, dt, A, Bm, Cm, D, state, layer, q_lens, *, chunk: int,
                 interpret: bool):
    """``ssd_window``, under a jit of its own (as ``_step_call``: one trace
    of the kernel a start, one lowering a program)."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    Q = chunk
    pad = -S % Q
    n_chunks = (S + pad) // Q
    hb = _block_heads(H, G, P * N * state.dtype.itemsize)
    nb, Hg = H // hb, H // G
    gb = max(hb // Hg, 1)                           # groups a block
    f32 = lambda a: a.astype(jnp.float32)
    dt = f32(dt)
    if q_lens is None:
        q_lens = state_leaf.tokens_brought(dt != 0)
    q_lens = q_lens.astype(jnp.int32)
    order, n_live = state_leaf.moving_rows_first(q_lens > 0)
    live_chunks = _chunks_with_tokens(q_lens, Q)
    rows = partial(state_leaf.whole_chunks, pad=pad)
    # ``dt`` and a chunk's running sum of ``dt A``, a head a sublane, a token
    # a lane
    dth = jnp.moveaxis(rows(dt), 1, 2)                          # [B,H,S+pad]
    cum = jnp.cumsum((dth * f32(A)[:, None]).reshape(B, H, n_chunks, Q),
                     axis=-1).reshape(dth.shape)
    # (row, block, chunk) of a step: as it is, the block of ``cum`` and ``dt``
    # [B, H, S]
    at = state_leaf.window_block(nb, lambda live_chunks, row: live_chunks[row])

    def tokens(i, c, k, *s):        # [B, S, heads' lanes]
        row, c, k = at(i, c, k, *s)
        return row, k, c

    def groups(i, c, k, *s):        # [B, S, groups' lanes]
        row, c, k = at(i, c, k, *s)
        return row, k, (c * hb) // (gb * Hg)

    y, state = state_leaf.visit(
        partial(_window_kernel, heads_a_group=Hg), name="ssd_window",
        grid=(B, nb, n_chunks), layer=layer, order=order, n_live=n_live,
        extra=live_chunks, at=at,
        in_specs=[pl.BlockSpec((1, Q, hb * P), tokens),
                  pl.BlockSpec((1, hb, Q), at),
                  pl.BlockSpec((1, hb, Q), at),
                  pl.BlockSpec((1, Q, gb * N), groups),
                  pl.BlockSpec((1, Q, gb * N), groups),
                  pl.BlockSpec((1, hb * P), lambda i, c, k, *s: (0, at(
                      i, c, k, *s)[1]))],
        out_specs=[pl.BlockSpec((1, Q, hb * P), tokens)],
        out_shape=[jax.ShapeDtypeStruct((B, S + pad, H * P), x.dtype)],
        scratch_shapes=[pltpu.VMEM((Q, Q), jnp.float32)],
        leaf=state, block=(1, 1, hb, P, N),
        plane=lambda layer, row, c: (layer, row, c, 0, 0),
        vmem_limit_bytes=_WINDOW_VMEM_BYTES, interpret=interpret,
    )(rows(x).reshape(B, S + pad, H * P), cum, dth,
      rows(Bm).reshape(B, S + pad, G * N), rows(Cm).reshape(B, S + pad, G * N),
      jnp.repeat(f32(D), P)[None])
    return state_leaf.window_rows(y, q_lens, S).reshape(B, S, H, P), state


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
