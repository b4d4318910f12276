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
    T    = (I + A)^-1              (unit lower triangular: by blocks,
                                    ``unit_lower_inverse``)
    U    = T (beta * V) - T (beta * exp(gamma) * K) S_0
    O    = (exp(gamma) * Q) S_0 + tril(Q K^T * exp(gamma_i - gamma_j)) U
    S_C  = exp(gamma_C) S_0 + (exp(gamma_C - gamma) * K)^T U

``T`` and the two products it is applied to hold nothing of the state, so
every chunk's are made at once; only the three products with ``S_0`` run
chunk after chunk. ``T`` is made by blocks (ISSUE 47): forward substitution
inside the diagonal blocks of 16 rows, every block of every chunk, head and
row at once, then the blocks merged two and two on the MXU, ``[[T1, 0],
[-T2 A21 T1, T2]]``, 16 -> 32 -> 64, and applied as one product. A row-by-row
solve of the whole chunk is 64 dependent steps for 0.6 M multiply-adds, and
XLA's (``triangular_solve``: on the TPU a custom call that inverts the one
diagonal block a 64-row system is) took 5 us a matrix, 77-80% of a
window's scan. The product of powers ``(I - A)(I + A^2)(I + A^4)...`` has
fewer steps still and is NOT used: it is off by 2e+19 where a chunk's keys
are nearly equal and written at ``beta`` 2. Every decay is formed as
``exp(gamma_i - gamma_j)`` under the causal mask: the factored
``exp(gamma_i) * exp(-gamma_j)`` overflows.
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
row to 256 and the leaf by a third.

A decode step is one Pallas kernel a layer (``gated_delta_step_kernel``,
ISSUE 46). It takes the WHOLE leaf ``[layers, B, d_k, H x d_v]``, aliased
input to output, and the layer as a prefetched scalar of its index maps, so
no plane is sliced out in front of it and none written back behind it; its
grid is (row, lane block of a whole number of heads in whole lane tiles: 10
of the 30, [96, 1,920]). A block's state is read into VMEM once; a pair of
heads (three 128-lane tiles) at a time, and of it a tile at a time, the key
and the query are broadcast down their own head's lanes, multiplied with
the tile and reduced over its sublanes (``S^T k`` and ``S^T q`` on the VPU,
exact float32), ``u``, ``alpha S + k u^T`` and ``o`` follow in float32, and
the tile is written once: the state crosses HBM once in and once out, and a
row that does not move (padding, a dead slot) not at all: the moving rows
take the grid's first steps and the others' steps name the block before
them again (ops/state_leaf.py: the frame every kernel on a state leaf
shares; this module has the body and its operands). ``gated_delta_step`` is the same step in ``jnp`` on one plane,
kept as what the tests hold the kernel to: it never splits the lane axis
into heads either, so a head's ``S^T k`` is one product of ALL heads' keys
with the row, of which the head's own block of lanes is kept (``_own``), 30
times the arithmetic and a second and third trip over the state.

The window's scan here is plain ``jax.numpy``, float32 at the highest matmul
precision; ops/gated_delta_window.py has its kernel, which tests hold to it.

FEWER KEY HEADS THAN VALUE HEADS (ISSUE 55: 16 for 32): value head h reads
key head ``h // r``. ``q`` and ``k`` then come with the key heads' count and
every function here takes both counts from its arguments' shapes. The decay,
``beta``, the solve and the state are a value head's; ``K K^T`` and ``Q K^T``
of a chunk depend on the key head alone, so ``gated_delta_scan`` makes them
once a key head and repeats the [C, C] products over its r value heads (half
the float32 products of a window at r = 2); the step repeats q and k
themselves (a few KB a row). Never the state, never a weight. At r = 1
nothing is repeated and every program is the one it was.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import state_leaf

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


def _over_value_heads(a, H: int):
    """``a`` [B, S or chunks, key heads, ...] with its key heads repeated to
    ``H`` value heads, head h taking key head h // r; ``a`` itself where the
    counts are equal."""
    r = H // a.shape[2]
    return a if r == 1 else jnp.repeat(a, r, axis=2)


def _own(H: int, dv: int):
    """[H, H x dv] float32: 1 where lane l belongs to head h."""
    return (jnp.arange(H * dv)[None, :] // dv
            == jnp.arange(H)[:, None]).astype(jnp.float32)


def gated_delta_step(q, k, v, g, beta, S0):
    """One token a row. q, k [B,1,Hk,dk] (normalised; Hk divides H); v
    [B,1,H,dv]; g, beta [B,1,H] (both 0 = the row does not move); S0
    [B,dk,H*dv]. Returns (o [B,1,H,dv] float32, S [B,dk,H*dv] STATE_DTYPE)."""
    B, _, H, dv = v.shape
    q, k = _over_value_heads(q, H), _over_value_heads(k, H)
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


#: Bytes of the state the kernel takes a grid step at the most: a lane block
#: is the most heads in whole 128-lane tiles under it (10 of the published
#: 30: [96, 1,920] float32, 737 KB, 3 steps a row; tools/
#: time_state_kernels.py times the other widths).
_STEP_BLOCK_BYTES = 2 ** 20
#: Scoped VMEM the kernel asks for: a block in and out, each double-buffered
#: by the pipeline (4 x 737 KB), and a tile's temporaries; a whole 30-head
#: row (4 x 2.2 MB) passes the 16 MiB default with them.
_STEP_VMEM_BYTES = 32 * 2 ** 20


def _block_heads(H: int, dk: int, dv: int, itemsize: int) -> int:
    """Heads a lane block of the step kernel: the most that divide ``H``,
    fill whole 128-lane tiles and stay under ``_STEP_BLOCK_BYTES``; the
    fewest that fill whole tiles, where none does; all ``H`` (the whole lane
    axis, which any width may be) where no number of heads fills tiles."""
    whole = [n for n in range(1, H + 1) if H % n == 0 and n * dv % 128 == 0]
    small = [n for n in whole if n * dv * dk * itemsize <= _STEP_BLOCK_BYTES]
    return max(small) if small else min(whole, default=H)


def _step_kernel(lyr_ref, order_ref, n_live_ref, kq_ref, vec_ref, s_ref,
                 o_ref, s_out_ref, *, dv: int, channel: bool = False):
    """Grid step (i, c): lane block c (``hb`` heads side by side) of the
    state of row ``order_ref[i]`` in layer ``lyr_ref[0]``, for the
    ``n_live_ref[0]`` rows that move (``order_ref`` names them first); a
    later step's row does not move, its state block is the last moving
    row's last one again (nothing is fetched, nothing written back) and its
    output is zeros. kq_ref [1,1,dk,2*hb]: the block's heads' keys, then
    their queries, a head a column; vec_ref [1,3,W]: v, alpha and beta
    along the lanes; s_ref, s_out_ref [1,1,dk,W] (one buffer: the leaf is
    aliased); o_ref [1,1,W]. The block is taken a GROUP of heads at a time,
    the fewest whose lanes are whole 128-lane tiles (2 of 192 lanes: three
    tiles), in a loop whose body is traced once (a kernel is lowered anew
    for every program of every start: its jaxpr's length is ``setup_s``),
    and a group a tile at a time. ``channel`` (Kimi delta attention): the
    decay is a vector a head, one ``alpha`` a key channel; kq_ref then
    holds it as a third run of columns [1,1,dk,3*hb], it multiplies the
    ROWS of the head's tile before anything reads them, and vec_ref is
    [1,2,W]: v and beta."""
    del lyr_ref, order_ref
    dk, W = s_ref.shape[-2:]
    hb = kq_ref.shape[-1] // (3 if channel else 2)
    G = next((n for n in range(1, hb) if hb % n == 0 and n * dv % 128 == 0),
             hb)
    T = 128 if G * dv % 128 == 0 else G * dv
    n_live = n_live_ref[0]

    @pl.when(state_leaf.passed_over(n_live))
    def _stays():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(state_leaf.none_moves(n_live))
    def _none_moves():      # the one block every step names: as it came
        s_out_ref[...] = s_ref[...]

    def group(p, carry):
        """Heads p*G .. p*G + G - 1 of the block: lanes p*G*dv onward (whole
        tiles in, or p is 0)."""
        kq = kq_ref[0, 0]
        at = jax.lax.broadcasted_iota(jnp.int32, kq.shape, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, T), 1)

        def col(c):
            """[dk, T]: column c of ``kq`` down every lane (the one lane
            kept and summed: exact, and c may be traced)."""
            return jnp.broadcast_to(jnp.sum(
                jnp.where(at == c, kq, 0.0), axis=1, keepdims=True), (dk, T))

        for lo in range(0, G * dv, T):
            heads = range(lo // dv, (lo + T - 1) // dv + 1)

            def down_the_lanes(first):
                """[dk, T]: lane l holds its own head's column of kq."""
                x = col(first + p * G + heads[0])
                for h in heads[1:]:
                    x = jnp.where(lane >= h * dv - lo, col(first + p * G + h),
                                  x)
                return x

            kx, qx = down_the_lanes(0), down_the_lanes(hb)
            lanes = pl.ds(pl.multiple_of(p * (G * dv) + lo, 128), T)
            S = s_ref[0, 0, :, lanes].astype(jnp.float32)
            vec = vec_ref[0, :, lanes]
            if channel:
                # S' = Diag(alpha) S; S = S' + k u^T; o = S^T q
                S = down_the_lanes(2 * hb) * S
                v, beta = vec[0:1], vec[1:2]
                u = beta * (v - jnp.sum(S * kx, axis=0, keepdims=True))
                s_out_ref[0, 0, :, lanes] = (S + kx * u).astype(
                    s_out_ref.dtype)
                o_ref[0, :, lanes] = (
                    jnp.sum(S * qx, axis=0, keepdims=True)
                    + jnp.sum(kx * qx, axis=0, keepdims=True) * u)
                continue
            v, alpha, beta = vec[0:1], vec[1:2], vec[2:3]
            Sk = jnp.sum(S * kx, axis=0, keepdims=True)             # [1, T]
            Sq = jnp.sum(S * qx, axis=0, keepdims=True)
            kdotq = jnp.sum(kx * qx, axis=0, keepdims=True)
            u = beta * (v - alpha * Sk)
            s_out_ref[0, 0, :, lanes] = (alpha * S + kx * u).astype(
                s_out_ref.dtype)
            o_ref[0, :, lanes] = alpha * Sq + kdotq * u
        return carry

    @pl.when(pl.program_id(0) < n_live)
    def _moves():
        jax.lax.fori_loop(0, hb // G, group, None)


def gated_delta_step_kernel(q, k, v, g, beta, state, layer, moves=None,
                            block_heads: int = 0):
    """``gated_delta_step`` on plane ``layer`` (a traced scalar) of the WHOLE
    state leaf ``state`` [layers, B, dk, H*dv], as one Pallas kernel that
    reads each moving row's plane once, updates it in VMEM and writes it
    once, in place: the leaf is aliased input to output and the layer is a
    prefetched scalar of the index maps, so no plane is sliced out in front
    of the call and none written back behind it. The grid is (row, lane
    block of ``block_heads`` heads; 0: ``_block_heads``); a head's ``S^T k``
    and ``S^T q`` are taken on the VPU in float32, the key broadcast down the
    head's own lanes, multiplied and reduced over the sublanes: exact
    float32 products, no ``_own`` mask and no 30-fold product. ``moves`` [B]
    bool: the rows whose token is real; absent, the rows whose ``g`` and
    ``beta`` are not all 0. A row that does not move (padding, a dead slot)
    has its state neither read nor written (the rows that move take the
    grid's first steps, the others' steps name the block before them
    again) and its output is zeros. Other arguments as
    ``gated_delta_step``'s; ``g`` [B,1,H,dk] is a decay a key channel
    (``channel_decay_step``'s rule: it multiplies the rows of a head's tile).
    Returns (o [B,1,H,dv] float32, the leaf). Off the TPU the kernel runs
    interpreted."""
    return _step_call(q, k, v, g, beta, state, layer, moves,
                      block_heads=block_heads,
                      interpret=jax.default_backend() != "tpu")


@partial(jax.jit, static_argnames=("block_heads", "interpret"))
def _step_call(q, k, v, g, beta, state, layer, moves, *, block_heads: int,
               interpret: bool):
    """``gated_delta_step_kernel``, under a jit of its own: a period's three
    linear layers, and every program of a start, then share ONE trace of the
    kernel, and a program lowers it once (a chunk program's lowering is
    ``setup_s``, compile-cache hit or not)."""
    B, _, H, dv = v.shape
    dk = q.shape[-1]
    # (fewer key heads than value heads: a value head's own column of kq)
    q, k = _over_value_heads(q, H), _over_value_heads(k, H)
    L = H * dv
    hb = block_heads or _block_heads(H, dk, dv, state.dtype.itemsize)
    nb, W = H // hb, hb * dv
    f32 = lambda a: a[:, 0].astype(jnp.float32)
    q, k, v, g, beta = f32(q), f32(k), f32(v), f32(g), f32(beta)
    channel = g.ndim == 3               # [B, H, dk]: a decay a key channel
    if moves is None:
        moves = state_leaf.any_gate(
            jnp.any(g != 0, axis=-1) if channel else g != 0, beta != 0)  # [B]
    order, n_live = state_leaf.moving_rows_first(moves)
    lanes = lambda a: jnp.repeat(a, dv, axis=-1)                    # [B, L]
    # a block's heads' keys then queries, each a column: [B, nb, dk, 2*hb]
    cols = lambda a: jnp.swapaxes(a.reshape(B, nb, hb, dk), 2, 3)
    if channel:
        kq = jnp.concatenate([cols(k), cols(q), cols(jnp.exp(g))], axis=-1)
        vec = jnp.stack([v.reshape(B, L), lanes(beta)], axis=1)     # [B, 2, L]
    else:
        kq = jnp.concatenate([cols(k), cols(q)], axis=-1)
        vec = jnp.stack([v.reshape(B, L), lanes(jnp.exp(g)), lanes(beta)],
                        axis=1)                                     # [B, 3, L]
    row = lambda i, c, lyr, order, n_live: (order[i], 0, c)
    o, state = state_leaf.visit(
        partial(_step_kernel, dv=dv, **({"channel": True} if channel
                                         else {})),
        name="gated_delta_step", grid=(B, nb), layer=layer, order=order,
        n_live=n_live, at=state_leaf.step_block(nb),
        in_specs=[pl.BlockSpec((1, 1, dk, kq.shape[-1]),
                               lambda i, c, lyr, order, n_live:
                               (order[i], c, 0, 0)),
                  pl.BlockSpec((1, vec.shape[1], W), row)],
        out_specs=[pl.BlockSpec((1, 1, W), row)],
        out_shape=[jax.ShapeDtypeStruct((B, 1, L), jnp.float32)],
        leaf=state, block=(1, 1, dk, W),
        plane=lambda layer, row, c: (layer, row, 0, c),
        vmem_limit_bytes=_STEP_VMEM_BYTES, interpret=interpret,
    )(kq, vec)
    return o.reshape(B, 1, H, dv), state


#: Rows a diagonal block of ``unit_lower_inverse``: the dependent steps of its
#: substitution, before log2(C / rows) merges on the MXU.
_SOLVE_BLOCK = 16


def unit_lower_inverse(A):
    """``(I + A)^-1`` for a strictly lower triangular ``A`` [..., C, C], float32,
    by blocks. Inside the diagonal blocks of ``_SOLVE_BLOCK`` rows, forward
    substitution row by row: row i of every block of every matrix at once is
    ``e_i - A_i T``, the order of operations of a row-by-row solve (which is why
    it keeps that solve's accuracy), ``_SOLVE_BLOCK`` - 1 dependent steps in a
    loop whose body is traced once. Then the blocks merge two and two, every
    pair of every matrix in one batched product: the inverse of ``[[I + A11,
    0], [A21, I + A22]]`` is ``[[T1, 0], [-T2 A21 T1, T2]]``. NOT by powers,
    ``(I - A)(I + A^2)(I + A^4)...``: that is the same matrix in exact
    arithmetic and reads a relative error of 2e+19 on a chunk of nearly equal
    keys written at ``beta`` 2, where ``A``'s powers grow like binomials times
    2^k and cancel (tests/test_linear_attention.py). A ``C`` that is not a
    block times a power of two is padded to one with identity rows; one below
    a block is one block."""
    C = A.shape[-1]
    b = size = min(_SOLVE_BLOCK, C)
    while size < C:
        size *= 2
    A = jnp.pad(A, ((0, 0),) * (A.ndim - 2) + ((0, size - C),) * 2)

    def diagonal(rows):     # A's diagonal blocks [..., size // rows, rows, rows]
        return jnp.stack([A[..., i:i + rows, i:i + rows]
                          for i in range(0, size, rows)], axis=-3)

    inside, eye = diagonal(b), jnp.eye(b, dtype=A.dtype)

    def row(i, T):          # rows < i of every block of T are done
        at = lambda x: jax.lax.dynamic_index_in_dim(x, i, -2, keepdims=False)
        new = at(eye) - jnp.einsum("...j,...jc->...c", at(inside), T,
                                   precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(T, new, i, -2)

    T = jax.lax.fori_loop(1, b, row, jnp.broadcast_to(eye, inside.shape))
    while b < size:
        T1, T2 = T[..., 0::2, :, :], T[..., 1::2, :, :]
        low = -jnp.einsum("...ij,...jk,...kl->...il", T2,
                          diagonal(2 * b)[..., b:, :b], T1, precision=_HI)
        T = jnp.concatenate([
            jnp.concatenate([T1, jnp.zeros_like(T1)], axis=-1),
            jnp.concatenate([low, T2], axis=-1)], axis=-2)
        b *= 2
    return T[..., 0, :C, :C]


def _unit_lower_solve(A, rhs):
    """``(I + A)^-1 rhs``, for both scans."""
    return jnp.einsum("...ij,...jv->...iv", unit_lower_inverse(A), rhs,
                      precision=_HI)


def gated_delta_scan(q, k, v, g, beta, S0, chunk: int = 0):
    """A window a row, in chunks of ``chunk`` tokens (0: ``CHUNK``). q, k
    [B,S,Hk,dk] (normalised; Hk divides H); v [B,S,H,dv]; g, beta [B,S,H] (0
    on padding); S0 [B,dk,H*dv]. Returns (o [B,S,H,dv] float32, the state
    after the window [B,dk,H*dv] STATE_DTYPE)."""
    B, S, H, dv = v.shape
    dk = q.shape[-1]
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
    # (the two products of keys and queries once a KEY head, repeated over
    # the value heads that read it; then q and k themselves)
    kk = _over_value_heads(
        jnp.einsum("bnhik,bnhjk->bnhij", kc, kc, precision=_HI), H)
    by_key_head = (qc, kc)
    qc, kc = _over_value_heads(qc, H), _over_value_heads(kc, H)
    A = jnp.where(jnp.tril(jnp.ones((C, C), bool), -1),
                  bc[..., None] * kk * decay, 0.0)
    eg = jnp.exp(gamma)[..., None]
    rhs = jnp.concatenate([bc[..., None] * vc, bc[..., None] * eg * kc], -1)
    X = _unit_lower_solve(A, rhs)
    U0, Wm = X[..., :dv], X[..., dv:]           # T (beta V), T (beta e^g K)
    qk = _over_value_heads(jnp.einsum(
        "bnhik,bnhjk->bnhij", *by_key_head, precision=_HI), H) * decay
    to_end = jnp.exp(gamma[..., -1:] - gamma)[..., None] * kc   # [B,n,H,C,dk]
    end = jnp.exp(gamma[..., -1])[..., None, None]              # [B,n,H,1,1]
    return _run_chunks(U0, Wm, qk, eg, qc, to_end, end, S0, S, H, dv)


def _run_chunks(U0, Wm, qk, eg, qc, to_end, end, S0, S: int, H: int,
                dv: int):
    """The chunks one after another from the state ``S0`` [B,dk,H*dv]: what a
    chunk's rows write given the state it starts with (``U0`` less ``Wm``
    times it), what they read (``eg * qc`` from it, ``qk`` from the chunk's own
    writes), and the state it leaves (``end`` times it, a scalar a head
    [B,n,H,1,1] or a decay a key channel [B,n,H,dk,1], and ``to_end`` times
    the writes). Returns ``gated_delta_scan``'s pair for the first ``S``
    rows."""
    B, n, _, C, dk = Wm.shape

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
    o = o.reshape(B, n * C, H, dv)[:, :S]
    S1 = jnp.moveaxis(Sh, 1, 2).reshape(B, dk, H * dv)
    return o, S1.astype(STATE_DTYPE)


def channel_decay_step(q, k, v, g, beta, S0):
    """``gated_delta_step`` with a decay for every key channel (Kimi delta
    attention): g [B,1,H,dk], ``alpha = exp(g)`` multiplies the ROWS of a
    head's state before the token reads and writes it,

        S' = Diag(alpha_t) S_{t-1}      u_t = beta_t (v_t - S'^T k_t)
        S_t = S' + k_t u_t^T            o_t = S_t^T q_t

    (with a head's channels all equal it is ``gated_delta_step``). Plain
    ``jnp`` on one plane; ``gated_delta_step_kernel`` takes the same ``g`` on
    the whole leaf."""
    B, _, H, dk = q.shape
    dv = v.shape[-1]
    f32 = lambda a: a[:, 0].astype(jnp.float32)
    q, k, v, g, beta = f32(q), f32(k), f32(v), f32(g), f32(beta)
    S = S0.astype(jnp.float32).reshape(B, dk, H, dv)
    S = jnp.swapaxes(jnp.exp(g), 1, 2)[..., None] * S
    u = beta[..., None] * (v - jnp.einsum("bkhv,bhk->bhv", S, k,
                                          precision=_HI))
    S = (S + jnp.einsum("bhk,bhv->bkhv", k, u, precision=_HI)
         ).astype(STATE_DTYPE)
    o = jnp.einsum("bkhv,bhk->bhv", S.astype(jnp.float32), q, precision=_HI)
    return o[:, None], S.reshape(B, dk, H * dv)


#: Rows of a window ``channel_decay_scan`` takes at once; more run in groups
#: of this many, one group after another.
_ROWS_AT_ONCE = 4


def channel_decay_scan(q, k, v, g, beta, S0, chunk: int = 0):
    """``gated_delta_scan`` with a decay for every key channel
    (``channel_decay_step``'s rule): g [B,S,H,dk] <= 0, bounded below so that
    ``_SOLVE_BLOCK`` rows' decays sum to no less than -88 (``ModelConfig.
    lin_decay_floor``: 16 x -5 = -80). The chunked form is the scalar one
    with the decay INSIDE each product of a row i with a row j <= i,

        A_ij = beta_i sum_c k_ic k_jc exp(gamma_ic - gamma_jc)

    which does not factor over a chunk (``exp(-gamma_j)`` overflows: 64 rows
    at the floor are -320) and would be a [C, C, d_k] tensor unfactored. It
    factors over a BLOCK of 16 columns against the block's last row: for
    column block J, row i >= J's first brings ``k_i exp(gamma_i - gamma_J)``
    (at most e^75 for J's own rows, at most 1 for later ones; earlier rows
    are above the diagonal and left out) and column j ``k_j exp(gamma_J -
    gamma_j)`` (at most 1): one product [rows, d_k] x [d_k, 16] a block, on
    the MXU, its operands a chunk's rows at the most (all four blocks' at
    once were 1.6 GiB of a 16 x 512 window's temporaries: AOT, PR 48). Everything else is ``gated_delta_scan``'s: the unit-triangular
    inverse by blocks, the chunks one after another (``_run_chunks``)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    if S == 1:
        return channel_decay_step(q, k, v, g, beta, S0)
    if B > _ROWS_AT_ONCE:
        # (a mixed window's 16 rows of 512 columns: a dozen float32
        # [B,n,H,C,dk] temporaries at once were 1.6 GiB beside the weights)
        row = lambda xs: tuple(a[0] for a in channel_decay_scan(
            *(x[None] for x in xs), chunk))
        return jax.lax.map(row, (q, k, v, g, beta, S0),
                           batch_size=_ROWS_AT_ONCE)
    b = min(_SOLVE_BLOCK, S, chunk or CHUNK)
    C = min(chunk or CHUNK, -(-S // b) * b)
    if C % b:
        raise ValueError(f"chunk {C} is not whole blocks of {b} rows")
    pad, nb = -S % C, C // b
    n = (S + pad) // C

    def chunks(a):      # [B, S, H, ...] -> [B, n, H, C, ...]
        a = jnp.pad(a.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((B, n, C) + a.shape[2:]), 3, 2)

    qc, kc, vc, gc, bc = (chunks(a) for a in (q, k, v, g, beta))
    gamma = jnp.cumsum(gc, axis=-2)                         # [B,n,H,C,dk] <= 0
    kk, qk = [], []
    for J in range(nb):
        # column block J against the rows at or below it (the rows above
        # are above the diagonal: zeros), both sides relative to J's last row
        edge = gamma[..., J * b + b - 1, None, :]           # [B,n,H,1,dk]
        cols = slice(J * b, J * b + b)
        right = kc[..., cols, :] * jnp.exp(edge - gamma[..., cols, :])
        left = jnp.exp(gamma[..., J * b:, :] - edge)        # [B,n,H,C-Jb,dk]
        for a, out in ((kc, kk), (qc, qk)):
            out.append(jnp.pad(
                jnp.einsum("bnhic,bnhjc->bnhij", a[..., J * b:, :] * left,
                           right, precision=_HI),
                ((0, 0),) * 3 + ((J * b, 0), (0, 0))))
    tril = jnp.tril(jnp.ones((C, C), bool))
    A = jnp.where(jnp.tril(tril, -1),
                  bc[..., None] * jnp.concatenate(kk, axis=-1), 0.0)
    eg = jnp.exp(gamma)
    rhs = jnp.concatenate([bc[..., None] * vc, bc[..., None] * eg * kc], -1)
    X = _unit_lower_solve(A, rhs)
    qk = jnp.where(tril, jnp.concatenate(qk, axis=-1), 0.0)
    to_end = jnp.exp(gamma[..., -1:, :] - gamma) * kc       # [B,n,H,C,dk]
    end = jnp.exp(gamma[..., -1, :])[..., None]             # [B,n,H,dk,1]
    return _run_chunks(X[..., :dv], X[..., dv:], qk, eg, qc, to_end, end,
                       S0, S, H, dv)


def gated_head_norm(o, z, w, eps: float, gate=jax.nn.silu):
    """RMSNorm over each head's ``d_v`` values of ``o`` [..., H, dv], times
    the gain ``w`` [dv], THEN times ``gate(z)`` (norm first, the gate
    after: the reverse of Mamba-2's ``gated_group_norm``; ``silu`` is the
    gated delta rule's, ``sigmoid`` Kimi delta attention's). float32."""
    o = o.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * w.astype(jnp.float32) * gate(z.astype(jnp.float32))
