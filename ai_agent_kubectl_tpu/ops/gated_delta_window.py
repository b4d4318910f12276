"""A WINDOW of the gated delta rule as one Pallas kernel on the state leaf.

ops/gated_delta.py has the recurrence, its chunked form (``gated_delta_scan``:
plain ``jax.numpy``, kept as what the tests hold this kernel to) and the
decode step's kernel; this module is the window's (S > 1: an eager piece of
one sequence, a chunk program's prologue over every slot; ISSUE 56), after
ops/ssd_scan.py::ssd_window. It lives apart from them because a compile
cache's key holds a kernel's source LINES: nothing of ops/gated_delta.py
moves for it.

``gated_delta_window`` takes the WHOLE leaf ``[layers, B, d_k, H x d_v]``,
aliased input to output, and the layer as a prefetched scalar of its index
maps (a Python int in an unrolled pattern, a traced ordinal inside a scanned
period): no plane is sliced out in front of it and none set back behind it.
Its grid is (row, block of heads, chunk of ``CHUNK`` tokens), the chunks
innermost: a block of a row's state is fetched once, stays in VMEM across
the row's chunks and is written once; the rows that brought tokens take the
grid's first steps, a row that brought none has its state neither read nor
written, and a chunk wholly past a row's ``q_len`` is passed over
(ops/state_leaf.py has that frame; this module the body and its operands).
In ``jnp`` every slot's every column was scanned, padding
too, every chunk's ``[64, 64]`` Gram products, decays and ``T`` went through
HBM as ``[B, n, H, 64, 64]`` float32 arrays, and the chunks' outputs were
stacked and turned over behind a ``lax.scan``.

A grid step is one chunk of one block of heads, in three passes over the
block, each a loop whose body is traced once (a kernel is lowered anew for
every program of every start: its jaxpr's length is ``setup_s``). TWO heads
go side by side along the lanes throughout (lane (m, j): head m of the pair,
token j of the chunk; [64, 128] where a head alone would fill half a vector
and a quarter of the MXU):

1. A pair's ``K K^T`` and ``Q K^T`` in ONE product (a key head's keys over
   its queries against the pair's keys; once a KEY head where its r value
   heads are the pair), the decays ``exp(gamma_i - gamma_j)``, ``A`` (``beta_i
   k_i.k_j exp(gamma_i - gamma_j)`` under the diagonal) and the decayed ``Q
   K^T`` into VMEM, and the diagonal blocks of ``A^T`` (made from the same
   symmetric ``K K^T``: nothing is turned over), ``_SOLVE_BLOCK`` rows each,
   where pass 2 reads them.
2. ``T = (I + A)^-1`` inside those blocks by forward substitution, row by
   row, EVERY block of every head of the block at once (15 dependent steps
   are 15 for the whole block, not for each head): ``unit_lower_inverse``'s
   order of operations, which is why it keeps that accuracy (NOT the product
   of powers: tests/test_linear_attention.py). Row m is ``e_m - sum_j A[m, j]
   T[j]``: the rows of ``T`` lie in vectors of their own ([16, pairs, 128]:
   row j of every block of every pair), so the sum runs from vector to
   vector on the VPU, and ``A[m, j]`` spread over its block's 16 lanes is
   made for all m BEFORE the dependent steps (a lane of ``A^T``'s block
   added to the lanes at its left by four rotations; what spills over a
   block's edge meets zeros of ``T`` or of ``A``), so those steps hold no
   rotation and no reduction across lanes.
3. A pair at a time: the blocks merge two and two on the MXU (``[[T1, 0],
   [-T2 A21 T1, T2]]``, 16 -> 32 -> 64, both heads in one product against a
   block-diagonal operand), then with ``S`` the state the chunk starts with

       U   = T (beta * (V - exp(gamma) * K S))
       O   = exp(gamma) * Q S + tril(Q K^T * exp(gamma_i - gamma_j)) U
       S'  = exp(gamma_C) S + (exp(gamma_C - gamma) * K)^T U

   (``gated_delta_scan``'s ``U0 - Wm S`` with ``T`` taken out of the bracket:
   one product with ``T`` a chunk, not two). ``K S`` and ``Q S`` are one
   product a KEY head against its value heads' lanes; ``T`` and the decayed
   ``Q K^T`` are applied to the pair's two heads one over the other in one
   product each. The outputs go straight to the window's rows [B, S, H x
   d_v].

A row that brought ONE token (a live decode row riding a chunk program's
prologue) is the step, not a chunk of one: ``S' = e^g S + k (beta (v - e^g
S^T k))^T``, ``o = S'^T q``, two small products a pair of heads.

Everything is float32 and every product at the highest precision; the state
is rounded to the leaf's dtype between chunks, as the scan rounds it to
``STATE_DTYPE``. A head whose ``d_v`` is no whole number of lane tiles (192:
a tile and a half) shares its pair's lanes (384: three tiles): the products
with ``T``, the decayed ``Q K^T`` and the keys run over the pair's lanes,
each head's rows zero outside its own.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import gated_delta, state_leaf
from .gated_delta import _HI, _SOLVE_BLOCK, _block_heads, gated_delta_scan

#: Scoped VMEM the kernel asks for: a block of the state in and out (1 MiB
#: each at 16 heads of 128 x 128), a chunk's keys, queries, values in and
#: outputs out, each double-buffered by the pipeline; 2 MiB of scratch (the
#: substitution's 15 spread rows, four [64, 128] tiles a pair) and a pair's
#: temporaries.
_WINDOW_VMEM_BYTES = 48 * 2 ** 20


def window_chunk(S: int) -> int:
    """Tokens a chunk the kernel walks a window of ``S`` in: ``CHUNK``, or for
    a narrower window the least ``_SOLVE_BLOCK`` x 2^n that holds it; 0 where
    ``CHUNK`` is no whole block (tools/refcheck_power.py patches it to 1 to
    round a bf16 state at every token: the plain scan's to do)."""
    C = _SOLVE_BLOCK
    while C < min(gated_delta.CHUNK, S):
        C *= 2
    return C if C <= gated_delta.CHUNK else 0


def _unit_heads(hb: int, r: int, dv: int) -> int:
    """Value heads the kernel's loops take at once out of a block of ``hb``:
    the fewest that are whole key heads' (``r`` each), whole lane tiles and
    whole pairs (two heads share the substitution's 128 lanes); 0 where the
    block has no such number."""
    tiles = next((n for n in range(1, hb + 1) if n * dv % 128 == 0), hb)
    u = math.lcm(tiles, r, 2)
    return u if hb % u == 0 else 0


def window_counts(q_lens, S: int):
    """int32 [4], what ``gated_delta_window`` does with a window of ``S``
    whose rows brought ``q_lens`` [B] tokens: the rows whose state it updates,
    the rows it passes over (they brought none), the chunks past a moving
    row's ``q_len`` that it passes over, and of the rows it updates those
    that brought one token and took the step (``KVCache.lin_window``)."""
    C = window_chunk(S) or min(gated_delta.CHUNK, S)
    moved = jnp.sum(q_lens > 0, dtype=jnp.int32)
    skipped = jnp.where(q_lens > 0, -(-S // C) - (q_lens + C - 1) // C, 0)
    return jnp.stack([moved, q_lens.shape[0] - moved,
                      jnp.sum(skipped, dtype=jnp.int32),
                      jnp.sum(q_lens == 1, dtype=jnp.int32)])


def _window_kernel(lyr_ref, order_ref, n_live_ref, lens_ref, q_ref, k_ref,
                   v_ref, pairs_ref, cols_ref, s_ref, o_ref, s_out_ref,
                   src_ref, m_ref, t_ref, a_ref, qk_ref, cum_ref, beta_ref,
                   *, r: int, u: int, dv: int):
    """Grid step (i, c, n): chunk n of heads c*hb .. c*hb + hb - 1 of row
    ``order_ref[i]``'s window, from and to its state in layer ``lyr_ref[0]``,
    for the ``n_live_ref[0]`` rows that brought tokens (``order_ref`` names
    them first) and the chunks that hold a row's ``lens_ref[row]`` tokens;
    every other step names the blocks of the step before it again (nothing is
    fetched, nothing written back) and does nothing. A row that brought ONE
    token (a live decode row riding a prologue) takes the step's arithmetic
    (``rides``), not a chunk's. The state block
    s_out_ref [1,1,dk,hb*dv] (one buffer with s_ref: the leaf is aliased) is
    filled from s_ref at a row's first chunk, stays in VMEM across its chunks
    and is written back once. q_ref, k_ref [1,hb/r,C,dk]: the block's KEY
    heads' queries and keys; v_ref, o_ref [1,C,hb*dv]: the chunk's values and
    outputs, a head dv lanes, as the mixer has them. The chunk's running sum
    of ``g`` (<= 0) then ``beta``, a head each, twice over: pairs_ref
    [1,1,H,2C], a token a lane, two heads a row; cols_ref [1,C,2H], a token a
    sublane. Scratch, a PAIR of heads side by side along the lanes throughout
    (lane (m, j): head m of the pair, token j of the chunk): src_ref, t_ref
    [16*P,128] and m_ref [15,16*P,128], the substitution's (row j*P + pair);
    a_ref, qk_ref, cum_ref, beta_ref [hb/2,C,2C]: ``A``, the decayed ``Q
    K^T``, and the running sum and ``beta`` down the sublanes. ``u`` value
    heads a loop step (``_unit_heads``)."""
    del lyr_ref
    C, dk = q_ref.shape[2:]
    H = cols_ref.shape[2] // 2
    hb = v_ref.shape[2] // dv
    b = min(_SOLVE_BLOCK, C)
    P = t_ref.shape[0] // b                 # sublanes a row of T's blocks
    aligned = dv % 128 == 0                 # a head's lanes: whole tiles
    i, n = pl.program_id(0), pl.program_id(2)
    n_live = n_live_ref[0]
    first = pl.program_id(1) * hb
    f32 = lambda a: a.astype(jnp.float32)
    dot = partial(jax.lax.dot_general, precision=_HI,
                  preferred_element_type=jnp.float32)
    nn = lambda x, y: dot(x, y, (((1,), (0,)), ((), ())))      # x y
    nt = lambda x, y: dot(x, y, (((1,), (1,)), ((), ())))      # x y^T
    tn = lambda x, y: dot(x, y, (((0,), (0,)), ((), ())))      # x^T y
    iota = lambda shape, d: jax.lax.broadcasted_iota(jnp.int32, shape, d)

    @pl.when(state_leaf.fetched(i, n, n_live))
    def _fetched():     # (where no row moves: the one block every step names)
        s_out_ref[...] = s_ref[...]

    def column(at):
        """[C, 1]: column ``at`` (traced) of cols_ref (the one lane kept and
        summed: exact)."""
        x = cols_ref[0]
        return jnp.sum(jnp.where(iota(x.shape, 1) == at, x, 0.0), axis=1,
                       keepdims=True)

    def key_head(t, w):
        """In-block index of the key head that value head ``w`` of unit ``t``
        reads."""
        return t * (u // r) + w // r

    def keys_over_queries(t, ws):
        """[2C, dk] a KEY head of the pair ``ws`` of unit ``t``: its keys over
        its queries; one where the pair's two value heads read one key head."""
        return [jnp.concatenate([f32(k_ref[0, key_head(t, w)]),
                                 f32(q_ref[0, key_head(t, w)])], axis=0)
                for w in (ws if ws[0] // r != ws[1] // r else ws[:1])]

    def lanes_of(t):
        """The lanes of unit ``t``'s heads in the block (whole tiles, or the
        block is one unit)."""
        W = u * dv
        return pl.ds(pl.multiple_of(t * W, 128) if W % 128 == 0 else 0, W)

    def moves():
        # (every mask of the three passes, once a grid step)
        row, col = iota((C, 2 * C), 0), iota((C, 2 * C), 1)
        tok = col & (C - 1)                 # the token a lane is
        left = col < C                      # ... of the pair's first head
        seg = (iota((b, 2 * C), 1) & (C - 1)) // b
        under, upto, over = row > tok, row >= tok, tok > row
        inside = row // b == tok // b
        below = []                          # a merge's A21 blocks
        size = b
        while size < C:
            below.append(((tok // size) % 2 == 0)
                         & (row // size == tok // size + 1))
            size *= 2
        halves = (iota((2 * C, 2 * C), 0) < C) == (iota((2 * C, 2 * C), 1) < C)
        two = lambda x: jnp.where(halves, jnp.concatenate([x, x], axis=0), 0.0)

        def pass_1(t, carry):
            """Unit ``t``: a pair's ``K K^T`` and ``Q K^T`` in one product,
            its decays, ``A`` and the decayed ``Q K^T`` into VMEM, and the
            diagonal blocks of ``A^T`` (from the same symmetric ``K K^T``:
            nothing is turned over) where the substitution reads them."""
            for p in range(u // 2):
                ws = (2 * p, 2 * p + 1)
                heads = [first + t * u + w for w in ws]
                kq = keys_over_queries(t, ws)
                both = jnp.concatenate([x[:C] for x in (kq * 2)[:2]], axis=0)
                kkqk = nt(kq[0], both)                              # [2C,2C]
                if len(kq) == 2:                    # two key heads a pair
                    kkqk = jnp.where(iota(kkqk.shape, 1) < C, kkqk,
                                     nt(kq[1], both))
                kk, qk = kkqk[:C], kkqk[C:]
                pair = (first + t * u) // 2 + p
                slot = t * (u // 2) + p
                cum_r = pairs_ref[0, 0, pl.ds(pair, 1)]             # [1,2C]
                beta_r = pairs_ref[0, 0, pl.ds(H // 2 + pair, 1)]
                cum = jnp.where(left, column(heads[0]), column(heads[1]))
                beta = jnp.where(left, column(H + heads[0]),
                                 column(H + heads[1]))
                fall = jnp.exp(jnp.minimum(cum - cum_r, 0.0))
                a_ref[slot] = jnp.where(under, beta * kk * fall, 0.0)
                qk_ref[slot] = jnp.where(upto, qk * fall, 0.0)
                cum_ref[slot], beta_ref[slot] = cum, beta
                At = jnp.where(over, beta_r * kk * jnp.exp(
                    jnp.minimum(cum_r - cum, 0.0)), 0.0)
                blocks = At[:b]
                for j in range(1, C // b):
                    blocks = jnp.where(seg == j, At[j * b:(j + 1) * b], blocks)
                src_ref[pl.ds(slot, b, stride=P), pl.ds(0, 2 * C)] = blocks
            return carry

        def pass_2():
            """``T`` inside every diagonal block of every head of the block
            at once: t_ref[j*P + pair, 16 x block + c] = T_block[j, c]. Row m
            is ``e_m - sum_j A[m, j] T[j]``; ``A[m, j]`` over a block's 16
            lanes (m_ref[m - 1]) is lane m of the block's ``A^T`` spread to
            the lanes at its left, the rows' products are summed from vector
            to vector, and the 15 dependent steps hold no rotation."""
            lane = iota(src_ref.shape, 1) & (b - 1)

            def spread(m, carry):
                x = jnp.where(lane == m, src_ref[...], 0.0)
                for s in (1, 2, 4, 8):
                    x = x + pltpu.roll(x, 128 - s, 1)
                m_ref[m - 1] = x
                return carry

            jax.lax.fori_loop(1, b, spread, 0)
            t_ref[...] = (iota(t_ref.shape, 0) // P == lane).astype(jnp.float32)
            one = iota((P, 128), 1) & (b - 1)

            def solve(m, carry):
                x = (m_ref[m - 1] * t_ref[...]).reshape(b, P, 128)
                t_ref[pl.ds(pl.multiple_of(m * P, P), P), :] = (
                    (one == m).astype(jnp.float32) - jnp.sum(x, axis=0))
                return carry

            jax.lax.fori_loop(1, b, solve, 0)

        def pass_3(t, carry):
            """Unit ``t``: its pairs' ``T`` merged, their outputs and
            state."""
            slab = lanes_of(t)
            S0, V = f32(s_out_ref[0, 0, :, slab]), f32(v_ref[0, :, slab])
            outs, news = [], []
            for p in range(u // 2):
                ws = (2 * p, 2 * p + 1)
                slot = t * (u // 2) + p
                pair = (first + t * u) // 2 + p
                A, cum, beta = a_ref[slot], cum_ref[slot], beta_ref[slot]
                T = jnp.where(inside, jnp.concatenate(
                    [t_ref[pl.ds(slot, b, stride=P), pl.ds(0, 2 * C)]]
                    * (C // b), axis=0), 0.0)
                for A21 in below:       # [[T1, 0], [-T2 A21 T1, T2]]
                    T = T - nn(nn(T, two(jnp.where(A21, A, 0.0))), two(T))
                mine = slice(2 * p * dv, 2 * (p + 1) * dv)
                lane = iota((1, 2 * dv), 1) < dv        # the first head's
                of = lambda x: jnp.where(lane, x[:, 0:1], x[:, C:C + 1])
                kq = keys_over_queries(t, ws)
                from_state = nn(kq[0], S0[:, mine])     # K S over Q S
                if len(kq) == 2:
                    from_state = jnp.where(lane, from_state,
                                           nn(kq[1], S0[:, mine]))
                grow = jnp.exp(cum)
                rhs = of(beta) * (V[:, mine] - of(grow) * from_state[:C])
                cum_r = pairs_ref[0, 0, pl.ds(pair, 1)]
                ends = [cum_r[:, (m + 1) * C - 1:(m + 1) * C] for m in (0, 1)]
                to_end = [jnp.exp(ends[m] - cum[:, m * C:m * C + 1])
                          * kq[m * (len(kq) - 1)][:C] for m in (0, 1)]
                end = jnp.exp(jnp.where(
                    lane, jnp.broadcast_to(ends[0], (1, 2 * dv)),
                    jnp.broadcast_to(ends[1], (1, 2 * dv))))
                if aligned:
                    # the two heads one over the other: [2C, dv]
                    U = nn(two(T), jnp.concatenate([rhs[:, :dv], rhs[:, dv:]],
                                                   axis=0))
                    O = nn(two(qk_ref[slot]), U)
                    o = jnp.concatenate([O[:C], O[C:]], axis=1)
                    s = jnp.concatenate([tn(to_end[0], U[:C]),
                                         tn(to_end[1], U[C:])], axis=1)
                else:
                    # ... each in its own lanes of the pair's: [2C, 2 dv]
                    U = nn(two(T), jnp.concatenate(
                        [jnp.where(lane, rhs, 0.0), jnp.where(lane, 0.0, rhs)],
                        axis=0))
                    O = nn(two(qk_ref[slot]), U)
                    o = O[:C] + O[C:]
                    s = tn(jnp.concatenate(to_end, axis=0), U)
                outs.append(of(grow) * from_state[C:] + o)
                news.append(end * S0[:, mine] + s)
            if aligned:
                for p in range(u // 2):
                    lanes = pl.ds(pl.multiple_of(t * u * dv + 2 * p * dv, 128),
                                  2 * dv)
                    o_ref[0, :, lanes] = outs[p]
                    s_out_ref[0, 0, :, lanes] = news[p].astype(s_out_ref.dtype)
            else:
                o_ref[0, :, slab] = jnp.concatenate(outs, axis=1)
                s_out_ref[0, 0, :, slab] = jnp.concatenate(
                    news, axis=1).astype(s_out_ref.dtype)
            return carry

        if 2 * C < 128:         # (lanes no pair fills are spread from too)
            src_ref[...] = jnp.zeros_like(src_ref)
        jax.lax.fori_loop(0, hb // u, pass_1, 0)
        pass_2()
        jax.lax.fori_loop(0, hb // u, pass_3, 0)

    def rides():
        """The row's one token: ``S' = e^g S + k (beta (v - e^g S^T k))^T``,
        ``o = S'^T q = e^g S^T q + (k . q) u`` (``gated_delta_step``'s), a pair
        of heads at a time; ``S^T k`` and ``S^T q`` are one product of eight
        rows of keys over eight of queries (the token's is the first of
        each), ``k u^T`` one of eight rows with the others zeroed."""
        R = 8
        top = iota((R, 1), 0) == 0

        def pairs_of(t, carry):
            slab = lanes_of(t)
            S0 = f32(s_out_ref[0, 0, :, slab])
            v0 = f32(v_ref[0, pl.ds(0, 2 * R), slab])[0:1]
            outs, news = [], []
            for p in range(u // 2):
                ws = (2 * p, 2 * p + 1)
                pair = (first + t * u) // 2 + p
                mine = slice(2 * p * dv, 2 * (p + 1) * dv)
                lane = iota((1, 2 * dv), 1) < dv        # the first head's
                of = lambda x: jnp.where(
                    lane, jnp.broadcast_to(x[:, 0:1], (1, 2 * dv)),
                    jnp.broadcast_to(x[:, C:C + 1], (1, 2 * dv)))
                alpha = jnp.exp(of(pairs_ref[0, 0, pl.ds(pair, 1)]))
                beta = of(pairs_ref[0, 0, pl.ds(H // 2 + pair, 1)])
                apart = ws[0] // r != ws[1] // r        # two key heads a pair
                ks = [f32(k_ref[0, key_head(t, w), pl.ds(0, R)])
                      for w in (ws if apart else ws[:1])]
                qs = [f32(q_ref[0, key_head(t, w), pl.ds(0, R)])
                      for w in (ws if apart else ws[:1])]
                read = [nn(jnp.concatenate([k, q], axis=0), S0[:, mine])
                        for k, q in zip(ks, qs)]        # S^T k over S^T q
                dots = [jnp.sum(k[0:1] * q[0:1], axis=1, keepdims=True)
                        for k, q in zip(ks, qs)]        # k . q  [1,1]
                both = lambda xs: (jnp.where(lane, xs[0], xs[1]) if apart
                                   else xs[0])
                Sk = both([x[0:1] for x in read])
                Sq = both([x[R:R + 1] for x in read])
                kdotq = both([jnp.broadcast_to(d, (1, 2 * dv)) for d in dots])
                uu = beta * (v0[:, mine] - alpha * Sk)              # [1,2dv]
                wrote = [jnp.where(lane, uu, 0.0), jnp.where(lane, 0.0, uu)
                         ] if apart else [uu]
                new = alpha * S0[:, mine]
                for k, x in zip(ks, wrote):
                    new = new + tn(jnp.where(top, k, 0.0),
                                   jnp.broadcast_to(x, (R, 2 * dv)))
                outs.append(jnp.where(top, jnp.broadcast_to(
                    alpha * Sq + kdotq * uu, (R, 2 * dv)), 0.0))
                news.append(new)
            o_ref[0, pl.ds(0, R), slab] = jnp.concatenate(outs, axis=1)
            s_out_ref[0, 0, :, slab] = jnp.concatenate(
                news, axis=1).astype(s_out_ref.dtype)
            return carry

        jax.lax.fori_loop(0, hb // u, pairs_of, 0)

    mine = lens_ref[order_ref[i]]
    pl.when((i < n_live) & (mine > 1) & (n * C < mine))(moves)
    pl.when((i < n_live) & (mine == 1) & (n == 0))(rides)


def gated_delta_window(q, k, v, g, beta, state, layer, q_lens=None):
    """``gated_delta_scan`` from and to plane ``layer`` (a Python int or a
    traced scalar) of the WHOLE state leaf ``state`` [layers, B, dk, H*dv],
    as one Pallas kernel: the leaf is aliased input to output and the layer
    a prefetched scalar of the index maps, so no plane is sliced out in
    front of the call and none set back behind it. The grid is (row, block
    of ``_block_heads`` heads, chunk of ``window_chunk`` tokens), the chunks
    innermost: a block of a row's state is read once, stays in VMEM across
    the row's chunks and is written once; a chunk's [C, C] products, decays,
    ``A`` and ``T`` are made in VMEM and its outputs written straight to the
    window's rows, so nothing is stacked behind a loop. ``q_lens`` [B]: a
    row's real tokens, a prefix of its columns (absent: up to its last ``g``
    or ``beta`` that is not 0); ``g`` and ``beta`` are 0 past them. A row
    that brought none has its state neither read nor written (the rows that
    brought some take the grid's first steps) and a chunk wholly past a
    row's ``q_len`` is passed over; outputs past ``q_len`` are zeros. Other
    arguments as ``gated_delta_scan``'s. Returns (o [B,S,H,dv] float32, the
    leaf). Where the heads make no whole units (``_unit_heads``) or ``CHUNK``
    no whole block, the plain scan runs from and to the plane. Off the TPU
    the kernel runs interpreted."""
    B, S, H, dv = v.shape
    hb = _block_heads(H, q.shape[-1], dv, state.dtype.itemsize)
    chunk = window_chunk(S)
    if not (chunk and _unit_heads(hb, H // q.shape[2], dv)):
        o, plane = gated_delta_scan(
            q, k, v, g, beta,
            jax.lax.dynamic_index_in_dim(state, layer, 0, False))
        return o, jax.lax.dynamic_update_index_in_dim(state, plane, layer, 0)
    return _window_call(q, k, v, g, beta, state, layer, q_lens, chunk=chunk,
                        interpret=jax.default_backend() != "tpu")


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _window_call(q, k, v, g, beta, state, layer, q_lens, *, chunk: int,
                 interpret: bool):
    """``gated_delta_window``, under a jit of its own (as the step kernel's
    ``_step_call``: one trace of the kernel a start, one lowering a
    program)."""
    B, S, H, dv = v.shape
    Hk, dk = q.shape[2:]
    r, C = H // Hk, chunk
    pad = -S % C
    n_chunks = (S + pad) // C
    hb = _block_heads(H, dk, dv, state.dtype.itemsize)
    u = _unit_heads(hb, r, dv)
    nb = H // hb
    f32 = lambda a: a.astype(jnp.float32)
    g, beta = f32(g), f32(beta)
    if q_lens is None:
        q_lens = state_leaf.tokens_brought(g != 0, beta != 0)
    q_lens = q_lens.astype(jnp.int32)
    order, n_live = state_leaf.moving_rows_first(q_lens > 0)
    rows = partial(state_leaf.whole_chunks, pad=pad)
    # the key heads apart, a head's chunk [C, dk] a tile of its own
    qh, kh = (jnp.swapaxes(rows(f32(a)), 1, 2) for a in (q, k))
    # a chunk's running sum of g, then beta, a head each: a token a sublane
    # [B, S, 2H], and a token a lane, two heads a row [B, n, H, 2C]
    gb = jnp.concatenate([
        jnp.cumsum(rows(g).reshape(B, n_chunks, C, H), axis=2),
        rows(beta).reshape(B, n_chunks, C, H)], axis=-1)
    b = min(_SOLVE_BLOCK, C)
    P = -(-(hb // 2) // 8) * 8      # pairs a block, in whole sublane tiles
    at = state_leaf.window_block(nb, lambda q_lens, row:
                                 (q_lens[row] + C - 1) // C)

    def key_heads(i, c, n, *s):     # [B, Hk, S, dk]
        row, c, n = at(i, c, n, *s)
        return row, c, n, 0

    def tokens(i, c, n, *s):        # [B, S, heads' lanes]
        row, c, n = at(i, c, n, *s)
        return row, n, c

    def by_chunk(i, c, n, *s):      # [B, n, .., ..], every head
        row, _, n = at(i, c, n, *s)
        return row, n, 0, 0

    def by_token(i, c, n, *s):      # [B, S, ..], every head
        row, _, n = at(i, c, n, *s)
        return row, n, 0

    o, state = state_leaf.visit(
        partial(_window_kernel, r=r, u=u, dv=dv), name="gated_delta_window",
        grid=(B, nb, n_chunks), layer=layer, order=order, n_live=n_live,
        extra=q_lens, at=at,
        in_specs=[pl.BlockSpec((1, hb // r, C, dk), key_heads),
                  pl.BlockSpec((1, hb // r, C, dk), key_heads),
                  pl.BlockSpec((1, C, hb * dv), tokens),
                  pl.BlockSpec((1, 1, H, 2 * C), by_chunk),
                  pl.BlockSpec((1, C, 2 * H), by_token)],
        out_specs=[pl.BlockSpec((1, C, hb * dv), tokens)],
        out_shape=[jax.ShapeDtypeStruct((B, S + pad, H * dv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((b * P, 128), jnp.float32),
                        pltpu.VMEM((b - 1, b * P, 128), jnp.float32),
                        pltpu.VMEM((b * P, 128), jnp.float32)]
        + [pltpu.VMEM((hb // 2, C, 2 * C), jnp.float32)] * 4,
        leaf=state, block=(1, 1, dk, hb * dv),
        plane=lambda layer, row, c: (layer, row, 0, c),
        vmem_limit_bytes=_WINDOW_VMEM_BYTES, interpret=interpret,
    )(qh, kh, rows(v).reshape(B, S + pad, H * dv),
      jnp.swapaxes(gb, 2, 3).reshape(B, n_chunks, H, 2 * C),
      gb.reshape(B, S + pad, 2 * H))
    return state_leaf.window_rows(o, q_lens, S).reshape(B, S, H, dv), state
