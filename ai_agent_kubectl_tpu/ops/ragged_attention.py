"""Pallas ragged paged attention (ISSUE 19; PAPERS.md ragged paged
attention — exactly this kernel, on TPU).

ONE kernel for every attention shape the serving loop runs over the
block pool: per-slot QUERY length ``q_lens[n]`` is 1 for a decode step,
k+1 for a speculative verify window, and a prompt-span for (suffix)
prefill — so a mixed chunk (fresh admissions + decoding slots + spec
verify) is a single program dispatch instead of separately compiled worlds
(the ``(bucket, kv_limit)`` dense prefill ladder and the dense gather
fallback).

Shape contract:

- ``q``            [N, W, H, hd] — per-slot query windows padded to W;
  slot n's valid queries are columns ``0 .. q_lens[n]-1``, the first at
  absolute position ``positions[n]`` (so column j sits at
  ``positions[n] + j``).
- ``k``/``v``      [n_blocks, page, KV, hd] — one layer of the shared
  block pool — or the whole stacked pool [L, n_blocks, page, KV, hd]
  with ``layer`` (a traced scalar) picking the layer. The layer index is
  scalar-prefetched, so the kernel copies pages of that layer straight
  out of the stacked buffer: a ``pool[layer]`` slice in front of the call
  would copy the layer every pass (ISSUE 25).
- ``q_lens``       [N] int32 — 0 freezes a slot (output rows are zeros,
  compute masked); 1 = decode; k+1 = verify; span = prefill.
- ``positions``    [N] int32 — absolute position of query column 0.
- ``block_tables`` [N, max_pages] int32 — pool block per sequence page;
  entries >= n_blocks are the unmapped-page sentinel.

TPU-first design: grid ``(slot, query tile, page block)``, the page axis
``cdiv(max_pages, P)`` blocks of ``P`` pages (``pages_per_step``: as many
as cover 512 KV positions, fewer where the query tile's scores leave no
VMEM for them). Positions, query lengths, tables and the layer index are
scalar-prefetched; K and V stay in HBM and the kernel fetches them itself
(ISSUE 30): one async copy per LIVE page of a block (through
``tbl[n, j]`` and the layer index) into a ring of ``stream_depth`` VMEM
buffers (2 to 4 blocks of ``P`` pages each of K and V, as VMEM allows),
all in flight together. The live blocks of a call are one stream through
the ring: a fetch cursor runs up to ``stream_depth - 1`` live blocks ahead
of the block being computed — through its own tile, then the first block
of the next tile that reads anything — so a slot's copies fly while the
slots before it compute (ISSUE 32). Online softmax state persists in VMEM
scratch across the sequential block axis. Pages past a tile's last live
page are never copied and blocks wholly past it do nothing but step (0.05
us; under one page a step through BlockSpecs a dead step cost 0.16 us and
a decode call over the engine's 65-page table took 1,040 of them: 194 us
against 27 now, v5e, PR 30).

A block's arithmetic takes one of two forms, by the tile's rows. A wide
tile (a prefill window) transposes the block to [KV, span, hd] and scores
it as ONE ``[KV, tq*G, P*page]`` tile. A narrow one (a decode row, a
verify window: while rows x KV heads <= ``_FLAT_SCORES_MAX``) cannot pay
for that: in buffers tiled [page, KV, hd] every key is a (KV, hd) tile of
its own, and loading and transposing 512 of them a leaf was 2.06 us of a
block's 2.50 at 2, 4 and 8 KV heads alike, whatever the bytes (v5e, PR 32:
the long-log cell's decode rows read 13-31 full blocks a slot a layer).
Its buffers are tiled by ROWS, [page*KV, hd] — the same bytes, so a
page's copy is unchanged — and it multiplies its [tq*H, hd] rows against
the block as stored, masking the columns of the other KV groups: 0.84 us
of arithmetic a block, and the stream then bounds the call at 86% of HBM
bandwidth (1.45 us a 1 MB block).

The window is cut into query tiles of ``_q_tile`` columns so the VMEM
working set depends on the head geometry alone, never on W: Mosaic
refused every admission width above 64 when a grid cell held the whole
window (scoped-VMEM limit, v5e). Tiles wholly past ``q_lens[n]`` cost
neither fetches nor compute. Causal-in-window
masking: query column j attends kv positions ``<= positions[n] + j`` —
bitwise the same semantics as the dense gather path
(models/transformer.py::_pool_gather + dense_attention with the decode
causal mask), which stays as the loud fallback for int8 KV and head
counts that don't divide tp.

Interpret mode runs the same kernel on CPU for tests and CI.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


#: Lanes of a TPU vector register: the minor dim of every tile the compiled
#: kernel copies, slices and multiplies.
LANES = 128


def lane_heads(head_dim: int, kv_heads: int) -> int:
    """KV heads a row of the pool holds a lane tile, as the COMPILED kernel
    reads the pool: 1 where a head is whole lane tiles (every 128-wide
    head); ``128 // head_dim`` where a head is a whole fraction of one and
    the KV heads come in such groups (64-wide heads, two a tile); 0 where
    no form serves the head. A token's K row of 8 heads of 64 is the same
    512 lanes as 4 heads of 128: the leaf is MADE [.., KV / n, n x head_dim]
    (models/transformer.py::KVCache.pool_zeros; a 64-lane minor dim has no
    home in HBM, the compiler pads it to 128 or turns the leaf over), the
    kernel runs ``heads x n / KV`` query heads a row of 128 lanes
    (``pair_queries``: a query's values in its own KV head's lanes, zeros
    in the others', so its scores are its own head's and its output's other
    lanes are the neighbour's values, dropped by ``unpair_outputs``). The
    K/V bytes a call streams, which bound a decode call, are unchanged; the
    MXU multiplies ``n`` times the lanes."""
    if head_dim % LANES == 0:
        return 1
    n = LANES // head_dim if LANES % head_dim == 0 else 0
    return n if n and kv_heads % n == 0 else 0


def ragged_supported(page_size: int, head_dim: int, n_pages: int,
                     kv_heads: int = 1) -> bool:
    """Compiled-kernel constraints: a head of whole lane tiles, or whole
    groups of KV heads that fill one (``lane_heads``), and a
    sublane-tileable page."""
    return (lane_heads(head_dim, kv_heads) > 0 and page_size >= 8
            and n_pages >= 1)


def _own_lanes(n_heads: int, kv_heads: int, n: int) -> jnp.ndarray:
    """bool [heads, n]: which of a pool row's ``n`` heads a lane tile is
    query head h's own KV head (h // (heads / KV)) % n."""
    own = (jnp.arange(n_heads) // (n_heads // kv_heads)) % n
    return own[:, None] == jnp.arange(n)[None, :]


def pair_queries(q: jnp.ndarray, kv_heads: int, n: int) -> jnp.ndarray:
    """[N, W, H, hd] -> [N, W, H, n x hd]: each query in the lanes of its
    own KV head of the ``n`` a pool row holds a tile, zeros in the others
    (``lane_heads``)."""
    own = _own_lanes(q.shape[2], kv_heads, n)
    wide = jnp.where(own[:, :, None], q[..., None, :], jnp.zeros((), q.dtype))
    return wide.reshape(q.shape[:-1] + (n * q.shape[-1],))


def unpair_outputs(o: jnp.ndarray, kv_heads: int, n: int) -> jnp.ndarray:
    """``pair_queries``' way back: [N, W, H, n x hd] -> [N, W, H, hd], each
    head's own KV head's lanes of its output."""
    own = _own_lanes(o.shape[2], kv_heads, n)
    o = o.reshape(o.shape[:-1] + (n, o.shape[-1] // n))
    return jnp.sum(jnp.where(own[:, :, None], o, jnp.zeros((), o.dtype)),
                   axis=-2)


def stacked_kv(k, v, layer):
    """(k, v, layer [1] int32) with K/V as a layer stack: a stacked cache
    comes with its traced ``layer`` index, a bare layer becomes a
    one-layer stack (a free reshape), so the kernel has one form and
    its page copies pick the layer — no ``cache[layer]`` copy in front of
    the call (ISSUE 25)."""
    if (k.ndim == 5) != (layer is not None):
        raise ValueError(
            "a stacked [L, ...] cache takes a layer index and a single "
            f"layer takes none; got k.ndim={k.ndim}, "
            f"layer={'set' if layer is not None else None}")
    if layer is None:
        k, v, layer = k[None], v[None], 0
    return k, v, jnp.asarray(layer, jnp.int32).reshape(1)


#: Query elements (columns x heads x head_dim) one grid cell holds: 64
#: columns of Llama-3-8B's 32 heads of 128. That tile compiles under the
#: 16 MiB scoped-VMEM default on v5e; 128 columns is refused (16.19 MiB:
#: q/out blocks double-buffered, f32 accumulator, lane-padded softmax
#: state, score tile and spills).
_Q_TILE_ELEMS = 64 * 32 * 128


def _q_tile(w: int, n_heads: int, head_dim: int) -> int:
    """Query columns per grid cell: the power of two that keeps a tile
    at ``_Q_TILE_ELEMS`` for this head geometry, or the whole window
    when that is narrower (decode, spec verify)."""
    tq = max(8, _Q_TILE_ELEMS // (n_heads * head_dim))
    return min(w, 1 << (tq.bit_length() - 1))


#: KV positions one grid step attends: eight pages of 64.
_KV_STEP_POSITIONS = 512

#: What a step's KV may take of the 16 MiB scoped VMEM (v5e): the ring of
#: K and V buffers plus the f32 score tile and its temporaries. The rest is
#: the query tile's (``_Q_TILE_ELEMS``: q/out blocks, accumulator,
#: lane-padded softmax state). Against the compiler's own count at
#: Mistral-7B's heads and a 64-column tile, two buffers: 4 pages a step
#: allocate 12.25 MiB, 8 pages 15.06, 16 pages 24.66 (refused); a decode
#: row with 8 pages 4.08 (AOT for a v5e, PR 30).
_KV_VMEM_BYTES = 9 * 2**20

#: Buffers the live-block stream may use where VMEM allows more: a block
#: being computed and three in flight.
_STREAM_DEPTH_MAX = 4

#: Score elements a KEY may cost a tile (its rows x the KV heads) for the
#: tile to multiply against the block as stored and mask the other KV
#: groups' columns, instead of transposing the block to [KV, span, hd]
#: (``_ragged_pool_kernel``). Timed at a verify window of 4 (v5e, PR 32, us
#: a call as stored / transposed): 32Q/4KV, 512 elements, 869 / 958; 8Q/2KV,
#: 64, 49.8 / 87.2; 32Q/8KV, 1,024, 128.8 / 103.9; and at 2 columns of
#: 32Q/8KV, 512, 103.6 / 104.2.
_FLAT_SCORES_MAX = 512


def _flat(tq: int, n_heads: int, kv_heads: int) -> bool:
    return tq * n_heads * kv_heads <= _FLAT_SCORES_MAX


def _score_bytes_per_page(page_size: int, n_heads: int, kv_heads: int,
                          tq: int) -> int:
    """Three f32 score tiles of a query tile's rows against one page."""
    if _flat(tq, n_heads, kv_heads):
        rows, cols = -(-tq * n_heads // 8) * 8, page_size * kv_heads
    else:
        rows = kv_heads * -(-tq * (n_heads // kv_heads) // 8) * 8
        cols = page_size
    return 3 * rows * cols * 4


def pages_per_step(n_pages: int, page_size: int, n_heads: int,
                   kv_heads: int, head_dim: int, w: int,
                   itemsize: int = 2) -> int:
    """KV pages one grid step fetches and attends, from shapes alone: the
    largest power of two whose pages cover at most ``_KV_STEP_POSITIONS``
    and whose VMEM — two buffers each of K and V, the least the stream
    runs on (Mosaic tiles a page's [KV, hd] rows compactly: 2 KV heads of
    a mesh shard take a quarter of 8), plus three f32 score tiles of the
    query tile's rows — stays within ``_KV_VMEM_BYTES``. Never wider than
    the table."""
    tq = _q_tile(w, n_heads, head_dim)
    kv_bytes = 2 * 2 * page_size * kv_heads * head_dim * itemsize
    score_bytes = _score_bytes_per_page(page_size, n_heads, kv_heads, tq)
    p = max(1, min(_KV_STEP_POSITIONS // page_size,
                   _KV_VMEM_BYTES // (kv_bytes + score_bytes), n_pages))
    return 1 << (p.bit_length() - 1)


def stream_depth(n_pages: int, page_size: int, n_heads: int,
                 kv_heads: int, head_dim: int, w: int,
                 itemsize: int = 2) -> int:
    """Buffers in the ring the live blocks stream through (the fetch
    cursor runs this many less one blocks ahead), from shapes alone: as
    many blocks of ``pages_per_step`` pages, K and V, as ``_KV_VMEM_BYTES``
    holds beside the score tiles, between 2 and ``_STREAM_DEPTH_MAX``: 4
    where a block is 1 MiB or less, 3 at 8 KV heads, at a decode row (2 MiB
    a block) and beside a full query tile (whose score tiles take 6
    MiB) alike."""
    shape = (page_size, n_heads, kv_heads, head_dim, w, itemsize)
    pps = pages_per_step(n_pages, *shape)
    block = 2 * pps * page_size * kv_heads * head_dim * itemsize
    scores = pps * _score_bytes_per_page(
        page_size, n_heads, kv_heads, _q_tile(w, n_heads, head_dim))
    return max(2, min(_STREAM_DEPTH_MAX, (_KV_VMEM_BYTES - scores) // block))


def grid_steps(n_slots: int, n_pages: int, page_size: int, n_heads: int,
               kv_heads: int, head_dim: int, w: int,
               itemsize: int = 2) -> int:
    """Grid steps of one kernel call (what /health reports for decode)."""
    pps = pages_per_step(n_pages, page_size, n_heads, kv_heads, head_dim, w,
                         itemsize)
    tq = _q_tile(w, n_heads, head_dim)
    return n_slots * pl.cdiv(w, tq) * pl.cdiv(n_pages, pps)


def _ragged_pool_kernel(pos_ref, qlen_ref, tbl_ref, lyr_ref, q_ref, k_hbm,
                        *rest, page_size: int, scale: float,
                        n_pages: int, kv_heads: int, tq: int, pps: int,
                        depth: int, flat: bool, grid: tuple,
                        selects: bool = False, v_lanes: int = 0,
                        window: int = 0):
    """Online-softmax body over one (slot, query tile, page block) grid
    cell: ``tq`` query columns against ``pps`` pages.

    The LIVE blocks of a call, in grid order, are one stream through a
    ring of ``depth`` buffers. A fetch cursor (``ring_ref``: blocks
    computed, blocks issued, and the cell, block and last page of the
    block it fetches next) runs up to ``depth - 1`` live blocks ahead of
    the block being computed: every live block first issues until
    ``depth`` blocks are out (the call's first primes the ring, the others
    issue one), into buffer ``issued % depth``, moving the cursor to the
    next block of its tile or, past the tile's last live page, to block 0
    of the next tile that reads anything (frozen slots and tiles past
    ``q_len`` read nothing); then it waits for its own copies in buffer
    ``done % depth`` and computes. A full block is waited for once a leaf,
    on the buffer's whole size; a tile's last, half-live block page by
    page.

    Two forms of the same arithmetic, by the tile's rows (``flat``). A
    wide tile's buffers are tiled as the pool is, [page, KV, hd]; it lays
    rows out [KV, tq*G] (row r is tile column ``r // G`` of KV group
    ``r % G``'s block), transposes the block to [KV, span, hd] once and
    serves every column and head by one KV-batched ``dot_general``. A
    narrow tile (a decode or verify row) has too few rows to pay for
    loading 512 one-key tiles a leaf and transposing them (module
    docstring): its buffers are tiled by rows, [page*KV, hd] — a page's
    bytes are the same, so its copy reads the pool through a reshaped
    view — and it multiplies its [tq*H, hd] rows against the block AS
    STORED, [span*KV, hd], masking the columns of the other KV groups.
    Sums run over the same keys with zeros between them, so the two forms
    agree to float32 rounding, not to the bit.

    Scalar code divides with ``lax.div``/``lax.rem`` (operands are never
    negative): ``//`` and ``%`` each lower through a traced sign
    correction, which cost 0.4 s of lowering a chunk program at every
    server start, compile cache or not.

    ``selects`` (a key-selecting configuration, ops/sparse_select.py):
    one more operand, ``sel_ref`` int8 [1, tq, pps*page] (``flat``: every
    key repeated for each KV head, [1, tq, pps*page*KV]), nonzero where
    the tile's query column may attend the block's key; it is ANDed into
    the causal mask and nothing else changes.

    ``v_lanes`` (latent attention, ``latent_attention_pool``): there is
    no V operand and no V ring; ONE leaf streams, of one row a key (the
    ``flat`` form with one KV head), and a key's value is the first
    ``v_lanes`` lanes of its own row.

    ``window`` > 0 (a sliding-attention layer): query column j attends
    only keys ``> pos + j - window``, and the tile's page stream STARTS at
    the page that holds its first column's first key instead of at page 0
    — the same stream, cursor and ring, with a lower end. The grid's block
    axis then counts from the tile's first live block (``first_block_of``)
    and is only as long as a span and a tile need."""
    sel_ref = v_hbm = v_buf = None
    if not v_lanes:
        v_hbm, *rest = rest
    if selects:
        sel_ref, *rest = rest
    if v_lanes:
        (o_ref, k_buf, sems, ring_ref, m_scr, l_scr, acc_scr) = rest
    else:
        (o_ref, k_buf, v_buf, sems, ring_ref, m_scr, l_scr, acc_scr) = rest
    n, t, b_grid = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_slots, n_qt, n_blk = grid
    n_cells = n_slots * n_qt
    div, rem = jax.lax.div, jax.lax.rem

    def last_page_of(n_, t_):
        """Pages tile ``t_`` of slot ``n_`` reads: 0 .. this; -1 = none
        (a tile wholly past ``q_len``). The last is the page of the
        tile's last valid column's own freshly-written row. Copies and
        compute are gated by the same number."""
        q_len = qlen_ref[n_]
        hi = jnp.minimum(t_ * tq + tq, q_len)
        last = jnp.minimum(div(pos_ref[n_] + hi - 1, page_size), n_pages - 1)
        return jnp.where(t_ * tq < q_len, last, -1)

    def first_page_of(n_, t_):
        """The first page tile ``t_`` of slot ``n_`` reads: page 0, or the
        page of its first column's first key under a window."""
        if not window:
            return 0
        return div(jnp.maximum(pos_ref[n_] + t_ * tq - (window - 1), 0),
                   page_size)

    def first_block_of(n_, t_):
        return div(first_page_of(n_, t_), pps) if window else 0

    def tile_of(cell):
        return (cell, 0) if n_qt == 1 else (div(cell, n_qt), rem(cell, n_qt))

    def first_reader(cell):
        """(cell, its last page) of the first cell at or after ``cell``,
        in grid order, that reads any page; ``n_cells`` when none does."""
        def last_of(c):
            return last_page_of(*tile_of(jnp.minimum(c, n_cells - 1)))

        return jax.lax.while_loop(
            lambda s: jnp.logical_and(s[0] < n_cells, s[1] < 0),
            lambda s: (s[0] + 1, last_of(s[0] + 1)), (cell, last_of(cell)))

    def for_live_pages(blk, first, last, do):
        """``do(page, its row of the buffer)`` for every page of block
        ``blk`` from page ``first`` up to page ``last``."""
        jax.lax.fori_loop(
            jnp.maximum(blk * pps, first),
            jnp.minimum(blk * pps + pps, last + 1),
            lambda j, _: do(j, j - blk * pps), None)

    leaves = (((k_hbm, k_buf),) if v_lanes
              else ((k_hbm, k_buf), (v_hbm, v_buf)))
    if flat and not v_lanes:
        # A page's [page, KV, hd] rows are [page*KV, hd] rows, byte for
        # byte: the copies land them as that, in buffers tiled by rows.
        # (Reshaping the VMEM side instead aborts the compiler.)
        leaves = tuple((hbm.reshape(*hbm.shape[:2], -1, hbm.shape[-1]), buf)
                       for hbm, buf in leaves)

    @pl.when(b_grid == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(jnp.logical_and(n == 0, t == 0))
        def _first():
            # A page past the live span of a half-live block is never
            # copied; its V rows meet probability 0, and 0 x NaN is NaN.
            # So the buffers only ever hold zeros or pool rows.
            values = k_buf if v_lanes else v_buf
            values[...] = jnp.zeros_like(values)
            cell, last = first_reader(0)
            blk0 = first_block_of(*tile_of(jnp.minimum(cell, n_cells - 1)))
            for i, x in enumerate((0, 0, cell, blk0, last)):
                ring_ref[i] = x

    pos = pos_ref[n]
    q_len = qlen_ref[n]
    q0 = t * tq                       # window column of tile row 0
    last_page = last_page_of(n, t)
    first_page = first_page_of(n, t)
    # the block this grid step stands for: the tile's blocks from its first
    # live one (block 0 without a window)
    b = b_grid + first_block_of(n, t)

    @pl.when(b * pps <= last_page)
    def _accumulate():
        done = ring_ref[0]
        ring_ref[0] = done + 1

        def issue(s):
            issued, cell, blk, last = s
            n_, t_ = tile_of(cell)
            buf_i = rem(issued, depth)

            def start(j, row):
                for c, (hbm, buf) in enumerate(leaves):
                    pltpu.make_async_copy(
                        hbm.at[lyr_ref[0], tbl_ref[n_, j]],
                        buf.at[buf_i, row], sems.at[c, buf_i]).start()

            for_live_pages(blk, first_page_of(n_, t_), last, start)
            more = (blk + 1) * pps <= last
            cell, last = first_reader(jnp.where(more, cell, cell + 1))
            blk0 = first_block_of(*tile_of(jnp.minimum(cell, n_cells - 1)))
            return issued + 1, cell, jnp.where(more, blk + 1, blk0), last

        cursor = jax.lax.while_loop(
            lambda s: jnp.logical_and(s[0] < done + depth, s[1] < n_cells),
            issue, tuple(ring_ref[i] for i in range(1, 5)))
        for i, x in enumerate(cursor):
            ring_ref[1 + i] = x

        slot = rem(done, depth)
        full = jnp.logical_and(b * pps + pps - 1 <= last_page,
                               b * pps >= first_page)

        # A wait needs only the destination and the semaphore.
        @pl.when(full)
        def _whole():
            for c, (_, buf) in enumerate(leaves):
                pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                      sems.at[c, slot]).wait()

        @pl.when(jnp.logical_not(full))
        def _pages():
            def wait(j, row):
                for c, (hbm, buf) in enumerate(leaves):
                    pltpu.make_async_copy(hbm.at[lyr_ref[0], 0],
                                          buf.at[slot, row],
                                          sems.at[c, slot]).wait()

            for_live_pages(b, first_page, last_page, wait)

        H, hd = q_ref.shape[2], q_ref.shape[3]
        G = H // kv_heads
        span = pps * page_size
        red = 1 if flat else 2          # the scores' key axis
        if v_lanes:
            # Latent rows, stored a PAIR of tokens a row (see
            # ``latent_attention_pool``): [c_even | c_odd | kr_even kr_odd].
            # Rows [tq*H] as in the flat form; the block's keys come out
            # even tokens first, then odd ones, and the mask follows.
            C, rows, half = v_lanes, tq * H, span // 2
            R2 = k_buf.shape[-1] - 2 * C
            qg = q_ref[0].reshape(rows, hd)
            kb = k_buf[slot].reshape(half, 2 * C + R2)
            c_even, c_odd, kr = kb[:, :C], kb[:, C:2 * C], kb[:, 2 * C:]

            def qk(a, b_):
                return jax.lax.dot_general(
                    a, b_, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)

            s = jnp.concatenate(
                [qk(qg[:, :C], c_even) + qk(qg[:, C:C + R2], kr),
                 qk(qg[:, :C], c_odd) + qk(qg[:, C + R2:], kr)],
                axis=1) * scale                         # [rows, span]
            col = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1)
            odd = (col >= half).astype(jnp.int32)
            kv_ids = b * span + 2 * (col - odd * half) + odd
            q_ids = q0 + div(jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0), H)
        elif flat:
            # Rows [tq*H]: column j's head h at j*H + h. Keys as stored:
            # position p's KV head g at p*KV + g.
            rows, cols = tq * H, span * kv_heads
            qg = q_ref[0].reshape(rows, hd)
            k = k_buf[slot].reshape(cols, hd)
            v = v_buf[slot].reshape(cols, hd)
            s = jax.lax.dot_general(
                qg, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # [rows, cols]
            col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            kv_ids = b * span + div(col, kv_heads)
            q_ids = q0 + div(row, H)
            own = rem(col, kv_heads) == div(rem(row, H), G)
        else:
            # [tq, H, hd] -> [KV, tq*G, hd]: head h of column j lands at
            # row j*G + h%G of KV group h//G — query column recoverable
            # as q0 + row // G for the causal mask below.
            qg = jnp.swapaxes(
                q_ref[0].reshape(tq, kv_heads, G, hd), 0, 1
            ).reshape(kv_heads, tq * G, hd)
            k = jnp.swapaxes(                           # [KV, span, hd]
                k_buf[slot].reshape(span, kv_heads, hd), 0, 1)
            v = jnp.swapaxes(
                v_buf[slot].reshape(span, kv_heads, hd), 0, 1)
            s = jax.lax.dot_general(
                qg, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            ) * scale                                   # [KV, tq*G, span]
            # The mask is the same for every KV group: [1, tq*G, span].
            kv_ids = b * span + jax.lax.broadcasted_iota(
                jnp.int32, (1, 1, span), 2)
            q_ids = q0 + div(jax.lax.broadcasted_iota(
                jnp.int32, (1, tq * G, 1), 1), G)
        # Causal-in-window: column j attends kv <= pos + j (which also
        # masks the block's pages past the live span); padded columns
        # (j >= q_len) mask everything — their normalizer stays 0 and the
        # finalize writes zeros (outputs are never read).
        mask = jnp.logical_and(kv_ids <= pos + q_ids, q_ids < q_len)
        if window:                  # ... and only the keys inside its span
            mask = jnp.logical_and(mask, kv_ids > pos + q_ids - window)
        if flat and not v_lanes:    # ... and only its own KV group's columns
            mask = jnp.logical_and(mask, own)
        if sel_ref is not None:
            picked = sel_ref[0].astype(jnp.int32) != 0
            if flat:    # [tq, cols] -> a column's row for each of its heads
                picked = jnp.broadcast_to(
                    picked[:, None, :], (tq, H, cols)).reshape(rows, cols)
            else:       # [tq, span] -> row j*G + g reads column j's row
                picked = jnp.broadcast_to(
                    picked[:, None, :], (tq, G, span)
                ).reshape(1, tq * G, span)
            mask = jnp.logical_and(mask, picked)
        s = jnp.where(mask, s, -jnp.inf)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=red, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0,
                          jnp.exp(m_prev - m_new))
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(pexp, axis=red,
                                              keepdims=True)
        if v_lanes:
            def pv(p_, c_):
                return jnp.dot(p_.astype(c_.dtype), c_,
                               preferred_element_type=jnp.float32)

            weighted = (pv(pexp[:, :half], c_even)
                        + pv(pexp[:, half:], c_odd))    # [tq*H, C]
        else:
            weighted = jax.lax.dot_general(
                pexp.astype(v.dtype), v,
                (((1,), (0,)), ((), ())) if flat
                else (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )                           # [tq*H, hd] | [KV, tq*G, hd]
        acc_scr[...] = acc_scr[...] * alpha + weighted

    @pl.when(b_grid == n_blk - 1)
    def _finalize():
        H, hd = o_ref.shape[2], o_ref.shape[3]
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        out = acc_scr[...] / l
        if not flat:
            out = jnp.swapaxes(
                out.reshape(kv_heads, tq, H // kv_heads, hd), 0, 1)
        o_ref[0] = out.reshape(tq, H, hd).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret", "v_lanes", "window"),
)
def ragged_attention_pool(
    q: jnp.ndarray,             # [N, W, H, hd] per-slot query windows
    k: jnp.ndarray,             # [n_blocks, page, KV, hd] one layer, or
    v: jnp.ndarray,             # [L, n_blocks, page, KV, hd] with ``layer``
    q_lens: jnp.ndarray,        # [N] int32 valid queries per slot
    positions: jnp.ndarray,     # [N] int32 abs position of column 0
    block_tables: jnp.ndarray,  # [N, max_pages] int32
    layer=None,                 # int32 scalar: layer of a stacked pool
    sel=None,                   # bool [N, W, max_pages*page]: keys each
                                # column may attend (None: every key)
    *,
    page_size: int = 128,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
    v_lanes: int = 0,
    window: int = 0,
) -> jnp.ndarray:
    """Ragged block-paged attention over the pool. Returns
    [N, W, H, hd]; rows past ``q_lens[n]`` are zeros (never read —
    ``logits_at`` gathers the last valid column). ``sel`` restricts each
    query column to the keys it names, inside the causal mask (a column
    whose ``sel`` row names every causal key gives the bits it gives
    without ``sel``).

    ``v_lanes`` > 0 (``latent_attention_pool``): ``v`` is None, ``k`` has
    one KV head, and a key's value is the first ``v_lanes`` lanes of its
    row; returns [N, W, H, v_lanes].

    ``window`` > 0 (sliding attention): column j attends only the
    ``window`` keys up to its own, ``positions[n] + j - window < kv``, and
    no page before the one holding a tile's first such key is visited —
    whatever the pool, a shared one through real tables or a sequence's
    ring through ``ring_tables``.

    Cost per slot tracks ``ceil((positions[n]+q_lens[n])/page)`` live
    pages, whatever mixture of decode / verify / prefill widths the
    batch carries — the mixed-chunk property ISSUE 19 is about."""
    if pltpu is None:
        raise NotImplementedError(
            "ragged_attention_pool requires jax.experimental.pallas.tpu; "
            "use the dense gather path"
        )
    N, W, H, hd = q.shape
    if v_lanes:
        # the latent leaf as it is stored, [L, n_blocks, page/2, lanes]: a
        # head axis of 1 put into it would be a copy of the pool
        if v is not None or sel is not None:
            raise ValueError("latent rows: one leaf, no V, no selection")
        if (k.ndim == 4) != (layer is not None):
            raise ValueError("a stacked latent leaf takes a layer index "
                             "and a single layer takes none")
        if layer is None:
            k, layer = k[None], 0
        lyr = jnp.asarray(layer, jnp.int32).reshape(1)
        (_, n_blocks, page, _), KV = k.shape, 1
        page *= LATENT_PAIR     # a row of the leaf holds two tokens
    else:
        k, v, lyr = stacked_kv(k, v, layer)
        _, n_blocks, page, KV, _ = k.shape
    if page != page_size:
        raise ValueError(f"pool page {page} != page_size {page_size}")
    n_pages = block_tables.shape[1]
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    tq = _q_tile(W, H, hd)
    n_qt = pl.cdiv(W, tq)
    if n_qt * tq != W:
        # Widths the tile doesn't divide pad up; the extra columns sit
        # past every q_len, so they are masked and sliced off below.
        q = jnp.pad(q, ((0, 0), (0, n_qt * tq - W), (0, 0), (0, 0)))
    pos = positions.astype(jnp.int32)
    qln = q_lens.astype(jnp.int32)
    tbl = jnp.clip(block_tables.astype(jnp.int32), 0, n_blocks - 1)
    # A table the block does not divide needs no padding: a page past the
    # table is past every live span, and only live pages are looked up.
    shape = (n_pages, page_size, H, KV, hd, W, k.dtype.itemsize)
    pps, depth, flat = pages_per_step(*shape), stream_depth(*shape), \
        _flat(tq, H, KV)
    if v_lanes:
        # one row a key is the flat form at any tile width; the budget
        # (reckoned for a K and a V ring) only has more room
        flat = True

    n_blk = pl.cdiv(n_pages, pps)
    if window:
        if sel is not None or v_lanes:
            raise ValueError("a window takes neither a selection nor "
                             "latent rows")
        # a tile's keys: ``window + tq - 1`` positions from anywhere in a
        # block
        n_blk = min(n_blk, (window + tq - 2) // (pps * page_size) + 2)
    grid = (N, n_qt, n_blk)
    kernel = functools.partial(
        _ragged_pool_kernel, page_size=page_size, scale=scale,
        n_pages=n_pages, kv_heads=KV, tq=tq, pps=pps, depth=depth,
        flat=flat, grid=grid, selects=sel is not None, v_lanes=v_lanes,
        window=window,
    )

    def q_map(n, t, b, pos_ref, qlen_ref, tbl_ref, lyr_ref):
        return (n, t, 0, 0)

    operands, sel_specs = [pos, qln, tbl, lyr, q, k] + (
        [] if v_lanes else [v]), []
    if sel is not None:
        span = pps * page_size
        sel = jnp.pad(sel, (
            (0, 0), (0, n_qt * tq - W), (0, grid[2] * span - sel.shape[2])))
        if flat:
            # A key's bit for each of its KV heads' columns, by the MXU:
            # chunks of keys times a 0/1 matrix. ``jnp.repeat`` along the
            # minor axis compiles to two transposing copies, 72 us of a
            # 578 us decode call at the long-log geometry (v5e, PR 32).
            c = math.gcd(span, 128)
            spread = (jnp.arange(c * KV)[None, :] // KV
                      == jnp.arange(c)[:, None]).astype(jnp.bfloat16)
            sel = jnp.dot(sel.reshape(-1, c).astype(jnp.bfloat16),
                          spread).reshape(N, n_qt * tq, -1)
            span *= KV
        sel = sel.astype(jnp.int8)
        operands.append(sel)
        sel_specs = [pl.BlockSpec(
            (1, tq, span), lambda n, t, b, *_: (n, t, b))]

    rows = (tq * H,) if flat else (KV, tq * (H // KV))
    page_rows = (page_size * KV,) if flat else (page_size, KV)
    if v_lanes:
        page_rows = (page_size // 2,)
    vd = v_lanes or hd                  # a value's width
    ring = pltpu.VMEM((depth, pps) + page_rows + (k.shape[-1],), k.dtype)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[pl.BlockSpec((1, tq, H, hd), q_map), any_space]
        + ([] if v_lanes else [any_space]) + sel_specs,
        out_specs=pl.BlockSpec((1, tq, H, vd), q_map),
        scratch_shapes=[ring] + ([] if v_lanes else [ring]) + [
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.SMEM((5,), jnp.int32),
            pltpu.VMEM(rows + (1,), jnp.float32),
            pltpu.VMEM(rows + (1,), jnp.float32),
            pltpu.VMEM(rows + (vd,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, n_qt * tq, H, vd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out[:, :W]


def ring_tables(n_rows: int, ring_pages: int, n_pages: int) -> jnp.ndarray:
    """Block tables [n_rows, n_pages] that read a leaf of ``n_rows`` rings
    of ``ring_pages`` pages each ([n_rows * ring_pages, page, ...]) as a
    pool: row r's sequence page p is block ``r * ring_pages + p %
    ring_pages`` — where position ``p * page + i`` was written, at row
    ``(p * page + i) % ring`` of its ring."""
    return (jnp.arange(n_rows, dtype=jnp.int32)[:, None] * ring_pages
            + jnp.arange(n_pages, dtype=jnp.int32)[None, :] % ring_pages)


#: Tokens a row of the latent leaf holds (``latent_pack``).
LATENT_PAIR = 2


def latent_pack(c, kr):
    """Token rows -> the latent leaf's rows: ``c`` [..., T, C] (the normed
    latent) and ``kr`` [..., T, R] (the rotated rope key), T even ->
    [..., T/2, 2C + 2R], a PAIR of tokens a row, laid out [c_even | c_odd
    | kr_even | kr_odd]. A row a token would be C + R = 320 lanes at the
    published sizes, no multiple of the TPU's 128: the compiler pads such
    a leaf to 384 lanes in HBM (768 B a token, not 640) and Mosaic refuses
    to copy a 320-lane page (AOT, PR 38). Two tokens a row are 640 lanes,
    five whole lane tiles, every part on a tile's edge: the leaf takes its
    640 B a token and not a byte more, and the kernel slices it for
    nothing."""
    pair = lambda a: a.reshape(a.shape[:-2] + (a.shape[-2] // 2, 2,
                                               a.shape[-1]))
    c, kr = pair(c), pair(kr)
    return jnp.concatenate([c[..., 0, :], c[..., 1, :],
                            kr[..., 0, :], kr[..., 1, :]], axis=-1)


def latent_unpack(rows, c_lanes: int):
    """``latent_pack``'s inverse: [..., T/2, 2C + 2R] -> (c [..., T, C],
    kr [..., T, R])."""
    C = c_lanes
    R = (rows.shape[-1] - 2 * C) // 2
    tok = lambda a, b: jnp.stack([a, b], axis=-2).reshape(
        rows.shape[:-2] + (2 * rows.shape[-2], a.shape[-1]))
    return (tok(rows[..., :C], rows[..., C:2 * C]),
            tok(rows[..., 2 * C:2 * C + R], rows[..., 2 * C + R:]))


def latent_query(q_c, q_r):
    """The kernel's query rows for absorbed queries ``q_c`` [..., C]
    (against the latent) and ``q_r`` [..., R] (against the rope key):
    [q_c | q_r 0 | 0 q_r], so that the rope part of an even and of an odd
    token's score each contract over the pair's whole 2R-lane tile."""
    z = jnp.zeros_like(q_r)
    return jnp.concatenate([q_c, q_r, z, z, q_r], axis=-1)


def latent_attention_pool(q, rows, q_lens, positions, block_tables,
                          layer=None, *, v_lanes: int, page_size: int,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Absorbed latent attention (MLA) over the pool: the same kernel, the
    same stream, ONE leaf. ``q`` [N, W, H, C + 4R] is ``latent_query``'s
    rows — each head's absorbed query, already times the softmax scale —
    and ``rows`` is the latent leaf (``latent_pack``), [n_blocks, page/2,
    2C + 2R] or stacked [L, ...] with ``layer``: 640 B a token at the
    published sizes, shared by every head (no head axis), a token's first
    ``v_lanes`` = C lanes also its value. ``page_size`` counts tokens.
    Returns [N, W, H, C]."""
    return ragged_attention_pool(
        q, rows, None, q_lens, positions, block_tables, layer,
        page_size=page_size, scale=1.0, interpret=interpret, v_lanes=v_lanes)


def ragged_attention_pool_sharded(
    q: jnp.ndarray,             # [N, W, H, hd]
    k: jnp.ndarray,             # [n_blocks, page, KV, hd], or the
    v: jnp.ndarray,             # stacked [L, ...] pool with ``layer``
    q_lens: jnp.ndarray,        # [N]
    positions: jnp.ndarray,     # [N]
    block_tables: jnp.ndarray,  # [N, max_pages]
    mesh,
    layer=None,
    *,
    page_size: int = 128,
) -> jnp.ndarray:
    """Mesh-aware ragged kernel dispatch (ISSUE 14): XLA can't
    auto-partition a ``pallas_call``, so under a >1 ``model`` axis the
    kernel runs shard_mapped with Q and KV heads split together over
    ``model`` — the pool shards on the KV-head axis
    (parallel/sharding.py::pool_cache_specs), so each shard holds whole
    KV groups and the local G = H_local/KV_local stays the true
    grouping. Positions, query lengths, tables and the layer index are
    replicated (per-slot host truth); a stacked pool's layer axis stays
    whole on every shard. Head counts that don't divide the axis serve
    the LOUD gather fallback instead — engine startup resolves that."""
    tp = mesh.shape["model"] if mesh is not None else 1
    H, KV = q.shape[2], k.shape[-2]
    if tp <= 1:
        return ragged_attention_pool(q, k, v, q_lens, positions,
                                     block_tables, layer,
                                     page_size=page_size)
    if KV % tp or H % tp:
        raise ValueError(
            f"ragged pool kernel needs KV ({KV}) and H ({H}) divisible "
            f"by the model axis ({tp}); engine startup resolves such "
            f"meshes to the gather path")
    import jax.sharding as jsh

    P_ = jsh.PartitionSpec

    def _local(ql, kl, vl, qlen, pos, tbl, lyr):
        return ragged_attention_pool(ql, kl, vl, qlen, pos, tbl, lyr[0],
                                     page_size=page_size)

    # One form through the shard_map: a layer stack (whole on every
    # shard) and its replicated index.
    k, v, lyr = stacked_kv(k, v, layer)
    kv_spec = P_(None, None, None, "model", None)
    return jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P_(None, None, "model", None), kv_spec, kv_spec,
                  P_(None), P_(None), P_(None, None), P_(None)),
        out_specs=P_(None, None, "model", None),
        axis_names=set(mesh.axis_names),
        # pallas_call can't express per-axis varying metadata for the
        # VMA checker; the specs above are the contract.
        check_vma=False,
    )(q, k, v, q_lens, positions, block_tables, lyr)
