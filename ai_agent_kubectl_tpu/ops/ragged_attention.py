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
``tbl[n, j]`` and the layer index) into a ``[2, P, page, KV, hd]`` VMEM
scratch, all in flight together. The live blocks of a call are one stream
through the two buffers: each starts the copies of the NEXT live block —
of its own tile, or the first block of the next tile that reads anything —
before it waits for its own, so a slot's copies fly while the slot before
it computes. A block is ONE ``[KV, tq*G, P*page]`` score tile; online
softmax state persists in VMEM scratch across the sequential block axis.
Pages past a tile's last live page are never copied and blocks wholly past
it do nothing but step (0.05 us; under one page a step through BlockSpecs
a dead step cost 0.16 us and a decode call over the engine's 65-page table
took 1,040 of them: 194 us against 27 now, v5e, PR 30).

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
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None


def ragged_supported(page_size: int, head_dim: int,
                     n_pages: int) -> bool:
    """Compiled-kernel constraints: lanes want a 128-multiple head dim
    and a sublane-tileable page."""
    return head_dim % 128 == 0 and page_size >= 8 and n_pages >= 1


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

#: What a step's KV may take of the 16 MiB scoped VMEM (v5e): the K and V
#: double buffers plus the f32 score tile and its temporaries. The rest is
#: the query tile's (``_Q_TILE_ELEMS``: q/out blocks, accumulator,
#: lane-padded softmax state). Against the compiler's own count at
#: Mistral-7B's heads and a 64-column tile: 4 pages a step allocate 12.25
#: MiB, 8 pages 15.06, 16 pages 24.66 (refused); a decode row with 8 pages
#: 4.08 (AOT for a v5e, PR 30).
_KV_VMEM_BYTES = 9 * 2**20


def pages_per_step(n_pages: int, page_size: int, n_heads: int,
                   kv_heads: int, head_dim: int, w: int,
                   itemsize: int = 2) -> int:
    """KV pages one grid step fetches and attends, from shapes alone: the
    largest power of two whose pages cover at most ``_KV_STEP_POSITIONS``
    and whose VMEM — K and V double-buffered (Mosaic tiles a page's
    [KV, hd] rows compactly: 2 KV heads of a mesh shard take a quarter of
    8) plus three f32 score tiles of the query tile's rows — stays within
    ``_KV_VMEM_BYTES``. Never wider than the table."""
    tq = _q_tile(w, n_heads, head_dim)
    kv_bytes = 2 * 2 * page_size * kv_heads * head_dim * itemsize
    rows = kv_heads * -(-tq * (n_heads // kv_heads) // 8) * 8
    score_bytes = 3 * rows * page_size * 4
    p = max(1, min(_KV_STEP_POSITIONS // page_size,
                   _KV_VMEM_BYTES // (kv_bytes + score_bytes), n_pages))
    return 1 << (p.bit_length() - 1)


def grid_steps(n_slots: int, n_pages: int, page_size: int, n_heads: int,
               kv_heads: int, head_dim: int, w: int,
               itemsize: int = 2) -> int:
    """Grid steps of one kernel call (what /health reports for decode)."""
    pps = pages_per_step(n_pages, page_size, n_heads, kv_heads, head_dim, w,
                         itemsize)
    tq = _q_tile(w, n_heads, head_dim)
    return n_slots * pl.cdiv(w, tq) * pl.cdiv(n_pages, pps)


def _ragged_pool_kernel(pos_ref, qlen_ref, tbl_ref, lyr_ref, q_ref, k_hbm,
                        v_hbm, *rest, page_size: int, scale: float,
                        n_pages: int, kv_heads: int, tq: int, pps: int,
                        grid: tuple, selects: bool = False):
    """Online-softmax body over one (slot, query tile, page block) grid
    cell: ``tq`` query columns against ``pps`` pages. Rows are laid out
    [KV, tq*G] (row r is tile column ``r // G`` of KV group ``r % G``'s
    block) so one KV-batched ``dot_general`` serves every query column
    and head of the block.

    The LIVE blocks of a call, in grid order, are one stream through two
    buffers: each starts the copies of the one after it — the next block
    of its tile or, past the tile's last live page, block 0 of the next
    tile that reads anything (frozen slots and tiles past ``q_len`` read
    nothing) — before it waits for its own. ``done_ref`` counts the
    blocks computed; its parity is the buffer the next one lands in.

    Scalar code divides with ``lax.div``/``lax.rem`` (operands are never
    negative): ``//`` and ``%`` each lower through a traced sign
    correction, which cost 0.4 s of lowering a chunk program at every
    server start, compile cache or not.

    ``selects`` (a key-selecting configuration, ops/sparse_select.py):
    one more operand, ``sel_ref`` [1, tq, pps*page] int8, nonzero where
    the tile's query column may attend the block's key; it is ANDed into
    the causal mask and nothing else changes."""
    sel_ref = None
    if selects:
        sel_ref, *rest = rest
    (o_ref, k_buf, v_buf, sems, done_ref, m_scr, l_scr, acc_scr) = rest
    n, t, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
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

    def for_live_pages(n_, blk, last, slot, do, block_of):
        """``do`` the K and V copy of every page of block ``blk`` up to
        page ``last`` (into buffer ``slot``, from pool block
        ``block_of(slot n_'s table, page)``)."""
        def page(j, _):
            for c, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                do(pltpu.make_async_copy(
                    hbm.at[lyr_ref[0], block_of(n_, j)],
                    buf.at[slot, j - blk * pps], sems.at[c, slot]))

        jax.lax.fori_loop(blk * pps, jnp.minimum(blk * pps + pps, last + 1),
                          page, None)

    def start(cp):
        cp.start()

    def from_table(n_, j):
        return tbl_ref[n_, j]

    def tile_of(cell):
        return (cell, 0) if n_qt == 1 else (div(cell, n_qt), rem(cell, n_qt))

    @pl.when(b == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

        @pl.when(jnp.logical_and(n == 0, t == 0))
        def _first():
            # A page past the live span of a half-live block is never
            # copied; its V rows meet probability 0, and 0 x NaN is NaN.
            # So the buffers only ever hold zeros or pool rows.
            v_buf[...] = jnp.zeros_like(v_buf)
            done_ref[0] = 0

    pos = pos_ref[n]
    q_len = qlen_ref[n]
    q0 = t * tq                       # window column of tile row 0
    last_page = last_page_of(n, t)

    @pl.when(b * pps <= last_page)
    def _accumulate():
        done = done_ref[0]
        done_ref[0] = done + 1
        slot = jnp.bitwise_and(done, 1)

        @pl.when(done == 0)
        def _own():     # the call's first live block: none before it
            for_live_pages(n, b, last_page, slot, start, from_table)

        # The next live block: of this tile, or block 0 of the first
        # tile after it (in grid order) that reads any page.
        more = (b + 1) * pps <= last_page
        cell = n * n_qt + t

        def reads_nothing(c):
            return last_page_of(*tile_of(jnp.minimum(c, n_cells - 1))) < 0

        nxt = jax.lax.while_loop(
            lambda c: jnp.logical_and(c < n_cells, reads_nothing(c)),
            lambda c: c + 1, jnp.where(more, cell, cell + 1))

        @pl.when(nxt < n_cells)
        def _next():
            n_, t_ = tile_of(nxt)
            for_live_pages(n_, jnp.where(more, b + 1, 0),
                           last_page_of(n_, t_), 1 - slot, start, from_table)

        # A wait needs only the destination and the semaphore.
        for_live_pages(n, b, last_page, slot, lambda cp: cp.wait(),
                       lambda n_, j: 0)

        H, hd = q_ref.shape[2], q_ref.shape[3]
        G = H // kv_heads
        # [tq, H, hd] -> [KV, tq*G, hd]: head h of column j lands at row
        # j*G + h%G of KV group h//G — query column recoverable as
        # q0 + row // G for the causal mask below.
        qg = jnp.swapaxes(
            q_ref[0].reshape(tq, kv_heads, G, hd), 0, 1
        ).reshape(kv_heads, tq * G, hd)
        span = pps * page_size
        k = jnp.swapaxes(                               # [KV, span, hd]
            k_buf[slot].reshape(span, kv_heads, hd), 0, 1)
        v = jnp.swapaxes(
            v_buf[slot].reshape(span, kv_heads, hd), 0, 1)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [KV, tq*G, span]
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
        if sel_ref is not None:
            # [tq, span] -> row j*G + g reads column j's row
            picked = jnp.broadcast_to(
                (sel_ref[0].astype(jnp.int32) != 0)[:, None, :],
                (tq, G, span)).reshape(1, tq * G, span)
            mask = jnp.logical_and(mask, picked)
        s = jnp.where(mask, s, -jnp.inf)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0,
                          jnp.exp(m_prev - m_new))
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(pexp, axis=2,
                                              keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                               # [KV, tq*G, hd]

    @pl.when(b == n_blk - 1)
    def _finalize():
        H, hd = o_ref.shape[2], o_ref.shape[3]
        G = H // kv_heads
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_scr[...] / l).reshape(kv_heads, tq, G, hd)
        o_ref[0] = jnp.swapaxes(out, 0, 1).reshape(
            tq, H, hd).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret"),
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
) -> jnp.ndarray:
    """Ragged block-paged attention over the pool. Returns
    [N, W, H, hd]; rows past ``q_lens[n]`` are zeros (never read —
    ``logits_at`` gathers the last valid column). ``sel`` restricts each
    query column to the keys it names, inside the causal mask (a column
    whose ``sel`` row names every causal key gives the bits it gives
    without ``sel``).

    Cost per slot tracks ``ceil((positions[n]+q_lens[n])/page)`` live
    pages, whatever mixture of decode / verify / prefill widths the
    batch carries — the mixed-chunk property ISSUE 19 is about."""
    if pltpu is None:
        raise NotImplementedError(
            "ragged_attention_pool requires jax.experimental.pallas.tpu; "
            "use the dense gather path"
        )
    N, W, H, hd = q.shape
    k, v, lyr = stacked_kv(k, v, layer)
    _, n_blocks, page, KV, _ = k.shape
    if page != page_size:
        raise ValueError(f"pool page {page} != page_size {page_size}")
    n_pages = block_tables.shape[1]
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    G = H // KV
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
    pps = pages_per_step(n_pages, page_size, H, KV, hd, W, k.dtype.itemsize)

    grid = (N, n_qt, pl.cdiv(n_pages, pps))
    kernel = functools.partial(
        _ragged_pool_kernel, page_size=page_size, scale=scale,
        n_pages=n_pages, kv_heads=KV, tq=tq, pps=pps, grid=grid,
        selects=sel is not None,
    )

    def q_map(n, t, b, pos_ref, qlen_ref, tbl_ref, lyr_ref):
        return (n, t, 0, 0)

    operands, sel_specs = [pos, qln, tbl, lyr, q, k, v], []
    if sel is not None:
        span = pps * page_size
        sel = jnp.pad(sel.astype(jnp.int8), (
            (0, 0), (0, n_qt * tq - W), (0, grid[2] * span - sel.shape[2])))
        operands.append(sel)
        sel_specs = [pl.BlockSpec(
            (1, tq, span), lambda n, t, b, *_: (n, t, b))]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tq, H, hd), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + sel_specs,
        out_specs=pl.BlockSpec((1, tq, H, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pps, page_size, KV, hd), k.dtype),
            pltpu.VMEM((2, pps, page_size, KV, hd), v.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((KV, tq * G, 1), jnp.float32),
            pltpu.VMEM((KV, tq * G, 1), jnp.float32),
            pltpu.VMEM((KV, tq * G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, n_qt * tq, H, hd), q.dtype),
        interpret=interpret,
    )(*operands)
    return out[:, :W]


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
