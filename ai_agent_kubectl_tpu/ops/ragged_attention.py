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
  scalar-prefetched into the K/V index map, so the kernel streams pages
  of that layer straight out of the stacked buffer: a ``pool[layer]``
  slice in front of the call would copy the layer every pass (ISSUE 25).
- ``q_lens``       [N] int32 — 0 freezes a slot (output rows are zeros,
  compute masked); 1 = decode; k+1 = verify; span = prefill.
- ``positions``    [N] int32 — absolute position of query column 0.
- ``block_tables`` [N, max_pages] int32 — pool block per sequence page;
  entries >= n_blocks are the unmapped-page sentinel.

TPU-first design: grid ``(slot, query tile, page)`` with
positions + query lengths + tables scalar-prefetched, dead pages clamped
to the tile's LAST LIVE page in the BlockSpec index map (repeat block
indices elide the HBM→VMEM fetch, ``pl.when`` elides the compute),
online softmax state persisted in VMEM scratch across the sequential
page dimension. The window is cut into query tiles of ``_q_tile``
columns so the VMEM working set depends on the head geometry alone,
never on W: Mosaic refused every admission width above 64 when a grid
cell held the whole window (scoped-VMEM limit, v5e). Tiles wholly past
``q_lens[n]`` cost neither fetches nor compute. Causal-in-window
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
    its index map picks the layer — no ``cache[layer]`` copy in front of
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


def _last_live_page(pos, q_len, q0, tq: int, page_size: int):
    """Last KV page any valid column of the tile starting at window
    column ``q0`` attends: the page of its last valid column's own
    freshly-written row. Shared by the kernel's compute gate and the
    index map's fetch clamp so the two never disagree."""
    hi = jnp.maximum(jnp.minimum(q0 + tq, q_len), 1)
    return (pos + hi - 1) // page_size


def _ragged_pool_kernel(pos_ref, qlen_ref, tbl_ref, lyr_ref, q_ref, k_ref,
                        v_ref, o_ref, m_scr, l_scr, acc_scr, *, page_size: int,
                        scale: float, n_pages: int, kv_heads: int,
                        tq: int):
    """Online-softmax body over one (slot, query tile, page) grid cell,
    ``tq`` query columns at a time. Rows are laid out [KV, tq*G] (row r
    is tile column ``r // G`` of KV group ``r % G``'s block) so one
    KV-batched ``dot_general`` serves every query column and head of
    the block."""
    del tbl_ref, lyr_ref              # consumed by the index map
    n = pl.program_id(0)
    q0 = pl.program_id(1) * tq        # window column of tile row 0
    p = pl.program_id(2)
    pos = pos_ref[n]
    q_len = qlen_ref[n]
    last_page = _last_live_page(pos, q_len, q0, tq, page_size)

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(p <= last_page, q0 < q_len))
    def _accumulate():
        H, hd = q_ref.shape[2], q_ref.shape[3]
        G = H // kv_heads
        # [tq, H, hd] -> [KV, tq*G, hd]: head h of column j lands at row
        # j*G + h%G of KV group h//G — query column recoverable as
        # q0 + row // G for the causal mask below.
        qg = jnp.swapaxes(
            q_ref[0].reshape(tq, kv_heads, G, hd), 0, 1
        ).reshape(kv_heads, tq * G, hd)
        k = jnp.swapaxes(k_ref[0], 0, 1)                # [KV, page, hd]
        v = jnp.swapaxes(v_ref[0], 0, 1)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                       # [KV, tq*G, page]
        kv_ids = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2
        )
        q_ids = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // G
        # Causal-in-window: column j attends kv <= pos + j; padded
        # columns (j >= q_len) mask everything — their normalizer stays
        # 0 and the finalize writes zeros (outputs are never read).
        mask = jnp.logical_and(kv_ids <= pos + q_ids, q_ids < q_len)
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

    @pl.when(p == n_pages - 1)
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
    *,
    page_size: int = 128,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Ragged block-paged attention over the pool. Returns
    [N, W, H, hd]; rows past ``q_lens[n]`` are zeros (never read —
    ``logits_at`` gathers the last valid column).

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

    kernel = functools.partial(
        _ragged_pool_kernel, page_size=page_size, scale=scale,
        n_pages=n_pages, kv_heads=KV, tq=tq,
    )

    def q_map(n, t, p, pos_ref, qlen_ref, tbl_ref, lyr_ref):
        return (n, t, 0, 0)

    def kv_map(n, t, p, pos_ref, qlen_ref, tbl_ref, lyr_ref):
        # Clamp dead pages to the tile's LAST LIVE page (which covers
        # its own freshly-written rows), then indirect through the
        # table — repeat block indices elide the fetch, pl.when elides
        # the compute.
        last = _last_live_page(pos_ref[n], qlen_ref[n], t * tq, tq,
                               page_size)
        return (lyr_ref[0], tbl_ref[n, jnp.minimum(p, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(N, n_qt, n_pages),
        in_specs=[
            pl.BlockSpec((1, tq, H, hd), q_map),
            pl.BlockSpec((None, 1, page_size, KV, hd), kv_map),
            pl.BlockSpec((None, 1, page_size, KV, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, tq, H, hd), q_map),
        scratch_shapes=[
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
    )(pos, qln, tbl, lyr, q, k, v)
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
