"""Pallas paged decode attention (SURVEY.md §2.2 row 2; PAPERS.md ragged
paged attention).

Decode-step attention where the cost per slot tracks that slot's LIVE
pages, not the allocated cache span: replaces the dense-over-bucket decode
path (engine/batcher.py KV ladder), whose cost is the max live length over
the whole batch, with true per-slot raggedness at ``page_size``
granularity.

TPU-first design (not a CUDA port — block tables and gather kernels are a
GPU idiom):

- The KV cache stays **contiguous per slot** ([N, S, KV, hd]); a "page" is
  an aligned S-range. Paging here is about *I/O and compute skipping*, not
  storage indirection — on TPU the win is reading only live pages, and
  contiguous layout keeps every other consumer (splice, prefix cache,
  dense fallback) a plain slice.
- Grid ``(slot, page)`` with ``positions`` scalar-prefetched. Pages past a
  slot's live length have their BlockSpec index **clamped to the last live
  page**: consecutive identical block indices elide the HBM→VMEM fetch
  (Mosaic pipelines skip repeat fetches), and ``pl.when`` skips their
  compute — dead pages cost neither bandwidth nor FLOPs.
- One program handles every KV head of its (slot, page) block via
  KV-batched ``dot_general`` — blocks keep the cache's native
  ``[page, KV, hd]`` layout (no transposed copy of the cache), and the
  kernel's working set stays a few hundred KB of VMEM.
- Online softmax (running max / normalizer / accumulator in VMEM scratch,
  persisted across the sequential page dimension of the grid) — the same
  merge the flash kernel and ring attention use; one pass, no S×S logits.

Semantics match ops/attention.py::dense_attention for a single query per
slot at absolute position ``positions[n]`` over ``k/v[n, :positions[n]+1]``
(causal: kv_pos <= q_pos). Interpret mode runs the same kernel on CPU for
tests.
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


def paged_supported(page_size: int, head_dim: int, n_pages: int) -> bool:
    """Compiled-kernel constraints: lanes want a 128-multiple head dim and
    a sublane-tileable page."""
    return head_dim % 128 == 0 and page_size >= 8 and n_pages >= 1


def stacked_kv(k, v, layer):
    """(k, v, layer [1] int32) with K/V as a layer stack: a stacked cache
    comes with its traced ``layer`` index, a bare layer becomes a
    one-layer stack (a free reshape), so each kernel has one form and
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


def _paged_kernel(pos_ref, lyr_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page_size: int, scale: float,
                  n_pages: int, kv_heads: int):
    del lyr_ref                       # consumed by the index map
    n = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[n]
    last_page = pos // page_size

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(p <= last_page)
    def _accumulate():
        H, hd = q_ref.shape[1], q_ref.shape[2]
        G = H // kv_heads
        qg = q_ref[0].reshape(kv_heads, G, hd)
        k = jnp.swapaxes(k_ref[0], 0, 1)                    # [KV, page, hd]
        v = jnp.swapaxes(v_ref[0], 0, 1)
        s = jax.lax.dot_general(
            qg, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale                                           # [KV, G, page]
        kv_ids = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2
        )
        mask = kv_ids <= pos
        s = jnp.where(mask, s, -jnp.inf)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.where(m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_new))
        m_scr[...] = m_new
        l_scr[...] = l_prev * alpha + jnp.sum(pexp, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                   # [KV, G, hd]

    @pl.when(p == n_pages - 1)
    def _finalize():
        H, hd = o_ref.shape[1], o_ref.shape[2]
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).reshape(H, hd).astype(o_ref.dtype)


def _paged_pool_kernel(pos_ref, tbl_ref, lyr_ref, q_ref, k_ref, v_ref,
                       o_ref, m_scr, l_scr, acc_scr, *, page_size: int,
                       scale: float, n_pages: int, kv_heads: int):
    """Block-table variant: identical online-softmax body, but the KV
    blocks arrive via the table-indirected index map (``tbl_ref`` is
    consumed there, not here). Kept separate so the contiguous-cache
    kernel's signature stays frozen."""
    del tbl_ref
    _paged_kernel(pos_ref, lyr_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                  l_scr, acc_scr, page_size=page_size, scale=scale,
                  n_pages=n_pages, kv_heads=kv_heads)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret"),
)
def paged_decode_attention_pool(
    q: jnp.ndarray,            # [N, H, hd]  one decode query per slot
    k: jnp.ndarray,            # [n_blocks, page, KV, hd]  one layer, or
    v: jnp.ndarray,            # [L, n_blocks, page, KV, hd] with ``layer``
    positions: jnp.ndarray,    # [N] int32 absolute query positions
    block_tables: jnp.ndarray,  # [N, max_pages] int32 pool block per page
    layer=None,                # int32 scalar: layer of a stacked pool
    *,
    page_size: int = 128,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Block-paged ragged decode attention (ISSUE 10): the kernel shape
    of ``paged_decode_attention`` extended from "contiguous pages per
    slot" to block-table indirection — slot n's page p streams from pool
    block ``block_tables[n, p]``. Pages past a slot's live length clamp
    to its last live block (repeat fetches elide, ``pl.when`` skips the
    compute), so cost still tracks live pages per slot. Sentinel table
    entries (>= n_blocks) additionally clamp to a valid block — they can
    only be reached by dead pages, whose compute is skipped anyway.

    Returns [N, H, hd]; semantics match dense attention over the
    gathered per-slot view (models/transformer.py::_pool_gather)."""
    if pltpu is None:
        raise NotImplementedError(
            "paged_decode_attention_pool requires "
            "jax.experimental.pallas.tpu; use the dense gather path"
        )
    N, H, hd = q.shape
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
    pos = positions.astype(jnp.int32)
    tbl = jnp.clip(block_tables.astype(jnp.int32), 0, n_blocks - 1)

    kernel = functools.partial(
        _paged_pool_kernel, page_size=page_size, scale=scale,
        n_pages=n_pages, kv_heads=KV,
    )

    def q_map(n, p, pos_ref, tbl_ref, lyr_ref):
        return (n, 0, 0)

    def kv_map(n, p, pos_ref, tbl_ref, lyr_ref):
        # Clamp dead pages to the slot's last live page, then indirect
        # through the table: the repeated block index elides the fetch,
        # pl.when elides the compute.
        pp = jnp.minimum(p, pos_ref[n] // page_size)
        return (lyr_ref[0], tbl_ref[n, pp], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, hd), q_map),
            pl.BlockSpec((None, 1, page_size, KV, hd), kv_map),
            pl.BlockSpec((None, 1, page_size, KV, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, H, hd), q.dtype),
        interpret=interpret,
    )(pos, tbl, lyr, q, k, v)
    return out


def paged_decode_attention_pool_sharded(
    q: jnp.ndarray,            # [N, H, hd]
    k: jnp.ndarray,            # [n_blocks, page, KV, hd], or the
    v: jnp.ndarray,            # stacked [L, ...] pool with ``layer``
    positions: jnp.ndarray,    # [N]
    block_tables: jnp.ndarray,  # [N, max_pages]
    mesh,
    layer=None,
    *,
    page_size: int = 128,
) -> jnp.ndarray:
    """Mesh-aware pool kernel dispatch (ISSUE 14): XLA can't
    auto-partition a ``pallas_call``, so under a >1 ``model`` axis the
    block-table kernel runs shard_mapped with Q and KV heads split
    together over ``model`` — the pool shards on the KV-head axis
    (parallel/sharding.py::pool_cache_specs), so each shard holds whole
    KV groups and the kernel's local G = H_local/KV_local stays the
    true grouping. Positions and tables are replicated (they are
    per-slot host truth). Falls back to the unsharded call when the
    head counts don't divide the axis (the gather/dense path serves
    those meshes instead — engine startup picks it)."""
    tp = mesh.shape["model"] if mesh is not None else 1
    H, KV = q.shape[1], k.shape[-2]
    if tp <= 1:
        return paged_decode_attention_pool(q, k, v, positions,
                                           block_tables, layer,
                                           page_size=page_size)
    if KV % tp or H % tp:
        raise ValueError(
            f"pool paged kernel needs KV ({KV}) and H ({H}) divisible "
            f"by the model axis ({tp}); engine startup resolves such "
            f"meshes to the gather path")
    import jax.sharding as jsh

    P_ = jsh.PartitionSpec

    def _local(ql, kl, vl, pos, tbl, lyr):
        return paged_decode_attention_pool(ql, kl, vl, pos, tbl, lyr[0],
                                           page_size=page_size)

    # One form through the shard_map: a layer stack (whole on every
    # shard) and its replicated index.
    k, v, lyr = stacked_kv(k, v, layer)
    kv_spec = P_(None, None, None, "model", None)
    return jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P_(None, "model", None), kv_spec, kv_spec,
                  P_(None), P_(None, None), P_(None)),
        out_specs=P_(None, "model", None),
        axis_names=set(mesh.axis_names),
        # pallas_call can't express per-axis varying metadata for the
        # VMA checker; the specs above are the contract (same rule as
        # the dense-path shard_map in models/transformer.py).
        check_vma=False,
    )(q, k, v, positions, block_tables, lyr)


@functools.partial(
    jax.jit,
    static_argnames=("page_size", "scale", "interpret"),
)
def paged_decode_attention(
    q: jnp.ndarray,          # [N, H, hd]  one decode query per slot
    k: jnp.ndarray,          # [N, S, KV, hd]  slot caches (abs positions),
    v: jnp.ndarray,          # or the stacked [L, N, S, KV, hd] with ``layer``
    positions: jnp.ndarray,  # [N] int32 absolute query positions
    layer=None,              # int32 scalar: layer of a stacked cache
    *,
    page_size: int = 128,
    scale: Optional[float] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Per-slot ragged decode attention. Returns [N, H, hd].

    Each slot reads only ``ceil((positions[n]+1)/page_size)`` KV pages.
    Requires S divisible by page_size (pad the cache allocation)."""
    if pltpu is None:
        # The grid spec and VMEM scratch below are TPU-pallas APIs even in
        # interpret mode; without them the kernel cannot run anywhere.
        raise NotImplementedError(
            "paged_decode_attention requires jax.experimental.pallas.tpu "
            "(unavailable in this JAX install); use the dense decode path "
            "(DECODE_ATTN=dense)"
        )
    N, H, hd = q.shape
    k, v, lyr = stacked_kv(k, v, layer)
    S, KV = k.shape[2], k.shape[3]
    if S % page_size:
        raise ValueError(f"cache span {S} not divisible by page {page_size}")
    n_pages = S // page_size
    if scale is None:
        scale = hd ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    G = H // KV
    pos = positions.astype(jnp.int32)

    kernel = functools.partial(
        _paged_kernel, page_size=page_size, scale=scale, n_pages=n_pages,
        kv_heads=KV,
    )

    def q_map(n, p, pos_ref, lyr_ref):
        return (n, 0, 0)

    def kv_map(n, p, pos_ref, lyr_ref):
        # Clamp dead pages to the last live page: the repeated block index
        # elides the fetch, pl.when elides the compute.
        return (lyr_ref[0], n, jnp.minimum(p, pos_ref[n] // page_size), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N, n_pages),
        in_specs=[
            pl.BlockSpec((1, H, hd), q_map),
            pl.BlockSpec((None, 1, page_size, KV, hd), kv_map),
            pl.BlockSpec((None, 1, page_size, KV, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, H, hd), q.dtype),
        interpret=interpret,
    )(pos, lyr, q, k, v)
    return out
