"""Ring attention: sequence-parallel causal attention over the ``seq`` axis.

The long-context story of the framework (SURVEY.md §5 long-context row; the
reference delegates all attention to a remote service, app.py:184, so this
component is created, not ported). TPU-first design:

- Q, K and V are sharded along the sequence dimension over the ``seq`` mesh
  axis (``shard_map``); each device holds one contiguous block. Peak memory
  per device is O(S/n), which is what makes contexts beyond one device's
  VMEM/HBM feasible at all.
- The K/V blocks travel around the ring with ``jax.lax.ppermute`` — on TPU
  this rides neighbouring ICI links, overlapping each hop with the local
  block's attention compute (the classic ring-attention schedule; see
  PAPERS.md long-sequence entries).
- Each device accumulates its queries' attention over every K/V block with
  the same online-softmax (running max ``m``, normalizer ``l``,
  accumulator ``acc``) the Pallas flash kernel uses
  (ops/flash_attention.py) — one pass, no S×S logits anywhere.
- Masking uses *absolute* positions carried alongside the K/V blocks, so
  causality is correct regardless of where a block currently sits in the
  ring, and ragged/offset layouts (prefix splicing) stay correct by
  construction.
- GQA/MQA: KV heads are shared across query-head groups via reshape, no
  materialized repetition.

Semantics match ops/attention.py::dense_attention with the causal mask
``kv_pos <= q_pos``; the parity test runs both on an 8-virtual-device CPU
mesh (tests/test_ring_attention.py).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def _block_attention(q, k, v, qpos, kpos, scale):
    """Online-softmax partial update for one K/V block.

    q: [B, Sq, H, hd]; k/v: [B, Sk, KV, hd]; qpos: [B, Sq]; kpos: [B, Sk].
    Returns the block's (m, l, acc) contribution in f32:
    m: [B, Sq, H, 1], l: [B, Sq, H, 1], acc: [B, Sq, H, hd].
    """
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV  # query heads per KV head

    qg = q.reshape(B, Sq, KV, G, hd)
    # scores [B, Sq, KV, G, Sk] — bf16 inputs, f32 accumulation (MXU-native)
    s = jnp.einsum("bqkgd,bskd->bqkgs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    mask = (kpos[:, None, :] <= qpos[:, :, None])[:, :, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)

    m = jnp.max(s, axis=-1, keepdims=True)                 # [B,Sq,KV,G,1]
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)                 # [B,Sq,KV,G,1]
    acc = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return (
        m.reshape(B, Sq, H, 1),
        l.reshape(B, Sq, H, 1),
        acc.reshape(B, Sq, H, hd),
    )


def _merge(m1, l1, acc1, m2, l2, acc2):
    """Combine two online-softmax partial states (flash-attention merge)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.where(m1 == -jnp.inf, 0.0, jnp.exp(m1 - m))
    a2 = jnp.where(m2 == -jnp.inf, 0.0, jnp.exp(m2 - m))
    return m, l1 * a1 + l2 * a2, acc1 * a1 + acc2 * a2


def _ring_shard(q, k, v, qpos, kpos, *, axis: str, scale: float):
    """Per-device body: rotate K/V blocks around the ring, accumulating
    this device's queries' attention with online softmax."""
    B, Sq, H, hd = q.shape
    n = jax.lax.psum(1, axis)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # pcast: the accumulator starts as a constant but becomes device-varying
    # after the first block — mark it so shard_map's carry typing agrees.
    m0 = jax.lax.pcast(
        jnp.full((B, Sq, H, 1), -jnp.inf, jnp.float32), axis, to="varying")
    l0 = jax.lax.pcast(
        jnp.zeros((B, Sq, H, 1), jnp.float32), axis, to="varying")
    acc0 = jax.lax.pcast(
        jnp.zeros((B, Sq, H, hd), jnp.float32), axis, to="varying")

    def step(i, carry):
        m, l, acc, k, v, kpos = carry
        bm, bl, bacc = _block_attention(q, k, v, qpos, kpos, scale)
        m, l, acc = _merge(m, l, acc, bm, bl, bacc)

        # Rotate the K/V block (and its absolute positions) one hop. XLA
        # overlaps the ppermute with this iteration's compute on ICI (the
        # rotation reads the same k/v the block attention reads). The last
        # iteration skips the hop — its rotation output would be discarded.
        def rot(ops):
            return tuple(jax.lax.ppermute(o, axis, perm) for o in ops)

        k, v, kpos = jax.lax.cond(i < n - 1, rot, lambda ops: ops,
                                  (k, v, kpos))
        return m, l, acc, k, v, kpos

    m, l, acc, _, _, _ = jax.lax.fori_loop(
        0, n, step, (m0, l0, acc0, k, v, kpos)
    )
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows output 0, not NaN
    return (acc / l).astype(q.dtype)


def ring_attention(
    q: jnp.ndarray,        # [B, S, H, hd], sharded over `axis` on dim 1
    k: jnp.ndarray,        # [B, S, KV, hd], same sharding
    v: jnp.ndarray,        # [B, S, KV, hd]
    positions: jnp.ndarray,  # [B, S] absolute positions, same sharding
    mesh: Mesh,
    *,
    axis: str = "seq",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Causal self-attention with the sequence sharded over ``axis``.

    Every device holds S/n of the sequence; K/V blocks rotate over the ring
    so no device ever materializes the full context. Output shards match
    the query sharding. Requires S divisible by the axis size (pad prompts
    to a bucket, as the engine already does for prefill).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by {axis} axis size {n}"
        )
    spec4 = P(None, axis, None, None)
    spec2 = P(None, axis)
    fn = jax.shard_map(
        partial(_ring_shard, axis=axis, scale=scale),
        mesh=mesh,
        in_specs=(spec4, spec4, spec4, spec2, spec2),
        out_specs=spec4,
    )
    return fn(q, k, v, positions, positions)
