"""Pipeline parallelism: layer-stack sharding over the ``pipe`` mesh axis
(SURVEY.md §2.4 PP row — config-gated, 70B multi-host).

TPU-native GPipe-style collective pipelining, not a port of a
rank-per-process PP runtime:

- The stacked-layer param tree ([L, ...] leaves) and KV cache shard over
  ``pipe`` on the layer axis — each stage holds L/n contiguous layers.
  This is what makes a model that doesn't fit one device's HBM fit n.
- Inside one ``shard_map`` program, hidden states flow stage→stage with
  ``jax.lax.ppermute`` (neighbouring ICI hops); the batch is split into
  microbatches so stages overlap work (classic GPipe schedule: at step t,
  stage s processes microbatch t−s; fill+drain bubble = (n−1)/(n−1+M)).
- **Partial-manual shard_map** (``axis_names={"pipe"}``): only the pipe
  axis is manual; every other mesh axis (``data``, ``model``, ``expert``)
  stays automatic, so the Megatron TP sharding of the per-stage weights
  keeps working inside the stage body — XLA still inserts the per-layer
  all-reduce over ``model``, composing PP × TP without hand-written
  collectives.
- Embedding and the LM head run outside the pipelined region (handled by
  ``models/transformer.py::forward``, which dispatches its layer stack
  here whenever the serving mesh has a >1 ``pipe`` axis); the last stage's
  outputs are combined with a masked ``psum`` so every device returns the
  same activations — SPMD in, SPMD out.

Numerics match models/transformer.py::forward exactly (same _layer body);
parity is tested on the 8-virtual-device CPU mesh (tests/test_pipeline.py)
and through the serving engines (tests/test_mesh_serving.py).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.config import ModelConfig
from ..models.transformer import KVCache, _layer
from ..ops.norms import rms_norm
from ..ops.quant import qmatmul


def _pipe_shard(lp, h_mb, pos_mb, k, v, *, cfg: ModelConfig, axis: str,
                n_stages: int, n_micro: int, kv_limit: int, attn_impl: str):
    """Per-stage body. lp leaves [L_local, ...]; h_mb [M, Bm, S, D]
    (replicated); pos_mb [M, Bm, S]; k/v [L_local, B, S, KV, hd] — plain
    arrays or ``QuantKV`` pytrees (int8 payload + per-(pos, head) scales):
    every cache op below is a tree.map over leading axes only, so both
    layouts flow through identically and _layer's dense path handles the
    dequantize (VERDICT r4 item 2: int8 KV x pipe)."""
    stage = jax.lax.axis_index(axis)
    M, Bm, S, D = h_mb.shape
    batch_idx = jnp.arange(Bm)[:, None]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    tmap = jax.tree_util.tree_map

    outs0 = jax.lax.pcast(
        jnp.zeros((M, Bm, S, D), h_mb.dtype), axis, to="varying")
    state0 = jax.lax.pcast(
        jnp.zeros((Bm, S, D), h_mb.dtype), axis, to="varying")

    def run_local_layers(h, positions, m_lo, k, v):
        """Scan this stage's layers over microbatch rows [m_lo, m_lo+Bm)."""

        def body(h, xs):
            lp_l, k_l, v_l = xs
            k_mb = tmap(
                lambda a: jax.lax.dynamic_slice_in_dim(a, m_lo, Bm, 0), k_l)
            v_mb = tmap(
                lambda a: jax.lax.dynamic_slice_in_dim(a, m_lo, Bm, 0), v_l)
            # moe_impl="dense": the EP all-to-all can't nest under this
            # shard_map; the engine raises at startup if the operator
            # forced MOE_IMPL=ep onto a pipe mesh.
            h, k_mb, v_mb, _, _ = _layer(cfg, attn_impl, None, "dense",
                                   h, lp_l, k_mb, v_mb, positions,
                                   kv_limit, batch_idx, None)
            k_l = tmap(
                lambda a, u: jax.lax.dynamic_update_slice_in_dim(
                    a, u, m_lo, 0), k_l, k_mb)
            v_l = tmap(
                lambda a, u: jax.lax.dynamic_update_slice_in_dim(
                    a, u, m_lo, 0), v_l, v_mb)
            return h, (k_l, v_l)

        h, (k, v) = jax.lax.scan(body, h, (lp, k, v))
        return h, k, v

    def step(t, carry):
        outs, state, k, v = carry
        m = t - stage
        valid = (m >= 0) & (m < n_micro)
        m_c = jnp.clip(m, 0, n_micro - 1)
        h_in = jnp.where(stage == 0, h_mb[m_c], state)
        positions = pos_mb[m_c]
        h_out, k_new, v_new = run_local_layers(h_in, positions, m_c * Bm,
                                               k, v)
        # Invalid (bubble) iterations must not corrupt the cache or the
        # output buffer — their writes land on the clamped microbatch.
        k = tmap(lambda new, old: jnp.where(valid, new, old), k_new, k)
        v = tmap(lambda new, old: jnp.where(valid, new, old), v_new, v)
        outs = jnp.where(
            valid & (stage == n_stages - 1),
            jax.lax.dynamic_update_slice_in_dim(outs, h_out[None], m_c, 0),
            outs,
        )
        state = jax.lax.ppermute(h_out, axis, perm)
        return outs, state, k, v

    outs, _, k, v = jax.lax.fori_loop(
        0, n_stages + n_micro - 1, step, (outs0, state0, k, v)
    )
    # Only the last stage holds real outputs; everyone else contributes
    # zeros — the psum broadcasts the result to all stages (SPMD out).
    outs = jax.lax.psum(
        jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis
    )
    return outs, k, v


def pipeline_layers(
    layer_params,
    cfg: ModelConfig,
    h: jnp.ndarray,               # [B, S, D] embedded hidden states
    positions: jnp.ndarray,       # [B, S] int32 absolute positions
    k,                            # [L, B, S_alloc, KV, hd] cache keys
                                  # (plain array or QuantKV)
    v,                            # [L, B, S_alloc, KV, hd] cache values
                                  # (plain array or QuantKV)
    mesh: Mesh,
    *,
    axis: str = "pipe",
    microbatches: Optional[int] = None,
    kv_limit: int,
    attn_impl: str = "dense",
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run the stacked layer stack pipelined over ``axis``; the embedding /
    final-norm / LM-head stay with the caller (forward()). Returns
    ``(h_out [B, S, D], new_k, new_v)``.

    Requires n_layers divisible by the stage count. The microbatch count
    defaults to the largest divisor of B within the stage count (B=1 —
    e.g. a single-slot admission prefill — degrades to a sequential stage
    relay: correct, just bubble-bound).

    Only the ``pipe`` axis is manual here; ``data``/``model``/``expert``
    shardings on the inputs flow through automatically (PP × TP works; the
    Pallas flash/ragged kernels and ring attention do NOT compose with the
    stage body — callers pass attn_impl="dense").
    """
    n_stages = mesh.shape[axis]
    B, S, D = h.shape
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers {cfg.n_layers} must divide pipe stages {n_stages}"
        )
    if microbatches is None:
        M = max(m for m in range(1, min(n_stages, B) + 1) if B % m == 0)
    else:
        M = microbatches
    if B % M:
        raise ValueError(
            f"microbatch count {M} must divide the batch ({B})"
        )
    Bm = B // M
    h_mb = h.reshape(M, Bm, S, D)
    pos_mb = positions.reshape(M, Bm, S)

    layer_specs = jax.tree_util.tree_map(lambda _: P(axis), layer_params)
    # k/v may be QuantKV pytrees — every leaf (int8 payload AND scales)
    # stacks layers on axis 0, so one per-leaf P(axis) spec shards both.
    k_specs = jax.tree_util.tree_map(lambda _: P(axis), k)
    v_specs = jax.tree_util.tree_map(lambda _: P(axis), v)
    fn = jax.shard_map(
        partial(_pipe_shard, cfg=cfg, axis=axis, n_stages=n_stages,
                n_micro=M, kv_limit=kv_limit, attn_impl=attn_impl),
        mesh=mesh,
        in_specs=(layer_specs, P(), P(), k_specs, v_specs),
        out_specs=(P(), k_specs, v_specs),
        axis_names={axis},
    )
    outs, new_k, new_v = fn(layer_params, h_mb, pos_mb, k, v)
    return outs.reshape(B, S, D), new_k, new_v


def pipeline_forward(
    params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,          # [B, S] int32
    positions: jnp.ndarray,       # [B, S] int32 absolute positions
    cache: KVCache,
    mesh: Mesh,
    *,
    axis: str = "pipe",
    microbatches: Optional[int] = None,
    kv_limit: Optional[int] = None,
    attn_impl: str = "dense",
) -> Tuple[jnp.ndarray, KVCache]:
    """forward() with the layer stack pipelined over ``axis``.

    Same contract as models/transformer.py::forward (which calls
    pipeline_layers itself on a >1-pipe mesh; this wrapper remains the
    library-level entry point and the unit-test surface).
    """
    if kv_limit is None:
        kv_limit = cache.max_seq

    h = params["embed"][tokens]
    if cfg.embed_scale:
        h = h * jnp.asarray(cfg.dim ** 0.5, h.dtype)

    h, new_k, new_v = pipeline_layers(
        params["layers"], cfg, h, positions, cache.k, cache.v, mesh,
        axis=axis, microbatches=microbatches, kv_limit=kv_limit,
        attn_impl=attn_impl,
    )

    h = rms_norm(h, params["final_norm"], cfg.rms_eps, cfg.rms_offset)
    if cfg.tie_embeddings:
        logits = h @ params["embed"].astype(h.dtype).T
    else:
        logits = qmatmul(h, params["lm_head"])

    new_lengths = jnp.maximum(cache.lengths, positions.max(axis=1) + 1)
    return logits.astype(jnp.float32), KVCache(k=new_k, v=new_v,
                                               lengths=new_lengths)
