"""PartitionSpec policy: how params, KV cache, and activations shard.

This file is the whole "distributed backend" of the framework in the sense
SURVEY.md §2.4 describes: sharding annotations are the comm API; XLA derives
the collectives. The policy is Megatron-style tensor parallelism expressed
as specs over the stacked-layer param tree of models/transformer.py:

- attention:  wq/wk/wv column-parallel (heads split over ``model``),
              wo row-parallel — one reduce-scatter/all-gather pair per layer,
              riding ICI.
- MLP:        w_gate/w_up column-parallel, w_down row-parallel.
- MoE:        experts split over ``expert``; within an expert the same
              column/row split over ``model``.
- embeddings: vocab-sharded (output logits gather over ``model`` only at the
              sampling step).
- KV cache:   batch over ``data``, kv-heads over ``model`` (decode attention
              is then fully local per TP shard until the wo reduce).

Every spec is passed through :func:`sanitize_spec`, which drops any mesh
axis that does not evenly divide the corresponding dimension — so the same
policy serves Gemma-2B (1 KV head → KV replicated under TP) through
Llama-3-70B (8 KV heads → KV sharded 8-way) without special cases.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.config import ModelConfig

logger = logging.getLogger(__name__)

Params = Dict[str, Any]


def sanitize_spec(mesh: Mesh, spec: P, shape: tuple) -> P:
    """Drop spec axes that don't divide their dimension (→ replicate there).

    Keeps one policy valid across model families: e.g. sharding KV heads
    over a model axis of 8 is a no-op for Gemma-2B's single KV head.
    """
    out = []
    for dim, names in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if names is None:
            out.append(None)
            continue
        group = names if isinstance(names, tuple) else (names,)
        prod = _axes_prod(mesh, group)
        if prod and dim % prod == 0:
            out.append(names)
        else:
            out.append(None)
    return P(*out)


def _axes_prod(mesh: Mesh, axes: tuple) -> int:
    p = 1
    for a in axes:
        p *= mesh.shape[a]
    return p


def param_specs(cfg: ModelConfig) -> Params:
    """PartitionSpec tree matching models/transformer.py::init_params.

    Leading axis of every layer param is the stacked ``n_layers`` axis:
    sharded over ``pipe`` (each pipeline stage holds L/pp contiguous
    layers — the memory win that fits a 70B across stages). On meshes
    without a >1 pipe axis that factor is a no-op and ``lax.scan``
    iterates the full stack as before.
    """
    layers: Params = {
        "attn_norm": P("pipe"),
        "wq": P("pipe", None, "model"),
        "wk": P("pipe", None, "model"),
        "wv": P("pipe", None, "model"),
        "wo": P("pipe", "model", None),
        "mlp_norm": P("pipe"),
    }
    if cfg.is_moe:
        layers.update(
            router=P("pipe"),
            w_gate=P("pipe", "expert", None, "model"),
            w_up=P("pipe", "expert", None, "model"),
            w_down=P("pipe", "expert", "model", None),
        )
    else:
        layers.update(
            w_gate=P("pipe", None, "model"),
            w_up=P("pipe", None, "model"),
            w_down=P("pipe", "model", None),
        )
    specs: Params = {
        "embed": P("model", None),
        "layers": layers,
        "final_norm": P(),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model")
    return specs


def cache_specs(cfg: ModelConfig) -> Dict[str, P]:
    """KVCache sharding: [L, B, S, KV, hd] — layers over pipe (each
    pipeline stage holds only its own layers' KV), batch over data, KV
    heads over model (local decode attention per TP shard)."""
    kv = P("pipe", "data", None, "model", None)
    return {"k": kv, "v": kv, "lengths": P("data")}


def pool_cache_specs(cfg: ModelConfig) -> Dict[str, P]:
    """Block-paged pool KVCache sharding: [L, n_blocks, page, KV, hd]
    shards on the KV-head axis over ``model`` exactly like dense KV —
    decode attention stays fully local per TP shard until the wo reduce.
    The block axis NEVER shards: blocks are a shared structure across
    slots (any slot's table may map any block), so slots-over-``data``
    does not apply — the engine falls back to the dense ladder on
    meshes with a >1 data/pipe/seq axis (engine/batcher.py,
    ``kv_pool_mesh_fallback``)."""
    kv = P(None, None, None, "model", None)
    return {"k": kv, "v": kv, "lengths": P()}


def draft_cache_specs(cfg: ModelConfig) -> Dict[str, P]:
    """Draft-world KVCache sharding (ISSUE 18): the 2B's dense per-slot
    [L2, N, S_alloc, KV2, hd] cache shards on the KV-head axis over
    ``model`` exactly like the target's ``cache_specs``, batch (slots)
    over ``data``. No pipe factor — the draft stack is never pipelined
    (it rides the tp/ep mesh whole). When the draft's KV heads don't
    divide the model axis (gemma-2b-it's single KV head under tp=8),
    ``sanitize_spec`` drops the axis and the cache replicates — the
    gather fallback ``draft_kv_fallback`` reports."""
    kv = P(None, "data", None, "model", None)
    return {"k": kv, "v": kv, "lengths": P("data")}


def draft_kv_fallback(mesh: Optional[Mesh], cfg: ModelConfig) -> bool:
    """True when the draft's KV-head axis does NOT divide the mesh's
    ``model`` axis, i.e. the draft KV cache serves replicated (each TP
    shard holds the full draft KV and the draft attention runs
    gathered). Correct but off the shard-local fast path — surfaced in
    /health's spec/sharding sections so a fleet can see which replicas
    pay the gather."""
    if (mesh is None or "model" not in mesh.axis_names
            or mesh.shape["model"] <= 1):
        return False
    return cfg.n_kv_heads % mesh.shape["model"] != 0


def shard_draft_cache(cache, mesh: Mesh, cfg: ModelConfig):
    """device_put the draft's dense KVCache onto the mesh per
    ``draft_cache_specs`` (divisibility-sanitized per leaf, so the
    single-KV-head 2B under tp=8 lands replicated rather than erroring).
    QuantKV is deliberately not special-cased: the draft cache is kept
    in the serving dtype (KV_QUANT applies to the target pool only)."""
    from ..models.transformer import KVCache

    specs = draft_cache_specs(cfg)

    def _put(a, spec):
        return jax.device_put(
            a, NamedSharding(mesh, sanitize_spec(mesh, spec, a.shape)))

    return KVCache(
        k=_put(cache.k, specs["k"]),
        v=_put(cache.v, specs["v"]),
        lengths=_put(cache.lengths, specs["lengths"]),
    )


def residual_spec(mesh: Mesh, shape: tuple) -> Optional[P]:
    """Where the [B, S, d] residual's TP factor lands under f≈1
    residual-path sharding (ISSUE 14): the batch axis when data×model
    divides B (the decode shape — norms, RoPE epilogues, residual adds
    and sampling scratch then run 1/tp-sized per shard, and XLA fuses
    the row-parallel GEMM all-reduce into a reduce-scatter at its
    output plus one all-gather at the next column-parallel input), else
    the sequence axis (prefill's B==1), else None — the mesh keeps the
    classic replicated-residual Megatron layout there.

    Gated off pipe/expert meshes: the pipeline stage body owns its own
    activation layout, and the EP all-to-all dispatch re-shards tokens
    over ``expert`` itself."""
    if (mesh is None or "model" not in mesh.axis_names
            or mesh.shape["model"] <= 1 or mesh.shape["pipe"] > 1
            or mesh.shape["expert"] > 1):
        return None
    B, S = shape[0], shape[1]
    batch = sanitize_spec(mesh, P(("data", "model"),), (B,))
    if batch[0] is not None:
        return P(("data", "model"), None, None)
    seq = sanitize_spec(mesh, P("model"), (S,))
    if S > 1 and seq[0] is not None:
        d_ax = ("data",) if B % max(1, mesh.shape["data"]) == 0 \
            and mesh.shape["data"] > 1 else None
        return P(d_ax[0] if d_ax else None, "model", None)
    return None


def logits_spec(mesh: Mesh, vocab: int) -> Optional[P]:
    """[B, S, vocab] logits sharding under f≈1: the vocab axis over
    ``model`` (the LM head is vocab-sharded, so the head's output never
    materializes replicated and the sampling chain's vocab-sized
    scratch shards with it). None when the vocab doesn't divide or the
    residual policy is off for this mesh."""
    if (mesh is None or "model" not in mesh.axis_names
            or mesh.shape["model"] <= 1 or mesh.shape["pipe"] > 1
            or mesh.shape["expert"] > 1):
        return None
    if vocab % mesh.shape["model"]:
        return None
    return P(None, None, "model")


def residual_fraction(mesh: Optional[Mesh], batch: int, dim: int) -> float:
    """The TP-shardable residual fraction f the active policy achieves
    at the decode shape [batch, 1, dim] — 1.0 when the residual
    batch-shards over data×model, else 0.0 (classic replicated
    residual). Surfaced in /health's sharding section so the operator
    can see which layout the serving config actually runs."""
    if mesh is None:
        return 0.0
    spec = residual_spec(mesh, (batch, 1, dim))
    if spec is None:
        return 0.0
    first = spec[0]
    group = first if isinstance(first, tuple) else (first,)
    return 1.0 if "model" in group else 0.0


def token_spec() -> P:
    """[B, S] token/position arrays: batch over data."""
    return P("data", None)


def param_shardings(params: Params, mesh: Mesh, cfg: ModelConfig) -> Params:
    """The ``NamedSharding`` of every leaf of ``params`` under the policy
    (``param_specs`` through ``sanitize_spec``, leaf by leaf), as a tree
    of the same structure. ``params`` may hold arrays or only their
    shapes (``jax.eval_shape``): ``shard_params`` places a live tree by
    it, and the seeded generator (ops/quant.py::
    random_params_int8_sharded) compiles its outputs to it, so a tree
    made on the mesh and a tree moved onto it cannot differ in layout.
    Int8-quantized weights (ops/quant.py::QuantInt8) shard their payload
    with the original weight's spec; the per-output-channel scales
    follow it (size-1 axes sanitize to replicated, the channel axis
    inherits the sharding; an int4 packed out/2 axis or a group-count
    axis that no longer divides under TP drops its mesh axis)."""
    import dataclasses as _dc

    from ..ops.quant import QuantInt8, QuantInt8W8A8
    from ..ops.quant4 import QuantInt4

    qtypes = (QuantInt8, QuantInt8W8A8, QuantInt4)

    def _named(leaf, spec):
        return NamedSharding(mesh, sanitize_spec(mesh, spec, leaf.shape))

    def _of(leaf, spec):
        if isinstance(leaf, qtypes):
            return _dc.replace(leaf, q=_named(leaf.q, spec),
                               scale=_named(leaf.scale, spec))
        return _named(leaf, spec)

    return jax.tree_util.tree_map(
        _of, params, param_specs(cfg),
        is_leaf=lambda x: isinstance(x, qtypes),
    )


def shard_params(params: Params, mesh: Mesh, cfg: ModelConfig) -> Params:
    """device_put the param tree onto the mesh per the policy
    (``param_shardings``: divisibility sanitization per leaf, quantized
    payload and scales by the original weight's spec)."""
    return jax.device_put(params, param_shardings(params, mesh, cfg))


def shard_cache(cache, mesh: Mesh, cfg: ModelConfig):
    """device_put a KVCache onto the mesh. int8 KV blocks
    (ops/quant.py::QuantKV) place the payload with the full KV spec and
    the per-(position, head) scales with the same spec minus the trailing
    head_dim axis — ``sanitize_spec`` zips spec entries against the
    4-dim scale shape, so the hd entry simply drops off."""
    from ..models.transformer import KVCache

    specs = cache_specs(cfg)

    def _put_kv(block, spec):
        from ..ops.quant import QuantKV

        def put(a):
            return jax.device_put(
                a, NamedSharding(mesh, sanitize_spec(mesh, spec, a.shape)))

        if isinstance(block, QuantKV):
            return QuantKV(q=put(block.q), s=put(block.s))
        return put(block)

    return KVCache(
        k=_put_kv(cache.k, specs["k"]),
        v=_put_kv(cache.v, specs["v"]),
        lengths=jax.device_put(
            cache.lengths,
            NamedSharding(mesh, sanitize_spec(mesh, specs["lengths"], cache.lengths.shape)),
        ),
    )


def pool_cache_shardings(cache, mesh: Mesh, cfg: ModelConfig):
    """The ``NamedSharding`` of every leaf of a block-paged pool KVCache
    (arrays or only their shapes): KV heads over ``model``, everything
    else replicated (``pool_cache_specs``). QuantKV leaves place the
    int8 payload with the full spec and the per-(block, page-row, head)
    scales with the same spec minus the trailing head_dim axis — same
    zip rule as ``shard_cache``. ``shard_pool_cache`` moves a pool by
    it; the engine makes its pool on the mesh by it
    (engine/batcher.py::_new_pool_cache), so a pool that does not fit
    one device never has to."""
    from ..models.transformer import KVCache
    from ..ops.quant import QuantKV

    specs = pool_cache_specs(cfg)

    def _named(a, spec):
        return NamedSharding(mesh, sanitize_spec(mesh, spec, a.shape))

    def _kv(block, spec):
        if isinstance(block, QuantKV):
            return QuantKV(q=_named(block.q, spec), s=_named(block.s, spec))
        return _named(block, spec)

    return KVCache(k=_kv(cache.k, specs["k"]), v=_kv(cache.v, specs["v"]),
                   lengths=NamedSharding(mesh, P()))


def shard_pool_cache(cache, mesh: Mesh, cfg: ModelConfig):
    """device_put a block-paged pool KVCache onto the mesh
    (``pool_cache_shardings``)."""
    return jax.device_put(cache, pool_cache_shardings(cache, mesh, cfg))


def replicate(arr, mesh: Mesh):
    """device_put an array fully replicated on the mesh — block tables
    and grammar tables ride dispatches as plain arguments and must be
    committed to the replicated layout their compiled programs expect
    (an uncommitted array would at best reshard per dispatch)."""
    return jax.device_put(arr, NamedSharding(mesh, P()))


def shard_tokens(tokens, mesh: Mesh):
    return jax.device_put(
        tokens, NamedSharding(mesh, sanitize_spec(mesh, token_spec(), tokens.shape))
    )
