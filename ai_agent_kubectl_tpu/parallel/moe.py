"""Mixture-of-Experts MLP: dense reference + expert-parallel dispatch.

``dense_moe`` evaluates every expert and mixes by router weights — O(E)
FLOPs but correct for any batch and trivially shardable; it is the
numerical reference for the EP path and what small/test configs use.

``expert_parallel_moe`` is the scaled version (SURVEY.md §2.4 EP row;
BASELINE config 4, Mixtral-8x7B over ICI): experts are sharded over the
``expert`` mesh axis, tokens are sharded over the same axis, and each
token's top-k expert computations happen on the device owning the expert —
GShard-style capacity-bounded dispatch/combine with two
``jax.lax.all_to_all`` collectives riding ICI. FLOPs per token are O(k),
not O(E).

Routing follows Mixtral (top-k over router logits, softmax *after*
selection, renormalized over the selected experts).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.config import ModelConfig


def router_weights(cfg: ModelConfig, logits: jnp.ndarray):
    """Top-k routing. logits [..., E] -> (mix [..., E], idx [..., k]).

    ``mix`` is dense over E with zeros off the top-k — dense mixing keeps
    the op jit-friendly (no ragged gathers) and maps to pure VPU work.
    """
    k = cfg.experts_per_token
    top_vals, top_idx = jax.lax.top_k(logits, k)                  # [..., k]
    top_w = jax.nn.softmax(top_vals.astype(jnp.float32), axis=-1)  # renorm over k
    mix = jnp.zeros(logits.shape, dtype=jnp.float32)
    mix = jnp.put_along_axis(mix, top_idx, top_w, axis=-1, inplace=False)
    return mix, top_idx


def _qeinsum(spec: str, x: jnp.ndarray, w, scale_shape: str) -> jnp.ndarray:
    """einsum with an optionally int8-quantized RHS ([E, in, out] with
    per-(expert, out-channel) scales [E, 1, out]). The dequant multiply
    sits in the einsum epilogue in f32 — same contract as
    ops/quant.py::qmatmul, so only int8 bytes cross HBM for the expert
    weights. ``scale_shape`` tells how to broadcast the [E, out] scales
    onto the result: "ef_last2" for results [..., E, out] (dense_moe's
    [B, S, E, F]) or "e_lead" for results [E, ..., out] (the EP shard's
    [E_local, C, out])."""
    from ..ops.quant import QuantInt8

    if not isinstance(w, QuantInt8):
        return jnp.einsum(spec, x, w)
    y = jnp.einsum(spec, x, w.q.astype(x.dtype))
    s = w.scale.squeeze(-2)                                # [E, out]
    if scale_shape == "e_lead":
        s = w.scale                                        # [E, 1, out]
    return (y.astype(jnp.float32) * s).astype(x.dtype)


def dense_moe(cfg: ModelConfig, lp: Dict[str, Any], x: jnp.ndarray,
              mesh: Optional[Mesh] = None) -> jnp.ndarray:
    """All-experts evaluation: x [B, S, D] -> [B, S, D].

    w_gate/w_up: [E, D, F], w_down: [E, F, D], router: [D, E] — the
    projections may be QuantInt8 (per-(expert, out-channel) scales; the
    router never is).

    Over a tensor-parallel ``mesh`` (``model`` > 1 dividing F, every
    other axis 1) the experts' inner width is split (parallel/
    sharding.py: w_gate/w_up columns, w_down rows), so each device's
    down projection is a PARTIAL sum. ``_down_and_mix`` then mixes the
    experts on each device first and reduces over ``model`` on
    [B, S, D]; left to the partitioner the reduce lands on the down
    projection's own result [B, S, E, D], E times the bytes on the
    wires (read off the compiled program, PERF.md Findings PR 27).
    Without such a mesh nothing here changes."""
    logits = (x @ lp["router"]).astype(jnp.float32)               # [B, S, E]
    mix, _ = router_weights(cfg, logits)

    gate = _qeinsum("bsd,edf->bsef", x, lp["w_gate"], "ef_last2")
    up = _qeinsum("bsd,edf->bsef", x, lp["w_up"], "ef_last2")
    hidden = _act(cfg, gate) * up                                 # [B, S, E, F]
    if _splits_expert_width(mesh, cfg):
        return _down_and_mix_sharded(hidden, lp["w_down"], mix, mesh)
    return _down_and_mix(hidden, lp["w_down"], mix)


def _down_and_mix(hidden, w_down, mix):
    """hidden [B, S, E, F] through w_down [E, F, D], the experts mixed by
    ``mix`` [B, S, E] (f32, zeros off the top-k) -> [B, S, D]."""
    y = _qeinsum("bsef,efd->bsed", hidden, w_down, "ef_last2")
    return jnp.einsum("bsed,bse->bsd", y.astype(jnp.float32),
                      mix).astype(hidden.dtype)


def _splits_expert_width(mesh: Optional[Mesh], cfg: ModelConfig) -> bool:
    """True on a pure tensor-parallel mesh whose ``model`` axis divides
    the experts' inner width: the one layout in which ``dense_moe``
    writes its own reduce (a data/expert/pipe/seq axis > 1 keeps the
    partitioner's program)."""
    if mesh is None or "model" not in mesh.axis_names:
        return False
    tp = mesh.shape["model"]
    return tp > 1 and mesh.size == tp and cfg.mlp_hidden % tp == 0


def _down_and_mix_sharded(hidden, w_down, mix, mesh: Mesh):
    """``_down_and_mix`` with F split over ``model``: each device runs
    exactly the one-device arithmetic on its F/tp columns of ``hidden``
    and rows of ``w_down`` (dequant scale and bf16 rounding of the
    partial result included) and mixes its partial expert outputs in
    f32; ONE reduce over ``model`` then adds the [B, S, D] partials, in
    the activation dtype as the dense MLP's row-parallel reduce is. It
    is a reduce-scatter onto the axis the residual is sharded on
    (parallel/sharding.py::residual_spec: batch at decode widths, else
    sequence), so the sum arrives where the layer's residual add wants
    it; a shape the residual policy leaves replicated gets a psum."""
    from ..ops.quant import QuantInt8
    from .sharding import residual_spec

    B, S, _, _ = hidden.shape
    D = w_down.shape[-1]
    res = residual_spec(mesh, (B, S, D))
    scatter = None if res is None else 0 if res[0] is not None else 1
    wspec = P(None, "model", None)
    if isinstance(w_down, QuantInt8):
        # [E, 1, D] scales: per (expert, out-channel), whole on every device
        wspec = QuantInt8(q=wspec, scale=P())

    def part(h, w, m):
        y = _down_and_mix(h, w, m)
        if scatter is None:
            return jax.lax.psum(y, "model")
        return jax.lax.psum_scatter(y, "model", scatter_dimension=scatter,
                                    tiled=True)

    out_spec = [None, None, None]
    if scatter is not None:
        out_spec[scatter] = "model"
    return jax.shard_map(
        part, mesh=mesh,
        in_specs=(P(None, None, None, "model"), wspec, P()),
        out_specs=P(*out_spec),
    )(hidden, w_down, mix)


def _act(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """One activation dispatch shared by dense and EP paths, so a config
    change can never make them silently diverge."""
    if cfg.activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _ffn(cfg: ModelConfig, w_gate, w_up, w_down, x):
    """Batched per-expert FFN: x [E_local, C, D] -> [E_local, C, D].
    Weights may be QuantInt8 — the dequant stays in each einsum's
    epilogue (VERDICT r4 item 3: int8 experts inside the EP dispatch)."""
    gate = _qeinsum("ecd,edf->ecf", x, w_gate, "e_lead")
    up = _qeinsum("ecd,edf->ecf", x, w_up, "e_lead")
    return _qeinsum("ecf,efd->ecd", _act(cfg, gate) * up, w_down, "e_lead")


def _ep_shard(x, mask, router, w_gate, w_up, w_down, *, cfg: ModelConfig,
              axis: str, model_axis: Optional[str], capacity: int):
    """Per-device body: dispatch local tokens to expert owners, run local
    experts, combine back. x: [T_local, D]; mask: [T_local] (0 = dead slot /
    bucket padding — excluded from routing so garbage tokens never consume
    expert capacity and starve live ones); router: [D, E] (replicated);
    w_*: [E_local, ...] (expert-sharded; F additionally sharded over
    ``model_axis`` when set — the per-device FFN then produces a partial sum
    psum'd at the end, Megatron row-parallel style, instead of jit
    all-gathering TP-sharded expert weights every step)."""
    T, D = x.shape
    logits = (x @ router).astype(jnp.float32)                 # [T, E]
    mix, _ = router_weights(cfg, logits)                      # [T, E] dense
    mix = mix * mask.astype(jnp.float32)[:, None]
    routed = (mix > 0.0).astype(jnp.float32)                  # 0/1 mask

    # Position of each token within its expert's capacity buffer; tokens
    # past capacity are dropped (GShard semantics — capacity_factor bounds
    # the static buffer; no host sync, no ragged shapes).
    pos = jnp.cumsum(routed, axis=0) - 1.0                    # [T, E]
    keep = routed * (pos < capacity)
    disp = keep[..., None] * jax.nn.one_hot(pos, capacity)    # [T, E, C]
    comb = disp * mix[..., None]                              # [T, E, C]

    x_send = jnp.einsum("tec,td->ecd", disp.astype(x.dtype), x)  # [E, C, D]
    # all-to-all #1: each device keeps its local experts' buffers from
    # every source device -> [E_local, ep*C, D].
    x_recv = jax.lax.all_to_all(x_send, axis, split_axis=0,
                                concat_axis=1, tiled=True)
    y_recv = _ffn(cfg, w_gate, w_up, w_down, x_recv)
    # all-to-all #2: route results back to the source device -> [E, C, D].
    y_send = jax.lax.all_to_all(y_recv, axis, split_axis=1,
                                concat_axis=0, tiled=True)
    y = jnp.einsum("ecd,tec->td", y_send.astype(jnp.float32), comb)
    if model_axis is not None:
        # FFN hidden dim was model-sharded: combine the partial sums on the
        # smallest tensor in the pipeline ([T_local, D]).
        y = jax.lax.psum(y, model_axis)
    return y.astype(x.dtype)


def expert_parallel_moe(
    cfg: ModelConfig,
    lp: Dict[str, Any],
    x: jnp.ndarray,               # [B, S, D]
    mesh: Mesh,
    *,
    axis: str = "expert",
    model_axis: str = "model",
    capacity_factor: float = 2.0,
    capacity: Optional[int] = None,
    token_mask: Optional[jnp.ndarray] = None,   # [B, S]; 0 = padding/dead
) -> jnp.ndarray:
    """Top-k MoE with experts and tokens sharded over ``axis``.

    Numerics match :func:`dense_moe` for every live token that fits within
    the per-expert ``capacity`` (tokens beyond it are dropped — standard
    capacity-factor semantics; pass an explicit ``capacity`` to make drops
    impossible, e.g. in parity tests). ``token_mask`` marks live tokens:
    dead decode slots and bucket padding are excluded from routing so they
    can never consume capacity that live tokens need.

    When ``model_axis`` has size > 1 and the FFN hidden dim divides it, the
    per-expert FFN additionally runs model-sharded (column/row parallel with
    a final psum) so TP-sharded expert weights are used in place rather
    than all-gathered into every step.

    Requires B*S divisible by the axis size and n_experts divisible by the
    axis size.
    """
    B, S, D = x.shape
    T = B * S
    ep = mesh.shape[axis]
    E = cfg.n_experts
    if T % ep or E % ep:
        raise ValueError(
            f"tokens {T} and experts {E} must divide the {axis} axis ({ep})"
        )
    T_local = T // ep
    if capacity is None:
        capacity = max(1, int(
            capacity_factor * cfg.experts_per_token * T_local / E
        ))
    tp = mesh.shape.get(model_axis, 1) if model_axis else 1
    use_tp = tp > 1 and cfg.mlp_hidden % tp == 0
    col = P(axis, None, model_axis) if use_tp else P(axis, None, None)
    row = P(axis, model_axis, None) if use_tp else P(axis, None, None)
    if token_mask is None:
        token_mask = jnp.ones((B, S), jnp.float32)

    def _wspec(w, qspec):
        """Per-leaf specs for an optionally-quantized expert weight: the
        int8 payload takes the weight's spec; the [E, 1, out] scales
        shard expert + out-channel only (their size-1 contraction axis
        can never take the model axis a row-parallel payload does)."""
        from ..ops.quant import QuantInt8

        if not isinstance(w, QuantInt8):
            return qspec
        sspec = P(qspec[0], None,
                  qspec[2] if len(qspec) > 2 else None)
        return QuantInt8(q=qspec, scale=sspec)

    fn = jax.shard_map(
        partial(_ep_shard, cfg=cfg, axis=axis,
                model_axis=model_axis if use_tp else None, capacity=capacity),
        mesh=mesh,
        in_specs=(P(axis, None), P(axis), P(),
                  _wspec(lp["w_gate"], col), _wspec(lp["w_up"], col),
                  _wspec(lp["w_down"], row)),
        out_specs=P(axis, None),
    )
    flat = fn(x.reshape(T, D), token_mask.reshape(T), lp["router"],
              lp["w_gate"], lp["w_up"], lp["w_down"])
    return flat.reshape(B, S, D)
