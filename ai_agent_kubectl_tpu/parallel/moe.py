"""Mixture-of-Experts MLP: dense reference, token-grouped expert GEMM,
expert-parallel dispatch.

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

``grouped_moe`` (ISSUE 31) is the one-device path of a configuration with
many experts a token does not pick (128 experts, 8 a token: evaluating
all of them is 16x the arithmetic): the (token, expert) pairs are sorted
by expert into tile-aligned groups and ONE Pallas kernel runs gate, up
and down for each tile against its expert's int8 leaves — only picked
experts' weights cross HBM, and no dequantized copy of a layer's experts
is ever made. It is told which experts it holds and computes those alone.

Routing follows Mixtral (top-k over router logits, softmax *after*
selection, renormalized over the selected experts).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from ..models.config import ModelConfig


def router_logits(cfg: ModelConfig, lp: Dict[str, Any], x: jnp.ndarray):
    """x [..., D] -> float32 [..., E]. The sigmoid router's scores decide
    a top-k among near-equal values, so its product runs in float32 at
    the highest precision (T x D x E: nothing beside an expert); the
    softmax router's is the activation dtype's, as it always was."""
    if cfg.router == "sigmoid_bias" or cfg.router_width:
        # (a router over a SHARE of the experts decides which chip computes
        # a token's expert: its top-k among 128 near-equal scores is taken
        # in float32 too, as the family's published code scores)
        return jnp.dot(x.astype(jnp.float32),
                       lp["router"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    return (x @ lp["router"]).astype(jnp.float32)


def top_k_routing(cfg: ModelConfig, logits: jnp.ndarray, bias=None):
    """logits float32 [..., E] -> (weights float32 [..., k], idx [..., k]).

    ``softmax`` (Mixtral, Qwen3-MoE): the k largest logits, softmax over
    them. ``sigmoid_bias`` (the DeepSeek-V3 router nemotron_h takes): the
    k largest of sigmoid(logits) + ``bias`` [E] (a learned selection
    bias that enters nothing else), the picked scores divided by their
    sum, times ``router_scale``. With ``n_group`` > 1 the choice is
    group-limited: the E scored experts are ``n_group`` groups side by
    side, a group's score the sum of its two largest score + bias, and
    only the experts of the ``topk_group`` best groups can be picked."""
    k = cfg.experts_per_token
    if cfg.router == "sigmoid_bias":
        s = jax.nn.sigmoid(logits)
        choice = s + bias.astype(jnp.float32)
        if cfg.n_group > 1:
            with jax.named_scope("group_choice"):
                groups = choice.reshape(choice.shape[:-1] + (cfg.n_group, -1))
                score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
                _, best = jax.lax.top_k(score, cfg.topk_group)
                stays = jnp.any(best[..., None] == jnp.arange(cfg.n_group),
                                axis=-2)                      # [..., n_group]
                choice = jnp.where(stays[..., None], groups,
                                   -jnp.inf).reshape(choice.shape)
        _, idx = jax.lax.top_k(choice, k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return w * cfg.router_scale, idx
    if cfg.router != "softmax":
        raise ValueError(f"unknown router kind {cfg.router!r}")
    top_vals, idx = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(top_vals.astype(jnp.float32), axis=-1)
    return (w * cfg.router_scale if cfg.router_scale != 1.0 else w), idx


def router_weights(cfg: ModelConfig, logits: jnp.ndarray, bias=None):
    """Top-k routing. logits [..., E] -> (mix [..., E], idx [..., k]).

    ``mix`` is dense over E with zeros off the top-k — dense mixing keeps
    the op jit-friendly (no ragged gathers) and maps to pure VPU work.
    """
    top_w, top_idx = top_k_routing(cfg, logits, bias)
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
    logits = router_logits(cfg, lp, x)                            # [B, S, E]
    mix, _ = router_weights(cfg, logits, lp.get("router_bias"))
    if cfg.router_width:
        # a share of the experts: the router scored all of them, the
        # leaves hold ``n_experts`` from ``first_expert`` on
        mix = mix[..., cfg.first_expert:cfg.first_expert + cfg.n_experts]

    up = _qeinsum("bsd,edf->bsef", x, lp["w_up"], "ef_last2")
    if cfg.gated_mlp:
        gate = _qeinsum("bsd,edf->bsef", x, lp["w_gate"], "ef_last2")
        hidden = _act(cfg, gate) * up                             # [B, S, E, F]
    else:
        hidden = _act(cfg, up)
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
    if cfg.activation == "relu2":
        return jnp.square(jax.nn.relu(x))
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


# ------------------------------------------------ token-grouped expert GEMM

#: Rows of one tile of the grouped kernel: at least one bf16 sublane tile,
#: at most what keeps a tile's activations small beside its expert's
#: weights in VMEM.
_GROUP_TILE_MIN, _GROUP_TILE_MAX = 16, 256


def _group_tile(pairs: int, experts: int) -> int:
    """Rows of a tile, from the call's shapes: what holds most groups
    WHOLE — the mean group (pairs / experts) plus its spread (a uniform
    router's groups scatter by about the root of their mean), in whole
    bf16 sublane tiles. The kernel is bound by its experts' stream (a
    live tile's conversion and products take under half the time its
    expert takes to arrive), and the pipeline fetches one step ahead: the
    first tile of a group split over two computes with nothing in flight
    and the second waits out the next expert's whole stream, 5.8 + 13.9
    us against 13.9 at 2688 x 1856 (PERF.md Findings, PR 34). At 24 rows
    an expert (an eager 512-wide piece of top-6 of 128) 16-row tiles
    split nearly every group and 32-row tiles one in twenty. No power of
    two: at 64 rows an expert 128-row tiles cost more in products than
    the stream hides, 80-row tiles do not. Decode (under one row an
    expert) stays at the smallest tile."""
    # (a share's expected pairs can round to none: one row of a small batch)
    mean = max(1, -(-pairs // max(1, experts)))
    need = mean + math.isqrt(mean)
    return min(_GROUP_TILE_MAX, -(-need // _GROUP_TILE_MIN) * _GROUP_TILE_MIN)


def _grid_tiles(pairs: int, tm: int, experts: int) -> int:
    """Steps of the kernel's grid: the most tiles ``pairs`` rows in groups
    of ``experts`` experts can need (every group that can have a row has
    one, the other rows fill whole tiles), and one more, so that the last
    tile is always dead: its zero rows are what an unpicked pair reads.
    A decode pass of 96 pairs over 128 experts cannot light 134 tiles;
    the rows the tighter bound saves are the gather's and the mix's, a
    dead step itself is 0.07 us (PR 34)."""
    groups = min(pairs, experts)
    return groups + (pairs - groups) // tm + 1


def grouped_kernel_shape(cfg: ModelConfig, tokens: int) -> Dict[str, int]:
    """What the grouped kernel resolves from shapes for a call of
    ``tokens`` tokens over all of ``cfg``'s experts (``/health.moe``,
    tools/time_grouped_kernel.py): a tile's rows and the grid's steps."""
    pairs = tokens * cfg.experts_per_token
    tm = _group_tile(pairs * cfg.n_experts // cfg.experts_scored,
                     cfg.n_experts)
    return {"tile_rows": tm,
            "grid_steps": _grid_tiles(pairs, tm, cfg.n_experts)}


def _payload_and_scale(w, stacked: bool):
    """(int8 or plain payload [L, E, in, out], f32 scales [L, E, 1, out]);
    one layer's leaves become a one-layer stack (a free reshape)."""
    from ..ops.quant import QuantInt8

    q, scale = (w.q, w.scale) if isinstance(w, QuantInt8) else (w, None)
    if not stacked:
        q, scale = q[None], None if scale is None else scale[None]
    if scale is None:
        scale = jnp.ones(q.shape[:2] + (1, q.shape[3]), jnp.float32)
    return q, scale


def _grouped_ffn_kernel(te_ref, live_ref, lyr_ref, x_ref, *refs,
                        activation: str, up_t: bool):
    """One tile of rows that share an expert. Gated (six weight refs):
    silu/gelu(x Wg) * (x Wu), then Wd; ``relu2`` (four): relu(x Wu)^2,
    then Wd. The weights arrive as stored (int8 where quantized) and are
    converted in VMEM; the per-channel scales multiply the f32 results.
    ``up_t``: the up (and gate) blocks are handed over as [F, D] (see
    ``_grouped_ffn``) and contract on their minor axis."""
    *w_refs, o_ref = refs
    i = pl.program_id(0)

    @pl.when(live_ref[i] == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    def up(x, w_ref, s_ref):
        w = w_ref[0, 0].astype(x.dtype)
        if up_t:
            y = jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        else:
            y = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return y * s_ref[0, 0]

    @pl.when(live_ref[i] != 0)
    def _tile():
        x = x_ref[...]
        if activation == "relu2":
            wu_ref, su_ref, wd_ref, sd_ref = w_refs
            h = jnp.square(jnp.maximum(up(x, wu_ref, su_ref), 0.0))
        else:
            wg_ref, sg_ref, wu_ref, su_ref, wd_ref, sd_ref = w_refs
            g = up(x, wg_ref, sg_ref)
            u = up(x, wu_ref, su_ref)
            act = (jax.nn.gelu(g, approximate=True) if activation == "gelu"
                   else jax.nn.silu(g))
            h = act * u
        y = jnp.dot(h.astype(x.dtype), wd_ref[0, 0].astype(x.dtype),
                    preferred_element_type=jnp.float32) * sd_ref[0, 0]
        o_ref[...] = y.astype(o_ref.dtype)


def _grouped_ffn(cfg: ModelConfig, lp, xs, tile_expert, tile_live, tm: int,
                 layer=None):
    """xs [n_tiles*tm, D] (tile i holds rows of expert ``tile_expert[i]``)
    -> [n_tiles*tm, D]. The tile's expert (and, for stacked leaves, the
    scalar-prefetched ``layer``) picks the weight block in the index maps,
    so no layer is sliced out of the stack in front of the call, and
    consecutive tiles of one expert (and the dead tiles after the last
    live one, which repeat its expert) fetch nothing new. The live tiles
    are a prefix of the grid and its last tile is dead (``_grid_tiles``):
    every dead step names that tile's rows, so they move one tile."""
    stacked = layer is not None
    wu, su = _payload_and_scale(lp["w_up"], stacked)
    wd, sd = _payload_and_scale(lp["w_down"], stacked)
    _, _, D, F = wu.shape
    # An expert's block is its whole matrix, so F is a full dimension of
    # the block and need not be a multiple of 128 (1,856). But where F is
    # not and D is, the TPU keeps a [.., D, F] leaf in HBM with D minor-
    # most (no lane padding), and a kernel that asks for it row-major gets
    # a copy of the WHOLE stack in front of every call (638 MB a layer a
    # pass at 2688 x 1856; AOT, PR 33). The up (and gate) stacks are then
    # handed over as [.., F, D] — the same bytes, a free relabelling —
    # and contracted on their minor axis (tests/test_tpu_aot.py pins
    # that no stack-sized copy is left).
    up_t = F % 128 != 0 and D % 128 == 0
    ups = [(wu, su)]
    if cfg.gated_mlp:
        ups.insert(0, _payload_and_scale(lp["w_gate"], stacked))
    if up_t:
        ups = [(jnp.swapaxes(w, -1, -2), sc) for w, sc in ups]
    weights = tuple(a for pair in ups for a in pair) + (wd, sd)
    kernel = partial(_grouped_ffn_kernel, activation=cfg.activation,
                     up_t=up_t)
    n_tiles = tile_expert.shape[0]
    lyr = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)

    def rows(i, te, live, lyr):
        # a dead step names the last tile (always dead): the dead steps of
        # a call fetch one tile of rows and write one tile of zeros
        return (jnp.where(live[i] != 0, i, n_tiles - 1), 0)

    def expert(i, te, live, lyr):
        return (lyr[0], te[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((tm, D), rows)] + [
            pl.BlockSpec((1, 1) + w.shape[2:], expert) for w in weights],
        out_specs=pl.BlockSpec((tm, D), rows),
    )
    interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(xs.shape, xs.dtype),
        interpret=interpret,
        name="grouped_expert_ffn",
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_GROUPED_VMEM_BYTES)}),
    )(tile_expert, tile_live, lyr, xs, *weights)


#: Scoped VMEM of the grouped kernel: an expert's matrices as stored,
#: double-buffered by the pipeline, their converted copies and a tile's
#: activations (Nemotron's 2 x 2688 x 1856 int8: 19 MiB + 19 MiB bf16 +
#: < 10 MiB at 256 rows; Keye's 3 x 2048 x 768: 9 + 9 + < 8) pass the
#: 16 MiB default; a v5e core has 128 MiB. Taking the inner width in
#: pieces would take the converted copy out, and buys no time: a tile's
#: conversion and products hide behind its expert's stream whole, and
#: in pieces they took 9.1 us against 6.4 (PERF.md Findings, PR 34).
_GROUPED_VMEM_BYTES = 64 * 2**20


def grouped_moe(cfg: ModelConfig, lp: Dict[str, Any], x: jnp.ndarray,
                token_mask: Optional[jnp.ndarray] = None,
                first_expert: int = 0, layer=None):
    """``grouped_moe_counted`` less the picks: (y, experts_read)."""
    return grouped_moe_counted(cfg, lp, x, token_mask, first_expert,
                               layer)[:2]


def grouped_moe_counted(cfg: ModelConfig, lp: Dict[str, Any], x: jnp.ndarray,
                        token_mask: Optional[jnp.ndarray] = None,
                        first_expert: int = 0, layer=None):
    """Top-k MoE that computes only picked experts: x [B, S, D] ->
    (y [B, S, D], experts_read int32, int32 [2]: the picks the live rows
    made among all the experts scored, and those of them that fell on an
    expert held here).

    The router scores all ``cfg.n_experts``; this device holds the
    experts of its leaves ([E, in, out], or with ``layer`` the whole
    stacks [L, E, in, out] of which the kernel reads that layer) from
    ``first_expert`` on (all of
    them on the one chip the benchmark serves) and computes the part of
    the result those give — a pick of an expert held elsewhere adds
    nothing here. ``token_mask`` ([B, S], 0 = padding or a dead slot)
    keeps garbage rows out of every group, so they read no expert.
    ``experts_read`` counts the held experts with at least one row:
    whose weights this pass streamed."""
    B, S, D = x.shape
    T, k = B * S, cfg.experts_per_token
    held = (lp["w_up"].q if hasattr(lp["w_up"], "q")
            else lp["w_up"]).shape[0 if layer is None else 1]
    xf = x.reshape(T, D)
    top_w, top_idx = top_k_routing(cfg, router_logits(cfg, lp, xf),
                                   lp.get("router_bias"))          # [T, k]

    # (token, pick) pairs -> local expert id; ``held`` = no expert here.
    e = top_idx.astype(jnp.int32) - first_expert
    e = jnp.where(jnp.logical_and(e >= 0, e < held), e, held)
    live = jnp.asarray(T, jnp.int32)
    if token_mask is not None:
        e = jnp.where(token_mask.reshape(T, 1) > 0, e, held)
        live = jnp.sum(token_mask > 0, dtype=jnp.int32)
    e = e.reshape(T * k)
    picks = jnp.stack([live * k, jnp.sum(e < held, dtype=jnp.int32)])
    M = T * k
    # a share of the experts expects its share of the pairs (a tile's
    # rows); the grid still has room for every pair landing here
    tm = _group_tile(M * held // cfg.experts_scored, held)
    n_tiles = _grid_tiles(M, tm, held)

    sizes = jnp.sum(e[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)                               # [held]
    tiles_of = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_of)                                # [held]
    start = (tile_end - tiles_of) * tm          # a group's first padded row
    order = jnp.argsort(e, stable=True)                            # [M]
    e_sorted = e[order]
    first_of = jnp.cumsum(sizes) - sizes        # a group's first sorted pair
    safe = jnp.minimum(e_sorted, held - 1)
    row_sorted = jnp.where(
        e_sorted < held,
        start[safe] + jnp.arange(M, dtype=jnp.int32) - first_of[safe],
        n_tiles * tm)                           # unheld / masked: dropped
    # padded row -> source token (T = a zero row)
    src = jnp.full((n_tiles * tm,), T, jnp.int32).at[row_sorted].set(
        (order // k).astype(jnp.int32), mode="drop")
    xs = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])[src]

    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    n_live = tile_end[-1]
    tile_expert = jnp.searchsorted(tile_end, jnp.minimum(tile, n_live - 1),
                                   side="right").astype(jnp.int32)
    tile_expert = jnp.clip(tile_expert, 0, held - 1)
    ys = _grouped_ffn(cfg, lp, xs, tile_expert,
                      (tile < n_live).astype(jnp.int32), tm, layer)

    # each pair's padded row, back in (token, pick) order
    row_of = jnp.zeros((M,), jnp.int32).at[order].set(row_sorted)
    picked = row_of < n_tiles * tm
    rows = jnp.minimum(row_of, n_tiles * tm - 1).reshape(T, k)
    w = jnp.where(picked.reshape(T, k), top_w, 0.0)
    y = jnp.einsum("tkd,tk->td", ys[rows].astype(jnp.float32), w)
    return (y.astype(x.dtype).reshape(B, S, D),
            jnp.sum(sizes > 0, dtype=jnp.int32), picks)
