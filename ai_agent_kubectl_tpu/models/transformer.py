"""Pure-JAX decoder-only transformer, parameterized by ``ModelConfig``.

Design (TPU-first, not a port — the reference has no model code at all,
SURVEY.md §3.5):

- **Functional**: parameters are a plain pytree; ``forward`` is a pure
  function of (params, tokens, positions, cache). No module framework —
  nothing between the code and XLA.
- **Layer-stacked + lax.scan**: per-layer params are stacked on a leading
  ``n_layers`` axis and the layer loop is a ``lax.scan``. One layer gets
  traced/compiled once regardless of depth — an 80-layer Llama-70B compiles
  in roughly the time of one layer, and XLA still overlaps per-layer
  collectives with compute. A projection's head split stays OUTSIDE its
  dot (``ops/quant.py::qmatmul_heads``), or the scan stages the layer's
  matrix in VMEM and turns it over every pass (tests/test_tpu_aot.py::
  test_decode_projections_stream_their_weights_as_stored_on_v5e).
- **Static shapes everywhere**: tokens are padded to bucket sizes; the KV
  cache is a fixed [L, B, S, KV, d] buffer with explicit write positions, so
  jit never recompiles across requests (SURVEY.md §7 hard part "continuous
  batching × jit").
- **Explicit positions**: RoPE and causal masks take absolute positions, so
  prefix-KV splicing and ragged decode are correct by construction.
- **bf16 params/activations, f32 softmax/norm accumulation.**

Attention backend is pluggable (``attn_impl``): "dense" (ops/attention.py
reference) or "flash" (Pallas, ops/flash_attention.py).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import dense_attention, dense_attention_quant
from ..ops.norms import rms_norm
from ..ops.quant import (QuantKV, embed_lookup, kv_quantize, qmatmul,
                         qmatmul_heads, tied_head)
from ..ops.rope import apply_rope
from .config import ModelConfig

Params = Dict[str, Any]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """Contiguous per-slot KV cache.

    k, v:    [n_layers, batch, max_seq, n_kv_heads, head_dim] — either the
             model dtype, or ``QuantKV`` (int8 payload + per-(position,
             head) f32 scales) when built with ``kv_quant="int8"``
    lengths: [batch] — number of valid positions per slot
    ik:      pool mode of a selecting configuration only
             (``ModelConfig.index_topk``): the indexer's keys, [n_layers,
             n_blocks, page, index_key_width] (the key in the first
             ``index_head_dim`` lanes), addressed by the K pool's
             block table — written, shared and copied wherever K and V
             are. Absent (None) everywhere else; ``forward``, given a
             selecting configuration and no leaf, makes a zero one of the
             K pool's block geometry and returns it.
    experts_read: int32 scalar, where the grouped expert path serves: the
             experts whose weights it has read (summed over layers and
             passes) since the chunk program last zeroed it
             (/health.moe.experts_read). Absent elsewhere.
    sel_rows: int32 [2], for a selecting configuration's engine: the keys
             its DECODE queries had before them and the keys the selector
             kept of those (the mask the kernel applies, counted where it
             is made), summed over layers and passes since the chunk
             program last zeroed it (/health.sparse_attention). Absent
             elsewhere.
    ssm, conv: a configuration with state-space layers
             (``ModelConfig.keeps_state``) only: the recurrent state,
             float32 [n_ssm_layers, batch, heads, head_dim, state], and
             the convolution's tail, [n_ssm_layers, batch, conv - 1,
             conv channels] — one plane a state-space layer, one row a
             batch row of the call, whatever the sequences' lengths. A
             row's state is the state at the last token the calls so far
             ran for it; a caller that continues another sequence in the
             row puts that sequence's state there first (engine/
             kv_pool.py::StateStore). ``k``/``v`` then hold a row only for
             each ATTENTION layer, addressed by its ordinal among them
             (spare rows are tolerated). ``forward``, given such a
             configuration and no leaf, starts every row from zero and
             returns the leaves.
    ssm_rows: int32 [1], for such a configuration's engine: the rows of
             decode passes whose state the state-space layers' step kernel
             neither read nor wrote (dead slots), summed over layers and
             passes since the chunk program last zeroed it
             (/health.ssm.decode_rows_still). Absent elsewhere.
    ssm_window: int32 [3], beside it: the rows of WINDOW passes whose state
             the state-space layers' window kernel updated, the rows it
             neither read nor wrote (they brought no token) and the chunks
             it passed over past a moving row's ``q_len``, summed likewise
             (/health.ssm.window_rows_moved, .window_rows_still,
             .window_chunks_skipped). Absent elsewhere.
    lat:     pool mode of a latent-attention configuration
             (``ModelConfig.latent``) only, and then THE paged cache: one
             compressed row a token a latent layer (in a pattern: a ``*``
             layer, addressed by its ordinal among them), [normed latent |
             rotated rope key], shared by every head — [n_layers, n_blocks, page / 2,
             2 x latent_row], a PAIR of tokens a leaf row (ops/
             ragged_attention.py::latent_pack: 640 B a token at the
             published sizes, every part on a lane tile's edge), addressed
             by the same block tables. ``k``/``v`` are then read by nothing:
             the engine's pool leaves them None; a caller that hands K and
             V pools anyway (benchmark/refcheck.py) gets them back
             untouched and, given no ``lat``, a zero one made on the K
             pool's block geometry and returned.
    lat_rows: int32 [2], for a latent configuration's engine: the DECODE
             queries the passes ran and the cached rows those had before
             them, summed over layers and passes since the chunk program
             last zeroed it (/health.latent_attention). Absent elsewhere.
    sk, sv:  a configuration with sliding-attention layers
             (``ModelConfig.slides``) only: those layers' K and V, a
             BOUNDED state a sequence and not rows of the pool — [n_sliding
             layers, batch, ring, n_kv_heads, head_dim], position p's row
             at ``p % ring``, the ring the span and the widest window a
             call writes beside it (``ModelConfig.sliding_ring``; the
             leaf's own shape is what the code reads). One row a batch row
             of the call, carried, cut and put back as ``ssm``/``conv``
             are; ``k``/``v`` hold a row for each FULL attention layer
             only. ``forward``, given such a configuration and no leaf,
             makes a zero one wide enough for the call's own window and
             returns it.
    span_rows: int32 [4], for such a configuration's engine: the DECODE
             queries its sliding layers ran and the keys those had inside
             their span, then the same two for its full layers (the live
             context), summed over layers and passes since the chunk
             program last zeroed it (/health.sliding_attention). Absent
             elsewhere.
    lin, lconv: a configuration with linear-attention layers
             (``ModelConfig.has_linear``) only: the gated delta rule's
             matrix state, float32 [n_linear_layers, batch, key_dim, heads
             x value_dim] (the heads side by side along the lanes:
             ops/gated_delta.py says why), and its convolution's tail,
             [n_linear_layers, batch, conv - 1, conv channels]; carried,
             cut and put back as ``ssm``/``conv`` are, and made by
             ``forward`` where absent.
    lin_rows: int32 [6], for such a configuration's engine: the DECODE
             rows its linear layers ran, the window rows they ran and the
             chunks their scans ran over, then the decode queries its full
             attention layers ran and the keys those had before them, then
             the rows of decode passes whose state the linear layers' step
             kernel neither read nor wrote (dead slots), summed over
             layers and passes since the chunk program last zeroed it
             (/health.linear_attention). Absent elsewhere.
    lin_window: int32 [4], beside it where the delta rule's decay is a
             scalar a head (no ``lin_channel_decay``): the rows of WINDOW
             passes whose state the linear layers' window kernel updated,
             the rows it neither read nor wrote (they brought no token),
             the chunks it passed over past a moving row's ``q_len`` and,
             of the rows it updated, those that rode the window with one
             token and took the step, summed likewise
             (/health.linear_attention.window_rows_moved,
             .window_rows_still, .window_chunks_skipped,
             .window_rows_stepped). Absent elsewhere.
    expert_picks: int32 [2], where the grouped expert path serves a chip's
             SHARE of the experts (``ModelConfig.router_width``): the picks
             the passes' live rows made among all the experts scored, and
             those that fell on an expert held here, summed over layers
             and passes since the chunk program last zeroed it
             (/health.moe.picks). Absent elsewhere.
    """

    k: Any
    v: Any
    lengths: jnp.ndarray
    ik: Any = None
    experts_read: Any = None
    sel_rows: Any = None
    ssm: Any = None
    conv: Any = None
    ssm_rows: Any = None
    ssm_window: Any = None
    lat: Any = None
    lat_rows: Any = None
    sk: Any = None
    sv: Any = None
    span_rows: Any = None
    lin: Any = None
    lconv: Any = None
    lin_rows: Any = None
    lin_window: Any = None
    expert_picks: Any = None

    #: the leaves that hold one bounded state a batch row (axis 1)
    STATE = ("ssm", "conv", "sk", "sv", "lin", "lconv")
    #: what the passes count on the device, zeroed by the chunk program
    COUNTS = ("experts_read", "sel_rows", "ssm_rows", "ssm_window",
              "lat_rows", "span_rows", "lin_rows", "lin_window",
              "expert_picks")

    @classmethod
    def zeros(cls, cfg: ModelConfig, batch: int, max_seq: int,
              dtype=jnp.bfloat16, kv_quant: str = "") -> "KVCache":
        shape = (cfg.n_of("*"), batch, max_seq, cfg.kv_heads_paged,
                 cfg.head_dim)
        return cls(k=_kv_zeros(shape, dtype, kv_quant),
                   v=_kv_zeros(shape, dtype, kv_quant),
                   lengths=jnp.zeros((batch,), dtype=jnp.int32))

    @classmethod
    def state_leaves_zeros(cls, cfg: ModelConfig, rows: int, *, ring: int,
                           dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
        """The state leaves (``STATE``) of ``rows`` sequences. A sliding
        layer's live K/V are a ring (``ModelConfig.sliding_ring``); a
        snapshot keeps the span's rows alone (``ring=cfg.sliding_window``)."""
        leaves = {}
        if cfg.has_ssm:
            leaves["ssm"], leaves["conv"] = state_zeros(cfg, rows, dtype)
        if cfg.slides:
            leaves["sk"], leaves["sv"] = sliding_zeros(cfg, rows, ring, dtype)
        if cfg.has_linear:
            leaves["lin"], leaves["lconv"] = linear_zeros(cfg, rows, dtype)
        return leaves

    @classmethod
    def pool_zeros(cls, cfg: ModelConfig, *, n_blocks: int, page: int,
                   slots: int, ring: int = 0, dtype=jnp.bfloat16,
                   kv_quant: str = "", counts_experts: bool = False,
                   lane_heads: int = 1) -> "KVCache":
        """The pool engine's cache: the paged leaves ([layers, n_blocks,
        page, ...]: K and V of the attention layers alone and a selecting
        configuration's index keys, or a latent one's compressed rows and
        nothing else), a live state row for each of ``slots`` decode slots,
        and the count leaves of the configuration's kinds (models/
        families.py; ``experts_read`` where the grouped path serves).
        ``lengths`` is [n_blocks]-shaped and purely structural: per-slot
        lengths are host truth. ``lane_heads`` KV heads of a row share a
        lane tile (ops/ragged_attention.py::lane_heads: a head under 128
        lanes before the compiled kernel): K and V are then [.., KV /
        lane_heads, lane_heads x head_dim], the same bytes in the same
        order."""
        from .families import kinds_of

        shape = (cfg.n_of("*"), n_blocks, page,
                 cfg.kv_heads_paged // lane_heads, cfg.head_dim * lane_heads)
        if cfg.latent:
            # a pair of tokens a leaf row (``lat`` above), a plane a latent
            # layer (every layer of a uniform block); no K, no V
            paged = dict(k=None, v=None, lat=jnp.zeros(
                (cfg.n_of("*"), n_blocks, page // 2, 2 * cfg.latent_row),
                dtype))
        else:
            paged = dict(k=_kv_zeros(shape, dtype, kv_quant),
                         v=_kv_zeros(shape, dtype, kv_quant))
            if cfg.selects_keys:
                paged["ik"] = jnp.zeros(
                    shape[:3] + (cfg.index_key_width,), dtype)
        # (a few zero words are PUT on the device, not computed there: every
        # shape of ``jnp.zeros`` called eagerly is a program a start compiles)
        counts = {kind.count_leaf: jnp.asarray(np.zeros(kind.count_shape,
                                                        np.int32))
                  for kind in kinds_of(cfg) if kind.count_leaf
                  and (counts_experts or kind.lane != "experts_read")}
        return cls(lengths=jnp.zeros((n_blocks,), jnp.int32), **paged,
                   **counts, **cls.state_leaves_zeros(
                       cfg, slots, ring=ring, dtype=dtype))

    @property
    def max_seq(self) -> int:
        leaf = self.k.q if isinstance(self.k, QuantKV) else self.k
        return leaf.shape[2]

    #: the leaves addressed through the block tables (pool mode): written,
    #: shared, copied, demoted and promoted together
    PAGED = ("k", "v", "ik", "lat")

    def paged(self) -> Tuple[Any, ...]:
        return tuple(getattr(self, name) for name in self.PAGED)

    def with_paged(self, leaves) -> "KVCache":
        return dataclasses.replace(self, **dict(zip(self.PAGED, leaves)))


def _kv_zeros(shape, dtype, kv_quant: str):
    """A K or V leaf of ``shape``: ``QuantKV`` (unit scales) under int8."""
    if kv_quant == "int8":
        return QuantKV(q=jnp.zeros(shape, jnp.int8),
                       s=jnp.ones(shape[:-1], jnp.float32))
    return jnp.zeros(shape, dtype)


def state_zeros(cfg: ModelConfig, rows: int, dtype=jnp.bfloat16):
    """(ssm, conv) leaves of ``rows`` sequences, every one at its start."""
    from ..ops.ssd_scan import STATE_DTYPE

    n = cfg.n_of("M")
    return (jnp.zeros((n, rows, cfg.ssm_heads, cfg.ssm_head_dim,
                       cfg.ssm_state), STATE_DTYPE),
            jnp.zeros((n, rows, cfg.ssm_conv - 1, cfg.ssm_conv_dim), dtype))


def linear_zeros(cfg: ModelConfig, rows: int, dtype=jnp.bfloat16):
    """(lin, lconv) leaves of ``rows`` sequences, every one at its start."""
    from ..ops.gated_delta import STATE_DTYPE

    n = cfg.n_of("L")
    return (jnp.zeros((n, rows, cfg.lin_key_dim,
                       cfg.lin_value_heads * cfg.lin_value_dim), STATE_DTYPE),
            jnp.zeros((n, rows, cfg.lin_conv - 1, cfg.lin_conv_dim), dtype))


def sliding_zeros(cfg: ModelConfig, rows: int, ring: int,
                  dtype=jnp.bfloat16):
    """(sk, sv) leaves of ``rows`` sequences: ``ring`` K and V rows a
    sliding-attention layer."""
    shape = (cfg.n_of("S"), rows, ring, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def _state_leaves(cache: KVCache):
    return {name: getattr(cache, name) for name in KVCache.STATE
            if getattr(cache, name) is not None}


def state_take_row(cache: KVCache, row) -> KVCache:
    """The cache with its state leaves cut to the one row ``row`` (a
    traced scalar): what a one-sequence call for that slot is handed."""
    one = lambda a: jax.lax.dynamic_slice_in_dim(a, row, 1, axis=1)
    return dataclasses.replace(
        cache, **{n: one(a) for n, a in _state_leaves(cache).items()})


def state_put_row(cache: KVCache, out: KVCache, row) -> KVCache:
    """``out`` (a one-row call's result) with ``cache``'s state leaves in
    the place of its one-row ones, those written back at ``row``."""
    put = lambda a, u: jax.lax.dynamic_update_slice_in_dim(a, u, row, axis=1)
    return dataclasses.replace(
        out, **{n: put(a, getattr(out, n))
                for n, a in _state_leaves(cache).items()})


# ----------------------------------------------------------------- init

def small_leaf_init(name: str, shape, dtype, key):
    """The leaves of a patterned configuration that are neither a
    projection nor a norm gain, by name (None for any other name); shared
    by ``init_params`` and the seeded int8 generator (ops/quant.py), so a
    layer's memory is a trained model's in both: ``A = -exp(A_log)`` from
    1 to 16 over the heads and a step bias whose softplus runs from 1e-3
    to 1e-1 (Mamba-2's initialisation) make a head forget over a few
    tokens to a few thousand — with a zero bias every head forgets in
    two, and neither a carried state nor its precision could be told
    from a logit. A linear-attention layer's decay is the same product
    (``g = -exp(A_log) softplus(x W_a + dt_bias)``) with a data-dependent
    part: ``W_a`` and ``W_b`` are small enough that the residual stream
    (whose RMS grows to ~8 over 64 output-normed sublayers) moves a head's
    step by a factor of a few and its write strength ``beta = 2
    sigmoid(x W_b)`` over both sides of 1, without saturating either. Of
    30 heads that is three that remember over 100 tokens, the slowest
    ~970 (~150 at the deepest layers), and a median of 12: the published
    initialisation's own spread. Not made slower for the comparison's
    sake: with EVERY head at 1,000 tokens a bf16 state still reads as
    float32 does over 3,500 positions on the chip (PERF.md, PR 45), what
    a long memory integrates being the bf16 activations' rounding."""
    H = shape[-1]
    if name in ("lin_wa", "lin_wb", "lin_wf"):
        return (jax.random.normal(key, shape, jnp.float32)
                * (0.5 if name == "lin_wb" else 0.25) * shape[-2] ** -0.5
                ).astype(dtype)
    if name == "lin_f_bias":
        # Kimi delta attention's bias a key channel, [.., heads, key_dim]: a
        # channel's decay is floor x sigmoid(A_h (x W_f + bias)); A_h bias
        # runs evenly from -2 to -9 over a head's channels whatever A_h is
        # (``lin_A_log`` below), so that at the floor of -5 a head's
        # channels remember from 2 tokens to ~1,600, evenly in the
        # logarithm, and the data (W_f: a deviation of 0.14 to 2.3 in that
        # argument, by the head's A) moves each by a factor of a few
        A = jnp.linspace(1.0, 16.0, shape[-2])[
            (37 * jnp.arange(shape[-2])) % shape[-2]]
        v = jnp.linspace(-2.0, -9.0, H)[None, :] / A[:, None]
        return jnp.broadcast_to(v, shape).astype(jnp.float32)
    name = {"lin_A_log": "ssm_A_log", "lin_dt_bias": "ssm_dt_bias",
            "lin_conv_w": "ssm_conv_w"}.get(name, name)
    if name == "ssm_A_log":
        # spread over the heads in another order than the steps are (h ->
        # 37 h mod H, a permutation for the even H of every preset), as
        # the two are drawn independently in the published initialisation:
        # every pairing of a short or long step with a slow or fast decay
        v = jnp.log(jnp.linspace(1.0, 16.0, H))[(37 * jnp.arange(H)) % H]
    elif name == "ssm_dt_bias":
        dt = jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(1e-1), H))
        v = dt + jnp.log(-jnp.expm1(-dt))           # softplus^-1(dt)
    elif name == "ssm_D":
        v = jnp.ones((H,))
    elif name == "ssm_conv_w":
        return (jax.random.normal(key, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)
    elif name in ("ssm_conv_b", "router_bias"):
        return (jax.random.normal(key, shape, jnp.float32)
                * 0.02).astype(jnp.float32 if name == "router_bias"
                               else dtype)
    else:
        return None
    return jnp.broadcast_to(v, shape).astype(jnp.float32)


def _init_patterned(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    """A patterned configuration's tree: one stacked leaf a weight A KIND,
    its leading axis the layers of that kind in order."""

    def dense(k, shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * shape[-2] ** -0.5).astype(dtype)

    keys = iter(jax.random.split(key, 32))
    d, hd, KV = cfg.dim, cfg.head_dim, cfg.n_kv_heads
    nA, nE, nM = cfg.n_of("*"), cfg.n_of("E"), cfg.n_of("M")
    layers: Params = {}
    # a gain of the (1 + w) norm starts at 0 (the gated head norms' gains
    # are plain ones whatever the block's norm is)
    gain = jnp.zeros if cfg.rms_offset else jnp.ones

    def attention(kind: str, n: int) -> Params:
        H = cfg.heads_of(kind)
        names = ATTENTION_LEAVES[kind]
        # (an elementwise gate: a head's columns of W_q are [q | gate])
        leaves = dict(zip(names, (
            gain((n, d), dtype),
            dense(next(keys), (n, d, H * hd
                               * (2 if cfg.gate_elementwise else 1))),
            dense(next(keys), (n, d, KV * hd)),
            dense(next(keys), (n, d, KV * hd)),
            dense(next(keys), (n, H * hd, d)))))
        if cfg.gate_per_head:
            leaves[names[5]] = dense(next(keys), (n, d, H))
        if cfg.qk_norm_whole:
            leaves[names[6]] = jnp.ones((n, H * hd), dtype)
            leaves[names[7]] = jnp.ones((n, KV * hd), dtype)
        elif cfg.qk_norm:
            leaves[names[6]] = gain((n, hd), dtype)
            leaves[names[7]] = gain((n, hd), dtype)
        return leaves

    if nA and cfg.latent:
        # latent attention inside a pattern: one stack a leaf over the
        # latent layers (``init_params`` below has the uniform block's)
        layers.update(_latent_leaves(cfg, nA, dense, keys, dtype))
    elif nA:
        layers.update(attention("*", nA))
    if nE:
        E, F, Fs = cfg.n_experts, cfg.mlp_hidden, cfg.shared_mlp_hidden
        layers.update(
            mlp_norm=gain((nE, d), dtype),
            router=dense(next(keys), (nE, d, cfg.experts_scored)),
            w_up=dense(next(keys), (nE, E, d, F)),
            w_down=dense(next(keys), (nE, E, F, d)))
        if cfg.router == "sigmoid_bias":
            # one a SCORED expert: it enters the choice among all of them
            layers["router_bias"] = small_leaf_init(
                "router_bias", (nE, cfg.experts_scored), dtype, next(keys))
        if cfg.gated_mlp:
            layers["w_gate"] = dense(next(keys), (nE, E, d, F))
        if Fs:
            layers["shared_up"] = dense(next(keys), (nE, d, Fs))
            layers["shared_down"] = dense(next(keys), (nE, Fs, d))
            if cfg.gated_mlp:
                layers["shared_gate"] = dense(next(keys), (nE, d, Fs))
            if cfg.shared_expert_gate:
                layers["shared_expert_gate"] = dense(next(keys), (nE, d, 1))
    if nM:
        Hs, di, C = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
        layers.update(
            ssm_in_norm=gain((nM, d), dtype),
            ssm_in=dense(next(keys), (nM, d, di + C + Hs)),
            ssm_gate_norm=jnp.ones((nM, di), dtype),
            ssm_out=dense(next(keys), (nM, di, d)))
        for name, shape in (("ssm_conv_w", (nM, cfg.ssm_conv, C)),
                            ("ssm_conv_b", (nM, C)), ("ssm_dt_bias", (nM, Hs)),
                            ("ssm_A_log", (nM, Hs)), ("ssm_D", (nM, Hs))):
            layers[name] = small_leaf_init(name, shape, dtype, next(keys))
    if cfg.n_of("S"):
        layers.update(attention("S", cfg.n_of("S")))
    if nL := cfg.n_of("L"):
        # W_q | W_k | W_v | W_g fused (the first three under the
        # convolution), W_o; W_a, W_b, the convolution, the decays and the
        # step biases are small leaves, bf16 or float32 in an int8 tree too
        Hl, dv, Cl = cfg.lin_value_heads, cfg.lin_value_dim, cfg.lin_conv_dim
        layers.update(
            lin_norm=gain((nL, d), dtype),
            lin_in=dense(next(keys), (nL, d, Cl + Hl * dv)),
            lin_gate_norm=jnp.ones((nL, dv), dtype),
            lin_out=dense(next(keys), (nL, Hl * dv, d)))
        # (a decay a key channel: W_f [d, heads x key_dim], a projection
        # like the others, and a bias a channel, in the places of W_a and
        # the step bias a head)
        dkl = cfg.lin_key_dim
        decay = ((("lin_wf", (nL, d, Hl * dkl)),
                  ("lin_f_bias", (nL, Hl, dkl))) if cfg.lin_channel_decay
                 else (("lin_wa", (nL, d, Hl)), ("lin_dt_bias", (nL, Hl))))
        for name, shape in (("lin_conv_w", (nL, cfg.lin_conv, Cl)),
                            decay[0], ("lin_wb", (nL, d, Hl)), decay[1],
                            ("lin_A_log", (nL, Hl))):
            layers[name] = small_leaf_init(name, shape, dtype, next(keys))
    if nD := cfg.n_of("D"):
        Fd = cfg.dense_mlp_hidden
        layers.update(
            dense_norm=gain((nD, d), dtype),
            dense_up=dense(next(keys), (nD, d, Fd)),
            dense_down=dense(next(keys), (nD, Fd, d)))
        if cfg.gated_mlp:
            layers["dense_gate"] = dense(next(keys), (nD, d, Fd))
    params: Params = {
        # (drawn without what the model multiplies its embedding by)
        "embed": (jax.random.normal(next(keys), (cfg.vocab_size, d),
                                    jnp.float32)
                  / cfg.embed_multiplier).astype(dtype),
        "layers": layers,
        "final_norm": gain((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(keys), (d, cfg.vocab_size))
    return params


def _latent_leaves(cfg: ModelConfig, L: int, dense, keys, dtype) -> Params:
    """The leaves of ``L`` latent-attention layers, stacked: the query's
    down and up projections with a norm between (``q_lora_rank`` 0: ONE
    projection ``wq`` and no norm), the joint down projection of latent and
    rope key with its norm, ONE up-projection of keys and values [latent,
    heads x (nope | v)] (bf16 in an int8 tree too: absorbed, it is
    contracted over its OUTPUT channels, where int8's per-channel scales
    sit), ``wo``, and the per-head gate's projection where there is one.
    ``dense(key, shape)`` draws a projection."""
    d, H, Qr, C = cfg.dim, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    leaves: Params = {"attn_norm": jnp.ones((L, d), dtype)}
    if Qr:
        leaves.update(w_dq=dense(next(keys), (L, d, Qr)),
                      dq_norm=jnp.ones((L, Qr), dtype),
                      w_uq=dense(next(keys), (L, Qr, H * (N + R))))
    else:
        leaves["wq"] = dense(next(keys), (L, d, H * (N + R)))
    leaves.update(w_dkv=dense(next(keys), (L, d, C + R)),
                  dkv_norm=jnp.ones((L, C), dtype),
                  w_ukv=dense(next(keys), (L, C, H * (N + V))),
                  wo=dense(next(keys), (L, H * V, d)))
    if cfg.attn_gate:
        leaves["wg"] = dense(next(keys), (L, d, H))
    return leaves


def init_params(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> Params:
    """Random init (scaled normal) with the layer axis stacked for scan."""
    if cfg.layer_kinds:
        return _init_patterned(key, cfg, dtype)

    def _dense_init(k, shape, scale):
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(dtype)

    # (a split's keys depend on its count: the other families keep theirs)
    keys = iter(jax.random.split(key, 32 if cfg.latent else 16))
    d, hd, H, KV, F, L = (cfg.dim, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads,
                          cfg.mlp_hidden, cfg.n_layers)
    s_in = d ** -0.5
    s_mlp = F ** -0.5

    layers: Params = {
        "attn_norm": jnp.zeros((L, d), dtype) if cfg.rms_offset else jnp.ones((L, d), dtype),
        "wq": _dense_init(next(keys), (L, d, H * hd), s_in),
        "wk": _dense_init(next(keys), (L, d, KV * hd), s_in),
        "wv": _dense_init(next(keys), (L, d, KV * hd), s_in),
        "wo": _dense_init(next(keys), (L, H * hd, d), (H * hd) ** -0.5),
        "mlp_norm": jnp.zeros((L, d), dtype) if cfg.rms_offset else jnp.ones((L, d), dtype),
    }
    if cfg.latent:
        # Latent attention: the query's down and up projections, the joint
        # down projection of latent and rope key, a norm behind each down
        # projection, and ONE up-projection of keys and values [latent,
        # heads x (nope | v)] — bf16 in an int8 tree too: absorbed, it is
        # contracted over its OUTPUT channels, where int8's per-channel
        # scales sit; no wq / wk / wv.
        Qr, C = cfg.q_lora_rank, cfg.kv_lora_rank
        N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        for name in ("wq", "wk", "wv"):
            del layers[name]
        layers.update(
            w_dq=_dense_init(next(keys), (L, d, Qr), s_in),
            dq_norm=jnp.ones((L, Qr), dtype),
            w_uq=_dense_init(next(keys), (L, Qr, H * (N + R)), Qr ** -0.5),
            w_dkv=_dense_init(next(keys), (L, d, C + R), s_in),
            dkv_norm=jnp.ones((L, C), dtype),
            w_ukv=_dense_init(next(keys), (L, C, H * (N + V)), C ** -0.5),
            wo=_dense_init(next(keys), (L, H * V, d), (H * V) ** -0.5))
    if cfg.qk_norm:
        gain = jnp.zeros if cfg.rms_offset else jnp.ones
        layers["q_norm"] = gain((L, hd), dtype)
        layers["k_norm"] = gain((L, hd), dtype)
    if cfg.selects_keys:
        J, di = cfg.index_heads, cfg.index_head_dim
        layers["idx_wq"] = _dense_init(next(keys), (L, d, J * di), s_in)
        layers["idx_wk"] = _dense_init(next(keys), (L, d, di), s_in)
        layers["idx_ww"] = _dense_init(next(keys), (L, d, J), s_in)
    if cfg.is_moe:
        # the router scores every expert; the leaves hold those of this
        # share (all of them unless ``router_width`` says otherwise)
        E, Fs = cfg.n_experts, cfg.shared_mlp_hidden
        layers["router"] = _dense_init(next(keys),
                                       (L, d, cfg.experts_scored), s_in)
        layers["w_gate"] = _dense_init(next(keys), (L, E, d, F), s_in)
        layers["w_up"] = _dense_init(next(keys), (L, E, d, F), s_in)
        layers["w_down"] = _dense_init(next(keys), (L, E, F, d), s_mlp)
        if Fs:
            layers["shared_gate"] = _dense_init(next(keys), (L, d, Fs), s_in)
            layers["shared_up"] = _dense_init(next(keys), (L, d, Fs), s_in)
            layers["shared_down"] = _dense_init(next(keys), (L, Fs, d),
                                                Fs ** -0.5)
            if cfg.shared_expert_gate:
                layers["shared_expert_gate"] = _dense_init(
                    next(keys), (L, d, 1), s_in)
    else:
        layers["w_gate"] = _dense_init(next(keys), (L, d, F), s_in)
        layers["w_up"] = _dense_init(next(keys), (L, d, F), s_in)
        layers["w_down"] = _dense_init(next(keys), (L, F, d), s_mlp)

    params: Params = {
        "embed": _dense_init(next(keys), (cfg.vocab_size, d),
                             1.0 / cfg.embed_multiplier),
        "layers": layers,
        "final_norm": jnp.zeros((d,), dtype) if cfg.rms_offset else jnp.ones((d,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = _dense_init(next(keys), (d, cfg.vocab_size), s_in)
    return params


# ----------------------------------------------------- block-paged pool
#
# Pool-mode KV (ISSUE 10): the cache leaves are [n_layers, n_blocks,
# page, KV, hd] — one shared block pool instead of per-slot regions —
# and a per-slot block table [B, max_pages] maps sequence page p of slot
# b to pool block tables[b, p]. The helpers below are the only places
# the indirection lives: writes scatter through the table into the
# flattened pool (out-of-bounds rows — table sentinel or a False
# write_mask — drop, exactly like the dense path's OOB trick), reads
# gather each slot's pages back into the dense [B, kv_limit, KV, hd]
# view the existing attention backends consume. The TPU fast path skips
# the gather entirely (ops/ragged_attention.py block-table kernel).


def _pool_flat_pos(tables, positions, page: int, n_blocks: int,
                   write_mask) -> jnp.ndarray:
    """[B, S] flat pool-row index per token; OOB (== n_blocks*page) for
    unmapped pages and masked rows, which the scatter drops."""
    pg = positions // page
    blk = jnp.take_along_axis(tables, pg, axis=1)
    flat = blk * page + positions % page
    oob = n_blocks * page
    flat = jnp.where(blk >= n_blocks, oob, flat)
    if write_mask is not None:
        # [B] gates whole rows (device-side termination); [B, S] gates
        # per token — ragged admission windows (ISSUE 19) write only
        # their first q_lens[b] columns.
        wm = write_mask if write_mask.ndim == 2 else write_mask[:, None]
        flat = jnp.where(wm, flat, oob)
    return flat


def _at_layer(layer, *idx):
    """Index tuple into a cache leaf: ``idx`` within a single layer, or
    ``(layer,) + idx`` within the stacked [L, ...] leaf the layer scan
    carries (ISSUE 25)."""
    return idx if layer is None else (layer,) + idx


def _pool_scatter(leaf, flat, updates, layer=None):
    """Scatter [B, S, ...] updates into a [n_blocks, page, ...] pool leaf
    — or, with ``layer``, into that layer of the stacked [L, n_blocks,
    page, ...] leaf — at flat row indices (OOB drops). One scatter into
    the buffer it is given: on a loop-carried, donated pool XLA performs
    it in place."""
    i = 0 if layer is None else 1
    f = leaf.reshape(leaf.shape[:i] + (leaf.shape[i] * leaf.shape[i + 1],)
                     + leaf.shape[i + 2:])
    f = f.at[_at_layer(layer, flat)].set(updates.astype(leaf.dtype))
    return f.reshape(leaf.shape)


def _pool_gather(leaf, tables, n_pages: int, layer=None):
    """Gather each slot's first ``n_pages`` pages (of ``layer``, when the
    leaf is the stacked pool) into the contiguous [B, n_pages*page, ...]
    view dense/flash attention reads. Sentinel table entries clamp to a
    real block — those positions sit beyond the slot's live length,
    where the causal mask already excludes them."""
    i = 0 if layer is None else 1
    idx = jnp.clip(tables[:, :n_pages], 0, leaf.shape[i] - 1)
    g = leaf[_at_layer(layer, idx)]
    return g.reshape((idx.shape[0], n_pages * leaf.shape[i + 1])
                     + leaf.shape[i + 2:])


# ------------------------------------------------ a window's valid rows
#
# A mixed admission window (ISSUE 19) is [N, W]: N slots, each with its
# first q_lens[n] columns valid (a staged suffix, or one decode token). Its
# residual stream carries only those rows, packed (ISSUE 39): R = W + N
# rows at most, where slot x width was 16 x 128 = 2,048 rows of GEMM for
# the 115 that were valid. The mixers (paged attention, the key selector,
# the state-space scan) and the cache writes keep their [N, W, ...]
# operands; ``unpack`` and ``pack`` are the two gathers around them.


class WindowRows(NamedTuple):
    """The valid rows of an [N, W] window in slot order, then padding rows
    (``valid`` False; they repeat row 0's indices and nothing reads what
    they compute)."""

    slot: jnp.ndarray     # [R] int32: the row's slot
    col: jnp.ndarray      # [R] int32: its column of the window
    valid: jnp.ndarray    # [1, R] bool: the packed token mask
    pos: jnp.ndarray      # [1, R] int32: its absolute position
    row_of: jnp.ndarray   # [N, W] int32: the packed row of (slot, column);
                          # an invalid column names some other row, whose
                          # values the mixers mask and the writes drop

    def pack(self, x: jnp.ndarray) -> jnp.ndarray:
        """[N, W, ...] -> [1, R, ...]"""
        return x[self.slot, self.col][None]

    def unpack(self, x: jnp.ndarray) -> jnp.ndarray:
        """[1, R, ...] -> [N, W, ...]"""
        return x[0][self.row_of]


def window_rows(q_lens: jnp.ndarray, positions: jnp.ndarray,
                n_rows: int) -> WindowRows:
    """Pack an [N, W] window (``positions``) whose slot n brings its first
    ``q_lens[n]`` columns into ``n_rows`` rows. The caller sees to it that
    they fit: the scheduler stages suffixes whose lengths sum to at most
    W, the other slots ride one column each, so W + N rows hold them."""
    N, W = positions.shape
    q_lens = q_lens.astype(jnp.int32)
    end = jnp.cumsum(q_lens)
    first = end - q_lens
    r = jnp.arange(n_rows, dtype=jnp.int32)
    valid = r < end[-1]
    slot = jnp.where(valid, jnp.minimum(
        jnp.searchsorted(end, r, side="right").astype(jnp.int32), N - 1), 0)
    col = jnp.where(valid, r - first[slot], 0)
    row_of = jnp.minimum(
        first[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :], n_rows - 1)
    return WindowRows(slot, col, valid[None], positions[slot, col][None],
                      row_of)


# ------------------------------------------------- residual sharding
#
# f≈1 residual-path TP sharding (ISSUE 14): with weights Megatron-split
# over ``model``, the classic layout replicates the [B, S, d] residual
# on every TP shard — norms, RoPE epilogues, residual adds and the
# sampling scratch then run tp× redundantly. Pinning the
# residual batch-sharded over data×model at the sites below makes XLA
# fuse each row-parallel GEMM's all-reduce into a reduce-scatter at its
# output (plus one all-gather at the next column-parallel input): the
# elementwise segments between GEMMs run 1/tp-sized per shard and the
# collective count stays 2 fused pairs per layer.
# ``parallel/sharding.py::residual_spec`` owns the
# policy (and the pipe/expert/divisibility gates).


def _shard_residual(mesh, x: jnp.ndarray) -> jnp.ndarray:
    """Pin the [B, S, d] residual to the f≈1 layout (no-op when the
    policy doesn't apply to this mesh/shape). The named_scope is what
    lets benchmark/xtrace.py find the fused collectives XLA materializes
    at this boundary."""
    if mesh is None:
        return x
    from ..parallel.sharding import residual_spec

    spec = residual_spec(mesh, x.shape)
    if spec is None:
        return x
    from jax.sharding import NamedSharding

    with jax.named_scope("all_reduce"):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))


def _shard_logits(mesh, logits: jnp.ndarray) -> jnp.ndarray:
    """Pin [B, S, vocab] logits vocab-sharded over ``model`` (the LM
    head's natural output layout) so the head output and the sampling
    chain's vocab-sized scratch shard instead of replicating — the
    lm_head_sampling slice of the f≈1 residual. Sampling semantics are
    untouched: ``sample_tokens_seeded`` runs the same program over the
    sharded operand and draws the identical token (the byte-identity
    suites are the tripwire)."""
    if mesh is None:
        return logits
    from ..parallel.sharding import logits_spec

    spec = logits_spec(mesh, logits.shape[-1])
    if spec is None:
        return logits
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(
        logits, NamedSharding(mesh, spec))


# -------------------------------------------------------------- blocks

def _activation(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if cfg.activation == "relu2":
        return jnp.square(jax.nn.relu(x))
    return jax.nn.silu(x)


def _scaled(cfg: ModelConfig, out: jnp.ndarray) -> jnp.ndarray:
    """A sublayer's output as it joins the residual: times ``residual_
    multiplier`` where the model has one (no multiply in a program of a
    model that has none)."""
    if cfg.residual_multiplier == 1.0:
        return out
    return out * jnp.asarray(cfg.residual_multiplier, out.dtype)


def _dense_mlp(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
               prefix: str = "w_") -> jnp.ndarray:
    """The dense MLP of leaves ``<prefix>gate/up/down``: gated, or for
    ``relu2`` two matrices with the activation between them."""
    up = qmatmul(x, lp[prefix + "up"])
    if cfg.gated_mlp:
        up = _activation(cfg, qmatmul(x, lp[prefix + "gate"])) * up
    else:
        up = _activation(cfg, up)
    return qmatmul(up, lp[prefix + "down"])


def _shared_expert(cfg: ModelConfig, lp: Params, x: jnp.ndarray):
    """The expert every token takes, whole on every chip: added as it is,
    or (``shared_expert_gate``) under sigmoid(x w_s), one scalar a token."""
    y = _dense_mlp(cfg, lp, x, "shared_")
    if not cfg.shared_expert_gate:
        return y
    with jax.named_scope("shared_gate"):
        gate = jax.nn.sigmoid(
            (x @ lp["shared_expert_gate"]).astype(jnp.float32))
        return (y.astype(jnp.float32) * gate).astype(y.dtype)


#: The expert leaves of a layer (stacked [L, E, in, out] in the param tree).
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def serves_grouped(cfg: ModelConfig, mesh, moe_impl: str) -> bool:
    """Whether ``_moe_mlp`` takes the token-grouped expert GEMM: one
    device, no forced implementation, and the configuration's own static
    rule (``ModelConfig.grouped_experts``)."""
    return cfg.grouped_experts and mesh is None and moe_impl == "auto"


def _moe_mlp(cfg: ModelConfig, lp: Params, x: jnp.ndarray,
             mesh=None, token_mask=None,
             moe_impl: str = "auto", layer=None):
    """MoE MLP with impl selection (the seam VERDICT r2 item 2 asked for).
    Returns (y, what the pass counted by ``KVCache`` field): the grouped
    path's ``experts_read`` and ``expert_picks``, nothing on the others.

    ``moe_impl``:

    - ``auto``: the expert-parallel all-to-all dispatch
      (parallel/moe.py::expert_parallel_moe) whenever a mesh with a >1
      ``expert`` axis is in scope and the static shapes divide it;
      otherwise, on one device, the token-grouped expert GEMM
      (parallel/moe.py::grouped_moe) for a configuration with many
      experts a token does not pick (``ModelConfig.grouped_experts``,
      the one static rule; no setting), and else the dense all-experts
      evaluation — the single-device reference the other two are
      parity-tested against.
    - ``ep``: ALWAYS the dispatch (requires a mesh with an ``expert``
      axis; ep=1 degenerates the all_to_alls to local copies) — how a
      single chip serves/benches the real dispatch path rather than the
      dense evaluation (VERDICT r4 item 3).
    - ``dense``: always the dense evaluation.

    The choice is static per compiled program (shapes and mesh are
    trace-time constants), so serving programs pay zero dispatch
    overhead. ``token_mask`` ([B, S], 0 = dead slot or bucket padding)
    keeps garbage tokens from consuming expert capacity.
    """
    from ..parallel.moe import (dense_moe, expert_parallel_moe,
                                grouped_moe_counted)

    if moe_impl == "dense":
        return dense_moe(cfg, lp, x, mesh), {}
    if mesh is not None and "expert" in mesh.axis_names:
        ep = mesh.shape["expert"]
        B, S, _ = x.shape
        if ((moe_impl == "ep" or ep > 1)
                and (B * S) % ep == 0 and cfg.n_experts % ep == 0):
            # Decode steps (S == 1) have only a handful of live tokens per
            # shard; capacity_factor sizing there would make drops likely
            # under routing skew. capacity = T_local makes drops impossible
            # at negligible buffer cost, preserving single-device parity.
            capacity = (B * S) // ep if S == 1 else None
            return expert_parallel_moe(cfg, lp, x, mesh, capacity=capacity,
                                       token_mask=token_mask), {}
    if moe_impl == "ep":
        raise ValueError(
            "MOE_IMPL=ep needs a mesh with an expert axis whose size "
            "divides tokens and experts")
    if serves_grouped(cfg, mesh, moe_impl):
        # ``lp``'s expert leaves are then the WHOLE stacks and ``layer``
        # picks the layer inside the kernel's index maps (``forward``'s
        # scan closes over them): sliced out as the scan's xs they were
        # copied, 604 MB a layer a pass, before the kernel read them.
        y, n_read, picks = grouped_moe_counted(
            cfg, lp, x, token_mask, cfg.first_expert, layer=layer)
        counted = {"experts_read": n_read}
        if cfg.counts_picks:    # models/families.py's ``expert_share`` kind
            counted["expert_picks"] = picks
        return y, counted
    return dense_moe(cfg, lp, x, mesh), {}


def _select_and_attend(cfg: ModelConfig, attn_impl: str, q, qi, wi,
                       layer_k, layer_v, layer_ik, positions, q_lens,
                       block_tables, n_pages: int, layer, token_mask):
    """Attention of a selecting configuration over the block pool, inside
    the caller's ``attention`` scope (ops/sparse_select.py has the
    mathematics). Returns (attn, int32 [2]: the keys the call's live
    decode rows had before them, and the keys kept of those — read off
    the mask itself, so a selector that keeps too much or too little
    shows in /health.sparse_attention). While no live row of the call
    has more than ``index_topk`` keys before it, every key is selected
    and the dense path serves, bit for bit. Past that, every row that
    rides (a decode step's one, a slot's first in a mixed window, and
    all the rows of a slot that brings a window) scores the slot's index keys through the
    block table, takes the exact top-k as a per-row mask, and the paged
    kernel applies it to its causal scores.

    Decode rows take the same path as window rows, a window of one. The
    first version fetched a decode query's selected K/V rows one by one
    (``jax.lax.top_k`` and an XLA gather through the table, 32,768 rows
    of 1 KB a layer at batch 16); on the chip it was never the cost (a
    command read 45.1 s with it and 46.8 s without: copies of whole pool
    leaves were, PERF.md PR 31), and it went for being a second path: at
    2,048 of ~11,000 keys every 64-row page holds a dozen selected rows,
    so the kernel's page stream reads little that a row fetch would skip.
    A kernel that keeps only the selected rows is ROADMAP's."""
    from ..ops.ragged_attention import ragged_attention_pool
    from ..ops.sparse_select import index_scores, window_selection

    B, S = positions.shape
    page = layer_k.shape[-3]
    kv_limit = n_pages * page
    ql = (jnp.full((B,), S, jnp.int32) if q_lens is None
          else q_lens.astype(jnp.int32))

    def attend(sel=None):
        if attn_impl == "ragged":
            return ragged_attention_pool(
                q, layer_k, layer_v, ql, positions[:, 0], block_tables,
                layer, sel=sel, page_size=page)
        mask = (jnp.arange(kv_limit)[None, None, :]
                <= positions[:, :, None])
        if sel is not None:
            mask = jnp.logical_and(mask, sel)
        return dense_attention(
            q, _pool_gather(layer_k, block_tables, n_pages, layer),
            _pool_gather(layer_v, block_tables, n_pages, layer), mask)

    # A decode row: a live slot's one query (a window's first column).
    decode = ql == 1
    if token_mask is not None:
        decode = jnp.logical_and(decode, token_mask[:, 0])
    live = jnp.sum(jnp.where(decode, positions[:, 0] + 1, 0))

    def every_key(_):
        return attend(), jnp.stack([live, live])

    def select(qi, wi, ik, positions):
        with jax.named_scope("index_scores"):
            scores = index_scores(qi, wi, ik, positions)      # [b, s, K]
        with jax.named_scope("select"):
            return window_selection(scores, cfg.index_topk)

    def selected(_):
        with jax.named_scope("index_scores"):
            ik = _pool_gather(layer_ik, block_tables, n_pages, layer)
            # the leaf's rows are 128 lanes wide, zeros above the key
            wide = jnp.pad(
                qi, ((0, 0),) * 3 + ((0, ik.shape[-1] - qi.shape[-1]),))
        # Every slot's first row, batched: a decode step's one row, and in
        # a mixed window the row of each slot that rides at q_len 1.
        sel = select(wide[:, :1], wi[:, :1], ik, positions[:, :1])
        if S > 1:
            # Only a slot that brings a window (q_len > 1) pays for a
            # window's rows, one slot at a time; the rows nobody rides
            # keep every key (the kernel masks them out whole).
            def window(xs):
                q_b, w_b, ik_b, pos_b, n = xs
                return jax.lax.cond(
                    n > 1,
                    lambda: select(q_b[None], w_b[None], ik_b[None],
                                   pos_b[None])[0],
                    lambda: jnp.ones((S, ik_b.shape[0]), bool))

            rows = jax.lax.map(window, (wide, wi, ik, positions, ql))
            first = jnp.where((ql > 1)[:, None, None], rows[:, :1], sel)
            sel = jnp.concatenate([first, rows[:, 1:]], axis=1)
        with jax.named_scope("select"):
            kept = jnp.sum(jnp.where(decode[:, None], sel[:, 0], False),
                           dtype=jnp.int32)
        return attend(sel), jnp.stack([live, kept])

    last = positions[:, 0] + ql          # keys the row's last query sees
    return jax.lax.cond(
        jnp.max(jnp.where(ql > 0, last, 0)) <= cfg.index_topk,
        every_key, selected, None)


def _latent_attention(cfg: ModelConfig, attn_impl: str, x, lp: Params,
                      layer_lat, positions, kv_limit: int, token_mask,
                      write_mask, block_tables, q_lens, layer, win=None):
    """Latent attention (MLA) of the normed hidden state ``x`` [B, S, d]
    over the block pool's latent leaf (ops/latent_attention.py has the two
    forms; this is the ABSORBED one for every row, decode and window
    alike, so a row that reads pooled tokens and one that reads its own
    window's are the same row). Returns (the layer's attention output
    through ``wo`` [B, S, d], the leaf with the window's rows written,
    int32 [2]: the call's live decode rows and the cached rows those had
    before them). With ``win`` ``x`` is the [1, R, d] packed rows of the
    [B, S] window (``_layer``): the projections, the rotary and both
    absorptions run packed, the write and the attention on the window.

    The sizes are the configuration's latent ones, never ``head_dim``. The
    softmax scale (``qk_head_dim ** -0.5`` times YaRN's temperature
    squared) and the position-dependent query scale are folded into the
    queries, in float32, before they are rounded: the kernel multiplies by
    1. Scopes: the projections and the absorption ``qkv_proj``, the
    un-absorption and ``wo`` ``o_proj``; the write ``kv_write``."""
    from ..ops.latent_attention import absorbed_attention, write_rows
    from ..ops.ragged_attention import (latent_attention_pool, latent_query,
                                        latent_unpack)
    from ..ops.rope import (apply_rope_scaled, query_scale, yarn_frequencies,
                            yarn_mscale)

    if block_tables is None or layer_lat is None:
        raise NotImplementedError(
            f"{cfg.name} keeps a latent cache (kv_lora_rank="
            f"{cfg.kv_lora_rank}): its rows live in the block pool, so it "
            "is served through the pool alone (KV_POOL, one device)")
    B, S = positions.shape
    Bh, Sh, _ = x.shape
    rope_pos = positions if win is None else win.pos
    H, C = cfg.n_heads, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("qkv_proj"):
        if cfg.q_lora_rank:
            cq = rms_norm(qmatmul(x, lp["w_dq"]), lp["dq_norm"], cfg.rms_eps)
            q = qmatmul_heads(cq, lp["w_uq"], H, N + R)
        else:
            # no query LoRA: one projection, no norm on the query's side
            q = qmatmul_heads(x, lp["wq"], H, N + R)
        ckr = qmatmul(x, lp["w_dkv"])
        c = rms_norm(ckr[..., :C], lp["dkv_norm"], cfg.rms_eps)
        gate = None
        if cfg.attn_gate:
            # one scalar a head, from the layer's normed input
            gate = jax.nn.sigmoid((x @ lp["wg"]).astype(jnp.float32))
    with jax.named_scope("rope"):
        inv_freq = yarn_frequencies(
            R, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_max,
            cfg.rope_beta_fast, cfg.rope_beta_slow)
        factor = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                  / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
        q_r = apply_rope_scaled(q[..., N:], rope_pos, inv_freq,
                                cfg.rope_interleave, factor)
        kr = apply_rope_scaled(ckr[..., None, C:], rope_pos, inv_freq,
                               cfg.rope_interleave, factor)[:, :, 0]
    w_ukv = lp["w_ukv"].reshape(C, H, N + V)
    with jax.named_scope("qkv_proj"):
        scale = ((N + R) ** -0.5
                 * yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2)
        g = scale * (query_scale(rope_pos, cfg.q_scale_beta,
                                 cfg.rope_original_max)
                     if cfg.q_scale_beta else jnp.ones(rope_pos.shape,
                                                       jnp.float32))
        g = g[..., None, None]
        # float32 operands (a TPU multiplies them as bf16 and accumulates
        # in float32 all the same; the CPU has no bf16 x bf16 -> f32 dot)
        q_c = jnp.einsum("bshn,chn->bshc", q[..., :N].astype(jnp.float32),
                         w_ukv[..., :N].astype(jnp.float32))
        q_c = (q_c * g).astype(x.dtype)
        q_r = (q_r.astype(jnp.float32) * g).astype(x.dtype)
    if win is not None:
        with jax.named_scope("window_unpack"):
            q_c, q_r, c, kr = (win.unpack(q_c), win.unpack(q_r),
                               win.unpack(c), win.unpack(kr))

    n_blocks, half_page = layer_lat.shape[-3:-1]
    page = 2 * half_page
    if kv_limit % page:
        raise ValueError(
            f"pool kv_limit {kv_limit} not a multiple of page {page}")
    flat = _pool_flat_pos(block_tables, positions, page, n_blocks, write_mask)
    with jax.named_scope("kv_write"):
        layer_lat = write_rows(layer_lat, flat, positions, c, kr, layer)
    ql = (jnp.full((B,), S, jnp.int32) if q_lens is None
          else q_lens.astype(jnp.int32))
    with jax.named_scope("attention"):
        if attn_impl == "ragged":
            o_c = latent_attention_pool(
                latent_query(q_c, q_r), layer_lat, ql, positions[:, 0],
                block_tables, layer, v_lanes=C, page_size=page)
        else:
            n_pages = kv_limit // page
            c_ctx, kr_ctx = latent_unpack(
                _pool_gather(layer_lat, block_tables, n_pages, layer), C)
            mask = jnp.arange(kv_limit)[None, None, :] <= positions[:, :, None]
            o_c = absorbed_attention(q_c, q_r, c_ctx, kr_ctx, mask)
    with jax.named_scope("o_proj"):
        if win is not None:
            o_c = win.pack(o_c)
        o = jnp.einsum("bshc,chv->bshv", o_c, w_ukv[..., N:])
        if gate is not None:
            o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
        out = qmatmul(o.reshape(Bh, Sh, H * V), lp["wo"])
    decode = ql == 1
    if token_mask is not None:
        decode = jnp.logical_and(decode, token_mask[:, 0] > 0)
    rows = jnp.stack([
        jnp.sum(decode, dtype=jnp.int32),
        jnp.sum(jnp.where(decode, positions[:, 0] + 1, 0), dtype=jnp.int32)])
    return out, layer_lat, rows


def _rotary_rule(cfg: ModelConfig, kind: str):
    """``rotate(x, positions)`` of attention kind ``kind``: the plain rotary
    embedding on every lane for every family but one; for a configuration
    with a rule a kind, the kind's theta on its rotated share of the lanes,
    the full kind's with YaRN frequencies and its factor on cos and sin."""
    from ..ops.rope import apply_rope_partial, yarn_frequencies

    theta, part = ((cfg.sliding_rope_theta, cfg.sliding_rope_partial)
                   if kind == "S" else (cfg.rope_theta, cfg.rope_partial))
    scaled = kind != "S" and (cfg.rope_factor > 1.0
                              or cfg.rope_attention_factor)
    if part == 1.0 and not scaled:
        return lambda x, pos: apply_rope(x, pos, theta)
    rot = int(cfg.head_dim * part) // 2 * 2
    # (a factor of 1 gives the plain frequencies)
    inv_freq = yarn_frequencies(
        rot, theta, cfg.rope_factor if scaled else 1.0,
        cfg.rope_original_max, cfg.rope_beta_fast, cfg.rope_beta_slow)
    factor = (cfg.rope_attention_factor or 1.0) if scaled else 1.0
    return lambda x, pos: apply_rope_partial(x, pos, inv_freq, factor)


def _query_lens(positions, q_lens):
    """[B] int32: each row's valid query columns (every column without
    ``q_lens``)."""
    B, S = positions.shape
    return (jnp.full((B,), S, jnp.int32) if q_lens is None
            else q_lens.astype(jnp.int32))


def _span_rows(cfg: ModelConfig, kind: str, positions, q_lens, token_mask):
    """int32 [4]: this layer's live decode rows and the keys their queries
    see, in the sliding kind's two words or the full kind's (``KVCache.
    span_rows``) — the bound the mask below applies, counted beside it."""
    decode = _query_lens(positions, q_lens) == 1
    if token_mask is not None:
        decode = jnp.logical_and(decode, token_mask[:, 0] > 0)
    keys = positions[:, 0] + 1
    if kind == "S":
        keys = jnp.minimum(keys, cfg.sliding_window)
    pair = jnp.stack([jnp.sum(decode, dtype=jnp.int32),
                      jnp.sum(jnp.where(decode, keys, 0), dtype=jnp.int32)])
    zero = jnp.zeros((2,), jnp.int32)
    return jnp.concatenate([pair, zero] if kind == "S" else [zero, pair])


def _pad_heads(a, n: int):
    """``a`` [..., heads, lanes] with ``n`` zero heads behind its own."""
    return jnp.pad(a, ((0, 0),) * (a.ndim - 2) + ((0, n), (0, 0)))


def _ring_write(sk, sv, k, v, positions, write_mask, layer):
    """The window's K/V rows into layer ``layer`` of the sequences' rings,
    position p at row ``p % ring`` of its batch row; masked rows drop."""
    B, ring = positions.shape[0], sk.shape[2]
    row = positions % ring
    if write_mask is not None:
        wm = write_mask if write_mask.ndim == 2 else write_mask[:, None]
        row = jnp.where(wm, row, ring)            # out of bounds: dropped
    idx = (layer, jnp.arange(B)[:, None], row)
    return (sk.at[idx].set(k.astype(sk.dtype), mode="drop"),
            sv.at[idx].set(v.astype(sv.dtype), mode="drop"))


def _sliding_attention(cfg: ModelConfig, attn_impl: str, q, sk, sv,
                       positions, q_lens, kv_limit: int, layer):
    """Attention of a sliding layer over the sequences' rings (``sk``/``sv``
    [layers, B, ring, KV, hd], the window's own rows already written):
    query t sees keys s with t - span < s <= t. The ragged kernel reads a
    ring as a small pool of its own — batch row b's sequence page p is the
    ring's block ``b * ring_pages + p % ring_pages`` — through the SAME
    page stream, started at the page that holds the tile's first key; the
    gather path reads the span + S - 1 rows the window can see."""
    B, S = positions.shape
    span, ring = cfg.sliding_window, sk.shape[2]
    if S + span > ring + 1:
        raise ValueError(
            f"a {S}-wide window beside a span of {span} needs a ring of "
            f"{S + span - 1} rows; this one has {ring}")
    if attn_impl == "ragged":
        from ..ops.ragged_attention import ragged_attention_pool, ring_tables

        page = math.gcd(ring, 64)     # the view's own page, not the pool's
        as_pool = lambda a: a.reshape(a.shape[0], B * (ring // page), page,
                                      *a.shape[3:])
        return ragged_attention_pool(
            q, as_pool(sk), as_pool(sv), _query_lens(positions, q_lens),
            positions[:, 0],
            ring_tables(B, ring // page, -(-kv_limit // page)), layer,
            page_size=page, window=span)
    first = positions[:, :1] - (span - 1)                       # [B, 1]
    key_pos = first + jnp.arange(span + S - 1)[None, :]          # [B, K]
    rows = (jnp.arange(B)[:, None], key_pos % ring)
    mask = jnp.logical_and(
        key_pos[:, None, :] <= positions[:, :, None],
        jnp.logical_and(key_pos[:, None, :] > positions[:, :, None] - span,
                        key_pos[:, None, :] >= 0))
    return dense_attention(q, sk[layer][rows], sv[layer][rows], mask)


def _layer(cfg: ModelConfig, attn_impl: str, mesh, moe_impl: str,
           h: jnp.ndarray, lp: Params,
           layer_k: jnp.ndarray, layer_v: jnp.ndarray,
           positions: jnp.ndarray, kv_limit: int,
           batch_idx: jnp.ndarray,
           token_mask,
           write_mask=None,
           block_tables=None,
           q_lens=None,
           layer=None,
           layer_ik=None,
           win: Optional[WindowRows] = None,
           kind: str = "*") -> Tuple[jnp.ndarray, ...]:
    """One transformer block. Returns (h_out, new_layer_k, new_layer_v,
    new_layer_ik, counts): ``layer_ik`` is the index-key leaf of a
    selecting configuration — or the latent leaf of a latent-attention
    one, whose K and V are returned as given (``_latent_attention``) —
    (None otherwise, returned as given) and
    ``counts`` holds what this pass counted on the device, under the
    ``KVCache`` field each adds to: ``experts_read`` where the grouped
    expert path served, ``sel_rows`` where keys were selected,
    ``lat_rows`` where latent rows were read.

    ``layer`` (a traced scalar; ISSUE 25): ``layer_k``/``layer_v`` are
    then the WHOLE stacked cache leaves [L, ...] that ``forward``'s layer
    scan carries, and every access below addresses ``[layer]`` inside
    them — the rows are scattered into the carried buffer in place, the
    Pallas kernels pick the layer in their index maps, and the XLA
    readers slice it where they consume it — so no layer, let alone the
    pool, is copied out of the stack and back. Without it (the pipe-mesh
    stage body) they are one layer's leaves, as before.

    The ``jax.named_scope`` blocks here (and in ``forward``/sampling) are
    zero-cost HLO metadata: XLA stamps each op's ``op_name`` with the
    scope path, which the profiler trace exports — the benchmark's trace
    reduction (benchmark/xtrace.py) bills device spans to op categories
    by these names instead of guessing from HLO op types.

    ``write_mask`` ([B] bool, decode only): rows whose mask is False skip
    the KV-cache scatter entirely — their write positions are pushed out
    of bounds, and OOB scatter updates are dropped by jax. This is how
    slots terminated mid-chunk by the device-resident done mask
    (engine/batcher.py) stop mutating their cache region instead of
    rewriting garbage at a frozen position every remaining step.

    ``win`` (ISSUE 39): ``h`` is then the [1, R, d] packed valid rows of
    the [N, W] window that ``positions``, the masks and ``q_lens``
    describe. Norms, projections, rotary and the MLP run on the packed
    rows; q, k, v (and a selector's operands) are unpacked to [N, W, ...]
    for the cache write and the attention, whose output is packed again.

    ``kind`` (static; a configuration with attention of two kinds): ``S``
    is a sliding-attention layer — its own head count and rotary rule,
    ``layer_k``/``layer_v`` the sequences' RINGS (``KVCache.sk``/``sv``)
    and not the pool, every query bounded to its span
    (``_sliding_attention``) — and ``*`` the full kind.
    """
    B, S = positions.shape              # the window the mixer sees
    Bh, Sh, d = h.shape                 # the rows the residual carries
    H, KV, hd = cfg.heads_of(kind), cfg.n_kv_heads, cfg.head_dim
    counts = {}
    rope_pos = positions if win is None else win.pos
    mlp_mask = token_mask if win is None else win.valid

    def mlp_block(h):
        x = rms_norm(h, lp["mlp_norm"], cfg.rms_eps, cfg.rms_offset)
        if not cfg.is_moe:
            return _dense_mlp(cfg, lp, x)
        y, got = _moe_mlp(cfg, lp, x, mesh, mlp_mask, moe_impl, layer)
        counts.update(got)
        if cfg.shared_mlp_hidden:
            y = y + _shared_expert(cfg, lp, x)
        return y

    def after_attention(h):
        """The block's second half: the MLP and its residual — nothing for
        a patterned configuration, whose attention layer is that mixer
        alone."""
        if cfg.layer_pattern:
            return h
        with jax.named_scope("mlp"):
            mlp = mlp_block(h)
        return _shard_residual(mesh, h + _scaled(cfg, mlp))

    def out_proj(attn):
        """The attention's [B, S, H, hd] output through ``wo`` onto the
        residual (packed first where the residual is), normed on its way
        where the block norms a sublayer's output."""
        with jax.named_scope("o_proj"):
            attn = attn[..., :H, :]         # less the spare heads' zeros
            if win is not None:
                attn = win.pack(attn)
            out = qmatmul(attn.reshape(Bh, Sh, H * hd), lp["wo"])
        if cfg.post_norm:
            with jax.named_scope("attn_norm"):
                out = rms_norm(out, lp["attn_norm"], cfg.rms_eps,
                               cfg.rms_offset)
        return _shard_residual(mesh, h + _scaled(cfg, out))

    x = h
    if not cfg.post_norm:
        with jax.named_scope("attn_norm"):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_eps, cfg.rms_offset)
    if cfg.latent:
        out, layer_ik, counts["lat_rows"] = _latent_attention(
            cfg, attn_impl, x, lp, layer_ik, positions, kv_limit,
            token_mask, write_mask, block_tables, q_lens, layer, win)
        return (after_attention(_shard_residual(mesh, h + _scaled(cfg, out))),
                layer_k, layer_v, layer_ik, counts)
    with jax.named_scope("qkv_proj"):
        gate = None
        if cfg.gate_elementwise:
            # a head's columns of W_q are [q | gate]: the gate's logits, as
            # wide as the head, ride beside q to the attention's output
            qg = qmatmul_heads(x, lp["wq"], H, 2 * hd)
            q, gate = qg[..., :hd], qg[..., hd:]
        else:
            q = qmatmul_heads(x, lp["wq"], H, hd)
        k = qmatmul_heads(x, lp["wk"], KV, hd)
        v = qmatmul_heads(x, lp["wv"], KV, hd)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"], cfg.rms_eps, cfg.rms_offset)
            k = rms_norm(k, lp["k_norm"], cfg.rms_eps, cfg.rms_offset)
        if cfg.qk_norm_whole:
            # one norm over a token's whole projection, every head's lanes
            whole = lambda a, w: rms_norm(
                a.reshape(Bh, Sh, -1), w, cfg.rms_eps).reshape(a.shape)
            q, k = whole(q, lp["q_norm"]), whole(k, lp["k_norm"])
        if cfg.selects_keys:
            # The indexer reads the same normed hidden state: its
            # queries, its ONE key a token, its per-head weights.
            J, di = cfg.index_heads, cfg.index_head_dim
            qi = qmatmul_heads(x, lp["idx_wq"], J, di)
            ki = qmatmul(x, lp["idx_wk"]).reshape(Bh, Sh, 1, di)
            wi = x @ lp["idx_ww"]
        if cfg.gate_per_head:
            # one scalar a head, from the layer's normed input
            gate = jax.nn.sigmoid((x @ lp["wg"]).astype(jnp.float32))
    with jax.named_scope("rope"):
        if cfg.use_rope:
            rotate = _rotary_rule(cfg, kind)
            q, k = rotate(q, rope_pos), rotate(k, rope_pos)
        if cfg.selects_keys:
            qi = apply_rope(qi, rope_pos, cfg.rope_theta)
            ki = apply_rope(ki, rope_pos, cfg.rope_theta)[:, :, 0]
    if win is not None:
        # the mixer's operands, a slot a row of the window again
        with jax.named_scope("window_unpack"):
            q, k, v = win.unpack(q), win.unpack(k), win.unpack(v)
            if cfg.selects_keys:
                qi, ki, wi = (win.unpack(qi), win.unpack(ki),
                              win.unpack(wi))
            if gate is not None:
                gate = win.unpack(gate)

    def gated(attn):
        """Each head's output times its gate (float32, rounded once): a
        scalar a head, or (elementwise) the sigmoid of a logit a lane."""
        if gate is None:
            return attn
        attn = attn[..., :H, :]
        if cfg.gate_elementwise:
            with jax.named_scope("gate"):
                return (attn.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(attn.dtype)
        return (attn.astype(jnp.float32) * gate[..., None]).astype(attn.dtype)

    # A cache row may hold more KV heads than the model has (``ModelConfig.
    # kv_heads_paged``: 30 are kept as 32): the spare heads' K and V are
    # written as zeros and their queries run as zeros, whose outputs
    # ``out_proj`` drops.
    # ... or hold several KV heads a lane tile (``KVCache.pool_zeros``'
    # ``lane_heads``: heads of 64 lie two a row of 128 lanes): the leaf's
    # own shape says which.
    leaf_heads, leaf_lanes = (layer_k.q if isinstance(layer_k, QuantKV)
                              else layer_k).shape[-2:]
    pair = leaf_lanes // hd
    spare = leaf_heads * pair - KV
    if pair > 1 and (spare or cfg.selects_keys
                     or isinstance(layer_k, QuantKV)
                     or (mesh is not None and mesh.size > 1)):
        raise NotImplementedError(
            f"{cfg.name}: {pair} KV heads a lane tile of the pool serve "
            "plain attention over a bf16 pool on one device, in whole "
            "tiles of the KV heads")
    if spare:
        q, k, v = (_pad_heads(q, spare * (H // KV)), _pad_heads(k, spare),
                   _pad_heads(v, spare))

    if cfg.slides:
        if block_tables is None:
            raise NotImplementedError(
                f"{cfg.name} has sliding-attention layers (sliding_window="
                f"{cfg.sliding_window}): their bounded K/V ride the pool "
                "engine's cache, so it is served through the pool alone "
                "(KV_POOL, one device); the dense per-slot ladder would "
                "attend to every key")
        counts["span_rows"] = _span_rows(cfg, kind, positions, q_lens,
                                         token_mask)
    if cfg.has_linear:
        # the full layers' two words of ``KVCache.lin_rows``
        counts["lin_rows"] = jnp.concatenate([
            jnp.zeros((3,), jnp.int32),
            _span_rows(cfg, kind, positions, q_lens, token_mask)[2:],
            jnp.zeros((1,), jnp.int32)])
    if kind == "S":
        with jax.named_scope("kv_write"):
            layer_k, layer_v = _ring_write(layer_k, layer_v, k, v, positions,
                                           write_mask, layer)
        with jax.named_scope("attention"), jax.named_scope("sliding"):
            attn = gated(_sliding_attention(
                cfg, attn_impl, q, layer_k, layer_v, positions, q_lens,
                kv_limit, layer))
        return (after_attention(out_proj(attn)), layer_k, layer_v, layer_ik,
                counts)
    if cfg.selects_keys and (block_tables is None or layer_ik is None):
        raise NotImplementedError(
            f"{cfg.name} selects its keys (index_topk={cfg.index_topk}): "
            "its index keys live in the block pool, so it is served "
            "through the pool alone (KV_POOL, no pipe axis)")

    if block_tables is not None:
        # Block-paged pool (ISSUE 10): layer_k/v are [n_blocks, page, KV,
        # hd] pool slices; every KV write and read goes through the
        # per-slot block table. Same absolute-position semantics as the
        # dense path — only the storage addressing changes, so pool and
        # dense transcripts are bit-identical.
        is_q = isinstance(layer_k, QuantKV)
        n_blocks, page = (layer_k.q if is_q else layer_k).shape[-4:-2]
        if kv_limit % page:
            raise ValueError(
                f"pool kv_limit {kv_limit} not a multiple of page {page}")
        flat = _pool_flat_pos(block_tables, positions, page, n_blocks,
                              write_mask)
        if pair > 1:
            # a token's row as the leaf holds it: the same lanes
            k, v = (a.reshape(B, S, KV // pair, pair * hd) for a in (k, v))
        with jax.named_scope("kv_write"):
            if is_q:
                qk, qv = kv_quantize(k), kv_quantize(v)
                layer_k = QuantKV(
                    q=_pool_scatter(layer_k.q, flat, qk.q, layer),
                    s=_pool_scatter(layer_k.s, flat, qk.s, layer))
                layer_v = QuantKV(
                    q=_pool_scatter(layer_v.q, flat, qv.q, layer),
                    s=_pool_scatter(layer_v.s, flat, qv.s, layer))
            else:
                layer_k = _pool_scatter(layer_k, flat, k, layer)
                layer_v = _pool_scatter(layer_v, flat, v, layer)
            if cfg.selects_keys:
                layer_ik = _pool_scatter(layer_ik, flat, jnp.pad(
                    ki, ((0, 0), (0, 0),
                         (0, cfg.index_key_width - ki.shape[-1]))), layer)
        n_pages = kv_limit // page
        kv_pos = jnp.arange(kv_limit)[None, None, :]
        mask = kv_pos <= positions[:, :, None]
        with jax.named_scope("attention"), (
                jax.named_scope("full") if cfg.slides
                else contextlib.nullcontext()):
            if cfg.selects_keys and kv_limit > cfg.index_topk:
                if is_q:
                    raise NotImplementedError(
                        "key selection reads bf16 K/V (KV_QUANT is not "
                        "served with index_topk)")
                attn, counts["sel_rows"] = _select_and_attend(
                    cfg, attn_impl, q, qi, wi, layer_k, layer_v, layer_ik,
                    positions, q_lens, block_tables, n_pages, layer,
                    token_mask)
            elif attn_impl == "ragged" and not is_q:
                # ONE kernel for every window shape (ISSUE 19): per-slot
                # q_len is 1 for decode, k+1 for spec verify, a prompt
                # span for (suffix) prefill — a mixed chunk is a single
                # dispatch. The scatter above already wrote the window's
                # own K/V into the pool, so the kernel reads everything
                # (context + window) through the block table; causal-in-
                # window masking gives column j exactly kv <= pos + j,
                # bitwise the gather path's semantics. int8 KV keeps the
                # loud gather fallback (is_q branch below) — the engine
                # resolves that regime at startup.
                ql = (jnp.full((B,), S, jnp.int32) if q_lens is None
                      else q_lens.astype(jnp.int32))
                if mesh is not None and mesh.shape["model"] > 1:
                    from ..ops.ragged_attention import \
                        ragged_attention_pool_sharded

                    attn = ragged_attention_pool_sharded(
                        q, layer_k, layer_v, ql, positions[:, 0],
                        block_tables, mesh, layer, page_size=page)
                else:
                    from ..ops.ragged_attention import (
                        pair_queries, ragged_attention_pool, unpair_outputs)

                    # (a query in its own KV head's lanes of the pool's row)
                    attn = ragged_attention_pool(
                        pair_queries(q, KV, pair) if pair > 1 else q,
                        layer_k, layer_v, ql, positions[:, 0], block_tables,
                        layer, page_size=page, scale=cfg.softmax_scale)
                    if pair > 1:
                        attn = unpair_outputs(attn, KV, pair)
            elif is_q:
                attn = dense_attention_quant(
                    q,
                    _pool_gather(layer_k.q, block_tables, n_pages, layer),
                    _pool_gather(layer_k.s, block_tables, n_pages, layer),
                    _pool_gather(layer_v.q, block_tables, n_pages, layer),
                    _pool_gather(layer_v.s, block_tables, n_pages, layer),
                    mask, scale=cfg.softmax_scale,
                )
            else:
                k_ctx = _pool_gather(layer_k, block_tables, n_pages, layer)
                v_ctx = _pool_gather(layer_v, block_tables, n_pages, layer)
                if pair > 1:
                    k_ctx, v_ctx = (a.reshape(B, kv_limit, KV, hd)
                                    for a in (k_ctx, v_ctx))
                if attn_impl == "flash" and S > 1:
                    from ..ops.flash_attention import flash_attention_cached

                    attn = flash_attention_cached(q, k_ctx, v_ctx,
                                                  positions)
                else:
                    attn = dense_attention(q, k_ctx, v_ctx, mask,
                                           scale=cfg.softmax_scale)
            attn = gated(attn)
        return (after_attention(out_proj(attn)), layer_k, layer_v, layer_ik,
                counts)

    if gate is not None:
        raise NotImplementedError(
            f"{cfg.name}: the attention's gate is served through the pool "
            "alone")
    # Write this chunk's K/V into the cache at its absolute positions.
    # (scatter; positions are per-slot absolute indices). Dead rows
    # (write_mask False) scatter at an out-of-bounds position, which jax
    # drops — the cache row stays untouched.
    if write_mask is not None:
        _cap = (layer_k.q if isinstance(layer_k, QuantKV)
                else layer_k).shape[-3]
        w_pos = jnp.where(write_mask[:, None], positions, _cap)
    else:
        w_pos = positions
    rows = _at_layer(layer, batch_idx, w_pos)
    ctx = _at_layer(layer, slice(None), slice(None, kv_limit))
    if isinstance(layer_k, QuantKV):
        # int8 KV: quantize the fresh chunk at write; the read span stays
        # int8 all the way into the attention dots —
        # dense_attention_quant commutes the per-(position, head) scales
        # onto the scores/probs, so only int8 bytes cross HBM for the
        # context (half the decode-attention traffic, half the pool) and
        # no dequantized copy ever materializes. The fresh chunk's own
        # k/v stay bf16 for the ring path.
        with jax.named_scope("kv_write"):
            qk, qv = kv_quantize(k), kv_quantize(v)
            layer_k = QuantKV(q=layer_k.q.at[rows].set(qk.q),
                              s=layer_k.s.at[rows].set(qk.s))
            layer_v = QuantKV(q=layer_v.q.at[rows].set(qv.q),
                              s=layer_v.s.at[rows].set(qv.s))
        with jax.named_scope("attention"):
            if attn_impl == "ring" and S > 1:
                # Ring prefill attends over the chunk's own fresh bf16 k/v
                # (no prior cache context); the quantized write above still
                # lands every position for later decode.
                from ..parallel.ring_attention import ring_attention

                attn = ring_attention(q, k, v, positions, mesh)
            else:
                kv_pos = jnp.arange(kv_limit)[None, None, :]
                mask = kv_pos <= positions[:, :, None]
                attn = dense_attention_quant(
                    q,
                    layer_k.q[ctx], layer_k.s[ctx],
                    layer_v.q[ctx], layer_v.s[ctx],
                    mask, scale=cfg.softmax_scale,
                )
        return (after_attention(out_proj(attn)), layer_k, layer_v, layer_ik,
                counts)
    else:
        with jax.named_scope("kv_write"):
            layer_k = layer_k.at[rows].set(k.astype(layer_k.dtype))
            layer_v = layer_v.at[rows].set(v.astype(layer_v.dtype))
        k_ctx = layer_k[ctx]
        v_ctx = layer_v[ctx]
    # Causal mask over absolute positions (padding queries read garbage but
    # their outputs are never used).
    kv_pos = jnp.arange(kv_limit)[None, None, :]
    mask = kv_pos <= positions[:, :, None]

    if attn_impl == "ring" and S > 1:
        # Sequence-parallel self-attention over the chunk itself (no prior
        # cache context) — the from-scratch long-prefill path. K/V blocks
        # rotate over the ``seq`` mesh axis via ppermute; the cache write
        # above still lands every position for later decode.
        from ..parallel.ring_attention import ring_attention

        with jax.named_scope("attention"):
            attn = ring_attention(q, k, v, positions, mesh)
    elif attn_impl == "flash" and S > 1:
        from ..ops.flash_attention import flash_attention_cached

        with jax.named_scope("attention"):
            attn = flash_attention_cached(q, k_ctx, v_ctx, positions)
    else:
        with jax.named_scope("attention"):
            attn = dense_attention(q, k_ctx, v_ctx, mask,
                                   scale=cfg.softmax_scale)
    return after_attention(out_proj(attn)), layer_k, layer_v, layer_ik, counts


# ------------------------------------------- one mixer a layer (patterned)

#: A patterned configuration's leaves by kind (those the tree holds). An
#: attention kind's are its norm, wq, wk, wv, wo and (``attn_gate``) the
#: per-head gate's projection, in that order: ``_layer`` reads them under
#: the full kind's names.
ATTENTION_LEAVES = {
    "*": ("attn_norm", "wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm"),
    "S": ("sw_norm", "sw_wq", "sw_wk", "sw_wv", "sw_wo", "sw_wg",
          "sw_q_norm", "sw_k_norm")}
#: ... and a latent-attention layer's in a pattern (``_latent_leaves``).
LATENT_LEAVES = ("attn_norm", "wq", "w_dq", "dq_norm", "w_uq", "w_dkv",
                 "dkv_norm", "w_ukv", "wo", "wg")
EXPERT_LAYER_LEAVES = ("router", "router_bias", "w_gate", "w_up", "w_down",
                       "shared_gate", "shared_up", "shared_down",
                       "shared_expert_gate")


def _at(leaf, j: int):
    """Layer ``j`` of a kind's stacked leaf (plain or quantized)."""
    return jax.tree_util.tree_map(lambda a: a[j], leaf)


def _expert_mixer(cfg: ModelConfig, layers: Params, j: int, h, mesh,
                  token_mask, moe_impl: str):
    """``h + experts(norm(h))`` for expert layer ``j``: the routed experts
    (``_moe_mlp``'s paths; the grouped one reads layer ``j`` out of the
    whole stacks) plus the shared expert every token takes. Returns
    (h, what ``_moe_mlp`` counted)."""
    with jax.named_scope("mlp_norm"):
        x = rms_norm(h, layers["mlp_norm"][j], cfg.rms_eps, cfg.rms_offset)
    grouped = serves_grouped(cfg, mesh, moe_impl)
    lp = {k: (layers[k] if grouped and k in EXPERT_LEAVES
              else _at(layers[k], j))
          for k in EXPERT_LAYER_LEAVES if k in layers}
    with jax.named_scope("mlp"):
        y, got = _moe_mlp(cfg, lp, x, mesh, token_mask, moe_impl,
                          jnp.asarray(j, jnp.int32) if grouped else None)
        if cfg.shared_mlp_hidden:
            y = y + _shared_expert(cfg, lp, x)
    return h + _scaled(cfg, y), got


def _ssm_mixer(cfg: ModelConfig, layers: Params, j: int, h, ssm, conv,
               valid, win: Optional[WindowRows] = None):
    """``h + mamba2(norm(h))`` for state-space layer ``j``, from and to
    plane ``j`` of the state leaves (a traced scalar inside the scan over
    periods); returns (h, ssm, conv, int32 [4]: ``KVCache.ssm_rows`` and
    the three words of ``KVCache.ssm_window``). ``valid`` [B, S] bool marks
    a row's real tokens (a prefix of its columns): the rest neither move
    the state nor enter the convolution's tail; a decode pass (S == 1)
    takes the step kernel and a window the window kernel, both on the whole
    ``ssm`` leaf in place, and both pass over the state of a row that
    brought no token altogether (no plane is sliced out, none set back).
    With ``win`` ``h`` is the
    window's packed rows: the two projections and the gate run on them,
    the convolution and the scan, which need a slot's tokens in a row,
    on the unpacked [B, S]. Scopes ``ssm/*`` on purpose
    hold no keyword of the benchmark's trace categories: the mixer is
    its own device time, not the attention's or the MLP's."""
    from ..ops.ssd_scan import (causal_conv, gated_group_norm,
                                ssd_step_kernel, ssd_window, window_counts)

    B, S = valid.shape
    H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                  cfg.ssm_groups)
    di = H * P
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
    with jax.named_scope("ssm"):
        with jax.named_scope("norm"):
            x = rms_norm(h, layers["ssm_in_norm"][j], cfg.rms_eps,
                         cfg.rms_offset)
        with jax.named_scope("in_proj"):
            zxd = qmatmul(x, _at(layers["ssm_in"], j))
            z, xbc, dt = (zxd[..., :di], zxd[..., di:di + cfg.ssm_conv_dim],
                          zxd[..., di + cfg.ssm_conv_dim:])
            if win is not None:
                xbc, dt = win.unpack(xbc), win.unpack(dt)
        with jax.named_scope("conv"):
            xbc, tail = causal_conv(xbc, conv[j], layers["ssm_conv_w"][j],
                                    layers["ssm_conv_b"][j], n_valid)
        with jax.named_scope("scan"):
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + layers["ssm_dt_bias"][j])
            dt = jnp.where(valid[..., None], dt, 0.0)
            args = (xbc[..., :di].reshape(B, S, H, P), dt,
                    -jnp.exp(layers["ssm_A_log"][j].astype(jnp.float32)),
                    xbc[..., di:di + G * N].reshape(B, S, G, N),
                    xbc[..., di + G * N:].reshape(B, S, G, N),
                    layers["ssm_D"][j])
            if S == 1:
                # a decode step: the kernel takes the whole leaf, in place
                with jax.named_scope("step"):
                    y, ssm = ssd_step_kernel(*args, ssm, j, valid[:, 0])
            else:
                with jax.named_scope("window"):
                    y, ssm = ssd_window(*args, ssm, j, cfg.ssm_chunk, n_valid)
            conv = conv.at[j].set(tail)
        with jax.named_scope("gate_norm"):
            y = y.reshape(B, S, di)
            if win is not None:
                y = win.pack(y)
            y = gated_group_norm(y, z,
                                 layers["ssm_gate_norm"][j], G, cfg.rms_eps)
        with jax.named_scope("out_proj"):
            out = qmatmul(y, _at(layers["ssm_out"], j))
    # the rows of a decode pass that the step kernel passed over, then what
    # the window kernel updated and passed over
    none = jnp.zeros((3,), jnp.int32)
    counted = (jnp.concatenate([jnp.sum(n_valid == 0, dtype=jnp.int32)[None],
                                none]) if S == 1 else
               jnp.concatenate([none[:1],
                                window_counts(n_valid, S, cfg.ssm_chunk)]))
    return h + _scaled(cfg, out), ssm, conv, counted


def _linear_mixer(cfg: ModelConfig, lp: Params, j, h, lin, lconv,
                  valid, q_lens, win: Optional[WindowRows] = None):
    """A linear-attention layer (the gated delta rule, ops/gated_delta.py)
    of leaves ``lp`` onto the residual, from and to plane ``j`` of the
    state leaves (a traced scalar inside the scan over periods); returns
    (h, lin, lconv, what the pass counted: the first three words of
    ``KVCache.lin_rows`` and its last, int32 [6], and where the decay is a
    scalar a head ``KVCache.lin_window``'s four). ``valid`` as for
    ``_ssm_mixer``: a padded token has ``g = 0`` and ``beta = 0`` and stays
    out of the convolution's tail; a decode pass (S == 1) takes the step
    kernel and a window of scalar decays the window kernel
    (ops/gated_delta_window.py), both on the whole leaf in place, and both
    pass over the state of a row that brought no token altogether; a window
    of per-channel decays slices its plane out and sets it back.
    With ``win`` the projections, the gated norm and the output projection
    run on the window's packed rows, the convolution and the scan on the
    unpacked [B, S]. Scopes ``lin/*`` hold, like ``ssm/*``, no keyword of
    the benchmark's trace categories: the mixer is its own device time."""
    from ..ops import gated_delta
    from ..ops.gated_delta_window import gated_delta_window, window_counts
    from ..ops.ssd_scan import causal_conv

    B, S = valid.shape
    H, dk, dv = cfg.lin_value_heads, cfg.lin_key_dim, cfg.lin_value_dim
    # q and k have the KEY heads' count: value head h reads key head
    # h // (H // Hk); ops/gated_delta.py repeats them, never the state
    Hk, C = cfg.lin_key_heads, cfg.lin_conv_dim
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
    with jax.named_scope("lin"):
        x = h
        if not cfg.post_norm:
            with jax.named_scope("norm"):
                x = rms_norm(h, lp["lin_norm"], cfg.rms_eps, cfg.rms_offset)
        with jax.named_scope("in_proj"):
            qkvz = qmatmul(x, lp["lin_in"])
            qkv, z = qkvz[..., :C], qkvz[..., C:]
            if cfg.lin_channel_decay:       # W_f: a decay a key channel
                # (the head split outside the dot: folded into it, the
                # compiler turned W_f over with a copy every layer of every
                # pass, 10.5 MB each: tools/aot_weight_staging.py, PR 48)
                a = qmatmul_heads(x, lp["lin_wf"], H, dk).astype(jnp.float32)
            else:
                a = (x @ lp["lin_wa"]).astype(jnp.float32)
            b = (x @ lp["lin_wb"]).astype(jnp.float32)
            if win is not None:
                qkv, a, b = win.unpack(qkv), win.unpack(a), win.unpack(b)
        with jax.named_scope("conv"):
            plane = lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, False)
            qkv, tail = causal_conv(qkv, plane(lconv), lp["lin_conv_w"],
                                    None, n_valid)
        with jax.named_scope("scan"):
            q = gated_delta.l2_normalize(
                qkv[..., :Hk * dk].reshape(B, S, Hk, dk), dk ** -0.5)
            k = gated_delta.l2_normalize(
                qkv[..., Hk * dk:2 * Hk * dk].reshape(B, S, Hk, dk))
            if cfg.lin_channel_decay:
                # a vector a head, in (floor, 0) for every key channel
                g = cfg.lin_decay_floor * jax.nn.sigmoid(
                    jnp.exp(lp["lin_A_log"].astype(jnp.float32))[:, None]
                    * (a + lp["lin_f_bias"]))
            else:
                g = -jnp.exp(lp["lin_A_log"].astype(jnp.float32)) \
                    * jax.nn.softplus(a + lp["lin_dt_bias"])
            beta = (2.0 if cfg.lin_neg_eigval else 1.0) * jax.nn.sigmoid(b)
            g = jnp.where(valid.reshape(valid.shape + (1,) * (g.ndim - 2)),
                          g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
            v = qkv[..., 2 * Hk * dk:].reshape(B, S, H, dv)
            if S == 1:
                # a decode step: the kernel takes the whole leaf, in place
                with jax.named_scope("step"):
                    o, lin = gated_delta.gated_delta_step_kernel(
                        q, k, v, g, beta, lin, j, valid[:, 0])
            elif cfg.lin_channel_decay:
                o, state = gated_delta.channel_decay_scan(q, k, v, g, beta,
                                                          plane(lin))
                lin = jax.lax.dynamic_update_index_in_dim(lin, state, j, 0)
            else:
                with jax.named_scope("window"):
                    o, lin = gated_delta_window(q, k, v, g, beta, lin, j,
                                                n_valid)
            lconv = jax.lax.dynamic_update_index_in_dim(lconv, tail, j, 0)
        with jax.named_scope("gate_norm"):
            if win is not None:
                o = win.pack(o)
            y = gated_delta.gated_head_norm(
                o, z.reshape(z.shape[:-1] + (H, dv)),
                lp["lin_gate_norm"], cfg.rms_eps,
                **({"gate": jax.nn.sigmoid} if cfg.lin_out_gate == "sigmoid"
                   else {})).astype(h.dtype)
        with jax.named_scope("out_proj"):
            out = qmatmul(y.reshape(y.shape[:-2] + (H * dv,)), lp["lin_out"])
        if cfg.post_norm:
            with jax.named_scope("norm"):
                out = rms_norm(out, lp["lin_norm"], cfg.rms_eps,
                               cfg.rms_offset)
    decode = jnp.logical_and(_query_lens(valid, q_lens) == 1, valid[:, 0])
    chunks = 0 if S == 1 else B * -(-S // min(gated_delta.CHUNK, S))
    rows = jnp.stack([
        jnp.sum(decode, dtype=jnp.int32),
        jnp.sum(jnp.where(decode, 0, n_valid), dtype=jnp.int32),
        jnp.asarray(chunks, jnp.int32)])
    # the rows of a decode pass that the step kernel passed over
    still = jnp.sum(jnp.logical_not(valid[:, 0]) if S == 1 else 0,
                    dtype=jnp.int32)
    counted = {"lin_rows": jnp.concatenate(
        [rows, jnp.zeros((2,), jnp.int32), still[None]])}
    if not cfg.lin_channel_decay:
        # what the window kernel updated and passed over
        counted["lin_window"] = (jnp.zeros((4,), jnp.int32) if S == 1
                                 else window_counts(n_valid, S))
    return h + _scaled(cfg, out), lin, lconv, counted


def _patterned_layers(cfg: ModelConfig, attn_impl: str, mesh, moe_impl: str,
                      layers: Params, h, cache: KVCache, positions,
                      kv_limit: int, batch_idx, token_mask, write_mask,
                      block_tables, q_lens, win=None):
    """The layer loop of a patterned configuration: layer l runs as the
    kind ``cfg.layer_kinds[l]`` names, on layer j of that kind's stacks (j
    its ordinal among its kind) — unrolled, for three kinds cannot share
    a scan body, but where every kind can take a traced ordinal
    (``_scan_period``): then one period's mixers are the body of a scan
    over its repeats, be there one. ``win``: ``h`` is the window's packed
    rows, which an expert layer and a dense MLP take as they are and the
    sequence mixers unpack around their scan or attention. Returns (h, the cache with its
    K/V and state leaves as the layers left them, what the passes counted
    by ``KVCache`` field)."""
    B, S = positions.shape
    valid = jnp.ones((B, S), bool)
    if token_mask is not None:
        valid = jnp.logical_and(valid, token_mask > 0)
    if write_mask is not None:
        valid = jnp.logical_and(
            valid, write_mask if write_mask.ndim == 2 else write_mask[:, None])
    if q_lens is not None:
        valid = jnp.logical_and(valid, jnp.arange(S)[None, :] < q_lens[:, None])
    k, v, ssm, conv = cache.k, cache.v, cache.ssm, cache.conv
    sk, sv, lin, lconv = cache.sk, cache.sv, cache.lin, cache.lconv
    lat = cache.lat
    if cfg.latent and lat is None and block_tables is not None:
        # no leaf given (benchmark/refcheck.py hands K and V pools, which
        # ride untouched): a zero one on the K pool's block geometry, a
        # plane a LATENT layer
        nb, page = k.shape[1:3]
        lat = jnp.zeros((cfg.n_of("*"), nb, page // 2, 2 * cfg.latent_row),
                        k.dtype)
    if cfg.has_ssm and ssm is None:
        ssm, conv = state_zeros(cfg, B, h.dtype)
    if cfg.has_linear and lin is None:
        lin, lconv = linear_zeros(cfg, B, h.dtype)
    if cfg.slides and sk is None and block_tables is not None:
        # no leaf given (benchmark/refcheck.py): one that holds this call's
        # own window beside the span, in the K pool's pages
        sk, sv = sliding_zeros(cfg, B, cfg.sliding_ring(S, k.shape[-3]),
                               h.dtype)
    step = partial(_layer, cfg, attn_impl, mesh, moe_impl)

    def run(kinds, h, st, counts, ordinal):
        """The mixers ``kinds`` in order. ``ordinal(kind, i)``: which layer
        of its kind the i-th ``kind`` of ``kinds`` is, in the kind's stacked
        leaves and in its planes of the cache alike (a traced scalar inside
        the scan over periods). ``st``: (k, v, ssm, conv, sk, sv, lin,
        lconv, lat); ``counts`` is added to."""
        k, v, ssm, conv, sk, sv, lin, lconv, lat = st
        leaf = lambda name, j: _at(layers[name], j)

        def count(new):
            for name, n in new.items():
                counts[name] = n if name not in counts else counts[name] + n

        seen = dict.fromkeys(kinds, 0)
        for kind in kinds:
            i = seen[kind]
            seen[kind] += 1
            j = ordinal(kind, i)
            if kind == "M":
                h, ssm, conv, n = _ssm_mixer(cfg, layers, j, h, ssm, conv,
                                             valid, win)
                count({"ssm_rows": n[:1], "ssm_window": n[1:]})
            elif kind == "E":
                h, n = _expert_mixer(
                    cfg, layers, j, h, mesh,
                    token_mask if win is None else win.valid, moe_impl)
                count(n)
            elif kind == "L":
                h, lin, lconv, n = _linear_mixer(
                    cfg, {name: leaf(name, j) for name in layers
                          if name.startswith("lin_")},
                    j, h, lin, lconv, valid, q_lens, win)
                count(n)
            elif kind == "D":
                lp = {name: leaf(name, j) for name in layers
                      if name.startswith("dense_")}
                norm = lambda a, w=lp["dense_norm"]: rms_norm(
                    a, w, cfg.rms_eps, cfg.rms_offset)
                # the norm on the MLP's input, or (``post_norm``) its output
                with jax.named_scope("mlp_norm"):
                    x = h if cfg.post_norm else norm(h)
                with jax.named_scope("mlp"):
                    y = _dense_mlp(cfg, lp, x, "dense_")
                    if not cfg.post_norm:
                        h = h + _scaled(cfg, y)
                if cfg.post_norm:
                    with jax.named_scope("mlp_norm"):
                        h = h + _scaled(cfg, norm(y))
            else:
                latent = cfg.latent and kind == "*"
                lp = ({name: leaf(name, j) for name in LATENT_LEAVES
                       if name in layers} if latent else
                      {name: leaf(own, j) for name, own
                       in zip(ATTENTION_LEAVES["*"], ATTENTION_LEAVES[kind])
                       if own in layers})
                args = (positions, kv_limit, batch_idx, token_mask,
                        write_mask, block_tables, q_lens,
                        jnp.asarray(j, jnp.int32))
                if kind == "S":
                    h, sk, sv, _, n = step(h, lp, sk, sv, *args, None, win,
                                           kind)
                elif latent:
                    # plane j of the latent leaf; K and V ride untouched
                    h, _, _, lat, n = step(h, lp, None, None, *args, lat,
                                           win, kind)
                else:
                    h, k, v, _, n = step(h, lp, k, v, *args, None, win, kind)
                count(n)
        return h, (k, v, ssm, conv, sk, sv, lin, lconv, lat)

    kinds = cfg.layer_kinds
    st = (k, v, ssm, conv, sk, sv, lin, lconv, lat)
    counts: Dict[str, Any] = {}
    period = _scan_period(kinds)
    if not period:
        h, st = run(kinds, h, st, counts, lambda kind, i: i)
    else:
        # A pattern of these kinds (LDLDLD*D eight times; once, where the
        # comparison with the reference cuts the depth) is ONE period's
        # mixers scanned over its repeats, the stacks and the caches whole
        # (closed over, and in the carry), a layer addressed in both by its
        # traced ordinal: each dot reads its own slice of its stack (cut
        # [repeats, layers a period, ...] as the scan's xs, a period's three
        # or four layers of a kind were copied out of the stack together,
        # every weight every pass: AOT, PR 45). A program of 8 mixers where
        # the unrolled loop's had 64, whose chunk programs took longer to
        # compile than a cell may take to start.
        reps = len(kinds) // period
        per = {kind: kinds[:period].count(kind) for kind in set(kinds)}

        def body(carry, r):
            h, st = carry
            got: Dict[str, Any] = {}
            h, st = run(kinds[:period], h, st, got,
                        lambda kind, i: r * per[kind] + i)
            return (h, st), got

        (h, st), per_rep = jax.lax.scan(
            body, (h, st), jnp.arange(reps, dtype=jnp.int32))
        counts = {name: jnp.sum(n, axis=0) for name, n in per_rep.items()}
    k, v, ssm, conv, sk, sv, lin, lconv, lat = st
    return h, dataclasses.replace(cache, k=k, v=v, ssm=ssm, conv=conv,
                                  sk=sk, sv=sv, lin=lin, lconv=lconv,
                                  lat=lat), counts


def _scan_period(kinds: Tuple[str, ...]) -> int:
    """The length of the shortest pattern that ``kinds`` is repeats of (its
    own, where nothing shorter repeats: a scan of one step, so that a
    configuration cut to one period runs the program its full depth does),
    where every mixer of it can address its layer by a traced index (linear
    attention, a Mamba-2 layer, full attention, a dense MLP; an expert layer
    and a sliding-attention layer cannot: a pattern that holds one keeps
    the unrolled programs it was measured with); 0 = run unrolled."""
    if set(kinds) - set("LM*D"):
        return 0
    n = len(kinds)
    return next(p for p in range(1, n + 1)
                if n % p == 0 and kinds == kinds[:p] * (n // p))


# -------------------------------------------------------------- forward

def forward(
    params: Params,
    cfg: ModelConfig,
    tokens: jnp.ndarray,          # [B, S] int32
    positions: jnp.ndarray,       # [B, S] int32 absolute positions
    cache: KVCache,
    *,
    kv_limit: Optional[int] = None,   # static: attend over cache[:, :kv_limit]
    attn_impl: str = "dense",
    mesh=None,                        # static: enables EP MoE dispatch when
                                      # an "expert" axis >1 is present
    token_mask: Optional[jnp.ndarray] = None,  # [B, S]; 0 marks padding /
                                      # dead-slot tokens (MoE capacity)
    page_size: Optional[int] = None,  # unused (the pool page is the cache
                                      # leaf's own shape); kept for callers
                                      # that name it (benchmark/refcheck.py)
    moe_impl: str = "auto",           # static: MoE dispatch policy
                                      # (auto | ep | dense; see _moe_mlp)
    logits_at: Optional[jnp.ndarray] = None,   # [B] int32: emit logits only
                                      # at this position per row
    write_mask: Optional[jnp.ndarray] = None,  # [B] bool: rows allowed to
                                      # write KV (device-side termination —
                                      # see _layer; ignored on the pipe
                                      # path, whose dead slots keep the
                                      # legacy frozen-position writes)
    block_tables: Optional[jnp.ndarray] = None,  # [B, max_pages] int32:
                                      # block-paged pool mode (ISSUE 10) —
                                      # cache leaves are [L, n_blocks,
                                      # page, ...] and every KV access
                                      # routes through the table; entries
                                      # >= n_blocks are the unmapped-page
                                      # sentinel (writes drop, reads are
                                      # causally masked)
    q_lens: Optional[jnp.ndarray] = None,  # [B] int32, attn_impl="ragged"
                                      # only: valid query columns per slot
                                      # (1=decode, k+1=spec verify,
                                      # span=prefill; 0 freezes). None =
                                      # all S columns valid. ISSUE 19.
    packed_rows: Optional[int] = None,  # static, with q_lens and
                                      # logits_at: the residual carries
                                      # the window's valid rows packed
                                      # into [1, packed_rows, D] (they
                                      # must fit), not [B, S, D]. ISSUE 39.
) -> Tuple[jnp.ndarray, KVCache]:
    """Run the model over a token chunk (prefill: S>1; decode: S=1).

    Returns (logits [B, S, vocab], updated cache). ``cache.lengths`` is
    advanced by the number of *valid* tokens, which the caller tracks —
    here we set it to max(positions)+1 per slot (padding positions are
    clamped by the caller).

    ``logits_at`` gathers each row's hidden state at one position BEFORE
    the LM-head projection, returning [B, 1, vocab]. Prefill only ever
    consumes the last valid position's logits, and the head is ~20% of a
    2B prefill's FLOPs (bucket × dim × 256k-vocab) and its largest
    activation (bucket × vocab f32) — this turns both into 1/bucket of
    themselves.

    ``packed_rows`` is the mixed admission window's entry (ISSUE 39): the
    arguments describe the [B, S] window as ever, and everything that
    works a row at a time (embedding, norms, projections, rotary, the MLP
    and the experts, the final norm) runs on its valid rows alone
    (``window_rows``); the cache writes and the sequence mixers see the
    window (``_layer``). ``logits_at`` then picks each slot's row.
    """
    if kv_limit is None:
        kv_limit = cache.max_seq
    B, S = tokens.shape
    batch_idx = jnp.arange(B)[:, None]
    if (block_tables is not None and cache.k is not None
            and not isinstance(cache.k, QuantKV)
            and cache.k.shape[-1] == cfg.head_dim
            and (short := cfg.kv_heads_paged - cache.k.shape[-2]) > 0):
        # a caller's own pool with ``n_kv_heads`` heads a row (benchmark/
        # refcheck.py builds one): made the engine's leaf once, here, and
        # returned so, so that every layer below writes and reads the rows
        # the server's do, their spare heads zeros (``KVCache.pool_zeros``)
        cache = dataclasses.replace(cache, k=_pad_heads(cache.k, short),
                                    v=_pad_heads(cache.v, short))
    if (block_tables is not None and attn_impl == "ragged"
            and cache.k is not None and not isinstance(cache.k, QuantKV)
            and cache.k.shape[-1] == cfg.head_dim
            and jax.default_backend() == "tpu"):
        # ... and one with a head a row of lanes where the compiled kernel
        # wants whole lane tiles (heads of 64): the engine's leaf likewise,
        # several KV heads a tile (``KVCache.pool_zeros``' ``lane_heads``)
        from ..ops.ragged_attention import lane_heads

        if (n := lane_heads(cfg.head_dim, cfg.kv_heads_paged)) > 1:
            tiled = lambda a: a.reshape(a.shape[:-2] + (a.shape[-2] // n,
                                                        n * a.shape[-1]))
            cache = dataclasses.replace(cache, k=tiled(cache.k),
                                        v=tiled(cache.v))
    if cfg.attention_multiplier and (
            cfg.selects_keys or cfg.slides or cfg.latent
            or attn_impl in ("flash", "ring")
            or (mesh is not None and mesh.size > 1)):
        raise NotImplementedError(
            f"{cfg.name} names its own softmax scale (attention_multiplier="
            f"{cfg.attention_multiplier}): plain attention on one device "
            "takes it, dense, gathered or through the ragged kernel")
    new_ik, state = cache.ik, cache
    counted = {name: getattr(cache, name) for name in KVCache.COUNTS}
    if cfg.latent and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            f"{cfg.name} keeps a latent cache: served on one device "
            "(parallel/sharding.py has no rule for its leaves)")
    if cfg.latent and not cfg.q_lora_rank and not cfg.layer_kinds:
        raise NotImplementedError(
            f"{cfg.name}: latent attention without a query LoRA "
            "(q_lora_rank 0) is written as a pattern's * layers")
    if cfg.post_norm and not cfg.layer_kinds:
        raise NotImplementedError(
            f"{cfg.name}: a block that norms its sublayers' outputs "
            "(post_norm) is written as its mixers (layer_pattern)")
    if cfg.layer_kinds and mesh is not None and mesh.size > 1:
        raise NotImplementedError(
            f"{cfg.name} runs one mixer a layer (layer_pattern) and is "
            "served on one device: parallel/sharding.py has no rule for "
            "its leaves")

    # final_norm is always a plain array in the model dtype — it anchors
    # the activation dtype when the embedding is stored int8.
    win = None
    if packed_rows is not None:
        if q_lens is None or logits_at is None or block_tables is None:
            raise ValueError("packed_rows is the pool's mixed window: it "
                             "needs block_tables, q_lens and logits_at")
        win = window_rows(q_lens, positions, packed_rows)
        tokens = win.pack(tokens)
    with jax.named_scope("embed"):
        h = embed_lookup(params["embed"], tokens,
                         dtype=params["final_norm"].dtype)
        if cfg.embed_scale:
            h = h * jnp.asarray(cfg.dim ** 0.5, h.dtype)
        if cfg.embed_multiplier != 1.0:
            h = h * jnp.asarray(cfg.embed_multiplier, h.dtype)

    if (block_tables is not None and mesh is not None
            and "pipe" in mesh.axis_names and mesh.shape["pipe"] > 1):
        # The pipelined stage body (parallel/pipeline.py) has no block-
        # table plumbing — the engine resolves KV_POOL under a pipe mesh
        # to the dense ladder before ever tracing this. TP/EP meshes
        # compose (ISSUE 14): the pool cache shards on the KV-head axis
        # (parallel/sharding.py::pool_cache_specs) and every access
        # routes through the same table indirection as single-chip.
        raise NotImplementedError(
            "block-paged KV does not compose with a pipe mesh axis "
            "(no table plumbing in the stage body); use the dense "
            "KV ladder")
    # f≈1 residual sharding starts at the embedding output — the scan
    # carry then stays in the sharded layout across every layer.
    h = _shard_residual(mesh, h)
    if mesh is not None and "pipe" in mesh.axis_names and mesh.shape["pipe"] > 1:
        # Pipeline-parallel serving: the layer stack (params and KV cache
        # sharded over ``pipe`` on the layer axis, parallel/sharding.py)
        # runs as a GPipe shard_map instead of the lax.scan — stages relay
        # hidden states over ICI via ppermute, TP stays automatic inside
        # each stage (parallel/pipeline.py). The Pallas flash/ragged kernels
        # and ring attention don't compose with the stage body, so the
        # pipelined path always runs dense attention; MoE layers likewise
        # evaluate densely (no EP all-to-all inside a stage — the engine
        # warns at mesh setup when pp>1 meets an expert axis). int8 KV
        # (QuantKV) flows through: the stage body's cache ops are
        # tree-mapped and _layer's dense path dequantizes in-place
        # (VERDICT r4 item 2 — the 70B pp x tp config needs int8 KV most).
        from ..parallel.pipeline import pipeline_layers

        h, new_k, new_v = pipeline_layers(
            params["layers"], cfg, h, positions, cache.k, cache.v, mesh,
            kv_limit=kv_limit, attn_impl="dense",
        )
    elif cfg.layer_kinds:
        h, state, counts = _patterned_layers(
            cfg, attn_impl, mesh, moe_impl, params["layers"], h, cache,
            positions, kv_limit, batch_idx, token_mask, write_mask,
            block_tables, q_lens, win)
        new_k, new_v = state.k, state.v
        if cfg.latent:
            new_ik = state.lat          # (the leaf's place: see below)
        for name, n in counts.items():
            if counted[name] is not None:
                counted[name] = counted[name] + n
    else:
        step = partial(_layer, cfg, attn_impl, mesh, moe_impl)

        # The cache rides the CARRY, whole (ISSUE 25): as the scan's xs
        # and ys it was sliced out a layer at a time and written back
        # into a second stacked buffer — three moves of the whole KV pool
        # every forward pass, to write a few rows. Carried, it stays in
        # one (donated) buffer from argument to result; ``_layer``
        # addresses its layer by the scanned index. One scan shape for
        # every path: dense, pool, ragged, int8 KV.
        # A selecting configuration's index keys ride it too; a caller
        # that built the pool without the leaf (benchmark/refcheck.py)
        # gets a zero one of the K pool's block geometry, returned below.
        ik = cache.ik
        if cfg.selects_keys and ik is None and block_tables is not None:
            ik = jnp.zeros(cache.k.shape[:3] + (cfg.index_key_width,),
                           cache.k.dtype)
        # A latent configuration's ONE leaf rides in that place (it has no
        # index keys); K and V, where a caller hands them, ride untouched.
        if cfg.latent:
            ik = cache.lat
            if ik is None and block_tables is not None:
                L_, nb, page = cache.k.shape[:3]
                ik = jnp.zeros((L_, nb, page // 2, 2 * cfg.latent_row),
                               cache.k.dtype)

        # The grouped expert kernel reads its experts out of the stacked
        # leaves by the layer index, so those stay out of the scan's xs.
        layers = params["layers"]
        stacks = {}
        if serves_grouped(cfg, mesh, moe_impl):
            stacks = {k: layers[k] for k in EXPERT_LEAVES if k in layers}
            layers = {k: v for k, v in layers.items() if k not in stacks}

        def scan_body(carry, xs):
            h, cache_k, cache_v, cache_ik = carry
            lp, layer = xs
            lp = {**lp, **stacks}
            h, cache_k, cache_v, cache_ik, counts = step(
                h, lp, cache_k, cache_v, positions, kv_limit, batch_idx,
                token_mask, write_mask, block_tables, q_lens, layer,
                cache_ik, win)
            return (h, cache_k, cache_v, cache_ik), counts

        (h, new_k, new_v, new_ik), counts = jax.lax.scan(
            scan_body, (h, cache.k, cache.v, ik),
            (layers, jnp.arange(cfg.n_layers, dtype=jnp.int32)))
        for name, per_layer in counts.items():
            if counted[name] is not None:
                counted[name] = counted[name] + jnp.sum(per_layer, axis=0)

    with jax.named_scope("final_norm"):
        h = rms_norm(h, params["final_norm"], cfg.rms_eps, cfg.rms_offset)
    if win is not None:
        h = h[0][win.row_of[jnp.arange(B), logits_at]][:, None]
    elif logits_at is not None:
        h = h[jnp.arange(B), logits_at][:, None]       # [B, 1, D]
    with jax.named_scope("lm_head"):
        if cfg.tie_embeddings:
            logits = tied_head(h, params["embed"])
        else:
            logits = qmatmul(h, params["lm_head"])
        # Keep the head's output in its vocab-sharded layout through the
        # sampling chain (f≈1: the [B, 256k] f32 scratch never
        # replicates; no-op off-mesh or when vocab doesn't divide).
        logits = _shard_logits(mesh, logits)

    if block_tables is not None:
        # Pool mode: lengths are per-SLOT host truth (the scheduler's
        # block tables track them); the pool cache's lengths leaf is
        # [n_blocks]-shaped and structural only.
        new_lengths = cache.lengths
    else:
        new_lengths = jnp.maximum(cache.lengths, positions.max(axis=1) + 1)
    new_lat = None
    if cfg.latent:
        new_lat, new_ik = new_ik, None
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        # in float32, before a grammar's mask and the sampler see them
        logits = logits / cfg.logits_scaling
    return logits, KVCache(
        k=new_k, v=new_v, lengths=new_lengths, ik=new_ik, lat=new_lat,
        **_state_leaves(state), **counted)
