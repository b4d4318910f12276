"""Weight-only int8 quantization (SURVEY.md §2.2 optional row, for the
70B-class configs).

Decode throughput on TPU is weight-read-bound (a bs=32 step ran at ~78%
of the HBM weight-read floor — earlier chip run, not re-measured), so halving weight bytes is a
near-1.9× decode lever for large dense models. TPU-native design:

- **Per-output-channel symmetric int8** for every projection matmul
  (attention qkv/o, MLP gate/up/down; MoE expert weights included via the
  same leaf type). Scales are f32, folded into the matmul epilogue —
  ``(x @ w_q) * scale`` — which XLA fuses. ``qmatmul`` upcasts the int8
  weight to the activation dtype before ``dot_general`` (the MXU computes
  in bf16), so the bandwidth win depends on XLA fusing that convert into
  the weight read — only int8 bytes may cross HBM, never a materialized
  bf16 copy. Verified on TPU via the compiled-HLO check in
  tests/test_tpu_kernels.py (the convert lands inside the dot's fusion)
  and consistent with the end-to-end uplift of an earlier chip run, not re-measured.
- **Embeddings and norms stay in the model dtype**: the embedding gather
  is row-wise (per-token), not a matmul, and norm weights are tiny.
- ``QuantInt8`` is a registered pytree node, so the quantized param tree
  flows through jit/donation/sharding unchanged; shard_params places the
  int8 payload with the same PartitionSpec policy as the original weight
  (scales follow the output-channel axis).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantInt8:
    """Per-output-channel symmetric int8 weight.

    q:     int8, same shape as the original weight
    scale: f32, shape = broadcastable per-output-channel scales
           (original shape with all but the last axis collapsed to 1)
    """

    q: jnp.ndarray
    scale: jnp.ndarray

    @property
    def shape(self):
        return self.q.shape

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantInt8W8A8:
    """Same payload/scales as QuantInt8, but ``qmatmul`` additionally
    quantizes the ACTIVATIONS per token and runs the dot s8×s8→s32 on the
    MXU (W8A8): the int8 weight feeds the MXU directly instead of being
    converted to bf16 first. Round-4 attribution measured the int8→bf16
    convert pacing the weight stream at roughly half the bf16 byte rate —
    this leaf type is the lever that removes the convert. Accuracy: adds
    per-token symmetric activation error (~0.5%) on top of the weight
    quantization; the type lives in the param tree, so the mode is static
    per compiled program."""

    q: jnp.ndarray
    scale: jnp.ndarray

    @property
    def shape(self):
        return self.q.shape


def quantize_int8(w: jnp.ndarray) -> QuantInt8:
    """Symmetric int8, one scale per (batch..., output channel): only the
    contraction axis (-2) is reduced, so stacked-layer weights [L, in, out]
    get per-(layer, out) scales and lax.scan slices them per layer."""
    wf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return QuantInt8(q=q, scale=scale.astype(jnp.float32))


def dequantize(w: QuantInt8, dtype=jnp.bfloat16) -> jnp.ndarray:
    return (w.q.astype(jnp.float32) * w.scale).astype(dtype)


def quantize_embed_int8(embed: jnp.ndarray, chunk: int = 65536) -> QuantInt8:
    """Per-ROW symmetric int8 for the embedding matrix [vocab, dim]: one
    f32 scale per vocab row serves both consumers —

    - the token gather dequantizes one row (``q[tok] * scale[tok]``), and
    - the tied LM head computes ``(h @ q.T) * scale.T`` with the scale in
      the epilogue, per output column.

    For tied-embedding models (Gemma) the head re-reads the whole matrix
    every decode step (1.57 GB bf16 on 7B — measured ~2.9 ms of the
    32.5 ms step), so this halves the largest non-layer weight read AND
    frees half the embedding's HBM. Quantized in vocab-row chunks to bound
    the f32 transient (a one-shot astype of a 7B embedding is ~3.1 GB).
    """
    qs, ss = [], []
    for i in range(0, embed.shape[0], chunk):
        blk = embed[i:i + chunk].astype(jnp.float32)
        absmax = jnp.max(jnp.abs(blk), axis=-1, keepdims=True)
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        qs.append(jnp.clip(jnp.round(blk / scale), -127, 127)
                  .astype(jnp.int8))
        ss.append(scale.astype(jnp.float32))
    return QuantInt8(q=jnp.concatenate(qs), scale=jnp.concatenate(ss))


def embed_lookup(emb, tokens, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Token-row gather for a plain or QuantInt8 (per-row) embedding."""
    if isinstance(emb, QuantInt8):
        return (emb.q[tokens].astype(jnp.float32)
                * emb.scale[tokens]).astype(dtype)
    return emb[tokens]


def tied_head(h: jnp.ndarray, emb) -> jnp.ndarray:
    """LM-head projection through a (possibly per-row-quantized) tied
    embedding: logits[..., v] = h · emb[v]."""
    if isinstance(emb, QuantInt8):
        y = jax.lax.dot_general(
            h, emb.q.astype(h.dtype),
            (((h.ndim - 1,), (1,)), ((), ())),
        )
        return (y.astype(jnp.float32) * emb.scale[:, 0]).astype(h.dtype)
    return h @ emb.astype(h.dtype).T


def qmatmul(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for plain, QuantInt8, QuantInt8W8A8, or QuantInt4 weights
    (w [in, out]). int8 dequant sits in the matmul epilogue (one fused
    multiply per output element); int4 routes to the Pallas packed-nibble
    kernel (ops/quant4.py) whose HBM read is half the int8 bytes."""
    from .quant4 import QuantInt4, qmatmul4

    if isinstance(w, QuantInt4):
        return qmatmul4(x, w)
    if isinstance(w, QuantInt8W8A8):
        # Per-token symmetric activation quantization, s8×s8→s32 MXU dot,
        # both scales in the f32 epilogue.
        ax = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32)
        sx = jnp.maximum(ax / 127.0, 1e-12)
        xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx),
                      -127, 127).astype(jnp.int8)
        y = jax.lax.dot_general(
            xq, w.q,
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        return (y.astype(jnp.float32) * sx * w.scale[0]).astype(x.dtype)
    if isinstance(w, QuantInt8):
        y = jax.lax.dot_general(
            x, w.q.astype(x.dtype),
            (((x.ndim - 1,), (0,)), ((), ())),
        )
        # Scale multiply in f32, cast once: rounding the scales to the
        # activation dtype first would add systematic per-channel error.
        return (y.astype(jnp.float32) * w.scale[0]).astype(x.dtype)
    return x @ w


def qmatmul_heads(x: jnp.ndarray, w, heads: int, head_dim: int) -> jnp.ndarray:
    """``qmatmul(x, w)`` [..., heads * head_dim] split into [..., heads,
    head_dim], the split kept OUTSIDE the projection's dot: the values are
    ``qmatmul(x, w).reshape(...)``'s, bit for bit.

    Why not the plain reshape: the TPU compiler folds a head split that
    follows a dot into the dot's result ([16, 32, 128] for a decode pass),
    gives the weight operand a contraction-minor layout to match, and — the
    weight being one layer of a stack the layer scan slices — then slices
    the layer's matrix out to VMEM with a blocking fusion and turns it over
    with a copy, every layer of every pass, before the dot reads it (25 MB a
    layer for Mistral-7B's wq, wk and wv: tests/test_tpu_aot.py::
    test_decode_projections_stream_their_weights_as_stored_on_v5e). Behind
    the barrier the dot is a plain [rows, in] x [in, out] over the weight as
    stored, the layer's slice fuses into it as it does for ``wo`` and the
    MLP's, and the bytes stream once from HBM."""
    y = jax.lax.optimization_barrier(qmatmul(x, w))
    return y.reshape(*x.shape[:-1], heads, head_dim)


# --------------------------------------------------------------- KV cache
#
# int8 KV cache (KV_QUANT=int8): decode attention reads the whole live KV
# span every step, and on HBM-bound 7B-class single-chip serving the KV
# pool is what caps the decode batch size (round 4: Gemma-7B int8 weights
# + a bf16 KV pool fit bs=16; the bs=32 rung OOMed). Halving KV bytes
# halves both the pool (→ 2× the slots in the same HBM) and the per-step
# attention read. Per-(token, head) symmetric scales over the head_dim
# axis — the finest granularity that adds only 1/head_dim of overhead
# (f32 scale per 256 int8 payload bytes ≈ 1.6%).


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantKV:
    """Symmetric int8 KV block with per-(…, head) scales.

    q: int8, the original KV shape  [..., n_kv_heads, head_dim]
    s: f32,  one scale per head vector  [..., n_kv_heads]

    A registered pytree: ``jax.tree.map`` recurses into (q, s), so cache
    splice/slice/scatter code written as tree.maps works identically for
    plain bf16 arrays and QuantKV (the scale leaf just has one fewer
    trailing axis — all structural ops below index leading axes only).
    """

    q: jnp.ndarray
    s: jnp.ndarray


def kv_quantize(x: jnp.ndarray) -> QuantKV:
    """[..., hd] bf16 → int8 with one f32 scale per trailing vector."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return QuantKV(q=q, s=s)


def kv_dequantize(kv: QuantKV, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Elementwise convert+scale; inside a jitted attention this fuses into
    the score matmul's operand read (same pattern as qmatmul's weight
    convert, HLO-verified in tests/test_tpu_kernels.py)."""
    return (kv.q.astype(jnp.float32) * kv.s[..., None]).astype(dtype)


def kv_tokens(kv) -> int:
    """Static length of the sequence axis (2) of a KV block
    ([n_layers, batch, seq, ...]); works for plain arrays and QuantKV."""
    leaf = kv.q if isinstance(kv, QuantKV) else kv
    return leaf.shape[2]


def kv_update_slice(dst, src):
    """dynamic_update_slice of a KV block at the origin, per leaf."""
    return jax.tree.map(
        lambda d, s: jax.lax.dynamic_update_slice(d, s, (0,) * d.ndim),
        dst, src)


def kv_slot_update(dst, src, slot):
    """Write a single-slot KV block ``src`` into slot ``slot`` (axis 1)."""
    zero = jnp.asarray(0, jnp.int32)

    def upd(d, s):
        idx = (zero, jnp.asarray(slot, jnp.int32)) + (zero,) * (d.ndim - 2)
        return jax.lax.dynamic_update_slice(d, s, idx)

    return jax.tree.map(upd, dst, src)


def kv_set_slots(dst, src, slots):
    """Scatter per-row KV blocks into slots (axis 1); out-of-bounds rows
    drop (the batched-admission padding contract).

    ``src`` may be SHALLOWER than ``dst`` along the sequence axis (2):
    group admissions prefill into suffix-depth scratch (kv_limit
    positions, not the slot's full S_alloc — engine/batcher.py), and only
    those positions are written. The slot's stale tail beyond src's depth
    is never read: decode's causal mask exposes only positions below the
    slot's live length, and each later position is overwritten by its own
    decode step before the mask ever reaches it."""
    def set_rows(d, s):
        if s.shape[2] < d.shape[2]:
            return d.at[:, slots, :s.shape[2]].set(s, mode="drop")
        return d.at[:, slots].set(s, mode="drop")

    return jax.tree.map(set_rows, dst, src)


def kv_broadcast_rows(src, n: int):
    """[L, 1, P, ...] → [L, n, P, ...] per leaf (prefix → batch splice)."""
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (a.shape[0], n) + a.shape[2:]), src)


def kv_prefix_trim(kv, p: int):
    """Trim a KV block to its first ``p`` sequence positions."""
    return jax.tree.map(lambda a: a[:, :, :p], kv)


def to_w8a8(params):
    """Re-tag the LAYER projections' QuantInt8 leaves as QuantInt8W8A8
    (same payload and scales — only qmatmul's dispatch changes). The
    embedding/head stay weight-only: their outputs are the logits, where
    activation-quant noise directly moves the argmax. Rank-4 MoE expert
    stacks also stay weight-only: the MoE einsum epilogues
    (parallel/moe.py::_qeinsum) have no W8A8 path, and the measured
    verdict on W8A8 (a no-op — earlier chip run, not re-measured)
    makes one pointless."""
    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(
        lambda x: (QuantInt8W8A8(q=x.q, scale=x.scale)
                   if isinstance(x, QuantInt8) and x.q.ndim == 3 else x),
        params["layers"],
        is_leaf=lambda x: isinstance(x, QuantInt8),
    )
    return out


#: The latent attention's key/value up-projection as this SEEDED generator
#: draws it (bench/dev only), in units of a unit-variance projection's
#: deviation (latent width ** -0.5). At 1 the scores' deviation is 1 (the int8
#: projections around it are uniform, deviation 0.58 of theirs) and attention
#: is a near-uniform average no comparison of logits could tell from another;
#: at 2 it is 1.9 and the values weigh twice as much in the residual. (At 4
#: the scores' deviation is 3.6 and bf16's rounding of queries and rows alone
#: moves a logit by 0.07 of the logits' deviation at toy widths, past what
#: the comparison can hold the other precisions to; at the stack's leading
#: size, what a bf16 leaf gets by default, it was 10: every softmax one key.)
SEEDED_UKV_GAIN = 2.0


#: projection weights eligible for quantization (matmul RHS with the
#: output channel last). Embeddings/norms/router excluded.
_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
               "idx_wq", "idx_wk", "ssm_in", "ssm_out", "shared_gate",
               "shared_up", "shared_down",
               # latent attention's projections; ``w_ukv`` stays out: the
               # absorbed form contracts it over its OUTPUT channels, where
               # the per-channel scales sit (models/transformer.py::
               # init_params)
               "w_dq", "w_uq", "w_dkv",
               # the sliding-attention kind's projections and the dense
               # MLP mixer's (a configuration with attention of two kinds)
               "sw_wq", "sw_wk", "sw_wv", "sw_wo",
               "dense_gate", "dense_up", "dense_down",
               # a linear-attention layer's fused in-projection and its
               # output projection (W_a, W_b stay bf16: small leaves), and
               # the projection of a decay a key channel (W_f: a full matrix)
               "lin_in", "lin_out", "lin_wf")


#: The decay projection of Kimi delta attention as this SEEDED generator
#: draws it, in units of the other projections' scale: the argument of the
#: decay's sigmoid is A_h (x W_f + bias) with A_h up to 16, and at 1 every
#: channel of the heads with a large A would sit at the floor or at none
#: (models/transformer.py::small_leaf_init has the bias and the spread).
SEEDED_DECAY_PROJ_GAIN = 0.25


#: The per-head q/k RMSNorm gains this SEEDED generator writes (bench/dev
#: only; ``init_params`` and a checkpoint have their own). Not 1: with unit
#: gains and random weights q.k/sqrt(hd) has unit variance, attention is a
#: near-uniform average of ~N values, a 2% part of the residual, and no
#: comparison of logits could tell which keys were attended. At 2 the
#: scores' standard deviation is 4, as in a trained model.
SEEDED_QK_NORM_GAIN = 2.0


#: An eagerly made leaf of this many bytes or more is filled in place,
#: slice by slice (``_slices_in_place``): its slices, their stack and the
#: stack's copy into the layout the TPU keeps a 4-D leaf in are three
#: times the leaf at once, and at 128 experts of 2688 x 1856 over 5 layers
#: (3.2 GB a leaf) the seeded init peaked at 16.68 GB of a chip's 16.9
#: (my chip run, PR 33). The same values as the stacked form, slice for
#: slice. Mixtral-8x7B's six-layer cut has leaves this large too (6 x 8
#: experts of 4096 x 14336: 2.8 GB) and takes this path; smaller leaves
#: keep the stacked form they were measured with.
_IN_PLACE_BYTES = 2 * 2 ** 30


def _slices_in_place(one, keys, shape):
    """int8 [*lead, in, out]: slice i is ``one(keys[i])``, as the stacked
    form draws it, written into one preallocated (donated) leaf."""
    import numpy as np

    lead = shape[:-2]

    def put(buf, k, idx):
        return jax.lax.dynamic_update_slice(
            buf, one(k).reshape((1,) * len(lead) + shape[-2:]),
            tuple(idx) + (0, 0))

    put = jax.jit(put, donate_argnums=0)
    buf = jnp.zeros(shape, jnp.int8)
    for i, idx in enumerate(np.ndindex(*lead)):
        buf = put(buf, keys[i], jnp.asarray(idx, jnp.int32))
    return buf


def random_params_int8(key, cfg, dtype=None,
                       quantize_embed: bool = False,
                       int4: bool = False) -> Dict[str, Any]:
    """Random-init a param tree DIRECTLY in quantized form, leaf by leaf
    and slice by slice (``_random_params_int8`` holds the recipe)."""
    return _random_params_int8(key, cfg, dtype, quantize_embed, int4,
                               slices_in_one_op=False)


def _random_params_int8(key, cfg, dtype, quantize_embed: bool, int4: bool,
                        slices_in_one_op: bool) -> Dict[str, Any]:
    """Random-init a param tree DIRECTLY in quantized form — no
    full-precision materialization anywhere (a 7B bf16 init is ~17 GB:
    HBM OOM before quantization could run, and a host-side init pays
    minutes of CPU PRNG plus a ~10 GB transfer). Bench/dev only: weight
    VALUES are arbitrary (same as any random init), but the tree
    structure, shapes, and dtypes match
    ``quantize_params_int8(init_params(...))`` exactly — every jitted
    serving program compiles identically to a real int8 checkpoint.

    ``int4=True`` (via ops/quant4.py::random_params_int4) generates
    kernel-tileable projection leaves at PACKED int4 size instead
    (payload [..., in, out/2] + group scales), matching
    ``quantize_params_int4``; non-tileable leaves stay int8.

    ``slices_in_one_op`` draws a stacked leaf's 2D slices with one
    batched op over their keys (``jax.vmap``: the same values as the
    loop, key for key) instead of one op a slice. Eager, that would
    materialize the PRNG's 32-bit words for the whole leaf; under
    ``jax.jit`` the words never leave the fusion, and the program is
    one op a leaf instead of one a slice (Mixtral-8x7B: 896 slices),
    which is what its compile time follows
    (``random_params_int8_sharded``).
    """
    import jax.numpy as _jnp

    from ..models.transformer import init_params, small_leaf_init
    from .quant4 import QuantInt4, pick_format

    if dtype is None:
        dtype = _jnp.bfloat16
    shapes = jax.eval_shape(lambda k: init_params(k, cfg, dtype=dtype), key)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(key, len(leaves))
    out = []
    for (path, sds), k in zip(leaves, keys):
        name = path[-1].key
        quantized = ((name in _QUANT_KEYS and len(sds.shape) in (3, 4))
                     or name == "lm_head")
        if quantized:
            # MoE expert stacks ([L, E, in, out]) stay int8 under int4
            # mode too — the int4 kernel serves 2D per-layer slices, and
            # the MoE einsum epilogues are int8-shaped (parallel/moe.py).
            fmt = (pick_format(sds.shape[-2], sds.shape[-1])
                   if int4 and len(sds.shape) <= 3 else None)
            payload_shape = (sds.shape[:-1] + (sds.shape[-1] // 2,)
                             if fmt else sds.shape)
            # Per-slice generation over the leading (layer/expert) dims:
            # the PRNG materializes uint32 bits (4 B/element) before the
            # int8 convert, so one call over a stacked 7B MLP leaf
            # ([28, 3072, 24576]) would transiently need ~8.5 GB — an OOM
            # on its own. 2D slices keep the transient at 1/lead of that;
            # the stack is pure int8.
            lead = payload_shape[:-2]
            if lead:
                n_lead = 1
                for d in lead:
                    n_lead *= d
                lk = jax.random.split(k, n_lead)

                def one(ki):
                    return jax.random.randint(ki, payload_shape[-2:],
                                              -127, 128, dtype=_jnp.int8)

                if slices_in_one_op:
                    q = jax.vmap(one)(lk).reshape(payload_shape)
                elif (n_lead * sds.shape[-2] * payload_shape[-1]
                        >= _IN_PLACE_BYTES
                        and not isinstance(key, jax.core.Tracer)):
                    q = _slices_in_place(one, lk, payload_shape)
                else:
                    q = _jnp.stack([one(lk[i]) for i in range(n_lead)]
                                   ).reshape(payload_shape)
            else:
                q = jax.random.randint(k, payload_shape, -127, 128,
                                       dtype=_jnp.int8)
            if fmt:
                G = sds.shape[-2] // fmt[0]
                sshape = sds.shape[:-2] + (G, sds.shape[-1])
                scale = _jnp.full(sshape, (sds.shape[-2] ** -0.5) / 7.0,
                                  _jnp.float32)
                out.append(QuantInt4(q=q, scale=scale,
                                     group_in=fmt[0], block_out=fmt[1]))
                continue
            sshape = tuple(1 if i == len(sds.shape) - 2 else s
                           for i, s in enumerate(sds.shape))
            # Plausible magnitude: absmax ≈ the init scale init_params uses.
            gain = SEEDED_DECAY_PROJ_GAIN if name == "lin_wf" else 1.0
            scale = _jnp.full(sshape, gain * (sds.shape[-2] ** -0.5) / 127.0,
                              _jnp.float32)
            out.append(QuantInt8(q=q, scale=scale))
        elif (small := small_leaf_init(name, sds.shape, dtype, k)) is not None:
            # a patterned configuration's step biases, decays, convolution
            # and selection bias: the values init_params gives them
            out.append(small)
        elif name in ("q_norm", "k_norm"):
            # (the gain a head is ``rms_offset`` + the leaf)
            out.append(_jnp.full(
                sds.shape, SEEDED_QK_NORM_GAIN - cfg.rms_offset, dtype))
        elif name.endswith("norm"):
            # (a gated head norm's gain is plain, whatever the block's norm)
            fill = (_jnp.zeros if cfg.rms_offset and not name.endswith(
                "gate_norm") else _jnp.ones)
            out.append(fill(sds.shape, dtype))
        elif name == "embed" and quantize_embed:
            q = jax.random.randint(k, sds.shape, -127, 128, dtype=_jnp.int8)
            # (what the model multiplies its embedding by, the seeded rows
            # are drawn without: ``embed_multiplier`` x E has the unit scale
            # every other configuration's embedding has, and the layers'
            # outputs, not the token's own row, decide a logit)
            out.append(QuantInt8(
                q=q,
                scale=_jnp.full((sds.shape[0], 1),
                                1.0 / 127.0 / cfg.embed_multiplier,
                                _jnp.float32),
            ))
        else:
            scale = (1.0 / cfg.embed_multiplier if name == "embed"
                     else sds.shape[0] ** -0.5)
            if name == "router" and (cfg.router == "sigmoid_bias"
                                     or cfg.router_width):
                # unit-variance logits: scores spread over (0, 1) and the
                # top-k is the input's, not a few saturated experts' (a
                # softmax router's logits at the default scale have a
                # deviation of 20-45: every token one expert at weight 1)
                scale = sds.shape[-2] ** -0.5
            if name == "w_ukv":
                scale = SEEDED_UKV_GAIN * sds.shape[-2] ** -0.5
            if name in ("wg", "sw_wg", "shared_expert_gate"):
                # a head's gate logit of unit variance: gates spread over
                # (0.1, 0.9), not all at 1/2
                scale = sds.shape[-2] ** -0.5
            out.append(
                (jax.random.normal(k, sds.shape, _jnp.float32) * scale)
                .astype(dtype)
            )
    return jax.tree_util.tree_unflatten(treedef, out)


def random_params_int8_sharded(key, cfg, mesh, dtype=None,
                               quantize_embed: bool = False,
                               int4: bool = False) -> Dict[str, Any]:
    """``random_params_int8``'s tree for the same key, made ON a mesh:
    one compiled call whose outputs carry the shardings
    parallel/sharding.py::shard_params would give them
    (``param_shardings``, the one policy), so each device generates and
    holds only its share and no leaf is ever whole on one device — a
    model served over a mesh because one chip cannot hold it (Mixtral-
    8x7B, 46.7 GB of int8) cannot pass through one chip on its way
    there. The values are the whole-tree generator's: it is the same
    recipe under ``jax.jit`` (a stacked leaf's slices drawn by one
    batched op, key for key), and the PRNG's bits depend on an
    element's index alone (``jax_threefry_partitionable``, on in the
    pinned jax), not on which device makes it."""
    from ..parallel.sharding import param_shardings

    def make(k):
        return _random_params_int8(k, cfg, dtype, quantize_embed, int4,
                                   slices_in_one_op=True)

    shardings = param_shardings(jax.eval_shape(make, key), mesh, cfg)
    return jax.jit(make, out_shardings=shardings)(key)


def quantize_params_int8(params: Dict[str, Any],
                         quantize_embed: bool = False) -> Dict[str, Any]:
    """Quantize every dense projection matmul weight in the param tree
    (models/transformer.py::init_params layout) to QuantInt8.

    Stacked MoE expert weights ([L, E, in, out], rank 4) quantize with
    per-(layer, expert, out-channel) scales — ``quantize_int8`` reduces
    only the contraction axis (-2), so the same call covers them, and the
    MoE einsums (parallel/moe.py) keep the dequant multiply in their
    epilogues exactly like ``qmatmul`` (no weight re-materialization;
    VERDICT r4 item 3 — Mixtral's 47 GB of expert weights are the reason
    BASELINE config 4 needs int8 at all). The router stays full precision
    (tiny, and routing decisions sit directly on its logits).

    ``quantize_embed`` additionally stores the embedding per-row int8
    (quantize_embed_int8) — halves the tied-head weight read and the
    embedding's HBM. The engine enables it whenever QUANT=int8; under a
    mesh the QuantInt8 leaf shards with the bf16 embedding's vocab-row
    spec (shard_params sanitizes the [V, 1] scale against the same spec).
    """
    out = dict(params)
    layers = dict(params["layers"])
    for key in _QUANT_KEYS:
        if key in layers and layers[key].ndim in (3, 4):
            layers[key] = quantize_int8(layers[key])
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"] = quantize_int8(params["lm_head"])
    if quantize_embed:
        out["embed"] = quantize_embed_int8(params["embed"])
    return out
