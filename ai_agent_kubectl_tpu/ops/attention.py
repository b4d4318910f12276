"""Dense reference attention (GQA, causal, KV-cache aware) + backend dispatch.

This is the numerically-trusted baseline every Pallas kernel is tested
against (SURVEY.md §4: kernel unit tests vs dense reference). It is also a
perfectly good TPU program for small shapes: one fused softmax(QK^T)V chain
that XLA maps straight onto the MXU.

Conventions:
- q:  [batch, q_len, n_heads, head_dim]
- k/v: [batch, kv_len, n_kv_heads, head_dim]   (GQA: n_kv_heads divides n_heads)
- mask: bool [batch, q_len, kv_len] or None — True = attend.
- softmax in float32, output in q.dtype.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def repeat_kv(kv: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for GQA: [b, s, n_kv, d] -> [b, s, n_kv * n_rep, d]."""
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def dense_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    scale: Optional[float] = None,
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """softmax(q k^T / sqrt(d) [+ mask]) v with GQA head expansion.

    ``logit_softcap`` applies Gemma-2-style tanh capping when > 0.

    The dots run in the QUERY dtype with f32 accumulation — under bf16
    serving the MXU takes bf16 operands at full rate (upcasting K/V to
    f32 first would both materialize a 2x-bytes copy of the whole KV
    span per layer per step and push the dot into the ~4x-slower f32 MXU
    mode — measured ~16 ms of a 34 ms 7B bs=48 decode step before r5);
    under the f32 test configs everything stays f32, preserving the
    reference numerics the kernels are validated against. Softmax and
    masking stay f32 always.

    GQA/MQA group queries instead of repeating KV (round 6, same
    structure ``dense_attention_quant`` proved in r5): queries reshape to
    [b, q, n_kv, g, d] and both dots contract against the UNREPEATED KV
    span — ``repeat_kv``'s broadcast+reshape is a materialization XLA
    cannot always fuse away, which on the MQA 2B headline model read the
    whole span ×8 (one per query head) per layer per decode step. The
    per-head math is unchanged (each grouped query row contracts the
    same KV vectors the repeated layout would have).
    """
    B, Q, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    if scale is None:
        scale = D ** -0.5
    qg = q.reshape(B, Q, KV, G, D)
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k.astype(q.dtype),
        preferred_element_type=jnp.float32,
    ) * scale
    if logit_softcap > 0.0:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs.astype(q.dtype), v.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Q, H, D).astype(q.dtype)


def dense_attention_quant(
    q: jnp.ndarray,
    k_q: jnp.ndarray,        # int8 [b, s, n_kv, d] payload
    k_s: jnp.ndarray,        # f32  [b, s, n_kv] scales
    v_q: jnp.ndarray,
    v_s: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    scale: Optional[float] = None,
    logit_softcap: float = 0.0,
) -> jnp.ndarray:
    """Dense attention reading an int8-quantized KV span DIRECTLY.

    The per-(position, head) dequant scale commutes out of the head_dim
    contraction: ``q . (K_q[s] * k_s[s]) == (q . K_q[s]) * k_s[s]``, so
    the K scale multiplies the [.., q, s] SCORES and the V scale folds
    into the softmax PROBS — both [s]-shaped surfaces, 1/head_dim the
    work of dequantizing the span — and the int8 payloads feed the MXU
    dots via the fusable in-dot convert. Before r5 the serving path
    dequantized the whole span to bf16 per layer per step
    (models/transformer.py kv_dequantize), which XLA materialized:
    ~13 GB of extra HBM traffic per 7B bs=48 step — the single largest
    cost in the decode step (device-profiled ablation on an
    earlier chip run, not re-measured).

    GQA is handled by grouping query heads ([b, q, n_kv, g, d]) instead
    of materializing repeated int8 KV.
    """
    B, Q, H, D = q.shape
    KV = k_q.shape[2]
    G = H // KV
    if scale is None:
        scale = D ** -0.5
    qg = q.reshape(B, Q, KV, G, D)
    # [b, kv, g, q, s] logits; K int8 -> q.dtype converts inside the dot.
    logits = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k_q.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    logits = logits * (k_s.transpose(0, 2, 1)[:, :, None, None, :] * scale)
    if logit_softcap > 0.0:
        logits = jnp.tanh(logits / logit_softcap) * logit_softcap
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    probs = probs * v_s.transpose(0, 2, 1)[:, :, None, None, :]
    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs.astype(q.dtype), v_q.astype(q.dtype),
        preferred_element_type=jnp.float32,
    )
    return out.reshape(B, Q, H, D).astype(q.dtype)


def causal_mask(q_len: int, kv_len: int, q_offset: jnp.ndarray | int = 0) -> jnp.ndarray:
    """[1, q_len, kv_len] causal mask: query i (at absolute position
    q_offset + i) may attend to kv position j iff j <= q_offset + i."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos <= q_pos)[None, :, :]
