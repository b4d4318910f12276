"""Rotary position embeddings (RoPE) with explicit position indices.

Positions are always passed explicitly (shape [batch, seq]) rather than
derived from array offsets — this is what makes prefix-KV splicing and
paged decode correct: a token's rotation depends on its absolute position
in the logical sequence, not on where its KV happens to live in cache
memory (SURVEY.md §7, "Prefix-KV sharing" hard part).

``apply_rope`` is the plain rotary embedding of most families.
``apply_rope_partial`` rotates the first lanes of a head and passes the rest
(``laguna``: YaRN on half the full-attention kind's lanes).
``apply_rope_scaled`` is the latent-attention family's (``mistral4``, after
DeepSeek-V2): YaRN frequencies, pairs taken interleaved, a factor on cos
and sin; ``query_scale`` is its position-dependent scale on the queries.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    """Inverse frequencies, shape [head_dim // 2], float32."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0) -> jnp.ndarray:
    """Rotate q or k.

    x:          [batch, seq, n_heads, head_dim]
    positions:  [batch, seq] absolute token positions (int32)

    Uses the "split halves" convention (dims [0:d/2] pair with [d/2:d]),
    matching HF Llama/Gemma/Mixtral — required for converted checkpoints to
    be numerically faithful.
    """
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)              # [d/2]
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [b, s, d/2]
    cos = jnp.cos(angles)[:, :, None, :]                      # [b, s, 1, d/2]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 m ln(factor) + 1 past factor 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1.0 else 1.0


def yarn_frequencies(dim: int, theta: float, factor: float, original_max: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies, float32 [dim // 2] (host constants).
    A pair that turns more than ``beta_fast`` times over the
    ``original_max`` trained positions keeps its frequency, one that turns
    fewer than ``beta_slow`` times has it divided by ``factor``, and a
    linear ramp over the pair index joins the two (the published
    ``_compute_yarn_parameters``, its bounds truncated to whole pairs)."""
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return (1.0 / pos_freqs).astype(np.float32)

    def pair_turning(rotations: float) -> float:
        return (dim * math.log(original_max / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1.0 - ramp)
    return inv.astype(np.float32)


def apply_rope_scaled(x: jnp.ndarray, positions: jnp.ndarray, inv_freq,
                      interleave: bool = False,
                      factor: float = 1.0) -> jnp.ndarray:
    """Rotate [batch, seq, heads, dim] by ``positions`` x ``inv_freq``,
    cos and sin times ``factor``. ``interleave``: the pairs are (2i, 2i+1)
    and the result comes out with the first members in its first half and
    the second members in its second (as the published code leaves it:
    queries and keys are both left so, and their products do not care)."""
    angles = positions[..., None].astype(jnp.float32) * jnp.asarray(inv_freq)
    cos = (jnp.cos(angles) * factor)[:, :, None, :]
    sin = (jnp.sin(angles) * factor)[:, :, None, :]
    xf = x.astype(jnp.float32)
    if interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    rotated = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return rotated.astype(x.dtype)


def query_scale(positions: jnp.ndarray, beta: float, original_max: int) -> jnp.ndarray:
    """The Llama-4 query scale, float32 like ``positions``: 1 + beta ln(1 +
    floor(pos / original_max)) — 1 inside the trained positions."""
    return 1.0 + beta * jnp.log1p(
        jnp.floor(positions.astype(jnp.float32) / original_max))



def apply_rope_partial(x: jnp.ndarray, positions: jnp.ndarray, inv_freq,
                       factor: float = 1.0) -> jnp.ndarray:
    """Rotate the first ``2 x len(inv_freq)`` lanes of [batch, seq, heads,
    dim], pairs (i, i + rot/2) within them, cos and sin times ``factor``;
    the lanes past them pass as they are (``partial_rotary_factor``)."""
    rot = 2 * len(inv_freq)
    if rot == x.shape[-1]:
        return apply_rope_scaled(x, positions, inv_freq, factor=factor)
    return jnp.concatenate(
        [apply_rope_scaled(x[..., :rot], positions, inv_freq, factor=factor),
         x[..., rot:]], axis=-1)
