"""Latent attention (MLA: DeepSeek-V2's, as ``mistral4`` takes it) outside
the kernel: a token's cached row, its write into the pool's pair-row leaf,
and the two forms of the attention itself over rows read back densely.

A token caches ONE row for all heads, ``[c | kr]``: the RMS-normed latent
(``kv_lora_rank`` values) and the rotated rope key (``qk_rope_head_dim``).
Head h's key is ``[c W_uk_h | kr]`` and its value ``c W_uv_h``:

- EXPANDED: make every head's key and value from the rows and attend as
  usual: per (query, key) pair and head 2 x (nope + rope) + 2 x v FLOPs,
  plus the expansion of every row read, every call.
- ABSORBED: fold ``W_uk_h`` into the query (``qt_h = q_nope_h W_uk_h^T``)
  and ``W_uv_h`` behind the output: scores ``qt_h . c + q_rope_h . kr``,
  the weighted sum over the rows' ``c`` themselves, then ``W_uv_h`` once a
  query. 32 heads over ONE key of 320 values whose first 256 are also the
  value; nothing a key is expanded. The same numbers (tests/
  test_latent_attention.py); the pool's kernel (ops/ragged_attention.py::
  latent_attention_pool) is this form.

The serving path is absorbed everywhere; PERF.md (PR 38) has the timing
that decided a window's own tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ragged_attention import LATENT_PAIR


def _softmax_rows(scores, mask):
    """float32 softmax over the last axis inside ``mask`` (a row with no
    key allowed — a padded query — gives zeros, as the kernel does)."""
    s = jnp.where(mask, scores, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.where(mask, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    return e / jnp.where(total == 0.0, 1.0, total)


def absorbed_attention(q_c, q_r, c, kr, mask):
    """q_c [B, S, H, C] and q_r [B, S, H, R] (absorbed and rope queries,
    already times the scale) over rows c [B, K, C], kr [B, K, R] under
    mask [B, S, K] -> the latent-space output [B, S, H, C] (the caller
    applies ``W_uv``). The dense read-back path (the CPU's, and the tests'):
    float32 throughout."""
    f32 = lambda a: a.astype(jnp.float32)
    scores = (jnp.einsum("bshc,bkc->bhsk", f32(q_c), f32(c))
              + jnp.einsum("bshr,bkr->bhsk", f32(q_r), f32(kr)))
    p = _softmax_rows(scores, mask[:, None])
    return jnp.einsum("bhsk,bkc->bshc", p, f32(c)).astype(q_c.dtype)


def expanded_attention(q_nope, q_r, c, kr, w_uk, w_uv, mask):
    """The same attention with every head's key and value made from the
    rows: q_nope [B, S, H, N] and q_r [B, S, H, R] (times the scale), w_uk
    [C, H, N], w_uv [C, H, V] -> [B, S, H, V]."""
    f32 = lambda a: a.astype(jnp.float32)
    k_nope = jnp.einsum("bkc,chn->bkhn", f32(c), f32(w_uk))
    v = jnp.einsum("bkc,chv->bkhv", f32(c), f32(w_uv))
    scores = (jnp.einsum("bshn,bkhn->bhsk", f32(q_nope), k_nope)
              + jnp.einsum("bshr,bkr->bhsk", f32(q_r), f32(kr)))
    p = _softmax_rows(scores, mask[:, None])
    return jnp.einsum("bhsk,bkhv->bshv", p, v).astype(q_nope.dtype)


def write_rows(leaf, flat, positions, c, kr, layer=None):
    """Write a window's rows into the latent leaf ([n_blocks, page/2, 2C +
    2R], or stacked [L, ...] with ``layer``; ops/ragged_attention.py::
    latent_pack has the layout) at flat token rows ``flat`` [B, S] (block x
    page + offset; out of bounds drops). A leaf row holds a PAIR of
    tokens, so a token's write is a row read, its half replaced, the row
    written back: the other half is its partner's new row where the
    partner is written by this same call (both then write the same whole
    row, in either order), else what the leaf holds. One gather of the
    window's rows and one in-place scatter."""
    C = c.shape[-1]
    i = 0 if layer is None else 1
    rows_shape = leaf.shape[:i] + (leaf.shape[i] * leaf.shape[i + 1],
                                   leaf.shape[-1])
    f = leaf.reshape(rows_shape)
    n_rows = rows_shape[i]
    pair = flat // LATENT_PAIR                  # OOB stays OOB (page is even)
    writable = pair < n_rows
    idx = jnp.minimum(pair, n_rows - 1)
    old = f[idx] if layer is None else f[layer, idx]       # [B, S, lanes]
    R = kr.shape[-1]
    even = (positions % LATENT_PAIR) == 0

    def neighbour(a, fill):
        """(the next column's, the previous column's) value of ``a``."""
        pad = [(0, 0)] * a.ndim
        pad[1] = (1, 1)
        p = jnp.pad(a, pad, constant_values=fill)
        return p[:, 2:], p[:, :-2]

    pos_next, pos_prev = neighbour(positions, -2)
    ok_next, ok_prev = neighbour(writable, False)
    partner_here = jnp.where(
        even, jnp.logical_and(ok_next, pos_next == positions + 1),
        jnp.logical_and(ok_prev, pos_prev == positions - 1))[..., None]

    def halves(new, old_even, old_odd):
        nxt, prv = neighbour(new, 0)
        e = even[..., None]
        first = jnp.where(e, new, jnp.where(partner_here, prv, old_even))
        second = jnp.where(e, jnp.where(partner_here, nxt, old_odd), new)
        return first, second

    dt = leaf.dtype
    c0, c1 = halves(c.astype(dt), old[..., :C], old[..., C:2 * C])
    r0, r1 = halves(kr.astype(dt), old[..., 2 * C:2 * C + R],
                    old[..., 2 * C + R:])
    rows = jnp.concatenate([c0, c1, r0, r1], axis=-1)
    at = (pair,) if layer is None else (layer, pair)
    return f.at[at].set(rows).reshape(leaf.shape)
