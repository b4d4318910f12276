"""Learned top-k key selection for paged attention (ISSUE 31).

A selecting configuration (``ModelConfig.index_topk`` > 0; the lightning
indexer DeepSeek-V3.2-Exp published, on a GQA model) keeps ONE index key
a token a layer beside K and V, in a pool leaf of its own under the same
block table. Query t scores every key s <= t,

    I[t, s] = sum_j w[t, j] * relu(q'[t, j] . k'[s])

and attends to the ``index_topk`` best alone (ties to the lower s), one
set for all heads. Everything here is exact: no block-level selection, no
approximate top-k. A context of at most ``index_topk`` keys selects every
key, and the caller then serves the dense ragged kernel unchanged
(models/transformer.py::_select_and_attend).

One form, plain XLA in this first version: ``window_selection``, a
[B, S, K] boolean mask for the ragged kernel's ``sel`` operand — the k-th
largest score of each row by a bit-by-bit search over the scores'
order-preserving integer keys (32 counting passes, no sort), ties at the
threshold resolved to the lower s. A decode step is a window of one row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: Bytes the [B, q, heads, K] float32 score tile of one query chunk may
#: take; the chunk is the largest divisor of the window under it (the
#: source's ``q_chunk_size``/``kv_chunk_size`` are such a tiling and
#: change no result).
_SCORE_TILE_BYTES = 512 * 2**20


def _q_chunk(B: int, S: int, J: int, K: int) -> int:
    cap = max(1, _SCORE_TILE_BYTES // (B * J * K * 4))
    return next(c for c in range(min(S, cap), 0, -1) if S % c == 0)


def index_scores(qi: jnp.ndarray, wi: jnp.ndarray, ik: jnp.ndarray,
                 positions: jnp.ndarray) -> jnp.ndarray:
    """I [B, S, K] float32; -inf where key s lies after query t.

    qi [B, S, J, di] index queries (rotary applied), wi [B, S, J] head
    weights, ik [B, K, di] the slots' index keys gathered through the
    block table (key s of slot b at row s), positions [B, S] absolute."""
    B, S, J, _ = qi.shape
    K = ik.shape[1]
    kv_pos = jnp.arange(K, dtype=jnp.int32)[None, None, :]

    def chunk(args):
        q, w, pos = args                              # [B, c, ...]
        s = jnp.einsum("bqjd,bkd->bqjk", q, ik.astype(q.dtype),
                       preferred_element_type=jnp.float32)
        s = jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(s),
                       w.astype(jnp.float32))
        return jnp.where(kv_pos <= pos[:, :, None], s, -jnp.inf)

    c = _q_chunk(B, S, J, K)
    if c == S:
        return chunk((qi, wi, positions))

    def split(x):       # [B, S, ...] -> [S/c, B, c, ...]
        return jnp.moveaxis(
            x.reshape((B, S // c, c) + x.shape[2:]), 1, 0)

    out = jax.lax.map(chunk, (split(qi), split(wi), split(positions)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, K)


def _order_keys(x: jnp.ndarray) -> jnp.ndarray:
    """float32 -> uint32 with the same order (-inf lowest, -0.0 < +0.0
    is the one difference from ``<`` and touches no tie: both zeros of a
    score are written by the same sum)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def window_selection(scores: jnp.ndarray, k: int) -> jnp.ndarray:
    """bool [B, S, K]: the ``k`` largest of each row's finite scores,
    ties to the lower index; every finite score where a row has at most
    ``k`` of them."""
    keys = _order_keys(scores)
    valid = scores > -jnp.inf

    def bit(i, prefix):
        cand = prefix | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        n = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= k, cand, prefix)

    # The largest key value that at least k keys reach: the k-th largest.
    thr = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = keys > thr[..., None]
    at = keys == thr[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    n_at = jnp.sum(at, axis=-1, dtype=jnp.int32)

    def ties_in_order(_):
        return jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room[..., None]

    # More keys AT the threshold than there is room for is rare (exact
    # ties of float32 sums), so the ordered count runs only then.
    keep_at = jax.lax.cond(jnp.any(n_at > room), ties_in_order,
                           lambda _: jnp.ones(at.shape, bool), None)
    return jnp.logical_and(
        valid, jnp.logical_or(above, jnp.logical_and(at, keep_at)))
