"""Plain reference for mixtral-8x7b-instruct-v0.1: the decoder's forward pass
in straightforward float32 jax.numpy — no kernels, no cache, no batching, no
mesh (one device, whatever mesh the program is served over).

Follows the published architecture (MixtralForCausalLM, config.json beside
this file): as Mistral-7B (RMSNorm, grouped-query attention with rotary
embeddings in the split-halves pairing, residuals, untied head), with the MLP
replaced by a sparse mixture of 8 SiLU-gated experts: a linear router over the
normed hidden state, the 2 largest logits kept, softmax over those 2, and the
weighted sum of the chosen experts' outputs. Nothing is cut: 32 layers, every
width as published (the comparison itself runs ``reference_check.layers`` of
them, so that the float32 copy fits beside the int8 weights; every layer is
the same kind). Departures: none in the mathematics; the weights are the
served int8 weights dequantized to float32. An expert's output enters a
token's sum with weight 0 unless it is one of the token's 2. This file is this
configuration's own copy: the equations are those of the six-layer
configuration's reference, because the block is the same.

``aux["router_top_gap"]`` [T] is, per token, the smallest gap over the layers
between the largest and the 2nd largest router logit. The two chosen experts
are mixed by softmax over their two logits, so by weights sigmoid(+-gap): where
the gap is small, the weights move by up to a quarter of any rounding of a
router logit, and the 2nd expert, whose place the 3rd may take at a near-tie,
carries real weight. Where it is large the 2nd expert's weight is e^-gap and
neither matters. The comparison holds every position with a clear gap to the
tolerance one by one, and the others as a group (refcheck.py).
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x [T, heads, hd]; pairs dim i with dim i + hd/2 (HF convention)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(cfg, lw, x):
    T = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    pos = jnp.arange(T)
    q = rope((x @ lw["wq"]).reshape(T, H, hd), pos, cfg["rope_theta"])
    k = rope((x @ lw["wk"]).reshape(T, KV, hd), pos, cfg["rope_theta"])
    v = (x @ lw["wv"]).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, H * hd) @ lw["wo"]


def sparse_moe(cfg, lw, x):
    """x [T, D] -> ([T, D], gap between the two largest router logits [T])."""
    k = cfg["num_experts_per_tok"]
    logits = x @ lw["router"]                                   # [T, E]
    top_vals, top_idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top_vals, axis=-1)                  # over the chosen k
    out = jnp.zeros_like(x)
    for e in range(cfg["num_local_experts"]):
        w_e = jnp.sum(jnp.where(top_idx == e, weights, 0.0), axis=-1)   # [T]
        y = (jax.nn.silu(x @ lw["w_gate"][e]) * (x @ lw["w_up"][e])) @ lw["w_down"][e]
        out = out + w_e[:, None] * y
    return out, top_vals[:, 0] - top_vals[:, 1]


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux)."""
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens]
        gap = jnp.full(tokens.shape, jnp.inf, jnp.float32)
        for lw in weights["layers"]:
            h = h + attention(cfg, lw, rms_norm(h, lw["attn_norm"], cfg["rms_norm_eps"]))
            y, g = sparse_moe(cfg, lw, rms_norm(h, lw["mlp_norm"], cfg["rms_norm_eps"]))
            h = h + y
            gap = jnp.minimum(gap, g)
        h = rms_norm(h, weights["final_norm"], cfg["rms_norm_eps"])
        return h @ weights["lm_head"], {"router_top_gap": gap}
