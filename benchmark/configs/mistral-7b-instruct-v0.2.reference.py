"""Plain reference for mistral-7b-instruct-v0.2: the decoder's forward pass in
straightforward float32 jax.numpy — no kernels, no cache, no batching.

Follows the published architecture (MistralForCausalLM, config.json beside
this file): token embedding; per layer RMSNorm -> grouped-query attention with
rotary embeddings (split-halves pairing, theta from the config, no sliding
window: the v0.2 config sets it null) -> residual -> RMSNorm -> SiLU-gated MLP
-> residual; final RMSNorm; untied output head. Departures: none in the
mathematics; the weights are the served int8 weights dequantized to float32
(the configuration states int8 weights), so the comparison isolates the
program's arithmetic, cache and kernels.
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
    k = jnp.repeat(k, H // KV, axis=1)          # each KV head serves H/KV query heads
    v = jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, H * hd) @ lw["wo"]


def mlp(cfg, lw, x):
    return (jax.nn.silu(x @ lw["w_gate"]) * (x @ lw["w_up"])) @ lw["w_down"]


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux).
    ``weights``: float32 arrays; ``weights["layers"]`` is a list of dicts."""
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens]
        for lw in weights["layers"]:
            h = h + attention(cfg, lw, rms_norm(h, lw["attn_norm"], cfg["rms_norm_eps"]))
            h = h + mlp(cfg, lw, rms_norm(h, lw["mlp_norm"], cfg["rms_norm_eps"]))
        h = rms_norm(h, weights["final_norm"], cfg["rms_norm_eps"])
        return h @ weights["lm_head"], {}
