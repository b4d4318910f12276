"""Plain reference for mistral-small-4-119b-2603-l9: the language model's
forward pass in straightforward float32 jax.numpy — no kernels, no cache, no
batching, no absorption of projections, no grouping of tokens by expert.

Follows the published configuration (config.json beside this file,
``model_type: mistral4``; the DeepSeek-V2/V3 family's latent attention and
router are the published description of what its keys mean). Every layer is the
same (``first_k_dense_replace`` 0; ``intermediate_size`` 12288 is the width of a
dense MLP no layer has). ``x`` is [T, 4096], one sequence:

    h      = RMSNorm(x)
    c_q    = RMSNorm(h W_dq)                      W_dq  [4096, 1024]
    q      = c_q W_uq  -> 32 heads x (64 nope | 64 rope)      W_uq [1024, 4096]
    [c|kr] = h W_dkv                              W_dkv [4096, 256 + 64]
    c      = RMSNorm(c)                           (the 256 latent lanes only)
    kr     = RoPE(kr),  q_rope = RoPE(q_rope)     ONE rope key a token, shared by all heads;
                                                  pairs interleaved (rope_interleave), YaRN frequencies
    expanded:  [k_nope_h | v_h] = c W_ukv         W_ukv [256, 32 x (64 + 128)]
               s_h = (q_nope_h . k_nope_h + q_rope_h . kr) * scale * g(pos_q)
               o_h = softmax_causal(s_h) v_h ;  out = concat_h(o_h) W_o      W_o [4096, 4096]
    absorbed:  qt_h = q_nope_h W_uk_h^T (256) ;  s_h = (qt_h . c + q_rope_h . kr) * scale * g(pos_q)
               ot_h = softmax_causal(s_h) c (256) ;  o_h = ot_h W_uv_h (128)   -- same numbers
    scale  = 128^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1 = 1.4852   (DeepSeek-family YaRN;
             cos/sin factor mscale/mscale_all_dim = 1)
    g(pos) = 1 + llama_4_scaling_beta * ln(1 + floor(pos / 8192))               (query scale)
    x      = x + out ;  h2 = RMSNorm(x)
    p      = softmax(h2 W_r) over 128, float32 ; pick top-4 ; weights renormalised (norm_topk_prob) x 1.0
    x      = x + sum_{e picked AND held here} w_e W_down_e( silu(W_gate_e h2) * W_up_e h2 )     experts [4096, 2048]
               + W_down_s( silu(W_gate_s h2) * W_up_s h2 )                                       shared, 2048

This file computes the EXPANDED form (the program serves the absorbed one; tests/
test_latent_attention.py holds the two together), one head at a time so that a
long sequence's [T, T] scores stay one head's. A final RMSNorm, then the untied
head.

The cut, in program and reference alike: ``num_hidden_layers`` of the 36, and
of the 128 routed experts the ``n_routed_experts`` (32) this chip of the stated
deployment holds, from ``first_routed_expert`` on. The router scores all
``n_routed_experts_scored`` (128) and picks 4 among ALL of them; a pick of an
expert held on another chip adds nothing here and the partial sum goes on to the
next layer; nothing stands in for the other chips or their exchange. The shared
expert, attention, router and vocabulary are whole.

Conventions the published config does not name, each under ``assumed`` in the
file: the router (softmax scores, the 4 largest, no selection bias, one group);
YaRN's ramp and the two scale formulas above; the rotary pairs (2i, 2i+1), the
result left with the pairs' first members in its first half (queries and keys
alike, so every product is the interleaved rotation's); weights are the served
int8 weights dequantised to float32 (``W_ukv`` is served in bf16).

What ``aux`` says of each position, for the comparison's rule (refcheck.py holds
``clear`` positions one by one and the others as a group, by their median, both
to the one ``tolerance_rel``): ``aux["clear_score"]`` [T] is the position's own
selection margin — the gap between its 4th and 5th largest router logits, the
least over the layers — over SENTINEL_MARGIN_MIN, at every SENTINEL_EVERY-th
token of the sequence (127, 255, ...), 0 elsewhere. Where the margin is small,
bf16 activations and this float32 pass pick different experts with nothing
wrong, and the token's output moves by most of an expert's (a held one's, one
pick in four): one position in a hundred reads five times the median. So, as in
the other files with experts, a few SENTINELS with a clear router are held one
by one and everything else as a group, by its median. The sentinels are spread
over the sequence and not its first tokens: a token with few rows before it
reads half again the error of one with thousands (nothing averages the rounding
of its one or two keys; measured by position, the file's ``tolerance_why``), and
what this model adds is attention over long contexts.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL_EVERY = 128
SENTINEL_MARGIN_MIN = 0.05


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def f32(leaf):
    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg):
    """[rope/2] float32: a pair that turns more than beta_fast times over the
    trained positions keeps its frequency, one that turns fewer than beta_slow
    times has it divided by ``factor``, a linear ramp over the pair index
    between (bounds truncated to whole pairs)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    factor, orig = float(cfg["factor"]), cfg["original_max_position_embeddings"]
    freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return (1.0 / freqs).astype(np.float32)
    turn = lambda rot: dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turn(cfg["beta_fast"])), 0)
    high = min(math.ceil(turn(cfg["beta_slow"])), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return ((1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1.0 - ramp)).astype(np.float32)


def rope(cfg, x, pos):
    """x [T, ..., rope] rotated by its position: pairs (2i, 2i+1) when
    rope_interleave; cos and sin times mscale / mscale_all_dim."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(yarn_inv_freq(cfg))
    factor = (mscale(cfg["factor"], cfg["mscale"])
              / mscale(cfg["factor"], cfg["mscale_all_dim"]))
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = (jnp.cos(ang) * factor).reshape(shape), (jnp.sin(ang) * factor).reshape(shape)
    if cfg["rope_interleave"]:
        a, b = x[..., 0::2], x[..., 1::2]
    else:
        a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(cfg, lw, h):
    T = h.shape[0]
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    N, R, V = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps, pos = cfg["rms_norm_eps"], jnp.arange(T)
    q = (rms_norm(h @ lw["w_dq"], lw["dq_norm"], eps) @ lw["w_uq"]).reshape(T, H, N + R)
    ckr = h @ lw["w_dkv"]
    c = rms_norm(ckr[:, :C], lw["dkv_norm"], eps)
    kr = rope(cfg, ckr[:, C:], pos)                             # [T, R]: one key for all heads
    q_rope = rope(cfg, q[..., N:], pos)                         # [T, H, R]
    kv = (c @ lw["w_ukv"]).reshape(T, H, N + V)                 # expanded
    scale = (N + R) ** -0.5 * mscale(cfg["factor"], cfg["mscale_all_dim"]) ** 2
    g = 1.0 + cfg["llama_4_scaling_beta"] * jnp.log1p(
        jnp.floor(pos / cfg["original_max_position_embeddings"]))
    causal = pos[:, None] >= pos[None, :]

    def head(xs):
        q_nope_h, q_rope_h, k_nope_h, v_h = xs
        s = (q_nope_h @ k_nope_h.T + q_rope_h @ kr.T) * scale * g[:, None]
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v_h

    heads = lambda a: jnp.swapaxes(a, 0, 1)                     # [H, T, .]
    o = jax.lax.map(head, (heads(q[..., :N]), heads(q_rope), heads(kv[..., :N]),
                           heads(kv[..., N:])))                 # [H, T, V]
    return jnp.swapaxes(o, 0, 1).reshape(T, H * V) @ lw["wo"]


def experts(cfg, lw, x):
    """x [T, D] -> ([T, D], the selection margin [T]): the held experts' part
    of the routed sum, plus the shared expert."""
    k = cfg["num_experts_per_tok"]
    first = cfg.get("first_routed_expert", 0)
    logits = x @ lw["router"]                                   # [T, all experts]
    top, idx = jax.lax.top_k(logits, k + 1)
    picked = jax.nn.softmax(logits, axis=-1)
    picked = jnp.take_along_axis(picked, idx[:, :k], axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    idx = idx[:, :k]

    def add_expert(out, expert):
        e, (gq, gs), (uq, us), (dq, ds) = expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)      # [T]
        gate, up, down = (a.astype(jnp.float32) * s for a, s in ((gq, gs), (uq, us), (dq, ds)))
        return out + w_e[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), None

    held = lw["w_up"]["q"].shape[0]
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(held), *((lw[n]["q"], lw[n]["scale"]) for n in ("w_gate", "w_up", "w_down"))))
    shared = (jax.nn.silu(x @ lw["shared_gate"]) * (x @ lw["shared_up"])) @ lw["shared_down"]
    return out + shared, top[:, k - 1] - top[:, k]


#: the leaves of a layer that are dequantised whole
WHOLE = ("attn_norm", "w_dq", "dq_norm", "w_uq", "w_dkv", "dkv_norm", "w_ukv", "wo",
         "mlp_norm", "router", "shared_gate", "shared_up", "shared_down")


def weights_from_program(params, n_layers):
    """The served weights as this file wants them: a list of layers, each
    leaf dequantised to float32 — but for the held routed experts, which stay
    int8 payload and scales ({"q", "scale"}: [held, in, out], [held, 1, out])
    and are dequantised one expert at a time inside ``experts``."""
    layers = params["layers"]
    at = lambda leaf, i: jax.tree_util.tree_map(lambda a: a[i], leaf)
    out = []
    for i in range(n_layers):
        lw = {name: f32(at(layers[name], i)) for name in WHOLE}
        for name in ("w_gate", "w_up", "w_down"):
            one = at(layers[name], i)
            q = getattr(one, "q", one)          # a float tree (the tests') has no scales
            scale = (one.scale.astype(jnp.float32) if hasattr(one, "scale")
                     else jnp.ones((q.shape[0], 1, q.shape[2]), jnp.float32))
            lw[name] = {"q": q, "scale": scale}
        out.append(lw)
    return {"embed": f32(params["embed"]), "final_norm": f32(params["final_norm"]),
            "lm_head": f32(params["lm_head"]), "layers": out}


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = weights["embed"][tokens]
        margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
        for lw in weights["layers"]:
            x = x + attention(cfg, lw, rms_norm(x, lw["attn_norm"], eps))
            y, m = experts(cfg, lw, rms_norm(x, lw["mlp_norm"], eps))
            x, margin = x + y, jnp.minimum(margin, m)
        x = rms_norm(x, weights["final_norm"], eps)
        seen = jnp.arange(1, tokens.shape[0] + 1)
        return x @ weights["lm_head"], {
            "clear_score": jnp.where(seen % SENTINEL_EVERY == 0,
                                     margin / SENTINEL_MARGIN_MIN, 0.0),
            "margin": margin}
