"""Plain reference for olmo-hybrid-7b: the language model's forward pass in
straightforward float32 jax.numpy — no kernels, no cache, no batching, no
chunked form of the recurrence.

Follows the published configuration (config.json beside this file,
``model_type: olmo_hybrid``). What the config's keys cannot say is under
``assumed`` in that file with its reason; program and reference agree on it.

A layer is a token mixer then an MLP, each normed on its OUTPUT and not on its
input (the Olmo 2 / Olmo 3 block): ``h = x + RMSNorm(mixer(x))``,
``y = h + RMSNorm(MLP(h))``, ``MLP(h) = W_d (silu(h W_g) * (h W_u))``; a final
RMSNorm, then the untied head. ``layer_types`` names the mixer of each layer:

- ``linear_attention`` (the gated delta rule; the keys ``linear_*`` are those of
  flash-linear-attention's GatedDeltaNet), H = linear_num_value_heads heads of
  d_k = linear_key_head_dim, d_v = linear_value_head_dim, per token t:
  [q | k | v] = silu(conv(x [W_q | W_k | W_v])), a causal depthwise convolution
  of linear_conv_kernel_dim taps over all channels, no bias;
  q^ = q / ||q||_2 / sqrt(d_k), k^ = k / ||k||_2 (per head; the norm is
  sqrt(sum x^2 + 1e-6), which keeps an all-zero row at zero);
  beta = 2 sigmoid(x W_b) (the 2 because linear_allow_neg_eigval), g =
  -exp(A_log) softplus(x W_a + dt_bias), alpha = exp(g); THE RECURRENCE, TOKEN
  BY TOKEN (a lax.scan over the sequence) on a state S [d_k, d_v] a head:
  u_t = beta_t (v_t - alpha_t S_{t-1}^T k^_t), S_t = alpha_t S_{t-1} + k^_t u_t^T,
  o_t = S_t^T q^_t; out = (RMSNorm_{d_v}(o_t) * w * silu(x W_g)) W_o: the norm
  first, the gate after.
- ``full_attention``: q = RMSNorm(x W_q), k = RMSNorm(x W_k), each over the WHOLE
  projection before the head split; v = x W_v; causal softmax attention at
  head_dim^-0.5 over every key s <= t; NO ROTARY EMBEDDING (rope_parameters.
  rope_theta is null: nothing to rotate by); W_o.

The weights are the served int8 weights dequantised to float32; the decays,
step biases, W_a, W_b and the convolution are the seeded generator's
(models/transformer.py::small_leaf_init). The model makes no discrete choice,
but a linear layer's per-head norm is ill-conditioned where the state holds
one or two keys: a sequence's first read-outs are ``o_0 = (k^_0 . q^_0) u_0``,
a vector times the product of two unit vectors that may be arbitrarily near
zero, and the norm divides by it (bf16 rounding then decides its sign).
``aux["position"]`` is each token's place in the sequence, for the
configuration's ``clear_if`` to set a sequence's first tokens apart.
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def f32(leaf):
    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def gated_delta(cfg, lw, x):
    """x [T, D] -> [T, D]: one linear-attention mixer, the recurrence step by step."""
    T = x.shape[0]
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    K = cfg["linear_conv_kernel_dim"]
    C = 2 * H * dk + H * dv
    qkvz = x @ lw["lin_in"]
    qkv, z = qkvz[:, :C], qkvz[:, C:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), qkv])
    qkv = jax.nn.silu(sum(padded[k:k + T] * lw["lin_conv_w"][k][None, :] for k in range(K)))
    q = l2_normalize(qkv[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = l2_normalize(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    beta = (2.0 if cfg["linear_allow_neg_eigval"] else 1.0) * jax.nn.sigmoid(x @ lw["lin_wb"])
    alpha = jnp.exp(-jnp.exp(lw["lin_A_log"])[None, :]
                    * jax.nn.softplus(x @ lw["lin_wa"] + lw["lin_dt_bias"][None, :]))

    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t
        u = b_t[:, None] * (v_t - a_t[:, None] * jnp.einsum("hkv,hk->hv", S, k_t))
        S = a_t[:, None, None] * S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = o * lw["lin_gate_norm"][None, None, :] * jax.nn.silu(z.reshape(T, H, dv))
    return o.reshape(T, H * dv) @ lw["lin_out"]


def attention(cfg, lw, x):
    T = x.shape[0]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm(x @ lw["wq"], lw["q_norm"], eps).reshape(T, H, hd)
    k = jnp.repeat(rms_norm(x @ lw["wk"], lw["k_norm"], eps).reshape(T, KV, hd), H // KV, axis=1)
    v = jnp.repeat((x @ lw["wv"]).reshape(T, KV, hd), H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, H * hd) @ lw["wo"]


def mlp(lw, h):
    return (jax.nn.silu(h @ lw["dense_gate"]) * (h @ lw["dense_up"])) @ lw["dense_down"]


#: a layer's mixer, in the published order (``layer_types``): three linear, one full
PERIOD = ("linear_attention", "linear_attention", "linear_attention", "full_attention")


def weights_from_program(params, n_layers):
    """The served weights as this file wants them: a list of ``n_layers`` layers
    in the published order, each its mixer's leaves and its MLP's, taken from the
    program's per-kind stacks (layer j of a kind is that kind's j-th layer) and
    dequantised to float32."""
    layers = params["layers"]
    at = lambda name, j: f32(jax.tree_util.tree_map(lambda a: a[j], layers[name]))
    names = {"linear_attention": [k for k in layers if k.startswith("lin_")],
             "full_attention": ["attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm"]}
    out, seen = [], dict.fromkeys(names, 0)
    for i in range(n_layers):
        kind = PERIOD[i % len(PERIOD)]
        lw = {name: at(name, seen[kind]) for name in names[kind]}
        lw.update({name: at(name, i) for name in layers if name.startswith("dense_")})
        lw["kind"] = kind
        seen[kind] += 1
        out.append(lw)
    return {"embed": f32(params["embed"]), "final_norm": f32(params["final_norm"]),
            "lm_head": f32(params["lm_head"]), "layers": out}


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = weights["embed"][tokens]
        for lw in weights["layers"]:
            if lw["kind"] == "linear_attention":
                h = h + rms_norm(gated_delta(cfg, lw, h), lw["lin_norm"], eps)
            else:
                h = h + rms_norm(attention(cfg, lw, h), lw["attn_norm"], eps)
            h = h + rms_norm(mlp(lw, h), lw["dense_norm"], eps)
        logits = rms_norm(h, weights["final_norm"], eps) @ weights["lm_head"]
        return logits, {"position": jnp.arange(tokens.shape[0])}
