"""Plain reference for granite-4.0-h-micro: the language model's forward pass in
straightforward float32 jax.numpy — no kernels, no cache, no batching, no
chunked form of the recurrence, no scan over the layers.

Follows the published configuration (config.json beside this file,
``model_type: granitemoehybrid``, no experts: ``num_local_experts`` 0, so every
layer's MLP is the dense one of ``shared_intermediate_size``). What the config's
keys cannot say is under ``assumed`` in that file with its reason; program and
reference agree on it. With r = ``residual_multiplier``, one sequence:

    x_0 = embedding_multiplier * E[token]
    a   = x + r * mixer_l(RMSNorm(x))              l = 0 .. num_hidden_layers - 1
    y   = a + r * W_d (silu(n W_g) * (n W_u)),     n = RMSNorm(a)
    logits = RMSNorm(y_last) E^T / logits_scaling  (the head is the embedding)

``layer_types`` names the mixer of each layer:

- ``mamba`` (Mamba-2, the Bamba mixer): [z | xBC | dt] = x W_in with d_inner =
  mamba_n_heads x mamba_d_head and xBC = d_inner + 2 x mamba_n_groups x
  mamba_d_state wide; xBC <- silu(causal depthwise conv over the last
  mamba_d_conv tokens + b); split into x [heads, d_head], B and C [groups,
  d_state] (ONE group: every head shares them); dt <- softplus(dt + dt_bias), no
  clamp; A = -exp(A_log) a head; THE RECURRENCE, TOKEN BY TOKEN (a lax.scan over
  the sequence): h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = C_t . h_t +
  D x_t; y <- RMSNorm over each group's slice of y * silu(z) (one group: all of
  d_inner; the gate first), times a gain; out y W_out. No projection bias.
  mamba_chunk_size tiles a chunked scan and is read by nothing here.
- ``attention``: grouped-query attention over every key s <= t, no bias, NO
  POSITIONAL EMBEDDING (position_embedding_type ``nope``), the scores times
  ``attention_multiplier`` (0.015625 = 1/64, NOT head_dim ** -0.5 = 1/8), W_o.

Departures and conventions, each also under ``assumed`` in the file: nothing is
cut; the weights are the served int8 weights dequantised to float32 (the tied
embedding by its per-row scales); the step biases, decays and convolution are
the seeded generator's (models/transformer.py::small_leaf_init: Mamba-2's
initialisation). The model makes no discrete choice, so ``aux`` is empty and
every position is held one by one.

``LEAVE_OUT`` (a set of the four multipliers' keys) reads that multiplier as 1,
or the attention's as head_dim ** -0.5: tests/test_ssm_dense_hybrid.py holds
that each of the four, left out, fails the comparison's tolerance.
"""

import jax
import jax.numpy as jnp

#: multipliers to read as absent (the tests' only)
LEAVE_OUT = frozenset()


def multiplier(cfg, key, absent=1.0):
    return absent if key in LEAVE_OUT else cfg[key]


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def f32(leaf):
    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def mamba2(cfg, lw, x):
    """x [T, D] -> [T, D]: one Mamba-2 mixer, the recurrence step by step."""
    T = x.shape[0]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, K = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    di = H * P
    zxd = x @ lw["ssm_in"]
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + di + 2 * G * N], zxd[:, 2 * di + 2 * G * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[k:k + T] * lw["ssm_conv_w"][k][None, :] for k in range(K))
    xbc = jax.nn.silu(conv + lw["ssm_conv_b"][None, :])
    xs = xbc[:, :di].reshape(T, H, P)
    Bm = jnp.repeat(xbc[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)   # [T, H, N]
    Cm = jnp.repeat(xbc[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lw["ssm_dt_bias"][None, :])                      # [T, H]
    A = -jnp.exp(lw["ssm_A_log"])                                              # [H]

    def step(h, t):
        x_t, dt_t, b_t, c_t = t
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xs, dt, Bm, Cm))
    y = (y + lw["ssm_D"][None, :, None] * xs).reshape(T, di)
    g = (y * jax.nn.silu(z)).reshape(T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    return (g.reshape(T, di) * lw["ssm_gate_norm"][None, :]) @ lw["ssm_out"]


def attention(cfg, lw, x):
    T = x.shape[0]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ lw["wq"]).reshape(T, H, hd)
    k = jnp.repeat((x @ lw["wk"]).reshape(T, KV, hd), H // KV, axis=1)
    v = jnp.repeat((x @ lw["wv"]).reshape(T, KV, hd), H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * multiplier(
        cfg, "attention_multiplier", hd ** -0.5)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, H * hd) @ lw["wo"]


def mlp(lw, n):
    return (jax.nn.silu(n @ lw["dense_gate"]) * (n @ lw["dense_up"])) @ lw["dense_down"]


#: the published layer_types as one character a layer (M mamba, * attention); a
#: toy's period beside it (models/config.py::toy-ssm-dense)
PUBLISHED_MIXERS = ("MMMMM*MMMM" * 4, "MM*MM" * 8)


def weights_from_program(params, n_layers):
    """The served weights as this file wants them: a list of ``n_layers`` layers
    in the published order, each its mixer's leaves and its MLP's taken from
    the program's per-kind stacks (layer j of a kind is that kind's j-th
    layer), dequantised to float32, and the embedding by its per-row scales
    (it is the head too). The call carries no configuration, so the mixers are
    the pattern's whose counts the stacks' leading axes show."""
    layers = params["layers"]
    lead = lambda name: (getattr(layers[name], "q", layers[name]).shape[0]
                         if name in layers else 0)
    for pattern in PUBLISHED_MIXERS:
        kinds = pattern[:n_layers]
        if (len(kinds) == n_layers and kinds.count("M") == lead("ssm_in")
                and kinds.count("*") == lead("wq")):
            break
    else:
        raise ValueError(f"no known pattern gives {lead('ssm_in')} Mamba and "
                         f"{lead('wq')} attention layers in {n_layers}")
    at = lambda leaf, j: jax.tree_util.tree_map(lambda a: a[j], leaf)
    names = {"M": [k for k in layers if k.startswith("ssm_")],
             "*": ["attn_norm", "wq", "wk", "wv", "wo"]}
    dense = [k for k in layers if k.startswith("dense_")]
    out, seen = [], {"M": 0, "*": 0}
    for l, kind in enumerate(kinds):
        j = seen[kind]
        seen[kind] += 1
        lw = {name: f32(at(layers[name], j)) for name in names[kind]}
        lw.update({name: f32(at(layers[name], l)) for name in dense})
        lw["kind"] = kind
        out.append(lw)
    return {"embed": f32(params["embed"]), "final_norm": f32(params["final_norm"]),
            "layers": out}


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        r = multiplier(cfg, "residual_multiplier")
        h = multiplier(cfg, "embedding_multiplier") * weights["embed"][tokens]
        for lw in weights["layers"]:
            if lw["kind"] == "M":
                h = h + r * mamba2(cfg, lw, rms_norm(h, lw["ssm_in_norm"], eps))
            else:
                h = h + r * attention(cfg, lw, rms_norm(h, lw["attn_norm"], eps))
            h = h + r * mlp(lw, rms_norm(h, lw["dense_norm"], eps))
        h = rms_norm(h, weights["final_norm"], eps)
        return (h @ weights["embed"].T) / multiplier(cfg, "logits_scaling"), {}
