"""Plain reference for nemotron-3-nano-30b-a3b-l13: the language model's
forward pass in straightforward float32 jax.numpy — no kernels, no cache, no
batching, no chunking of the recurrence, no grouping of tokens by expert.

Follows the published configuration (config.json beside this file,
``model_type: nemotron_h``; the family's modelling code is the published
description). Layer l is ONE mixer, ``h <- h + mixer_l(RMSNorm(h))``, of the
kind the l-th character of ``hybrid_override_pattern`` names (the first
``num_hidden_layers`` characters where the pattern is longer); no MLP follows a
mixer; a final RMSNorm, then the untied head. One sequence, x = RMSNorm(h):

- ``M`` (Mamba-2): [z | xBC | dt] = x W_in, with d_inner = mamba_num_heads x
  mamba_head_dim and xBC = d_inner + 2 x n_groups x ssm_state_size wide; xBC <-
  silu(causal depthwise conv over the last conv_kernel tokens + b); split into
  x [heads, head_dim], B and C [n_groups, ssm_state_size] (a group's heads share
  them); dt <- softplus(dt + dt_bias); A = -exp(A_log) a head; THE RECURRENCE,
  TOKEN BY TOKEN (a lax.scan over the sequence): h_t = exp(dt_t A) h_{t-1} +
  dt_t x_t (x) B_t, y_t = C_t . h_t + D x_t; y <- RMSNorm over each of the
  n_groups slices of y * silu(z), times a gain; out y W_out. No projection bias.
- ``E``: s = sigmoid(x W_r) over the n_routed_experts; the num_experts_per_tok
  largest of s + bias are picked (the bias enters nothing else); their weights
  are the picked s over their sum (norm_topk_prob), times routed_scaling_factor;
  an expert is relu(x W_up)^2 W_down (mlp_hidden_act relu2: two matrices, no
  gate); plus the shared expert, the same form at
  moe_shared_expert_intermediate_size, for every token. Every expert is
  evaluated for every token, one after the other (a scan over the experts that
  dequantises ONE expert's int8 weights at a time: two layers' experts in
  float32 would be 10 GB), and enters a token's sum with weight 0 unless picked.
- ``*``: grouped-query attention over every key s <= t, no bias and NO ROTARY
  EMBEDDING (``assumed.attention_positions`` in the file: the family's attention
  layers take their positions from the state-space layers; rope_theta is carried
  and used by nothing).

Departures and conventions, each also under ``assumed`` in the file: depth is the
only cut; the weights are the served int8 weights dequantised to float32; the
step biases, decays and convolution are the seeded generator's
(models/transformer.py::small_leaf_init: Mamba-2's initialisation).

What ``aux`` says of each position, for the comparison's rule (refcheck.py holds
``clear`` positions one by one and the others as a group, by their median):
``aux["clear_score"]`` [T] is the selection margin — the gap between the k-th and
the (k+1)-th largest of s + bias, in router-logit units (over the slope s (1 - s)
at the k-th), the least over the expert layers AND OVER EVERY TOKEN UP TO THIS
ONE — over SENTINEL_MARGIN_MIN, for the first SENTINEL_POSITIONS tokens of the
sequence, 0 after them. Where a token's margin is small, bf16 activations and
this float32 pass pick different experts with nothing wrong (the k-th and
(k+1)-th of 128 scores lie 0.08 logits apart on average, one token-layer in ten
under 0.01), and the token's output moves by most of an expert's; a state-space
layer then carries the difference to every later position of the sequence. So a
position can be held one by one only while no token so far had a small margin
(a sequence's clear prefix: none for three sequences in five, else 1-2 tokens:
the comparison runs 24 sequences so that some have one), and everything
else is held as a group, by its median.
"""

import jax
import jax.numpy as jnp

SENTINEL_POSITIONS = 16
SENTINEL_MARGIN_MIN = 0.04


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def f32(leaf):
    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def mamba2(cfg, lw, x):
    """x [T, D] -> [T, D]: one Mamba-2 mixer, the recurrence step by step."""
    T = x.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
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


def experts(cfg, lw, x):
    """x [T, D] -> ([T, D], the selection margin [T])."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ lw["router"])                         # [T, E]
    chosen = s + lw["router_bias"][None, :]
    top, idx = jax.lax.top_k(chosen, k + 1)
    idx = idx[:, :k]
    picked = jnp.take_along_axis(s, idx, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]

    def add_expert(out, expert):
        e, up_q, up_s, down_q, down_s = expert
        w_e = jnp.sum(jnp.where(idx == e, weights, 0.0), axis=-1)             # [T]
        up = up_q.astype(jnp.float32) * up_s
        down = down_q.astype(jnp.float32) * down_s
        return out + w_e[:, None] * (jnp.square(jax.nn.relu(x @ up)) @ down), None

    up, down = lw["w_up"], lw["w_down"]
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          (jnp.arange(up["q"].shape[0]), up["q"], up["scale"],
                           down["q"], down["scale"]))
    shared = jnp.square(jax.nn.relu(x @ lw["shared_up"])) @ lw["shared_down"]
    s_k = jnp.take_along_axis(s, idx[:, k - 1:k], axis=-1)[:, 0]
    return out + shared, (top[:, k - 1] - top[:, k]) / (s_k * (1.0 - s_k))


def attention(cfg, lw, x):
    T = x.shape[0]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (x @ lw["wq"]).reshape(T, H, hd)
    k = jnp.repeat((x @ lw["wk"]).reshape(T, KV, hd), H // KV, axis=1)
    v = jnp.repeat((x @ lw["wv"]).reshape(T, KV, hd), H // KV, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) * hd ** -0.5
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(T, H * hd) @ lw["wo"]


def weights_from_program(params, n_layers):
    """The served weights as this file wants them: a list of layers in the
    pattern's order, each holding its kind's leaves taken from the program's
    per-kind stacks (layer j of a kind is that kind's j-th layer), dequantised
    to float32 — but for the routed experts, which stay int8 payload and scales
    ({"q", "scale"}, [E, in, out] and [E, 1, out]) and are dequantised one
    expert at a time inside ``experts``. The call carries no configuration, so
    the kinds of the ``n_layers`` layers are the pattern's whose counts the
    stacks' leading axes show (``_kinds_from_counts``)."""
    layers = params["layers"]
    lead = lambda name: (getattr(layers[name], "q", layers[name]).shape[0]
                         if name in layers else 0)
    kinds = _kinds_from_counts(
        {"M": lead("ssm_in"), "E": lead("w_up"), "*": lead("wq")}, n_layers)
    at = lambda leaf, j: jax.tree_util.tree_map(lambda a: a[j], leaf)
    names = {"M": [k for k in layers if k.startswith("ssm_")],
             "E": ["mlp_norm", "router", "router_bias", "shared_up", "shared_down"],
             "*": ["attn_norm", "wq", "wk", "wv", "wo"]}
    out, seen = [], {"M": 0, "E": 0, "*": 0}
    for kind in kinds:
        j = seen[kind]
        seen[kind] += 1
        lw = {name: f32(at(layers[name], j)) for name in names[kind]}
        if kind == "E":
            for name in ("w_up", "w_down"):
                one = at(layers[name], j)
                q = getattr(one, "q", one)      # a float tree (the tests') has no scales
                scale = (one.scale.astype(jnp.float32) if hasattr(one, "scale")
                         else jnp.ones((q.shape[0], 1, q.shape[2]), jnp.float32))
                lw[name] = {"q": q, "scale": scale}
        lw["kind"] = kind
        out.append(lw)
    return {"embed": f32(params["embed"]), "final_norm": f32(params["final_norm"]),
            "lm_head": f32(params["lm_head"]), "layers": out}


#: the published pattern; a cut in depth keeps its first characters
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _kinds_from_counts(n_of, n_layers):
    """The first ``n_layers`` kinds of the published pattern (or of a toy's
    ``ME*`` period), checked against how many layers of each kind the
    program's stacks hold."""
    for pat in (PUBLISHED_PATTERN, "ME*" * 16):
        kinds = pat[:n_layers]
        if all(kinds.count(k) == n for k, n in n_of.items()):
            return kinds
    raise ValueError(f"no known pattern gives {n_of} in {n_layers} layers")


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = weights["embed"][tokens]
        T = tokens.shape[0]
        margin = jnp.full((T,), jnp.inf, jnp.float32)
        for lw in weights["layers"]:
            if lw["kind"] == "M":
                h = h + mamba2(cfg, lw, rms_norm(h, lw["ssm_in_norm"], eps))
            elif lw["kind"] == "E":
                y, m = experts(cfg, lw, rms_norm(h, lw["mlp_norm"], eps))
                h, margin = h + y, jnp.minimum(margin, m)
            else:
                h = h + attention(cfg, lw, rms_norm(h, lw["attn_norm"], eps))
        h = rms_norm(h, weights["final_norm"], eps)
        seen = jnp.arange(1, T + 1, dtype=jnp.float32)
        so_far = jax.lax.cummin(margin, axis=0)
        return h @ weights["lm_head"], {
            "clear_score": jnp.where(seen <= SENTINEL_POSITIONS,
                                     so_far / SENTINEL_MARGIN_MIN, 0.0),
            "margin": margin}
