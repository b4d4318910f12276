"""Plain reference for qwen3-next-80b-a3b-instruct-l12: the forward pass in
straightforward float32 jax.numpy — no kernels, no cache, no batching, no
chunked form of the recurrence, no grouping of tokens by expert.

Follows the published configuration (config.json beside this file). What the
config's keys cannot say is under ``assumed`` in that file with its reason;
program and reference agree on it. ``x`` is [T, 2048], one sequence.
``N(x) = x / rms(x) * (1 + w)``, eps 1e-6 (the family's zero-centred norm). A
layer is a pre-norm block of two mixers, ``a = x + mixer(N(x))``,
``y = a + moe(N(a))``; after the last layer a final ``N``, then the untied head.

THE ORDER (``full_attention_interval`` 4, ``decoder_sparse_step`` 1,
``mlp_only_layers`` empty): layer l (0-based) is gated attention where
(l + 1) % 4 == 0, else the gated delta rule; every layer's MLP is experts.

- gated delta rule (flash-linear-attention's GatedDeltaNet, as the family's
  modelling code takes it), 16 KEY heads and 32 VALUE heads of 128:
  [q | k | v | z] = n W_in of 2,048 + 2,048 + 4,096 + 4,096; b = n W_b, a =
  n W_a [32 each]; [q | k | v] = silu(conv4([q | k | v])), a causal depthwise
  convolution of ``linear_conv_kernel_dim`` taps over the 8,192 channels, no
  bias; q^ = q / ||q|| / sqrt(128), k^ = k / ||k|| a KEY head (the norm is
  sqrt(sum x^2 + 1e-6)); VALUE HEAD h READS KEY HEAD h // 2; beta = sigmoid(b)
  in (0, 1); g = -exp(A_log_h) softplus(a + dt_bias_h), alpha = exp(g); THE
  RECURRENCE, TOKEN BY TOKEN (a lax.scan over the sequence) on a float32 state
  S [128, 128] a value head: u_t = beta_t (v_t - alpha_t S_{t-1}^T k^_t); S_t =
  alpha_t S_{t-1} + k^_t u_t^T; o_t = S_t^T q^_t; out = (RMSNorm_128(o_t) * w *
  silu(z)) W_out: the norm first, with a PLAIN gain, the gate after.
  DEPARTURES: the source interleaves W_in's columns by key-head group ([q_g |
  k_g | v_g | z_g] for group g) and fuses W_b and W_a into one W_ba; here the
  columns are [q | k | v | z] whole and W_b, W_a are two leaves. With weights
  from a seed the order is a convention (models/convert.py would permute a
  checkpoint's).
- gated attention, 16 query heads over 2 KV heads of 256: a head's columns of
  W_q are [q_h | gate_h] (2 x 256); k, v = n W_k, n W_v; q_h = N_256(q_h), k_h =
  N_256(k_h) (the (1 + w) norm a head); rotary on the first 64 lanes
  (``partial_rotary_factor`` 0.25), half-split pairs (i, i + 32), theta 1e7,
  no scaling; causal softmax(q k^T / 16) v; out = (attn * sigmoid(gate)) W_o,
  the gate lane by lane. No bias anywhere.
- experts (``moe_intermediate_size`` 512, gated silu): p = softmax(n W_r) over
  all ``num_experts_scored`` (512), float32; the ``num_experts_per_tok`` 10
  largest, renormalised to sum 1 (``norm_topk_prob``); the held experts' part of
  the routed sum, plus sigmoid(n w_s) * shared(n), ONE scalar a token on the
  shared expert (512 wide).

The cut, in program and reference alike: ``num_hidden_layers`` of the 48, and of
the 512 routed experts the ``num_experts`` (128) this chip of the stated
deployment holds, from ``first_routed_expert`` on. The router scores all 512 and
picks 10 among ALL of them; a pick of an expert held on another chip adds
nothing here and the partial sum goes on to the next layer; nothing stands in
for the other chips or their exchange. The multi-token head is not served.

The weights are the served int8 weights dequantised to float32 (W_a, W_b, the
shared expert's gate, the router, the convolution, A_log, dt_bias and the norm
gains are served in bf16 or float32: models/transformer.py::small_leaf_init).

What ``aux`` says of each position, for the comparison's rule (refcheck.py holds
``clear`` positions one by one and the others as a group, by their median, both
to the one ``tolerance_rel``). With 10 picks among 512 logits whose neighbours
near the cut lie ~0.04 apart, four expert layers deep, bf16 activations and this
float32 pass pick different experts at many positions with nothing wrong, and a
token's logits then move by an expert's worth — but ONLY where the pick that
changed is of an expert THIS CHIP HOLDS (ling-3.0-flash-vl-l12's reference has
the argument). ``held_margin`` gives, a layer, the least change of one router
logit against another's that would alter which HELD experts the token picks (a
held pick against the 11th largest logit, a held expert not picked against the
10th). ``aux["clear_score"]`` [T] is 1 where every expert layer's margin is at
least CLEAR_MIN at the position AND at least NEIGHBOUR_MIN at each of the
NEIGHBOURS tokens before it (a delta-rule layer's convolution has four taps and
its state has just been written), and 0 at a sequence's first two tokens (where
the state holds one or two keys and the per-head norm divides by the product of
two unit vectors, as olmo-hybrid-7b's file says). ``aux["margin"]`` [T, expert
layers] is what the rule read.
"""

import jax
import jax.numpy as jnp

#: a router logit against another's: for the position itself, and for each of
#: the NEIGHBOURS tokens before it
CLEAR_MIN, NEIGHBOUR_MIN, NEIGHBOURS = 0.05, 0.025, 3
#: the published order where the sizes handed in do not name it
FULL_ATTENTION_INTERVAL = 4


def norm(x, w, eps):
    """The zero-centred norm: x / rms(x) * (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def f32(leaf):
    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def order(cfg, n_layers):
    """The token mixers of the first ``n_layers`` layers, as published."""
    every = cfg.get("full_attention_interval", FULL_ATTENTION_INTERVAL)
    return ["attention" if (i + 1) % every == 0 else "delta" for i in range(n_layers)]


def delta_rule(cfg, lw, n):
    """n [T, D] (normed) -> [T, D]: one gated-delta-rule mixer, the recurrence
    step by step."""
    T = n.shape[0]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    K = cfg["linear_conv_kernel_dim"]
    C = 2 * Hk * dk + Hv * dv
    qkvz = n @ lw["lin_in"]
    qkv, z = qkvz[:, :C], qkvz[:, C:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), qkv])
    qkv = jax.nn.silu(sum(padded[k:k + T] * lw["lin_conv_w"][k][None, :] for k in range(K)))
    q = l2_normalize(qkv[:, :Hk * dk].reshape(T, Hk, dk)) * dk ** -0.5
    k = l2_normalize(qkv[:, Hk * dk:2 * Hk * dk].reshape(T, Hk, dk))
    # value head h reads key head h // (Hv // Hk)
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    v = qkv[:, 2 * Hk * dk:].reshape(T, Hv, dv)
    beta = jax.nn.sigmoid(n @ lw["lin_wb"])                              # [T, Hv]
    g = -jnp.exp(lw["lin_A_log"])[None, :] * jax.nn.softplus(
        n @ lw["lin_wa"] + lw["lin_dt_bias"][None, :])
    alpha = jnp.exp(g)                                                   # [T, Hv]

    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t
        u = b_t[:, None] * (v_t - a_t[:, None] * jnp.einsum("hkv,hk->hv", S, k_t))
        S = a_t[:, None, None] * S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((Hv, dk, dv), jnp.float32), (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = o * lw["lin_gate_norm"][None, None, :] * jax.nn.silu(z.reshape(T, Hv, dv))
    return o.reshape(T, Hv * dv) @ lw["lin_out"]


def rope(cfg, x, pos):
    """x [T, heads, hd]: the first ``partial_rotary_factor`` of the lanes rotated
    by position, pairs (i, i + rot/2); the rest pass."""
    hd = x.shape[-1]
    rot = int(hd * cfg.get("partial_rotary_factor", 1.0)) // 2 * 2
    inv = 1.0 / (float(cfg["rope_theta"]) ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def attention(cfg, lw, n):
    """n [T, D] (normed) -> [T, D]: gated attention, a head at a time."""
    T = n.shape[0]
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps, pos = cfg["rms_norm_eps"], jnp.arange(T)
    qg = (n @ lw["wq"]).reshape(T, H, 2 * hd)                   # a head: [q | gate]
    q = rope(cfg, norm(qg[..., :hd], lw["q_norm"], eps), pos)
    gate = jax.nn.sigmoid(qg[..., hd:])
    k = rope(cfg, norm((n @ lw["wk"]).reshape(T, KV, hd), lw["k_norm"], eps), pos)
    v = (n @ lw["wv"]).reshape(T, KV, hd)
    seen = pos[:, None] >= pos[None, :]

    def head(xs):
        q_h, kv_h = xs
        s = (q_h @ k[:, kv_h].T) * hd ** -0.5
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1) @ v[:, kv_h]

    o = jax.lax.map(head, (jnp.swapaxes(q, 0, 1), jnp.arange(H) // (H // KV)))  # [H, T, hd]
    return (jnp.swapaxes(o, 0, 1) * gate).reshape(T, H * hd) @ lw["wo"]


def held_margin(cfg, logits, first, held):
    """logits [T, scored] -> [T]: the least change of one logit against
    another's that would alter which of the experts held here are picked."""
    k = cfg["num_experts_per_tok"]
    top = jax.lax.top_k(logits, k + 1)[0]
    mine = logits[:, first:first + held]
    picked = mine >= top[:, k - 1:k]
    # a picked one leaves when the 11th passes it, another enters when it
    # passes the 10th
    return jnp.min(jnp.where(picked, mine - top[:, k:], top[:, k - 1:k] - mine), axis=-1)


def experts(cfg, lw, n):
    """n [T, D] -> ([T, D], the selection margin [T]): the held experts' part
    of the routed sum, plus the shared expert under its sigmoid scalar."""
    k = cfg["num_experts_per_tok"]
    first, held = cfg.get("first_routed_expert", 0), lw["w_up"]["q"].shape[0]
    logits = n @ lw["router"]                                   # [T, scored]
    p = jax.nn.softmax(logits, axis=-1)
    picked, idx = jax.lax.top_k(p, k)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True)

    def add_expert(out, expert):
        e, (gq, gs), (uq, us), (dq, ds) = expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)      # [T]
        gate, up, down = (a.astype(jnp.float32) * s for a, s in ((gq, gs), (uq, us), (dq, ds)))
        return out + w_e[:, None] * ((jax.nn.silu(n @ gate) * (n @ up)) @ down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(n), (
        jnp.arange(held), *((lw[m]["q"], lw[m]["scale"]) for m in ("w_gate", "w_up", "w_down"))))
    shared = (jax.nn.silu(n @ lw["shared_gate"]) * (n @ lw["shared_up"])) @ lw["shared_down"]
    return (out + jax.nn.sigmoid(n @ lw["shared_expert_gate"]) * shared,
            held_margin(cfg, logits, first, held))


#: a mixer's leaves in the program's per-kind stacks
LEAVES = {
    "delta": ("lin_norm", "lin_in", "lin_conv_w", "lin_wa", "lin_wb", "lin_dt_bias",
              "lin_A_log", "lin_gate_norm", "lin_out"),
    "attention": ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm"),
    "experts": ("mlp_norm", "router", "shared_gate", "shared_up", "shared_down",
                "shared_expert_gate"),
}


def weights_from_program(params, n_layers, cfg=None):
    """The served weights as this file wants them: a list of ``n_layers`` layers
    in the published order, each its token mixer's leaves and its expert
    layer's, taken from the program's per-kind stacks (layer j of a kind is that
    kind's j-th layer) and dequantised to float32 — but for the held routed
    experts, which stay int8 payload and scales ({"q", "scale"}) and are
    dequantised one expert at a time inside ``experts``."""
    layers = params["layers"]
    at = lambda name, j: jax.tree_util.tree_map(lambda a: a[j], layers[name])
    out, seen = [], dict.fromkeys(LEAVES, 0)
    for mixer in order(cfg or {}, n_layers):
        lw = {"mixer": mixer}
        for kind in (mixer, "experts"):
            lw.update({name: f32(at(name, seen[kind])) for name in LEAVES[kind]})
            seen[kind] += 1
        for name in ("w_gate", "w_up", "w_down"):
            one = at(name, seen["experts"] - 1)
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
        margins = []
        for lw in weights["layers"]:
            if lw["mixer"] == "delta":
                x = x + delta_rule(cfg, lw, norm(x, lw["lin_norm"], eps))
            else:
                x = x + attention(cfg, lw, norm(x, lw["attn_norm"], eps))
            y, m = experts(cfg, lw, norm(x, lw["mlp_norm"], eps))
            x = x + y
            margins.append(m)
        x = norm(x, weights["final_norm"], eps)
        position = jnp.arange(tokens.shape[0])
        margin = jnp.stack(margins, axis=1)                     # [T, expert layers]
        least = jnp.min(margin, axis=1)
        near = least >= NEIGHBOUR_MIN
        behind = jnp.all(jnp.stack([
            jnp.concatenate([jnp.ones((j,), bool), near[:-j]])
            for j in range(1, NEIGHBOURS + 1)]), axis=0)
        clear = (least >= CLEAR_MIN) & behind & (position >= 2)
        return x @ weights["lm_head"], {
            "clear_score": clear.astype(jnp.float32), "margin": margin, "position": position}
