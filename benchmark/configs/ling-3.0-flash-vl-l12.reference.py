"""Plain reference for ling-3.0-flash-vl-l12: the language model's forward pass
in straightforward float32 jax.numpy — no kernels, no cache, no batching, no
chunked form of the recurrence, no absorption of projections, no grouping of
tokens by expert.

Follows the published configuration (config.json beside this file; the
language model of Ling-3.0-flash-VL, served alone). What the config's keys
cannot say is under ``assumed`` in that file with its reason; program and
reference agree on it. ``x`` is [T, 2560], one sequence. A layer is a pre-norm
block of two mixers, ``h = x + mixer(RMSNorm(x))``, ``y = h + mlp(RMSNorm(h))``;
a final RMSNorm, then the untied head.

THE ORDER (``layer_group_size`` 6, ``first_k_dense_replace`` 2): layer i
(0-based) is a latent layer where (i + 1) % 6 == 0, else a KDA layer; its MLP is
dense (``intermediate_size`` 6144) for i < 2, else experts.

- KDA layer (Kimi delta attention: Kimi Linear, arXiv:2510.26692; flash-linear-
  attention's KimiDeltaAttention), H = 32 heads, key and value dim 128:
  [q | k | v] = silu(conv4(n [W_q | W_k | W_v])), a causal depthwise convolution
  of ``short_conv_kernel_size`` taps over all 12,288 channels, no bias;
  q^ = q / ||q|| / sqrt(128), k^ = k / ||k|| a head (the norm is
  sqrt(sum x^2 + 1e-6)); beta = sigmoid(n W_b) [32]; the decay is a VECTOR a
  head, a = n W_f [32 x 128], g = kda_lower_bound x sigmoid(exp(A_log_h) (a +
  f_bias)), alpha = exp(g) in (e^-5, 1) for every key channel; THE RECURRENCE,
  TOKEN BY TOKEN (a lax.scan over the sequence) on a state S [128, 128] a head:
  S' = Diag(alpha_t) S_{t-1}; u_t = beta_t (v_t - S'^T k^_t); S_t = S' + k^_t
  u_t^T; o_t = S_t^T q^_t; out = (RMSNorm_128(o_t) * w * sigmoid(n W_g)) W_o:
  the norm first, the gate after. No rotary embedding.
- latent layer (``q_lora_rank`` null: NO query LoRA and no query norm):
  q = n W_q -> 32 heads x (128 nope | 64 rope); [c | r] = n W_dkv [512 + 64];
  c^ = RMSNorm_512(c); plain rope at theta 6,000,000 on q's last 64 lanes and on
  r, half-split pairs (i, i + 32), no YaRN, no query scale; EXPANDED:
  [k_nope_h | v_h] = c^ W_ukv; s_h = (q_nope_h . k_nope_h + q_rope_h . r) x
  192^-0.5; o_h = softmax_causal(s_h) v_h; out = concat_h(sigmoid(n W_a)_h o_h)
  W_o, one gate a head (``gated_attention_proj_granularity_type`` head_wise).
- experts (``moe_intermediate_size`` 768, gated silu): s = sigmoid(n W_r) over
  all ``num_experts_scored`` (512), float32; the choice is on s + bias and is
  GROUP-LIMITED: 8 groups of 64, a group's score the sum of its two largest
  s + bias, the ``topk_group`` 4 best groups stay, the ``num_experts_per_tok``
  8 largest s + bias among their experts are picked; weights are the picked s
  (no bias) over their sum, times ``routed_scaling_factor`` 2.5; the held
  experts' part of the routed sum, plus the shared expert (768).

The cut, in program and reference alike: ``num_hidden_layers`` of the 42, and of
the 512 routed experts the ``num_experts`` (128) this chip of the stated
deployment holds, from ``first_routed_expert`` on. The router scores all 512,
limits to 4 of 8 groups and picks 8 among ALL of them; a pick of an expert held
on another chip adds nothing here and the partial sum goes on to the next
layer; nothing stands in for the other chips or their exchange.

The weights are the served int8 weights dequantised to float32 (``W_ukv``,
``W_b``, the gates' projections, the convolution, ``A_log`` and the biases are
served in bf16 or float32: models/transformer.py::small_leaf_init).

What ``aux`` says of each position, for the comparison's rule (refcheck.py holds
``clear`` positions one by one and the others as a group, by their median, both
to the one ``tolerance_rel``). With 8 picks among 256 allowed scores that lie
~0.003 apart, four expert layers deep, bf16 activations and this float32 pass
often pick different experts with nothing wrong, and a token's logits then move
by 0.15-0.4 of their deviation (an expert's worth) — but ONLY where the pick
that changed is of an expert THIS CHIP HOLDS: a pick that changes among the 384
held elsewhere changes nothing computed here (the weights' common divisor moves
by a thousandth). So the rule is structural. ``route`` gives, a layer:
``here``, the least change of one score against another's that would alter
which HELD experts the token picks (a held pick against the 9th allowed score,
a held expert not picked against the 8th); ``group``, the gap between the 4th
and 5th group scores; ``swap``, ``here`` again were those two groups to change
places, and 0 where that would alter the held picks; ``deep``, how far any
further exchange of groups is. ``steady(m, least, least_group)`` says whether
every expert layer keeps its held picks under such changes, and
``aux["clear_score"]`` [T] is 1 where the position is steady at CLEAR_MIN (a
group at CLEAR_GROUP_MIN) AND each of the NEIGHBOURS tokens before it is steady
at NEIGHBOUR_MIN (NEIGHBOUR_GROUP_MIN) — the three tokens behind a token whose
held picks changed read 0.08-0.14 where the rest read 0.06, for a KDA layer's
convolution has four taps and its state has just been written — and 0 at a
sequence's first two tokens (where a KDA layer's state holds one or two keys
and its per-head norm divides by the product of two unit vectors, as
olmo-hybrid-7b's file says of the gated delta rule). Measured by position on
the chip (the file's ``tolerance_why``): the share of positions whose logits
moved by an expert's worth falls with ``here`` as a normal tail of deviation
0.0015 (a half at 0, 2% at 0.003, none of 472 from 0.0035 up); about one
position in six hundred is clear, decode positions among them.
``aux["steadiness"]`` [T, expert layers, 4] is what ``steady`` read
(tools/refcheck_power.py --positions dumps it beside every position's error).
"""

import jax
import jax.numpy as jnp

#: a score against another's, a group's against another's: for the position
#: itself, and for each of the NEIGHBOURS tokens before it
CLEAR_MIN, CLEAR_GROUP_MIN = 0.006, 0.012
NEIGHBOUR_MIN, NEIGHBOUR_GROUP_MIN, NEIGHBOURS = 0.003, 0.006, 3
#: the published order where the sizes handed in do not name it
LAYER_GROUP_SIZE, FIRST_K_DENSE = 6, 2


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def f32(leaf):
    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def order(cfg, n_layers):
    """[(token mixer, mlp)] of the first ``n_layers`` layers, as published."""
    group = cfg.get("layer_group_size", LAYER_GROUP_SIZE)
    dense = cfg.get("first_k_dense_replace", FIRST_K_DENSE)
    return [("latent" if (i + 1) % group == 0 else "kda",
             "dense" if i < dense else "experts") for i in range(n_layers)]


def kda(cfg, lw, n):
    """n [T, D] (normed) -> [T, D]: one KDA mixer, the recurrence step by step."""
    T = n.shape[0]
    H, dk, dv = (cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])
    K = cfg["short_conv_kernel_size"]
    C = 2 * H * dk + H * dv
    qkvz = n @ lw["lin_in"]
    qkv, z = qkvz[:, :C], qkvz[:, C:]
    padded = jnp.concatenate([jnp.zeros((K - 1, C)), qkv])
    qkv = jax.nn.silu(sum(padded[k:k + T] * lw["lin_conv_w"][k][None, :] for k in range(K)))
    q = l2_normalize(qkv[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = l2_normalize(qkv[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = qkv[:, 2 * H * dk:].reshape(T, H, dv)
    beta = jax.nn.sigmoid(n @ lw["lin_wb"])                              # [T, H]
    a = (n @ lw["lin_wf"]).reshape(T, H, dk)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(lw["lin_A_log"])[None, :, None] * (a + lw["lin_f_bias"][None]))
    alpha = jnp.exp(g)                                                   # [T, H, dk]

    def step(S, t):
        q_t, k_t, v_t, a_t, b_t = t
        S = a_t[:, :, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32), (q, k, v, alpha, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg["rms_norm_eps"])
    o = o * lw["lin_gate_norm"][None, None, :] * jax.nn.sigmoid(z.reshape(T, H, dv))
    return o.reshape(T, H * dv) @ lw["lin_out"]


def rope(cfg, x, pos):
    """x [T, ..., rope] rotated by its position: the plain rotary embedding,
    pairs (i, i + rope/2)."""
    dim = x.shape[-1]
    inv = 1.0 / (float(cfg["rope_theta"]) ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = pos.astype(jnp.float32)[:, None] * inv
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latent(cfg, lw, n):
    """n [T, D] (normed) -> [T, D]: latent attention, expanded, a head at a time."""
    T = n.shape[0]
    H, C = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    N, R, V = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    pos = jnp.arange(T)
    q = (n @ lw["wq"]).reshape(T, H, N + R)
    cr = n @ lw["w_dkv"]
    c = rms_norm(cr[:, :C], lw["dkv_norm"], cfg["rms_norm_eps"])
    r = rope(cfg, cr[:, C:], pos)                               # [T, R]: one key for all heads
    q_rope = rope(cfg, q[..., N:], pos)                         # [T, H, R]
    kv = (c @ lw["w_ukv"]).reshape(T, H, N + V)
    scale = (N + R) ** -0.5
    causal = pos[:, None] >= pos[None, :]

    def head(xs):
        q_nope_h, q_rope_h, k_nope_h, v_h = xs
        s = (q_nope_h @ k_nope_h.T + q_rope_h @ r.T) * scale
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1) @ v_h

    heads = lambda a: jnp.swapaxes(a, 0, 1)                     # [H, T, .]
    o = jax.lax.map(head, (heads(q[..., :N]), heads(q_rope), heads(kv[..., :N]),
                           heads(kv[..., N:])))                 # [H, T, V]
    o = jnp.swapaxes(o, 0, 1) * jax.nn.sigmoid(n @ lw["wg"])[:, :, None]
    return o.reshape(T, H * V) @ lw["wo"]


def dense_mlp(lw, n):
    return (jax.nn.silu(n @ lw["dense_gate"]) * (n @ lw["dense_up"])) @ lw["dense_down"]


def held_picks(cfg, groups, stays, first, held):
    """groups [T, G, scored / G] (s + bias), stays [T, G] (the groups that may
    be picked from) -> (which of the experts held here are picked [T, held], the
    least change of a score against another's that would alter that [T])."""
    k, T = cfg["num_experts_per_tok"], groups.shape[0]
    may = jnp.broadcast_to(stays[:, :, None], groups.shape).reshape(T, -1)
    choice = groups.reshape(T, -1)
    top = jax.lax.top_k(jnp.where(may, choice, -jnp.inf), k + 1)[0]
    mine, may = choice[:, first:first + held], may[:, first:first + held]
    picked = may & (mine >= top[:, k - 1:k])
    # a picked one leaves when the 9th passes it, another enters when it
    # passes the 8th
    gap = jnp.where(picked, mine - top[:, k:], top[:, k - 1:k] - mine)
    return picked, jnp.min(jnp.where(may, gap, jnp.inf), axis=-1)


def route(cfg, lw, n, first=0, held=0):
    """n [T, D] -> (weights [T, k], idx [T, k], [T, 4]: what ``steady`` reads
    of this layer)."""
    k, G, keep = cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"]
    s = jax.nn.sigmoid(n @ lw["router"])                        # [T, scored]
    choice = s + lw["router_bias"][None, :]
    groups = choice.reshape(choice.shape[0], max(G, 1), -1)
    stays = jnp.ones(groups.shape[:2], bool)
    group = swap = deep = jnp.full(n.shape[:1], jnp.inf)
    other = None
    if G > 1:
        score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)   # [T, G]
        best, idx_g = jax.lax.top_k(score, min(G, keep + 2))
        best = jnp.pad(best, ((0, 0), (0, keep + 2 - best.shape[1])), constant_values=-jnp.inf)
        among = lambda idx: jnp.any(idx[:, :, None] == jnp.arange(G)[None, None, :], axis=1)
        stays = among(idx_g[:, :keep])
        choice = jnp.where(stays[:, :, None], groups, -jnp.inf).reshape(choice.shape)
        # were the 4th and the 5th group to change places (``deep``: how far
        # any further exchange is)
        group = best[:, keep - 1] - best[:, keep]
        other, swap = held_picks(cfg, groups, among(jnp.concatenate(
            [idx_g[:, :keep - 1], idx_g[:, keep:keep + 1]], axis=1)), first, held)
        deep = jnp.minimum(best[:, keep - 2] - best[:, keep], best[:, keep - 1] - best[:, keep + 1])
    _, idx = jax.lax.top_k(choice, k)
    mine, here = held_picks(cfg, groups, stays, first, held)
    if other is not None:       # harmless only where the held picks are the same either way
        swap = jnp.where(jnp.all(mine == other, axis=-1), swap, 0.0)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    weights = picked / jnp.sum(picked, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]
    return weights, idx, jnp.stack([here, group, swap, deep], axis=-1)


def steady(m, least, least_group):
    """m [T, layers, 4] (``route``'s "steady") -> [T] bool: no score moving
    against another's by less than ``least``, and no group's by less than
    ``least_group``, alters which experts HELD HERE a token picks, in any
    layer. A pick that changes among the experts held on the other chips
    changes nothing this chip computes; an exchange of the 4th and 5th group
    is harmless where the held picks are the same either way (and, either
    way, ``least`` from changing), and no further exchange is near."""
    here, group, swap, deep = (m[..., i] for i in range(4))
    return jnp.all((here >= least) & ((group >= least_group)
                                      | (swap >= least) & (deep >= least_group)), axis=-1)


def experts(cfg, lw, n):
    """n [T, D] -> ([T, D], the selection margins [T, 4], ``route``'s): the
    held experts' part of the routed sum, plus the shared expert."""
    first, held = cfg.get("first_routed_expert", 0), lw["w_up"]["q"].shape[0]
    weights, idx, margin = route(cfg, lw, n, first, held)

    def add_expert(out, expert):
        e, (gq, gs), (uq, us), (dq, ds) = expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)      # [T]
        gate, up, down = (a.astype(jnp.float32) * s for a, s in ((gq, gs), (uq, us), (dq, ds)))
        return out + w_e[:, None] * ((jax.nn.silu(n @ gate) * (n @ up)) @ down), None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(n), (
        jnp.arange(held), *((lw[m]["q"], lw[m]["scale"]) for m in ("w_gate", "w_up", "w_down"))))
    shared = (jax.nn.silu(n @ lw["shared_gate"]) * (n @ lw["shared_up"])) @ lw["shared_down"]
    return out + shared, margin


#: a mixer's leaves in the program's per-kind stacks
LEAVES = {
    "kda": ("lin_norm", "lin_in", "lin_conv_w", "lin_wf", "lin_wb", "lin_f_bias", "lin_A_log",
            "lin_gate_norm", "lin_out"),
    "latent": ("attn_norm", "wq", "w_dkv", "dkv_norm", "w_ukv", "wo", "wg"),
    "dense": ("dense_norm", "dense_gate", "dense_up", "dense_down"),
    "experts": ("mlp_norm", "router", "router_bias", "shared_gate", "shared_up", "shared_down"),
}


def weights_from_program(params, n_layers, cfg=None):
    """The served weights as this file wants them: a list of ``n_layers`` layers
    in the published order, each its token mixer's leaves and its MLP's, taken
    from the program's per-kind stacks (layer j of a kind is that kind's j-th
    layer) and dequantised to float32 — but for the held routed experts, which
    stay int8 payload and scales ({"q", "scale"}) and are dequantised one
    expert at a time inside ``experts``."""
    layers = params["layers"]
    at = lambda name, j: jax.tree_util.tree_map(lambda a: a[j], layers[name])
    out, seen = [], dict.fromkeys(LEAVES, 0)
    for mixer, mlp in order(cfg or {}, n_layers):
        lw = {"mixer": mixer, "mlp": mlp}
        for kind in (mixer, mlp):
            lw.update({name: f32(at(name, seen[kind])) for name in LEAVES[kind]})
            if kind == "experts":
                for name in ("w_gate", "w_up", "w_down"):
                    one = at(name, seen[kind])
                    q = getattr(one, "q", one)      # a float tree (the tests') has no scales
                    scale = (one.scale.astype(jnp.float32) if hasattr(one, "scale")
                             else jnp.ones((q.shape[0], 1, q.shape[2]), jnp.float32))
                    lw[name] = {"q": q, "scale": scale}
            seen[kind] += 1
        out.append(lw)
    return {"embed": f32(params["embed"]), "final_norm": f32(params["final_norm"]),
            "lm_head": f32(params["lm_head"]), "layers": out}


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux)."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = weights["embed"][tokens]
        steadiness = []
        for lw in weights["layers"]:
            if lw["mixer"] == "kda":
                x = x + kda(cfg, lw, rms_norm(x, lw["lin_norm"], eps))
            else:
                x = x + latent(cfg, lw, rms_norm(x, lw["attn_norm"], eps))
            if lw["mlp"] == "dense":
                x = x + dense_mlp(lw, rms_norm(x, lw["dense_norm"], eps))
            else:
                y, m = experts(cfg, lw, rms_norm(x, lw["mlp_norm"], eps))
                x = x + y
                steadiness.append(m)
        x = rms_norm(x, weights["final_norm"], eps)
        position = jnp.arange(tokens.shape[0])
        steadiness = jnp.stack(steadiness, axis=1)              # [T, expert layers, 4]
        near = steady(steadiness, NEIGHBOUR_MIN, NEIGHBOUR_GROUP_MIN)
        behind = jnp.all(jnp.stack([
            jnp.concatenate([jnp.ones((j,), bool), near[:-j]])
            for j in range(1, NEIGHBOURS + 1)]), axis=0)
        clear = steady(steadiness, CLEAR_MIN, CLEAR_GROUP_MIN) & behind & (position >= 2)
        return x @ weights["lm_head"], {
            "clear_score": clear.astype(jnp.float32), "steadiness": steadiness,
            "position": position}
