"""Plain reference for laguna-s-2.1-l12: the language model's forward pass in
straightforward float32 jax.numpy — no kernels, no cache, no ring, no batching,
no grouping of tokens by expert. One sequence, full score matrices, one head at
a time.

Follows the published configuration (config.json beside this file, ``model_type:
laguna``); every convention the config does not spell out is under ``assumed``
in the file, and named here where it is computed. Layer l is a pre-norm block,
written as its TWO mixers (``layer_mixers``: ``*D`` for layer 0, ``SE`` or ``*E``
after it), each ``x <- x + mixer(RMSNorm(x))``; a final RMSNorm, then the untied
head. With xh = RMSNorm(x):

- ``*`` / ``S`` (attention, full / sliding): H = num_attention_heads (48) /
  num_attention_heads_sliding (72) query heads over the same
  num_key_value_heads (8) of head_dim (128); q = xh Wq, k = xh Wk, v = xh Wv, no
  bias, no QK-norm; g = sigmoid(xh Wg) [H], one scalar a head (``gating``
  per-head). Rotary, pairs (i, i + rot/2) of the first rot lanes: the full kind
  rotates partial_rotary_factor 0.5 of the lanes with YaRN frequencies
  (rope_theta 500,000, factor 128 over 8,192, beta 32 / 1), cos and sin times
  attention_factor; the sliding kind all 128 lanes, theta 10,000, no scaling.
  Scores q . k / sqrt(head_dim) under the causal mask ``s <= t``, written as a
  comparison, and for the sliding kind also ``s > t - sliding_window`` (512
  keys, the query's own included). o_h = g_h softmax(scores_h) v; x += concat(o)
  Wo.
- ``D``: Wd(silu(xh Wg) * (xh Wu)), intermediate_size wide (layer 0 only).
- ``E``: router logits xh Wr over all num_experts_scored (256) in float32,
  softmax over all of them, the num_experts_per_tok (10) largest renormalised to
  sum 1 (norm_topk_prob), times moe_routed_scaling_factor (2.5), weights on the
  experts' OUTPUTS; an expert is Wd(silu(x Wg) * (x Wu)) at
  moe_intermediate_size; plus ONE shared expert of
  shared_expert_intermediate_size for every token, ungated. THE HELD SHARE: the
  leaves hold num_experts (64) experts, from first_routed_expert on; a pick of
  an expert held elsewhere adds nothing, in the program and here alike. Every
  held expert is evaluated for every token, one after the other (a scan that
  dequantises ONE expert's int8 weights at a time), and enters a token's sum
  with weight 0 unless picked.

Departures: depth and the experts held are the only cuts; the weights are the
served int8 weights dequantised to float32.

What ``aux`` says of each position, for the comparison's rule (refcheck.py holds
``clear`` positions one by one and the others as a group, by their median):
``aux["clear_score"]`` [T] is the position's own selection margin — the gap
between its 10th and 11th largest router logits, the least over the expert
layers — over SENTINEL_MARGIN_MIN, at every SENTINEL_EVERY-th token of the
sequence (127, 255, ...), 0 elsewhere. Where the margin is small, bf16
activations and this float32 pass pick different experts with nothing wrong and
the token's output moves by most of an expert's; with 10 picks of 256 a token
some pick is by a hair at most positions. So, as in the other files with
experts, a few SENTINELS with a clear router are held one by one and everything
else as a group, by its median.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

SENTINEL_EVERY = 128
SENTINEL_MARGIN_MIN = 0.05

#: the published block order, two mixers a layer; a cut in depth keeps its
#: first characters
PUBLISHED_MIXERS = "*D" + "SESESE*E" * 11 + "SESESE"
#: the program's toy (models/config.py::TOY_SLIDING_MOE), for the CPU tests
TOY_MIXERS = "*DSE*ESESE*E"


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def f32(leaf):
    if hasattr(leaf, "q") and hasattr(leaf, "scale"):
        return leaf.q.astype(jnp.float32) * leaf.scale.astype(jnp.float32)
    return leaf.astype(jnp.float32)


def inv_freq(dim, base, factor=1.0, orig=0, beta_fast=32.0, beta_slow=1.0):
    """[dim/2] float32; with factor > 1, YaRN's: a pair that turns more than
    beta_fast times over the trained positions keeps its frequency, one that
    turns fewer than beta_slow times has it divided by ``factor``, a linear ramp
    over the pair index between (bounds truncated to whole pairs)."""
    freqs = float(base) ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1:
        return (1.0 / freqs).astype(np.float32)
    turn = lambda rot: dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(turn(beta_fast)), 0)
    high = min(math.ceil(turn(beta_slow)), dim - 1)
    high = high + 0.001 if low == high else high
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return ((1.0 / (factor * freqs)) * ramp + (1.0 / freqs) * (1.0 - ramp)).astype(np.float32)


def rope(x, pos, freqs, factor):
    """x [T, heads, hd]: the first 2 x len(freqs) lanes rotated by position,
    pairs (i, i + rot/2), cos and sin times ``factor``; the rest pass."""
    rot = 2 * len(freqs)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs)
    cos, sin = (jnp.cos(ang) * factor)[:, None, :], (jnp.sin(ang) * factor)[:, None, :]
    a, b = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def rotary_rule(cfg, kind):
    hd = cfg["head_dim"]
    if kind == "S":
        rot = int(hd * cfg.get("sliding_partial_rotary_factor", 1.0)) // 2 * 2
        return inv_freq(rot, cfg["sliding_rope_theta"]), 1.0
    rot = int(hd * cfg.get("partial_rotary_factor", 1.0)) // 2 * 2
    factor = float(cfg.get("factor", 1.0))
    freqs = inv_freq(rot, cfg["rope_theta"], factor,
                     cfg.get("original_max_position_embeddings", 0),
                     cfg.get("beta_fast", 32.0), cfg.get("beta_slow", 1.0))
    return freqs, float(cfg.get("attention_factor") or 1.0)


def attention(cfg, lw, xh, kind):
    T = xh.shape[0]
    H = cfg["num_attention_heads_sliding"] if kind == "S" else cfg["num_attention_heads"]
    KV, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    pos = jnp.arange(T)
    freqs, factor = rotary_rule(cfg, kind)
    q = rope((xh @ lw["wq"]).reshape(T, H, hd), pos, freqs, factor)
    k = rope((xh @ lw["wk"]).reshape(T, KV, hd), pos, freqs, factor)
    v = (xh @ lw["wv"]).reshape(T, KV, hd)
    g = jax.nn.sigmoid(xh @ lw["wg"])                           # [T, H]
    seen = pos[:, None] >= pos[None, :]                         # s <= t
    if kind == "S":
        seen = jnp.logical_and(seen, pos[None, :] > pos[:, None] - cfg["sliding_window"])
    G = H // KV

    def head(xs):
        q_h, g_h, kv_h = xs
        s = (q_h @ k[:, kv_h].T) * hd ** -0.5
        return g_h[:, None] * (jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
                               @ v[:, kv_h])

    o = jax.lax.map(head, (jnp.swapaxes(q, 0, 1), g.T, jnp.arange(H) // G))   # [H, T, hd]
    return jnp.swapaxes(o, 0, 1).reshape(T, H * hd) @ lw["wo"]


def dense_mlp(lw, x, prefix):
    return (jax.nn.silu(x @ lw[prefix + "gate"]) * (x @ lw[prefix + "up"])) @ lw[prefix + "down"]


def experts(cfg, lw, x):
    """x [T, D] -> ([T, D], the selection margin [T]): the held experts' part
    of the routed sum, plus the shared expert."""
    k = cfg["num_experts_per_tok"]
    first = cfg.get("first_routed_expert", 0)
    logits = x @ lw["router"]                                   # [T, all experts]
    top, idx = jax.lax.top_k(logits, k + 1)
    idx = idx[:, :k]
    picked = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1), idx, axis=-1)
    weights = (picked / jnp.sum(picked, axis=-1, keepdims=True)
               * cfg.get("moe_routed_scaling_factor", 1.0))

    def add_expert(out, expert):
        e, (gq, gs), (uq, us), (dq, ds) = expert
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)      # [T]
        gate, up, down = (a.astype(jnp.float32) * s for a, s in ((gq, gs), (uq, us), (dq, ds)))
        return out + w_e[:, None] * ((jax.nn.silu(x @ gate) * (x @ up)) @ down), None

    held = lw["w_up"]["q"].shape[0]
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), (
        jnp.arange(held), *((lw[n]["q"], lw[n]["scale"]) for n in ("w_gate", "w_up", "w_down"))))
    return out + dense_mlp(lw, x, "shared_"), top[:, k - 1] - top[:, k]


#: a kind's leaves in the program's per-kind stacks -> the names used here
LEAVES = {
    "*": {"attn_norm": "norm", "wq": "wq", "wk": "wk", "wv": "wv", "wo": "wo", "wg": "wg"},
    "S": {"sw_norm": "norm", "sw_wq": "wq", "sw_wk": "wk", "sw_wv": "wv", "sw_wo": "wo",
          "sw_wg": "wg"},
    "D": {"dense_norm": "norm", "dense_gate": "dense_gate", "dense_up": "dense_up",
          "dense_down": "dense_down"},
    "E": {"mlp_norm": "norm", "router": "router", "shared_gate": "shared_gate",
          "shared_up": "shared_up", "shared_down": "shared_down"},
}


def weights_from_program(params, n_layers):
    """The served weights as this file wants them: a list of MIXERS in the
    blocks' order, each holding its kind's leaves taken from the program's
    per-kind stacks (mixer j of a kind is that kind's j-th), dequantised to
    float32 — but for the held routed experts, which stay int8 payload and
    scales ({"q", "scale"}: [held, in, out], [held, 1, out]) and are dequantised
    one expert at a time inside ``experts``. The call carries no configuration,
    so the kinds of the ``n_layers`` layers are the pattern's whose counts the
    stacks' leading axes show."""
    layers = params["layers"]
    lead = lambda name: (getattr(layers[name], "q", layers[name]).shape[0]
                         if name in layers else 0)
    n_of = {"*": lead("wq"), "S": lead("sw_wq"), "D": lead("dense_up"), "E": lead("w_up")}
    for pattern in (PUBLISHED_MIXERS, TOY_MIXERS):
        kinds = pattern[:2 * n_layers]
        if len(kinds) == 2 * n_layers and all(kinds.count(k) == n for k, n in n_of.items()):
            break
    else:
        raise ValueError(f"no known pattern gives {n_of} in {n_layers} layers")
    at = lambda leaf, j: jax.tree_util.tree_map(lambda a: a[j], leaf)
    out, seen = [], dict.fromkeys(LEAVES, 0)
    for kind in kinds:
        j = seen[kind]
        seen[kind] += 1
        lw = {ours: f32(at(layers[name], j)) for name, ours in LEAVES[kind].items()}
        if kind == "E":
            for name in ("w_gate", "w_up", "w_down"):
                one = at(layers[name], j)
                q = getattr(one, "q", one)          # a float tree (the tests') has no scales
                scale = (one.scale.astype(jnp.float32) if hasattr(one, "scale")
                         else jnp.ones((q.shape[0], 1, q.shape[2]), jnp.float32))
                lw[name] = {"q": q, "scale": scale}
        lw["kind"] = kind
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
            xh = rms_norm(x, lw["norm"], eps)
            if lw["kind"] in "*S":
                x = x + attention(cfg, lw, xh, lw["kind"])
            elif lw["kind"] == "D":
                x = x + dense_mlp(lw, xh, "dense_")
            else:
                y, m = experts(cfg, lw, xh)
                x, margin = x + y, jnp.minimum(margin, m)
        x = rms_norm(x, weights["final_norm"], eps)
        seen = jnp.arange(1, tokens.shape[0] + 1)
        return x @ weights["lm_head"], {
            "clear_score": jnp.where(seen % SENTINEL_EVERY == 0,
                                     margin / SENTINEL_MARGIN_MIN, 0.0),
            "margin": margin}
