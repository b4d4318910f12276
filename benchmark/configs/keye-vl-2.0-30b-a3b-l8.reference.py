"""Plain reference for keye-vl-2.0-30b-a3b-l8: the language model's forward
pass in straightforward float32 jax.numpy — no kernels, no cache, no
batching, no grouping of tokens by expert.

Follows the published configuration (config.json beside this file; the
Qwen3-MoE family's layout and key names) and, for the sparse attention its
``sa_config`` declares, the lightning indexer and top-k token selection that
DeepSeek-V3.2-Exp published. One sequence, token t, keys s <= t, x =
RMSNorm(h):

- q_t = RoPE(RMSNorm_hd(W_q x_t)) for every query head, k_t =
  RoPE(RMSNorm_hd(W_k x_t)) for every KV head, v_t = W_v x_t; no bias.
- Indexer: q'_{t,j} = RoPE(W'_q x_t)_j for j = 1..indexer_num_heads, one key
  k'_s = RoPE(W'_k x_s), head weights w_t = W'_w x_t;
  I_{t,s} = sum_j w_{t,j} relu(q'_{t,j} . k'_s).
- S_t = the min(topk, t + 1) keys of largest I_{t,s}, ties to the lower s;
  one set for all heads.
- o_t = sum_{s in S_t} softmax_{s in S_t}(q_t . k_s / sqrt(hd)) v_s, grouped
  queries; W_o; residual.
- Experts: logits x W_r, the num_experts_per_tok largest, softmax over those
  (``norm_topk_prob``: softmax over all then renormalise is the same
  numbers), sum of w_e W_down,e(silu(W_gate,e x) * W_up,e x); residual. Every
  layer (decoder_sparse_step 1, mlp_only_layers []). Final RMSNorm, untied
  head.

Departures and conventions, each also under ``assumed`` in the file:

- Depth is the only cut (8 of 48 layers; the comparison runs 2).
- The vision tower is not built; the service takes text. A text token's three
  M-RoPE position streams (t, h, w) are equal, so ``mrope_section`` [16, 24,
  24] over the 64 frequency pairs is ordinary rotary embedding at
  ``rope_theta``: ``mrope`` below implements the sectioned form and is fed
  t = h = w (tests/test_sparse_attention.py shows it equals ops/rope.py).
- QK-norm is the Qwen3-MoE convention (per-head RMSNorm on q and k before the
  rotary embedding).
- The indexer projects from the normed hidden state, rotates its whole 64
  dims at the model's theta (plain rotary: its 32 pairs have no published
  sections, and equal streams make any sectioning the same), has no norm on
  its key and no bias: DeepSeek-V3.2-Exp's indexer as far as a GQA model has
  its inputs. ``q_chunk_size`` / ``kv_chunk_size`` tile the score
  computation and change no result; scores here are computed in blocks of
  ``q_chunk_size`` query rows.
- The weights are the served int8 weights dequantized to float32; the seeded
  QK-norm gains are 2 (ops/quant.py::SEEDED_QK_NORM_GAIN).

What ``aux`` says of each position, for the comparison's rule (refcheck.py holds
``clear`` positions one by one and the others as a group, by their median, both
to the one ``tolerance_rel``):

- ``aux["clear_score"]`` [T]: the top expert's lead (the gap between the two
  largest router logits as a share of the token's router logits' standard
  deviation, the least over the layers) over SENTINEL_LEAD_MIN for the first
  SENTINEL_POSITIONS tokens of the sequence, 0 after them: at least 1 on the few
  SENTINELS that are held one by one (at most SENTINEL_POSITIONS keys, no cut,
  a clear router; 6-15 of them a run).
- Everything else is the GROUP, and the comparison's prompts (the file's
  ``reference_check``: 5,000 and 4,600 tokens) are long enough that the
  positions past ``topk``, whose logits depend on WHICH keys were selected, are
  57% of it: against a reference that attends to every key the group's median
  reads 0.77, sixteen times the limit. They are also short enough that the
  median sits among positions a few hundred keys past ``topk``, where rounding
  still separates precisions (bf16 0.043-0.044, 8-bit activations 0.058-0.060):
  a position's error grows with the keys cut away (0.03 before ``topk``, 0.09
  at twice ``topk``), because index scores move by ~0.5% of their spread in
  bf16, a few keys of some thousands change sides between the program and this
  float32 reference, and where one of them carries real attention weight in a
  head the position's logits move by up to their whole deviation; far past
  ``topk`` that noise buries the difference between precisions.
- No position past ``topk`` can be held one by one (1-2% of them read 0.1-1.2
  with nothing wrong; no band of ranks around the cut predicted which), and no
  position before it either (with attention as peaked as a trained model's, a
  query can put most of a head on a key whose OWN expert choice fell the other
  way, at any router gap of its own: 1 in 4,000 reads 0.048 and more where the
  median is 0.030). So the 6 decode rows are held only as members of the
  group: a fault in the decode rows' selection alone is invisible to this
  comparison (PERF.md, Open questions) and is what the CPU tests and the
  device's own count of kept keys (/health.sparse_attention) are for.
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


def mrope(x, positions3, theta, sections):
    """Sectioned multimodal rotary embedding. x [T, heads, hd]; positions3
    [3, T] (temporal, height, width); frequency pair i takes its angle from
    the stream whose section holds it (sections scaled to hd/2 pairs where a
    rehearsal's head is narrower than the published 128)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    edges = jnp.cumsum(jnp.asarray(sections)) * half // sum(sections)
    stream = jnp.searchsorted(edges, jnp.arange(half), side="right")   # [half]
    pos = positions3.astype(jnp.float32)[jnp.clip(stream, 0, 2)]       # [half, T]
    ang = pos.T * inv_freq[None, :]                                     # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


#: The positions held one by one (``aux["clear_score"]``): the sequence's first
#: tokens whose top expert leads, in every layer, by this share of the standard
#: deviation of the token's 128 router logits (32 at the published widths with
#: seeded weights, so 8 logits: the 2nd expert's weight is then e^-8).
SENTINEL_POSITIONS = 16
SENTINEL_LEAD_MIN = 0.25


def selection(cfg, lw, x, pos, rows):
    """bool [len(rows), T]: the keys each of the query rows ``rows`` attends."""
    T = x.shape[0]
    J, di, topk = cfg["indexer_num_heads"], cfg["indexer_head_dim"], cfg["topk"]
    qi = rope((x[rows] @ lw["idx_wq"]).reshape(-1, J, di), pos[rows], cfg["rope_theta"])
    ki = rope((x @ lw["idx_wk"]).reshape(T, 1, di), pos, cfg["rope_theta"])[:, 0]
    wi = x[rows] @ lw["idx_ww"]                                          # [R, J]
    scores = jnp.einsum("rj,rjs->rs", wi, jax.nn.relu(jnp.einsum("rjd,sd->rjs", qi, ki)))
    causal = pos[rows][:, None] >= pos[None, :]
    if topk >= T:
        return causal
    scores = jnp.where(causal, scores, -jnp.inf)
    _, idx = jax.lax.top_k(scores, topk)         # ties: the lower index first
    picked = jnp.zeros(scores.shape, bool).at[jnp.arange(len(rows))[:, None], idx].set(True)
    return jnp.logical_and(picked, causal)


def attention(cfg, lw, x):
    T = x.shape[0]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(T)
    pos3 = jnp.stack([pos, pos, pos])            # text: t = h = w
    sections = (cfg.get("rope_scaling") or {}).get("mrope_section", [16, 24, 24])
    q = (x @ lw["wq"]).reshape(T, H, hd)
    k = (x @ lw["wk"]).reshape(T, KV, hd)
    if cfg.get("qk_norm", True):
        q, k = rms_norm(q, lw["q_norm"], eps), rms_norm(k, lw["k_norm"], eps)
    q = mrope(q, pos3, cfg["rope_theta"], sections)
    k = mrope(k, pos3, cfg["rope_theta"], sections)
    v = (x @ lw["wv"]).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    block = cfg.get("q_chunk_size", 512)
    out = []
    for lo in range(0, T, block):                # scores in blocks of query rows
        rows = jnp.arange(lo, min(lo + block, T))
        keep = selection(cfg, lw, x, pos, rows)
        scores = jnp.einsum("rhd,shd->hrs", q[rows], k) * hd ** -0.5
        scores = jnp.where(keep[None], scores, -jnp.inf)
        out.append(jnp.einsum("hrs,shd->rhd", jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(out).reshape(T, H * hd) @ lw["wo"]


def sparse_moe(cfg, lw, x):
    """x [T, D] -> ([T, D], the gap between the two largest router logits as a
    share of the token's router logits' standard deviation [T]).
    Every expert is evaluated for every token, one after the other, and enters
    a token's sum with weight 0 unless it is one of the token's k."""
    k = cfg["num_experts_per_tok"]
    logits = x @ lw["router"]                                    # [T, E]
    top_vals, top_idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top_vals, axis=-1)                  # over the chosen k

    def add_expert(out, expert):
        e, w_gate, w_up, w_down = expert
        w_e = jnp.sum(jnp.where(top_idx == e, weights, 0.0), axis=-1)   # [T]
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return out + w_e[:, None] * y, None

    n = lw["w_gate"].shape[0]
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(x),
                          (jnp.arange(n), lw["w_gate"], lw["w_up"], lw["w_down"]))
    return out, (top_vals[:, 0] - top_vals[:, 1]) / jnp.std(logits, axis=-1)


def weights_from_program(params, n_layers):
    """The served weights dequantized to float32, a layer's experts as ONE
    [E, in, out] array each (refcheck.reference_weights makes a Python list of
    128: 768 arrays and an unrolled loop a layer, minutes of compile)."""
    def f32(leaf, i=None):
        one = leaf if i is None else jax.tree_util.tree_map(lambda a: a[i], leaf)
        if hasattr(one, "q") and hasattr(one, "scale"):
            return one.q.astype(jnp.float32) * one.scale.astype(jnp.float32)
        return one.astype(jnp.float32)

    return {"embed": f32(params["embed"]), "final_norm": f32(params["final_norm"]),
            "lm_head": f32(params["lm_head"]),
            "layers": [{k: f32(v, i) for k, v in params["layers"].items()}
                       for i in range(n_layers)]}


def forward(cfg, weights, tokens):
    """One sequence. tokens [T] int32 -> (logits [T, vocab] float32, aux)."""
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens]
        T = tokens.shape[0]
        lead = jnp.full((T,), jnp.inf, jnp.float32)
        for lw in weights["layers"]:
            h = h + attention(cfg, lw, rms_norm(h, lw["attn_norm"], cfg["rms_norm_eps"]))
            y, g_rel = sparse_moe(cfg, lw, rms_norm(h, lw["mlp_norm"], cfg["rms_norm_eps"]))
            h, lead = h + y, jnp.minimum(lead, g_rel)
        h = rms_norm(h, weights["final_norm"], cfg["rms_norm_eps"])
        seen = jnp.arange(1, T + 1, dtype=jnp.float32)
        return h @ weights["lm_head"], {
            "clear_score": jnp.where(seen <= SENTINEL_POSITIONS, lead / SENTINEL_LEAD_MIN, 0.0)}
