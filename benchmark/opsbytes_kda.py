"""Bytes and operations a model of Kimi-delta-attention and latent-attention
layers with a chip's share of its experts needs (ling-3.0-flash-vl-l12: five
KDA layers to one latent layer behind two dense layers, 128 held of 512
experts), computed from a configuration's sizes under the program's
``ModelConfig`` field names (``modelmap.fields``), as opsbytes_linear.py and
opsbytes_mla.py do for the two families it joins. The program's own timers are
not consulted; its COUNTS (forward passes, rows moved, latent rows read, experts
read) are, because how many rows a pass holds, how long the live contexts are
and which experts a batch picks is traffic, not shape."""

from __future__ import annotations

from typing import Optional


def kinds(sz: dict) -> str:
    """The kinds of the model's mixers: the pattern's first ``n_layers`` x
    ``mixers_per_layer`` characters."""
    return sz["layer_pattern"][:sz["n_layers"] * sz.get("mixers_per_layer", 1)]


def kda_values(sz: dict) -> int:
    """A token's values in a KDA layer: heads x value_dim."""
    return sz["lin_value_heads"] * sz["lin_value_dim"]


def kda_keys(sz: dict) -> int:
    """A token's key channels in a KDA layer: heads x key_dim (the width of
    the decay's projection W_f: a decay a channel)."""
    return sz["lin_key_heads"] * sz["lin_key_dim"]


def kda_conv_channels(sz: dict) -> int:
    """Channels under the convolution: [q | k | v]."""
    return 2 * kda_keys(sz) + kda_values(sz)


def kda_layer_bytes(sz: dict) -> int:
    """A KDA layer's projections: int8 [W_q | W_k | W_v | W_g], W_f and W_o,
    bf16 W_b (the convolution's taps, A_log, the biases and the gains: 0.06M of
    63.05M, left out)."""
    d = sz["dim"]
    return (d * (kda_conv_channels(sz) + kda_values(sz)) + d * kda_keys(sz)
            + kda_values(sz) * d + 2 * d * sz["lin_value_heads"])


def kda_matrix_bytes(sz: dict) -> int:
    """One sequence's float32 matrix state in ONE KDA layer, [key_dim, heads x
    value_dim]: what a decode step's kernel reads once and writes once."""
    return 4 * sz["lin_key_dim"] * kda_values(sz)


def kda_state_bytes(sz: dict) -> int:
    """... and with the bf16 convolution tail: what a snapshot keeps a layer."""
    return kda_matrix_bytes(sz) + 2 * (sz["lin_conv"] - 1) * kda_conv_channels(sz)


def state_bytes_per_sequence(sz: dict) -> int:
    return kinds(sz).count("L") * kda_state_bytes(sz)


def latent_row_values(sz: dict) -> int:
    """Values of the ONE row a token caches a latent layer (512 + 64)."""
    return sz["kv_lora_rank"] + sz["qk_rope_head_dim"]


def cache_bytes_per_token(sz: dict, itemsize: int = 2) -> int:
    """A token's rows in the pool: one a LATENT layer (2 x 576 x 2 B)."""
    return kinds(sz).count("*") * latent_row_values(sz) * itemsize


def latent_layer_bytes(sz: dict) -> int:
    """A latent layer's weights as served: W_q (no query LoRA), W_dkv and W_o
    int8, W_ukv and the head gate's projection bf16."""
    d, H, C = sz["dim"], sz["n_heads"], sz["kv_lora_rank"]
    N, R, V = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], sz["v_head_dim"]
    return (d * H * (N + R) + d * (C + R) + 2 * C * H * (N + V) + H * V * d
            + 2 * d * H)


def latent_flops_per_pair(sz: dict) -> int:
    """FLOPs of ONE query row against ONE cached row in one latent layer,
    absorbed: every head's score over the row's 576 values and its weighted sum
    over the row's first 512 (69,632)."""
    return sz["n_heads"] * 2 * (latent_row_values(sz) + sz["kv_lora_rank"])


def latent_least_seconds(sz: dict, decode_rows_read: float, window_pairs: float,
                         peaks: dict) -> float:
    """The least time the chip could take for the latent attention of the
    counted work. ``decode_rows_read``: cached rows the decode queries had
    before them, summed over the LATENT layers; ``window_pairs``: (query, key)
    pairs of the prompt rows prefilled, ONE layer's. The larger of the decode
    rows' bytes at peak bandwidth and the operations at peak bf16."""
    pairs = decode_rows_read + window_pairs * kinds(sz).count("*")
    return max(decode_rows_read * latent_row_values(sz) * 2 / peaks["hbm_bytes_per_s"],
               pairs * latent_flops_per_pair(sz) / peaks["bf16_flops"])


def dense_layer_bytes(sz: dict) -> int:
    """A ``D`` mixer: gate, up and down."""
    return 3 * sz["dim"] * sz["dense_mlp_hidden"]


def expert_layer_bytes(sz: dict, experts: Optional[float] = None) -> float:
    """An ``E`` mixer's weights THIS CHIP streams: ``experts`` of the experts it
    holds (None: all held), the shared expert, the bf16 router over every
    expert scored."""
    held = sz["n_experts"]
    n = held if experts is None else min(held, experts)
    return (3 * sz["dim"] * (n * sz["mlp_hidden"] + sz.get("shared_mlp_hidden", 0))
            + 2 * sz["dim"] * (sz.get("router_width") or held))


def head_bytes(sz: dict) -> int:
    return sz["dim"] * sz["vocab_size"]


def whole_model_bytes(sz: dict) -> float:
    """Every weight byte the chip holds: the mixers by kind, embedding, head."""
    per = {"L": kda_layer_bytes(sz), "*": latent_layer_bytes(sz),
           "D": dense_layer_bytes(sz), "E": expert_layer_bytes(sz)}
    return sum(per[k] for k in kinds(sz)) + 2 * head_bytes(sz)


def gemm_stream_bytes(sz: dict, experts_streamed: Optional[float] = None) -> float:
    """Bytes one forward pass streams in the trace's three weight-GEMM
    categories (``mlp``, ``attn_proj``, ``lm_head``): the dense MLPs, the expert
    layers (the held experts read, the shared expert, the router), the latent
    layers' projections, the head. The KDA layers' projections run under
    ``lin/*`` scopes and are counted by ``kda_pass_bytes``."""
    k = kinds(sz)
    return (k.count("D") * dense_layer_bytes(sz)
            + k.count("E") * expert_layer_bytes(sz, experts_streamed)
            + k.count("*") * latent_layer_bytes(sz) + head_bytes(sz))


def kda_pass_bytes(sz: dict) -> int:
    """Bytes the KDA layers' projections stream in one forward pass, whatever
    rows it holds. The rows' matrix states are counted beside them, by the
    rows the program says its decode passes moved (``kda_matrix_bytes`` a row a
    layer, read once and written once by the step kernel; a window's rows'
    states, read and written once a window, are left out: a lower bound)."""
    return kinds(sz).count("L") * kda_layer_bytes(sz)
