"""Bytes and operations a latent-attention (MLA) model with a chip's share of
its experts needs, computed from a configuration's sizes under the program's
``ModelConfig`` field names (``modelmap.fields``), as opsbytes.py does for a
GQA block and opsbytes_hybrid.py for a patterned one. The program's own timers
are not consulted; its COUNTS (latent rows read, experts read, forward passes)
are, because how long the live contexts are and which experts a batch picks is
traffic, not shape."""

from __future__ import annotations

from typing import Optional


def latent_row_values(sz: dict) -> int:
    """Values of the ONE row a token caches a layer: the normed latent and
    the rotated rope key (256 + 64)."""
    return sz["kv_lora_rank"] + sz["qk_rope_head_dim"]


def latent_row_bytes(sz: dict, itemsize: int = 2) -> int:
    """Bytes a token keeps in the pool a layer (640 in bf16)."""
    return latent_row_values(sz) * itemsize


def cache_bytes_per_token(sz: dict, itemsize: int = 2) -> int:
    return sz["n_layers"] * latent_row_bytes(sz, itemsize)


def attention_flops_per_pair(sz: dict) -> int:
    """FLOPs of ONE query row against ONE cached row in one layer, absorbed:
    every head's score over the row's 320 values and its weighted sum over
    the row's first 256 (36,864 at the published sizes)."""
    return sz["n_heads"] * 2 * (latent_row_values(sz) + sz["kv_lora_rank"])


def attention_flops_per_pair_expanded(sz: dict) -> int:
    """The same pair with keys and values expanded per head (16,384), the
    re-expansion of the row itself left out."""
    return sz["n_heads"] * 2 * (sz["qk_nope_head_dim"] + sz["qk_rope_head_dim"]
                                + sz["v_head_dim"])


def attention_layer_bytes(sz: dict) -> int:
    """One layer's attention weights as served: W_dq, W_uq, W_dkv and W_o
    int8, W_ukv bf16 (the file's ``assumed.precisions``)."""
    d, H, C = sz["dim"], sz["n_heads"], sz["kv_lora_rank"]
    N, R, V = sz["qk_nope_head_dim"], sz["qk_rope_head_dim"], sz["v_head_dim"]
    return (d * sz["q_lora_rank"] + sz["q_lora_rank"] * H * (N + R)
            + d * (C + R) + 2 * C * H * (N + V) + H * V * d)


def expert_layer_bytes(sz: dict, experts: Optional[float] = None) -> float:
    """One layer's expert weights THIS CHIP streams: ``experts`` of the
    experts it holds (None: all held), the shared expert, and the bf16
    router over every expert scored."""
    held = sz["n_experts"]
    n = held if experts is None else min(held, experts)
    scored = sz.get("router_width") or held
    return (3 * sz["dim"] * (n * sz["mlp_hidden"] + sz.get("shared_mlp_hidden", 0))
            + 2 * sz["dim"] * scored)


def head_bytes(sz: dict) -> int:
    return sz["dim"] * sz["vocab_size"]


def whole_model_bytes(sz: dict) -> float:
    """Every weight byte the chip holds: the layers, embedding and head."""
    return (sz["n_layers"] * (attention_layer_bytes(sz) + expert_layer_bytes(sz))
            + 2 * head_bytes(sz))


def gemm_stream_bytes(sz: dict, experts_streamed: Optional[float] = None) -> float:
    """Bytes one forward pass streams in the trace's three weight-GEMM
    categories (``mlp``, ``attn_proj``, ``lm_head``): the latent projections
    and W_o, the shared expert, the router, the held experts read, the head."""
    return (sz["n_layers"] * (attention_layer_bytes(sz)
                              + expert_layer_bytes(sz, experts_streamed))
            + head_bytes(sz))


def attention_least_seconds(sz: dict, decode_rows_read: float, window_pairs: float,
                            peaks: dict) -> float:
    """The least time the chip could take for the attention itself of the
    counted work. ``decode_rows_read``: cached rows the decode queries had
    before them, summed over layers (each is a row moved from HBM and one
    (query, key) pair). ``window_pairs``: (query, key) pairs of the prompt
    rows prefilled, ONE layer's. The larger of the bytes at peak bandwidth
    (the decode rows': a window's rows are shared by its queries and counted
    at nothing) and the operations at peak bf16."""
    pairs = decode_rows_read + window_pairs * sz["n_layers"]
    return max(decode_rows_read * latent_row_bytes(sz) / peaks["hbm_bytes_per_s"],
               pairs * attention_flops_per_pair(sz) / peaks["bf16_flops"])
