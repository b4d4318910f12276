"""Operations and bytes a kernel needs, computed from a configuration's sizes
(the keys of its file; see modelmap.py). The program's own counters are not
consulted."""

from __future__ import annotations


def weight_stream_bytes(sz: dict, weight_bytes: int = 1) -> int:
    """Bytes of projection weights one forward pass streams from HBM: every
    attention and MLP matrix of every layer and the output head, at
    ``weight_bytes`` a weight (1: int8). A mixture-of-experts layer counts
    all its experts: the program evaluates every expert (parallel/moe.py::
    dense_moe), so every expert's weights cross HBM each pass. Embedding rows
    (a gather of batch x window rows), norms, the router and the
    per-channel scales are left out (under 0.1% of the bytes)."""
    d, hd = sz["hidden_size"], sz["head_dim"]
    h, kv = sz["num_attention_heads"], sz["num_key_value_heads"]
    f = sz["intermediate_size"]
    experts = max(1, sz.get("num_local_experts", 0) or 0)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = experts * 3 * d * f
    return (sz["num_hidden_layers"] * (attn + mlp) + d * sz["vocab_size"]) * weight_bytes


def weight_gemm_flops_per_token(sz: dict) -> int:
    """Multiply-adds x 2 of the same matrices for one token (all experts
    evaluated, as the program does)."""
    return 2 * weight_stream_bytes(sz, 1)


def kv_bytes_per_token(sz: dict, kv_bytes: int = 2) -> int:
    return 2 * sz["num_hidden_layers"] * sz["num_key_value_heads"] * sz["head_dim"] * kv_bytes
