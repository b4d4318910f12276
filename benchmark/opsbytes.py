"""Operations and bytes a kernel needs, computed from a configuration's sizes
under the program's ``ModelConfig`` field names (``modelmap.fields`` of the
file's keys; a family that names a size otherwise says so in its file's
``keys``). The program's own counters are not consulted."""

from __future__ import annotations

from typing import Optional


def weight_stream_bytes(sz: dict, weight_bytes: int = 1, shards: int = 1,
                        experts_streamed: Optional[float] = None,
                        expert_shards: int = 1) -> float:
    """Bytes of projection weights ONE CHIP streams from its HBM in one
    forward pass: every attention and MLP matrix of every layer and the
    output head, at ``weight_bytes`` a weight (1: int8). Every one of them is
    split by the Megatron policy (parallel/sharding.py::param_specs: columns or
    rows over the mesh's ``model`` axis, experts also over ``expert``), so a
    chip holds and streams 1/``shards`` of each, and 1/(``shards`` x
    ``expert_shards``) of the experts.

    ``experts_streamed`` is how many experts' weights cross HBM in a layer's
    pass. The default, all of them, is what the program does today: it
    evaluates every expert (parallel/moe.py::dense_moe). A configuration whose
    program reads only the experts its tokens picked sets it from a counter
    (readers/trace_roofline.py). Embedding rows (a gather of batch x window
    rows), norms, the router and the per-channel scales are left out (under
    0.1% of the bytes)."""
    d, hd = sz["dim"], sz["head_dim"]
    h, kv = sz["n_heads"], sz["n_kv_heads"]
    f = sz["mlp_hidden"]
    experts = max(1, sz.get("n_experts", 0) or 0)
    if experts_streamed is not None:
        experts = min(experts, experts_streamed)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = experts * 3 * d * f
    whole = sz["n_layers"] * (attn + mlp / expert_shards) + d * sz["vocab_size"]
    return whole * weight_bytes / shards


def weight_gemm_flops_per_token(sz: dict) -> float:
    """Multiply-adds x 2 of the same matrices for one token, on all chips
    together (all experts evaluated, as the program does)."""
    return 2 * weight_stream_bytes(sz, 1)


def kv_bytes_per_token(sz: dict, kv_bytes: int = 2) -> int:
    return 2 * sz["n_layers"] * sz["n_kv_heads"] * sz["head_dim"] * kv_bytes
