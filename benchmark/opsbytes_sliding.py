"""Bytes and operations a model with attention layers of two kinds (full and
sliding, each with its own head count), a leading dense MLP and a chip's share
of its experts needs, computed from a configuration's sizes under the program's
``ModelConfig`` field names (``modelmap.fields``), as opsbytes_hybrid.py does
for a patterned model and opsbytes_mla.py for a latent one. The program's own
timers are not consulted; its COUNTS (keys read by kind, experts read, forward
passes) are, because how long the live contexts are and which experts a batch
picks is traffic, not shape."""

from __future__ import annotations

from typing import Optional


def mixers(sz: dict) -> str:
    """The kinds of the mixers served, in order: ``*`` full attention, ``S``
    sliding attention, ``D`` the dense MLP, ``E`` experts."""
    return sz["layer_pattern"][:sz["n_layers"] * sz.get("mixers_per_layer", 1)]


def heads(sz: dict, kind: str) -> int:
    return (sz.get("sliding_n_heads") or sz["n_heads"]) if kind == "S" else sz["n_heads"]


def kv_row_bytes(sz: dict, itemsize: int = 2) -> int:
    """Bytes of ONE token's K and V rows in one attention layer (4,096)."""
    return 2 * sz["n_kv_heads"] * sz["head_dim"] * itemsize


def pool_bytes_per_token(sz: dict, itemsize: int = 2) -> int:
    """What a token keeps in the paged pool: a K/V row for each FULL layer
    (12,288 at three of them; the sliding layers' rows are a bounded state a
    sequence and no part of it)."""
    return mixers(sz).count("*") * kv_row_bytes(sz, itemsize)


def sliding_state_bytes(sz: dict, itemsize: int = 2) -> int:
    """One sequence's sliding state as a snapshot keeps it: the span's K and V
    rows in every sliding layer (18.9 MB)."""
    return mixers(sz).count("S") * sz["sliding_window"] * kv_row_bytes(sz, itemsize)


def attention_flops_per_pair(sz: dict, kind: str) -> int:
    """FLOPs of ONE query row against ONE key in one layer of ``kind``: every
    head's score and its weighted sum over head_dim (24,576 full, 36,864
    sliding)."""
    return heads(sz, kind) * 2 * 2 * sz["head_dim"]


def attention_layer_bytes(sz: dict, kind: str) -> int:
    """One attention layer's weights as served: Wq, Wk, Wv, Wo int8 and the
    per-head gate's projection bf16."""
    d, hd, H, KV = sz["dim"], sz["head_dim"], heads(sz, kind), sz["n_kv_heads"]
    gate = 2 * d * H if sz.get("attn_gate") else 0
    return 2 * d * H * hd + 2 * d * KV * hd + gate


def dense_layer_bytes(sz: dict) -> int:
    return 3 * sz["dim"] * sz["dense_mlp_hidden"]


def expert_layer_bytes(sz: dict, experts: Optional[float] = None) -> float:
    """One expert layer's weights THIS CHIP streams: ``experts`` of the experts
    it holds (None: all held), the shared expert, and the bf16 router over
    every expert scored."""
    held = sz["n_experts"]
    n = held if experts is None else min(held, experts)
    scored = sz.get("router_width") or held
    return (3 * sz["dim"] * (n * sz["mlp_hidden"] + sz.get("shared_mlp_hidden", 0))
            + 2 * sz["dim"] * scored)


def head_bytes(sz: dict) -> int:
    return sz["dim"] * sz["vocab_size"]


def layers_bytes(sz: dict, experts_streamed: Optional[float] = None) -> float:
    per = {"*": attention_layer_bytes(sz, "*"), "S": attention_layer_bytes(sz, "S"),
           "D": dense_layer_bytes(sz), "E": expert_layer_bytes(sz, experts_streamed)}
    return sum(per[kind] for kind in mixers(sz))


def whole_model_bytes(sz: dict) -> float:
    """Every weight byte the chip holds: the mixers, embedding and head."""
    return layers_bytes(sz) + 2 * head_bytes(sz)


def gemm_stream_bytes(sz: dict, experts_streamed: Optional[float] = None) -> float:
    """Bytes one forward pass streams in the trace's three weight-GEMM
    categories (``mlp``, ``attn_proj``, ``lm_head``)."""
    return layers_bytes(sz, experts_streamed) + head_bytes(sz)


def attention_least_seconds(sz: dict, sliding_keys_read: float, full_keys_read: float,
                            window_pairs_sliding: float, window_pairs_full: float,
                            peaks: dict) -> float:
    """The least time the chip could take for the attention itself of the
    counted work, both kinds. ``*_keys_read``: keys the decode queries had to
    read, summed over that kind's layers (each a K and a V row moved from HBM
    and one (query, key) pair). ``window_pairs_*``: (query, key) pairs of the
    prompt rows prefilled, ONE layer's of that kind. The larger of the bytes at
    peak bandwidth (the decode rows': a window's rows are shared by its queries
    and counted at nothing) and the operations at peak bf16."""
    kinds = mixers(sz)
    flops = ((sliding_keys_read + window_pairs_sliding * kinds.count("S"))
             * attention_flops_per_pair(sz, "S")
             + (full_keys_read + window_pairs_full * kinds.count("*"))
             * attention_flops_per_pair(sz, "*"))
    moved = (sliding_keys_read + full_keys_read) * kv_row_bytes(sz)
    return max(moved / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops"])
