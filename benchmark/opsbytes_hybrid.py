"""Bytes a model of one mixer a layer needs (state-space, expert and attention
layers in a pattern), computed from a configuration's sizes under the program's
``ModelConfig`` field names (``modelmap.fields``), as opsbytes.py does for a
model whose layers are all alike. The program's own timers are not consulted;
its COUNTS (experts read, forward passes, live rows) are, because which experts
a batch picks and how many rows a pass holds is traffic, not shape."""

from __future__ import annotations

from typing import Optional


def kinds(sz: dict) -> str:
    """The kinds of the model's layers: the pattern's first ``n_layers``."""
    return sz["layer_pattern"][:sz["n_layers"]]


def expert_matrices(sz: dict) -> int:
    """An expert's matrices: two for ``relu2`` (no gate), else three."""
    return 2 if sz.get("activation") == "relu2" else 3


def attention_layer_bytes(sz: dict) -> int:
    d, hd = sz["dim"], sz["head_dim"]
    return d * sz["n_heads"] * hd + 2 * d * sz["n_kv_heads"] * hd + sz["n_heads"] * hd * d


def expert_layer_bytes(sz: dict, experts: Optional[float] = None) -> float:
    """One expert layer's int8 weights with ``experts`` of its routed experts
    (None: all), the shared expert and the router's bf16 [d, E] left out as
    opsbytes.py leaves routers out (0.3M of 1,297M)."""
    n = sz["n_experts"] if experts is None else min(sz["n_experts"], experts)
    mats = expert_matrices(sz)
    return mats * sz["dim"] * (n * sz["mlp_hidden"] + sz.get("shared_mlp_hidden", 0))


def ssm_layer_bytes(sz: dict) -> int:
    """A Mamba-2 layer's two projections (convolution, biases, decays and the
    gate norm's gain: 0.03M of 38.7M, left out)."""
    inner = sz["ssm_heads"] * sz["ssm_head_dim"]
    conv = inner + 2 * sz["ssm_groups"] * sz["ssm_state"]
    return sz["dim"] * (inner + conv + sz["ssm_heads"]) + inner * sz["dim"]


def ssm_state_bytes(sz: dict) -> int:
    """One sequence's recurrent state in ONE layer: float32 [heads, head_dim,
    state] and the bf16 convolution tail."""
    inner = sz["ssm_heads"] * sz["ssm_head_dim"]
    conv = inner + 2 * sz["ssm_groups"] * sz["ssm_state"]
    return 4 * inner * sz["ssm_state"] + 2 * (sz["ssm_conv"] - 1) * conv


def head_bytes(sz: dict) -> int:
    return sz["dim"] * sz["vocab_size"]


def whole_model_bytes(sz: dict) -> float:
    """Every int8 weight the chip holds: the layers by kind, embedding, head."""
    per = {"M": ssm_layer_bytes(sz), "E": expert_layer_bytes(sz),
           "*": attention_layer_bytes(sz)}
    return sum(per[k] for k in kinds(sz)) + 2 * head_bytes(sz)


def gemm_stream_bytes(sz: dict, experts_streamed: Optional[float] = None) -> float:
    """Bytes one forward pass streams in the trace's three weight-GEMM
    categories (``mlp``, ``attn_proj``, ``lm_head``): the expert layers'
    experts read (x 2 matrices) and shared expert, the attention layers'
    projections, the head. The state-space layers' projections run under
    ``ssm/*`` scopes and are counted by ``ssm_pass_bytes``."""
    k = kinds(sz)
    return (k.count("E") * expert_layer_bytes(sz, experts_streamed)
            + k.count("*") * attention_layer_bytes(sz) + head_bytes(sz))


def ssm_pass_bytes(sz: dict, rows: float) -> float:
    """Least bytes the state-space layers move in one forward pass that holds
    ``rows`` sequences: both projections once, and every row's state read
    and written."""
    return kinds(sz).count("M") * (ssm_layer_bytes(sz) + 2 * rows * ssm_state_bytes(sz))
