"""Bytes a model of linear-attention and full-attention layers needs (the gated
delta rule three layers to one, each followed by a dense MLP), computed from a
configuration's sizes under the program's ``ModelConfig`` field names
(``modelmap.fields``), as opsbytes_hybrid.py does for a model with state-space
layers. The program's own timers are not consulted; its COUNTS (forward passes,
live rows) are, because how many rows a pass holds is traffic, not shape."""

from __future__ import annotations


def kinds(sz: dict) -> str:
    """The kinds of the model's mixers: the pattern's first ``n_layers`` x
    ``mixers_per_layer`` characters."""
    return sz["layer_pattern"][:sz["n_layers"] * sz.get("mixers_per_layer", 1)]


def lin_values(sz: dict) -> int:
    """A token's values in a linear layer: heads x value_dim."""
    return sz["lin_value_heads"] * sz["lin_value_dim"]


def lin_conv_channels(sz: dict) -> int:
    """Channels under the convolution: [q | k | v]."""
    return 2 * sz["lin_key_heads"] * sz["lin_key_dim"] + lin_values(sz)


def lin_layer_bytes(sz: dict) -> int:
    """A linear layer's projections: int8 [W_q | W_k | W_v | W_g] and W_o, bf16
    W_a and W_b (the convolution's 4 x 11,520 taps, the decays, the step biases
    and the gains: 0.05M of 88.7M, left out)."""
    d = sz["dim"]
    return (d * (lin_conv_channels(sz) + lin_values(sz)) + lin_values(sz) * d
            + 2 * 2 * d * sz["lin_value_heads"])


def lin_state_bytes(sz: dict) -> int:
    """One sequence's state in ONE linear layer: float32 [key_dim, heads x
    value_dim] and the bf16 convolution tail."""
    return (4 * sz["lin_key_dim"] * lin_values(sz)
            + 2 * (sz["lin_conv"] - 1) * lin_conv_channels(sz))


def state_bytes_per_sequence(sz: dict) -> int:
    """What a snapshot keeps: every linear layer's state."""
    return kinds(sz).count("L") * lin_state_bytes(sz)


def attention_layer_bytes(sz: dict) -> int:
    d, hd = sz["dim"], sz["head_dim"]
    return 2 * d * hd * (sz["n_heads"] + sz["n_kv_heads"])


def mlp_layer_bytes(sz: dict) -> int:
    """A ``D`` mixer: gate, up and down."""
    return 3 * sz["dim"] * sz["dense_mlp_hidden"]


def head_bytes(sz: dict) -> int:
    return sz["dim"] * sz["vocab_size"]


def kv_bytes_per_token(sz: dict, itemsize: int = 2) -> int:
    """K and V of the full-attention layers alone, as the model has them (the
    pool's rows hold whole tiles of 8 heads: /health.kv_pool.bytes_per_token
    says what the memory holds)."""
    return kinds(sz).count("*") * 2 * sz["n_kv_heads"] * sz["head_dim"] * itemsize


def whole_model_bytes(sz: dict) -> int:
    """Every weight the chip holds: the mixers by kind, embedding, head."""
    per = {"L": lin_layer_bytes(sz), "*": attention_layer_bytes(sz),
           "D": mlp_layer_bytes(sz)}
    return sum(per[k] for k in kinds(sz)) + 2 * head_bytes(sz)


def gemm_stream_bytes(sz: dict) -> int:
    """Bytes one forward pass streams in the trace's three weight-GEMM categories
    (``mlp``, ``attn_proj``, ``lm_head``): the dense MLPs, the full-attention
    layers' projections, the head. The linear layers' projections run under
    ``lin/*`` scopes and are counted by ``lin_pass_bytes``."""
    k = kinds(sz)
    return (k.count("D") * mlp_layer_bytes(sz)
            + k.count("*") * attention_layer_bytes(sz) + head_bytes(sz))


def lin_pass_bytes(sz: dict) -> int:
    """Bytes the linear layers' projections stream in one forward pass, whatever
    rows it holds. The rows' matrix states (``lin_state_bytes`` a row a layer,
    read and written) are left out: the trace bills their device time to another
    category than the one these bytes are divided by (metrics/
    lin_mixer_roofline.json)."""
    return kinds(sz).count("L") * lin_layer_bytes(sz)
