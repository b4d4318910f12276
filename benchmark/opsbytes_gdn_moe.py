"""Bytes a model of gated-delta-rule layers with fewer key heads than value
heads, gated attention layers and a chip's share of its experts beside a gated
shared expert needs (qwen3-next-80b-a3b-instruct-l12: three delta-rule layers to
one of attention, an expert layer behind each, 128 held of 512 experts),
computed from a configuration's sizes under the program's ``ModelConfig`` field
names (``modelmap.fields``), as opsbytes_linear.py and opsbytes_kda.py do for
the families it stands between. The program's own timers are not consulted; its
COUNTS (forward passes, rows moved, keys read, experts read) are, because how
many rows a pass holds, how long the live contexts are and which experts a batch
picks is traffic, not shape."""

from __future__ import annotations

from typing import Optional


def kinds(sz: dict) -> str:
    """The kinds of the model's mixers: the pattern's first ``n_layers`` x
    ``mixers_per_layer`` characters."""
    return sz["layer_pattern"][:sz["n_layers"] * sz.get("mixers_per_layer", 1)]


def lin_values(sz: dict) -> int:
    """A token's values in a delta-rule layer: VALUE heads x value_dim."""
    return sz["lin_value_heads"] * sz["lin_value_dim"]


def lin_keys(sz: dict) -> int:
    """A token's key (and query) channels: KEY heads x key_dim."""
    return sz["lin_key_heads"] * sz["lin_key_dim"]


def lin_conv_channels(sz: dict) -> int:
    """Channels under the convolution: [q | k | v]."""
    return 2 * lin_keys(sz) + lin_values(sz)


def lin_int8_params(sz: dict) -> int:
    """W_in [q | k | v | z] and W_out of a delta-rule layer."""
    return sz["dim"] * (lin_conv_channels(sz) + 2 * lin_values(sz))


def lin_layer_params(sz: dict) -> int:
    """Every parameter of a delta-rule layer: W_in, W_out, W_a and W_b a value
    head, the convolution's taps, A_log and dt_bias, the gated head norm's
    gain, the block norm's."""
    d, Hv = sz["dim"], sz["lin_value_heads"]
    return (lin_int8_params(sz) + 2 * d * Hv + sz["lin_conv"] * lin_conv_channels(sz)
            + 2 * Hv + sz["lin_value_dim"] + d)


def lin_layer_bytes(sz: dict) -> int:
    """A delta-rule layer's projections as a pass streams them: int8 W_in and
    W_out, bf16 W_a and W_b (the taps, decays, biases and gains, 0.04M of 33.7M,
    left out)."""
    return lin_int8_params(sz) + 2 * 2 * sz["dim"] * sz["lin_value_heads"]


def lin_matrix_bytes(sz: dict) -> int:
    """One sequence's float32 matrix state in ONE delta-rule layer, [key_dim,
    value heads x value_dim]: what a decode step's kernel reads once and writes
    once. A VALUE head's: the key heads' count does not enter."""
    return 4 * sz["lin_key_dim"] * lin_values(sz)


def lin_state_bytes(sz: dict) -> int:
    """... and with the bf16 convolution tail: what a snapshot keeps a layer."""
    return lin_matrix_bytes(sz) + 2 * (sz["lin_conv"] - 1) * lin_conv_channels(sz)


def state_bytes_per_sequence(sz: dict) -> int:
    return kinds(sz).count("L") * lin_state_bytes(sz)


def attention_layer_params(sz: dict) -> int:
    """Every parameter of a gated attention layer: W_q [q | gate] a head, W_k,
    W_v, W_o, a head's q and k gains, the block norm's."""
    d, hd, H, KV = sz["dim"], sz["head_dim"], sz["n_heads"], sz["n_kv_heads"]
    gate = d * H * hd if sz.get("attn_gate") == "elementwise" else 0
    return 2 * d * hd * (H + KV) + gate + (2 * hd if sz.get("qk_norm") else 0) + d


def attention_layer_bytes(sz: dict) -> int:
    """... as a pass streams them, all int8 (the gains left out)."""
    return attention_layer_params(sz) - sz["dim"] - (
        2 * sz["head_dim"] if sz.get("qk_norm") else 0)


def kv_bytes_per_key(sz: dict, itemsize: int = 2) -> int:
    """K and V of ONE live key in ONE attention layer (2 x 2 heads x 256 x 2 B)."""
    return 2 * sz["n_kv_heads"] * sz["head_dim"] * itemsize


def cache_bytes_per_token(sz: dict, itemsize: int = 2) -> int:
    """A token's rows in the pool: K and V in the attention layers alone."""
    return kinds(sz).count("*") * kv_bytes_per_key(sz, itemsize)


def expert_layer_params(sz: dict) -> int:
    """Every parameter of an expert layer as THIS CHIP holds it: the held
    experts, the shared expert and its scalar gate, the router over every
    expert scored, the block norm."""
    d, held = sz["dim"], sz["n_experts"]
    return (3 * d * (held * sz["mlp_hidden"] + sz.get("shared_mlp_hidden", 0))
            + d * (sz.get("router_width") or held)
            + (d if sz.get("shared_expert_gate") else 0) + d)


def expert_layer_bytes(sz: dict, experts: Optional[float] = None) -> float:
    """An expert layer's weights THIS CHIP streams in a pass: ``experts`` of the
    experts it holds (None: all held), the shared expert, the bf16 router over
    every expert scored and the bf16 gate of the shared expert."""
    d, held = sz["dim"], sz["n_experts"]
    n = held if experts is None else min(held, experts)
    return (3 * d * (n * sz["mlp_hidden"] + sz.get("shared_mlp_hidden", 0))
            + 2 * d * (sz.get("router_width") or held)
            + (2 * d if sz.get("shared_expert_gate") else 0))


def head_bytes(sz: dict) -> int:
    return sz["dim"] * sz["vocab_size"]


def param_count(sz: dict) -> int:
    """``ModelConfig.param_count`` from the sizes alone: the mixers by kind with
    their norms, embedding, head, the final norm."""
    per = {"L": lin_layer_params(sz), "*": attention_layer_params(sz),
           "E": expert_layer_params(sz)}
    return sum(per[k] for k in kinds(sz)) + 2 * head_bytes(sz) + sz["dim"]


def gemm_stream_bytes(sz: dict, experts_streamed: Optional[float] = None) -> float:
    """Bytes one forward pass streams in the trace's three weight-GEMM
    categories (``mlp``, ``attn_proj``, ``lm_head``): the expert layers (the held
    experts read, the shared expert, its gate, the router), the attention
    layers' projections, the head. The delta-rule layers' projections run under
    ``lin/*`` scopes and are counted by ``lin_pass_bytes``."""
    k = kinds(sz)
    return (k.count("E") * expert_layer_bytes(sz, experts_streamed)
            + k.count("*") * attention_layer_bytes(sz) + head_bytes(sz))


def lin_pass_bytes(sz: dict) -> int:
    """Bytes the delta-rule layers' projections stream in one forward pass,
    whatever rows it holds. The rows' matrix states are counted beside them, by
    the rows the program says its passes moved (``lin_matrix_bytes`` a row a
    layer, read once and written once; a window's prompt rows' states, read and
    written once a window, are left out: a lower bound)."""
    return kinds(sz).count("L") * lin_layer_bytes(sz)


def step_kernel_bytes(sz: dict, moving_row_layers: float) -> float:
    """Bytes the step kernel moves for ``moving_row_layers`` (row, delta-rule
    layer) pairs: each pair's matrix state once in and once out."""
    return moving_row_layers * 2 * lin_matrix_bytes(sz)


def of_family(sz: dict) -> bool:
    """A configuration whose pattern holds delta-rule layers with a decay a HEAD
    and expert layers (a decay a key channel is opsbytes_kda.py's family)."""
    return ("layer_pattern" in sz and "lin_value_heads" in sz
            and {"L", "E"} <= set(kinds(sz)) and not sz.get("lin_channel_decay"))
