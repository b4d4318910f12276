"""Bytes a model of Mamba-2 and attention layers, each followed by a dense MLP,
needs (the granitemoehybrid family without experts), computed from a
configuration's sizes under the program's ``ModelConfig`` field names
(``modelmap.fields``), as opsbytes_hybrid.py does for a model of one mixer a
layer and opsbytes_linear.py for linear attention. The program's own timers are
not consulted; its COUNTS (forward passes, windows, rows that did not move) are,
because how many rows a pass moves is traffic, not shape."""

from __future__ import annotations


def kinds(sz: dict) -> str:
    """The kinds of the model's mixers: the pattern's first ``n_layers`` x
    ``mixers_per_layer`` characters (opsbytes_hybrid.kinds takes ``n_layers``
    of them and would count half of this model)."""
    return sz["layer_pattern"][:sz["n_layers"] * sz.get("mixers_per_layer", 1)]


def ssm_inner(sz: dict) -> int:
    return sz["ssm_heads"] * sz["ssm_head_dim"]


def ssm_conv_channels(sz: dict) -> int:
    """Channels under the convolution: [x | B | C]."""
    return ssm_inner(sz) + 2 * sz["ssm_groups"] * sz["ssm_state"]


def ssm_layer_bytes(sz: dict) -> int:
    """A Mamba-2 layer's two int8 projections (what a pass streams)."""
    return (sz["dim"] * (ssm_inner(sz) + ssm_conv_channels(sz) + sz["ssm_heads"])
            + ssm_inner(sz) * sz["dim"])


def ssm_small_leaves(sz: dict) -> int:
    """... and its small leaves' elements: the convolution's taps and bias, the
    step biases, decays and D a head, the gated norm's gain."""
    return ((sz["ssm_conv"] + 1) * ssm_conv_channels(sz) + 3 * sz["ssm_heads"]
            + ssm_inner(sz))


def ssm_matrix_state_bytes(sz: dict) -> int:
    """One sequence's float32 [heads, head_dim, state] in ONE layer: what the
    decode step's kernel reads once and writes once for a row that moves."""
    return 4 * ssm_inner(sz) * sz["ssm_state"]


def ssm_state_bytes(sz: dict) -> int:
    """... with the bf16 convolution tail: a layer's share of a snapshot."""
    return (ssm_matrix_state_bytes(sz)
            + 2 * (sz["ssm_conv"] - 1) * ssm_conv_channels(sz))


def state_bytes_per_sequence(sz: dict) -> int:
    """What a snapshot keeps and a restore copies: every Mamba layer's state."""
    return kinds(sz).count("M") * ssm_state_bytes(sz)


def attention_layer_bytes(sz: dict) -> int:
    d, hd = sz["dim"], sz["head_dim"]
    return 2 * d * hd * (sz["n_heads"] + sz["n_kv_heads"])


def mlp_layer_bytes(sz: dict) -> int:
    """A ``D`` mixer: gate, up and down."""
    return 3 * sz["dim"] * sz["dense_mlp_hidden"]


def head_bytes(sz: dict) -> int:
    """The embedding, which is the head too where the two are tied."""
    return sz["dim"] * sz["vocab_size"]


def kv_bytes_per_token(sz: dict, itemsize: int = 2) -> int:
    """K and V of the attention layers alone."""
    return kinds(sz).count("*") * 2 * sz["n_kv_heads"] * sz["head_dim"] * itemsize


def param_count(sz: dict) -> int:
    """Every parameter, as ``ModelConfig.param_count`` counts them: the mixers
    by kind with their norm's gain, the embedding (once where the head is tied
    to it), the final norm."""
    d = sz["dim"]
    per = {"M": ssm_layer_bytes(sz) + ssm_small_leaves(sz),
           "*": attention_layer_bytes(sz), "D": mlp_layer_bytes(sz)}
    heads = 1 if sz.get("tie_embeddings") else 2
    return sum(per[k] + d for k in kinds(sz)) + heads * head_bytes(sz) + d


def gemm_stream_bytes(sz: dict) -> int:
    """Bytes one forward pass streams in the trace's three weight-GEMM categories
    (``mlp``, ``attn_proj``, ``lm_head``): the dense MLPs, the attention layers'
    projections, the head ONCE (the tied embedding is read whole as the head;
    as the embedding a pass gathers its rows). The Mamba layers' projections run
    under ``ssm/*`` scopes and are counted by ``ssm_pass_bytes``."""
    k = kinds(sz)
    return (k.count("D") * mlp_layer_bytes(sz)
            + k.count("*") * attention_layer_bytes(sz) + head_bytes(sz))


def ssm_pass_bytes(sz: dict) -> int:
    """Bytes the Mamba layers' projections stream in one forward pass, whatever
    rows it holds."""
    return kinds(sz).count("M") * ssm_layer_bytes(sz)


def step_kernel_bytes(sz: dict, moving_row_layers: float) -> float:
    """Least bytes the decode step's kernel moves for ``moving_row_layers``
    (row, layer) pairs whose row moved: the matrix state read once and written
    once. A row that does not move is passed over and moves nothing."""
    return 2.0 * moving_row_layers * ssm_matrix_state_bytes(sz)
