"""HF safetensors checkpoint → framework parameter conversion.

Maps HuggingFace Llama/Gemma/Mixtral checkpoints onto the layer-stacked
param pytree ``transformer.init_params`` defines (SURVEY.md §7 hard part
"weight conversion fidelity" — validated by logit-parity tests against the
``transformers`` reference implementations in tests/test_convert.py).

Layout notes:
- HF ``nn.Linear`` stores [out_features, in_features]; our matmuls are
  ``x @ w`` so every projection is transposed on load.
- Per-layer tensors are stacked along a leading ``n_layers`` axis (the scan
  layout), so conversion is stream-friendly: one layer at a time, never two
  copies of the full model in host RAM.
- HF Llama/Gemma/Mixtral all use the rotate-half RoPE convention, matching
  ``ops.rope.apply_rope`` — no head permutation needed.
"""

from __future__ import annotations

import logging
from pathlib import Path
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

logger = logging.getLogger(__name__)


def _open_checkpoint(path: str | Path) -> Tuple[Callable[[str], np.ndarray], List[str]]:
    """Return (tensor_getter, key_list) over one or many .safetensors files."""
    from safetensors import safe_open

    path = Path(path)
    files = sorted(path.glob("*.safetensors")) if path.is_dir() else [path]
    if not files:
        raise FileNotFoundError(f"No .safetensors files under {path}")
    handles = [safe_open(str(f), framework="np") for f in files]
    index: Dict[str, Any] = {}
    for h in handles:
        for k in h.keys():
            index[k] = h
    keys = list(index)

    def get(key: str) -> np.ndarray:
        return index[key].get_tensor(key)

    return get, keys


def _to_dtype(x: np.ndarray, dtype) -> jnp.ndarray:
    return jnp.asarray(x).astype(dtype)


def convert_hf_checkpoint(
    cfg: ModelConfig,
    path: str | Path,
    dtype=jnp.bfloat16,
    quant: str = "",
    quantize_embed: bool = False,
) -> Dict[str, Any]:
    """Convert an HF checkpoint directory/file to framework params.

    ``quant`` ("" | "int8" | "int4"): quantize each projection DURING
    conversion, one layer at a time — the device never holds more than
    the (quantized) tree plus one layer's full-precision slice. Without
    this a 7B-class load would OOM a 16 GB chip before any post-hoc
    quantization could run: the bf16 tree alone is ~17 GB (VERDICT r4
    item 7 — the streaming-load + quantize transients at real size).
    int4 falls back per leaf to int8 where the kernel format can't tile
    (ops/quant4.py::pick_format). ``quantize_embed`` stores the
    embedding per-row int8 (the tied-head read halves).
    """
    if cfg.latent or cfg.router_width:
        raise NotImplementedError(
            f"{cfg.name}: no checkpoint mapping for latent attention or a "
            f"share of the experts (kv_lora_rank={cfg.kv_lora_rank}, "
            f"router_width={cfg.router_width}); it is served with seeded "
            f"weights only")
    if cfg.qk_norm or cfg.selects_keys:
        # The Keye-VL-2.0 family (QK-norm, a key selector with its own
        # projections, experts under the Qwen3-MoE names): its checkpoint
        # names are not published with what this repo has, and a guessed
        # mapping would load the wrong tensors in silence.
        raise NotImplementedError(
            f"{cfg.name}: no checkpoint mapping for a configuration with "
            f"QK-norm or a key selector (qk_norm={cfg.qk_norm}, index_topk="
            f"{cfg.index_topk}); it is served with seeded weights only")
    get, keys = _open_checkpoint(path)
    pfx = "model." if any(k.startswith("model.") for k in keys) else ""
    L = cfg.n_layers

    def t(key: str) -> np.ndarray:  # transpose linear
        return get(key).T

    def _quantize_slice(w: jnp.ndarray):
        """One layer's projection slice -> quantized leaf (or passthrough)."""
        from ..ops.quant import quantize_int8
        from ..ops.quant4 import pick_format, quantize_int4

        if quant == "int4":
            fmt = (pick_format(w.shape[-2], w.shape[-1])
                   if w.ndim == 2 else None)
            if fmt is not None:
                return quantize_int4(w, group_in=fmt[0], block_out=fmt[1])
            return quantize_int8(w)
        if quant == "int8":
            return quantize_int8(w)
        return w

    def _stack_leaves(parts: List[Any]):
        """Stack per-layer leaves ([in, out] arrays or quantized
        dataclasses) along a new leading L axis."""
        first = parts[0]
        if isinstance(first, jnp.ndarray):
            return jnp.stack(parts)
        import dataclasses as _dc

        kw = {f.name: jnp.stack([getattr(p, f.name) for p in parts])
              for f in _dc.fields(first) if f.name in ("q", "scale", "s")}
        return _dc.replace(first, **kw)

    def stack(fn: Callable[[int], np.ndarray]) -> jnp.ndarray:
        return jnp.stack([_to_dtype(fn(i), dtype) for i in range(L)])

    def qstack(fn: Callable[[int], np.ndarray]):
        """Stream-quantizing stack for projection leaves: load one layer,
        quantize on device, free the full-precision slice."""
        return _stack_leaves(
            [_quantize_slice(_to_dtype(fn(i), dtype)) for i in range(L)])

    layers: Dict[str, Any] = {
        "attn_norm": stack(lambda i: get(f"{pfx}layers.{i}.input_layernorm.weight")),
        "mlp_norm": stack(lambda i: get(f"{pfx}layers.{i}.post_attention_layernorm.weight")),
        "wq": qstack(lambda i: t(f"{pfx}layers.{i}.self_attn.q_proj.weight")),
        "wk": qstack(lambda i: t(f"{pfx}layers.{i}.self_attn.k_proj.weight")),
        "wv": qstack(lambda i: t(f"{pfx}layers.{i}.self_attn.v_proj.weight")),
        "wo": qstack(lambda i: t(f"{pfx}layers.{i}.self_attn.o_proj.weight")),
    }

    if cfg.is_moe:
        E = cfg.n_experts

        def eslice_q(i: int, part: str):
            """One layer's [E, in, out] expert stack, quantized per
            (layer, expert) slice (int8 even under int4 — the MoE einsum
            epilogues are int8-shaped)."""
            from ..ops.quant import quantize_int8

            parts = []
            for e in range(E):
                w = _to_dtype(
                    t(f"{pfx}layers.{i}.block_sparse_moe.experts.{e}."
                      f"{part}.weight"), dtype)
                parts.append(quantize_int8(w) if quant else w)
            return _stack_leaves(parts)

        layers["router"] = stack(
            lambda i: t(f"{pfx}layers.{i}.block_sparse_moe.gate.weight")
        )
        # experts.{e}.w1 = gate [F, D], w3 = up [F, D], w2 = down [D, F]
        layers["w_gate"] = _stack_leaves(
            [eslice_q(i, "w1") for i in range(L)])
        layers["w_up"] = _stack_leaves(
            [eslice_q(i, "w3") for i in range(L)])
        layers["w_down"] = _stack_leaves(
            [eslice_q(i, "w2") for i in range(L)])
    else:
        layers["w_gate"] = qstack(lambda i: t(f"{pfx}layers.{i}.mlp.gate_proj.weight"))
        layers["w_up"] = qstack(lambda i: t(f"{pfx}layers.{i}.mlp.up_proj.weight"))
        layers["w_down"] = qstack(lambda i: t(f"{pfx}layers.{i}.mlp.down_proj.weight"))

    if quantize_embed and quant:
        from ..ops.quant import quantize_embed_int8

        # Row-chunked quantization straight off the host array: the full
        # f32 working copy never materializes (quantize_embed_int8
        # chunks), and the bf16 copy is freed immediately after.
        embed = quantize_embed_int8(
            _to_dtype(get(f"{pfx}embed_tokens.weight"), dtype))
    else:
        embed = _to_dtype(get(f"{pfx}embed_tokens.weight"), dtype)

    params: Dict[str, Any] = {
        "embed": embed,
        "layers": layers,
        "final_norm": _to_dtype(get(f"{pfx}norm.weight"), dtype),
    }
    if not cfg.tie_embeddings:
        if "lm_head.weight" in keys:
            params["lm_head"] = _quantize_slice(
                _to_dtype(get("lm_head.weight").T, dtype))
        else:
            logger.warning("lm_head.weight absent; tying to embeddings")
            # Reuse the already-loaded embedding when it is still a plain
            # array — re-reading the checkpoint's largest tensor would be
            # a redundant full transfer; only a per-row-quantized embed
            # (whose scales are row-wise, not column-wise) forces a
            # fresh full-precision read.
            if isinstance(embed, jnp.ndarray):
                params["lm_head"] = _quantize_slice(embed.T)
            else:
                params["lm_head"] = _quantize_slice(
                    _to_dtype(get(f"{pfx}embed_tokens.weight").T, dtype))

    _validate_shapes(cfg, params)
    return params


def _validate_shapes(cfg: ModelConfig, params: Dict[str, Any]) -> None:
    d, hd, H, KV, L = cfg.dim, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.n_layers
    expect = {
        ("embed",): (cfg.vocab_size, d),
        ("final_norm",): (d,),
        ("layers", "wq"): (L, d, H * hd),
        ("layers", "wk"): (L, d, KV * hd),
        ("layers", "wv"): (L, d, KV * hd),
        ("layers", "wo"): (L, H * hd, d),
    }
    for keypath, shape in expect.items():
        node: Any = params
        for k in keypath:
            node = node[k]
        if tuple(node.shape) != shape:
            raise ValueError(
                f"Checkpoint/config mismatch at {'.'.join(keypath)}: "
                f"got {tuple(node.shape)}, expected {shape} for {cfg.name}"
            )
