"""How a configuration file's keys (the source's own names) map onto the
program's ``ModelConfig`` fields. Used by the launcher, the comparison with
the plain reference, and the ops/bytes functions. Imports nothing heavy."""

from __future__ import annotations

#: config-file key -> ModelConfig field. Every key on the left must be in the
#: file (head_dim and pad_token_id may sit under "assumed").
KEY_MAP = {
    "vocab_size": "vocab_size", "hidden_size": "dim",
    "num_hidden_layers": "n_layers", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "intermediate_size": "mlp_hidden", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "hidden_act": "activation",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "n_experts", "num_experts_per_tok": "experts_per_token",
    "bos_token_id": "bos_id", "pad_token_id": "pad_id",
    "max_position_embeddings": "max_seq_len",
}
MOE_KEYS = ("num_local_experts", "num_experts_per_tok")


def sizes(cfg_file: dict) -> dict:
    """The file's architecture keys, with the assumed ones folded in."""
    out = {}
    for key in KEY_MAP:
        if key in cfg_file:
            out[key] = cfg_file[key]
        elif key in cfg_file.get("assumed", {}):
            out[key] = cfg_file["assumed"][key]
        elif key not in MOE_KEYS:
            raise SystemExit(f"serve: configuration file lacks {key!r}")
    if cfg_file.get("sliding_window") is not None:
        raise SystemExit("serve: the program has no sliding-window attention; "
                         "a configuration that sets sliding_window cannot be served")
    out["eos_token_id"] = cfg_file["eos_token_id"]
    return out


def fold_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; the engine adds small offsets to it."""
    return int(seed) % 2_000_000_011
