"""How a configuration file's keys (the source's own names) map onto the
program's ``ModelConfig`` fields, and the mesh the file asks for. Used by the
runner, the launcher, the comparison with the plain reference, and the
ops/bytes functions. Imports nothing heavy."""

from __future__ import annotations

import math

#: config-file key -> ModelConfig field, for every configuration. A file's own
#: ``"keys": {"<source key>": "<ModelConfig field>"}`` extends it (key_map).
#: Every key on the left must be in the file (or under its "assumed").
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
#: keys of KEY_MAP a file may leave out (a dense model has no experts).
MOE_KEYS = ("num_local_experts", "num_experts_per_tok")
#: source keys that change the mathematics and that KEY_MAP has no field for:
#: a file that sets one is refused until its ``keys`` map it to a field.
NEEDS_A_FIELD = {"sliding_window": "the program has no sliding-window attention"}
#: the program's mesh axes (parallel/mesh.py::AXES) and MESH_SHAPE's short names
MESH_AXES = {"dp": "data", "ep": "expert", "pp": "pipe", "sp": "seq", "tp": "model",
             "data": "data", "expert": "expert", "pipe": "pipe", "seq": "seq",
             "model": "model"}


def key_map(cfg_file: dict) -> dict:
    """KEY_MAP with the file's own ``keys`` on top."""
    return {**KEY_MAP, **cfg_file.get("keys", {})}


def sizes(cfg_file: dict) -> dict:
    """The file's architecture keys (every key of its merged map), with the
    assumed ones folded in: what the plain reference receives as ``cfg``."""
    kmap = key_map(cfg_file)
    out = {}
    for key in kmap:
        if key in cfg_file:
            out[key] = cfg_file[key]
        elif key in cfg_file.get("assumed", {}):
            out[key] = cfg_file["assumed"][key]
        elif key not in MOE_KEYS:
            raise SystemExit(f"serve: configuration file lacks {key!r}")
    for key, why in NEEDS_A_FIELD.items():
        if cfg_file.get(key) is not None and key not in kmap:
            raise SystemExit(f"serve: {why}; a configuration that sets {key} cannot be "
                             f"served until its \"keys\" map it to a ModelConfig field")
    out["eos_token_id"] = cfg_file["eos_token_id"]
    return out


def fields(sz: dict, kmap: dict) -> dict:
    """ModelConfig field -> value: the view the ops/bytes functions count
    from, so a family that names a size otherwise needs no edit there."""
    out = {}
    for key, field in kmap.items():
        if key not in sz:
            continue
        if field in out and out[field] != sz[key]:
            raise SystemExit(f"serve: two keys of the file map to ModelConfig.{field} "
                             f"with different values ({out[field]!r}, {key}={sz[key]!r})")
        out[field] = sz[key]
    return out


def model_config(name: str, sz: dict, kmap: dict):
    """The program's ``ModelConfig`` built from the file's keys alone. A key
    mapped to a field the program lacks ends the run: a family the program's
    block does not cover needs the program changed first."""
    import dataclasses

    from ai_agent_kubectl_tpu.models.config import ModelConfig

    have = {f.name for f in dataclasses.fields(ModelConfig)}
    for key, field in kmap.items():
        if key in sz and field not in have:
            raise SystemExit(f"serve: {key} maps to ModelConfig.{field}, "
                             f"which the program does not have")
    eos = sz["eos_token_id"]
    eos_ids = tuple(eos) if isinstance(eos, (list, tuple)) else (eos,)
    return ModelConfig(name=name, eos_ids=eos_ids, **fields(sz, kmap))


def parse_mesh(spec: str) -> dict:
    """``MESH_SHAPE`` ("model:4", "tp=4,dp=1") as {axis: n} under the
    program's axis names (parallel/mesh.py::MeshConfig.parse's rule)."""
    out = {}
    for part in filter(None, (p.strip() for p in (spec or "").split(","))):
        name, _, val = part.replace(":", "=").partition("=")
        axis = MESH_AXES.get(name.strip().lower())
        if axis is None:
            raise SystemExit(f"bench: unknown mesh axis {name!r} in MESH_SHAPE {spec!r}")
        out[axis] = int(val)
    return out


def mesh_of(cfg_file: dict) -> dict:
    """The file's ``"mesh": {"<axis>": n}`` with only the axes larger than 1
    (absent or all 1: one device, the empty dict)."""
    mesh = cfg_file.get("mesh") or {}
    for axis in mesh:
        if MESH_AXES.get(axis) != axis:
            raise SystemExit(f"bench: unknown mesh axis {axis!r} in the configuration's "
                             f"mesh; the program's axes are {sorted(set(MESH_AXES.values()))}")
    return {a: int(n) for a, n in mesh.items() if int(n) > 1}


def rehearsal_mesh(mesh: dict) -> dict:
    """The rehearsal's toy widths have 2 KV heads: every axis of the
    configuration's mesh is cut to 2, on CPU devices the host platform is
    told to pretend (run.py::child_env)."""
    return {axis: 2 for axis in mesh}


def mesh_problems(cfg_file: dict, chips: int) -> list:
    """Why this configuration cannot run as a cell of ``chips`` chips: the
    mesh's product, the cell's chips and ``server_env.MESH_SHAPE`` must say
    the same. Checked before anything is launched."""
    mesh = mesh_of(cfg_file)
    spec = cfg_file.get("server_env", {}).get("MESH_SHAPE", "")
    served = {a: n for a, n in parse_mesh(spec).items() if n > 1}
    problems = []
    if math.prod(mesh.values()) != chips:
        problems.append(f"the configuration's mesh {mesh or 'of one device'} is "
                        f"{math.prod(mesh.values())} devices, the cell asks for {chips} chips")
    if served != mesh:
        problems.append(f"server_env.MESH_SHAPE {spec!r} is {served or 'one device'}, "
                        f"the configuration's mesh is {mesh or 'one device'}")
    return problems


def fold_seed(seed: int) -> int:
    """--seed may exceed 32 signed bits; the engine adds small offsets to it."""
    return int(seed) % 2_000_000_011
