"""The benchmark harness's own cases (benchmark/tests/test_harness.py, PR 26:
a configuration the harness was not written for comes in as files), counted in
tier-1 since PR 27 (PERF.md Open question 13a), plus the four-chip
configuration that PR 27 brought in as files only. One parametrised test, a
case each. ``benchmark/`` is not a package: its modules are found by path, as
``benchmark/tests/conftest.py`` finds them."""

import copy
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmark"
sys.path.insert(0, str(BENCH))

import modelmap  # noqa: E402
import run as R  # noqa: E402
import xtrace  # noqa: E402

RULES = json.loads((BENCH / "trace_categories.json").read_text())


def ev(name, start_us, dur_us, scope=""):
    """A trace event as xtrace's reports hold them (benchmark/tests/test_xtrace.py)."""
    return [name, start_us * 1000, dur_us * 1000, scope]

#: https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json
#: (the catalog's ``config``), with what a configuration file adds around it.
OLMOE = {
    "name": "olmoe-1b-7b-0125-instruct", "attention_bias": False, "clip_qkv": None,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe", "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 16, "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000, "tie_word_embeddings": False,
    "vocab_size": 50304, "eos_token_id": 50279,
    "assumed": {"head_dim": 128, "pad_token_id": 1, "bos_token_id": 50279},
    "keys": {"num_experts": "n_experts"},
}


def shipped(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def meshed(mesh, mesh_shape):
    cfg = shipped("mistral-7b-instruct-v0.2")
    cfg["mesh"] = mesh
    cfg["server_env"]["MESH_SHAPE"] = mesh_shape
    return cfg


#: what engine/batcher.py::sharding_health reports for a sound model:4 server
SOUND = {"mesh": {"data": 1, "expert": 1, "pipe": 1, "seq": 1, "model": 4}, "devices": 4,
         "residual_tp_fraction": 1.0, "weights_shard_fraction": 0.25, "pool_sharded": True,
         "kv_pool_mesh_fallback": False, "draft_sharded": False, "draft_kv_fallback": False,
         "attention_regime": "ragged"}


def health(**sharding):
    return {"engine": "jax-batched", "model": "mistral-7b-instruct-v0.2", "platform": "tpu",
            "device_kind": "TPU v5 lite", "devices": 4,
            "kv_pool": {"attention_regime": "ragged"}, "sharding": {**SOUND, **sharding}}


def check(h, mesh={"model": 4}):
    peaks = json.loads((BENCH / "peaks.json").read_text())
    return R.check_health(h, shipped("mistral-7b-instruct-v0.2"), peaks, {"chips": 4}, mesh, False)


def case_own_keys_reach_the_model_config():
    import serve

    cfg, sz = serve.register(OLMOE)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.mlp_hidden) == (64, 8, 1024)
    assert sz["num_experts"] == 64 and "num_local_experts" not in sz    # what the reference gets
    f = modelmap.fields(sz, modelmap.key_map(OLMOE))
    assert f["n_experts"] == 64 and f["dim"] == 2048
    # without its "keys" the file's experts would be dropped in silence: a dense model
    dense = {k: v for k, v in OLMOE.items() if k != "keys"}
    assert serve.register(dense)[0].n_experts == 0


def case_a_field_the_program_lacks_ends_the_run():
    import serve

    bad = dict(OLMOE, keys={"num_experts": "n_experts", "norm_topk_prob": "norm_topk_prob"})
    with pytest.raises(SystemExit) as e:
        serve.register(bad)
    assert str(e.value) == ("serve: norm_topk_prob maps to ModelConfig.norm_topk_prob, "
                            "which the program does not have")


def case_two_keys_for_one_field_must_agree():
    both = dict(OLMOE, num_local_experts=8)
    with pytest.raises(SystemExit, match="two keys of the file map to ModelConfig.n_experts"):
        modelmap.model_config("x", modelmap.sizes(both), modelmap.key_map(both))


def case_sliding_window_is_refused_only_while_unmapped():
    cfg = dict(shipped("mistral-7b-instruct-v0.2"), sliding_window=4096)
    with pytest.raises(SystemExit, match="no sliding-window attention"):
        modelmap.sizes(cfg)
    mapped = dict(cfg, keys={"sliding_window": "sliding_window"})
    assert modelmap.sizes(mapped)["sliding_window"] == 4096      # now the field must exist
    # ... and since PR 40 it does: the key reaches it
    built = modelmap.model_config("x", modelmap.sizes(mapped), modelmap.key_map(mapped))
    assert built.sliding_window == 4096
    # a key mapped to a field the program lacks still ends the run there
    lacking = dict(cfg, keys={"sliding_window": "no_such_field"})
    with pytest.raises(SystemExit, match="ModelConfig.no_such_field, which the program"):
        modelmap.model_config("x", modelmap.sizes(lacking), modelmap.key_map(lacking))


def case_a_collective_inside_a_scope_is_billed_to_collectives():
    mlp = "jit(chunk)/while/body/while/body/mlp/dot_general"
    head = "jit(chunk)/while/body/lm_head/dot_general"
    ops = [ev("%fusion.5 = bf16[] fusion(...)", 0, 60, mlp),
           ev("%all-reduce.4 = bf16[] all-reduce(...)", 60, 30, mlp),
           ev("%all-gather-start.2 = bf16[] all-gather-start(...)", 90, 5, "jit(chunk)/o_proj/x"),
           ev("%fusion.9 = f32[] fusion(...)", 100, 20, head)]
    plane = lambda i: {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules", "events": [ev("jit_chunk(1)", 0, 120)]},
        {"name": "XLA Ops", "events": copy.deepcopy(ops)}]}
    r = xtrace.reduce({"planes": [plane(i) for i in range(4)]}, RULES, 2, chips=4)
    assert r["problems"] == [] and r["devices"] == 4 and r["forward_passes"] == 1
    assert r["category_s"]["collectives"] == pytest.approx(35e-6)
    assert r["category_s"]["mlp"] == pytest.approx(60e-6)       # and to nothing else
    assert "attn_proj" not in r["category_s"]
    assert ["collectives:all-reduce", 30e-6] in [[n, pytest.approx(s)] for n, s in
                                                 r["breakdown"]["device_ops"]]
    # fewer planes than chips, or planes that ran different passes: not this cell
    three = xtrace.reduce({"planes": [plane(i) for i in range(3)]}, RULES, 2, chips=4)
    assert three["problems"] == ["the trace holds 3 device planes, the cell runs on 4 chips"]
    odd = plane(3)      # up to 2 apart is the capture's two edges
    odd["lines"][1]["events"] += [ev("%fusion.9 = f32[] fusion(...)", 130 + 25 * i, 20, head)
                                  for i in range(3)]
    uneven = xtrace.reduce({"planes": [plane(0), plane(1), plane(2), odd]}, RULES, 2, chips=4)
    assert uneven["problems"] == [
        "the device planes ran different numbers of forward passes: [1, 1, 1, 4]"]


def case_a_sound_sharded_server_passes():
    assert check(health()) == []


def case_a_fallback_or_a_wrong_mesh_is_refused():
    assert "kv_pool_mesh_fallback" in check(health(kv_pool_mesh_fallback=True))[0]
    assert "pool_sharded" in check(health(pool_sharded=False))[0]
    assert "weights_shard_fraction 1.0" in check(health(weights_shard_fraction=1.0))[0]
    two = check(health(mesh={"data": 2, "model": 2}, weights_shard_fraction=0.5))
    assert any("sharding.mesh {'data': 2, 'model': 2}, want {'model': 4}" in p for p in two)
    assert "sharding.devices 2" in check(health(devices=2))[0]
    assert "attention_regime 'gather'" in check(health(attention_regime="gather"))[0]
    unsharded = dict(health(), sharding=None)       # a server that built no mesh
    assert "no sharding" in check(unsharded)[0]
    assert check(unsharded, mesh={}) == []          # which is what a one-chip cell wants


def case_mesh_chips_and_mesh_shape_must_agree():
    assert modelmap.mesh_problems(shipped("mistral-7b-instruct-v0.2"), 1) == []
    assert modelmap.mesh_problems(meshed({"model": 4}, "model:4"), 4) == []
    assert modelmap.mesh_problems(meshed({"model": 4, "data": 1}, "tp=4"), 4) == []
    assert "the cell asks for 1 chips" in modelmap.mesh_problems(meshed({"model": 4}, "model:4"), 1)[0]
    assert "MESH_SHAPE ''" in modelmap.mesh_problems(meshed({"model": 4}, ""), 4)[0]
    assert "MESH_SHAPE 'model:2'" in modelmap.mesh_problems(meshed({"model": 4}, "model:2"), 4)[0]
    assert len(modelmap.mesh_problems(shipped("mistral-7b-instruct-v0.2"), 4)) == 1
    with pytest.raises(SystemExit, match="unknown mesh axis 'tensor'"):
        modelmap.mesh_of({"mesh": {"tensor": 4}})


def case_a_disagreement_is_refused_before_launch(monkeypatch, capsys):
    cell = {"name": "x", "config": "c", "traffic": "chat-steady", "chips": 4}
    monkeypatch.setattr(R, "resolve_cell", lambda bench, workload: (
        cell, {}, meshed({"model": 4}, "model:2"), {}))
    monkeypatch.setattr(R, "launch", lambda *a, **k: pytest.fail("launched"))
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", "x", "--seed", "1", "--seconds", "1"])
    assert R.main() == 2
    assert "MESH_SHAPE 'model:2'" in capsys.readouterr().err


def case_the_roofline_reader_counts_a_chips_share():
    spec = importlib.util.spec_from_file_location("r", BENCH / "readers" / "trace_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    cfg = shipped("mixtral-8x7b-instruct-v0.1-l6")
    sz = modelmap.sizes(cfg)
    trace = {"forward_passes": 5.0, "category_s": {"mlp": 0.00018, "lm_head": 0.000115}}
    ctx = {"trace": trace, "trace_rules": RULES, "sizes": sz, "config": cfg, "mesh": {},
           "fields": modelmap.fields(sz, modelmap.key_map(cfg)),
           "peaks": {"hbm_bytes_per_s": 819e9}}
    whole = reader.read(ctx, {})
    assert whole == 18291.109670743568        # the parent's reading of this trace, to the digit
    assert reader.read(dict(ctx, mesh={"model": 4}), {}) == pytest.approx(whole / 4)
    # experts_streamed: a number, or a counter over a count of layer passes under /health
    two = reader.read(dict(ctx, config=dict(cfg, experts_streamed=2)), {})
    counted = dict(cfg, experts_streamed={"counter": ["moe", "experts_read"],
                                          "per": ["moe", "layer_passes"]})
    probes = {"health_before": {"moe": {"experts_read": 100, "layer_passes": 10}},
              "health_after": {"moe": {"experts_read": 300, "layer_passes": 110}}}
    assert reader.read(dict(ctx, config=counted, **probes), {}) == pytest.approx(two)
    assert two < whole
    with pytest.raises(LookupError):          # the file names a counter the program lacks
        reader.read(dict(ctx, config=counted), {})


def case_the_comparison_runs_over_a_two_device_mesh(tmp_path):
    """--rehearse of refcheck.py: toy widths, the file's mesh cut to two of
    four pretended CPU devices, placed by the program's own sharding policy."""
    cfg = meshed({"model": 4}, "model:4")
    (tmp_path / "cfg.json").write_text(json.dumps(cfg))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    subprocess.run([sys.executable, str(BENCH / "refcheck.py"), "--config",
                    str(tmp_path / "cfg.json"), "--seed", "11", "--rehearse",
                    "--out", str(tmp_path / "out.json")], env=env, check=True, timeout=600)
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["ok"] and out["mesh"] == {"model": 2} and out["devices"] == 2
    assert out["rel_err"] < out["tolerance_rel"] and out["positions_clear"] == 167


def case_a_reference_may_bring_its_own_weights(tmp_path):
    import refcheck

    ref = tmp_path / "ref.py"
    ref.write_text("def forward(cfg, weights, tokens):\n    return weights\n"
                   "def weights_from_program(params, n_layers):\n    return 'own'\n")
    mod = refcheck.load_reference(str(ref))
    assert refcheck.weights_function(mod)(None, 2) == "own"
    shipped_ref = refcheck.load_reference(shipped("mistral-7b-instruct-v0.2")["reference"])
    assert refcheck.weights_function(shipped_ref) is refcheck.reference_weights


def case_a_configuration_names_its_rehearsal_model():
    moe = shipped("mixtral-8x7b-instruct-v0.1-l6")
    assert R.child_env(moe, 1, True)["MODEL_NAME"] == "toy-moe"
    assert R.child_env(shipped("mistral-7b-instruct-v0.2"), 1, True)["MODEL_NAME"] == "toy-8m"
    env = R.child_env(meshed({"model": 4}, "model:4"), 1, True)
    assert env["MESH_SHAPE"] == "model:2" and env["XLA_FLAGS"].endswith("device_count=2")
    assert R.child_env(moe, 1, False)["MODEL_NAME"] == moe["name"]


def case_the_four_chip_configuration_is_a_cell_of_four_chips_only():
    """mixtral-8x7b-instruct-v0.1 (PR 27): the file's mesh, its MESH_SHAPE and
    the cell's chips say the same, so a one-chip cell over it is refused
    before launch; nothing of its source is reduced; it rehearses on the toy
    expert model over a mesh cut to 2."""
    cfg = shipped("mixtral-8x7b-instruct-v0.1")
    assert modelmap.mesh_of(cfg) == {"model": 4}
    assert modelmap.mesh_problems(cfg, 4) == []
    assert "the cell asks for 1 chips" in modelmap.mesh_problems(cfg, 1)[0]
    assert cfg["num_hidden_layers"] == 32 and cfg["reduced"] == {}
    assert "weights_in_one_call" not in cfg
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, "mixtral8x7b-tp4-chat-steady")
    assert (cell["chips"], entry["reduced"], file, mix["name"]) == (4, [], cfg, "chat-steady")
    l6 = shipped("mixtral-8x7b-instruct-v0.1-l6")
    widths = [k for k in modelmap.sizes(cfg) if k != "num_hidden_layers"]
    assert all(modelmap.sizes(cfg)[k] == modelmap.sizes(l6)[k] for k in widths)
    differ = {k for k in cfg["server_env"] if cfg["server_env"][k] != l6["server_env"].get(k)}
    assert differ == {"MESH_SHAPE", "KV_POOL_BLOCKS"}
    env = R.child_env(cfg, 1, True)
    assert (env["MODEL_NAME"], env["MESH_SHAPE"]) == ("toy-moe", "model:2")


#: https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json as
#: the catalog beside the model-configs guide has it: every number of it.
KEYE_SOURCE = {
    "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48, "moe_intermediate_size": 768,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06, "rope_theta": 10000000, "vocab_size": 151936}
KEYE_GROUPS = {
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}}


def case_the_keye_configuration_is_its_source_cut_in_depth_alone():
    """keye-vl-2.0-30b-a3b-l8 (PR 31): every number of the source under its own
    key, depth the one cut, sa_config's entries also flat (modelmap.sizes reads
    the top level), every mapped key reaching the ModelConfig, and the bytes the
    file states are the bytes the ops/bytes functions count."""
    import opsbytes
    import serve

    cfg = shipped("keye-vl-2.0-30b-a3b-l8")
    differ = {k for k, v in KEYE_SOURCE.items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers"} == set(cfg["reduced"])
    assert cfg["reduced"]["num_hidden_layers"] == {**cfg["reduced"]["num_hidden_layers"],
                                                  "source": 48, "here": 8}
    assert all(cfg[g] == v for g, v in KEYE_GROUPS.items())
    assert all(cfg[k] == v for k, v in KEYE_GROUPS["sa_config"].items())      # flat too
    model, sz = serve.register(cfg)
    assert (model.n_layers, model.n_experts, model.experts_per_token) == (8, 128, 8)
    assert (model.mlp_hidden, model.dense_mlp_hidden, model.dim) == (768, 6144, 2048)
    assert (model.index_topk, model.index_heads, model.index_head_dim) == (2048, 16, 64)
    assert model.qk_norm and model.selects_keys and model.grouped_experts
    assert (model.n_heads, model.n_kv_heads, model.head_dim, model.vocab_size) == (32, 4, 128, 151936)
    assert sz["topk"] == 2048 and sz["moe_intermediate_size"] == 768     # what the reference gets
    f = modelmap.fields(sz, modelmap.key_map(cfg))
    layer = 128 * 3 * 2048 * 768 + 2 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128
    assert opsbytes.weight_stream_bytes(f) == 8 * layer + 2048 * 151936
    indexer_and_router = 8 * 2048 * (16 * 64 + 64 + 16 + 128)
    total = 8 * layer + indexer_and_router + 2 * 2048 * 151936
    assert round(total / 1e9, 2) == cfg["sizing"]["weights_GB"] == 5.63
    assert opsbytes.weight_stream_bytes(f, experts_streamed=82) < 0.7 * opsbytes.weight_stream_bytes(f)
    sizing = cfg["sizing"]
    assert opsbytes.kv_bytes_per_token(f) == sizing["kv_bytes_per_token"] == 16384
    # an index-key row is 128 lanes wide (ModelConfig.index_key_width), the key its first 64
    assert model.index_key_width == 128
    assert sizing["cache_bytes_per_token"] == 16384 + 8 * 128 * 2 == 18432
    env = cfg["server_env"]
    assert int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"]) == sizing["pool_tokens"] == 262144
    assert round(sizing["pool_tokens"] * 18432 / 1e9, 2) == sizing["pool_GB"]
    assert modelmap.mesh_problems(cfg, 1) == [] and modelmap.mesh_of(cfg) == {}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, "keye30b-l8-longlogs-replay")
    assert (cell["chips"], entry["reduced"], file) == (1, ["num_hidden_layers"], cfg)
    assert (mix["pattern"], mix["asks_per_log"], mix["ask_distance"]) == ("replay", 3, 6)
    longest = 80 + max(mix["log_tokens"]) + mix["question_tokens"] + int(env["MAX_NEW_TOKENS"])
    assert (min(mix["log_tokens"]), max(mix["log_tokens"])) == (6144, 15360)
    assert longest <= int(env["MAX_SEQ_LEN"]) and min(mix["log_tokens"]) > 3 * cfg["topk"] - 1
    assert R.child_env(cfg, 1, True)["MODEL_NAME"] == "toy-sparse-moe"
    ref = refcheck_module().load_reference(cfg["reference"])
    assert callable(ref.forward) and cfg["reference_check"]["clear_if"]["aux"] == "clear_score"
    chk = cfg["reference_check"]
    assert min(chk["prompt_tokens"]) > cfg["topk"] and chk["window"] >= max(chk["prompt_tokens"])


#: https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json as the
#: catalog beside the model-configs guide has it: every number of it.
OLMO_HYBRID_SOURCE = {
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30, "num_key_value_heads": 30,
    "max_position_embeddings": 65536, "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4}


def case_the_olmo_hybrid_configuration_is_its_source_whole():
    """olmo-hybrid-7b (PR 45): every number of the source under its own key,
    NOTHING reduced, the per-layer list copied whole and said again flat as the
    two-mixers-a-layer pattern, every mapped key reaching the ModelConfig, the
    bytes the file states the bytes the ops/bytes functions count, the cell
    found by its name."""
    import opsbytes_linear
    import serve

    cfg = shipped("olmo-hybrid-7b")
    assert {k for k, v in OLMO_HYBRID_SOURCE.items() if cfg.get(k) != v} == set()
    assert cfg["reduced"] == {} and cfg["model_type"] == "olmo_hybrid"
    assert cfg["hidden_act"] == "silu" and cfg["linear_allow_neg_eigval"] is True
    assert cfg["attention_bias"] is False and cfg["tie_word_embeddings"] is False
    assert cfg["rope_parameters"] == {"rope_theta": None}
    assert cfg["layer_types"] == (["linear_attention"] * 3 + ["full_attention"]) * 8
    mixers = "".join({"linear_attention": "LD", "full_attention": "*D"}[t]
                     for t in cfg["layer_types"])
    assert cfg["layer_mixers"] == mixers and cfg["mixers_per_layer"] == 2
    model, sz = serve.register(cfg)
    assert (model.n_layers, model.dim, model.n_heads, model.n_kv_heads, model.head_dim) == (
        32, 3840, 30, 30, 128)
    assert (model.dense_mlp_hidden, model.mlp_hidden, model.vocab_size) == (11008, 0, 100352)
    assert (model.lin_key_heads, model.lin_value_heads, model.lin_key_dim,
            model.lin_value_dim, model.lin_conv, model.lin_neg_eigval) == (30, 30, 96, 192, 4, True)
    assert model.post_norm and model.qk_norm_whole and not model.use_rope
    assert model.has_linear and model.keeps_state and not model.has_ssm and not model.is_moe
    assert (model.n_of("L"), model.n_of("*"), model.n_of("D")) == (24, 8, 32)
    assert model.kv_heads_paged == 32           # a pool row: whole tiles of 8 heads
    assert sz["linear_key_head_dim"] == 96 and sz["layer_mixers"] == mixers   # the reference's
    f = modelmap.fields(sz, modelmap.key_map(cfg))
    sizing, env = cfg["sizing"], cfg["server_env"]
    assert round(opsbytes_linear.whole_model_bytes(f) / 1e9, 2) == sizing["weights_GB"] == 7.43
    assert round(model.param_count() / 1e9, 2) == 7.43
    assert model.state_bytes() == sizing["state_bytes_per_sequence"] == 54743040
    assert opsbytes_linear.kv_bytes_per_token(f) == sizing["kv_bytes_per_token"] == 122880
    assert int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"]) == sizing["pool_tokens"]
    # the pool's rows hold 32 heads for the model's 30
    assert round(sizing["pool_tokens"] * 122880 * 32 / 30 / 1e9, 2) == sizing["pool_GB"]
    assert round(int(env["STATE_SNAPSHOTS"]) * 54743040 / 1e9, 2) == sizing["snapshot_store_GB"]
    assert round(int(env["DECODE_BATCH_SIZE"]) * 54743040 / 1e9, 2) == sizing["live_states_GB"]
    assert modelmap.mesh_problems(cfg, 1) == [] and modelmap.mesh_of(cfg) == {}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, "olmohybrid7b-agent-sessions")
    assert (cell["chips"], entry["reduced"], file) == (1, [], cfg)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert (mix["name"], mix["clients"], mix["preamble_tokens"]) == ("agent-sessions", 8, 2048)
    longest = mix["preamble_tokens"] + sum(mix["turn_added_tokens"]) + int(env["MAX_NEW_TOKENS"])
    assert longest < int(env["MAX_SEQ_LEN"]) == 4096
    assert R.child_env(cfg, 1, True)["MODEL_NAME"] == "toy-linear-hybrid"
    ref = refcheck_module().load_reference(cfg["reference"])
    assert callable(ref.forward) and callable(ref.weights_from_program)
    # a sequence's first two tokens are held as a group, every other one alone
    chk = cfg["reference_check"]
    assert chk["clear_if"] == {"aux": "position", "min": 2} and chk["layers"] == 8
    share = 2 * chk["batch"] / (sum(chk["prompt_tokens"]) + 3 * chk["batch"])
    assert share < chk["unclear_share_max"] <= 0.01
    # a program without the fields ends at once, by name (what the parent does)
    lacking = dict(cfg, keys=dict(cfg["keys"], linear_key_head_dim="no_such_field"))
    with pytest.raises(SystemExit, match="linear_key_head_dim maps to ModelConfig.no_such_field"):
        modelmap.model_config("x", modelmap.sizes(lacking), modelmap.key_map(lacking))


#: https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json as
#: the catalog beside the model-configs guide has it: every number of it.
LING3_FLASH_SOURCE = {
    "image_patch_token": 157157, "video_patch_token": 156909, "image_start_token": 157158,
    "video_start_token": 157160, "num_hidden_layers": 42, "hidden_size": 2560,
    "intermediate_size": 6144, "first_k_dense_replace": 2, "max_position_embeddings": 131072,
    "moe_intermediate_size": 768, "num_experts_per_tok": 8, "num_attention_heads": 32,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "num_experts": 512, "num_key_value_heads": 32, "rope_theta": 6000000, "rms_norm_eps": 1e-06,
    "head_dim": 128, "vocab_size": 157184, "partial_rotary_factor": 0.5,
    "routed_scaling_factor": 2.5, "n_group": 8, "topk_group": 4,
    "moe_shared_expert_intermediate_size": 768, "layer_group_size": 6,
    "num_kv_heads_for_linear_attn": 0, "group_norm_size": 1, "rotary_dim": 64,
    "short_conv_kernel_size": 4, "kda_lower_bound": -5}


def case_the_ling3_flash_configuration_keeps_every_width_and_says_its_two_cuts():
    """ling-3.0-flash-vl-l12 (PR 48): every number of the source under its own
    key but the two the file lists under ``reduced`` (depth, and the experts
    this chip holds: the published 512 stays the router's width), the order's
    rule said again flat as the two-mixers-a-layer pattern, every mapped key
    reaching the ModelConfig, the cell found by its name."""
    import serve

    cfg = shipped("ling-3.0-flash-vl-l12")
    differ = {k for k, v in LING3_FLASH_SOURCE.items() if cfg.get(k) != v}
    assert differ == {"num_hidden_layers", "num_experts"} == set(cfg["reduced"])
    assert {k: (v["source"], v["here"]) for k, v in cfg["reduced"].items()} == {
        "num_hidden_layers": (42, 12), "num_experts": (512, 128)}
    assert cfg["q_lora_rank"] is None and "q_lora_rank" not in cfg["keys"]
    assert cfg["score_function"] == "sigmoid" and cfg["moe_router_enable_expert_bias"] is True
    assert cfg["expert_swiglu_limit_list"][:12] == [0] * 12 == cfg[
        "share_expert_swiglu_limit_list"][:12] and len(cfg["expert_swiglu_limit_list"]) == 42
    # the order, by the source's own rule
    mixers = "".join(("*" if (i + 1) % cfg["layer_group_size"] == 0 else "L")
                     + ("D" if i < cfg["first_k_dense_replace"] else "E") for i in range(12))
    assert cfg["layer_mixers"] == mixers and cfg["mixers_per_layer"] == 2
    model, sz = serve.register(cfg)
    assert (model.n_layers, model.dim, model.n_heads, model.vocab_size) == (12, 2560, 32, 157184)
    assert (model.dense_mlp_hidden, model.mlp_hidden, model.shared_mlp_hidden) == (6144, 768, 768)
    assert (model.n_experts, model.experts_scored, model.first_expert,
            model.experts_per_token) == (128, 512, 0, 8)
    assert (model.n_group, model.topk_group, model.router, model.router_scale) == (
        8, 4, "sigmoid_bias", 2.5)
    assert (model.kv_lora_rank, model.q_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim, model.latent_row) == (512, 0, 128, 64, 128, 576)
    assert (model.lin_key_heads, model.lin_value_heads, model.lin_key_dim, model.lin_value_dim,
            model.lin_conv, model.lin_channel_decay, model.lin_decay_floor,
            model.lin_out_gate) == (32, 32, 128, 128, 4, True, -5, "sigmoid")
    assert model.rope_theta == 6000000 and model.rope_factor == 1.0 and not model.rope_interleave
    assert model.latent and model.has_linear and model.keeps_state and model.grouped_experts
    assert (model.n_of("L"), model.n_of("*"), model.n_of("D"), model.n_of("E")) == (10, 2, 2, 10)
    sizing, env = cfg["sizing"], cfg["server_env"]
    assert model.param_count() == sizing["param_count"]
    assert round(model.param_count() / 1e9, 2) == sizing["weights_GB"] == 9.22
    assert model.state_bytes() == sizing["state_bytes_per_sequence"] == 21708800
    assert sizing["cache_bytes_per_token"] == 2 * 576 * 2
    assert int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"]) == sizing["pool_tokens"]
    assert round(sizing["pool_tokens"] * 2304 / 1e9, 2) == sizing["pool_GB"] == 1.21
    assert round(int(env["STATE_SNAPSHOTS"]) * 21708800 / 1e9, 2) == sizing["snapshot_store_GB"]
    assert round(int(env["DECODE_BATCH_SIZE"]) * 21708800 / 1e9, 2) == sizing["live_states_GB"]
    assert modelmap.mesh_problems(cfg, 1) == [] and modelmap.mesh_of(cfg) == {}
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, "ling3flash-l12-xlonglogs-replay")
    assert (cell["chips"], entry["reduced"], file) == (1, list(cfg["reduced"]), cfg)
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/inclusionAI/Ling-3.0-flash-VL/blob/main/config.json")
    assert mix["name"] == "xlong-logs-replay" and mix["clients"]["from_server_env"] == \
        "DECODE_BATCH_SIZE" and env["DECODE_BATCH_SIZE"] == "16"
    assert R.child_env(cfg, 1, True)["MODEL_NAME"] == "toy-kda-mla-moe"
    ref = refcheck_module().load_reference(cfg["reference"])
    assert callable(ref.forward) and callable(ref.weights_from_program)
    assert [m for m, _ in ref.order({}, 12)] == ["kda"] * 5 + ["latent"] + ["kda"] * 5 + ["latent"]
    assert [m for _, m in ref.order({}, 12)] == ["dense"] * 2 + ["experts"] * 10
    # a program without the fields ends at once, by name (what the parent does)
    lacking = dict(cfg, keys=dict(cfg["keys"], n_group="no_such_field"))
    with pytest.raises(SystemExit, match="n_group maps to ModelConfig.no_such_field"):
        modelmap.model_config("x", modelmap.sizes(lacking), modelmap.key_map(lacking))


def refcheck_module():
    import refcheck

    return refcheck


def case_the_selection_and_expert_counters_are_read_as_growth_between_the_probes():
    """The three metrics PR 31 adds: growth of /health counts between the
    probes; a program without the counters (the parent) gives None."""
    ratio, roof = R.load_reader("health_growth_ratio"), R.load_reader("sparse_attention_roofline")
    spec = lambda n: json.loads((BENCH / "metrics" / f"{n}.json").read_text())
    before = {"sparse_attention": {"decode_rows_live": 1000, "decode_rows_selected": 1000,
                                   "index_rows_scanned": 5000, "forward_passes": 10},
              "moe": {"experts_read": 800, "layer_passes": 8}}
    after = {"sparse_attention": {"decode_rows_live": 101000, "decode_rows_selected": 21000,
                                  "index_rows_scanned": 405000, "forward_passes": 210},
             "moe": {"experts_read": 8800 + 800, "layer_passes": 108}}
    ctx = {"health_before": before, "health_after": after}
    assert ratio.read(ctx, spec("sparse_attn_rows_read_share")["params"]) == 20.0
    assert ratio.read(ctx, spec("experts_read_per_layer_pass")["params"]) == 88.0
    cfg = shipped("keye-vl-2.0-30b-a3b-l8")
    sz = modelmap.sizes(cfg)
    fields = modelmap.fields(sz, modelmap.key_map(cfg))
    trace = {"forward_passes": 50, "category_s": {"attention": 0.001}, "busy_s": 1.0}
    ctx.update(trace=trace, fields=fields, peaks={"hbm_bytes_per_s": 819e9})
    # 8 layers x (400,000 scanned x 128 B + 20,000 selected x 2,048 B) over the
    # run's 200 passes, the capture holding 50 of them
    least = 8 * (400000 * 128 + 20000 * 2048) * 50 / 200 / 819e9
    assert roof.read(ctx, {}) == pytest.approx(100.0 * least / 0.001)
    for parent in ({}, {"sparse_attention": None, "moe": None}):
        old = dict(ctx, health_before=parent, health_after=parent)
        assert ratio.read(old, spec("sparse_attn_rows_read_share")["params"]) is None
        assert ratio.read(old, spec("experts_read_per_layer_pass")["params"]) is None
        assert roof.read(old, {}) is None
    assert roof.read(dict(ctx, trace=None), {}) is None
    mistral = shipped("mistral-7b-instruct-v0.2")
    dense = modelmap.fields(modelmap.sizes(mistral), modelmap.key_map(mistral))
    assert roof.read(dict(ctx, fields=dense), {}) is None


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_harness(case, request):
    names = case.__code__.co_varnames[:case.__code__.co_argcount]
    case(**{n: request.getfixturevalue(n) for n in names})
