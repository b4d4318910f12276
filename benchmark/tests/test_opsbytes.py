import json
from pathlib import Path

import pytest

import opsbytes
from modelmap import fields, key_map, model_config, sizes

BENCH = Path(__file__).resolve().parent.parent


#: bytes a pass streams at the parent of PR 26 (source-key arithmetic, one chip)
SHIPPED = [("mistral-7b-instruct-v0.2", 7110393856), ("mixtral-8x7b-instruct-v0.1-l6", 8838447104)]


def shipped(name):
    cfg_file = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    sz, kmap = sizes(cfg_file), key_map(cfg_file)
    return cfg_file, model_config(name, sz, kmap), fields(sz, kmap)


@pytest.mark.parametrize("name,nbytes", SHIPPED)
def test_weight_stream_against_param_count(name, nbytes):
    cfg_file, cfg, f = shipped(name)
    d, L = cfg.dim, cfg.n_layers
    not_streamed = cfg.vocab_size * d + L * d * cfg.n_experts + L * 2 * d + d
    assert opsbytes.weight_stream_bytes(f) == cfg.param_count() - not_streamed == nbytes
    assert opsbytes.kv_bytes_per_token(f) == cfg_file["sizing"]["kv_bytes_per_token"]
    assert opsbytes.weight_gemm_flops_per_token(f) == 2 * nbytes


@pytest.mark.parametrize("name,nbytes", SHIPPED)
def test_a_chip_of_four_streams_a_quarter(name, nbytes):
    _, cfg, f = shipped(name)
    assert opsbytes.weight_stream_bytes(f, shards=4) == nbytes / 4
    assert opsbytes.weight_stream_bytes(f, shards=1, experts_streamed=None) == nbytes
    # an expert axis splits the experts once more and nothing else
    experts = cfg.n_layers * max(cfg.n_experts, 1) * 3 * cfg.dim * cfg.mlp_hidden
    rest = nbytes - experts
    assert opsbytes.weight_stream_bytes(f, shards=2, expert_shards=2) == rest / 2 + experts / 4


def test_experts_streamed_counts_only_the_experts_read():
    _, cfg, f = shipped("mixtral-8x7b-instruct-v0.1-l6")
    one_expert = cfg.n_layers * 3 * cfg.dim * cfg.mlp_hidden
    all8 = opsbytes.weight_stream_bytes(f)
    assert opsbytes.weight_stream_bytes(f, experts_streamed=2) == all8 - 6 * one_expert
    assert opsbytes.weight_stream_bytes(f, experts_streamed=5.5) == all8 - 2.5 * one_expert
    assert opsbytes.weight_stream_bytes(f, experts_streamed=64) == all8     # never above all


def test_every_key_reaches_the_model_config():
    import serve

    cfg_file = json.loads((BENCH / "configs" / "mixtral-8x7b-instruct-v0.1-l6.json").read_text())
    cfg, sz = serve.register(cfg_file)
    assert (cfg.n_layers, cfg.n_experts, cfg.experts_per_token) == (6, 8, 2)
    assert (cfg.dim, cfg.mlp_hidden, cfg.head_dim, cfg.n_kv_heads) == (4096, 14336, 128, 8)
    assert cfg.rope_theta == 1e6 and cfg.rms_eps == 1e-5 and cfg.max_seq_len == 32768
    bad = dict(cfg_file, sliding_window=4096)
    with pytest.raises(SystemExit):
        serve.register(bad)
