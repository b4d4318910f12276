import json
from pathlib import Path

import pytest

import opsbytes
from modelmap import KEY_MAP, sizes

BENCH = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("name,gb", [("mistral-7b-instruct-v0.2", 7.11),
                                     ("mixtral-8x7b-instruct-v0.1-l6", 8.84)])
def test_weight_stream_against_param_count(name, gb):
    from ai_agent_kubectl_tpu.models.config import ModelConfig

    cfg_file = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    sz = sizes(cfg_file)
    cfg = ModelConfig(name=name, eos_ids=(sz["eos_token_id"],),
                      **{KEY_MAP[k]: v for k, v in sz.items() if k in KEY_MAP})
    d, L = cfg.dim, cfg.n_layers
    not_streamed = cfg.vocab_size * d + L * d * cfg.n_experts + L * 2 * d + d
    assert opsbytes.weight_stream_bytes(sz) == cfg.param_count() - not_streamed
    assert opsbytes.weight_stream_bytes(sz) / 1e9 == pytest.approx(gb, abs=0.01)
    assert opsbytes.kv_bytes_per_token(sz) == cfg_file["sizing"]["kv_bytes_per_token"]


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
