"""What PR 55 adds for a model of gated-delta-rule layers with fewer key heads
than value heads, gated attention layers and a chip's share of its experts:
``opsbytes_gdn_moe`` against ``ModelConfig.param_count`` and the cache's leaves,
the per-layer metrics of ``qwen3next-l12-longlogs-replay`` on a /health pair and
on a recorded excerpt of the cell's own trace (and ``None`` off the family). One parametrised test, a case each; the cell is found by its name,
never by its place in BENCHMARK.json."""

import gzip
import json
from pathlib import Path

import pytest

import modelmap
import opsbytes_gdn_moe as OB
import run as R
import xtrace

BENCH = Path(__file__).resolve().parent.parent
CELL = "qwen3next-l12-longlogs-replay"
CONFIG = "qwen3-next-80b-a3b-instruct-l12"
NEW = {"gdn_moe_mixer_roofline", "gated_delta_step_roofline",
       "gdn_moe_weight_gemms_roofline", "gdn_moe_attention_roofline"}
#: metrics the benchmark had whose readers read the same thing here, unedited
JOINED = {"experts_read_per_layer_pass", "expert_picks_held_share", "lin_dev_share",
          "lin_state_bytes_per_sequence", "lin_full_keys_per_decode_row",
          "state_prefix_usable_share", "state_snapshots_held_peak",
          "window_rows_per_valid_row"}
RULES = json.loads((BENCH / "trace_categories.json").read_text())
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def config():
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    """A /health pair as the cell leaves it (the shapes of /health.moe, .ssm,
    .linear_attention, .ragged and .kv_pool; counts rounded): 8,000 passes
    between the probes, 2,400 of them eager pieces and 300 a chunk's window; of
    the decode passes' 16 x 9 (row, layer) pairs a pass, 9 x 5,300 did not move."""
    lin = {"layers_linear": 9, "layers_full": 3, "key_heads": 16, "value_heads": 32,
           "state_bytes_per_sequence": 19316736}
    before = {"ssm": {"forward_passes": 1000, "eager_prefill_passes": 300, "live_rows": 16,
                      "prefix_tokens_matched": 100000, "prefix_tokens_usable": 99000,
                      "held_peak": 40},
              "linear_attention": dict(lin, forward_passes=1000, decode_rows_linear=90000,
                                       decode_rows_still=9 * 700, decode_rows_full=30000,
                                       full_keys_read=300000000),
              "moe": {"experts_read": 300000, "layer_passes": 12000, "picks": 1000000,
                      "picks_held": 250000},
              "kv_pool": {"bytes_per_token": 6144.0},
              "ragged": {"window": {"windows": 40, "rows_computed": 1000, "rows_valid": 800}}}
    after = {"ssm": {"forward_passes": 9000, "eager_prefill_passes": 2700, "live_rows": 16,
                     "prefix_tokens_matched": 900000, "prefix_tokens_usable": 895000,
                     "held_peak": 96},
             "linear_attention": dict(lin, forward_passes=9000, decode_rows_linear=810000,
                                      decode_rows_still=9 * 6000, decode_rows_full=270000,
                                      full_keys_read=3000000000),
             "moe": {"experts_read": 3000000, "layer_passes": 108000, "picks": 9000000,
                     "picks_held": 2270000},
             "kv_pool": {"bytes_per_token": 6144.0},
             "ragged": {"window": {"windows": 340, "rows_computed": 13000, "rows_valid": 10800}}}
    return {"health_before": before, "health_after": after}


def ctx_with(trace):
    cfg, f = config()
    return dict(probes(), config=cfg, fields=f, peaks=PEAKS, trace_rules=RULES, trace=trace)


def recorded():
    """The cell's own trace, 0.02 s of it (cut from a traced run's
    trace_small.json; my chip run, PR 55), reduced as run.py reduces a capture."""
    rep = json.load(gzip.open(BENCH / "tests" / "data" / "trace_gdn_moe_small.json.gz", "rt"))
    return xtrace.reduce(rep, RULES, 12)


def case_bytes_by_kind_are_the_issues_arithmetic_and_the_programs():
    import serve

    cfg, f = config()
    model, _ = serve.register(cfg)
    assert OB.kinds(f) == "LELELE*E" * 3 and OB.of_family(f)
    assert (OB.lin_keys(f), OB.lin_values(f)) == (16 * 128, 32 * 128)
    assert OB.lin_conv_channels(f) == 8192 == model.lin_conv_dim
    # W_in 2048 x 12,288, W_out 4096 x 2048 int8; W_a and W_b 2048 x 32 bf16
    assert OB.lin_layer_bytes(f) == 2048 * 12288 + 4096 * 2048 + 2 * 2 * 2048 * 32
    assert OB.lin_layer_params(f) == (2048 * 12288 + 4096 * 2048 + 2 * 2048 * 32 + 4 * 8192
                                      + 2 * 32 + 128 + 2048)
    # W_q 2048 x 8,192 [q | gate], W_k and W_v 2048 x 512, W_o 4096 x 2048
    assert OB.attention_layer_bytes(f) == 2048 * (8192 + 512 + 512) + 4096 * 2048
    assert OB.expert_layer_bytes(f) == (3 * 2048 * 512 * 129 + 2 * 2048 * 512 + 2 * 2048)
    assert OB.expert_layer_bytes(f, 34.0) == (3 * 2048 * 512 * 35 + 2 * 2048 * 512 + 2 * 2048)
    assert OB.head_bytes(f) == 151936 * 2048
    # every parameter, by the program's own count and the issue's
    assert OB.param_count(f) == model.param_count() == cfg["sizing"]["param_count"] == 5889832128
    assert round(model.param_count() / 1e9, 2) == cfg["sizing"]["weights_GB"] == 5.89
    # a sequence's state, and a token's rows
    assert OB.lin_matrix_bytes(f) == 4 * 128 * 4096 == 2097152
    assert OB.lin_state_bytes(f) == 2097152 + 2 * 3 * 8192
    assert OB.state_bytes_per_sequence(f) == model.state_bytes() == 19316736 == \
        cfg["sizing"]["state_bytes_per_sequence"]
    assert OB.kv_bytes_per_key(f) == 2048
    assert OB.cache_bytes_per_token(f) == cfg["sizing"]["cache_bytes_per_token"] == 6144
    assert OB.lin_pass_bytes(f) == 9 * OB.lin_layer_bytes(f)
    assert OB.gemm_stream_bytes(f, 34.0) == (
        12 * OB.expert_layer_bytes(f, 34.0) + 3 * OB.attention_layer_bytes(f) + OB.head_bytes(f))
    assert OB.step_kernel_bytes(f, 10) == 10 * 2 * 2097152
    env = cfg["server_env"]
    assert int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"]) == cfg["sizing"]["pool_tokens"]
    assert round(cfg["sizing"]["pool_tokens"] * 6144 / 1e9, 2) == cfg["sizing"]["pool_GB"] == 2.42
    assert round(int(env["STATE_SNAPSHOTS"]) * 19316736 / 1e9, 2) == \
        cfg["sizing"]["snapshot_store_GB"]


def case_the_leaves_hold_what_the_functions_count():
    """The engine's cache at the published sizes, abstract: K and V planes for
    the three attention layers alone, a plane a delta-rule layer of float32
    state a VALUE head wide."""
    import jax
    import jax.numpy as jnp
    import serve
    from ai_agent_kubectl_tpu.models.transformer import KVCache

    cfg, f = config()
    model, _ = serve.register(cfg)
    made = jax.eval_shape(lambda: KVCache.pool_zeros(
        model, n_blocks=512, page=64, slots=16, dtype=jnp.bfloat16, counts_experts=True))
    assert made.k.shape == made.v.shape == (3, 512, 64, 2, 256)
    assert 2 * made.k.size * 2 / (512 * 64) == OB.cache_bytes_per_token(f)
    assert made.lin.shape == (9, 16, 128, 4096) and made.lin.dtype == jnp.float32
    assert made.lconv.shape == (9, 16, 3, 8192)
    row = lambda a: a.size // a.shape[1] * a.dtype.itemsize
    assert row(made.lin) + row(made.lconv) == OB.state_bytes_per_sequence(f)
    assert (made.lin_rows.shape, made.expert_picks.shape) == ((6,), (2,))


def case_the_step_kernels_roofline_counts_the_rows_that_moved():
    roof = R.load_reader("gated_delta_step_roofline")
    # 8,000 passes, 5,300 of them decode passes; 9 x (5,300 x 16 - 5,300) pairs moved
    pairs = 9 * (5300 * 16 - 5300)
    assert roof.moved(ctx_with(None)) == {"passes": 8000, "moving_row_layers": pairs}
    trace = {"forward_passes": 400, "busy_s": 3.0, "category_s": {"other_device": 2.0},
             "breakdown": {"device_ops": [["mlp:fusion", 0.9],
                                          ["other_device:gated_delta_step", 0.25],
                                          ["other_device:fusion", 0.5]]}}
    got = roof.read(ctx_with(trace), {})
    least = 2 * pairs * 2097152 * (400 / 8000) / 819e9
    assert got == pytest.approx(100.0 * least / 0.25) and 0 < got < 100
    quiet = dict(trace, breakdown={"device_ops": [["mlp:fusion", 0.9]]})
    assert roof.read(ctx_with(quiet), {}) is None
    assert roof.read(ctx_with(None), {}) is None


def case_the_mixer_roofline_counts_projections_and_the_rows_moved():
    roof = R.load_reader("gdn_moe_mixer_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 6.0, "category_s": {"other_device": 1.6}}
    got = roof.read(ctx_with(trace), {})
    moved = (810000 - 90000) * 400 / 8000           # rows x layers in the capture
    least = (OB.lin_pass_bytes(f) * 400 + moved * 2 * 2097152) / 819e9
    assert got == pytest.approx(100.0 * least / 1.6) and 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(ctx_with({"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_the_gemm_and_attention_rooflines_count_by_kind():
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 4.0,
             "category_s": {"mlp": 1.6, "attn_proj": 0.1, "lm_head": 0.25, "attention": 0.6}}
    read_a_pass = (3000000 - 300000) / 96000
    got = R.load_reader("gdn_moe_weight_gemms_roofline").read(ctx_with(trace), {})
    least = OB.gemm_stream_bytes(f, read_a_pass) * 400 / 819e9
    assert got == pytest.approx(100.0 * least / 1.95) and 0 < got < 100
    got = R.load_reader("gdn_moe_attention_roofline").read(ctx_with(trace), {})
    keys = (3000000000 - 300000000) * 400 / 8000
    assert got == pytest.approx(100.0 * keys * 2048 / 819e9 / 0.6) and 0 < got < 100


def case_shares_and_counters_come_from_health():
    ctx = ctx_with({"forward_passes": 1, "busy_s": 2.0, "category_s": {"other_device": 0.5}})
    read = lambda name: R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"])
    assert read("lin_dev_share") == 25.0
    assert read("lin_state_bytes_per_sequence") == 19316736.0
    assert read("lin_full_keys_per_decode_row") == pytest.approx(2700000000 / 240000)
    assert read("experts_read_per_layer_pass") == pytest.approx(2700000 / 96000)
    assert read("expert_picks_held_share") == pytest.approx(100.0 * 2020000 / 8000000)
    assert read("state_prefix_usable_share") == 99.5
    assert read("state_snapshots_held_peak") == 96.0
    assert read("window_rows_per_valid_row") == 1.2


def case_every_new_reader_reads_the_recorded_trace():
    """The cell's own capture, cut to 0.02 s: the step kernel is among the
    listed ops under its name, the ``lin/*`` scopes land in ``other_device``,
    the attention's gate under ``attention`` and the shared expert's under
    ``mlp``, and each reader gives a share over 0 of it (the probes' counts
    stand for the excerpt's own passes, so the size of a share means nothing)."""
    tr = recorded()
    assert tr["devices"] == 1 and tr["forward_passes"] > 0
    for cat in ("other_device", "attention", "mlp", "attn_proj", "lm_head"):
        assert tr["category_s"].get(cat, 0) > 0, cat
    ops = dict(tr["breakdown"]["device_ops"])
    assert ops.get("other_device:gated_delta_step", 0) > 0
    step = R.load_reader("gated_delta_step_roofline")
    assert step.kernel_seconds(tr, "gated_delta_step") == ops["other_device:gated_delta_step"]
    ctx = ctx_with(tr)
    for name in NEW:
        got = R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"])
        assert got is not None and got > 0, (name, got)
    share = R.load_reader("trace_category_share").read(ctx, spec("lin_dev_share")["params"])
    assert 10 < share < 60


def case_a_program_without_the_counters_reports_none_of_them():
    """The parent of PR 55, or any other configuration: nothing raises, every
    new metric is left out."""
    others = []
    for name in ("mistral-7b-instruct-v0.2", "olmo-hybrid-7b", "ling-3.0-flash-vl-l12"):
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        others.append(modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg)))
    trace = {"forward_passes": 50, "busy_s": 1.0,
             "category_s": {"other_device": 0.1, "mlp": 0.5, "attention": 0.2},
             "breakdown": {"device_ops": [["other_device:gated_delta_step", 0.05]]}}
    for health in ({}, {"ssm": None, "linear_attention": None},
                   {"ssm": {"forward_passes": 10}, "linear_attention": {"forward_passes": 10}}):
        ctx = dict(ctx_with(trace), health_before=health, health_after=health)
        for name in NEW - {"gdn_moe_weight_gemms_roofline"}:
            assert R.load_reader(name).read(ctx, {}) is None, name
        for name in NEW:
            for fields in others:
                assert R.load_reader(name).read(dict(ctx, fields=fields), {}) is None, name
    for name in NEW:
        assert R.load_reader(name).read(ctx_with(None), {}) is None


def case_the_new_metrics_are_this_cells_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], entry["reduced"]) == (
        1, "long-logs-replay", ["num_hidden_layers", "num_experts"])
    assert list(file["reduced"]) == entry["reduced"] and entry["source"] == file["source"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert mix["name"] == "long-logs-replay" and file["server_env"]["DECODE_BATCH_SIZE"] == "16"
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    assert NEW | JOINED <= mine
    assert not mine & {"weight_gemms_roofline", "lin_mixer_roofline", "lin_weight_gemms_roofline",
                       "kda_mixer_roofline", "kda_weight_gemms_roofline"}
    assert {m["name"] for m in R.cell_metrics(bench, "end_to_end", CELL)} == {
        "latency_p50_ms", "setup_s"}
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not NEW & {m["name"] for m in R.cell_metrics(bench, "per_layer", w["name"])}
    for name in NEW:
        listed = next(m for m in bench["per_layer"] if m["name"] == name)
        s = spec(name)
        assert (s["unit"], s["source"], s["layer"], s["moves"], s["better"]) == (
            listed["unit"], listed["source"], listed["layer"], listed["moves"], listed["better"])
        assert listed["workloads"] == [CELL]
        assert (BENCH / "readers" / f"{s['reader']}.py").exists() and s["what"]


def case_the_file_holds_the_sources_numbers():
    """Every number of the published config is in the file under its own key
    but the two it lists under ``reduced``; the keys the program reads reach its
    ModelConfig; the reference names the published order."""
    import refcheck
    import serve

    source = {"decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
              "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
              "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
              "linear_num_key_heads": 16, "linear_num_value_heads": 32,
              "linear_value_head_dim": 128, "max_position_embeddings": 262144,
              "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512,
              "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
              "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
              "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
              "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
              "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936}
    cfg, f = config()
    differ = {k for k, v in source.items() if cfg.get(k, "absent") != v}
    assert differ == {"num_hidden_layers", "num_experts"} == set(cfg["reduced"])
    assert {k: (v["source"], v["here"]) for k, v in cfg["reduced"].items()} == {
        "num_hidden_layers": (48, 12), "num_experts": (512, 128)}
    mixers = "".join(("*" if (i + 1) % cfg["full_attention_interval"] == 0 else "L") + "E"
                     for i in range(48))
    assert cfg["layer_mixers"] == mixers and cfg["mixers_per_layer"] == 2
    model, sz = serve.register(cfg)
    assert (model.n_layers, model.dim, model.n_heads, model.n_kv_heads, model.head_dim,
            model.vocab_size) == (12, 2048, 16, 2, 256, 151936)
    assert (model.lin_key_heads, model.lin_value_heads, model.lin_key_dim, model.lin_value_dim,
            model.lin_conv, model.lin_neg_eigval, model.lin_channel_decay) == (
        16, 32, 128, 128, 4, False, False)
    assert (model.n_experts, model.experts_scored, model.first_expert, model.experts_per_token,
            model.mlp_hidden, model.shared_mlp_hidden, model.dense_mlp_hidden) == (
        128, 512, 0, 10, 512, 512, 5120)
    assert (model.attn_gate, model.rms_offset, model.qk_norm, model.shared_expert_gate,
            model.rope_partial, model.rope_theta, model.router) == (
        "elementwise", 1.0, True, True, 0.25, 10000000, "softmax")
    assert (model.n_of("L"), model.n_of("*"), model.n_of("E"), model.n_of("D")) == (9, 3, 12, 0)
    assert model.counts_picks and model.grouped_experts and model.keeps_state
    assert R.child_env(cfg, 1, True)["MODEL_NAME"] == "toy-gdn-moe"
    ref = refcheck.load_reference(cfg["reference"])
    assert callable(ref.forward) and callable(ref.weights_from_program)
    assert ref.order({}, 12) == (["delta"] * 3 + ["attention"]) * 3
    # a program without the fields ends at once, by name (what the parent does)
    lacking = dict(cfg, keys=dict(cfg["keys"], shared_expert_gate="no_such_field"))
    with pytest.raises(SystemExit, match="shared_expert_gate maps to ModelConfig.no_such_field"):
        modelmap.model_config("x", modelmap.sizes(lacking), modelmap.key_map(lacking))


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__[5:] for c in CASES])
def test_gdn_moe_metrics(case):
    case()
