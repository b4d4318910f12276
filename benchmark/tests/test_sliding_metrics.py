"""What PR 40 adds for a model with full and sliding attention layers, a leading
dense MLP and a share of its experts: ``opsbytes_sliding`` against the issue's
arithmetic, the configuration file against the catalog's keys, and the per-layer
metrics of ``laguna-l12-longlogs-replay`` on a /health pair and a reduced trace.
One parametrised test, a case each."""

import json
from pathlib import Path

import pytest

import modelmap
import opsbytes_sliding as OB
import run as R

BENCH = Path(__file__).resolve().parent.parent
CELL = "laguna-l12-longlogs-replay"
CONFIG = "laguna-s-2.1-l12"
NEW = {"sliding_keys_per_decode_row", "full_keys_per_decode_row",
       "sliding_cache_bytes_per_token", "mixed_attention_roofline",
       "sliding_weight_gemms_roofline"}
APPENDED = {"experts_read_per_layer_pass", "window_rows_per_valid_row",
            "state_prefix_usable_share", "state_snapshots_held_peak"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def config(name=CONFIG):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    """A /health pair as the cell leaves it (the shapes of
    /health.sliding_attention, /health.moe, /health.ssm and /health.kv_pool;
    counts rounded): 8,000 forward passes, 100,000 decode rows."""
    sl = lambda rows, passes, pairs_s, pairs_f: {
        "span": 512, "layers_sliding": 9, "layers_full": 3,
        "decode_rows_sliding": 9 * rows, "sliding_keys_read": 9 * rows * 512,
        "decode_rows_full": 3 * rows, "full_keys_read": 3 * rows * 11000,
        "window_rows": rows, "window_pairs_sliding": pairs_s, "window_pairs_full": pairs_f,
        "forward_passes": passes}
    before = {"sliding_attention": sl(1000, 1000, 10 ** 7, 10 ** 8),
              "moe": {"experts_read": 10000, "layer_passes": 1100},
              "ssm": {"prefix_tokens_usable": 1000, "prefix_tokens_matched": 1000,
                      "held_peak": 10},
              "kv_pool": {"bytes_per_token": 12288}}
    after = {"sliding_attention": sl(101_000, 9000, 10 ** 7 + 10 ** 9, 10 ** 8 + 10 ** 10),
             "moe": {"experts_read": 10000 + 15 * 88000, "layer_passes": 89100},
             "ssm": {"prefix_tokens_usable": 991_000, "prefix_tokens_matched": 1_001_000,
                     "held_peak": 64},
             "kv_pool": {"bytes_per_token": 12288}}
    return {"health_before": before, "health_after": after}


def ctx_with(trace, fields=None):
    cfg, f = config()
    return dict(probes(), config=cfg, fields=fields or f, peaks=PEAKS,
                trace_rules=json.loads((BENCH / "trace_categories.json").read_text()),
                trace=trace)


def case_bytes_and_operations_are_the_issues_arithmetic():
    _, f = config()
    assert OB.mixers(f) == "*D" + "SESESE*E" * 2 + "SESESE" and len(OB.mixers(f)) == 24
    assert (OB.heads(f, "*"), OB.heads(f, "S")) == (48, 72)
    assert OB.kv_row_bytes(f) == 4096
    assert OB.pool_bytes_per_token(f) == 12288             # 3 full layers, not 12
    assert OB.sliding_state_bytes(f) == 9 * 512 * 4096 == 18_874_368
    assert (OB.attention_flops_per_pair(f, "*"), OB.attention_flops_per_pair(f, "S")) == (
        24576, 36864)
    # 44.04M / 62.91M of int8 and the bf16 gate (0.29M / 0.44M bytes)
    assert OB.attention_layer_bytes(f, "*") == 2 * 3072 * 48 * 128 + 2 * 3072 * 8 * 128 + 2 * 3072 * 48
    assert OB.attention_layer_bytes(f, "S") == pytest.approx(63.14e6, rel=5e-3)
    assert OB.dense_layer_bytes(f) == 3 * 3072 * 12288
    assert OB.expert_layer_bytes(f) == 3 * 3072 * 1024 * 65 + 2 * 3072 * 256
    assert OB.whole_model_bytes(f) == pytest.approx(8.19e9, rel=3e-3)
    # a decode pass with 30 of the 64 held experts read a layer: ~4.35 GB
    assert OB.gemm_stream_bytes(f, 30) == pytest.approx(4.35e9, rel=2e-2)
    assert OB.gemm_stream_bytes(f, 99) == OB.gemm_stream_bytes(f)        # never above the held
    whole = dict(f, n_layers=48, n_experts=256)
    assert OB.whole_model_bytes(whole) == pytest.approx(117.6e9, rel=5e-3)


def case_the_file_states_the_catalogs_keys_and_the_cut():
    cfg, f = config()
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_experts"] == list(cfg["reduced"])
    if CATALOG.exists():
        cat = next(json.loads(line) for line in open(CATALOG) if '"Laguna-S-2.1"' in line)
        assert entry["source"] == cfg["source"] == cat["source_url"]
        assert {k for k, v in cat["config"].items() if cfg.get(k) != v} == set(entry["reduced"])
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_experts_scored"],
            cfg["first_routed_expert"]) == (12, 64, 256, 0)
    assert (f["n_experts"], f["router_width"], f["experts_per_token"], f["router_scale"]) == (
        64, 256, 10, 2.5)
    # the per-layer lists and groups, repeated flat, agree with the copies
    full, sliding = (cfg["rope_parameters"][k] for k in ("full_attention", "sliding_attention"))
    assert all(cfg[k] == full[k] for k in full if k != "rope_type")
    assert (cfg["sliding_rope_theta"], cfg["sliding_partial_rotary_factor"]) == (
        sliding["rope_theta"], sliding["partial_rotary_factor"])
    heads = {"full_attention": cfg["num_attention_heads"],
             "sliding_attention": cfg["num_attention_heads_sliding"]}
    assert cfg["num_attention_heads_per_layer"] == [heads[t] for t in cfg["layer_types"]]
    mixers = "".join({"full_attention": "*", "sliding_attention": "S"}[a]
                     + {"dense": "D", "sparse": "E"}[m]
                     for a, m in zip(cfg["layer_types"], cfg["mlp_layer_types"]))
    assert cfg["layer_mixers"] == mixers and cfg["mixers_per_layer"] == 2
    assert set(cfg["gating_types"]) == {"per_head"} and cfg["gating"] == "per-head"
    for name in ("gate", "qk_norm", "rope_pairs", "rope_full", "rope_sliding",
                 "sliding_window_keys", "router", "shared_expert", "dense_layer", "hidden_act"):
        assert cfg["assumed"][name], name
    env = cfg["server_env"]
    keye = json.loads((BENCH / "configs" / "keye-vl-2.0-30b-a3b-l8.json").read_text())
    # Keye's file, plus the snapshot store and a quarter more pool (pool_why)
    assert {k: v for k, v in env.items() if keye["server_env"].get(k) != v} == {
        "STATE_SNAPSHOTS": "64", "KV_POOL_BLOCKS": "5120", "RADIX_LRU_BLOCKS": "4096"}
    assert cfg["sizing"]["cache_bytes_per_token"] == 12288


def case_every_key_reaches_the_model_config_and_a_parent_ends_at_once():
    import serve
    cfg_file, _ = config()
    cfg, _ = serve.register(cfg_file)
    assert cfg.slides and cfg.keeps_state and not cfg.has_ssm
    assert (cfg.n_of("*"), cfg.n_of("S"), cfg.n_of("D"), cfg.n_of("E")) == (3, 9, 1, 11)
    assert (cfg.heads_of("*"), cfg.heads_of("S"), cfg.sliding_window) == (48, 72, 512)
    assert cfg.grouped_experts and cfg.experts_scored == 256
    assert cfg.param_count() == pytest.approx(8.19e9, rel=3e-3)
    assert cfg.state_bytes() == OB.sliding_state_bytes(modelmap.fields(
        modelmap.sizes(cfg_file), modelmap.key_map(cfg_file)))
    assert cfg.sliding_ring(512, 64) == 1024
    # a program without a field: the run ends at the key that maps to it
    unknown = dict(cfg_file, keys=dict(cfg_file["keys"], sliding_window="no_such_field"))
    with pytest.raises(SystemExit, match="sliding_window maps to ModelConfig.no_such_field, "
                                         "which the program does not have"):
        serve.register(unknown)


def case_the_attention_roofline_counts_both_kinds():
    roof = R.load_reader("mixed_attention_roofline")
    _, f = config()
    keys_s, keys_f = 9 * 100_000 * 512, 3 * 100_000 * 11000
    pairs_s, pairs_f = 10 ** 9, 10 ** 10
    by_bytes = (keys_s + keys_f) * 4096 / 819e9
    by_flops = ((keys_s + 9 * pairs_s) * 36864 + (keys_f + 3 * pairs_f) * 24576) / 197e12
    assert OB.attention_least_seconds(f, keys_s, keys_f, pairs_s, pairs_f, PEAKS) == max(
        by_bytes, by_flops) == by_bytes
    assert OB.attention_least_seconds(f, 0, 0, pairs_s, pairs_f, PEAKS) == (
        9 * pairs_s * 36864 + 3 * pairs_f * 24576) / 197e12
    trace = {"forward_passes": 400, "busy_s": 2.8, "category_s": {"attention": 1.5, "mlp": 1.0}}
    got = roof.read(ctx_with(trace), {})
    assert got == pytest.approx(100.0 * by_bytes * 400 / 8000 / 1.5) and 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(ctx_with({"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_the_gemm_roofline_reads_the_experts_counter():
    roof = R.load_reader("sliding_weight_gemms_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 3.0,
             "category_s": {"mlp": 1.6, "attn_proj": 0.4, "lm_head": 0.3, "attention": 0.5}}
    ctx = ctx_with(trace)
    least = OB.gemm_stream_bytes(f, 15.0) * 400 / 819e9
    got = roof.read(ctx, {})
    assert got == pytest.approx(100.0 * least / 2.3) and 40 < got < 100


def case_the_counters_come_from_health():
    ctx = ctx_with({"forward_passes": 1, "busy_s": 2.0, "category_s": {"attention": 0.5}})
    ratio = R.load_reader("health_growth_ratio")
    assert R.load_reader("health_path").read(
        ctx, spec("sliding_cache_bytes_per_token")["params"]) == 12288.0
    assert ratio.read(ctx, spec("sliding_keys_per_decode_row")["params"]) == 512.0
    assert ratio.read(ctx, spec("full_keys_per_decode_row")["params"]) == 11000.0
    assert ratio.read(ctx, spec("experts_read_per_layer_pass")["params"]) == 15.0
    assert ratio.read(ctx, spec("state_prefix_usable_share")["params"]) == 99.0
    assert R.load_reader(spec("state_snapshots_held_peak")["reader"]).read(
        ctx, spec("state_snapshots_held_peak")["params"]) == 64.0


def case_a_program_without_the_counters_reports_none_of_them():
    """Any configuration whose attention layers are of one kind, or the parent
    of PR 40 under this PR's benchmark files."""
    _, keye = config("keye-vl-2.0-30b-a3b-l8")
    trace = {"forward_passes": 50, "busy_s": 1.0, "category_s": {"attention": 0.1, "mlp": 0.5}}
    for health in ({}, {"sliding_attention": None, "moe": None, "kv_pool": {}}):
        ctx = dict(ctx_with(trace), health_before=health, health_after=health)
        assert R.load_reader("mixed_attention_roofline").read(ctx, {}) is None
        assert R.load_reader("sliding_weight_gemms_roofline").read(ctx, {}) is None
        assert R.load_reader("health_path").read(
            ctx, spec("sliding_cache_bytes_per_token")["params"]) is None
        for name in ("sliding_keys_per_decode_row", "full_keys_per_decode_row"):
            assert R.load_reader("health_growth_ratio").read(ctx, spec(name)["params"]) is None
    for name in ("mixed_attention_roofline", "sliding_weight_gemms_roofline"):
        assert R.load_reader(name).read(ctx_with(trace, keye), {}) is None


def case_the_new_metrics_are_the_new_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    assert NEW | APPENDED <= mine
    assert not {"weight_gemms_roofline", "hybrid_weight_gemms_roofline",
                "mla_weight_gemms_roofline", "latent_attention_roofline"} & mine
    assert {"prefix_hit_share", "attn_dev_share", "mlp_dev_share", "device_idle_share"} <= mine
    assert {m["name"] for m in R.cell_metrics(bench, "end_to_end", CELL)} == {
        "latency_p50_ms", "setup_s"}
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not NEW & {m["name"] for m in R.cell_metrics(bench, "per_layer", w["name"])}
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "long-logs-replay", 1)
    assert len(cell["why"]) <= 200
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        s = spec(name)
        assert (s["unit"], s["source"], s["layer"], s["moves"], s["better"]) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"], entry["better"])
        assert entry["workloads"] == [CELL]
        assert (BENCH / "readers" / f"{s['reader']}.py").exists() and s["what"]


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_sliding_metrics(case):
    case()
