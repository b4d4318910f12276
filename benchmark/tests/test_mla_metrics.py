"""What PR 38 adds for a latent-attention model holding a share of its experts:
``opsbytes_mla`` against the issue's arithmetic, the configuration file against
the catalog's keys, and the per-layer metrics of ``mistral4-l9-xlonglogs-replay``
on a /health pair and a reduced trace. One parametrised test, a case each."""

import json
from pathlib import Path

import pytest

import modelmap
import opsbytes_mla as OB
import run as R

BENCH = Path(__file__).resolve().parent.parent
CELL = "mistral4-l9-xlonglogs-replay"
CONFIG = "mistral-small-4-119b-2603-l9"
NEW = {"latent_attention_roofline", "mla_weight_gemms_roofline",
       "latent_cache_bytes_per_token", "latent_rows_per_decode_row"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def config(name=CONFIG):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    """A /health pair as the cell leaves it (the shapes of
    /health.latent_attention, /health.moe and /health.kv_pool; counts rounded)."""
    before = {"latent_attention": {"row_bytes": 5760, "layers": 9, "decode_rows": 1000,
                                   "latent_rows_read": 180_000_000, "window_pairs": 10 ** 9,
                                   "forward_passes": 1000},
              "moe": {"experts_read": 10000, "layer_passes": 900},
              "kv_pool": {"bytes_per_token": 5760}}
    after = {"latent_attention": {"row_bytes": 5760, "layers": 9, "decode_rows": 101_000,
                                  "latent_rows_read": 18_180_000_000, "window_pairs": 41 * 10 ** 9,
                                  "forward_passes": 9000},
             "moe": {"experts_read": 1_153_000, "layer_passes": 90_900},
             "kv_pool": {"bytes_per_token": 5760}}
    return {"health_before": before, "health_after": after}


def ctx_with(trace, fields=None):
    cfg, f = config()
    return dict(probes(), config=cfg, fields=fields or f, peaks=PEAKS,
                trace_rules=json.loads((BENCH / "trace_categories.json").read_text()),
                trace=trace)


def case_bytes_and_operations_are_the_issues_arithmetic():
    _, f = config()
    assert OB.latent_row_bytes(f) == 640 and OB.cache_bytes_per_token(f) == 5760
    assert OB.attention_flops_per_pair(f) == 36864
    assert OB.attention_flops_per_pair_expanded(f) == 16384      # 32 x 2 x (128 + 128)
    # 4.19 + 4.19 + 1.31 + 16.78M int8 and 1.57M in bf16
    assert OB.attention_layer_bytes(f) == 28_049_408 + 1_572_864
    assert OB.expert_layer_bytes(f) == 3 * 4096 * 2048 * 33 + 2 * 4096 * 128
    assert OB.whole_model_bytes(f) == pytest.approx(8.81e9, rel=3e-3)
    # a decode pass with 12.7 of the 32 held experts read a layer: ~3.9 GB
    assert OB.gemm_stream_bytes(f, 12.7) == pytest.approx(3.9e9, rel=2e-2)
    assert OB.gemm_stream_bytes(f, 64) == OB.gemm_stream_bytes(f)        # never above the held
    whole = dict(f, n_layers=36, n_experts=128)
    assert OB.whole_model_bytes(whole) == pytest.approx(119.0e9, rel=3e-3)


def case_the_file_states_the_catalogs_keys_and_the_cut():
    cfg, f = config()
    cat = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Mistral-Small-4-119B-2603"' in line) if Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts"] == list(cfg["reduced"])
    if cat is not None:
        assert entry["source"] == cfg["source"] == cat["source_url"]
        differ = {k for k, v in cat["config"].items() if cfg.get(k) != v}
        assert differ == set(entry["reduced"])
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["n_routed_experts_scored"], cfg["first_routed_expert"]) == (9, 32, 128, 0)
    assert (f["n_experts"], f["router_width"], f["experts_per_token"]) == (32, 128, 4)
    assert (f["kv_lora_rank"], f["q_lora_rank"], f["qk_nope_head_dim"],
            f["qk_rope_head_dim"], f["v_head_dim"]) == (256, 1024, 64, 64, 128)
    group = cfg["rope_parameters"]
    assert all(cfg[k] == group[k] for k in group if k not in ("rope_type", "type"))
    env = cfg["server_env"]
    assert (env["MAX_SEQ_LEN"], env["KV_POOL_BLOCKS"], env["RADIX_LRU_BLOCKS"]) == (
        "32768", "8192", "6144")
    keye = json.loads((BENCH / "configs" / "keye-vl-2.0-30b-a3b-l8.json").read_text())
    assert {k: v for k, v in env.items() if keye["server_env"].get(k) != v} == {
        "MAX_SEQ_LEN": "32768", "KV_POOL_BLOCKS": "8192", "RADIX_LRU_BLOCKS": "6144"}


def case_every_key_reaches_the_model_config_and_a_parent_ends_at_once():
    import serve
    cfg_file, _ = config()
    cfg, _ = serve.register(cfg_file)
    assert cfg.latent and cfg.n_layers * cfg.latent_row * 2 == 5760
    assert cfg.grouped_experts and cfg.experts_scored == 128 and cfg.shared_mlp_hidden == 2048
    assert cfg.param_count() == pytest.approx(8.81e9, rel=3e-3)
    # a program without the fields: the first key of the file's map it lacks
    unknown = dict(cfg_file, keys={"kv_lora_rank": "no_such_field", **cfg_file["keys"]})
    unknown["keys"]["kv_lora_rank"] = "no_such_field"
    with pytest.raises(SystemExit, match="kv_lora_rank maps to ModelConfig.no_such_field, "
                                         "which the program does not have"):
        serve.register(unknown)
    assert list(cfg_file["keys"])[0] == "kv_lora_rank"


def case_the_attention_roofline_takes_the_larger_of_bytes_and_operations():
    roof = R.load_reader("latent_attention_roofline")
    _, f = config()
    rows, pairs = 18_000_000_000, 40 * 10 ** 9
    by_bytes = rows * 640 / 819e9
    by_flops = (rows + pairs * 9) * 36864 / 197e12
    assert OB.attention_least_seconds(f, rows, pairs, PEAKS) == max(by_bytes, by_flops) == by_flops
    assert OB.attention_least_seconds(f, rows, 0, PEAKS) == by_bytes      # decode alone: the stream
    trace = {"forward_passes": 400, "busy_s": 2.8, "category_s": {"attention": 6.0, "mlp": 1.0}}
    got = roof.read(ctx_with(trace), {})
    assert got == pytest.approx(100.0 * by_flops * 400 / 8000 / 6.0) and 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(ctx_with({"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_the_gemm_roofline_reads_the_experts_counter():
    roof = R.load_reader("mla_weight_gemms_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 3.0,
             "category_s": {"mlp": 1.6, "attn_proj": 0.3, "lm_head": 0.3, "attention": 0.5}}
    ctx = ctx_with(trace)
    assert roof.experts_streamed(ctx) == 12.7
    least = OB.gemm_stream_bytes(f, 12.7) * 400 / 819e9
    got = roof.read(ctx, {})
    assert got == pytest.approx(100.0 * least / 2.2) and 80 < got < 100


def case_the_counters_come_from_health():
    ctx = ctx_with({"forward_passes": 1, "busy_s": 2.0, "category_s": {"attention": 0.5}})
    assert R.load_reader("health_path").read(
        ctx, spec("latent_cache_bytes_per_token")["params"]) == 5760.0
    assert R.load_reader("health_growth_ratio").read(
        ctx, spec("latent_rows_per_decode_row")["params"]) == pytest.approx(20000.0)
    assert R.load_reader("health_growth_ratio").read(
        ctx, spec("experts_read_per_layer_pass")["params"]) == 12.7


def case_a_program_without_the_counters_reports_none_of_them():
    """Any configuration that caches K and V, or the parent of PR 38."""
    _, keye = config("keye-vl-2.0-30b-a3b-l8")
    trace = {"forward_passes": 50, "busy_s": 1.0, "category_s": {"attention": 0.1, "mlp": 0.5}}
    for health in ({}, {"latent_attention": None, "moe": None, "kv_pool": {}}):
        ctx = dict(ctx_with(trace), health_before=health, health_after=health)
        assert R.load_reader("latent_attention_roofline").read(ctx, {}) is None
        assert R.load_reader("mla_weight_gemms_roofline").read(ctx, {}) is None
        assert R.load_reader("health_path").read(
            ctx, spec("latent_cache_bytes_per_token")["params"]) is None
        assert R.load_reader("health_growth_ratio").read(
            ctx, spec("latent_rows_per_decode_row")["params"]) is None
    for name in ("latent_attention_roofline", "mla_weight_gemms_roofline"):
        assert R.load_reader(name).read(ctx_with(trace, keye), {}) is None


def case_the_new_metrics_are_the_new_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    assert NEW <= mine and "experts_read_per_layer_pass" in mine
    assert not {"weight_gemms_roofline", "hybrid_weight_gemms_roofline",
                "sparse_attention_roofline"} & mine
    assert {"prefix_hit_share", "attn_dev_share", "mlp_dev_share", "device_idle_share"} <= mine
    assert {m["name"] for m in R.cell_metrics(bench, "end_to_end", CELL)} == {
        "latency_p50_ms", "setup_s"}
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert not NEW & {m["name"] for m in R.cell_metrics(bench, "per_layer", w["name"])}
    assert bench["workloads"][-1]["name"] == CELL and bench["workloads"][-1]["chips"] == 1
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        s = spec(name)
        assert (s["unit"], s["source"], s["layer"], s["moves"], s["better"]) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"], entry["better"])
        assert entry["workloads"] == [CELL]
        assert (BENCH / "readers" / f"{s['reader']}.py").exists() and s["what"]


def case_the_mix_is_the_issues_and_the_plan_builds():
    import workgen
    mix = json.loads((BENCH / "traffic" / "xlong-logs-replay.json").read_text())
    lens = mix["log_tokens"]
    assert (mix["pattern"], mix["asks_per_log"], mix["ask_distance"], mix["question_tokens"],
            mix["think_s"]) == ("replay", 3, 6, 24, 0.0)
    assert lens == [round(16384 + 8192 * i / 15) for i in range(16)]
    assert sum(lens) / 16 == 20480.0
    other = json.loads((BENCH / "traffic" / "long-logs-replay.json").read_text())
    # the ramp is longer, and says why: the first asks' prefills outlast the older mix's
    assert {k for k in mix if mix[k] != other.get(k)} == {
        "name", "users", "log_tokens", "replay_rule", "ramp_s", "stagger_s", "ramp_why"}
    assert (mix["ramp_s"], mix["stagger_s"]) == (45.0, 30.0)
    plan = workgen.build(mix, {}, {"DECODE_BATCH_SIZE": "16"}, 3000000019, 50.0,
                         workgen.Words(None))
    assert plan.offered == {"clients": 16} and len(plan.starts) == 16
    asks = [plan.next_request(i % 16) for i in range(60)]
    assert all(16384 + 24 <= r.query_tokens <= 24576 + 24 for r in asks)
    # position p = 3m + k asks question k of log m - 6k: the re-asks come 19
    # and 38 requests after the first
    log_of = lambda r: r.query[:2000]
    assert log_of(asks[0]) == log_of(asks[19]) == log_of(asks[38])
    assert asks[0].tag == "ask0" and asks[19].tag == "ask1" and asks[38].tag == "ask2"


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_mla_metrics(case):
    case()
