"""What PR 33 adds for a model of one mixer a layer: ``opsbytes_hybrid`` against
the issue's arithmetic, and the per-layer metrics of
``nemotron30b-l13-agent-sessions`` on a /health pair and a reduced trace. One
parametrised test, a case each."""

import json
from pathlib import Path

import pytest

import modelmap
import opsbytes_hybrid as OB
import run as R

BENCH = Path(__file__).resolve().parent.parent
CELL = "nemotron30b-l13-agent-sessions"
NEW = {"hybrid_weight_gemms_roofline", "ssm_mixer_roofline", "ssm_dev_share",
       "state_prefix_usable_share", "state_snapshots_held_peak"}


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def config():
    cfg = json.loads((BENCH / "configs" / "nemotron-3-nano-30b-a3b-l13.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    """A /health pair as the cell leaves it (the shape of /health.ssm and
    /health.moe on the chip; counts rounded)."""
    before = {"ssm": {"forward_passes": 1000, "eager_prefill_passes": 200, "live_rows": 16,
                      "prefix_tokens_matched": 50000, "prefix_tokens_usable": 40000,
                      "held_peak": 20, "snapshots_held": 20, "capacity": 192},
              "moe": {"experts_read": 10000, "layer_passes": 200}}
    after = {"ssm": {"forward_passes": 11000, "eager_prefill_passes": 1200, "live_rows": 16,
                     "prefix_tokens_matched": 1050000, "prefix_tokens_usable": 990000,
                     "held_peak": 141, "snapshots_held": 120, "capacity": 192},
             "moe": {"experts_read": 353000, "layer_passes": 5200}}
    return {"health_before": before, "health_after": after}


def ctx_with(trace):
    cfg, f = config()
    return dict(probes(), config=cfg, fields=f, peaks={"hbm_bytes_per_s": 819e9},
                trace_rules=json.loads((BENCH / "trace_categories.json").read_text()),
                trace=trace)


def case_bytes_by_layer_kind_are_the_issues_arithmetic():
    _, f = config()
    assert OB.kinds(f) == "MEMEM*EMEMEM*" and OB.expert_matrices(f) == 2
    assert OB.expert_layer_bytes(f) == 128 * 2 * 2688 * 1856 + 2 * 2688 * 3712      # 1,297.2M
    assert OB.ssm_layer_bytes(f) == 2688 * 10304 + 4096 * 2688                      # 38.7M
    assert OB.attention_layer_bytes(f) == 2 * 2688 * 128 * 34                       # 23.4M
    assert OB.whole_model_bytes(f) == pytest.approx(7.47e9, rel=2e-3)
    # a decode pass with 68.6 experts read a layer: 3.9 GB in the three GEMM categories
    assert OB.gemm_stream_bytes(f, 68.6) == pytest.approx(3.92e9, rel=5e-3)
    assert OB.gemm_stream_bytes(f) == 5 * OB.expert_layer_bytes(f) + 2 * OB.attention_layer_bytes(f) + OB.head_bytes(f)
    # a sequence's state: 12.8 MB over the 6 Mamba layers
    assert 6 * OB.ssm_state_bytes(f) == 12804096
    assert OB.ssm_pass_bytes(f, 16) == 6 * (OB.ssm_layer_bytes(f) + 32 * OB.ssm_state_bytes(f))
    whole = dict(f, n_layers=52)
    assert OB.whole_model_bytes(whole) == pytest.approx(31.6e9, rel=2e-3)


def case_the_gemm_roofline_counts_by_kind_and_reads_the_experts_counter():
    roof = R.load_reader("hybrid_weight_gemms_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 3.0,
             "category_s": {"mlp": 2.0, "attn_proj": 0.05, "lm_head": 0.25, "other_device": 0.5}}
    ctx = ctx_with(trace)
    assert roof.experts_streamed(ctx) == 68.6
    least = OB.gemm_stream_bytes(f, 68.6) * 400 / 819e9
    got = roof.read(ctx, {})
    assert got == pytest.approx(100.0 * least / 2.3) and 80 < got < 100
    # the uniform-block count would read it impossible: why this cell has its own metric
    import opsbytes
    assert opsbytes.weight_stream_bytes(f, experts_streamed=68.6) > 3 * OB.gemm_stream_bytes(f, 68.6)


def case_the_mixer_roofline_matches_the_capture_to_the_counters_by_passes():
    roof = R.load_reader("ssm_mixer_roofline")
    _, f = config()
    trace = {"forward_passes": 500, "busy_s": 3.0, "category_s": {"other_device": 0.9}}
    run = OB.ssm_pass_bytes(f, 1) * 1000 + OB.ssm_pass_bytes(f, 16) * 9000
    want = 100.0 * (run * 500 / 10000 / 819e9) / 0.9
    got = roof.read(ctx_with(trace), {})
    assert got == pytest.approx(want) and 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(ctx_with({"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_shares_and_the_peak_come_from_health():
    ctx = ctx_with({"forward_passes": 1, "busy_s": 2.0, "category_s": {"other_device": 0.5}})
    assert R.load_reader("health_growth_ratio").read(
        ctx, spec("state_prefix_usable_share")["params"]) == 95.0
    assert R.load_reader("health_path").read(
        ctx, spec("state_snapshots_held_peak")["params"]) == 141.0
    assert R.load_reader("trace_category_share").read(ctx, spec("ssm_dev_share")["params"]) == 25.0
    # the grouped expert kernel's counter, as Keye's cell reads it
    assert R.load_reader("health_growth_ratio").read(
        ctx, spec("experts_read_per_layer_pass")["params"]) == 68.6


def case_a_program_without_the_counters_reports_none_of_them():
    """Any configuration that keeps no state, or the parent of PR 33."""
    _, keye = (lambda c: (c, modelmap.fields(modelmap.sizes(c), modelmap.key_map(c))))(
        json.loads((BENCH / "configs" / "keye-vl-2.0-30b-a3b-l8.json").read_text()))
    for health in ({}, {"ssm": None, "moe": None}):
        ctx = dict(ctx_with({"forward_passes": 50, "busy_s": 1.0,
                             "category_s": {"other_device": 0.1, "mlp": 0.5}}),
                   health_before=health, health_after=health)
        assert R.load_reader("ssm_mixer_roofline").read(ctx, {}) is None
        assert R.load_reader("hybrid_weight_gemms_roofline").read(ctx, {}) is None
        assert R.load_reader("health_growth_ratio").read(
            ctx, spec("state_prefix_usable_share")["params"]) is None
        assert R.load_reader("health_path").read(
            ctx, spec("state_snapshots_held_peak")["params"]) is None
        assert R.load_reader("ssm_mixer_roofline").read(dict(ctx, fields=keye), {}) is None
        assert R.load_reader("hybrid_weight_gemms_roofline").read(dict(ctx, fields=keye), {}) is None


def case_the_new_metrics_are_the_new_cells_and_the_old_roofline_is_not():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    assert NEW <= mine and "weight_gemms_roofline" not in mine
    assert "experts_read_per_layer_pass" in mine        # the cell has the counter: listed beside Keye's
    assert {"prefix_hit_share", "attn_dev_share", "mlp_dev_share", "device_idle_share"} <= mine
    assert {m["name"] for m in R.cell_metrics(bench, "end_to_end", CELL)} == {"latency_p50_ms", "setup_s"}
    for w in bench["workloads"][:-1]:
        theirs = {m["name"] for m in R.cell_metrics(bench, "per_layer", w["name"])}
        assert not NEW & theirs and "weight_gemms_roofline" in theirs
    for name in NEW:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        s = spec(name)
        assert (s["unit"], s["source"], s["layer"], s["moves"]) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
        assert (BENCH / "readers" / f"{s['reader']}.py").exists() and s["what"]


def case_the_mix_is_the_issues_and_the_plan_builds():
    import workgen
    mix = json.loads((BENCH / "traffic" / "long-agent-sessions.json").read_text())
    assert (mix["pattern"], mix["clusters"], mix["preamble_tokens"]) == ("sessions", 2, 4096)
    assert mix["turn_added_tokens"] == [300 + 150 * i for i in range(8)]
    plan = workgen.build(mix, {}, {"DECODE_BATCH_SIZE": "16"}, 7, 50.0, workgen.Words(None))
    assert plan.offered == {"clients": 16} and len(plan.starts) == 16
    first = [plan.next_request(a) for a in range(16)]
    assert all(4096 + 300 <= r.query_tokens <= 4096 + 1350 for r in first)
    assert len({" ".join(r.query.split(" ")[:4096]) for r in first}) == 2      # two preambles


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_hybrid_metrics(case):
    case()
