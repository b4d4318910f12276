"""What PR 48 adds for a model of Kimi-delta-attention and latent-attention
layers with a chip's share of its experts: ``opsbytes_kda`` against
``ModelConfig.param_count`` and the cache's leaves, the per-layer metrics of
``ling3flash-l12-xlonglogs-replay`` on a /health pair and a reduced trace. One
parametrised test, a case each; the cell is found by its name, never by its
place in BENCHMARK.json."""

import json
from pathlib import Path

import pytest

import modelmap
import opsbytes_kda as OB
import run as R

BENCH = Path(__file__).resolve().parent.parent
CELL = "ling3flash-l12-xlonglogs-replay"
CONFIG = "ling-3.0-flash-vl-l12"
NEW = {"kda_mixer_roofline", "kda_weight_gemms_roofline", "expert_picks_held_share",
       "kda_latent_attention_roofline", "kda_latent_rows_per_decode_row"}
#: metrics the benchmark had whose readers read the same thing here, unedited
JOINED = {"window_rows_per_valid_row", "experts_read_per_layer_pass",
          "state_prefix_usable_share", "state_snapshots_held_peak",
          "latent_cache_bytes_per_token", "lin_dev_share", "lin_state_bytes_per_sequence"}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def config():
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    """A /health pair as the cell leaves it (the shapes of /health.moe, .ssm,
    .linear_attention, .latent_attention and .kv_pool; counts rounded)."""
    lin = {"layers_linear": 10, "layers_full": 2, "state_bytes_per_sequence": 21708800}
    before = {"ssm": {"prefix_tokens_matched": 100000, "prefix_tokens_usable": 99000,
                      "held_peak": 40},
              "linear_attention": dict(lin, forward_passes=1000, decode_rows_linear=100000),
              "latent_attention": {"forward_passes": 1000, "decode_rows": 10000,
                                   "latent_rows_read": 400000000, "window_pairs": 10 ** 9},
              "moe": {"experts_read": 200000, "layer_passes": 10000, "picks": 1000000,
                      "picks_held": 250000},
              "kv_pool": {"bytes_per_token": 2304.0},
              "ragged": {"window": {"rows_computed": 1000, "rows_valid": 800}}}
    after = {"ssm": {"prefix_tokens_matched": 900000, "prefix_tokens_usable": 895000,
                     "held_peak": 128},
             "linear_attention": dict(lin, forward_passes=5000, decode_rows_linear=740000),
             "latent_attention": {"forward_passes": 5000, "decode_rows": 74000,
                                  "latent_rows_read": 3000000000, "window_pairs": 9 * 10 ** 9},
             "moe": {"experts_read": 1400000, "layer_passes": 50000, "picks": 9000000,
                     "picks_held": 2350000},
             "kv_pool": {"bytes_per_token": 2304.0},
             "ragged": {"window": {"rows_computed": 13000, "rows_valid": 10800}}}
    return {"health_before": before, "health_after": after}


def ctx_with(trace):
    cfg, f = config()
    return dict(probes(), config=cfg, fields=f, peaks=PEAKS,
                trace_rules=json.loads((BENCH / "trace_categories.json").read_text()),
                trace=trace)


def case_bytes_by_kind_are_the_issues_arithmetic_and_the_programs():
    import serve

    cfg, f = config()
    model, _ = serve.register(cfg)
    assert OB.kinds(f) == "LDLDLELELE*E" + "LELELELELE*E"
    assert OB.kda_conv_channels(f) == 12288 == model.lin_conv_dim
    # fused W_q | W_k | W_v | W_g 2560 x 16,384; W_f and W_o 2560 x 4096; W_b bf16
    assert OB.kda_layer_bytes(f) == 2560 * (16384 + 4096 + 4096) + 2 * 2560 * 32
    # W_q 2560 x 6144, W_dkv 2560 x 576, W_o 4096 x 2560 int8; W_ukv 512 x 8192
    # and the head gate 2560 x 32 bf16
    assert OB.latent_layer_bytes(f) == (2560 * (6144 + 576 + 4096)
                                        + 2 * (512 * 8192 + 2560 * 32))
    assert OB.dense_layer_bytes(f) == 3 * 2560 * 6144                       # 47.19M
    assert OB.expert_layer_bytes(f) == 3 * 2560 * 768 * 129 + 2 * 2560 * 512
    assert OB.expert_layer_bytes(f, 28.0) == 3 * 2560 * 768 * 29 + 2 * 2560 * 512
    assert OB.head_bytes(f) == 157184 * 2560
    # one parameter a byte but W_ukv, W_b, the head gate and the router (2):
    # the program's own count, less the leaves the functions leave out (the
    # convolution, A_log, the biases, the gains)
    small = (10 * (4 * 12288 + 32 + 4096 + 128 + 2560) + 2 * (512 + 2560)
             + 2 * 2560 + 10 * (512 + 2560) + 2560)
    bf16_twice = (10 * 2560 * 32 + 2 * (512 * 8192 + 2560 * 32) + 10 * 2560 * 512)
    assert OB.whole_model_bytes(f) == model.param_count() - small + bf16_twice
    assert model.param_count() == cfg["sizing"]["param_count"]
    assert round(model.param_count() / 1e9, 2) == cfg["sizing"]["weights_GB"] == 9.22
    # a sequence's state, and a token's rows
    assert OB.kda_matrix_bytes(f) == 4 * 128 * 4096 == 2097152
    assert OB.kda_state_bytes(f) == 2097152 + 2 * 3 * 12288
    assert OB.state_bytes_per_sequence(f) == model.state_bytes() == 21708800
    assert OB.cache_bytes_per_token(f) == cfg["sizing"]["cache_bytes_per_token"] == 2304
    assert OB.latent_flops_per_pair(f) == 32 * 2 * (576 + 512) == 69632
    assert OB.kda_pass_bytes(f) == 10 * OB.kda_layer_bytes(f)
    assert OB.gemm_stream_bytes(f, 28.0) == (
        2 * OB.dense_layer_bytes(f) + 10 * OB.expert_layer_bytes(f, 28.0)
        + 2 * OB.latent_layer_bytes(f) + OB.head_bytes(f))
    env = cfg["server_env"]
    assert int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"]) == cfg["sizing"]["pool_tokens"]


def case_the_leaves_hold_what_the_functions_count():
    """The engine's cache at the published sizes, abstract: a plane a LATENT
    layer of 1,152-lane pair rows, a plane a KDA layer of float32 state."""
    import jax
    import jax.numpy as jnp
    import serve
    from ai_agent_kubectl_tpu.models.transformer import KVCache

    cfg, f = config()
    model, _ = serve.register(cfg)
    made = jax.eval_shape(lambda: KVCache.pool_zeros(
        model, n_blocks=512, page=64, slots=16, dtype=jnp.bfloat16, counts_experts=True))
    assert made.k is None and made.v is None
    assert made.lat.shape == (2, 512, 32, 1152)
    assert made.lat.size * 2 / (512 * 64) == OB.cache_bytes_per_token(f)
    assert made.lin.shape == (10, 16, 128, 4096) and made.lin.dtype == jnp.float32
    assert made.lconv.shape == (10, 16, 3, 12288)
    row = lambda a: a.size // a.shape[1] * a.dtype.itemsize
    assert row(made.lin) + row(made.lconv) == OB.state_bytes_per_sequence(f)
    assert (made.lin_rows.shape, made.lat_rows.shape, made.expert_picks.shape) == (
        (6,), (2,), (2,))


def case_the_mixer_roofline_counts_projections_and_the_rows_moved():
    roof = R.load_reader("kda_mixer_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 6.0, "category_s": {"other_device": 1.6}}
    got = roof.read(ctx_with(trace), {})
    moved = (740000 - 100000) * 400 / 4000          # rows x layers in the capture
    least = (OB.kda_pass_bytes(f) * 400 + moved * 2 * 2097152) / 819e9
    assert got == pytest.approx(100.0 * least / 1.6) and 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(ctx_with({"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_the_gemm_and_latent_rooflines_count_by_kind():
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 4.0,
             "category_s": {"mlp": 1.2, "attn_proj": 0.2, "lm_head": 0.25, "attention": 0.9}}
    read_a_pass = (1400000 - 200000) / 40000
    got = R.load_reader("kda_weight_gemms_roofline").read(ctx_with(trace), {})
    assert got == pytest.approx(
        100.0 * OB.gemm_stream_bytes(f, read_a_pass) * 400 / 819e9 / 1.65) and 0 < got < 100
    got = R.load_reader("kda_latent_attention_roofline").read(ctx_with(trace), {})
    least = OB.latent_least_seconds(f, 2.6e9, 8e9, PEAKS)
    assert least == pytest.approx(max(2.6e9 * 1152 / 819e9, (2.6e9 + 2 * 8e9) * 69632 / 197e12))
    assert got == pytest.approx(100.0 * least * 400 / 4000 / 0.9) and 0 < got < 100


def case_shares_and_counters_come_from_health():
    ctx = ctx_with({"forward_passes": 1, "busy_s": 2.0, "category_s": {"other_device": 0.5}})
    read = lambda name: R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"])
    assert read("lin_dev_share") == 25.0
    assert read("lin_state_bytes_per_sequence") == 21708800.0
    assert read("expert_picks_held_share") == pytest.approx(26.25)
    assert read("kda_latent_rows_per_decode_row") == pytest.approx(2.6e9 / 64000 / 2)
    assert read("latent_cache_bytes_per_token") == 2304.0
    assert read("experts_read_per_layer_pass") == 30.0
    assert read("state_prefix_usable_share") == 99.5
    assert read("state_snapshots_held_peak") == 128.0
    assert read("window_rows_per_valid_row") == 1.2


def case_a_program_without_the_counters_reports_none_of_them():
    """The parent of PR 48, or a configuration of another family: nothing
    raises, every new metric is left out."""
    _, mine = config()
    trace = {"forward_passes": 50, "busy_s": 1.0,
             "category_s": {"other_device": 0.1, "mlp": 0.5, "attention": 0.2}}
    others = []
    for name in ("mistral-7b-instruct-v0.2", "olmo-hybrid-7b", "mistral-small-4-119b-2603-l9"):
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        others.append(modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg)))
    for health in ({}, {"moe": None, "linear_attention": None, "latent_attention": None}):
        ctx = dict(ctx_with(trace), health_before=health, health_after=health)
        for name in sorted(NEW):
            assert R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"]) is None, name
    for f in others:
        for name in ("kda_mixer_roofline", "kda_weight_gemms_roofline",
                     "kda_latent_attention_roofline"):
            assert R.load_reader(name).read(dict(ctx_with(trace), fields=f), {}) is None, name
    assert R.load_reader("kda_mixer_roofline").read(ctx_with(None), {}) is None


def case_the_new_metrics_are_this_cells_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], entry["reduced"]) == (
        1, "xlong-logs-replay", ["num_hidden_layers", "num_experts"])
    assert list(file["reduced"]) == entry["reduced"]
    assert len(bench["workloads"]) == 9 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    assert NEW | JOINED <= mine
    assert not mine & {"weight_gemms_roofline", "lin_mixer_roofline",
                       "latent_attention_roofline", "mla_weight_gemms_roofline",
                       "latent_rows_per_decode_row", "lin_weight_gemms_roofline"}
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
    # the same mix as the other latent configuration's cell, unedited
    other = R.resolve_cell(bench, "mistral4-l9-xlonglogs-replay")
    assert other[3] == mix and other[0]["traffic"] == cell["traffic"]


def case_the_mix_is_the_issues_and_the_plan_builds():
    import workgen

    cfg, _ = config()
    mix = json.loads((BENCH / "traffic" / "xlong-logs-replay.json").read_text())
    plan = workgen.build(mix, {}, cfg["server_env"], 2147483659, 50.0, workgen.Words(None))
    assert plan.offered == {"clients": 16}, plan.offered
    env = cfg["server_env"]
    assert int(env["DECODE_BATCH_SIZE"]) == 16 and int(env["STATE_SNAPSHOTS"]) == 128
    longest = 24576 + 24 + int(env["MAX_NEW_TOKENS"])
    assert longest < int(env["MAX_SEQ_LEN"])
    # 24 logs of the mix's mean beside 16 live tails fit the pool
    assert 24 * 20480 + 16 * 512 < int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"])


def case_the_comparison_covers_a_whole_period_through_window_state_and_pool():
    cfg, _ = config()
    chk = cfg["reference_check"]
    lens = chk["prompt_tokens"]
    # both dense layers, four expert layers, five KDA layers, one latent layer
    assert chk["layers"] >= 6 and cfg["layer_mixers"][:12] == "LDLDLELELE*E"
    assert chk["clear_if"] == {"aux": "clear_score", "min": 1.0} and chk["unclear_share_max"] == 1.0
    # sixteen rows (the scan takes a window's rows four at a time), and decode
    # steps enough that some decode positions are held by themselves: a
    # position in ~600 is (the reference's ``steady``), about nine a comparison
    assert len(lens) == chk["batch"] == 16 and max(lens) <= chk["window"] == 512
    assert chk["decode_steps"] >= 64 and sum(lens) + chk["decode_steps"] * len(lens) > 5000
    # the rule's thresholds are the reference's: a score against another's, a
    # group's at twice that; the three tokens behind at half
    import refcheck

    ref = refcheck.load_reference(cfg["reference"])
    assert (ref.CLEAR_MIN, ref.CLEAR_GROUP_MIN) == (0.006, 0.012)
    assert (ref.NEIGHBOUR_MIN, ref.NEIGHBOUR_GROUP_MIN, ref.NEIGHBOURS) == (0.003, 0.006, 3)
    # rows cross the scan's 64-row chunks and 16-row blocks; one is shorter than
    # the convolution's taps
    assert any(n > 448 for n in lens) and any(64 < n < 128 for n in lens)
    assert min(lens) < cfg["short_conv_kernel_size"]
    assert R.child_env(cfg, 1, True)["MODEL_NAME"] == "toy-kda-mla-moe"
    assert "w8a8" in chk["tolerance_why"] or "8-bit" in chk["tolerance_why"]


def case_the_parent_ends_at_once_on_the_cell():
    """A program without ``ModelConfig.n_group`` is told so by the first key of
    the file it lacks a field for, before anything is launched on the chip."""
    import dataclasses

    from ai_agent_kubectl_tpu.models import config as mc

    cfg, _ = config()
    sz, kmap = modelmap.sizes(cfg), modelmap.key_map(cfg)
    fields = [f for f in dataclasses.fields(mc.ModelConfig)
              if f.name not in ("n_group", "topk_group", "lin_channel_decay", "lin_decay_floor",
                                "lin_out_gate")]
    parent = dataclasses.make_dataclass("ModelConfig", [(f.name, f.type, f) for f in fields],
                                        frozen=True)
    real, mc.ModelConfig = mc.ModelConfig, parent
    try:
        with pytest.raises(SystemExit, match="n_group maps to ModelConfig.n_group, which the "
                                             "program does not have"):
            modelmap.model_config(cfg["name"], sz, kmap)
    finally:
        mc.ModelConfig = real


def case_the_cell_rehearses():
    """``run.py --rehearse`` of the cell by its name (benchmark/tests/
    test_rehearsal.py goes over every cell): launcher, the comparison at toy
    widths under the file's own rule, generator, readers."""
    import subprocess
    import sys

    run = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL, "--seed", "3000000023",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=1200, cwd=BENCH.parent)
    assert run.returncode == 0, run.stderr[-3000:]
    lines = run.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["rehearsal"] is True and last["failed"] == 0
    said = [json.loads(ln[len("bench: "):]) for ln in lines if ln.startswith("bench: ")]
    refcheck = next(s["refcheck"] for s in said if "refcheck" in s)
    assert refcheck["ok"] and refcheck["positions_clear"] > 0
    values = next(s["rehearsal_values"] for s in said if "rehearsal_values" in s)
    # (a trace's metrics find nothing on the CPU; what /health states does)
    assert values["lin_state_bytes_per_sequence"] > 0 and values["latent_cache_bytes_per_token"] > 0


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_kda_metrics(case):
    case()
