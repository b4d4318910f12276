"""What PR 53 adds for a model of Mamba-2 and attention layers with a dense MLP
behind each: ``opsbytes_ssm_dense`` against ``ModelConfig.param_count`` and the
cache's leaves, the per-layer metrics of ``granite4hmicro-agent-sessions`` on a
/health pair and on a recorded excerpt of the cell's own trace (and ``None`` off
it). One parametrised test, a case each; the cell is found by its name, never by
its place in BENCHMARK.json."""

import gzip
import json
from pathlib import Path

import pytest

import modelmap
import opsbytes_ssm_dense as OB
import run as R
import xtrace

BENCH = Path(__file__).resolve().parent.parent
CELL = "granite4hmicro-agent-sessions"
CONFIG = "granite-4.0-h-micro"
NEW = {"ssd_step_roofline", "ssm_dense_mixer_roofline",
       "ssm_dense_weight_gemms_roofline", "ssm_state_bytes_per_sequence"}
JOINED = {"ssm_dev_share", "state_prefix_usable_share", "state_snapshots_held_peak",
          "window_rows_per_valid_row"}
RULES = json.loads((BENCH / "trace_categories.json").read_text())


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def config():
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    """A /health pair as the cell leaves it (the shape of /health.ssm and
    /health.ragged on the chip; counts rounded): 8,000 passes between the
    probes, 400 of them eager pieces and 500 a chunk's window; of the decode
    passes' 16 x 36 (row, layer) pairs a pass, 36 x 7,100 did not move."""
    before = {"ssm": {"forward_passes": 1000, "eager_prefill_passes": 100, "live_rows": 16,
                      "decode_rows_still": 36 * 900, "state_bytes": 76437504,
                      "prefix_tokens_matched": 50000, "prefix_tokens_usable": 49000,
                      "held_peak": 20},
              "ragged": {"window": {"windows": 60, "rows_computed": 1000, "rows_valid": 800}}}
    after = {"ssm": {"forward_passes": 9000, "eager_prefill_passes": 500, "live_rows": 16,
                     "decode_rows_still": 36 * 8000, "state_bytes": 76437504,
                     "prefix_tokens_matched": 450000, "prefix_tokens_usable": 447000,
                     "held_peak": 96},
             "ragged": {"window": {"windows": 560, "rows_computed": 13000, "rows_valid": 10800}}}
    return {"health_before": before, "health_after": after}


def ctx_with(trace):
    cfg, f = config()
    return dict(probes(), config=cfg, fields=f, peaks={"hbm_bytes_per_s": 819e9},
                trace_rules=RULES, trace=trace)


def recorded():
    """The cell's own trace, 0.05 s of it (cut from a traced run's
    trace_small.json; my chip run, PR 53), reduced as run.py reduces a capture."""
    rep = json.load(gzip.open(BENCH / "tests" / "data" / "trace_granite_small.json.gz", "rt"))
    return xtrace.reduce(rep, RULES, 40)


def case_bytes_by_kind_are_the_issues_arithmetic_and_the_programs():
    import serve

    cfg, f = config()
    model, _ = serve.register(cfg)
    assert OB.kinds(f) == "MDMDMDMDMD*DMDMDMDMD" * 4
    assert (OB.kinds(f).count("M"), OB.kinds(f).count("D"), OB.kinds(f).count("*")) == (36, 40, 4)
    assert OB.ssm_conv_channels(f) == 4352 == model.ssm_conv_dim
    # W_in 2048 x (4096 z + 4352 xBC + 64 dt), W_out 4096 x 2048
    assert OB.ssm_layer_bytes(f) == 2048 * 8512 + 4096 * 2048
    assert OB.ssm_layer_bytes(f) + OB.ssm_small_leaves(f) + 2048 == 25849280      # 25.85M
    assert OB.mlp_layer_bytes(f) + 2048 == 50333696                               # 50.33M
    assert OB.attention_layer_bytes(f) + 2048 == 10487808                         # 10.49M
    assert OB.head_bytes(f) == 100352 * 2048                                      # 205.5M, tied
    # the program's own count, and the issue's 3,191M
    assert OB.param_count(f) == model.param_count() == 3191396096
    assert round(OB.param_count(f) / 1e9, 2) == cfg["sizing"]["weights_GB"] == 3.19
    # a sequence's state, and a token's K/V
    assert OB.ssm_matrix_state_bytes(f) == 4 * 64 * 64 * 128
    assert OB.ssm_state_bytes(f) == 4 * 64 * 64 * 128 + 2 * 3 * 4352 == 2123264
    assert OB.state_bytes_per_sequence(f) == model.state_bytes() == 76437504
    assert cfg["sizing"]["state_bytes_per_sequence"] == 76437504
    assert OB.kv_bytes_per_token(f) == cfg["sizing"]["kv_bytes_per_token"] == 8192
    # a pass: the Mamba projections 0.93 GB, the other GEMMs 2.26 GB (head once)
    assert OB.ssm_pass_bytes(f) == 36 * OB.ssm_layer_bytes(f)
    assert round(OB.ssm_pass_bytes(f) / 1e9, 2) == 0.93
    assert OB.gemm_stream_bytes(f) == (40 * OB.mlp_layer_bytes(f)
                                       + 4 * OB.attention_layer_bytes(f) + OB.head_bytes(f))
    # 16 moving rows in 36 layers: 2.42 GB of state a decode pass
    assert round(OB.step_kernel_bytes(f, 16 * 36) / 1e9, 2) == 2.42
    env = cfg["server_env"]
    assert int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"]) == cfg["sizing"]["pool_tokens"]
    assert (cfg["reduced"], cfg["rehearsal_model"]) == ({}, "toy-ssm-dense")


def case_the_leaves_hold_what_the_functions_count():
    """The engine's cache at the published sizes, abstract: the state leaves are
    the functions' bytes; a pool row of the compiled kernel holds 4 heads of 128
    lanes for the model's 8 of 64, the same bytes."""
    import jax
    import jax.numpy as jnp
    import serve
    from ai_agent_kubectl_tpu.models.transformer import KVCache
    from ai_agent_kubectl_tpu.ops.ragged_attention import lane_heads

    cfg, f = config()
    model, _ = serve.register(cfg)
    assert lane_heads(model.head_dim, model.kv_heads_paged) == 2
    made = jax.eval_shape(lambda: KVCache.pool_zeros(
        model, n_blocks=4096, page=64, slots=16, dtype=jnp.bfloat16, lane_heads=2))
    assert made.ssm.shape == (36, 16, 64, 64, 128) and made.ssm.dtype == jnp.float32
    assert made.conv.shape == (36, 16, 3, 4352) and made.ssm_rows.shape == (1,)
    row = lambda a: a.size // a.shape[1] * a.dtype.itemsize
    assert row(made.ssm) + row(made.conv) == OB.state_bytes_per_sequence(f)
    assert made.k.shape == (4, 4096, 64, 4, 128)
    assert 2 * made.k.size * 2 / (4096 * 64) == OB.kv_bytes_per_token(f)
    plain = jax.eval_shape(lambda: KVCache.pool_zeros(
        model, n_blocks=4096, page=64, slots=16, dtype=jnp.bfloat16))
    assert plain.k.shape == (4, 4096, 64, 8, 64)


def case_the_step_kernels_roofline_counts_the_rows_that_moved():
    roof = R.load_reader("ssd_step_roofline")
    _, f = config()
    # 8,000 passes, 7,100 of them decode passes; 36 x (7,100 x 16 - 7,100) pairs moved
    pairs = 36 * (7100 * 16 - 7100)
    assert roof.moved(ctx_with(None)) == {"passes": 8000, "moving_row_layers": pairs}
    trace = {"forward_passes": 400, "busy_s": 3.0, "category_s": {"other_device": 2.0},
             "breakdown": {"device_ops": [["mlp:fusion", 0.9], ["other_device:ssd_step", 1.2],
                                          ["other_device:fusion", 0.5]]}}
    got = roof.read(ctx_with(trace), {})
    least = 2 * pairs * 4 * 64 * 64 * 128 * (400 / 8000) / 819e9
    assert got == pytest.approx(100.0 * least / 1.2) and 0 < got < 100
    # the op is not among the ten listed, or there is no trace: nothing
    quiet = dict(trace, breakdown={"device_ops": [["mlp:fusion", 0.9]]})
    assert roof.read(ctx_with(quiet), {}) is None
    assert roof.read(ctx_with(None), {}) is None


def case_the_mixer_roofline_is_the_projections_and_the_moved_state():
    roof = R.load_reader("ssm_dense_mixer_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 6.0, "category_s": {"other_device": 2.5}}
    pairs = 36 * (7100 * 16 - 7100)
    run_bytes = OB.ssm_pass_bytes(f) * 8000 + OB.step_kernel_bytes(f, pairs)
    got = roof.read(ctx_with(trace), {})
    assert got == pytest.approx(100.0 * run_bytes * (400 / 8000) / 819e9 / 2.5)
    assert 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(ctx_with({"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_the_gemm_roofline_counts_by_kind_and_the_head_once():
    roof = R.load_reader("ssm_dense_weight_gemms_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 4.0,
             "category_s": {"mlp": 1.1, "attn_proj": 0.1, "lm_head": 0.2, "other_device": 2.0}}
    least = OB.gemm_stream_bytes(f) * 400 / 819e9
    got = roof.read(ctx_with(trace), {})
    assert got == pytest.approx(100.0 * least / 1.4) and 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 5, "category_s": {"other_device": 1.0}}), {}) is None


def case_shares_and_counters_come_from_health():
    ctx = ctx_with({"forward_passes": 1, "busy_s": 2.0, "category_s": {"other_device": 0.5}})
    read = lambda name: R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"])
    assert read("ssm_dev_share") == 25.0
    assert read("ssm_state_bytes_per_sequence") == 76437504.0
    assert read("state_prefix_usable_share") == 99.5
    assert read("state_snapshots_held_peak") == 96.0
    assert read("window_rows_per_valid_row") == 1.2


def case_every_new_reader_reads_the_recorded_trace():
    """The cell's own capture, cut to 0.05 s: the kernel is among the listed ops
    under its name, the ``ssm/*`` scopes land in ``other_device``, and each
    reader gives a share between 0 and 100 of it."""
    tr = recorded()
    assert tr["devices"] == 1 and tr["forward_passes"] > 0
    ops = dict(tr["breakdown"]["device_ops"])
    assert ops.get("other_device:ssd_step", 0) > 0
    assert R.load_reader("ssd_step_roofline").kernel_seconds(tr) == ops["other_device:ssd_step"]
    # the probes' counts stand for the excerpt's own passes
    ctx = ctx_with(tr)
    for name in NEW - {"ssm_state_bytes_per_sequence"}:
        got = R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"])
        assert got is not None and 0 < got < 100, (name, got)
    share = R.load_reader("trace_category_share").read(ctx, spec("ssm_dev_share")["params"])
    assert 20 < share < 90


def case_a_program_without_the_counters_reports_none_of_them():
    """The parent of PR 53, or any other configuration: nothing raises, every
    new metric is left out."""
    cfg = json.loads((BENCH / "configs" / "mistral-7b-instruct-v0.2.json").read_text())
    dense = modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))
    nemo = json.loads((BENCH / "configs" / "nemotron-3-nano-30b-a3b-l13.json").read_text())
    hybrid = modelmap.fields(modelmap.sizes(nemo), modelmap.key_map(nemo))
    trace = {"forward_passes": 50, "busy_s": 1.0,
             "category_s": {"other_device": 0.1, "mlp": 0.5},
             "breakdown": {"device_ops": [["other_device:ssd_step", 0.05]]}}
    for health in ({}, {"ssm": None}, {"ssm": {"forward_passes": 10}}):
        ctx = dict(ctx_with(trace), health_before=health, health_after=health)
        assert R.load_reader("health_path").read(
            ctx, spec("ssm_state_bytes_per_sequence")["params"]) is None
        for name in ("ssd_step_roofline", "ssm_dense_mixer_roofline"):
            assert R.load_reader(name).read(ctx, {}) is None, name
        for name in NEW - {"ssm_state_bytes_per_sequence"}:
            for fields in (dense, hybrid):
                assert R.load_reader(name).read(dict(ctx, fields=fields), {}) is None, name
    for name in NEW - {"ssm_state_bytes_per_sequence"}:
        assert R.load_reader(name).read(ctx_with(None), {}) is None


def case_the_new_metrics_are_this_cells_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], entry["reduced"], file["reduced"]) == (
        1, "long-agent-sessions", [], {})
    assert entry["source"] == file["source"]
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    assert NEW | JOINED <= mine
    assert not mine & {"weight_gemms_roofline", "ssm_mixer_roofline",
                       "hybrid_weight_gemms_roofline", "lin_mixer_roofline"}
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
    """Every number of the published config is in the file under its own key;
    the keys the program reads reach its ModelConfig."""
    import serve

    cfg, f = config()
    model, sz = serve.register(cfg)
    assert (model.embed_multiplier, model.residual_multiplier, model.attention_multiplier,
            model.logits_scaling) == (12, 0.22, 0.015625, 8)
    assert model.softmax_scale == 1 / 64 and model.head_dim ** -0.5 == 1 / 8
    assert (model.tie_embeddings, model.use_rope, model.mixers_per_layer) == (True, False, 2)
    assert cfg["layer_types"] == [
        "attention" if c == "*" else "mamba" for c in cfg["layer_mixers"][0::2]]
    assert cfg["layer_types"].count("mamba") == 36 and len(cfg["layer_types"]) == 40
    assert [l for l, t in enumerate(cfg["layer_types"]) if t == "attention"] == [5, 15, 25, 35]
    assert (model.n_of("M"), model.n_of("D"), model.n_of("*")) == (36, 40, 4)


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=[c.__name__[5:] for c in CASES])
def test_ssm_dense_metrics(case):
    case()
