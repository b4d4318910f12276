"""What PR 45 adds for a model of linear-attention and full-attention layers:
``opsbytes_linear`` against ``ModelConfig.param_count`` and the cache's leaves,
the per-layer metrics of ``olmohybrid7b-agent-sessions`` on a /health pair and a
reduced trace, and the cell's rehearsal. One parametrised test, a case each; the
cell is found by its name, never by its place in BENCHMARK.json."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

import modelmap
import opsbytes_linear as OB
import run as R

BENCH = Path(__file__).resolve().parent.parent
CELL = "olmohybrid7b-agent-sessions"
CONFIG = "olmo-hybrid-7b"
NEW = {"lin_mixer_roofline", "lin_dev_share", "lin_weight_gemms_roofline",
       "lin_state_bytes_per_sequence", "lin_full_keys_per_decode_row"}
JOINED = {"state_prefix_usable_share", "state_snapshots_held_peak",
          "window_rows_per_valid_row"}


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def config():
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    """A /health pair as the cell leaves it (the shape of /health.ssm and
    /health.linear_attention on the chip; counts rounded)."""
    lin = {"layers_linear": 24, "layers_full": 8, "state_bytes_per_sequence": 54743040}
    before = {"ssm": {"forward_passes": 1000, "eager_prefill_passes": 100, "live_rows": 8,
                      "prefix_tokens_matched": 50000, "prefix_tokens_usable": 49000,
                      "held_peak": 20},
              "linear_attention": dict(lin, decode_rows_full=8000, full_keys_read=20000000),
              "ragged": {"window": {"rows_computed": 1000, "rows_valid": 800}}}
    after = {"ssm": {"forward_passes": 9000, "eager_prefill_passes": 500, "live_rows": 8,
                     "prefix_tokens_matched": 450000, "prefix_tokens_usable": 447000,
                     "held_peak": 48},
             "linear_attention": dict(lin, decode_rows_full=72000, full_keys_read=212000000),
             "ragged": {"window": {"rows_computed": 13000, "rows_valid": 10800}}}
    return {"health_before": before, "health_after": after}


def ctx_with(trace):
    cfg, f = config()
    return dict(probes(), config=cfg, fields=f, peaks={"hbm_bytes_per_s": 819e9},
                trace_rules=json.loads((BENCH / "trace_categories.json").read_text()),
                trace=trace)


def case_bytes_by_kind_are_the_issues_arithmetic_and_the_programs():
    import serve

    cfg, f = config()
    model, _ = serve.register(cfg)
    assert OB.kinds(f) == "LDLDLD*D" * 8
    assert OB.lin_conv_channels(f) == 11520 == model.lin_conv_dim
    # W_q, W_k 3840 x 2880; W_v, W_g 3840 x 5760; W_o 5760 x 3840; W_a, W_b bf16
    assert OB.lin_layer_bytes(f) == 3840 * (2 * 2880 + 3 * 5760) + 2 * 2 * 3840 * 30
    assert OB.attention_layer_bytes(f) == 4 * 3840 * 3840                   # 59.0M
    assert OB.mlp_layer_bytes(f) == 3 * 3840 * 11008                        # 126.8M
    assert OB.head_bytes(f) == 100352 * 3840
    # one parameter a byte but W_a and W_b (2): the program's own count, less
    # the leaves the functions leave out (convolution, decays, biases, gains)
    small = (24 * (4 * 11520 + 2 * 30 + 192 + 3840) + 8 * (2 * 3840 + 3840)
             + 32 * 3840 + 3840)
    extra_bf16 = 24 * 2 * 3840 * 30
    assert OB.whole_model_bytes(f) == model.param_count() - small + extra_bf16
    assert round(OB.whole_model_bytes(f) / 1e9, 2) == cfg["sizing"]["weights_GB"] == 7.43
    # a sequence's state, and a token's K/V
    assert OB.lin_state_bytes(f) == 4 * 96 * 5760 + 2 * 3 * 11520 == 2280960
    assert OB.state_bytes_per_sequence(f) == model.state_bytes() == 54743040
    assert cfg["sizing"]["state_bytes_per_sequence"] == 54743040
    assert OB.kv_bytes_per_token(f) == cfg["sizing"]["kv_bytes_per_token"] == 122880
    # a pass of the linear layers: the projections, 2.13 GB, whatever its rows
    assert OB.lin_pass_bytes(f) == 24 * OB.lin_layer_bytes(f)
    assert round(OB.lin_pass_bytes(f) / 1e9, 2) == 2.13
    assert OB.gemm_stream_bytes(f) == (32 * OB.mlp_layer_bytes(f)
                                       + 8 * OB.attention_layer_bytes(f) + OB.head_bytes(f))
    env = cfg["server_env"]
    assert int(env["KV_POOL_BLOCKS"]) * int(env["KV_POOL_PAGE"]) == cfg["sizing"]["pool_tokens"]


def case_the_leaves_hold_what_the_functions_count():
    """The engine's cache at the published sizes, abstract: the state leaves are
    the functions' bytes; a pool row holds 32 KV heads for the model's 30."""
    import jax
    import jax.numpy as jnp
    import serve
    from ai_agent_kubectl_tpu.models.transformer import KVCache

    cfg, f = config()
    model, _ = serve.register(cfg)
    made = jax.eval_shape(lambda: KVCache.pool_zeros(
        model, n_blocks=512, page=64, slots=8, dtype=jnp.bfloat16))
    assert made.lin.shape == (24, 8, 96, 5760) and made.lin.dtype == jnp.float32
    assert made.lconv.shape == (24, 8, 3, 11520) and made.lin_rows.shape == (5,)
    row = lambda a: a.size // a.shape[1] * a.dtype.itemsize
    assert row(made.lin) + row(made.lconv) == OB.state_bytes_per_sequence(f)
    assert made.k.shape == (8, 512, 64, 32, 128)
    held = 2 * made.k.size * 2 / (512 * 64)
    assert held == 131072 and OB.kv_bytes_per_token(f) == held * 30 / 32


def case_the_mixer_roofline_is_the_projections_a_captured_pass():
    roof = R.load_reader("lin_mixer_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 6.0, "category_s": {"other_device": 2.0}}
    got = roof.read(ctx_with(trace), spec("lin_mixer_roofline")["params"])
    assert got == pytest.approx(100.0 * (OB.lin_pass_bytes(f) * 400 / 819e9) / 2.0)
    assert 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(ctx_with({"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_the_gemm_roofline_counts_by_kind():
    roof = R.load_reader("lin_weight_gemms_roofline")
    _, f = config()
    trace = {"forward_passes": 400, "busy_s": 4.0,
             "category_s": {"mlp": 2.3, "attn_proj": 0.3, "lm_head": 0.2, "other_device": 1.0}}
    least = OB.gemm_stream_bytes(f) * 400 / 819e9
    got = roof.read(ctx_with(trace), {})
    assert got == pytest.approx(100.0 * least / 2.8) and 0 < got < 100
    assert roof.read(ctx_with({"forward_passes": 5, "category_s": {"other_device": 1.0}}), {}) is None


def case_shares_and_counters_come_from_health():
    ctx = ctx_with({"forward_passes": 1, "busy_s": 2.0, "category_s": {"other_device": 0.5}})
    read = lambda name: R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"])
    assert read("lin_dev_share") == 25.0
    assert read("lin_state_bytes_per_sequence") == 54743040.0
    assert read("lin_full_keys_per_decode_row") == 3000.0
    assert read("state_prefix_usable_share") == 99.5
    assert read("state_snapshots_held_peak") == 48.0
    assert read("window_rows_per_valid_row") == 1.2


def case_a_program_without_the_counters_reports_none_of_them():
    """The parent of PR 45, or any configuration without linear layers: nothing
    raises, every new metric is left out."""
    cfg = json.loads((BENCH / "configs" / "mistral-7b-instruct-v0.2.json").read_text())
    dense = modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))
    trace = {"forward_passes": 50, "busy_s": 1.0, "category_s": {"other_device": 0.1, "mlp": 0.5}}
    for health in ({}, {"ssm": None, "linear_attention": None}):
        ctx = dict(ctx_with(trace), health_before=health, health_after=health)
        # (the two program counters; the rooflines and the share read the trace
        # and the configuration's fields, no counter)
        for name in ("lin_state_bytes_per_sequence", "lin_full_keys_per_decode_row"):
            assert R.load_reader(spec(name)["reader"]).read(ctx, spec(name)["params"]) is None, name
        for name in ("lin_mixer_roofline", "lin_weight_gemms_roofline"):
            assert R.load_reader(name).read(dict(ctx, fields=dense), {}) is None
    assert R.load_reader("lin_mixer_roofline").read(dict(ctx_with(None)), {}) is None


def case_the_new_metrics_are_this_cells_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell, entry, file, mix = R.resolve_cell(bench, CELL)
    assert (cell["chips"], cell["traffic"], entry["reduced"], file["reduced"]) == (
        1, "agent-sessions", [], {})
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    assert NEW | JOINED <= mine
    assert not mine & {"weight_gemms_roofline", "ssm_mixer_roofline", "ssm_dev_share",
                       "hybrid_weight_gemms_roofline", "mixed_attention_roofline"}
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


def case_the_mix_is_the_issues_and_the_plan_builds():
    import workgen

    cfg, _ = config()
    mix = json.loads((BENCH / "traffic" / "agent-sessions.json").read_text())
    assert (mix["pattern"], mix["clusters"], mix["preamble_tokens"], mix["clients"]) == (
        "sessions", 2, 2048, 8)
    assert mix["turn_added_tokens"] == [150, 170, 190, 210, 230, 250]
    plan = workgen.build(mix, {}, cfg["server_env"], 2147483659, 50.0, workgen.Words(None))
    assert plan.offered == {"clients": 8} and len(plan.starts) == 8
    first = [plan.next_request(a) for a in range(8)]
    assert all(2048 + 150 <= r.query_tokens <= 2048 + 250 for r in first)
    assert len({" ".join(r.query.split(" ")[:2048]) for r in first}) == 2      # two preambles
    longest = 2048 + sum(mix["turn_added_tokens"]) + int(cfg["server_env"]["MAX_NEW_TOKENS"])
    assert longest < int(cfg["server_env"]["MAX_SEQ_LEN"])
    # one slot an agent, and a snapshot row for every turn of every agent
    assert int(cfg["server_env"]["DECODE_BATCH_SIZE"]) == mix["clients"]
    assert int(cfg["server_env"]["STATE_SNAPSHOTS"]) >= mix["clients"] * mix["turns_per_session"]


def case_the_comparison_crosses_the_chunks_and_the_convolutions_taps():
    cfg, _ = config()
    chk = cfg["reference_check"]
    lens = chk["prompt_tokens"]
    # two periods, so that the second addresses its layers by a traced ordinal
    # past 0: the scan over periods the cell times is the program compared
    assert chk["layers"] == 8 and chk["decode_steps"] == 3
    # a sequence's first two tokens (a state of one or two keys under the
    # per-head norm) are held as a group by their median, the rest one by one
    assert chk["clear_if"] == {"aux": "position", "min": 2}
    assert 2 * len(lens) / (sum(lens) + 3 * len(lens)) < chk["unclear_share_max"] <= 0.01
    assert cfg["layer_mixers"][:16] == "LDLDLD*D" * 2
    assert len(lens) == chk["batch"] == 5 and max(lens) <= chk["window"] == 512
    assert any(n > 448 for n in lens) and any(64 < n < 128 for n in lens)
    assert any(128 < n < 192 for n in lens) and min(lens) < cfg["linear_conv_kernel_dim"]
    assert R.child_env(cfg, 1, True)["MODEL_NAME"] == "toy-linear-hybrid"


def case_the_cell_rehearses():
    """``run.py --rehearse`` of the cell on the CPU: toy-linear-hybrid behind the
    real server, the comparison at toy widths over two periods under the file's
    tolerance, every new program counter read, ``correct`` (on the CPU the engine
    serves the ``gather`` regime, whose eager pieces take one KV rung for a model
    of long prompts, so every program a request runs was run before ready)."""
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL, "--seed", "7",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=1500)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and last["metrics"] == {} and last["failed"] == 0
    assert last["correct"] is True and last["attempted"] > 0, out.stderr[-3000:]
    said = [json.loads(ln[len("bench: "):]) for ln in lines if ln.startswith("bench: ")]
    check = next(s["refcheck"] for s in said if "refcheck" in s)
    assert check["ok"] is True and check["layers"] == 8 and check["positions_unclear"] == 2 * 5
    assert check["positions_clear"] == 500 + 452 + 131 + 70 + 3 + 5 * 3 - 2 * 5
    values = next(s["rehearsal_values"] for s in said if "rehearsal_values" in s)
    assert {"lin_state_bytes_per_sequence", "lin_full_keys_per_decode_row",
            "state_snapshots_held_peak"} <= set(values)
    assert values["lin_state_bytes_per_sequence"] == 104832.0


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_linear_metrics(case):
    case()
