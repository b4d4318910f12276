"""The metrics PR 31 adds for a key-selecting configuration with grouped
experts: ``sparse_attn_rows_read_share``, ``experts_read_per_layer_pass`` and
``sparse_attention_roofline``. One parametrised test, a case each."""

import json
from pathlib import Path

import pytest

import modelmap
import opsbytes_sparse
import run as R

BENCH = Path(__file__).resolve().parent.parent
CELL = "keye30b-l8-longlogs-replay"


def spec(name):
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())


def keye_fields():
    cfg = json.loads((BENCH / "configs" / "keye-vl-2.0-30b-a3b-l8.json").read_text())
    return cfg, modelmap.fields(modelmap.sizes(cfg), modelmap.key_map(cfg))


def probes():
    before = {"sparse_attention": {"decode_rows_live": 1000, "decode_rows_selected": 1000,
                                   "index_rows_scanned": 5000, "forward_passes": 10},
              "moe": {"experts_read": 800, "layer_passes": 8}}
    after = {"sparse_attention": {"decode_rows_live": 101000, "decode_rows_selected": 21000,
                                  "index_rows_scanned": 405000, "forward_passes": 210},
             "moe": {"experts_read": 9600, "layer_passes": 108}}
    return {"health_before": before, "health_after": after}


def case_bytes_of_a_selection():
    _, f = keye_fields()
    assert opsbytes_sparse.index_key_bytes(f) == 128
    assert opsbytes_sparse.kv_row_bytes(f) == 2048
    # a decode query over 15,600 live keys: 2 MB of index keys + 4.2 MB of K and V a layer
    a_layer = opsbytes_sparse.selection_bytes(dict(f, n_layers=1), 15600, 2048)
    assert a_layer == 15600 * 128 + 2048 * 2048
    assert opsbytes_sparse.selection_bytes(f, 15600, 2048) == 8 * a_layer


def case_shares_are_growth_between_the_probes():
    ratio = R.load_reader("health_growth_ratio")
    assert ratio.read(probes(), spec("sparse_attn_rows_read_share")["params"]) == 20.0
    assert ratio.read(probes(), spec("experts_read_per_layer_pass")["params"]) == 88.0


def case_the_roofline_matches_the_capture_to_the_counters_by_passes():
    roof = R.load_reader("sparse_attention_roofline")
    _, f = keye_fields()
    ctx = dict(probes(), fields=f, peaks={"hbm_bytes_per_s": 819e9},
               trace={"forward_passes": 50, "category_s": {"attention": 0.002}, "busy_s": 1.0})
    least = 8 * (400000 * 128 + 20000 * 2048) * 50 / 200 / 819e9
    assert roof.read(ctx, {}) == pytest.approx(100.0 * least / 0.002)
    assert roof.read(dict(ctx, trace={"forward_passes": 0, "category_s": {}}), {}) is None
    assert roof.read(dict(ctx, trace={"forward_passes": 9, "category_s": {"mlp": 1.0}}), {}) is None


def case_a_program_without_the_counters_reports_none_of_them():
    """The parent of PR 31, or any configuration that neither selects nor groups."""
    ratio, roof = R.load_reader("health_growth_ratio"), R.load_reader("sparse_attention_roofline")
    _, f = keye_fields()
    for health in ({}, {"sparse_attention": None, "moe": None}):
        ctx = {"health_before": health, "health_after": health, "fields": f,
               "peaks": {"hbm_bytes_per_s": 819e9},
               "trace": {"forward_passes": 50, "category_s": {"attention": 0.002}}}
        assert ratio.read(ctx, spec("sparse_attn_rows_read_share")["params"]) is None
        assert ratio.read(ctx, spec("experts_read_per_layer_pass")["params"]) is None
        assert roof.read(ctx, {}) is None


def case_the_three_are_reported_in_the_new_cell_alone():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    mine = {m["name"] for m in R.cell_metrics(bench, "per_layer", CELL)}
    new = {"sparse_attn_rows_read_share", "experts_read_per_layer_pass", "sparse_attention_roofline"}
    assert new <= mine and {"weight_gemms_roofline", "attn_dev_share", "mlp_dev_share"} <= mine
    for cell in ("mistral7b-chat-steady", "mixtral6l-chat-steady", "mixtral8x7b-tp4-chat-steady"):
        assert not new & {m["name"] for m in R.cell_metrics(bench, "per_layer", cell)}
    for name in new:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        s = spec(name)
        assert (s["unit"], s["source"], s["layer"], s["moves"]) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
        assert (BENCH / "readers" / f"{s['reader']}.py").exists() and s["what"]


def case_the_file_streams_the_experts_the_counters_say():
    roofline = R.load_reader("trace_roofline")
    cfg, f = keye_fields()
    ctx = dict(probes(), config=cfg)
    assert roofline.experts_streamed(ctx) == 88.0


CASES = [v for k, v in sorted(globals().items()) if k.startswith("case_")]


@pytest.mark.parametrize("case", CASES, ids=lambda f: f.__name__[5:])
def test_sparse_metrics(case):
    case()
