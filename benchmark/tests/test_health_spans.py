"""readers/health_spans.py on a recorded /health pair (tests/data/
health_spans_pair.json: toy-8m on the CPU, so the numbers are only
arithmetic here): means add, a program without the section gives None."""
import json
from pathlib import Path

import pytest

import run

HERE = Path(__file__).resolve().parent
NEW = ("sched_wait_ms_mean", "slot_wait_ms_mean", "admit_host_ms_mean",
       "stage_wait_ms_mean", "first_chunk_ms_mean", "chunks_ahead_mean",
       "sched_host_ms_per_chunk", "sched_fetch_wait_share")


@pytest.fixture(scope="module")
def pair():
    return json.loads((HERE / "data" / "health_spans_pair.json").read_text())


def value(name, ctx):
    spec = run.load_json(run.HERE / "metrics" / f"{name}.json")
    assert spec["reader"] == "health_spans" and spec["name"] == name
    return run.load_reader(spec["reader"]).read(ctx, spec.get("params", {}))


def delta(pair, span, key):
    return (pair["health_after"]["spans"][span][key]
            - pair["health_before"]["spans"].get(span, {}).get(key, 0))


def test_every_new_metric_is_declared_like_its_file():
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec = run.load_json(run.HERE / "metrics" / f"{name}.json")
        assert "workloads" not in by[name]            # every cell
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by[name][key] == spec[key], (name, key)
        assert "/health.spans" in spec["what"]


def test_means_add_to_their_parents(pair):
    v = {name: value(name, pair) for name in NEW}
    assert all(x is not None for x in v.values()), v
    n_q = delta(pair, "queue_wait", "count")
    n_p = delta(pair, "prefill", "count")
    assert n_q == n_p == 7
    # sched_wait + slot_wait = the mean queue wait
    assert v["sched_wait_ms_mean"] + v["slot_wait_ms_mean"] == pytest.approx(
        delta(pair, "queue_wait", "total_ms") / n_q)
    # admit_host + stage_wait + first_chunk = the mean prefill (recorded
    # totals are rounded to the microsecond)
    assert (v["admit_host_ms_mean"] + v["stage_wait_ms_mean"]
            + v["first_chunk_ms_mean"]) == pytest.approx(
        delta(pair, "prefill", "total_ms") / n_p, abs=0.01)
    assert v["slot_wait_ms_mean"] > v["sched_wait_ms_mean"] >= 0   # batch 2
    assert v["chunks_ahead_mean"] == pytest.approx(12 / 7)
    assert 0 < v["sched_fetch_wait_share"] <= 100
    sched = {k: delta(pair, "sched_thread_s", k)
             for k in pair["health_after"]["spans"]["sched_thread_s"]}
    assert v["sched_host_ms_per_chunk"] == pytest.approx(
        1000 * (sched["admit"] + sched["dispatch"] + sched["consume"]
                + sched["other"]) / sched["chunks_consumed"])
    assert v["sched_fetch_wait_share"] == pytest.approx(
        100 * sched["fetch_wait"] / (sched["elapsed"] - sched["idle"]))


@pytest.mark.parametrize("ctx", [
    {},                                                      # no probes
    {"health_before": {"kv_pool": {}}, "health_after": {"kv_pool": {}}},
    {"health_before": {}, "health_after": {"spans": None}},  # the parent
])
def test_a_program_without_the_section_reports_nothing(ctx):
    assert all(value(name, ctx) is None for name in NEW)


def test_a_span_that_never_closed_counts_zero(pair):
    """Non-ragged admissions write no stage_wait: 0 ms each, not a hole."""
    ctx = json.loads(json.dumps(pair))
    for h in ("health_before", "health_after"):
        ctx[h]["spans"].pop("stage_wait")
    assert value("stage_wait_ms_mean", ctx) == 0.0
    # nothing finished between the probes: no mean to report
    same = {"health_before": pair["health_after"],
            "health_after": pair["health_after"]}
    assert value("first_chunk_ms_mean", same) is None
