"""The six per-layer metrics that read ``/health.spans.sched_drained_s``, the
two regions an admission's unnamed launches got, and ``first_chunk``'s count
of unready chunks (ISSUE 50): data files for ``readers/health_spans.py``, read
from a recorded /health pair of the fake engine (tests/data/
health_drained_pair.json: a CPU's clock, so the numbers are only arithmetic)."""
import copy
import json
from pathlib import Path

import pytest

import run

HERE = Path(__file__).resolve().parent
NEW = ("device_drained_share", "device_drained_unseen_share",
       "device_unloaded_share", "drained_in_admit_share",
       "admit_unnamed_share", "chunks_unready_mean")
#: the 58 entries the accepted benchmark had, then these, in this order
ACCEPTED = 58


@pytest.fixture(scope="module")
def pair():
    return json.loads((HERE / "data" / "health_drained_pair.json").read_text())


def value(name, ctx):
    spec = run.load_json(run.HERE / "metrics" / f"{name}.json")
    assert spec["reader"] == "health_spans" and spec["name"] == name
    return run.load_reader(spec["reader"]).read(ctx, spec.get("params", {}))


def grew(pair, *path):
    def at(tree):
        for key in path:
            tree = (tree or {}).get(key)
        return tree or 0.0
    return at(pair["health_after"]["spans"]) - at(pair["health_before"]["spans"])


def test_every_new_metric_parses_is_declared_and_reads_the_recorded_pair(pair):
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    names = [m["name"] for m in bench["per_layer"]]
    assert names[ACCEPTED:ACCEPTED + len(NEW)] == list(NEW)
    by = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        spec = run.load_json(run.HERE / "metrics" / f"{name}.json")
        assert set(by[name]) == {"name", "unit", "better", "source", "layer",
                                 "moves"}              # no workloads: every cell
        for key in ("unit", "better", "source", "layer", "moves"):
            assert by[name][key] == spec[key], (name, key)
        assert spec["moves"] == "latency_p50_ms" and spec["better"] == "lower"
        assert "/health.spans" in spec["what"]
    v = {name: value(name, pair) for name in NEW}
    assert all(isinstance(x, float) for x in v.values()), v
    elapsed = grew(pair, "sched_thread_s", "elapsed")
    total = grew(pair, "sched_drained_s", "total")
    assert total == grew(pair, "sched_drained_s", "with_work", "total") > 0
    assert v["device_drained_share"] == pytest.approx(100 * total / elapsed)
    assert v["device_drained_unseen_share"] == pytest.approx(
        100 * grew(pair, "sched_drained_s", "unseen") / elapsed)
    assert v["device_unloaded_share"] == pytest.approx(
        100 * grew(pair, "sched_drained_s", "no_work", "total") / elapsed)
    assert v["drained_in_admit_share"] == pytest.approx(
        100 * grew(pair, "sched_drained_s", "with_work", "admit") / total)
    assert (v["device_drained_share"] + v["device_unloaded_share"]) <= 100
    assert 0 <= v["drained_in_admit_share"] <= 100
    # the fake names its radix walks alone: the rest of sched/admit
    admit = grew(pair, "sched/admit", "total_ms")
    assert v["admit_unnamed_share"] == pytest.approx(
        100 * (admit - grew(pair, "sched/radix_match", "total_ms")) / admit)
    assert 0 < v["admit_unnamed_share"] < 100
    assert v["chunks_unready_mean"] == pytest.approx(
        grew(pair, "first_chunk", "chunks_unready_total")
        / grew(pair, "first_chunk", "count"))
    assert v["chunks_unready_mean"] <= value("chunks_ahead_mean", pair)


def test_a_program_without_the_section_reads_zero_or_is_left_out(pair):
    """The parent commit: /health.spans without ``sched_drained_s``, without
    the two new regions and without ``chunks_unready_total``. Nothing raises;
    a share of the thread's seconds reads 0, a share of no drained second is
    left out, and the unnamed rest still reads what the parent's names leave."""
    old = copy.deepcopy(pair)
    for probe in ("health_before", "health_after"):
        spans = old[probe]["spans"]
        spans.pop("sched_drained_s")
        spans["first_chunk"].pop("chunks_unready_total", None)
    v = {name: value(name, old) for name in NEW}
    assert v["device_drained_share"] == v["device_drained_unseen_share"] == 0.0
    assert v["device_unloaded_share"] == 0.0
    assert v["drained_in_admit_share"] is None
    assert v["chunks_unready_mean"] == 0.0
    assert v["admit_unnamed_share"] == value("admit_unnamed_share", pair)
    for ctx in ({}, {"health_before": {}, "health_after": {"spans": None}}):
        assert all(value(name, ctx) is None for name in NEW)
