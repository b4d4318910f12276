"""The four per-layer metrics that read the scheduler thread's child
regions and its pipe-empty seconds (ISSUE 35): data files for the
benchmark's ``health_spans`` reader, declared in BENCHMARK.json like their
files, read here through ``run.load_reader`` from a recorded pair of
/health probes (tests/data/health_spans_regions_pair.json: toy-8m on the
CPU, so the numbers are only arithmetic). ``benchmark/`` is not a package:
its modules are found by path, as tests/test_benchmark_harness.py finds
them."""

import copy
import json
import sys
from pathlib import Path

import pytest

from ai_agent_kubectl_tpu.obs.trace import SCHED_STATES

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
sys.path.insert(0, str(BENCH))

import run as R  # noqa: E402

KV = "KV pool and radix engine/kv_pool.py"
CHUNK = "chunk program engine/batcher.py"
NEW = {"radix_evict_ms_per_chunk": ("ms", KV),
       "eager_prefill_ms_per_chunk": ("ms", CHUNK),
       "admit_launch_ms_per_chunk": ("ms", CHUNK),
       "pipe_starved_share": ("%", CHUNK)}
#: the regions in which the thread is inside a device call at admission
LAUNCHES = (("sched/eager_prefill", "call_total_ms"), ("sched/arm", "total_ms"),
            ("sched/cow", "total_ms"), ("sched/state_restore", "total_ms"),
            ("sched/state_snapshot", "total_ms"))


@pytest.fixture(scope="module")
def pair():
    return json.loads(
        (ROOT / "tests" / "data" / "health_spans_regions_pair.json").read_text())


def spec_of(name):
    return R.load_json(BENCH / "metrics" / f"{name}.json")


def value(name, ctx):
    spec = spec_of(name)
    return R.load_reader(spec["reader"]).read(ctx, spec.get("params", {}))


def grew(pair, span, key):
    return (pair["health_after"]["spans"].get(span, {}).get(key, 0)
            - pair["health_before"]["spans"].get(span, {}).get(key, 0))


@pytest.mark.parametrize("name", sorted(NEW))
def test_declared_in_the_benchmark_like_its_file(name):
    bench = R.load_json(ROOT / "BENCHMARK.json")
    by = {m["name"]: m for m in bench["per_layer"]}
    spec = spec_of(name)
    unit, layer = NEW[name]
    assert spec["name"] == name and spec["reader"] == "health_spans"
    assert (spec["unit"], spec["layer"]) == (unit, layer)
    assert (spec["better"], spec["source"], spec["moves"]) == (
        "lower", "program_span", "latency_p50_ms")
    assert set(by[name]) == {"name", "unit", "better", "source", "layer",
                             "moves"}                  # no workloads: every cell
    for key in ("unit", "better", "source", "layer", "moves"):
        assert by[name][key] == spec[key], (name, key)
    assert "/health.spans" in spec["what"]
    # appended in this order, and kept so by what later PRs append after them
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("radix_evict_ms_per_chunk")
    assert names[first:first + 4] == ["radix_evict_ms_per_chunk",
                                      "eager_prefill_ms_per_chunk",
                                      "admit_launch_ms_per_chunk",
                                      "pipe_starved_share"]
    # every cell reports the end-to-end metric it moves
    for cell in bench["workloads"]:
        reported = {m["name"] for m in
                    R.cell_metrics(bench, "per_layer", cell["name"])}
        assert set(NEW) <= reported, cell["name"]


def test_they_read_the_recorded_pair(pair):
    v = {name: value(name, pair) for name in NEW}
    chunks = grew(pair, "sched_thread_s", "chunks_consumed")
    assert chunks == 20
    assert v["radix_evict_ms_per_chunk"] == pytest.approx(
        grew(pair, "sched/radix_evict", "total_ms") / chunks)
    assert v["eager_prefill_ms_per_chunk"] == pytest.approx(
        grew(pair, "sched/eager_prefill", "total_ms") / chunks)
    assert v["admit_launch_ms_per_chunk"] == pytest.approx(
        sum(grew(pair, span, key) for span, key in LAUNCHES) / chunks)
    assert v["pipe_starved_share"] == pytest.approx(
        100 * grew(pair, "sched_starved_s", "total")
        / grew(pair, "sched_thread_s", "elapsed"))
    assert all(x > 0 for x in v.values()), v
    assert v["pipe_starved_share"] <= 100
    # the eager pieces' calls are a part of the pieces; the three ms metrics
    # are parts of sched_host_ms_per_chunk (they subtract from it)
    assert grew(pair, "sched/eager_prefill", "call_total_ms") <= \
        grew(pair, "sched/eager_prefill", "total_ms")
    host = value("sched_host_ms_per_chunk", pair)
    assert v["radix_evict_ms_per_chunk"] + v["eager_prefill_ms_per_chunk"] \
        <= host
    assert v["admit_launch_ms_per_chunk"] <= host
    # the starved seconds are a partition of their total
    after = pair["health_after"]["spans"]["sched_starved_s"]
    assert sum(after[s] for s in SCHED_STATES) == pytest.approx(
        after["total"], abs=1e-5)


def test_a_region_that_never_ran_counts_zero(pair):
    """toy-8m keeps no recurrent state: no restore, no snapshot, and the
    launches' sum still reads; a tree under no pressure never walked."""
    spans = pair["health_after"]["spans"]
    assert "sched/state_restore" not in spans
    assert "sched/state_snapshot" not in spans
    assert value("admit_launch_ms_per_chunk", pair) > 0
    calm = copy.deepcopy(pair)
    for probe in ("health_before", "health_after"):
        calm[probe]["spans"].pop("sched/radix_evict", None)
        calm[probe]["spans"].pop("sched/eager_prefill", None)
    assert value("radix_evict_ms_per_chunk", calm) == 0.0
    assert value("eager_prefill_ms_per_chunk", calm) == 0.0
    assert 0 < value("admit_launch_ms_per_chunk", calm) < \
        value("admit_launch_ms_per_chunk", pair)


@pytest.mark.parametrize("ctx", [
    {},                                                      # no probes
    {"health_before": {"kv_pool": {}}, "health_after": {"kv_pool": {}}},
    {"health_before": {}, "health_after": {"spans": None}},  # no scheduler
])
def test_a_program_without_the_section_leaves_them_out(ctx):
    assert all(value(name, ctx) is None for name in NEW)


def test_the_parents_health_reads_zero_not_an_error(pair):
    """The parent commit has /health.spans without the child regions and
    without ``sched_starved_s``: the reader finds nothing to add up, reads
    0 and does not raise (the driver does not compare a metric that is new
    in this PR); with no chunk consumed there is nothing to divide by."""
    old = copy.deepcopy(pair)
    keep = ("sched/admit", "sched/dispatch", "sched/fetch", "sched/consume",
            "sched_thread_s")
    for probe in ("health_before", "health_after"):
        spans = old[probe]["spans"]
        for name in list(spans):
            if name.startswith("sched") and name not in keep:
                del spans[name]
    assert {name: value(name, old) for name in NEW} == dict.fromkeys(NEW, 0.0)
    old["health_after"]["spans"]["sched_thread_s"]["chunks_consumed"] = \
        old["health_before"]["spans"]["sched_thread_s"]["chunks_consumed"]
    for name in NEW:
        if name != "pipe_starved_share":
            assert value(name, old) is None
