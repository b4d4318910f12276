"""The six per-layer metrics of ISSUE 50, read as the benchmark reads them
(``run.load_reader`` on the data files under benchmark/metrics/) from live
``/health.spans`` probes of the fake engine, and from the recorded pair the
benchmark's own case reads (benchmark/tests/data/health_drained_pair.json).
``benchmark/`` is not a package: its modules are found by path, as
tests/test_sched_region_metrics.py finds them."""

import asyncio
import copy
import json
import sys
from pathlib import Path

import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.obs.trace import SCHED_STATES
from ai_agent_kubectl_tpu.testing.faults import FaultInjector

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "benchmark"
sys.path.insert(0, str(BENCH))

import run as R  # noqa: E402

DEVICE, LOAD = "device", "load generator (benchmark)"
QOS, CHUNK = ("QoS queue and admission engine/qos.py",
              "chunk program engine/batcher.py")
NEW = {"device_drained_share": ("%", DEVICE, "program_span"),
       "device_drained_unseen_share": ("%", DEVICE, "program_span"),
       "device_unloaded_share": ("%", LOAD, "program_span"),
       "drained_in_admit_share": ("%", QOS, "program_span"),
       "admit_unnamed_share": ("%", QOS, "program_span"),
       "chunks_unready_mean": ("chunks", CHUNK, "program_counter")}
#: every sched/* region that is a child of an admission and of nothing else
ADMIT_KIDS = ("arm", "cow", "eager_prefill", "eager_tail", "placeholder",
              "state_restore", "state_snapshot", "state_zero", "radix_match")


def spec_of(name):
    return R.load_json(BENCH / "metrics" / f"{name}.json")


def value(name, ctx):
    spec = spec_of(name)
    return R.load_reader(spec["reader"]).read(ctx, spec.get("params", {}))


@pytest.fixture(scope="module")
def pair():
    return json.loads((BENCH / "tests" / "data" /
                       "health_drained_pair.json").read_text())


@pytest.mark.parametrize("name", sorted(NEW))
def test_declared_in_the_benchmark_like_its_file(name):
    bench = R.load_json(ROOT / "BENCHMARK.json")
    by = {m["name"]: m for m in bench["per_layer"]}
    spec = spec_of(name)
    unit, layer, source = NEW[name]
    assert spec["name"] == name and spec["reader"] == "health_spans"
    assert (spec["unit"], spec["layer"], spec["source"]) == (unit, layer,
                                                             source)
    assert (spec["better"], spec["moves"]) == ("lower", "latency_p50_ms")
    assert set(by[name]) == {"name", "unit", "better", "source", "layer",
                             "moves"}                  # no workloads: every cell
    for key in ("unit", "better", "source", "layer", "moves"):
        assert by[name][key] == spec[key], (name, key)
    assert "/health.spans" in spec["what"]
    # every path the file names is one EngineSpans.health() can serve
    for key in ("plus", "minus", "over"):
        for path in spec["params"].get(key, []):
            head = path[0]
            assert head in ("sched_drained_s", "sched_thread_s",
                            "first_chunk") or head.startswith("sched/"), path
    # appended after what the accepted benchmark had, in the issue's order
    # (a later PR's own metrics come behind them)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("device_drained_share")
    assert names[first:first + len(NEW)] == [
        "device_drained_share", "device_drained_unseen_share",
        "device_unloaded_share", "drained_in_admit_share",
        "admit_unnamed_share", "chunks_unready_mean"]
    for cell in bench["workloads"]:
        assert name in {m["name"] for m in
                        R.cell_metrics(bench, "per_layer", cell["name"])}


def test_the_unnamed_rest_subtracts_every_child_of_an_admission_alone():
    params = spec_of("admit_unnamed_share")["params"]
    assert params["plus"] == params["over"] == [["sched/admit", "total_ms"]]
    assert params["minus"] == [[f"sched/{k}", "total_ms"] for k in ADMIT_KIDS]
    assert "radix_evict" in spec_of("admit_unnamed_share")["what"]


async def test_they_read_live_probes_of_the_fake_engine():
    """Two /health.spans probes around some traffic, as benchmark/run.py
    takes them: the four shares follow the section's own numbers, and the
    seconds the device stood with nothing to run and nothing to be given are
    the load's, not the program's."""
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.002)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2, chunk_pipe_depth=2,
                            kv_pool=True, force_ragged=True, faults=inj,
                            stream_fn=lambda _p: [9] * 30 + [2])
    await eng.start()
    try:
        await eng.generate("warm the engine first", max_tokens=8)
        before = {"spans": eng.spans_health()}
        await asyncio.gather(*[
            eng.generate(f"list pods in namespace n{i}", max_tokens=16)
            for i in range(5)])
        await asyncio.sleep(0.08)          # the load leaves the device alone
        ctx = {"health_before": before,
               "health_after": {"spans": eng.spans_health()}}
    finally:
        inj.clear()
        await eng.stop()
    v = {name: value(name, ctx) for name in NEW}
    assert all(x is not None for x in v.values()), v
    after, was = ctx["health_after"]["spans"], before["spans"]
    elapsed = after["sched_thread_s"]["elapsed"] - was["sched_thread_s"]["elapsed"]
    total = after["sched_drained_s"]["total"] - was["sched_drained_s"]["total"]
    assert v["device_drained_share"] == pytest.approx(100 * total / elapsed)
    assert v["device_unloaded_share"] >= 100 * 0.07 / elapsed
    assert v["device_drained_share"] + v["device_unloaded_share"] \
        + v["device_drained_unseen_share"] <= 100 + 1e-6
    assert 0 <= v["drained_in_admit_share"] <= 100
    assert 0 < v["admit_unnamed_share"] <= 100
    assert 0 <= v["chunks_unready_mean"] <= value("chunks_ahead_mean", ctx)
    for part in ("with_work", "no_work"):
        section = after["sched_drained_s"][part]
        assert sum(section[s] for s in SCHED_STATES) == pytest.approx(
            section["total"], abs=1e-5)


def test_the_parents_health_reads_zero_or_is_left_out(pair):
    """The parent commit serves /health.spans without the section: a share
    of the thread's seconds reads 0, a share of no drained second is left
    out, nothing raises (the driver does not compare a metric new in this
    PR); with no probe at all every one is left out."""
    assert all(value(name, pair) is not None for name in NEW)
    old = copy.deepcopy(pair)
    for probe in ("health_before", "health_after"):
        old[probe]["spans"].pop("sched_drained_s")
        old[probe]["spans"]["first_chunk"].pop("chunks_unready_total")
    got = {name: value(name, old) for name in NEW}
    assert got == {"device_drained_share": 0.0,
                   "device_drained_unseen_share": 0.0,
                   "device_unloaded_share": 0.0,
                   "drained_in_admit_share": None,
                   "admit_unnamed_share": value("admit_unnamed_share", pair),
                   "chunks_unready_mean": 0.0}
    for ctx in ({}, {"health_before": {}, "health_after": {"spans": None}}):
        assert all(value(name, ctx) is None for name in NEW)
