"""Engine spans stamped where the work happens (obs/trace.py RequestSpans,
SchedSpans, SpanStats): the span tree of a request on both generate
routes, the slot-wait split of queue_wait, the carrying chunk's
``chunks_ahead``, the scheduler thread's wall-time partition,
``/health.spans``, the four event messages the benchmark's regex reads, and
the ``sched/*`` annotations in a ``jax.profiler`` capture."""

import asyncio
import importlib.util
import time
from pathlib import Path

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_agent_kubectl_tpu.config import ServiceConfig
from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.obs import Trace, use_trace
from ai_agent_kubectl_tpu.obs.trace import (PHASES, SCHED_STATES,
                                            RequestSpans, SchedSpans,
                                            SpanStats, new_request_id)
from ai_agent_kubectl_tpu.server.app import create_app
from ai_agent_kubectl_tpu.server.executor import CommandExecutor
from ai_agent_kubectl_tpu.testing.faults import FaultInjector

ROOT = Path(__file__).resolve().parent.parent
ENGINE_PHASES = ("queue_wait", "prefill", "decode", "detokenize")
PREFILL_CHILDREN = ("admit_host", "stage_wait", "first_chunk")
ROUTES = ("/kubectl-command", "/kubectl-command/stream")


def _fake(**kw):
    defaults = dict(batch_size=2, chunk_len=2, chunk_pipe_depth=3,
                    kv_pool=True, force_ragged=True)
    defaults.update(kw)
    return FakeChunkedEngine(**defaults)


def _toy_jax():
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    return BatchedJaxEngine(
        get_config("toy-8m"), tokenizer=ByteTokenizer(), dtype="float32",
        max_seq_len=192, prefill_buckets=(32, 64), prefix_cache=False,
        batch_size=2, chunk_len=4, force_ragged=True)


async def _client(engine, max_new_tokens: int = 12):
    cfg = ServiceConfig(engine="fake", model_name="fake", llm_timeout=60.0,
                        rate_limit="1000/minute",
                        max_new_tokens=max_new_tokens)
    app = create_app(cfg, engine,
                     executor=CommandExecutor(timeout=5.0,
                                              kubectl_binary="kubectl"))
    client = TestClient(TestServer(app))
    await client.start_server()
    return client


async def _ask(client, route: str, query: str) -> dict:
    """One generation on ``route``; its /debug/requests/{id} detail."""
    resp = await client.post(route, json={"query": query})
    await resp.read()
    rid = resp.headers["X-Request-ID"]
    detail = await (await client.get(f"/debug/requests/{rid}")).json()
    detail["server_timing"] = resp.headers.get("Server-Timing", "")
    return detail


def _check_tree(detail: dict) -> dict:
    """The span tree every engine with a scheduler writes: the four
    engine phases top level and back to back, prefill's children inside
    it, in order, covering it. Returns spans by name."""
    spans = detail["spans"]
    ids = [s["id"] for s in spans]
    assert len(set(ids)) == len(ids) and all(isinstance(i, int) for i in ids)
    by = {s["phase"]: s for s in spans}
    for name in ENGINE_PHASES:
        assert by[name]["parent"] is None, name
    q, p, d, k = (by[n] for n in ENGINE_PHASES)
    assert q["end_ms"] == pytest.approx(p["start_ms"], abs=0.01)
    assert p["end_ms"] == pytest.approx(d["start_ms"], abs=0.01)
    assert d["end_ms"] == pytest.approx(k["start_ms"], abs=0.01)
    assert q["start_ms"] <= q["end_ms"] <= d["end_ms"] <= k["end_ms"]
    assert "slot_wait_ms" in q["meta"]
    kids = [by[n] for n in PREFILL_CHILDREN]
    for c in kids:
        assert c["parent"] == p["id"], c
        assert p["start_ms"] - 0.01 <= c["start_ms"] <= c["end_ms"] \
            <= p["end_ms"] + 0.01
    a, s, f = kids
    assert a["start_ms"] == pytest.approx(p["start_ms"], abs=0.01)
    assert a["end_ms"] == pytest.approx(s["start_ms"], abs=0.01)
    assert s["end_ms"] == pytest.approx(f["start_ms"], abs=0.01)
    assert f["end_ms"] == pytest.approx(p["end_ms"], abs=0.01)
    # a parent's self time is its duration less its children's: none here
    assert sum(c["duration_ms"] for c in kids) == pytest.approx(
        p["duration_ms"], abs=0.05)
    assert f["meta"]["chunk"] >= 1 and f["meta"]["chunks_ahead"] >= 0
    assert d["meta"]["chunks"] >= 1
    # only names the closed allowlist knows
    assert {s["phase"] for s in spans} <= set(PHASES)
    return by


# ------------------------------------------------------------ span model

def test_span_ids_parents_and_top_level_sums():
    t = Trace("abc")
    top = t.add_span("prefill", t.t0, t.t0 + 0.3, prompt_tokens=7)
    kid = t.add_span("first_chunk", t.t0 + 0.1, t.t0 + 0.3, parent=top)
    t.add_span("decode", t.t0 + 0.3, t.t0 + 0.5)
    assert (top, kid) == (1, 2)
    assert set(t.phase_durations()) == {"prefill", "decode"}
    assert t.phase_durations(children=True)["first_chunk"] == \
        pytest.approx(200.0, abs=0.5)
    assert "first_chunk" not in t.server_timing()
    d = t.to_dict()["spans"]
    assert [(s["id"], s["parent"], s["phase"]) for s in d] == [
        (1, None, "prefill"), (2, 1, "first_chunk"), (3, None, "decode")]


def test_request_spans_preempt_opens_a_second_pair():
    """Preempt/resume walks a second queue_wait/prefill pair and never
    stretches the first; every phase is written once per crossing."""
    t, stats = Trace("r"), SpanStats()
    sp = RequestSpans(t, stats, 10.0)
    sp.admitted(10.5, slot_free_since=10.2)
    sp.admitted(10.6, slot_free_since=None)      # no-op: already admitted
    sp.staged(10.6, blocks=3)
    sp.dispatched(10.7, chunk=4, chunks_ahead=2, adm_w=64)
    sp.first_token(11.5)
    sp.first_token(11.9)                          # replay: no second prefill
    sp.chunk_consumed()
    sp.requeued(12.0)
    sp.admitted(12.4, slot_free_since=12.4)
    sp.staged(12.5, chunks_ahead=1)
    sp.first_token(12.9)
    sp.finished(13.5, tokens=9)
    sp.resumed(13.6)
    names = [s["phase"] for s in t.to_dict()["spans"]]
    assert names == ["queue_wait", "prefill", "admit_host", "stage_wait",
                     "first_chunk", "decode", "queue_wait", "prefill",
                     "admit_host", "first_chunk", "decode", "detokenize"]
    snap = stats.snapshot()
    assert snap["queue_wait"]["count"] == 2
    assert snap["queue_wait"]["slot_wait_total_ms"] == pytest.approx(600.0)
    assert snap["first_chunk"]["chunks_ahead_total"] == 3
    assert snap["stage_wait"]["count"] == 1      # non-ragged has none
    total = {n: snap[n]["total_ms"] for n in snap}
    assert (total["admit_host"] + total["stage_wait"]
            + total["first_chunk"]) == pytest.approx(total["prefill"])


def test_sched_spans_partition_and_ring():
    from collections import deque

    ring, stats = deque(maxlen=8), SpanStats()
    calls = []

    class Ann:
        def __init__(self, name, **kw):
            calls.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    sched = SchedSpans(stats, ring, annotate=Ann)
    with sched.region("dispatch", "dispatch", chunk=1):   # before start():
        pass                                               # charges nothing
    assert sched.snapshot()["elapsed"] == 0.0
    sched.start()
    with sched.region("dispatch", "dispatch", chunk=2, slots=0) as e:
        time.sleep(0.01)
        e["slots"] = 3
    with sched.region("idle"):
        time.sleep(0.01)
        with sched.region("admit", "admit", chunk=3):      # nested: returns
            time.sleep(0.01)                               # to idle after
        time.sleep(0.01)
    sched.mark("prune", chunk=2)
    time.sleep(0.005)
    sched.stop()
    snap = sched.snapshot()
    assert set(snap) == set(SCHED_STATES) | {"elapsed"}
    assert sum(snap[s] for s in SCHED_STATES) == pytest.approx(
        snap["elapsed"], rel=1e-3)
    assert snap["idle"] >= 0.018 and snap["admit"] >= 0.009
    assert snap["dispatch"] >= 0.009 and snap["other"] >= 0.004
    assert [(e["event"], e["chunk"]) for e in ring] == [
        ("dispatch", 1), ("dispatch", 2), ("admit", 3), ("prune", 2)]
    disp = ring[1]
    assert disp["span"] == "sched/dispatch" and disp["slots"] == 3
    assert disp["t1"] - disp["t0"] == pytest.approx(disp["ms"] / 1e3)
    assert abs(disp["t"] - time.time()) < 5.0            # wall clock kept
    assert calls == [("sched/dispatch", {"chunk": 1}),
                     ("sched/dispatch", {"chunk": 2}),
                     ("sched/admit", {"chunk": 3})]
    assert stats.snapshot()["sched/dispatch"]["count"] == 2


# ----------------------------------------------- fake engine, both routes

@pytest.mark.parametrize("route", ROUTES)
async def test_fake_span_tree_on_both_routes(route):
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.01)      # ms-scale phases, not µs
    eng = _fake(faults=inj, grammar_decode=True)
    client = await _client(eng)
    try:
        detail = await _ask(client, route, "list all pods")
        assert detail["status"] == 200
        by = _check_tree(detail)
        assert by["decode"]["meta"]["tokens"] > 0
        if route == "/kubectl-command":
            # the header names the same top-level phases as before, and
            # the top level still sums to the wall time
            timing = detail["server_timing"]
            for name in ("validate", *ENGINE_PHASES, "safety"):
                assert f"{name};dur=" in timing, (name, timing)
            for name in PREFILL_CHILDREN:
                assert name not in timing
            top = sum(s["duration_ms"] for s in detail["spans"]
                      if s["parent"] is None)
            assert top == pytest.approx(detail["duration_ms"], rel=0.25,
                                        abs=5.0)
        # both routes feed the phase histogram, children included
        text = await (await client.get("/metrics")).text()
        for name in (*ENGINE_PHASES, *PREFILL_CHILDREN):
            assert f'request_phase_seconds_count{{phase="{name}"}} 1.0' \
                in text, name
    finally:
        inj.clear()
        await client.close()


async def test_slot_wait_is_zero_with_a_free_slot_and_the_wait_without():
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.02)
    eng = _fake(batch_size=1, faults=inj)
    await eng.start()
    try:
        traces = [Trace(new_request_id()) for _ in range(2)]

        async def run(t, prompt):
            with use_trace(t):
                return await eng.generate(prompt, max_tokens=8)

        first = asyncio.ensure_future(run(traces[0], "first in"))
        await asyncio.sleep(0.03)          # the one slot is taken by now
        await asyncio.gather(first, run(traces[1], "second waits"))
        q = [next(s for s in t.to_dict()["spans"]
                  if s["phase"] == "queue_wait") for t in traces]
        # a free slot: the wait is the scheduler being elsewhere
        assert q[0]["meta"]["slot_wait_ms"] == pytest.approx(0.0, abs=0.5)
        # batch size 1, two requests: the second's wait is for the slot
        assert q[1]["duration_ms"] > 40.0
        assert q[1]["meta"]["slot_wait_ms"] == pytest.approx(
            q[1]["duration_ms"], rel=0.1, abs=3.0)
        qw = eng.spans_health()["queue_wait"]
        assert qw["count"] == 2
        assert qw["slot_wait_total_ms"] == pytest.approx(
            q[1]["meta"]["slot_wait_ms"], abs=0.01)
    finally:
        inj.clear()
        await eng.stop()


async def test_chunks_ahead_is_the_pipes_content_at_dispatch():
    """A request staged while decode chunks are queued rides the next
    chunk behind exactly those chunks — and the ring's dispatch entry for
    that chunk number says the same."""
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.01)
    eng = _fake(batch_size=2, faults=inj,
                stream_fn=lambda _p: [9] * 40 + [2])
    await eng.start()
    try:
        traces = [Trace(new_request_id()) for _ in range(2)]

        async def run(t, prompt):
            with use_trace(t):
                return await eng.generate(prompt, max_tokens=24)

        first = asyncio.ensure_future(run(traces[0], "long runner"))
        await asyncio.sleep(0.08)          # its pipe is full by now
        await asyncio.gather(first, run(traces[1], "late joiner"))
        fc = [next(s for s in t.to_dict()["spans"]
                   if s["phase"] == "first_chunk")["meta"] for t in traces]
        assert fc[0]["chunks_ahead"] == 0 and fc[0]["chunk"] == 1
        late = fc[1]
        assert 1 <= late["chunks_ahead"] <= eng.chunk_pipe_depth - 1
        disp = next(e for e in eng._chunk_log
                    if e["event"] == "dispatch"
                    and e["chunk"] == late["chunk"])
        # "pipe" counts the chunk itself
        assert disp["pipe"] == late["chunks_ahead"] + 1
        assert disp["admissions"] == 1
        assert eng.spans_health()["first_chunk"]["chunks_ahead_total"] == \
            late["chunks_ahead"]
    finally:
        inj.clear()
        await eng.stop()


async def test_sched_thread_parts_sum_to_elapsed_and_health_counts():
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.005)
    eng = _fake(batch_size=4, faults=inj, grammar_decode=True)
    client = await _client(eng)
    try:
        n = 6
        await asyncio.gather(*[
            _ask(client, ROUTES[i % 2], f"list pods in namespace n{i}")
            for i in range(n)])
        await asyncio.sleep(0.05)          # some idle time in the books
        spans = (await (await client.get("/health")).json())["spans"]
        # every finished request closed every phase exactly once
        for name in (*ENGINE_PHASES, *PREFILL_CHILDREN):
            assert spans[name]["count"] == n, (name, spans[name])
            assert spans[name]["max_ms"] <= spans[name]["total_ms"] + 1e-6
        assert spans["queue_wait"]["slot_wait_total_ms"] <= \
            spans["queue_wait"]["total_ms"]
        sched = spans["sched_thread_s"]
        parts = sum(sched[s] for s in SCHED_STATES)
        assert parts == pytest.approx(sched["elapsed"], rel=0.02)
        assert sched["chunks_consumed"] == eng._chunks_consumed > 0
        assert sched["dispatch"] > 0 and sched["idle"] > 0
        for name in ("admit", "dispatch", "fetch", "consume"):
            assert spans[f"sched/{name}"]["count"] > 0
        assert spans["sched/consume"]["count"] == sched["chunks_consumed"]
        # the ring: intervals with a chunk number on both clocks
        ring = (await (await client.get("/debug/chunks?limit=500")).json()
                )["events"]
        disp = [e["chunk"] for e in ring if e["event"] == "dispatch"]
        assert disp == sorted(set(disp)) and disp[0] == 1
        for e in ring:
            if e["event"] in ("admit", "dispatch", "fetch", "consume"):
                assert e["span"] == f"sched/{e['event']}"
                assert e["t1"] >= e["t0"] and e["ms"] >= 0 and e["t"] > 1e9
        assert {e["chunk"] for e in ring if e["event"] == "consume"} <= \
            set(disp)
    finally:
        inj.clear()
        await client.close()


# ------------------------------------------------- toy JAX engine (CPU)

def _bench_run():
    """benchmark/run.py as a module: the regexes under test live there."""
    spec = importlib.util.spec_from_file_location(
        "bench_run", ROOT / "benchmark" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


async def test_jax_span_tree_both_routes_and_the_four_event_messages():
    """The batcher's scheduler THREAD stamps the same tree on both
    routes, and the four messages benchmark/run.py::EVENT_RE matches are
    still the ones a finished request carries — the ledger's
    queue_ms/prefill_ms/decode_ms read them until a benchmark issue
    retires the regex; the spans agree with what it recovers."""
    run = _bench_run()
    eng = _toy_jax()
    client = await _client(eng)
    try:
        await _ask(client, ROUTES[1], "warm every shape first")
        health = await (await client.get("/health")).json()
        before = health["spans"]
        # ISSUE 30: what the ragged kernel resolved at start rides /health
        # beside the regime: pages a grid step, and the decode program's
        # grid steps a call (slots x one query tile x page blocks).
        pool = health["kv_pool"]
        assert pool["attention_regime"] == "ragged"
        pages = pool["attention_pages_per_step"]
        assert 1 < pages <= eng._pool_max_pages
        assert pool["attention_decode_grid_steps"] == \
            eng.batch_size * -(-eng._pool_max_pages // pages)
        # ISSUE 32: and the buffers its live blocks stream through, from
        # the same shapes (the toy's whole ring is a few KB: the cap)
        from ai_agent_kubectl_tpu.ops.ragged_attention import stream_depth
        cfg = eng.model_cfg
        assert pool["attention_stream_depth"] == stream_depth(
            eng._pool_max_pages, eng.kv_pool_page, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim, 1, 4) == 4
        for route in ROUTES:
            detail = await _ask(client, route, f"list pods via {route}")
            by = _check_tree(detail)
            assert by["prefill"]["meta"]["prompt_tokens"] > 0
            assert by["prefill"]["meta"]["staged_w"] in (32, 64)
            assert by["first_chunk"]["meta"]["adm_w"] == \
                by["prefill"]["meta"]["staged_w"]
            assert by["admit_host"]["meta"]["blocks"] >= 1
            assert by["decode"]["meta"] == {
                "tokens": 12, "finish": "length",
                "chunks": by["decode"]["meta"]["chunks"]}
            msgs = [e["message"] for e in detail["events"]]
            hits = {key: [m for m in msgs if rx.match(m)]
                    for key, rx in run.EVENT_RE.items()}
            assert all(hits.values()), hits
            assert hits["submitted"][0].startswith(
                "engine: submitted to batch scheduler (queue depth ")
            assert hits["admitted"][0].startswith("engine: admitted to slot ")
            assert hits["first"][0].startswith("engine: chunk consumed (+")
            assert hits["finished"] == ["engine: finished (length, 12 tokens)"]
            rec = run.engine_record(detail)
            assert rec["completion_tokens"] == 12
            # The regex's boundaries are the EVENTS: "admitted" is stamped
            # once the admission's host work is done, so its queue_ms
            # holds admit_host and its prefill_ms does not.
            dur = {n: s["duration_ms"] for n, s in by.items()}
            assert rec["queue_ms"] == pytest.approx(
                dur["queue_wait"] + dur["admit_host"], abs=25.0)
            assert rec["prefill_ms"] == pytest.approx(
                dur["stage_wait"] + dur["first_chunk"], abs=25.0)
            assert rec["decode_ms"] == pytest.approx(dur["decode"], abs=25.0)
            assert (rec["queue_ms"] + rec["prefill_ms"] + rec["decode_ms"]
                    ) == pytest.approx(dur["queue_wait"] + dur["prefill"]
                                       + dur["decode"], abs=25.0)
        spans = (await (await client.get("/health")).json())["spans"]
        for name in (*ENGINE_PHASES, *PREFILL_CHILDREN):
            assert spans[name]["count"] - before[name]["count"] == 2, name
        assert (spans["admit_host"]["total_ms"]
                + spans["stage_wait"]["total_ms"]
                + spans["first_chunk"]["total_ms"]) == pytest.approx(
            spans["prefill"]["total_ms"], rel=0.02)
        sched = spans["sched_thread_s"]
        assert sum(sched[s] for s in SCHED_STATES) == pytest.approx(
            sched["elapsed"], rel=0.02)
        assert sched["fetch_wait"] > 0 and sched["chunks_consumed"] > 0
    finally:
        await client.close()


async def test_profile_capture_holds_sched_annotations_with_chunk_numbers():
    """A /debug/profile capture on the CPU backend holds the scheduler's
    spans as TraceAnnotations with a ``chunk`` stat, on the trace's own
    clock; the summary carries the two clock pairs."""
    import glob

    import jax

    client = await _client(_toy_jax())
    try:
        await _ask(client, ROUTES[1], "warm every shape first")
        prof = asyncio.ensure_future(
            client.post("/debug/profile?seconds=1.0"))
        await asyncio.sleep(0.2)
        await _ask(client, ROUTES[1], "list pods while the capture runs")
        body = await (await prof).json()
        (m0, w0), (m1, w1) = body["clock_start"], body["clock_stop"]
        assert m1 - m0 == pytest.approx((w1 - w0) / 1e9, abs=0.05)
        assert m1 - m0 >= 1.0
        path = glob.glob(body["trace_dir"] + "/plugins/profile/*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(path[0])
        seen = {}
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("sched/"):
                        seen.setdefault(ev.name, []).append(
                            (dict(ev.stats).get("chunk"), ev.duration_ns))
        assert {"sched/dispatch", "sched/fetch", "sched/consume",
                "sched/admit"} <= set(seen), sorted(seen)
        for name in ("sched/dispatch", "sched/fetch"):
            chunks = [c for c, _ in seen[name] if c is not None]
            assert chunks and all(int(c) >= 1 for c in chunks), seen[name]
        # the same chunk is dispatched, then fetched
        assert {c for c, _ in seen["sched/fetch"] if c is not None} & \
            {c for c, _ in seen["sched/dispatch"]}
    finally:
        await client.close()
