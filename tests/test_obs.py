"""Observability subsystem tests: trace context + spans, flight recorder,
request-ID propagation, Server-Timing, phase histograms, JSON logging,
windowed throughput, and the token-gated debug endpoints."""

import asyncio
import json
import logging
import re

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_agent_kubectl_tpu.config import ServiceConfig
from ai_agent_kubectl_tpu.engine.fake import FakeEngine
from ai_agent_kubectl_tpu.engine.protocol import EngineUnavailable
from ai_agent_kubectl_tpu.logging_setup import JsonFormatter, RequestIdFilter
from ai_agent_kubectl_tpu.obs import FlightRecorder, Trace, use_trace
from ai_agent_kubectl_tpu.obs.trace import (current_trace, new_request_id,
                                            sanitize_request_id)
from ai_agent_kubectl_tpu.server.app import create_app
from ai_agent_kubectl_tpu.server.executor import CommandExecutor
from ai_agent_kubectl_tpu.server.metrics import WindowedRate


def make_cfg(**over):
    defaults = dict(engine="fake", model_name="fake", llm_timeout=2.0)
    defaults.update(over)
    return ServiceConfig(**defaults)


async def make_client(cfg, engine=None, kubectl_binary="kubectl"):
    engine = engine or FakeEngine()
    executor = CommandExecutor(timeout=cfg.execution_timeout,
                               kubectl_binary=kubectl_binary)
    app = create_app(cfg, engine, executor=executor)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, engine


# --------------------------------------------------------------- trace unit


def test_trace_spans_and_durations():
    t = Trace("abc123", "POST", "/kubectl-command")
    with t.span("validate"):
        pass
    t.add_span("decode", t.t0, t.t0 + 0.25)
    t.add_span("decode", t.t0 + 0.25, t.t0 + 0.35)   # merged by name
    durs = t.phase_durations()
    assert set(durs) == {"validate", "decode"}
    assert durs["decode"] == pytest.approx(350.0, abs=1.0)
    t.finish(status=200)
    d = t.to_dict()
    assert d["request_id"] == "abc123"
    assert d["status"] == 200
    # spans sorted by start, offsets relative to trace start
    assert sorted(s["phase"] for s in d["spans"]) == \
        ["decode", "decode", "validate"]
    starts = [s["start_ms"] for s in d["spans"]]
    assert starts == sorted(starts)
    assert all(s["start_ms"] >= 0 for s in d["spans"])


def test_trace_server_timing_format():
    t = Trace(new_request_id())
    t.add_span("queue_wait", t.t0, t.t0 + 0.0012)
    t.add_span("decode", t.t0 + 0.0012, t.t0 + 0.1)
    header = t.server_timing()
    assert re.match(r"^queue_wait;dur=\d+\.\d\d, decode;dur=\d+\.\d\d$",
                    header)


def test_trace_events_thread_safe_shape():
    t = Trace(new_request_id())
    t.event("engine: admitted to slot 3", slot=3)
    d = t.to_dict()
    assert d["events"][0]["message"].startswith("engine: admitted")
    assert d["events"][0]["meta"] == {"slot": 3}


def test_sanitize_request_id():
    assert sanitize_request_id("abc-DEF_1.2") == "abc-DEF_1.2"
    assert sanitize_request_id(None) is None
    assert sanitize_request_id("") is None
    assert sanitize_request_id("x" * 65) is None          # too long
    assert sanitize_request_id("evil\nheader") is None    # injection
    assert sanitize_request_id("späce") is None


def test_current_trace_contextvar():
    assert current_trace() is None
    t = Trace(new_request_id())
    with use_trace(t):
        assert current_trace() is t
    assert current_trace() is None


async def test_trace_propagates_into_tasks():
    """asyncio copies the context into created tasks — the single-flight
    supplier sees the submitting request's trace."""
    t = Trace(new_request_id())

    async def probe():
        return current_trace()

    with use_trace(t):
        seen = await asyncio.get_running_loop().create_task(probe())
    assert seen is t


# ---------------------------------------------------------- flight recorder


def test_flight_recorder_ring_eviction_and_lookup():
    rec = FlightRecorder(size=3)
    ids = []
    for i in range(5):
        t = Trace(f"rid-{i}")
        t.finish(status=200)
        rec.record(t)
        ids.append(t.request_id)
    assert len(rec) == 3
    assert rec.get("rid-0") is None and rec.get("rid-1") is None
    assert rec.get("rid-4")["request_id"] == "rid-4"
    listing = rec.list()
    assert [e["request_id"] for e in listing] == ["rid-4", "rid-3", "rid-2"]
    assert all("spans" not in e and "n_spans" in e for e in listing)
    assert rec.recorded == 5


def test_flight_recorder_duplicate_id_overwrites():
    rec = FlightRecorder(size=4)
    a = Trace("same-id")
    a.finish(status=500)
    rec.record(a)
    b = Trace("same-id")
    b.finish(status=200)
    rec.record(b)
    assert len(rec) == 1
    assert rec.get("same-id")["status"] == 200


# ------------------------------------------------------------ windowed rate


def test_windowed_rate():
    now = [1000.0]
    r = WindowedRate(window_secs=60.0, timer=lambda: now[0])
    assert r.rate() == 0.0
    r.add(120)
    assert r.rate() == pytest.approx(2.0)          # 120 tok / 60 s window
    now[0] += 30
    r.add(60)
    assert r.rate() == pytest.approx(3.0)          # 180 in window
    now[0] += 31                                   # first burst ages out
    assert r.rate() == pytest.approx(1.0)
    now[0] += 120                                  # idle decays to zero
    assert r.rate() == 0.0


# ------------------------------------------------------------- HTTP surface


async def test_request_id_minted_and_echoed():
    client, _ = await make_client(make_cfg())
    try:
        resp = await client.post("/kubectl-command",
                                 json={"query": "list all pods"})
        rid = resp.headers.get("X-Request-ID")
        assert rid and re.match(r"^[0-9a-f]{16}$", rid)

        # A safe client-supplied ID is echoed verbatim...
        resp = await client.post("/kubectl-command",
                                 json={"query": "list all nodes"},
                                 headers={"X-Request-ID": "client-id-42"})
        assert resp.headers["X-Request-ID"] == "client-id-42"
        # ...an unsafe one is replaced.
        resp = await client.post("/kubectl-command",
                                 json={"query": "show deployments"},
                                 headers={"X-Request-ID": "x" * 200})
        assert resp.headers["X-Request-ID"] != "x" * 200
    finally:
        await client.close()


async def test_request_id_on_error_and_shed_paths():
    engine = FakeEngine()
    client, _ = await make_client(make_cfg(), engine=engine)
    try:
        # 400 validation error
        resp = await client.post("/kubectl-command", json={"query": "ab"})
        assert resp.status == 400 and resp.headers.get("X-Request-ID")
        # 404 unmatched
        resp = await client.get("/nope")
        assert resp.status == 404 and resp.headers.get("X-Request-ID")
        # 503 engine down
        engine.fail_with = EngineUnavailable("down")
        resp = await client.post("/kubectl-command",
                                 json={"query": "list pods"})
        assert resp.status == 503 and resp.headers.get("X-Request-ID")
    finally:
        await client.close()

    # 429 rate-limited, on a fresh quota
    client, _ = await make_client(make_cfg(rate_limit="1/minute"))
    try:
        assert (await client.post(
            "/kubectl-command", json={"query": "list pods"})).status == 200
        resp = await client.post("/kubectl-command",
                                 json={"query": "list nodes"})
        assert resp.status == 429 and resp.headers.get("X-Request-ID")
        # ...and the shed flag is in its flight-recorder record
        entry = client.app["service"].recorder.get(
            resp.headers["X-Request-ID"])
        assert entry is not None and entry["shed"] is True
    finally:
        await client.close()


async def test_request_id_on_inflight_shed():
    """The MAX_INFLIGHT_REQUESTS fast 503 carries an X-Request-ID and
    lands in the flight recorder flagged shed."""
    engine = FakeEngine(delay=0.5)
    client, _ = await make_client(
        make_cfg(max_inflight_requests=1), engine=engine)
    try:
        slow = asyncio.ensure_future(
            client.post("/kubectl-command", json={"query": "list pods"}))
        await asyncio.sleep(0.1)     # let it occupy the inflight slot
        resp = await client.post("/kubectl-command",
                                 json={"query": "list nodes"})
        assert resp.status == 503
        rid = resp.headers.get("X-Request-ID")
        assert rid
        assert resp.headers.get("Retry-After")
        entry = client.app["service"].recorder.get(rid)
        assert entry is not None and entry["shed"] is True
        assert entry["status"] == 503
        await slow
    finally:
        await client.close()


async def test_server_timing_and_timeline_phases_sum_to_wall():
    """Acceptance: an end-to-end request yields ≥6 named phases in the
    /debug/requests/{id} timeline whose TOP-LEVEL durations sum to ~wall
    time (children are parts of their parent, not more time), the same
    top-level phases in the Server-Timing header, and every span in the
    phase histogram."""
    engine = FakeEngine(delay=0.05)
    client, _ = await make_client(make_cfg(), engine=engine)
    try:
        resp = await client.post("/kubectl-command",
                                 json={"query": "list all pods"})
        assert resp.status == 200
        rid = resp.headers["X-Request-ID"]
        st = resp.headers["Server-Timing"]
        phases = dict(
            (part.split(";")[0], float(part.split("dur=")[1]))
            for part in st.split(", ")
        )
        for name in ("validate", "queue_wait", "prefill", "decode",
                     "detokenize", "safety"):
            assert name in phases, (name, st)
        assert len(phases) >= 6

        # body timings mirror the header (respond is recorded after the
        # body is built, so compare the shared keys)
        body = await resp.json()
        assert body["timings"] is not None
        for k in body["timings"]:
            assert k in phases

        # flight-recorder timeline: same phases at the top level (the
        # header names nothing else), their sum ≈ wall; the engine's
        # prefill children hang under prefill
        detail = await (await client.get(f"/debug/requests/{rid}")).json()
        top = [s for s in detail["spans"] if s["parent"] is None]
        assert {s["phase"] for s in top} == set(phases)
        assert {"validate", "queue_wait", "prefill", "decode",
                "detokenize", "safety"} <= set(phases)
        prefill = next(s for s in top if s["phase"] == "prefill")
        kids = [s for s in detail["spans"] if s["parent"] is not None]
        assert kids and all(s["parent"] == prefill["id"] for s in kids)
        total = sum(s["duration_ms"] for s in top)
        wall = detail["duration_ms"]
        # spans cover the engine block (~50ms of fake delay) plus the
        # handler phases; everything but middleware slack is attributed
        assert total == pytest.approx(wall, rel=0.25, abs=15.0)
        assert total >= 45.0   # the fake engine's 50ms delay is in there

        # same phases appear as request_phase_seconds buckets
        text = await (await client.get("/metrics")).text()
        for name in ("queue_wait", "prefill", "decode", "detokenize",
                     "safety", "validate"):
            assert f'request_phase_seconds_count{{phase="{name}"}}' in text
    finally:
        await client.close()


async def test_execute_phase_recorded(fake_kubectl):
    client, _ = await make_client(make_cfg(), kubectl_binary=fake_kubectl)
    try:
        resp = await client.post("/execute", json={"execute": "kubectl get pods"})
        assert resp.status == 200
        body = await resp.json()
        assert "execute" in body["timings"]
        rid = resp.headers["X-Request-ID"]
        detail = await (await client.get(f"/debug/requests/{rid}")).json()
        assert "execute" in {s["phase"] for s in detail["spans"]}
        # executor events made it onto the timeline
        msgs = [e["message"] for e in detail["events"]]
        assert any(m.startswith("exec: spawning") for m in msgs)
        assert any("exited rc=0" in m for m in msgs)
        text = await (await client.get("/metrics")).text()
        assert 'request_phase_seconds_count{phase="execute"}' in text
    finally:
        await client.close()


async def test_flight_recorder_index_and_404():
    client, _ = await make_client(make_cfg())
    try:
        r1 = await client.post("/kubectl-command", json={"query": "list pods"})
        r2 = await client.post("/kubectl-command", json={"query": "list pods"})
        idx = await (await client.get("/debug/requests")).json()
        assert idx["size"] == 256
        ids = [e["request_id"] for e in idx["requests"]]
        assert r2.headers["X-Request-ID"] == ids[0]   # newest first
        assert r1.headers["X-Request-ID"] in ids
        # the cache-hit flag is on the second request's record
        assert idx["requests"][0]["from_cache"] is True
        resp = await client.get("/debug/requests/nonexistent")
        assert resp.status == 404
    finally:
        await client.close()


async def test_flight_recorder_skips_probe_routes_and_scanner_404s():
    client, _ = await make_client(make_cfg())
    try:
        for _ in range(3):
            await client.get("/health")
            await client.get("/metrics")
        await client.get("/debug/requests")
        # unmatched 404s bypass the rate limiter, so a scanner could
        # otherwise flush the ring — they must not be recorded either
        for path in ("/scan-a", "/scan-b", "/wp-login.php"):
            assert (await client.get(path)).status == 404
        idx = await (await client.get("/debug/requests")).json()
        assert idx["requests"] == []
    finally:
        await client.close()


async def test_flight_recorder_cache_events_on_timeline():
    client, _ = await make_client(make_cfg())
    try:
        await client.post("/kubectl-command", json={"query": "list pods"})
        r2 = await client.post("/kubectl-command", json={"query": "list pods"})
        detail = await (await client.get(
            f"/debug/requests/{r2.headers['X-Request-ID']}")).json()
        msgs = [e["message"] for e in detail["events"]]
        assert any(m == "cache: hit" for m in msgs)
        assert "cache" in {s["phase"] for s in detail["spans"]}
    finally:
        await client.close()


async def test_debug_token_gates_debug_endpoints():
    client, _ = await make_client(make_cfg(debug_token="hunter2"))
    try:
        assert (await client.get("/debug/requests")).status == 403
        assert (await client.post("/debug/profile?seconds=0.1")).status == 403
        resp = await client.get("/debug/requests",
                                headers={"X-Debug-Token": "wrong"})
        assert resp.status == 403
        resp = await client.get("/debug/requests",
                                headers={"X-Debug-Token": "hunter2"})
        assert resp.status == 200
        # non-ASCII header bytes must 403, not 500 (compare_digest on
        # str raises TypeError for non-ASCII input)
        resp = await client.get("/debug/requests",
                                headers={"X-Debug-Token": "café"})
        assert resp.status == 403
    finally:
        await client.close()


async def test_debug_profile_produces_trace_dir():
    """Acceptance: POST /debug/profile yields a non-empty jax.profiler
    trace directory (CPU backend suffices for xplane emission)."""
    import os

    client, _ = await make_client(make_cfg())
    try:
        resp = await client.post("/debug/profile?seconds=0.2")
        assert resp.status == 200
        body = await resp.json()
        assert body["seconds"] == 0.2
        assert os.path.isdir(body["trace_dir"])
        contents = []
        for root, _dirs, files in os.walk(body["trace_dir"]):
            contents.extend(files)
        assert contents, "profiler produced an empty trace directory"
        # clamping + bad input
        resp = await client.post("/debug/profile?seconds=nope")
        assert resp.status == 400
    finally:
        await client.close()


async def test_degraded_response_flagged_in_recorder():
    engine = FakeEngine()
    client, _ = await make_client(
        make_cfg(degraded_fallback=True), engine=engine)
    try:
        engine.fail_with = EngineUnavailable("engine down")
        resp = await client.post("/kubectl-command",
                                 json={"query": "list pods"})
        assert resp.status == 200
        body = await resp.json()
        assert body["degraded"] is True
        detail = await (await client.get(
            f"/debug/requests/{resp.headers['X-Request-ID']}")).json()
        assert detail["degraded"] is True
        assert "fallback" in {s["phase"] for s in detail["spans"]}
    finally:
        await client.close()


# ----------------------------------------------------- /metrics scrape tests


async def test_metrics_content_type_and_phase_histograms():
    client, _ = await make_client(make_cfg())
    try:
        await client.post("/kubectl-command", json={"query": "list pods"})
        resp = await client.get("/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = await resp.text()
        assert "request_phase_seconds_bucket" in text
        assert 'phase="decode"' in text
    finally:
        await client.close()


async def test_metrics_phase_label_cardinality_bounded():
    """Unmatched-route scans must not mint phase labels (or any new
    series): the phase allowlist is fixed."""
    from ai_agent_kubectl_tpu.obs import PHASES

    client, _ = await make_client(make_cfg())
    try:
        for path in ("/scan-1", "/.git/config", "/admin/../../etc"):
            await client.get(path)
        await client.post("/kubectl-command", json={"query": "list pods"})
        text = await (await client.get("/metrics")).text()
        seen = set(re.findall(r'request_phase_seconds_count\{phase="([^"]+)"\}',
                              text))
        assert seen
        assert seen <= set(PHASES)
        assert 'handler="unmatched"' in text
        assert "scan-1" not in text
    finally:
        await client.close()


async def test_metrics_tokens_per_sec_windowed():
    """The gauge reports the trailing-window rate, not the last request's
    instantaneous throughput."""
    client, _ = await make_client(make_cfg())
    try:
        await client.post("/kubectl-command", json={"query": "list pods"})
        text = await (await client.get("/metrics")).text()
        m = re.search(r"^engine_tokens_per_sec ([0-9.e+-]+)$", text,
                      re.MULTILINE)
        assert m is not None
        # fake engine returned ~3 completion tokens; windowed over 60s
        # this is well under 1 tok/s — the old gauge reported 10^3+ here.
        assert 0.0 < float(m.group(1)) < 10.0
        assert "trailing 60s window" in text   # HELP text documents it
    finally:
        await client.close()


async def test_metrics_tokens_per_sec_prefers_engine_window():
    class StatsEngine(FakeEngine):
        def stats(self):
            return {"tokens_per_sec_window": 123.5}

    client, _ = await make_client(make_cfg(), engine=StatsEngine())
    try:
        text = await (await client.get("/metrics")).text()
        assert "engine_tokens_per_sec 123.5" in text
    finally:
        await client.close()


# ------------------------------------------------------------- JSON logging


def test_json_log_formatter_stamps_request_id():
    formatter = JsonFormatter()
    fltr = RequestIdFilter()
    record = logging.LogRecord("ai_agent_kubectl_tpu.test", logging.INFO,
                               __file__, 1, "served %s", ("q1",), None)
    t = Trace("rid-json-1")
    with use_trace(t):
        fltr.filter(record)
    line = formatter.format(record)
    entry = json.loads(line)
    assert entry["message"] == "served q1"
    assert entry["request_id"] == "rid-json-1"
    assert entry["level"] == "INFO"
    assert entry["logger"] == "ai_agent_kubectl_tpu.test"

    # outside a request: request_id is null, still valid JSON
    record2 = logging.LogRecord("x", logging.WARNING, __file__, 1,
                                "no ctx", (), None)
    fltr.filter(record2)
    assert json.loads(formatter.format(record2))["request_id"] is None


def test_json_log_formatter_exception_and_unserializable():
    formatter = JsonFormatter()
    try:
        raise ValueError("boom")
    except ValueError:
        import sys

        record = logging.LogRecord("x", logging.ERROR, __file__, 1,
                                   "failed", (), sys.exc_info())
    entry = json.loads(formatter.format(record))
    assert "boom" in entry["exc_info"]


def test_setup_logging_json_mode():
    from ai_agent_kubectl_tpu.logging_setup import setup_logging

    try:
        logger = setup_logging("INFO", "json")
        root = logging.getLogger()
        assert any(isinstance(h.formatter, JsonFormatter)
                   for h in root.handlers)
        assert logger.name == "ai_agent_kubectl_tpu"
    finally:
        # restore default text config so later tests' log output stays sane
        setup_logging("INFO", "text")


# -------------------------------------------- batched-engine trace propagation


@pytest.mark.slow
async def test_batcher_annotates_trace_from_scheduler_thread():
    """The trace captured at submit time crosses the admission queue and
    comes back annotated by the scheduler thread: submit → admit → first
    token → finish all appear on the timeline, and the EngineResult
    carries the accumulated host detok time."""
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.config import get_config

    eng = BatchedJaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        max_seq_len=128,
        prefill_buckets=(64,),
        batch_size=2,
        chunk_len=4,
        prefix_cache=False,
    )
    await eng.start()
    try:
        t = Trace(new_request_id())
        with use_trace(t):
            result = await eng.generate("list the pods", max_tokens=8)
        msgs = [e["message"] for e in t.to_dict()["events"]]
        assert any(m.startswith("engine: submitted") for m in msgs)
        assert any(m.startswith("engine: admitted to slot") for m in msgs)
        assert "engine: first token" in msgs
        assert any(m.startswith("engine: finished") for m in msgs)
        assert result.completion_tokens > 0
        assert result.detok_ms >= 0.0
        # scheduler-side windowed throughput is now nonzero
        assert eng.stats()["tokens_per_sec_window"] > 0.0
    finally:
        await eng.stop()
