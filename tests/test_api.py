"""Integration tests: full HTTP surface with FakeEngine + fake kubectl
(SURVEY.md §4 integration row) — every status code enumerated at reference
app.py:288-297 and app.py:360-367."""

import asyncio

import pytest
from aiohttp.test_utils import TestClient, TestServer

from ai_agent_kubectl_tpu.config import ServiceConfig
from ai_agent_kubectl_tpu.engine.fake import FakeEngine
from ai_agent_kubectl_tpu.engine.protocol import EngineUnavailable
from ai_agent_kubectl_tpu.server.app import create_app
from ai_agent_kubectl_tpu.server.executor import CommandExecutor


def make_cfg(**over):
    defaults = dict(engine="fake", model_name="fake", llm_timeout=2.0)
    defaults.update(over)
    return ServiceConfig(**defaults)


async def make_client(cfg, engine=None, kubectl_binary="kubectl"):
    engine = engine or FakeEngine()
    executor = CommandExecutor(timeout=cfg.execution_timeout, kubectl_binary=kubectl_binary)
    app = create_app(cfg, engine, executor=executor)
    client = TestClient(TestServer(app))
    await client.start_server()
    return client, engine


async def test_kubectl_command_happy_path():
    client, engine = await make_client(make_cfg())
    try:
        resp = await client.post("/kubectl-command", json={"query": "list all pods"})
        assert resp.status == 200
        body = await resp.json()
        assert body["kubectl_command"] == "kubectl get pods"
        assert body["from_cache"] is False
        assert body["execution_result"] is None  # B1: generation only
        assert body["metadata"]["success"] is True
        assert body["engine_metadata"]["engine"] == "fake"

        # Second identical query → cache hit
        resp2 = await client.post("/kubectl-command", json={"query": "list all pods"})
        body2 = await resp2.json()
        assert body2["from_cache"] is True
        assert engine.calls == 1
    finally:
        await client.close()


async def test_kubectl_command_sanitizes_query():
    client, engine = await make_client(make_cfg())
    try:
        r1 = await client.post("/kubectl-command", json={"query": "list\n\tall   pods"})
        r2 = await client.post("/kubectl-command", json={"query": "list all pods"})
        assert (await r1.json())["kubectl_command"] == (await r2.json())["kubectl_command"]
        assert (await r2.json())["from_cache"] is True  # same sanitized key
    finally:
        await client.close()


async def test_kubectl_command_400_validation():
    client, _ = await make_client(make_cfg())
    try:
        assert (await client.post("/kubectl-command", json={"query": "ab"})).status == 400
        assert (await client.post("/kubectl-command", json={})).status == 400
        resp = await client.post(
            "/kubectl-command", data=b"not json", headers={"Content-Type": "application/json"}
        )
        assert resp.status == 400
    finally:
        await client.close()


async def test_kubectl_command_422_unsafe():
    client, engine = await make_client(make_cfg())
    try:
        engine.scripted.append("kubectl get pods; rm -rf /")
        resp = await client.post("/kubectl-command", json={"query": "do bad things"})
        assert resp.status == 422
        assert "unsafe" in (await resp.json())["detail"].lower()
    finally:
        await client.close()


async def test_kubectl_command_fence_stripping_e2e():
    client, engine = await make_client(make_cfg())
    try:
        engine.scripted.append("```bash\nkubectl get pods -n default\n```")
        resp = await client.post("/kubectl-command", json={"query": "pods in default"})
        assert resp.status == 200
        assert (await resp.json())["kubectl_command"] == "kubectl get pods -n default"
    finally:
        await client.close()


async def test_kubectl_command_503_degraded():
    engine = FakeEngine()
    client, _ = await make_client(make_cfg(), engine=engine)
    try:
        engine.fail_with = EngineUnavailable("engine down")
        resp = await client.post("/kubectl-command", json={"query": "list pods"})
        assert resp.status == 503
    finally:
        await client.close()


async def test_kubectl_command_504_timeout():
    engine = FakeEngine(delay=10.0)
    client, _ = await make_client(make_cfg(llm_timeout=0.1), engine=engine)
    try:
        resp = await client.post("/kubectl-command", json={"query": "list pods"})
        assert resp.status == 504
    finally:
        await client.close()


async def test_kubectl_command_500_generic():
    engine = FakeEngine()
    client, _ = await make_client(make_cfg(), engine=engine)
    try:
        engine.fail_with = RuntimeError("kaboom")
        resp = await client.post("/kubectl-command", json={"query": "list pods"})
        assert resp.status == 500
    finally:
        await client.close()


async def test_auth_401_paths():
    client, _ = await make_client(make_cfg(api_auth_key="sekrit"))
    try:
        resp = await client.post("/kubectl-command", json={"query": "list pods"})
        assert resp.status == 401
        assert "Missing" in (await resp.json())["detail"]
        resp = await client.post(
            "/kubectl-command", json={"query": "list pods"}, headers={"X-API-Key": "wrong"}
        )
        assert resp.status == 401
        resp = await client.post(
            "/kubectl-command", json={"query": "list pods"}, headers={"X-API-Key": "sekrit"}
        )
        assert resp.status == 200
        # health/metrics stay open (parity: reference only guards the two POSTs)
        assert (await client.get("/health")).status == 200
        assert (await client.get("/metrics")).status == 200
    finally:
        await client.close()


async def test_rate_limit_429():
    client, _ = await make_client(make_cfg(rate_limit="2/minute"))
    try:
        assert (await client.post("/kubectl-command", json={"query": "list pods"})).status == 200
        assert (await client.post("/kubectl-command", json={"query": "list pods"})).status == 200
        resp = await client.post("/kubectl-command", json={"query": "list pods"})
        assert resp.status == 429
        assert "Retry-After" in resp.headers
        # Reset is delta-seconds within the window, not a monotonic epoch.
        assert 0 < int(resp.headers["X-RateLimit-Reset"]) <= 60
    finally:
        await client.close()


async def test_execute_endpoint(fake_kubectl, monkeypatch):
    monkeypatch.setenv("FAKE_KUBECTL_MODE", "table")
    client, _ = await make_client(make_cfg(), kubectl_binary=fake_kubectl)
    try:
        resp = await client.post("/execute", json={"execute": "kubectl get pods"})
        assert resp.status == 200
        body = await resp.json()
        assert body["execution_result"]["type"] == "table"
        assert body["metadata"]["success"] is True

        # 400 on unsafe command
        resp = await client.post("/execute", json={"execute": "kubectl get pods; ls"})
        assert resp.status == 400

        # kubectl error → structured 200 (B2 fixed: no 500)
        monkeypatch.setenv("FAKE_KUBECTL_MODE", "error")
        resp = await client.post("/execute", json={"execute": "kubectl get pods"})
        assert resp.status == 200
        body = await resp.json()
        assert body["execution_error"]["type"] == "kubectl_error"
        assert body["metadata"]["success"] is False
    finally:
        await client.close()


async def test_streaming_multi_turn_agent_loop(fake_kubectl, monkeypatch):
    """BASELINE config 5's workload shape: a multi-turn agent loop —
    stream a command token-by-token, execute it, feed the execution
    result back into the next query, repeat. Exercises the SSE path and
    /execute interleaved under one client session (the pattern a
    kubectl agent drives), not just each endpoint in isolation."""
    monkeypatch.setenv("FAKE_KUBECTL_MODE", "table")
    client, engine = await make_client(make_cfg(), kubectl_binary=fake_kubectl)
    try:
        context = ""
        commands = []
        for turn, query in enumerate([
            "list all pods",
            "describe the first pod from: {ctx}",
            "get logs for the pod in: {ctx}",
        ]):
            q = query.format(ctx=context[:80] or "default")
            # -- stream the command (SSE) --
            resp = await client.post("/kubectl-command/stream",
                                     json={"query": q})
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/event-stream")
            events, data = [], None
            async for raw in resp.content:
                line = raw.decode().strip()
                if line.startswith("event: "):
                    events.append(line.split(": ", 1)[1])
                elif line.startswith("data: "):
                    data = line.split(": ", 1)[1]
            assert events[-1] == "done", (turn, events)
            command = data
            assert command.startswith("kubectl ")
            commands.append(command)
            # -- execute it, carry the result into the next turn --
            resp = await client.post("/execute", json={"execute": command})
            assert resp.status == 200
            body = await resp.json()
            assert body["metadata"]["success"] is True
            context = str(body["execution_result"]["data"])
        assert len(commands) == 3 and len(set(commands)) >= 2
        assert engine.calls == 3        # one generation per turn, no cache
    finally:
        await client.close()


async def test_execute_timeout_structured(fake_kubectl, monkeypatch):
    monkeypatch.setenv("FAKE_KUBECTL_MODE", "slow")
    monkeypatch.setenv("FAKE_KUBECTL_SLEEP", "5")
    client, _ = await make_client(make_cfg(execution_timeout=0.2), kubectl_binary=fake_kubectl)
    try:
        resp = await client.post("/execute", json={"execute": "kubectl get pods"})
        assert resp.status == 200  # B2 fixed: structured error, not 500
        body = await resp.json()
        assert body["execution_error"]["type"] == "timeout"
    finally:
        await client.close()


async def test_health_readiness_gated():
    engine = FakeEngine()
    client, _ = await make_client(make_cfg(), engine=engine)
    try:
        resp = await client.get("/health")
        assert resp.status == 200
        body = await resp.json()
        assert body["status"] == "healthy" and body["engine_ready"] is True
        await engine.stop()
        resp = await client.get("/health")
        assert resp.status == 503
        assert (await resp.json())["status"] == "degraded"
    finally:
        await client.close()


async def test_metrics_exposition():
    client, _ = await make_client(make_cfg())
    try:
        await client.post("/kubectl-command", json={"query": "list pods"})
        await client.post("/kubectl-command", json={"query": "list pods"})
        text = await (await client.get("/metrics")).text()
        assert "http_requests_total" in text
        assert "response_cache_hits_total 1.0" in text
        assert "engine_ttft_seconds" in text
    finally:
        await client.close()


async def test_stream_endpoint():
    client, engine = await make_client(make_cfg())
    try:
        engine.scripted.append("kubectl get pods -o wide")
        resp = await client.post("/kubectl-command/stream", json={"query": "wide pods"})
        assert resp.status == 200
        text = await resp.text()
        assert "event: done" in text
        assert "kubectl get pods -o wide" in text
    finally:
        await client.close()


async def test_concurrent_identical_queries_single_engine_call():
    # Service-level single-flight (B4 fix) through the real HTTP stack.
    engine = FakeEngine(delay=0.1)
    client, _ = await make_client(make_cfg(rate_limit="100/minute"), engine=engine)
    try:
        tasks = [
            client.post("/kubectl-command", json={"query": "list all pods"})
            for _ in range(5)
        ]
        resps = await asyncio.gather(*tasks)
        assert all(r.status == 200 for r in resps)
        assert engine.calls == 1
    finally:
        await client.close()


async def test_xff_not_trusted_by_default():
    # Forged X-Forwarded-For must not mint fresh rate-limit buckets.
    client, _ = await make_client(make_cfg(rate_limit="1/minute"))
    try:
        r1 = await client.post(
            "/kubectl-command", json={"query": "list pods"},
            headers={"X-Forwarded-For": "1.1.1.1"},
        )
        assert r1.status == 200
        r2 = await client.post(
            "/kubectl-command", json={"query": "list pods"},
            headers={"X-Forwarded-For": "2.2.2.2"},
        )
        assert r2.status == 429
    finally:
        await client.close()


async def test_xff_trusted_behind_proxy_keys_per_client():
    """TRUST_PROXY mode (behind a fronting router tier every request
    arrives from one upstream peer IP): the leftmost X-Forwarded-For hop
    keys the rate-limit bucket, so distinct clients get distinct quotas
    while one client's second request still 429s."""
    client, _ = await make_client(
        make_cfg(rate_limit="1/minute", trust_proxy_headers=True))
    try:
        r1 = await client.post(
            "/kubectl-command", json={"query": "list pods"},
            headers={"X-Forwarded-For": "1.1.1.1, 10.0.0.1"},
        )
        assert r1.status == 200
        # A DIFFERENT client through the same proxy: its own bucket.
        r2 = await client.post(
            "/kubectl-command", json={"query": "list pods"},
            headers={"X-Forwarded-For": "2.2.2.2, 10.0.0.1"},
        )
        assert r2.status == 200
        # The first client again: over ITS quota.
        r3 = await client.post(
            "/kubectl-command", json={"query": "list pods"},
            headers={"X-Forwarded-For": "1.1.1.1, 10.0.0.1"},
        )
        assert r3.status == 429
    finally:
        await client.close()


async def test_stream_uses_and_fills_cache():
    client, engine = await make_client(make_cfg())
    try:
        engine.scripted.append("kubectl get ns")
        resp = await client.post("/kubectl-command/stream", json={"query": "all namespaces"})
        assert "event: done" in await resp.text()
        # Non-stream endpoint now hits the cache the stream filled.
        resp2 = await client.post("/kubectl-command", json={"query": "all namespaces"})
        body = await resp2.json()
        assert body["from_cache"] is True and body["kubectl_command"] == "kubectl get ns"
        assert engine.calls == 1
    finally:
        await client.close()


async def test_concurrent_identical_streams_single_engine_call():
    # The streaming endpoint must share the non-streaming single-flight
    # (VERDICT r3 weak #7): concurrent identical stream misses coalesce
    # onto ONE generation; waiters replay the final command.
    engine = FakeEngine(delay=0.1)
    client, _ = await make_client(make_cfg(rate_limit="100/minute"), engine=engine)
    try:
        engine.scripted.extend(["kubectl get pods"] * 5)
        tasks = [
            client.post("/kubectl-command/stream", json={"query": "list all pods"})
            for _ in range(5)
        ]
        resps = await asyncio.gather(*tasks)
        texts = await asyncio.gather(*[r.text() for r in resps])
        assert all(r.status == 200 for r in resps)
        assert all("event: done" in t and "kubectl get pods" in t for t in texts)
        assert engine.calls == 1
    finally:
        await client.close()


async def test_stream_and_nonstream_share_one_flight():
    # A non-streaming request arriving while an identical stream is in
    # flight must coalesce onto it (and vice versa).
    started = asyncio.Event()

    class SignalEngine(FakeEngine):
        async def generate(self, *args, **kwargs):
            started.set()
            return await super().generate(*args, **kwargs)

    engine = SignalEngine(delay=0.3)
    client, _ = await make_client(make_cfg(rate_limit="100/minute"), engine=engine)
    try:
        stream_task = asyncio.ensure_future(
            client.post("/kubectl-command/stream", json={"query": "list all pods"})
        )
        # Wait until the stream's flight has actually reached the engine —
        # a fixed sleep would race the handler on a loaded host.
        await asyncio.wait_for(started.wait(), 5.0)
        resp = await client.post("/kubectl-command", json={"query": "list all pods"})
        body = await resp.json()
        assert body["from_cache"] is True  # coalesced onto the stream's flight
        sresp = await stream_task
        assert "event: done" in await sresp.text()
        assert engine.calls == 1
    finally:
        await client.close()


async def test_stream_generic_engine_error_yields_error_event():
    client, engine = await make_client(make_cfg())
    try:
        engine.fail_with = RuntimeError("boom")
        resp = await client.post("/kubectl-command/stream", json={"query": "list pods"})
        text = await resp.text()
        assert "event: error" in text and "internal error" in text
    finally:
        await client.close()


async def test_metrics_engine_gauges_sampled_at_scrape():
    # The batch/queue/KV gauges are set from engine.stats() at scrape time
    # (round-1 review: registered but never written).
    class StatsEngine(FakeEngine):
        def stats(self):
            return {"batch_occupancy": 3, "queue_depth": 2,
                    "kv_pages_used": 12, "kv_pages_total": 256}

    client, _ = await make_client(make_cfg(), engine=StatsEngine())
    try:
        text = await (await client.get("/metrics")).text()
        assert "engine_batch_occupancy 3.0" in text
        assert "engine_queue_depth 2.0" in text
        assert "engine_kv_pages_used 12.0" in text
        assert "engine_kv_pages_total 256.0" in text
    finally:
        await client.close()


async def test_debug_trace_endpoint():
    """POST /debug/trace captures a jax.profiler trace (SURVEY.md §5
    tracing row) and is auth-gated like the serving routes."""
    client, _ = await make_client(make_cfg(api_auth_key="sekrit"))
    try:
        resp = await client.post("/debug/trace?seconds=0.1")
        assert resp.status == 401  # auth-gated
        resp = await client.post("/debug/trace?seconds=0.1",
                                 headers={"X-API-Key": "sekrit"})
        assert resp.status == 200
        body = await resp.json()
        assert body["seconds"] == 0.1
        import os

        assert os.path.isdir(body["trace_dir"])
        resp = await client.post("/debug/trace?seconds=nope",
                                 headers={"X-API-Key": "sekrit"})
        assert resp.status == 400
    finally:
        await client.close()


async def test_openapi_document_served_and_complete():
    """/openapi.json (VERDICT r4 missing #1): a valid OpenAPI 3.1 document
    built from the live pydantic schemas, unauthenticated (reference
    FastAPI parity, app.py:131), covering every route and the documented
    status-code contract; /docs renders it as HTML."""
    cfg = make_cfg(api_auth_key="sekrit")   # docs must NOT require auth
    client, _ = await make_client(cfg)
    try:
        resp = await client.get("/openapi.json")
        assert resp.status == 200
        doc = await resp.json()
        assert doc["openapi"].startswith("3.")
        assert doc["info"]["title"] == "Kubectl NLP Service"
        assert doc["info"]["version"] == "1.0.0"
        for path in ("/kubectl-command", "/kubectl-command/stream",
                     "/execute", "/health", "/metrics", "/debug/trace"):
            assert path in doc["paths"], path
        # The reference's documented status-code catalog (app.py:288-297).
        kc = doc["paths"]["/kubectl-command"]["post"]["responses"]
        assert set(kc) == {"200", "400", "401", "410", "422", "429",
                           "500", "503", "504"}
        ex = doc["paths"]["/execute"]["post"]["responses"]
        assert set(ex) == {"200", "400", "401", "429", "500"}
        # Schemas come from the real pydantic models; $refs resolve.
        comps = doc["components"]["schemas"]
        for name in ("Query", "ExecuteRequest", "CommandResponse",
                     "ExecutionMetadata", "HealthResponse",
                     "ErrorResponse"):
            assert name in comps, name
        assert comps["Query"]["properties"]["query"]["minLength"] == 3
        import json as _json

        for ref in _json.dumps(doc).split('"#/components/schemas/')[1:]:
            assert ref.split('"')[0] in comps

        resp = await client.get("/docs")
        assert resp.status == 200
        html = await resp.text()
        assert "/openapi.json" in html and "/kubectl-command" in html
    finally:
        await client.close()


async def test_stream_client_disconnect_still_fills_cache():
    """A client dropping mid-SSE-stream must not cancel the shared
    single-flight generation: it completes, fills the cache, and the
    next (non-stream) request is served from_cache without a new engine
    call (the documented SingleFlight semantics, previously unasserted)."""
    engine = FakeEngine(delay=0.4)
    client, _ = await make_client(make_cfg(), engine=engine)
    try:
        resp = await client.post("/kubectl-command/stream",
                                 json={"query": "list all pods"})
        assert resp.status == 200         # headers are sent pre-generation
        resp.close()                      # drop the connection mid-stream
        # the shared flight keeps running; wait for it to land in the cache
        for _ in range(100):
            if engine.calls == 1 and len(
                    client.app["service"].cache.cache) == 1:
                break
            await asyncio.sleep(0.05)
        resp2 = await client.post("/kubectl-command",
                                  json={"query": "list all pods"})
        body = await resp2.json()
        assert body["from_cache"] is True
        assert body["kubectl_command"] == "kubectl get pods"
        assert engine.calls == 1          # no second generation
    finally:
        await client.close()


async def test_metrics_label_cardinality_bounded():
    """Scanner 404 traffic must not mint a Prometheus series per random
    URL: unmatched routes collapse into one "unmatched" handler label."""
    client, _ = await make_client(make_cfg())
    try:
        for path in ("/wp-admin.php", "/.env", "/random/deep/path-123"):
            assert (await client.get(path)).status == 404
        await client.get("/health")
        text = await (await client.get("/metrics")).text()
        assert 'handler="unmatched"' in text
        assert "wp-admin" not in text and "path-123" not in text
        assert 'handler="/health"' in text   # matched routes keep their path
    finally:
        await client.close()


async def test_health_device_info_cached_at_startup():
    """/health names what JAX serves from — device count, platform and
    device kind, here the CPU the tests force — enumerated once at
    startup instead of re-importing jax and listing devices on every LB
    probe."""
    import jax

    client, _ = await make_client(make_cfg())
    try:
        cached = client.app["_device_info"]       # set by the startup hook
        dev = jax.devices()[0]
        assert cached == {"devices": len(jax.devices()),
                          "platform": "cpu", "device_kind": dev.device_kind}
        body = await (await client.get("/health")).json()
        assert {k: body[k] for k in cached} == cached
        # prove the probe reads the cache, not a fresh enumeration
        client.app["_device_info"] = dict(cached, devices=cached["devices"] + 7)
        body = await (await client.get("/health")).json()
        assert body["devices"] == cached["devices"] + 7
    finally:
        await client.close()
