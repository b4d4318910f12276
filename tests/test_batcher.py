"""Continuous-batching scheduler tests (SURVEY.md §5: batcher invariants
under pytest-asyncio-style stress; greedy parity vs the single-sequence
engine)."""

import asyncio
import time

import jax.numpy as jnp
import pytest

from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.jax_engine import JaxEngine
from ai_agent_kubectl_tpu.engine.protocol import GenerationTimeout
from ai_agent_kubectl_tpu.models.config import get_config


@pytest.fixture(scope="module")
def batched():
    eng = BatchedJaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        max_seq_len=256,
        prefill_buckets=(64, 128),
        batch_size=4,
        chunk_len=4,
    )
    asyncio.run(eng.start())
    yield eng
    asyncio.run(eng.stop())


@pytest.fixture(scope="module")
def single():
    eng = JaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        max_seq_len=256,
        prefill_buckets=(64, 128),
    )
    asyncio.run(eng.start())
    yield eng
    asyncio.run(eng.stop())


async def test_graceful_drain_finishes_inflight_and_rejects_new():
    """stop(drain_secs=...) lets an in-flight generation complete while
    new submissions are rejected (readiness drops first) — the graceful
    drain SURVEY.md §5 plans against the reference's abort-only teardown."""
    from ai_agent_kubectl_tpu.engine.protocol import EngineUnavailable

    eng = BatchedJaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        max_seq_len=256,
        prefill_buckets=(64, 128),
        batch_size=2,
        chunk_len=4,
        prefix_cache=False,
    )
    await eng.start()
    inflight = asyncio.create_task(
        eng.generate("list pods with a longish generation",
                     max_tokens=40, temperature=0.0))
    await asyncio.sleep(0.2)            # let it admit and start decoding
    stop_task = asyncio.create_task(eng.stop(drain_secs=30.0))
    await asyncio.sleep(0.05)           # readiness has dropped
    with pytest.raises(EngineUnavailable):
        await eng.generate("rejected during drain", max_tokens=4,
                           temperature=0.0)
    result = await inflight             # drained, not aborted
    assert result.completion_tokens > 0
    await stop_task


async def test_restart_after_drained_stop():
    """stop(drain_secs) → start() must fully re-arm the engine (the
    _stopping drain flag would otherwise keep the watchdog from ever
    re-marking it ready)."""
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
    r1 = await eng.generate("get pods", max_tokens=4, temperature=0.0)
    await eng.stop(drain_secs=5)
    assert eng._stopping
    await eng.start()
    try:
        assert not eng._stopping and eng.ready
        r2 = await eng.generate("get pods", max_tokens=4, temperature=0.0)
        assert r1.text == r2.text
    finally:
        await eng.stop()


async def test_greedy_parity_with_single_engine(batched, single):
    prompt = "list all pods in kube-system"
    a = await batched.generate(prompt, max_tokens=24, temperature=0.0)
    b = await single.generate(prompt, max_tokens=24, temperature=0.0)
    assert a.text == b.text
    assert a.completion_tokens == b.completion_tokens
    assert a.engine == "jax-batched"


async def test_concurrent_requests_all_complete(batched):
    # 10 concurrent requests over 4 slots: queueing + slot reuse.
    prompts = [f"describe pod web-{i}" for i in range(10)]
    results = await asyncio.gather(*[
        batched.generate(p, max_tokens=8 + (i % 5), temperature=0.0)
        for i, p in enumerate(prompts)
    ])
    for i, r in enumerate(results):
        assert r.completion_tokens <= 8 + (i % 5)
        assert r.finish_reason in ("stop", "length")
        assert r.ttft_ms >= 0.0


async def test_concurrent_matches_sequential(batched):
    # The same prompt generated alone and under concurrency must match
    # (per-slot isolation: one request's KV never bleeds into another's).
    prompt = "get deployments in default namespace"
    alone = await batched.generate(prompt, max_tokens=16, temperature=0.0)
    mixed = await asyncio.gather(*[
        batched.generate(p, max_tokens=16, temperature=0.0)
        for p in [prompt, "scale replicaset web to 3", prompt,
                  "delete pod stuck-pod", prompt]
    ])
    assert mixed[0].text == alone.text
    assert mixed[2].text == alone.text
    assert mixed[4].text == alone.text


async def test_streaming_matches_generate(batched):
    prompt = "rollout status of deployment api"
    pieces = []
    async for piece in batched.generate_stream(prompt, max_tokens=12):
        pieces.append(piece)
    full = await batched.generate(prompt, max_tokens=12)
    assert "".join(pieces) == full.text


async def test_timeout_raises(batched):
    with pytest.raises(GenerationTimeout):
        await batched.generate("get events --watch", max_tokens=200,
                               timeout=0.001)


async def test_sampled_temperature_runs(batched):
    r = await batched.generate("get pods", max_tokens=8, temperature=0.9)
    assert r.completion_tokens >= 0
    assert r.finish_reason in ("stop", "length")


async def test_max_tokens_respected_exactly(batched):
    r = await batched.generate("list services everywhere", max_tokens=5,
                               temperature=0.0)
    assert r.completion_tokens <= 5


async def test_cache_capacity_finishes_cleanly(batched):
    # max_tokens larger than cache capacity: must end with finish=length,
    # not crash or overrun the KV buffer.
    r = await batched.generate("x" * 40, max_tokens=10_000, temperature=0.0)
    assert r.finish_reason in ("stop", "length")
    assert r.completion_tokens < batched.max_seq_len
    if r.finish_reason == "length":
        # Capacity finishes must drain in-flight pipeline chunks rather
        # than drop them (code-review regression): the KV region should be
        # filled to within one chunk of max_seq.
        used = r.prompt_tokens + r.completion_tokens
        # The one-chunk slack allocation (S_alloc = max_seq + chunk_len)
        # lets the final chunk run at full length, so capacity finishes
        # fill the cache to max_seq instead of cutting off at chunk
        # granularity.
        assert used >= batched.max_seq_len


def test_factory_selects_batched():
    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.server.factory import build_engine

    cfg = ServiceConfig(engine="jax", model_name="toy-8m",
                        decode_batch_size=4)
    eng = build_engine(cfg)
    assert eng.name == "jax-batched"

    cfg1 = ServiceConfig(engine="jax", model_name="toy-8m",
                         decode_batch_size=1)
    eng1 = build_engine(cfg1)
    assert eng1.name == "jax"


def test_from_config_round_trips_scheduler_shape(monkeypatch):
    """CHUNK_LEN / CHUNK_PIPE_DEPTH reach the engine from env config — the
    benched scheduler shape must be reachable from production config
    (VERDICT r4 weak #4)."""
    from ai_agent_kubectl_tpu.config import ServiceConfig

    monkeypatch.setenv("MODEL_NAME", "toy-8m")
    monkeypatch.setenv("CHUNK_LEN", "16")
    monkeypatch.setenv("CHUNK_PIPE_DEPTH", "3")
    cfg = ServiceConfig.from_env(env_file=None)
    assert cfg.chunk_len == 16 and cfg.chunk_pipe_depth == 3
    eng = BatchedJaxEngine.from_config(cfg)
    assert eng.chunk_len == 16
    assert eng.chunk_pipe_depth == 3
    # Defaults: chunk 16 (earlier chip run, not re-measured) / depth 2 (one
    # chunk running, one queued: a third cost every request a chunk period
    # before its first token and covered nothing — ISSUE 36), with
    # DEVICE_TERMINATION defaulting on. The fake engine, which runs the
    # same protocol, keeps the same default (the real engine's own is
    # pinned by test_termination_pipeline's default_depth fixture).
    monkeypatch.delenv("CHUNK_LEN")
    monkeypatch.delenv("CHUNK_PIPE_DEPTH")
    dflt = ServiceConfig.from_env(env_file=None)
    assert (dflt.chunk_len, dflt.chunk_pipe_depth) == (16, 2)
    assert ServiceConfig().chunk_pipe_depth == 2
    assert FakeChunkedEngine().chunk_pipe_depth == 2
    assert dflt.device_termination is True
    monkeypatch.setenv("DEVICE_TERMINATION", "false")
    off = ServiceConfig.from_env(env_file=None)
    assert off.device_termination is False
    eng_off = BatchedJaxEngine.from_config(off)
    assert eng_off.device_termination is False


async def test_group_admission_burst_parity():
    """Concurrent prefix-hit requests admit through the batched group path
    (one prefill program for the whole burst) and produce exactly the
    single-admission greedy outputs (round-3 review: the group path had no
    coverage). The scheduler is driven by hand with the worker stopped so
    the burst is deterministic."""
    import threading

    from ai_agent_kubectl_tpu.engine.batcher import _Request
    from ai_agent_kubectl_tpu.engine.prompts import render_prompt
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    def mk_engine():
        # kv_pool=False on purpose: this test exercises the DENSE
        # group-admission scratch path. Pool mode has no group scratch —
        # suffixes prefill directly into freshly allocated blocks
        # (ISSUE 10), which tests/test_kv_pool.py covers.
        return BatchedJaxEngine(
            get_config("toy-8m"), tokenizer=ByteTokenizer(), dtype="float32",
            max_seq_len=768, prefill_buckets=(64, 128, 512),
            prefix_cache=True, batch_size=8, chunk_len=4, kv_pool=False)

    queries = ["list pods", "get deployments -o wide",
               "describe node worker-1", "scale deployment web to 3",
               "get events"]
    prompts = [render_prompt(q) for q in queries]

    # Reference: sequential single admissions through the normal worker.
    ref_eng = mk_engine()
    await ref_eng.start()
    ref = []
    for p in prompts:
        r = await ref_eng.generate(p, max_tokens=6, temperature=0.0)
        assert r.prefix_cache_hit
        ref.append(r.text)
    await ref_eng.stop()

    # Group path: stop the worker, enqueue the burst, drive the scheduler
    # deterministically by hand (same loop body the worker runs).
    eng = mk_engine()
    await eng.start()
    eng._running = False
    await asyncio.to_thread(eng._worker.join, 30.0)
    eng._worker = None
    loop = asyncio.get_running_loop()
    reqs = [
        _Request(prompt_ids=eng.tokenizer.encode(p), max_tokens=6,
                 temperature=0.0, deadline=None, loop=loop,
                 out_queue=asyncio.Queue(), cancel=threading.Event(),
                 t_submit=time.monotonic())
        for p in prompts
    ]
    for r in reqs:
        eng._admissions.put(r)
    eng._inflight = []
    eng._admit_pending()
    assert eng._group_admitted >= 1, "burst must use the batched group path"
    for _ in range(500):
        eng._sweep_finishes()
        eng._prune_dead_chunks()
        n_active = sum(s is not None and not s.exhausted for s in eng._slots)
        chunks = sum(1 for e in eng._inflight if e[0] == "chunk")
        if n_active and chunks < 2:
            eng._dispatch_chunk()
        elif eng._inflight:
            eng._consume_oldest()
        if all(s is None for s in eng._slots) and not eng._inflight:
            break
        await asyncio.sleep(0)  # let call_soon_threadsafe callbacks land
    else:
        pytest.fail("scheduler did not drain the burst")

    texts = []
    for r in reqs:
        text = None
        while not r.out_queue.empty():
            ev, payload = r.out_queue.get_nowait()
            if ev == "done":
                text = payload.text
                assert payload.prefix_cache_hit
        texts.append(text)
    assert texts == ref
    await eng.stop()


async def test_watchdog_fails_hung_slots_and_degrades():
    """A stalled scheduler (hung device dispatch) must not leave clients
    blocked forever: the watchdog marks the engine degraded and fails
    every active slot and queued admission (SURVEY.md §5 failure-detection
    row)."""
    import threading

    from ai_agent_kubectl_tpu.engine.batcher import _Request, _Slot
    from ai_agent_kubectl_tpu.engine.protocol import EngineUnavailable
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder

    eng = BatchedJaxEngine(
        get_config("toy-8m"), tokenizer=ByteTokenizer(), dtype="float32",
        max_seq_len=64, prefill_buckets=(32,), prefix_cache=False,
        batch_size=2, chunk_len=4, watchdog_secs=5.0)
    await eng.start()
    # Stop the real worker so the "hang" is fully simulated.
    eng._running = False
    await asyncio.to_thread(eng._worker.join, 30.0)
    eng._worker = None
    eng._ready = True

    loop = asyncio.get_running_loop()

    def mk_req():
        return _Request(prompt_ids=[1, 2, 3], max_tokens=4, temperature=0.0,
                        deadline=None, loop=loop, out_queue=asyncio.Queue(),
                        cancel=threading.Event(), t_submit=time.monotonic())

    active = mk_req()
    queued = mk_req()
    eng._slots[0] = _Slot(req=active, detok=StreamDecoder(eng.tokenizer),
                          n_prompt=3, pos=3, queue_ms=0.0,
                          t_admit=time.monotonic())
    eng._inflight = [("chunk", None, [active, None])]
    eng._admissions.put(queued)

    # Fresh progress: must NOT fire.
    eng._last_progress = time.monotonic()
    assert eng._watchdog_check() is False
    assert eng.ready

    # Stale progress with work in flight: fires once.
    eng._last_progress = time.monotonic() - 999.0
    assert eng._watchdog_check() is True
    assert not eng.ready
    # Slot cleanup belongs to the scheduler thread (ADVICE r3): the
    # watchdog only cancels the request — a scheduler that was merely slow
    # drops it at its next sweep instead of decoding into a dead queue.
    assert eng._slots[0] is not None
    assert active.cancel.is_set()
    assert queued.cancel.is_set()
    await asyncio.sleep(0)  # deliver call_soon_threadsafe callbacks
    for req in (active, queued):
        event, payload = req.out_queue.get_nowait()
        assert event == "error"
        assert isinstance(payload, EngineUnavailable)
    eng._slots[0] = None
    eng._inflight = []
    await eng.stop()


async def test_watchdog_startup_grace_and_admission_grace():
    """VERDICT r5 weak #4: a >watchdog_secs cold compile must not be
    mis-read as a hung dispatch. The no-progress limit widens to
    ENGINE_STARTUP_GRACE_SECS until the first pipeline entry is consumed,
    and again whenever an admission (the lazy-compile site) is mid-flight
    on the scheduler thread; a steady-state hang still fires at
    watchdog_secs."""
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    eng = BatchedJaxEngine(
        get_config("toy-8m"), tokenizer=ByteTokenizer(), dtype="float32",
        max_seq_len=64, prefill_buckets=(32,), prefix_cache=False,
        batch_size=2, chunk_len=4, watchdog_secs=5.0,
        startup_grace_secs=600.0)
    await eng.start()
    assert eng._first_consumed          # warmup generation consumed entries
    try:
        # Simulate "busy but no progress for > watchdog_secs".
        eng._inflight = [("chunk", None, [None, None])]
        eng._last_progress = time.monotonic() - 30.0

        # An admission in flight on the scheduler thread => grace.
        eng._admitting = 1
        assert eng._watchdog_check() is False
        assert eng.ready

        # Cold start (nothing consumed yet) => grace.
        eng._admitting = 0
        eng._first_consumed = False
        assert eng._watchdog_check() is False
        assert eng.ready

        # Steady state: the same stall is a real hang — fires.
        eng._first_consumed = True
        assert eng._watchdog_check() is True
        assert not eng.ready
    finally:
        eng._inflight = []
        await eng.stop()


async def test_watchdog_survives_slow_cold_admissions_end_to_end():
    """Slow-start fake (ISSUE 3 satellite): every admission stalls the
    scheduler thread for multiples of watchdog_secs — the shape of a cold
    7B compile — while other slots are decoding. With the grace the
    engine serves the whole burst and stays ready; without it this
    configuration degraded mid-warmup and failed slots."""
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer

    eng = BatchedJaxEngine(
        get_config("toy-8m"), tokenizer=ByteTokenizer(), dtype="float32",
        max_seq_len=128, prefill_buckets=(32,), prefix_cache=False,
        batch_size=2, chunk_len=4, watchdog_secs=0.5,
        startup_grace_secs=60.0)
    orig = eng._prefill_prompt

    def slow_prefill(prompt_ids, max_tokens):
        time.sleep(1.3)                  # >> watchdog_secs, < grace
        return orig(prompt_ids, max_tokens)

    eng._prefill_prompt = slow_prefill
    await eng.start()                    # warmup admission is already slow
    try:
        results = await asyncio.gather(*[
            eng.generate(f"list pods {i}", max_tokens=24, temperature=0.0)
            for i in range(2)])
        assert all(r.completion_tokens > 0 for r in results)
        assert eng.ready                 # no spurious degraded window
    finally:
        await eng.stop()
