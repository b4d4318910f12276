"""Device-side termination + deep chunk pipelining (ISSUE 4).

Covers the packed chunk-result contract (one fetch per chunk carrying
tokens + done mask + live lengths + n_alive), the device-resident
termination semantics (EOS mid-chunk, per-request max_tokens expiring
mid-chunk, all-done-early chunks), the CHUNK_PIPE_DEPTH 1/2/3 transcript
invariance, wasted-decode-step accounting, and deep-pipe client
disconnects — on both the numpy FakeChunkedEngine (milliseconds, runs the
same protocol.py consume code) and the real BatchedJaxEngine on CPU.
"""

import asyncio

import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.protocol import (consume_chunk_row,
                                                  pack_chunk,
                                                  packed_chunk_size,
                                                  scan_chunk_row,
                                                  unpack_chunk)
from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
from ai_agent_kubectl_tpu.models.config import get_config

# ---------------------------------------------------------------------------
# Packed-buffer schema
# ---------------------------------------------------------------------------


def test_packed_chunk_roundtrip():
    n, c = 3, 4
    toks = np.arange(n * c, dtype=np.int32).reshape(n, c)
    done = np.array([True, False, True])
    lengths = np.array([7, 9, 2], np.int32)
    buf = pack_chunk(toks, done, lengths, 1)
    assert buf.shape == (packed_chunk_size(n, c),)
    assert buf.dtype == np.int32
    res = unpack_chunk(buf, n, c)
    np.testing.assert_array_equal(res.tokens, toks)
    np.testing.assert_array_equal(res.done, done)
    np.testing.assert_array_equal(res.lengths, lengths)
    assert res.n_alive == 1


def test_packed_chunk_shape_mismatch_raises():
    buf = np.zeros((10,), np.int32)
    with pytest.raises(ValueError):
        unpack_chunk(buf, 3, 4)


# ---------------------------------------------------------------------------
# Shared consume semantics (the SAME functions both engines run)
# ---------------------------------------------------------------------------


def test_consume_row_eos_mid_chunk():
    # Slot emitted 2 tokens before this chunk; chunk produced 2 valid
    # tokens then EOS at step 2 (mid-chunk): lengths = 4 cumulative.
    row = [11, 12, 2, 2]
    new_ids, finish = consume_chunk_row(row, True, 4, 2, 4, (2,))
    assert new_ids == [11, 12]
    assert finish == "stop"


def test_consume_row_budget_mid_chunk():
    # Budget expired mid-chunk: 3 valid tokens, none of them EOS.
    row = [11, 12, 13, 13]
    new_ids, finish = consume_chunk_row(row, True, 6, 3, 4, (2,))
    assert new_ids == [11, 12, 13]
    assert finish == "length"


def test_consume_row_budget_at_chunk_boundary():
    # Budget expired exactly at the last step: the whole row is valid and
    # there is no EOS entry to inspect — must still read as length.
    row = [11, 12, 13, 14]
    new_ids, finish = consume_chunk_row(row, True, 4, 0, 4, (2,))
    assert new_ids == [11, 12, 13, 14]
    assert finish == "length"


def test_consume_row_not_done():
    row = [11, 12, 13, 14]
    new_ids, finish = consume_chunk_row(row, False, 8, 4, 4, (2,))
    assert new_ids == [11, 12, 13, 14]
    assert finish is None


def test_scan_row_legacy_waste():
    # Legacy host scan: EOS at step 1 wastes the remaining 2 steps.
    new_ids, finish, wasted = scan_chunk_row([11, 2, 99, 98], 0, (2,), 64)
    assert new_ids == [11] and finish == "stop" and wasted == 2
    # Budget finish at step 2 wastes 1.
    new_ids, finish, wasted = scan_chunk_row([11, 12, 13, 99], 5, (2,), 8)
    assert new_ids == [11, 12, 13] and finish == "length" and wasted == 1
    # No finish: nothing wasted.
    assert scan_chunk_row([11, 12, 13, 14], 0, (2,), 64)[2] == 0


# ---------------------------------------------------------------------------
# FakeChunkedEngine — pipeline semantics in milliseconds
# ---------------------------------------------------------------------------

RAGGED = [(f"query {i}", 1 + (i * 5) % 17) for i in range(16)]


async def _run_fake(depth, device_termination=True):
    eng = FakeChunkedEngine(batch_size=4, chunk_len=4,
                            chunk_pipe_depth=depth,
                            device_termination=device_termination)
    await eng.start()
    rs = await asyncio.gather(*[
        eng.generate(p, max_tokens=mt) for p, mt in RAGGED])
    out = [(r.text, r.completion_tokens, r.finish_reason) for r in rs]
    stats = eng.stats()
    await eng.stop()
    return out, stats


@pytest.mark.parametrize("depth", [1, 2, 3])
async def test_fake_depth_sweep_same_transcripts(depth):
    """Every pipe depth (2 is the default) must serve the depth-1
    transcripts and finish reasons byte for byte over a ragged mix of
    EOS- and budget-terminated requests (the CI depth-sweep smoke): the
    depth decides when a chunk is fetched, never what is served."""
    a, sa = await _run_fake(1)
    b, sb = await _run_fake(depth)
    assert a == b
    # The ragged mix must actually exercise both finish flavours.
    reasons = {r for _, _, r in a}
    assert reasons == {"stop", "length"}
    # Done-mask accounting: no decode steps for already-finished slots.
    assert sa["wasted_decode_steps"] == 0
    assert sb["wasted_decode_steps"] == 0
    assert sb["pipe_depth"] == depth


async def test_fake_legacy_host_scan_same_transcripts_but_wastes():
    """DEVICE_TERMINATION=false (the pre-change path) serves the same
    transcripts — termination semantics are unchanged — but executes
    decode steps for finished slots, which the counter must show."""
    a, _ = await _run_fake(3)
    c, sc = await _run_fake(3, device_termination=False)
    assert c == a
    assert sc["wasted_decode_steps"] > 0


async def test_fake_single_fetch_per_chunk():
    """The scheduler performs exactly ONE fetch per consumed chunk; pruned
    chunks are never fetched."""
    _, stats = await _run_fake(3)
    assert stats["fetches"] == stats["chunks_consumed"]
    assert stats["chunks_dispatched"] == (
        stats["chunks_consumed"] + stats["chunks_pruned"])


async def test_fake_deep_pipe_client_disconnect_abort():
    """A client disconnect mid-stream at depth 3 frees the slot at the
    next sweep and bills the speculative chunks to the waste counter."""
    eng = FakeChunkedEngine(batch_size=2, chunk_len=4, chunk_pipe_depth=3)
    await eng.start()
    agen = eng.generate_stream("disconnect me please", max_tokens=500)
    it = agen.__aiter__()
    await it.__anext__()
    await agen.aclose()             # disconnect
    for _ in range(100):
        await asyncio.sleep(0.005)
        if all(s is None for s in eng._slots):
            break
    assert all(s is None for s in eng._slots)
    assert eng.stats()["wasted_decode_steps"] > 0
    # The engine still serves after the abort.
    r = await eng.generate("next request", max_tokens=6)
    assert r.completion_tokens > 0
    await eng.stop()


# ---------------------------------------------------------------------------
# Pipeline observability through the serving stack
# ---------------------------------------------------------------------------


async def test_metrics_and_debug_chunks_expose_pipeline():
    """/metrics carries the decode-pipeline series (occupancy gauge,
    wasted-steps counter, chunk event counters, fetch histogram) and
    /debug/chunks returns the pipeline stats — wired through an engine
    speaking the packed-chunk contract (legacy termination here, so the
    wasted counter provably moves)."""
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    cfg = ServiceConfig(engine="fake", model_name="fake", llm_timeout=5.0)
    engine = FakeChunkedEngine(batch_size=2, chunk_len=4,
                               chunk_pipe_depth=3,
                               device_termination=False)
    app = create_app(cfg, engine,
                     executor=CommandExecutor(timeout=2.0))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await engine.generate("list pods", max_tokens=64)
        text = await (await client.get("/metrics")).text()
        assert "decode_pipe_occupancy" in text
        assert "decode_pipe_depth 3.0" in text
        assert "wasted_decode_steps_total" in text
        assert 'decode_chunks_total{event="consume"}' in text
        assert "chunk_fetch_seconds" in text
        wasted = [ln for ln in text.splitlines()
                  if ln.startswith("wasted_decode_steps_total")]
        assert wasted and float(wasted[0].split()[-1]) > 0
        resp = await client.get("/debug/chunks")
        assert resp.status == 200
        body = await resp.json()
        assert body["pipeline"]["pipe_depth"] == 3
        assert body["pipeline"]["wasted_decode_steps"] > 0
        assert "events" in body
    finally:
        await client.close()
        await engine.stop()


# ---------------------------------------------------------------------------
# BatchedJaxEngine on CPU — the real packed contract end to end
# ---------------------------------------------------------------------------

ENGINE_KW = dict(dtype="float32", max_seq_len=128, prefill_buckets=(32,),
                 prefix_cache=False,
                 batch_size=3, chunk_len=4)


def _started(**kw):
    eng = BatchedJaxEngine(get_config("toy-8m"), tokenizer=ByteTokenizer(),
                           **ENGINE_KW, **kw)
    asyncio.run(eng.start())
    yield eng
    asyncio.run(eng.stop())


@pytest.fixture(scope="module")
def deep():
    yield from _started(chunk_pipe_depth=3)


@pytest.fixture(scope="module")
def shallow():
    yield from _started(chunk_pipe_depth=1)


@pytest.fixture(scope="module")
def default_depth():
    yield from _started()


@pytest.fixture(params=[("deep", 3), ("default_depth", 2)],
                ids=lambda p: p[0])
def piped(request):
    name, depth = request.param
    eng = request.getfixturevalue(name)
    assert eng.chunk_pipe_depth == depth
    return eng


async def test_jax_depth_parity_ragged(piped, shallow):
    """CHUNK_PIPE_DEPTH 3 and the default (2) serve the depth-1
    transcripts on the real engine (greedy; budgets chosen to expire at
    every chunk phase)."""
    prompts = [("list pods", 9), ("get events", 6), ("describe node x", 13),
               ("scale web to 3", 4)]
    for p, mt in prompts:
        a = await piped.generate(p, max_tokens=mt, temperature=0.0)
        b = await shallow.generate(p, max_tokens=mt, temperature=0.0)
        assert a.text == b.text
        assert a.completion_tokens == b.completion_tokens
        assert a.finish_reason == b.finish_reason


async def test_jax_budget_expires_mid_chunk(deep):
    """max_tokens=6 with chunk_len=4 terminates at step 1 of chunk 2 —
    the device budget check must stop the slot exactly there."""
    w0 = deep.stats()["wasted_decode_steps"]
    r = await deep.generate("list services everywhere", max_tokens=6,
                            temperature=0.0)
    assert r.completion_tokens == 6
    assert r.finish_reason == "length"
    assert deep.stats()["wasted_decode_steps"] == w0


async def test_jax_all_done_early_and_ragged_wasted_zero(deep):
    """A concurrent ragged burst whose slots all terminate ahead of the
    depth-3 speculative pipeline: every request completes, and with the
    device-resident done mask no decode step runs for a finished slot
    (wasted_decode_steps_total stays flat — it was nonzero on the
    host-scan path for this exact shape)."""
    w0 = deep.stats()["wasted_decode_steps"]
    rs = await asyncio.gather(*[
        deep.generate(f"describe pod web-{i}", max_tokens=2 + 3 * i,
                      temperature=0.0)
        for i in range(3)])
    for i, r in enumerate(rs):
        assert r.completion_tokens <= 2 + 3 * i
        assert r.finish_reason in ("stop", "length")
    assert deep.stats()["wasted_decode_steps"] == w0


async def test_jax_single_fetch_per_pipeline_entry(deep):
    """The one-fetch-per-chunk invariant on the real engine: during a
    generation, device→host reads == consumed pipeline entries (chunks +
    the admission's first-token entry); pruned chunks are never read."""
    calls = []
    orig = deep._fetch
    deep._fetch = lambda arr: (calls.append(1), orig(arr))[1]
    s0 = deep.stats()
    try:
        r = await deep.generate("rollout status of deployment api",
                                max_tokens=10, temperature=0.0)
        assert r.completion_tokens > 0
    finally:
        deep._fetch = orig
    s1 = deep.stats()
    consumed_chunks = s1["chunks_consumed"] - s0["chunks_consumed"]
    # one fetch per consumed chunk + one for the admission's first token
    assert len(calls) == consumed_chunks + 1
    # speculative chunks beyond the tail were pruned, not fetched
    assert s1["chunks_dispatched"] - s0["chunks_dispatched"] >= consumed_chunks


async def test_jax_deep_pipe_client_disconnect_abort(deep):
    """Client disconnect mid-stream at depth 3: the slot frees at the
    next sweep and the engine keeps serving."""
    agen = deep.generate_stream("get events --watch", max_tokens=100)
    it = agen.__aiter__()
    await it.__anext__()
    await agen.aclose()
    for _ in range(200):
        await asyncio.sleep(0.01)
        if all(s is None for s in deep._slots):
            break
    assert all(s is None for s in deep._slots)
    r = await deep.generate("get pods", max_tokens=4, temperature=0.0)
    assert r.completion_tokens > 0


async def test_jax_eos_mid_chunk_device_stop(deep):
    """EOS termination mid-chunk, deterministically: record the greedy
    token stream for a prompt through the packed buffers (the contract
    itself), then rebuild the engine with cfg.eos_ids set to a token that
    first appears mid-chunk — generation must stop exactly there with
    finish_reason=stop and the device must not bill any wasted steps."""
    prompt = "get deployments in default namespace"
    ids = []
    orig = deep._fetch

    def spy(arr):
        out = orig(arr)
        flat = np.asarray(out)
        if flat.shape == (packed_chunk_size(deep.batch_size,
                                            deep.chunk_len),):
            res = unpack_chunk(flat, deep.batch_size, deep.chunk_len)
            ids.append(res)
        return out

    deep._fetch = spy
    try:
        full = await deep.generate(prompt, max_tokens=20, temperature=0.0)
    finally:
        deep._fetch = orig
    # Reconstruct slot-0's emitted stream from the packed chunks.
    stream = []
    for res in ids:
        v = min(int(res.lengths[0]) - 1 - len(stream), deep.chunk_len)
        stream.extend(int(t) for t in res.tokens[0][:max(0, v)])
    assert len(stream) >= full.completion_tokens - 1

    # Pick a mid-chunk position whose token value has not occurred before
    # (so the crafted EOS fires exactly there).
    k = None
    for cand in range(1, len(stream)):
        # position in the full completion stream: first token came from
        # the admission program, so chunk step = cand % chunk_len.
        if (cand + 1) % deep.chunk_len != 0 and \
                stream[cand] not in stream[:cand]:
            k = cand
            break
    if k is None:
        pytest.skip("toy stream has no unique mid-chunk token to craft")
    eos_tok = stream[k]

    eng = BatchedJaxEngine(
        get_config("toy-8m", eos_ids=(eos_tok,)),
        tokenizer=ByteTokenizer(), chunk_pipe_depth=3, **ENGINE_KW)
    await eng.start()
    try:
        r = await eng.generate(prompt, max_tokens=20, temperature=0.0)
        # first token + stream[:k] were emitted; stream[k] became EOS.
        assert r.finish_reason == "stop"
        assert r.completion_tokens == k + 1
        assert eng.stats()["wasted_decode_steps"] == 0
    finally:
        await eng.stop()
