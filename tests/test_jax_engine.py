"""JaxEngine tests: generation mechanics end-to-end on CPU with the toy
model + byte tokenizer (SURVEY.md §7 step 3 — the minimum end-to-end
slice, minus real weights)."""

import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.jax_engine import JaxEngine
from ai_agent_kubectl_tpu.engine.protocol import EngineResult
from ai_agent_kubectl_tpu.models.config import get_config


@pytest.fixture(scope="module")
def engine():
    import asyncio

    eng = JaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        max_seq_len=256,
        prefill_buckets=(64, 128),
        seed=0,
    )
    asyncio.run(eng.start())
    return eng


async def test_generate_mechanics(engine):
    result = await engine.generate("list all pods", max_tokens=8)
    assert isinstance(result, EngineResult)
    assert result.prompt_tokens > 0
    assert 0 <= result.completion_tokens <= 8
    assert result.prefill_ms > 0 and result.ttft_ms > 0
    assert result.engine == "jax"
    assert result.finish_reason in ("stop", "length")


async def test_greedy_determinism(engine):
    # temperature=0 (reference parity, app.py:109) must be reproducible.
    r1 = await engine.generate("show me the nodes", max_tokens=6, temperature=0.0)
    r2 = await engine.generate("show me the nodes", max_tokens=6, temperature=0.0)
    assert r1.text == r2.text


async def test_stream_matches_generate(engine):
    pieces = []
    async for piece in engine.generate_stream("get deployments", max_tokens=6):
        pieces.append(piece)
    full = await engine.generate("get deployments", max_tokens=6)
    assert "".join(pieces) == full.text


async def test_bucket_selection(engine):
    assert engine._bucket_for(10) == 64
    assert engine._bucket_for(64) == 64
    assert engine._bucket_for(65) == 128
    with pytest.raises(ValueError):
        engine._bucket_for(1000)


async def test_long_prompt_served_chunked_up_to_capacity(engine):
    # Prompts beyond the biggest bucket are served via chunked prefill
    # (round-3: no bucket truncation); only the KV capacity itself
    # (max_seq - generation budget) left-truncates.
    result = await engine.generate("x" * 500, max_tokens=4)
    assert result.prompt_tokens == engine.max_seq_len - 4


async def test_drain_completes_queued_waiter():
    """stop(drain_secs) must finish a request that was accepted and is
    QUEUED on the engine lock — not just the one holding it (ADVICE r4:
    the lock-polling drain 503'd queued work). New requests after the
    drain starts are rejected immediately."""
    import asyncio

    from ai_agent_kubectl_tpu.engine.protocol import EngineUnavailable

    eng = JaxEngine(
        get_config("toy-8m"),
        dtype="float32",
        max_seq_len=256,
        prefill_buckets=(64,),
        seed=0,
        prefix_cache=False,
    )
    await eng.start()
    holder = asyncio.create_task(
        eng.generate("first request", max_tokens=12))
    await asyncio.sleep(0.05)          # holder owns the lock
    queued = asyncio.create_task(
        eng.generate("second request", max_tokens=4))
    await asyncio.sleep(0.01)          # queued is waiting on the lock
    stop = asyncio.create_task(eng.stop(drain_secs=30.0))
    await asyncio.sleep(0.01)          # drain began: _ready is now False
    with pytest.raises(EngineUnavailable):
        await eng.generate("late request", max_tokens=2)
    r1, r2 = await asyncio.gather(holder, queued)
    assert r1.completion_tokens > 0 and r2.completion_tokens > 0
    await stop
    assert eng._gen_inflight == 0


async def test_engine_not_started_raises():
    from ai_agent_kubectl_tpu.engine.protocol import EngineUnavailable

    eng = JaxEngine(get_config("toy-8m"), dtype="float32", max_seq_len=64,
                    prefill_buckets=(32,))
    with pytest.raises(EngineUnavailable):
        await eng.generate("hello there")


async def test_served_through_http():
    """Full slice: HTTP → service → JaxEngine → toy model → response.

    A random-init toy model emits arbitrary bytes, so the valid outcomes
    are 200 (lucky valid command) or 422 (safety validator caught it) —
    both prove the whole path executed.
    """
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.server.app import create_app

    cfg = ServiceConfig(
        engine="jax", model_name="toy-8m", dtype="float32",
        max_seq_len=256, prefill_buckets="64,128", max_new_tokens=8,
    )
    eng = JaxEngine(
        get_config("toy-8m"), dtype="float32", max_seq_len=256,
        prefill_buckets=(64, 128),
    )
    app = create_app(cfg, eng)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        resp = await client.post("/kubectl-command", json={"query": "list all pods"})
        assert resp.status in (200, 422)
        health = await (await client.get("/health")).json()
        assert health["engine"] == "jax" and health["engine_ready"] is True
    finally:
        await client.close()


def test_stream_decoder_holds_back_split_multibyte():
    # A token boundary mid-way through a multi-byte character must not leak
    # U+FFFD into the stream (code-review regression). ByteTokenizer makes
    # every byte its own token, so 'é' (2 bytes) and '✓' (3 bytes) are
    # guaranteed to split across pushes.
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder

    tok = ByteTokenizer()
    ids = tok.encode("é✓x", add_bos=False)
    assert len(ids) == 6  # 2 + 3 + 1 bytes

    detok = StreamDecoder(tok)
    pieces = [p for i in ids if (p := detok.push(i)) is not None]
    tail = detok.flush()
    if tail is not None:
        pieces.append(tail)
    assert all("�" not in p for p in pieces), pieces
    assert "".join(pieces) == "é✓x"


def test_stream_decoder_releases_genuinely_invalid_bytes():
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder

    tok = ByteTokenizer()
    detok = StreamDecoder(tok)
    # 0xFF is never valid UTF-8; after 3 following chars it must be released
    # as U+FFFD rather than held back forever.
    pieces = []
    for i in [0xFF + 3] + tok.encode("abcd", add_bos=False):
        p = detok.push(i)
        if p is not None:
            pieces.append(p)
    tail = detok.flush()
    if tail is not None:
        pieces.append(tail)
    assert "".join(pieces) == "�abcd"


async def test_max_tokens_clamped_to_cache(engine):
    # MAX_NEW_TOKENS >= MAX_SEQ_LEN must not overflow the KV cache
    # (code-review regression: falsy-zero max_prompt).
    result = await engine.generate("list pods", max_tokens=10_000)
    assert result.completion_tokens < engine.max_seq_len


async def test_stream_cancellation_releases_engine(engine):
    # Cancelling a stream mid-generation must not wedge the engine lock or
    # raise "generator already executing" (code-review regression).
    import asyncio

    async def consume_one():
        agen = engine.generate_stream("show all deployments", max_tokens=64)
        async for _ in agen:
            break  # disconnect after the first piece
        await agen.aclose()

    await asyncio.wait_for(consume_one(), timeout=30)
    # Engine must still serve the next request.
    result = await asyncio.wait_for(
        engine.generate("list pods", max_tokens=4), timeout=30
    )
    assert result.engine == "jax"


def test_stream_decoder_window_stays_bounded():
    # Incremental decode: per-push work is a short trailing window, not the
    # whole id list (round-1 review: O(n^2) host cost per generation).
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder

    tok = ByteTokenizer()
    detok = StreamDecoder(tok)
    for i in tok.encode("kubectl get pods -n staging " * 40, add_bos=False):
        detok.push(i)
        assert len(detok.ids) - detok._prefix_idx <= 4
    assert detok.text == "kubectl get pods -n staging " * 40


def test_stream_decoder_caps_invalid_run_window():
    # An adversarial all-invalid byte stream must not grow the re-decode
    # window without bound: past _WINDOW_CAP it is force-released.
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder

    tok = ByteTokenizer()
    detok = StreamDecoder(tok)
    cap = StreamDecoder._WINDOW_CAP
    for _ in range(cap * 3):
        detok.push(0xFF + tok.SPECIALS)
        assert len(detok.ids) - detok._prefix_idx <= cap + 1
    detok.flush()
    assert detok.text == "�" * (cap * 3)


def test_stream_decoder_cap_release_keeps_pending_split_char():
    # Cap-triggered force release must not flush a split multi-byte char
    # pending completion (round-2 advisor): the window advances only to the
    # last replacement-free id boundary, so bytes completing after the
    # release still decode correctly.
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder

    tok = ByteTokenizer()
    detok = StreamDecoder(tok)
    cap = StreamDecoder._WINDOW_CAP
    bad = 0xFF + tok.SPECIALS
    # One oversized push: garbage run + clean 'x' + first byte of 'é'.
    detok.push(*([bad] * cap + tok.encode("x", add_bos=False) + [0xC3 + tok.SPECIALS]))
    # The partial 0xC3 must still be pending, not flushed as U+FFFD.
    assert detok.text == "�" * cap + "x"
    detok.push(0xA9 + tok.SPECIALS, *tok.encode("y", add_bos=False))
    detok.flush()
    assert detok.text == "�" * cap + "xéy"


def test_stream_decoder_position_dependent_tokenizer():
    # Real HF tokenizers (SentencePiece Strip(left=1) + byte-fallback Fuse)
    # decode a chunk of ids differently standalone than in context — naive
    # chunk-decode concatenation drops the inter-token spaces (code-review
    # regression). The prefix-window diff must reproduce the full decode.
    from ai_agent_kubectl_tpu.engine.tokenizer import StreamDecoder

    class StripTokenizer:
        """decode() joins word-pieces with spaces and strips the leading
        space — the observable behaviour of Llama/Gemma tokenizer.json."""

        vocab = ["<pad>", "<bos>", "<eos>", "kubectl", "get", "pods", "-n",
                 "staging"]
        eos_ids = (2,)
        bos_id, pad_id, vocab_size = 1, 0, 8

        def encode(self, text, *, add_bos=True):
            return [self.vocab.index(w) for w in text.split()]

        def decode(self, ids):
            return " ".join(self.vocab[i] for i in ids if i > 2)

    tok = StripTokenizer()
    ids = tok.encode("kubectl get pods -n staging")
    full = tok.decode(ids)

    detok = StreamDecoder(tok)
    pieces = [p for i in ids if (p := detok.push(i)) is not None]
    tail = detok.flush()
    if tail is not None:
        pieces.append(tail)
    assert "".join(pieces) == full == "kubectl get pods -n staging"
    assert detok.text == full


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """The persistent compilation cache rule (PR 21): on the CPU no
    cache is configured; off-CPU with JAX_COMPILATION_CACHE_DIR unset,
    every construction points at the same git-ignored directory inside
    the checkout; with it set, JAX already holds the directory and the
    engine sets none in code — only the compile-time threshold."""
    from pathlib import Path

    import jax

    from ai_agent_kubectl_tpu.config import DEFAULT_COMPILE_CACHE_DIR

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))

    def setup():
        updates.clear()
        JaxEngine(get_config("toy-8m"))._setup_compile_cache()
        return dict(updates)

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert setup() == {}                                  # CPU backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    repo = Path(__file__).resolve().parent.parent
    assert Path(DEFAULT_COMPILE_CACHE_DIR) == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()
    for _ in range(2):
        assert setup() == {
            "jax_compilation_cache_dir": DEFAULT_COMPILE_CACHE_DIR,
            "jax_persistent_cache_min_compile_time_secs": 0.2}

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert setup() == {"jax_persistent_cache_min_compile_time_secs": 0.2}
