"""Engine spans stamped where the work happens (obs/trace.py RequestSpans,
SchedSpans, SpanStats): the span tree of a request on both generate
routes, the slot-wait split of queue_wait, the carrying chunk's
``chunks_ahead`` and ``chunks_unready``, the scheduler thread's wall-time
partition and its two companions (``sched_starved_s``, the pipe empty;
``sched_drained_s``, the newest launch of any kind done), ``/health.spans``,
the four event messages the benchmark's regex reads, and the ``sched/*``
annotations in a ``jax.profiler`` capture, which holds no Python-tracer
frame unless asked."""

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


def test_child_region_keeps_the_enclosing_state_and_its_chunk():
    """A child names a part of the region that runs it: ring entry,
    totals and annotation under the parent's chunk number, the thread's
    time still charged to the parent's state, the six states still
    summing to elapsed; outside a running scheduler it records nothing."""
    from collections import deque

    ring, stats = deque(maxlen=16), SpanStats()
    calls = []

    class Ann:
        def __init__(self, name, **kw):
            calls.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    sched = SchedSpans(stats, ring, annotate=Ann)
    with sched.child("radix_match", totals=("tokens",), tokens=5) as e:
        e["tokens"] = 7                      # the warm-up's: nobody's child
    assert not ring and not calls and stats.snapshot() == {}
    sched.start()
    with sched.region("admit", "admit", chunk=4):
        time.sleep(0.005)
        with sched.child("radix_match", totals=("tokens", "matched"),
                         tokens=40) as e:
            time.sleep(0.01)
            e["matched"] = 32
            with sched.child("radix_evict", totals=("blocks_freed",)) as w:
                time.sleep(0.005)
                w["blocks_freed"] = 3
        with sched.child("eager_prefill", totals=("tokens", "call_ms"),
                         tokens=9) as e:
            e["call_ms"] = 2.5
    with sched.region("consume", "consume", chunk=4):
        with sched.child("radix_insert", totals=("blocks",)) as e:
            time.sleep(0.005)
            e["blocks"] = 2
    sched.stop()
    snap = sched.snapshot()
    assert sum(snap[s] for s in SCHED_STATES) == pytest.approx(
        snap["elapsed"], rel=1e-3)
    # the children's time is the parents': admit holds the match and its
    # walk, consume the insert, and no state but those two and "other"
    assert snap["admit"] >= 0.019 and snap["consume"] >= 0.004
    assert snap["dispatch"] == snap["fetch_wait"] == snap["idle"] == 0.0
    got = stats.snapshot()
    assert got["sched/radix_match"] == {
        "count": 1, "total_ms": got["sched/radix_match"]["total_ms"],
        "max_ms": got["sched/radix_match"]["max_ms"],
        "tokens_total": 40, "matched_total": 32}
    assert got["sched/radix_evict"]["blocks_freed_total"] == 3
    assert got["sched/eager_prefill"]["tokens_total"] == 9
    assert got["sched/eager_prefill"]["call_total_ms"] == 2.5
    assert got["sched/radix_insert"]["blocks_total"] == 2
    assert (got["sched/radix_evict"]["total_ms"]
            <= got["sched/radix_match"]["total_ms"]
            <= got["sched/admit"]["total_ms"])
    assert got["sched/radix_insert"]["total_ms"] <= \
        got["sched/consume"]["total_ms"]
    by = {e["event"]: e for e in ring}
    # a child closes before its parent: it is in the ring first
    assert [e["event"] for e in ring] == [
        "radix_evict", "radix_match", "eager_prefill", "admit",
        "radix_insert", "consume"]
    for kid, parent in (("radix_evict", "radix_match"),
                        ("radix_match", "admit"), ("eager_prefill", "admit"),
                        ("radix_insert", "consume")):
        assert by[parent]["t0"] <= by[kid]["t0"] <= by[kid]["t1"] \
            <= by[parent]["t1"], (kid, parent)
        assert by[kid]["chunk"] == 4 and by[kid]["span"] == f"sched/{kid}"
    assert by["radix_match"]["tokens"] == 40 and by["radix_match"]["matched"] == 32
    assert ("sched/radix_evict", {"chunk": 4}) in calls
    assert calls[0] == ("sched/admit", {"chunk": 4})


def test_starved_partition_counts_only_an_empty_pipe_with_work_at_hand():
    from collections import deque

    sched = SchedSpans(SpanStats(), deque(maxlen=8))
    sched.start()
    sched.note_live(True)
    assert sched.note_pipe(1) < 1.0        # ms: empty for no time at all
    with sched.region("consume", "consume", chunk=1):
        time.sleep(0.01)                   # a chunk is in flight: not starved
    assert sched.starved()["total"] < 0.002
    sched.note_pipe(0)
    with sched.region("consume", "consume", chunk=1):
        time.sleep(0.01)
    with sched.region("admit", "admit", chunk=2):
        time.sleep(0.01)
    with sched.region("dispatch", "dispatch", chunk=2) as entry:
        time.sleep(0.005)
        entry["pipe_empty_ms"] = sched.note_pipe(1)
        time.sleep(0.005)                  # the launch is out: not starved
    starved = sched.starved()
    assert starved["consume"] >= 0.009 and starved["admit"] >= 0.009
    assert 0.004 <= starved["dispatch"] < 0.009
    assert sum(starved[s] for s in SCHED_STATES) == pytest.approx(
        starved["total"], abs=1e-5)
    # how long the pipe stood empty when that chunk was issued
    assert entry["pipe_empty_ms"] == pytest.approx(
        starved["total"] * 1e3, rel=0.1)
    # nothing live and nothing in hand: an idle engine is not starved
    sched.note_pipe(0)
    sched.note_live(False)
    with sched.region("idle"):
        time.sleep(0.01)
    assert sched.starved()["idle"] == 0.0
    with sched.region("admit", "admit", chunk=3):   # an admission in hand
        time.sleep(0.005)
    sched.stop()
    after = sched.starved()
    assert after["admit"] >= starved["admit"] + 0.004
    snap = sched.snapshot()
    for s in SCHED_STATES:
        assert after[s] <= snap[s] + 1e-6, s


def test_a_chunk_the_device_is_done_with_has_left_the_pipe():
    """The pipe is what the DEVICE still works on: a dispatched chunk
    whose buffer is ready has left it though nobody fetched it, and the
    thread learns so where it looks, the end of a child region among
    them (a long admission is many)."""
    from collections import deque

    from ai_agent_kubectl_tpu.obs.trace import EngineSpans

    class Buf:
        done = False

        def is_ready(self):
            return self.done

    spans = EngineSpans(deque(maxlen=16))
    inflight = [("first", Buf()), ("chunk", Buf())]
    inflight[0][1].done = True             # not a chunk program: no matter
    spans.sched.start()
    spans.note_slots([object(), None])
    spans.note_pipe(inflight)
    with spans.sched.region("admit", "admit", chunk=2):
        with spans.sched.child("eager_prefill"):
            time.sleep(0.005)
        assert spans.sched.starved()["total"] < 0.001   # the device is at it
        inflight[1][1].done = True         # ... and done, unseen so far
        with spans.sched.child("eager_prefill"):
            time.sleep(0.005)              # its end looks at the pipe
        time.sleep(0.01)
    starved = spans.sched.starved()
    assert 0.009 <= starved["admit"] < 0.015
    with spans.sched.region("dispatch", "dispatch", chunk=2) as entry:
        inflight.append(("chunk", Buf()))
        entry["pipe_empty_ms"] = spans.note_pipe(inflight)
    assert entry["pipe_empty_ms"] == pytest.approx(
        spans.sched.starved()["total"] * 1e3, rel=0.05)
    assert entry["pipe_empty_ms"] >= 9.0
    time.sleep(0.005)                      # one chunk at work: not starved
    spans.sched.stop()
    assert spans.sched.starved()["total"] == pytest.approx(
        entry["pipe_empty_ms"] / 1e3, rel=0.05)


class _Buf:
    """A launch's output handle: what ``jax.Array`` answers, by hand."""

    def __init__(self, done=False):
        self.done, self.deleted, self.asked = done, False, 0

    def is_ready(self):
        self.asked += 1
        if self.deleted:
            raise RuntimeError("Array has been deleted.")
        return self.done

    def is_deleted(self):
        return self.deleted


def _engine_spans(n=32):
    from collections import deque

    from ai_agent_kubectl_tpu.obs.trace import EngineSpans

    return EngineSpans(deque(maxlen=n))


def _check_drained(drained: dict, thread: dict) -> None:
    """``sched_drained_s``: two partitions by state, each with its total,
    the section's total the one with work, the regions' seconds its split."""
    assert set(drained) == {"with_work", "no_work", "total", "unseen",
                            "by_region"}
    for part in ("with_work", "no_work"):
        assert set(drained[part]) == {*SCHED_STATES, "total"}
        assert sum(drained[part][s] for s in SCHED_STATES) == pytest.approx(
            drained[part]["total"], abs=1e-5)
    assert drained["total"] == drained["with_work"]["total"]
    assert sum(drained["by_region"].values()) == pytest.approx(
        drained["total"], abs=1e-4)
    assert drained["unseen"] >= 0
    for s in SCHED_STATES:      # parts of the thread's seconds, state by state
        assert drained["with_work"][s] + drained["no_work"][s] <= \
            thread[s] + 1e-4, s
    assert drained["with_work"]["total"] + drained["no_work"]["total"] <= \
        thread["elapsed"] + 1e-4


def test_drained_is_a_partition_split_by_the_work_at_hand():
    """The seconds while the newest launch is done, by state: with a slot
    live or an admission in hand they are what the host could win, with
    neither they say the traffic left the device alone. Nothing is charged
    while the device is at a launch of any kind."""
    spans = _engine_spans()
    spans.sched.start()
    spans.note_slots([None, None])
    with spans.sched.region("idle"):
        time.sleep(0.01)                    # nothing launched, nothing to do
    first = spans.sched.drained()
    assert first["no_work"]["idle"] >= 0.009 and first["total"] == 0.0
    spans.note_slots([object(), None])
    piece = _Buf()
    with spans.sched.region("admit", "admit", chunk=1):
        time.sleep(0.005)                   # in hand, nothing on the device
        spans.launched(piece)
        time.sleep(0.01)                    # the device is at the piece
    mid = spans.sched.drained()
    assert 0.004 <= mid["with_work"]["admit"] < 0.009
    piece.done = True
    with spans.sched.region("consume", "consume", chunk=1):
        time.sleep(0.005)                   # done, but nobody has looked
    assert spans.sched.drained()["total"] == mid["total"]
    spans.note_pipe([])                     # the loop's look
    with spans.sched.region("dispatch", "dispatch", chunk=2) as entry:
        time.sleep(0.005)
        chunk = _Buf()
        inflight = [("chunk", chunk)]
        entry.update(spans.dispatched(inflight, chunk))
        time.sleep(0.005)                   # the chunk is out
    spans.sched.stop()
    drained, thread = spans.sched.drained(), spans.sched.snapshot()
    _check_drained(drained, thread)
    assert 0.004 <= drained["with_work"]["dispatch"] < 0.009
    assert drained["with_work"]["consume"] == 0.0
    assert drained["no_work"]["idle"] == first["no_work"]["idle"]
    assert drained["no_work"]["total"] == pytest.approx(
        first["no_work"]["total"], abs=0.001)
    # the dispatch's ring entry: what the device stood drained since the
    # dispatch before it (none: since the start), beside pipe_empty_ms
    assert entry["drained_ms"] == pytest.approx(drained["total"] * 1e3,
                                                rel=0.05)
    assert entry["pipe_empty_ms"] > entry["drained_ms"]
    assert piece.asked >= 1 and chunk.asked >= 1


def test_an_eager_piece_on_the_device_is_starved_and_not_drained():
    """The two accounts apart: no chunk program is in flight, so the pipe
    is empty and the seconds are ``starved``; the admission's eager piece
    is still on the device, so not one of them is ``drained``."""
    spans = _engine_spans()
    inflight = []
    spans.sched.start()
    spans.note_slots([object(), None])
    spans.note_pipe(inflight)
    piece = _Buf()
    with spans.sched.region("admit", "admit", chunk=1):
        with spans.sched.child("eager_prefill"):
            spans.launched(piece)
        time.sleep(0.01)
        with spans.sched.child("radix_evict"):      # its end looks
            time.sleep(0.002)
    spans.sched.stop()
    assert spans.sched.starved()["admit"] >= 0.011
    drained = spans.sched.drained()
    assert drained["total"] < 0.0005 and drained["unseen"] == 0.0


def test_unseen_grows_only_between_a_busy_look_and_the_done_look_after():
    """What lies between the last look that saw the device busy and the
    first that saw it done is not known: ``unseen``. Looks that agree add
    nothing, and neither does a wait for the device (``fetch_wait``), at
    whose end the thread KNOWS when it was done."""
    spans = _engine_spans()
    inflight = []
    spans.sched.start()
    spans.note_slots([object()])
    buf = _Buf()
    spans.launched(buf)
    for _ in range(3):
        time.sleep(0.003)
        spans.note_pipe(inflight)           # busy, busy, busy
    assert spans.sched.drained()["unseen"] == 0.0
    time.sleep(0.01)
    buf.done = True                         # ... somewhere in here
    time.sleep(0.002)
    spans.note_pipe(inflight)               # the first look that sees it
    unseen = spans.sched.drained()["unseen"]
    assert 0.011 <= unseen < 0.02
    assert buf.asked == 4
    for _ in range(3):
        time.sleep(0.003)
        spans.note_pipe(inflight)           # done, done, done
    assert spans.sched.drained()["unseen"] == unseen
    assert buf.asked == 4                   # done is done: not asked again
    total = spans.sched.drained()["total"]
    assert 0.008 <= total < 0.015           # counted from the look on
    # the thread blocks on the newest launch's buffer: busy until then
    buf = _Buf()
    spans.launched(buf)
    spans.note_pipe(inflight)
    with spans.sched.region("fetch_wait", "fetch", chunk=1):
        time.sleep(0.01)
        buf.done = True
    spans.note_pipe(inflight)
    assert spans.sched.drained()["unseen"] == pytest.approx(unseen, abs=0.001)
    # a launch that finds the one before it done, unseen by any look
    unseen = spans.sched.drained()["unseen"]
    buf, t0 = _Buf(), time.monotonic()
    spans.launched(buf)
    buf.done = True
    time.sleep(0.005)
    spans.launched(_Buf())
    grew = spans.sched.drained()["unseen"] - unseen
    assert 0.004 <= grew <= time.monotonic() - t0
    spans.sched.stop()
    _check_drained(spans.sched.drained(), spans.sched.snapshot())


def test_a_launch_before_the_thread_starts_leaves_nothing_unseen():
    """The warm-up launches from another thread, long before a scheduler
    runs: the stretch from its launch to the scheduler's first look is
    nobody's, and ``unseen`` stays within the thread's own seconds."""
    spans = _engine_spans()
    warm = _Buf()
    spans.launched(warm)                    # no scheduler yet
    time.sleep(0.02)
    warm.done = True
    spans.sched.start()
    spans.note_slots([object()])
    time.sleep(0.003)
    spans.note_pipe([])                     # the first look: done
    spans.sched.stop()
    drained, thread = spans.sched.drained(), spans.sched.snapshot()
    assert drained["unseen"] <= thread["elapsed"] < 0.015
    _check_drained(drained, thread)
    # ... and one that ends before any scheduler has started adds nothing
    spans = _engine_spans()
    spans.launched(_Buf(done=True))
    time.sleep(0.005)
    spans.launched(_Buf(done=True))
    assert spans.sched.drained()["unseen"] == 0.0


def test_by_region_bills_a_drained_stretch_to_the_innermost_open_region():
    spans = _engine_spans()
    inflight = []
    spans.sched.start()
    spans.note_slots([object()])
    spans.note_pipe(inflight)               # nothing launched: drained
    with spans.sched.region("admit", "admit", chunk=3):
        time.sleep(0.004)                   # the admission's own
        with spans.sched.child("radix_evict"):
            time.sleep(0.006)
        with spans.sched.child("arm") as arm:
            time.sleep(0.008)               # the launch waits on the host
            spans.launched(_Buf())
            time.sleep(0.005)               # and then the device has it
    spans.sched.stop()
    by = spans.sched.drained()["by_region"]
    assert 0.0075 <= by["sched/arm"] < 0.012
    assert 0.0055 <= by["sched/radix_evict"] < 0.008
    assert 0.0035 <= by["sched/admit"] < 0.006
    assert set(by) == {"sched/arm", "sched/radix_evict", "sched/admit",
                       "none"}
    assert by["none"] < 0.001               # between start() and the admit
    assert arm["ms"] >= 13.0
    _check_drained(spans.sched.drained(), spans.sched.snapshot())


def test_a_donated_or_deleted_handle_does_not_raise():
    """``is_ready()`` of a donated array raises: a deleted handle is not
    asked, and reads as busy (a later program took it) until that program
    has told of itself. With real arrays and a real donation too."""
    import jax
    import jax.numpy as jnp

    spans = _engine_spans()
    spans.sched.start()
    spans.note_slots([object()])
    gone = _Buf()
    spans.launched(gone)
    was = spans.sched.drained()["total"]    # start() to the launch
    gone.deleted = True
    assert spans.note_pipe([]) == 0.0
    time.sleep(0.003)
    spans.note_pipe([])
    assert gone.asked == 0 and spans.sched.drained()["total"] == was
    spans.launched(_Buf(done=True))         # the program that took it
    spans.note_pipe([])
    time.sleep(0.003)
    assert spans.sched.drained()["total"] >= was + 0.002

    bump = jax.jit(lambda a: a + 1, donate_argnums=(0,))
    a = bump(jnp.zeros((8,), jnp.int32))
    spans.launched(a)
    b = bump(a)                             # takes ``a`` donated
    assert a.is_deleted()
    spans.note_pipe([])                     # looks at a deleted handle
    spans.launched(b)
    b.block_until_ready()
    spans.note_pipe([])
    spans.launched(None)                    # nothing in flight
    spans.note_pipe([])
    spans.sched.stop()
    _check_drained(spans.sched.drained(), spans.sched.snapshot())


def test_spans_growth_reaches_into_the_drained_sections_parts():
    from ai_agent_kubectl_tpu.obs.trace import spans_growth

    before = {"sched/arm": {"count": 2, "total_ms": 3.0, "max_ms": 2.0},
              "sched_drained_s": {
                  "with_work": {"admit": 1.0, "total": 1.0},
                  "no_work": {"idle": 4.0, "total": 4.0},
                  "total": 1.0, "unseen": 0.25,
                  "by_region": {"sched/arm": 1.0}}}
    after = {"sched/arm": {"count": 5, "total_ms": 9.5, "max_ms": 2.0},
             "sched_drained_s": {
                 "with_work": {"admit": 1.5, "total": 1.5},
                 "no_work": {"idle": 4.0, "total": 4.0},
                 "total": 1.5, "unseen": 0.5,
                 "by_region": {"sched/arm": 1.25, "none": 0.25}}}
    assert spans_growth(before, after) == {
        "sched/arm": {"count": 3, "total_ms": 6.5},
        "sched_drained_s": {
            "with_work": {"admit": 0.5, "total": 0.5},
            "no_work": {"idle": 0.0, "total": 0.0},
            "total": 0.5, "unseen": 0.25,
            "by_region": {"sched/arm": 0.25, "none": 0.25}}}
    assert spans_growth(None, after)["sched_drained_s"]["by_region"] == \
        after["sched_drained_s"]["by_region"]
    assert spans_growth(before, None) is None


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


async def test_default_depth_puts_one_chunk_in_front_of_a_late_prompt():
    """At the DEFAULT pipe depth (2: one chunk running, one queued) a
    prompt that arrives while the pipe is full is staged right after the
    consume the scheduler was in, finds ONE chunk in front of the chunk
    that carries it, and has its first token with the SECOND chunk
    consumed after it was staged (a depth of 3 made that the third)."""
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.005)
    eng = FakeChunkedEngine(batch_size=2, chunk_len=2, kv_pool=True,
                            force_ragged=True, faults=inj,
                            stream_fn=lambda _p: [9] * 60 + [2])
    assert eng.chunk_pipe_depth == ServiceConfig().chunk_pipe_depth == 2
    # The late prompt arrives during a consume that found the pipe full:
    # the event wakes its submitter before the scheduler's next tick.
    arrived = asyncio.Event()
    consume = eng._consume_oldest

    def consume_with_the_pipe_full():
        full = len(eng._inflight) == eng.chunk_pipe_depth
        consume()
        if full and eng._chunks_consumed >= 3:
            arrived.set()

    eng._consume_oldest = consume_with_the_pipe_full
    await eng.start()
    try:
        traces = [Trace(new_request_id()) for _ in range(2)]

        async def run(t, prompt):
            with use_trace(t):
                return await eng.generate(prompt, max_tokens=40)

        first = asyncio.ensure_future(run(traces[0], "long runner"))
        await arrived.wait()
        # awaited in this task, not as a new one: the prompt is in the
        # queue before the scheduler's next tick
        await run(traces[1], "late joiner")
        await first
        late = next(s for s in traces[1].to_dict()["spans"]
                    if s["phase"] == "first_chunk")["meta"]
        assert late["chunks_ahead"] == 1
        ring = list(eng._chunk_log)
        staged = max(i for i, e in enumerate(ring)
                     if e["event"] == "admit" and e.get("requests"))
        assert ring[staged]["chunk"] == late["chunk"]
        consumed = [e["chunk"] for e in ring[staged:]
                    if e["event"] == "consume"
                    and e["chunk"] <= late["chunk"]]
        assert consumed == [late["chunk"] - 1, late["chunk"]]
        assert eng.spans_health()["first_chunk"]["chunks_ahead_total"] == 1
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


def _nested_in_a_parent(ring, kid) -> bool:
    return any(p["event"] in ("admit", "dispatch", "consume")
               and p["t0"] <= kid["t0"] and kid["t1"] <= p["t1"]
               and p["chunk"] == kid["chunk"] for p in ring)


async def test_radix_regions_on_the_fake_under_a_tiny_lru_budget():
    """The radix regions come from the shared code (radix_cache.py,
    kv_pool.map_prefix), so the fake has them: a match an admission, an
    insert a release, and ``sched/radix_evict`` only once a walk really
    ran, with the blocks it freed as a total beside its time."""
    eng = _fake(kv_pool_page=4, radix_lru_blocks=6)
    await eng.start()
    try:
        def prompt(i):
            return " ".join(f"w{i}x{j}" for j in range(9))

        # 9 prompt tokens + 5 emitted: 4 blocks of 4 under a budget of 6
        await eng.generate(prompt(0), max_tokens=6)
        spans, radix = eng.spans_health(), eng._radix.stats()
        assert "sched/radix_evict" not in spans       # no walk ran yet
        assert radix["evicted_blocks"] == 0
        assert spans["sched/radix_insert"]["count"] == 1
        assert spans["sched/radix_insert"]["blocks_total"] == \
            radix["cached_blocks"] == 4
        n = 7
        for i in range(1, n):
            await eng.generate(prompt(i), max_tokens=6)
        await eng.generate(prompt(n - 1), max_tokens=6)   # a re-ask: a hit
        spans, radix = eng.spans_health(), eng._radix.stats()
        walk = spans["sched/radix_evict"]
        assert 0 < walk["count"] <= n
        assert walk["blocks_freed_total"] == radix["evicted_blocks"] > 0
        assert walk["nodes_walked_total"] >= walk["count"]
        match, ins = spans["sched/radix_match"], spans["sched/radix_insert"]
        assert match["count"] == spans["sched/admit"]["count"] == n + 1
        assert match["tokens_total"] == \
            radix["hit_tokens"] + radix["miss_tokens"]
        assert match["matched_total"] == radix["hit_tokens"] > 0
        assert ins["count"] == radix["insertions"] == n + 1
        # a child's time is inside its parent's
        assert walk["total_ms"] <= ins["total_ms"]
        assert match["total_ms"] <= spans["sched/admit"]["total_ms"]
        assert ins["total_ms"] <= (spans["sched/consume"]["total_ms"]
                                   + spans["sched/admit"]["total_ms"])
        assert walk["max_ms"] <= ins["max_ms"]
        ring = list(eng._chunk_log)
        kids = [e for e in ring if e["event"].startswith("radix_")]
        assert {e["event"] for e in kids} == {
            "radix_match", "radix_insert", "radix_evict"}
        for kid in kids:
            assert kid["span"] == f"sched/{kid['event']}"
            assert _nested_in_a_parent(ring, kid), kid
        freed = [e for e in kids if e["event"] == "radix_evict"]
        assert all(e["blocks_freed"] >= 1 and e["nodes_walked"] >= 1
                   for e in freed)
        # the six states still sum to the thread's elapsed time
        sched = spans["sched_thread_s"]
        assert sum(sched[s] for s in SCHED_STATES) == pytest.approx(
            sched["elapsed"], rel=0.02)
    finally:
        await eng.stop()


async def test_starved_seconds_on_the_fake_follow_the_pipe():
    """``sched_starved_s``: nothing is charged while a chunk program is
    in flight; with a pipe one deep every consume and dispatch finds it
    empty with a slot live, and an admission that finds it so is charged
    its whole length; each dispatch's ring entry says how long the pipe
    had stood empty when it was issued."""
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.003)
    eng = _fake(batch_size=2, faults=inj, chunk_pipe_depth=3,
                stream_fn=lambda _p: [9] * 80 + [2])
    await eng.start()
    try:
        seen = []
        async for _ in eng.generate_stream("long runner", max_tokens=60):
            seen.append(eng.spans_health()["sched_starved_s"])
        # the first admission found an idle engine: in hand, pipe empty
        assert seen[0]["admit"] > 0 and seen[0]["dispatch"] > 0
        # from the second chunk to the one before the last the pipe held
        # a chunk all the time: not one more microsecond
        assert len(seen) > 10
        assert seen[2] == seen[-3]
        for snap in seen:
            assert sum(snap[s] for s in SCHED_STATES) == pytest.approx(
                snap["total"], abs=1e-5)
    finally:
        await eng.stop()

    eng = _fake(batch_size=2, faults=inj, chunk_pipe_depth=1,
                stream_fn=lambda _p: [9] * 400 + [2])
    nap = 0.004
    mapped = eng._pool_map_prefix

    def slow_map(*a, **kw):
        time.sleep(nap)                   # an admission that takes a while
        return mapped(*a, **kw)

    eng._pool_map_prefix = slow_map
    await eng.start()
    try:
        runner = asyncio.ensure_future(
            eng.generate("long runner", max_tokens=200))
        await asyncio.sleep(0.05)
        grew = []
        for phase in range(6):
            # ticks alternate dispatch and consume at depth 1: an admission
            # behind a dispatch finds a chunk in flight, one behind a
            # consume finds none; walk both phases
            before = eng.spans_health()["sched_starved_s"]["admit"]
            for _ in range(phase):
                await asyncio.sleep(0)
            await eng.generate(f"late joiner {phase}", max_tokens=2)
            grew.append(
                eng.spans_health()["sched_starved_s"]["admit"] - before)
        spans = eng.spans_health()
        starved, thread = spans["sched_starved_s"], spans["sched_thread_s"]
        # an admission is charged whole or not at all
        assert any(g >= nap * 0.9 for g in grew), grew
        assert all(g >= nap * 0.9 or g < 0.001 for g in grew), grew
        assert starved["consume"] > 0 and starved["dispatch"] > 0
        assert starved["fetch_wait"] == 0.0     # the chunk is in the pipe
        for s in SCHED_STATES:
            assert starved[s] <= thread[s] + 1e-4, s
        assert sum(starved[s] for s in SCHED_STATES) == pytest.approx(
            starved["total"], abs=1e-5)
        disp = [e for e in eng._chunk_log if e["event"] == "dispatch"]
        assert disp and all(e["pipe_empty_ms"] > 0 for e in disp
                            if e["slots"])
        # whatever was charged was charged before some dispatch refilled
        # the pipe: the ring's entries cannot exceed the total
        assert sum(e["pipe_empty_ms"] for e in disp) <= \
            starved["total"] * 1e3 + 0.01
        runner.cancel()
    finally:
        inj.clear()
        await eng.stop()


async def test_drained_seconds_and_unready_chunks_on_the_fake():
    """The fake scheduler goes through the same ``EngineSpans``: its packed
    buffer is the newest launch, on the device until it is fetched. With
    the pipe kept full nothing is drained; once the last chunk is fetched
    everything is, and with no request left the seconds are ``no_work``'s.
    Every ``first_chunk`` carries both counts of the chunks in front."""
    inj = FaultInjector()
    inj.set("chunk", "delay", 0.003)
    eng = _fake(batch_size=2, faults=inj, chunk_pipe_depth=3,
                stream_fn=lambda _p: [9] * 80 + [2])
    await eng.start()
    try:
        seen = []
        async for _ in eng.generate_stream("long runner", max_tokens=60):
            seen.append(eng.spans_health()["sched_drained_s"])
        assert len(seen) > 10
        # from the second chunk to the one before the last a newer chunk
        # was always out: not one more microsecond, seen or unseen
        for key in ("with_work", "unseen", "by_region"):
            assert seen[2][key] == seen[-3][key], key
        traces = [Trace(new_request_id()) for _ in range(3)]

        async def run(t, prompt):
            with use_trace(t):
                return await eng.generate(prompt, max_tokens=24)

        first = asyncio.ensure_future(run(traces[0], "another long runner"))
        await asyncio.sleep(0.05)
        await asyncio.gather(first, run(traces[1], "late joiner"),
                             run(traces[2], "later joiner"))
        metas = [next(s for s in t.to_dict()["spans"]
                      if s["phase"] == "first_chunk")["meta"] for t in traces]
        assert all(0 <= m["chunks_unready"] <= m["chunks_ahead"]
                   for m in metas), metas
        await asyncio.sleep(0.06)          # nothing to run, nothing to do
        spans = eng.spans_health()
        drained, thread = spans["sched_drained_s"], spans["sched_thread_s"]
        _check_drained(drained, thread)
        assert drained["no_work"]["idle"] >= 0.04
        assert drained["no_work"]["total"] > drained["with_work"]["total"]
        assert set(drained["by_region"]) <= {
            "none", "sched/admit", "sched/dispatch", "sched/consume",
            "sched/fetch", "sched/radix_match", "sched/radix_insert",
            "sched/radix_evict"}
        fc = spans["first_chunk"]
        assert fc["chunks_unready_total"] == sum(
            m["chunks_unready"] for m in metas) <= fc["chunks_ahead_total"]
        disp = [e for e in eng._chunk_log if e["event"] == "dispatch"]
        assert disp and all(e["drained_ms"] >= 0 for e in disp)
        assert sum(e["drained_ms"] for e in disp) <= \
            drained["total"] * 1e3 + 0.01
    finally:
        inj.clear()
        await eng.stop()


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


async def test_jax_eager_pieces_are_counted_and_timed_one_for_one():
    """Every eager prefill piece the scheduler thread runs is one
    ``sched/eager_prefill``: its count follows the engine's own pass count,
    its ``tokens_total`` the rows that were prefilled eagerly (prompt less
    what the tree matched less what rode the chunk's window), and the
    jitted call alone is a part of the piece."""
    eng = _toy_jax()
    client = await _client(eng)
    try:
        await _ask(client, ROUTES[1], "warm every shape first")
        before = (await (await client.get("/health")).json())["spans"]
        passes0, rows = eng._counts.eager_passes, 0
        for i in range(3):
            detail = await _ask(client, ROUTES[i % 2], f"list pods of app {i}")
            meta = next(s["meta"] for s in detail["spans"]
                        if s["phase"] == "prefill")
            rows += (meta["prompt_tokens"] - meta["prefix_hit_tokens"]
                     - meta["staged_w"])
        spans = (await (await client.get("/health")).json())["spans"]
        piece, was = spans["sched/eager_prefill"], before["sched/eager_prefill"]
        assert piece["count"] - was["count"] == eng._counts.eager_passes - passes0 > 0
        assert piece["tokens_total"] - was["tokens_total"] == rows > 0
        assert 0 < piece["call_total_ms"] <= piece["total_ms"]
        # the warm-up's pieces ran before the scheduler did: not its children
        assert piece["count"] < eng._counts.eager_passes
        # children are inside sched/admit, and what they leave is its rest
        kids = ("radix_match", "eager_prefill", "arm", "cow")
        inside = sum(spans.get(f"sched/{k}", {}).get("total_ms", 0)
                     for k in kids)
        assert 0 < inside <= spans["sched/admit"]["total_ms"]
        assert spans["sched/arm"]["count"] >= 3
        assert spans["sched/radix_match"]["count"] == \
            spans["sched/admit"]["count"]
        sched = spans["sched_thread_s"]
        assert sum(sched[s] for s in SCHED_STATES) == pytest.approx(
            sched["elapsed"], rel=0.02)
        starved = spans["sched_starved_s"]
        assert sum(starved[s] for s in SCHED_STATES) == pytest.approx(
            starved["total"], abs=1e-5)
        assert 0 < starved["total"] <= sched["elapsed"]
        ring = (await (await client.get("/debug/chunks?limit=500")).json()
                )["events"]
        pieces = [e for e in ring if e["event"] == "eager_prefill"]
        assert pieces and all(
            0 < e["call_ms"] <= e["ms"] and 0 < e["tokens"] <= e["bucket"]
            and _nested_in_a_parent(ring, e) for e in pieces)
        assert all("pipe_empty_ms" in e for e in ring
                   if e["event"] == "dispatch")
    finally:
        await client.close()


async def test_jax_unnamed_launches_have_names_and_every_launch_is_told():
    """The two launches of an admission that had no name: the slice of an
    eager span's last row (``sched/eager_tail``) and the placeholder token
    in front of the arm (``sched/placeholder``), children of the
    ``sched/admit`` of their chunk number. Every launch tells
    ``EngineSpans.launched``, so ``sched_drained_s`` is there, a partition,
    on the real engine; its donated handles never raise; and a
    ``first_chunk`` counts the chunks the device is still at beside the
    ones nobody has fetched."""
    eng = _toy_jax()
    client = await _client(eng)
    try:
        await _ask(client, ROUTES[1], "warm every shape first")
        before = (await (await client.get("/health")).json())["spans"]
        metas = []
        for i in range(3):
            detail = await _ask(client, ROUTES[i % 2], f"list pods of app {i}")
            metas.append(next(s["meta"] for s in detail["spans"]
                              if s["phase"] == "first_chunk"))
        await asyncio.sleep(0.1)           # an idle engine in the books
        spans = (await (await client.get("/health")).json())["spans"]
        assert all(0 <= m["chunks_unready"] <= m["chunks_ahead"]
                   for m in metas), metas
        fc = spans["first_chunk"]
        assert fc["chunks_unready_total"] <= fc["chunks_ahead_total"]
        grew = {name: spans[name]["count"] - before.get(name, {}).get(
            "count", 0) for name in ("sched/eager_tail", "sched/placeholder",
                                     "sched/arm", "sched/admit")}
        # one placeholder an arm (every admission stages a window), one
        # tail an eager span
        assert grew["sched/placeholder"] == grew["sched/arm"] == 3
        assert 1 <= grew["sched/eager_tail"] <= \
            spans["sched/eager_prefill"]["count"]
        ring = (await (await client.get("/debug/chunks?limit=500")).json()
                )["events"]
        for name in ("eager_tail", "placeholder"):
            kids = [e for e in ring if e["event"] == name]
            assert kids and all(
                e["span"] == f"sched/{name}" and _nested_in_a_parent(ring, e)
                and e["chunk"] >= 1 for e in kids), name
            admits = [p for p in ring if p["event"] == "admit"]
            assert all(any(p["t0"] <= e["t0"] and e["t1"] <= p["t1"]
                           and p["chunk"] == e["chunk"] for p in admits)
                       for e in kids), name
        # what sched/admit's children leave unnamed
        kids = ("radix_match", "eager_prefill", "eager_tail", "placeholder",
                "arm", "cow")
        inside = sum(spans.get(f"sched/{k}", {}).get("total_ms", 0)
                     for k in kids)
        assert 0 < inside <= spans["sched/admit"]["total_ms"]
        drained, thread = spans["sched_drained_s"], spans["sched_thread_s"]
        _check_drained(drained, thread)
        assert drained["no_work"]["idle"] > 0.05
        assert drained["total"] > 0        # the CPU "device" is quick
        assert set(drained["by_region"]) <= {"none"} | {
            n for n in spans if n.startswith("sched/")}
        disp = [e for e in ring if e["event"] == "dispatch"]
        assert disp and all("drained_ms" in e and "pipe_empty_ms" in e
                            for e in disp)
    finally:
        await client.close()


@pytest.mark.parametrize("python_tracer", [False, True])
async def test_profile_capture_holds_sched_annotations_with_chunk_numbers(
        python_tracer):
    """A /debug/profile capture on the CPU backend holds the scheduler's
    spans as TraceAnnotations with a ``chunk`` stat, on the trace's own
    clock; the summary carries the two clock pairs. The child regions are
    there too, on the scheduler thread's line, nested inside the
    ``sched/admit`` (or ``sched/consume``) that ran them, under its chunk
    number; and the response says what ``/health.spans`` grew by between
    the two stamps, the scheduler thread's three partitions among it. By
    default the capture holds NO frame of the profiler's Python tracer
    (``$file:line function``), which hooked every call of the scheduler
    thread; ``python_tracer=1`` brings them back, and the answer says
    which it was."""
    import glob

    import jax

    client = await _client(_toy_jax())
    try:
        await _ask(client, ROUTES[1], "warm every shape first")
        prof = asyncio.ensure_future(client.post(
            "/debug/profile?seconds=1.0"
            + ("&python_tracer=1" if python_tracer else "")))
        await asyncio.sleep(0.2)
        await _ask(client, ROUTES[1], "list pods while the capture runs")
        body = await (await prof).json()
        assert body["python_tracer"] is python_tracer
        (m0, w0), (m1, w1) = body["clock_start"], body["clock_stop"]
        assert m1 - m0 == pytest.approx((w1 - w0) / 1e9, abs=0.05)
        assert m1 - m0 >= 1.0
        path = glob.glob(body["trace_dir"] + "/plugins/profile/*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(path[0])
        seen, lines, frames = {}, {}, 0
        for plane in data.planes:
            for line in plane.lines:
                for ev in line.events:
                    frames += ev.name.startswith("$")
                    if ev.name.startswith("sched/"):
                        chunk = dict(ev.stats).get("chunk")
                        seen.setdefault(ev.name, []).append(
                            (chunk, ev.duration_ns))
                        lines.setdefault((plane.name, line.name), []).append(
                            (ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns, chunk))
        assert {"sched/dispatch", "sched/fetch", "sched/consume",
                "sched/admit"} <= set(seen), sorted(seen)
        assert (frames > 100) if python_tracer else (frames == 0), frames
        for name in ("sched/dispatch", "sched/fetch"):
            chunks = [c for c, _ in seen[name] if c is not None]
            assert chunks and all(int(c) >= 1 for c in chunks), seen[name]
        # the same chunk is dispatched, then fetched
        assert {c for c, _ in seen["sched/fetch"] if c is not None} & \
            {c for c, _ in seen["sched/dispatch"]}
        # every region is on ONE line, the scheduler thread's, and a child
        # lies inside the parent that ran it, under the parent's chunk
        assert len(lines) == 1, sorted(lines)
        (events,) = lines.values()
        parents = {"sched/radix_match": "sched/admit",
                   "sched/eager_prefill": "sched/admit",
                   "sched/eager_tail": "sched/admit",
                   "sched/placeholder": "sched/admit",
                   "sched/arm": "sched/admit",
                   "sched/radix_insert": "sched/consume"}
        assert set(parents) <= set(seen), sorted(seen)
        for name, t0, t1, chunk in events:
            if name in parents:
                assert any(p == parents[name] and p0 <= t0 and t1 <= p1
                           and pc == chunk
                           for p, p0, p1, pc in events), (name, chunk)
        # what /health.spans grew by between clock_start and clock_stop:
        # the same regions, counted on the host's clock inside the capture
        grew = body["spans"]
        for name in parents:
            assert grew[name]["count"] == len(seen[name]), name
            assert grew[name]["total_ms"] > 0
        assert grew["sched/eager_prefill"]["tokens_total"] > 0
        assert grew["sched_thread_s"]["elapsed"] == pytest.approx(
            m1 - m0, abs=0.1)
        assert grew["decode"]["count"] == 1
        # the three partitions over the capture's own interval: the
        # program's account of the device's idle seconds beside the trace's
        assert sum(grew["sched_thread_s"][s] for s in SCHED_STATES) == \
            pytest.approx(m1 - m0, abs=0.1)
        assert 0 < grew["sched_starved_s"]["total"] <= m1 - m0 + 0.1
        _check_drained(grew["sched_drained_s"], grew["sched_thread_s"])
        assert grew["sched_drained_s"]["no_work"]["total"] > 0.2
    finally:
        await client.close()
