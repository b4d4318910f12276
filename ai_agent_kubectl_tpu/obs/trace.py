"""Request-lifecycle trace context: request ID + span API.

One ``Trace`` per HTTP request, created by the observability middleware
and finished when the response (or exception) leaves it. Spans carry
``time.monotonic()`` begin/end stamps relative to nothing — offsets are
computed against the trace's own t0 at serialization time, so clock
adjustments can never skew a timeline. Every span has an ``id`` and a
``parent`` (the id of the span that caused it, ``None`` at the top level):
the top level partitions the request's wall time, and a parent's self
time is its duration less its children's. Events are point-in-time
annotations ("admitted to slot 3", "breaker opened") recorded from
wherever the trace travels, including the batch scheduler thread — all
mutation goes through one lock.

Propagation is two-legged:

- **async leg** (middleware, cache, breaker, engine submit, executor):
  the ``ContextVar`` below. asyncio copies the context into every task,
  so ``current_trace()`` works anywhere downstream of the middleware on
  the event loop.
- **thread leg** (batch scheduler): ContextVars do not cross threads, so
  the engine's submit path captures ``current_trace()`` into the queued
  request object and the scheduler annotates that reference directly.

The engine's side of the timeline is stamped where the work happens:
``RequestSpans`` (one per queued request) writes the engine phases as the
scheduler crosses each boundary, ``SchedSpans`` records the scheduler
thread's own per-chunk intervals, their named parts (the radix walk, an
eager prefill piece), the time the chunk pipe stood empty and the time the
device had nothing at all to run into the ``/debug/chunks`` ring and
``/health.spans``, and
``SpanStats`` keeps the cumulative per-name totals ``/health.spans``
serves. Both engines with a scheduler (engine/batcher.py, engine/fake.py)
hold the three in one ``EngineSpans``, so the names cannot drift apart.
"""

from __future__ import annotations

import contextvars
import re
import threading
import time
import uuid
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Deque, Dict, Iterable, List, Optional, Tuple

#: phase names admitted into the ``request_phase_seconds`` histogram.
#: A fixed allowlist, NOT whatever span names show up — a bug (or a
#: hostile client header echoed into a span) must never mint unbounded
#: Prometheus label values.
PHASES = (
    "validate",      # body parse + pydantic + sanitation
    "queue_wait",    # submit → admission into a decode slot
    "prefill",       # admission → first token consumed (children below)
    "admit_host",    # prefill child: host admission work until staged/armed
    "stage_wait",    # prefill child: staged → the carrying chunk is issued
    "first_chunk",   # prefill child: that dispatch → its buffer is fetched
    "decode",        # token generation
    "detokenize",    # token → text + engine/event-loop handoff
    "safety",        # output parsing + safety validation
    "execute",       # kubectl subprocess run (/execute)
    "cache",         # response-cache lookup serving a hit
    "fallback",      # rule-based degraded generation
    "respond",       # response model build + serialization
)

_RID_RE = re.compile(r"^[A-Za-z0-9._-]{1,64}$")


def new_request_id() -> str:
    """16 hex chars — short enough to quote in a bug report, random
    enough that collisions inside one flight-recorder window are moot."""
    return uuid.uuid4().hex[:16]


def sanitize_request_id(raw: Optional[str]) -> Optional[str]:
    """Echo a client-supplied X-Request-ID only when it is boringly safe:
    ≤64 chars of [A-Za-z0-9._-]. Anything else (header injection, log
    forging, 4 KB of junk) is discarded and a fresh ID is minted."""
    if raw and _RID_RE.match(raw):
        return raw
    return None


class Span:
    """One named interval inside a trace. ``t0``/``t1`` are raw
    ``time.monotonic()`` stamps; offsets are derived at read time.
    ``id`` is unique within the trace; ``parent`` is the id of the span
    this one is a part of, or ``None`` at the top level."""

    __slots__ = ("id", "parent", "name", "t0", "t1", "meta")

    def __init__(self, span_id: int, name: str, t0: float, t1: float,
                 parent: Optional[int] = None,
                 meta: Optional[Dict[str, Any]] = None):
        self.id = span_id
        self.parent = parent
        self.name = name
        self.t0 = t0
        self.t1 = max(t1, t0)
        self.meta = meta or {}

    @property
    def duration_ms(self) -> float:
        return (self.t1 - self.t0) * 1000.0


class Trace:
    """Span timeline + event log for one request."""

    def __init__(self, request_id: str, method: str = "", path: str = ""):
        self.request_id = request_id
        self.method = method
        self.path = path
        self.t0 = time.monotonic()
        self.wall_start = time.time()
        self.status: Optional[int] = None
        self.error: Optional[str] = None
        # outcome flags the flight recorder filters/surfaces on
        self.shed = False
        self.degraded = False
        self.from_cache = False
        self._t_end: Optional[float] = None
        self._spans: List[Span] = []
        self._events: List[tuple] = []
        self._links: List[tuple] = []
        self._lock = threading.Lock()

    # ------------------------------------------------------------ recording

    @contextmanager
    def span(self, name: str, **meta):
        t0 = time.monotonic()
        try:
            yield self
        finally:
            self.add_span(name, t0, time.monotonic(), **meta)

    def add_span(self, name: str, t0: float, t1: float,
                 parent: Optional[int] = None, **meta) -> int:
        """Record an interval from explicit monotonic stamps (the
        scheduler thread stamps boundaries as it crosses them and writes
        the span when it closes); returns the span's id, which a child
        names as its ``parent``. Safe from any thread."""
        with self._lock:
            span_id = len(self._spans) + 1
            self._spans.append(
                Span(span_id, name, t0, t1, parent, meta or None))
        return span_id

    def event(self, message: str, **meta) -> None:
        """Point-in-time annotation; safe from any thread."""
        with self._lock:
            self._events.append((time.monotonic(), message, meta or None))

    def link(self, link_type: str, **meta) -> None:
        """Causal span link: a handoff where this request's execution
        moved — preempted out of a slot, migrated off a replica, raced
        on a hedge branch, a loser branch cancelled. Links are what
        stitch ONE timeline out of a request that crossed scheduler
        boundaries: every engine annotates the same Trace object (same
        process, same monotonic clock, so offsets reconcile for free),
        and the links name which segment each stretch of events belongs
        to — including branches that lost and would otherwise vanish.
        Safe from any thread, like ``event``."""
        with self._lock:
            self._links.append((time.monotonic(), link_type, meta or None))

    def finish(self, status: Optional[int] = None,
               error: Optional[str] = None) -> None:
        if status is not None:
            self.status = status
        if error is not None:
            self.error = error
        self._t_end = time.monotonic()

    # -------------------------------------------------------------- reading

    @property
    def duration_ms(self) -> float:
        end = self._t_end if self._t_end is not None else time.monotonic()
        return (end - self.t0) * 1000.0

    def phase_durations(self, children: bool = False) -> Dict[str, float]:
        """name → total ms (same-named spans merged), insertion-ordered.
        Top-level spans only unless ``children``: the top level is what
        sums to the wall time (Server-Timing, ``timings``); the phase
        histogram wants every span."""
        out: Dict[str, float] = {}
        with self._lock:
            for s in self._spans:
                if children or s.parent is None:
                    out[s.name] = out.get(s.name, 0.0) + s.duration_ms
        return out

    def server_timing(self) -> str:
        """RFC 8941 Server-Timing value: ``queue_wait;dur=1.2, ...``.
        Span names are from code (never client input), so no escaping."""
        return ", ".join(
            f"{name};dur={dur:.2f}"
            for name, dur in self.phase_durations().items()
        )

    def summary(self) -> Dict[str, Any]:
        return {
            "request_id": self.request_id,
            "method": self.method,
            "path": self.path,
            "status": self.status,
            "duration_ms": round(self.duration_ms, 3),
            "shed": self.shed,
            "degraded": self.degraded,
            "from_cache": self.from_cache,
            "error": self.error,
            "start_time": self.wall_start,
        }

    def to_dict(self) -> Dict[str, Any]:
        """Full timeline — what /debug/requests/{id} serves. Offsets are
        milliseconds from request start."""
        with self._lock:
            spans = [
                {
                    "id": s.id,
                    "parent": s.parent,
                    "phase": s.name,
                    "start_ms": round((s.t0 - self.t0) * 1000.0, 3),
                    "end_ms": round((s.t1 - self.t0) * 1000.0, 3),
                    "duration_ms": round(s.duration_ms, 3),
                    **({"meta": s.meta} if s.meta else {}),
                }
                for s in sorted(self._spans, key=lambda s: s.t0)
            ]
            events = [
                {
                    "offset_ms": round((t - self.t0) * 1000.0, 3),
                    "message": msg,
                    **({"meta": meta} if meta else {}),
                }
                for t, msg, meta in self._events
            ]
            links = [
                {
                    "offset_ms": round((t - self.t0) * 1000.0, 3),
                    "type": link_type,
                    **({"meta": meta} if meta else {}),
                }
                for t, link_type, meta in self._links
            ]
        d = self.summary()
        d["spans"] = spans
        d["events"] = events
        d["links"] = links
        return d


# ------------------------------------------------------ engine-side spans

class SpanStats:
    """Cumulative ``{count, total_ms, max_ms}`` per span name since start —
    plain counters behind one lock, updated when a span closes. What
    ``/health.spans`` serves, so a benchmark can difference two probes
    into means without a new endpoint. Extra running totals ride an entry
    by keyword (``queue_wait``'s ``slot_wait_total_ms``)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._by_name: Dict[str, Dict[str, float]] = {}

    def note_all(self, entries: Iterable[Tuple[str, float, Dict[str, float]]]
                 ) -> None:
        """``(name, ms, extra totals)`` for spans that closed together,
        under ONE lock hold — a reader never sees a parent without the
        children that were written with it."""
        with self._lock:
            for name, ms, totals in entries:
                e = self._by_name.get(name)
                if e is None:
                    e = self._by_name[name] = {
                        "count": 0, "total_ms": 0.0, "max_ms": 0.0}
                e["count"] += 1
                e["total_ms"] += ms
                e["max_ms"] = max(e["max_ms"], ms)
                for k, v in totals.items():
                    e[k] = e.get(k, 0) + v

    def note(self, name: str, ms: float, **totals: float) -> None:
        self.note_all([(name, ms, totals)])

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {name: {k: round(v, 3) for k, v in e.items()}
                    for name, e in self._by_name.items()}


def _grown(entry: Dict[str, Any], was: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in entry.items():
        if isinstance(v, dict):
            out[k] = _grown(v, was.get(k) or {})
        elif k != "max_ms":
            out[k] = round(v - was.get(k, 0), 6)
    if entry.get("max_ms", 0) > was.get("max_ms", 0):
        out["max_ms"] = entry["max_ms"]
    return out


def spans_growth(before: Optional[Dict[str, Dict[str, Any]]],
                 after: Optional[Dict[str, Dict[str, Any]]]
                 ) -> Optional[Dict[str, Dict[str, Any]]]:
    """What two ``/health.spans`` sections (``EngineSpans.health``) differ
    by, entry by entry: counts, totals and seconds grow (the parts of
    ``sched_drained_s`` one level further down); ``max_ms`` is kept only
    where it grew, since a larger one was set inside the interval."""
    if not after:
        return None
    return {name: _grown(entry, (before or {}).get(name) or {})
            for name, entry in after.items()}


class RequestSpans:
    """One request's engine phases, stamped by the scheduler at the moment
    each boundary is crossed and written (to the request's ``Trace``, if
    it has one, and to the engine's ``SpanStats``, if it keeps one) when
    the phase closes:

    ``queue_wait`` → ``prefill`` (children ``admit_host``, ``stage_wait``,
    ``first_chunk``) → ``decode`` → ``detokenize``

    Every method is a no-op outside the phase it closes, so replays,
    races and double finishes cannot write a phase twice. A preempted
    request is ``requeued`` and walks a second ``queue_wait``/``prefill``
    pair rather than stretching the first."""

    __slots__ = ("trace", "stats", "phase", "t_mark", "t_staged",
                 "prefill_meta", "admit_meta", "t_disp", "chunk_meta",
                 "chunks")

    def __init__(self, trace: Optional[Trace], stats: Optional[SpanStats],
                 t_submit: float):
        self.trace = trace
        self.stats = stats
        self.phase = "queue_wait"
        self.t_mark = t_submit       # start of the open phase
        self.t_staged: Optional[float] = None
        self.t_disp: Optional[float] = None
        self.prefill_meta: Dict[str, Any] = {}
        self.admit_meta: Dict[str, Any] = {}
        self.chunk_meta: Dict[str, Any] = {}
        self.chunks = 0

    def _write(self, *spans: tuple) -> None:
        """``(name, t0, t1, totals, meta)``: one span, or a parent and
        then its children, written (and counted) together."""
        if self.stats is not None:
            self.stats.note_all(
                [(name, max(t1 - t0, 0.0) * 1000.0, totals)
                 for name, t0, t1, totals, _ in spans])
        if self.trace is None:
            return
        parent = None
        for name, t0, t1, _, meta in spans:
            sid = self.trace.add_span(name, t0, t1, parent=parent, **meta)
            parent = parent or sid

    def admitted(self, t_adm: float, slot_free_since: Optional[float]
                 ) -> None:
        """Popped off the queue into a slot. ``slot_free_since`` is when
        the scheduler last went from no free slot to one (``None`` = it
        never had none): the wait before it was a wait for a slot, the
        remainder is time the scheduler was elsewhere."""
        if self.phase != "queue_wait":
            return
        wait = max(t_adm - self.t_mark, 0.0)
        slot_wait = (0.0 if slot_free_since is None
                     else min(max(slot_free_since - self.t_mark, 0.0), wait))
        self._write(("queue_wait", self.t_mark, t_adm,
                     {"slot_wait_total_ms": slot_wait * 1000.0},
                     {"slot_wait_ms": round(slot_wait * 1000.0, 3)}))
        self.phase, self.t_mark = "prefill", t_adm
        self.t_staged = self.t_disp = None
        self.prefill_meta, self.admit_meta, self.chunk_meta = {}, {}, {}

    def staged(self, t: float, chunks_ahead: Optional[int] = None,
               chunks_unready: Optional[int] = None,
               prefill: Optional[Dict[str, Any]] = None, **meta) -> None:
        """The admission's host work is done: its window is staged for
        the next chunk (``dispatched`` follows), or its own admission
        program is issued behind ``chunks_ahead`` decode chunks — then
        the first token comes from that program and nothing waits for a
        dispatch. ``prefill`` is the parent span's meta, ``meta``
        ``admit_host``'s."""
        if self.phase == "prefill" and self.t_staged is None:
            self.t_staged, self.admit_meta = t, meta
            self.prefill_meta = prefill or {}
            if chunks_ahead is not None:
                self.chunk_meta = _ahead(chunks_ahead, chunks_unready)

    def dispatched(self, t: float, chunk: int, chunks_ahead: int,
                   chunks_unready: Optional[int] = None, **meta) -> None:
        """The chunk that carries the staged window is issued, behind
        ``chunks_ahead`` decode chunks nobody has fetched yet, of which
        the device is still at work on ``chunks_unready``
        (``EngineSpans.pipe_chunks``): what the window really waits for."""
        if self.phase == "prefill" and self.t_disp is None:
            self.t_disp = t
            self.chunk_meta = dict(meta, chunk=chunk,
                                   **_ahead(chunks_ahead, chunks_unready))

    def first_token(self, t: float) -> None:
        if self.phase != "prefill":
            return
        t_adm = self.t_mark
        t_staged = min(self.t_staged if self.t_staged is not None else t, t)
        spans = [("prefill", t_adm, t, {}, self.prefill_meta),
                 ("admit_host", t_adm, t_staged, {}, self.admit_meta)]
        t_chunk = t_staged
        if self.t_disp is not None:     # rode a chunk: it waited for one
            t_chunk = min(max(self.t_disp, t_staged), t)
            spans.append(("stage_wait", t_staged, t_chunk, {}, {}))
        spans.append(("first_chunk", t_chunk, t,
                      {f"{k}_total": self.chunk_meta.get(k, 0)
                       for k in ("chunks_ahead", "chunks_unready")},
                      self.chunk_meta))
        self._write(*spans)
        self.phase, self.t_mark, self.chunks = "decode", t, 0

    def chunk_consumed(self) -> None:
        if self.phase == "decode":
            self.chunks += 1

    def finished(self, t: float, **meta) -> None:
        """The request's last consume: closes whatever is open (a request
        that ends before its first token closes ``prefill`` there) and
        leaves ``detokenize`` open for the handler to close."""
        if self.phase == "prefill":
            self.first_token(t)
        if self.phase != "decode":
            return
        self._write(("decode", self.t_mark, t, {},
                     dict(meta, chunks=self.chunks)))
        self.phase, self.t_mark = "detokenize", t

    def resumed(self, t: float, **meta) -> None:
        """The handler's coroutine picked the result up."""
        if self.phase != "detokenize":
            return
        self._write(("detokenize", self.t_mark, t, {}, meta))
        self.phase = "done"

    def whole_call(self, t_end: float, **meta) -> None:
        """An engine that is one opaque call (a remote API, the rule
        table) has no boundaries of its own to stamp: everything between
        submit and its return is ``decode``, the rest is empty."""
        t0 = self.t_mark
        self.admitted(t0, None)
        self.staged(t0)
        self.first_token(t0)
        self.finished(t_end, **meta)
        self.resumed(time.monotonic())

    def requeued(self, t: float) -> None:
        """Preempted out of its slot: the open phase ends here and a new
        ``queue_wait`` starts."""
        self.finished(t, preempted=True)
        self.phase, self.t_mark = "queue_wait", t


def _ahead(chunks_ahead: int, chunks_unready: Optional[int]) -> Dict[str, int]:
    """``first_chunk``'s two counts of the chunks in front of it; a caller
    that asks no buffer (one opaque program) has them equal."""
    return {"chunks_ahead": chunks_ahead,
            "chunks_unready": (chunks_ahead if chunks_unready is None
                               else chunks_unready)}


#: the states that partition the scheduler thread's wall time
SCHED_STATES = ("admit", "dispatch", "fetch_wait", "consume", "idle",
                "other")


class SchedSpans:
    """The scheduler thread's own spans. One ring (the ``/debug/chunks``
    log the engine already keeps), one clock (``time.monotonic()``, with
    ``time.time()`` beside it for readers that join on the wall clock).

    ``region(state, name, chunk)`` marks an interval: the thread's wall
    time is charged to ``state`` for its length (a cumulative partition
    over ``SCHED_STATES`` whose parts sum to the thread's elapsed time;
    whatever no region covers is ``other``), and a named region is also
    appended to the ring as ``sched/<name>``, counted into ``SpanStats``,
    and wrapped in ``annotate("sched/<name>", chunk=n)`` — the engine
    passes ``jax.profiler.TraceAnnotation`` there, so a ``/debug/profile``
    capture holds the same spans on the scheduler thread's line, on the
    device ops' clock; a no-op outside a capture. This module stays
    jax-free.

    ``child(name)`` names a part of whatever region runs it (the radix
    walk inside ``sched/admit``): the same ring entry, totals and
    annotation, under the enclosing region's chunk number, but the time
    stays charged to the enclosing state, so the partition is untouched
    and a parent's ``total_ms`` less its children's is the host work that
    still has no name. Only while a scheduler runs: the start-up warm-up
    calls the same code from another thread and is nobody's child.

    Beside the partition the thread keeps two more, with no profiler
    attached and in every run. Both learn what the device has done only
    where the thread looks: ``look`` (the scheduler's loop, a dispatch,
    after a fetch, the end of every child region) asks ``probe``.

    ``starved``: the same seconds by state, counted only while the chunk
    pipe holds no chunk program and a slot is live (``note_live``) or an
    admission is in hand (state ``admit``). The host's view of a drained
    pipe, and an upper bound on idle: an admission's eager pieces, state
    copies and arm run on the device meanwhile.

    ``drained``: the seconds while the NEWEST program the thread launched,
    of any kind, is done (``launched`` tells of each; the device runs one
    stream in launch order, so it then has nothing to run), split by
    whether there was work at hand (``with_work``: what a host-side change
    can win) or not (``no_work``: the traffic is too light), the former
    also by the innermost open named region (``by_region``, ``none`` where
    no region is open). A stretch is counted from the first look that
    finds the device done; what lies between the last look that saw it
    busy and that one, less the thread's own waits for the device
    (``fetch_wait``: it was busy, or the wait was over at once), is
    ``unseen`` (from the thread's start on: a launch of the warm-up is
    nobody's until a scheduler runs). So ``total`` is a lower bound of the device's idle time
    with work at hand and ``total + unseen`` an upper one, but for what
    the thread cannot see: a program queued behind its own host-to-device
    copy, and a buffer's way back to the host."""

    def __init__(self, stats: SpanStats, log: Deque[dict],
                 annotate: Optional[Callable[..., Any]] = None):
        self._stats = stats
        self._log = log
        self._annotate = annotate
        self._lock = threading.Lock()   # the readers are other threads
        # every second the thread has charged, ``_charge``'s to add to
        self._acc: Dict[str, Dict[str, float]] = {
            table: dict.fromkeys(SCHED_STATES, 0.0)
            for table in ("state", "starved", "with_work", "no_work")}
        self._acc["by_region"] = {}
        self._state: Optional[str] = None
        self._t_state = 0.0
        self._elapsed = 0.0             # of scheduler threads that ended
        self._t_start = 0.0
        # of the innermost open region: its chunk number, and the name of
        # the innermost NAMED one
        self._chunk: Optional[int] = None
        self._region: Optional[str] = None
        self._pipe_chunks = 0           # chunk programs in flight
        self._live = False              # any slot seated
        self._starved_at_empty = 0.0    # starved total when the pipe drained
        self._drained = True            # nothing launched yet: nothing to run
        self._t_busy = 0.0              # the last look that saw it busy
        self._fetch_s_at_busy = 0.0     # ... and fetch_wait's seconds then
        self._unseen_s = 0.0
        self._drained_at_mark = 0.0     # with_work total at ``drained_ms``
        # ``EngineSpans`` sets it: (chunk programs the device is not done
        # with, is it done with the newest launch). No call site marks the
        # moment the DEVICE ends a program, so the thread asks.
        self.probe: Optional[Callable[[], Tuple[int, bool]]] = None

    def _has_work(self) -> bool:
        return self._live or self._state == "admit"

    def _charge(self, acc: Dict[str, Dict[str, float]], dt: float) -> None:
        """``dt`` more seconds of the open state, into every table they
        belong to."""
        state = self._state
        acc["state"][state] += dt
        work = self._has_work()
        if work and self._pipe_chunks == 0:
            acc["starved"][state] += dt
        if self._drained:
            acc["with_work" if work else "no_work"][state] += dt
            if work:
                name = self._region or "none"
                acc["by_region"][name] = acc["by_region"].get(name, 0.0) + dt

    def _settle(self, now: float) -> None:
        """Charge the open state up to ``now`` (caller holds the lock)."""
        if self._state is not None:
            self._charge(self._acc, now - self._t_state)
            self._t_state = now

    def sections(self) -> Dict[str, Dict[str, Any]]:
        """The three partitions as ``/health.spans`` serves them, from ONE
        read of the tables with the open state charged up to now.
        ``sched_thread_s``: seconds per state since the first start(), and
        their sum's independent check ``elapsed`` (thread start → now).
        ``sched_starved_s``: the part of each spent with the pipe empty and
        work at hand, and ``total``, their sum. ``sched_drained_s``: the
        part of each spent with the newest launch done, with work at hand
        and without, each with its ``total``; the section's ``total`` is
        ``with_work``'s, ``by_region`` its split by the innermost open
        named region, ``unseen`` what no look saw (see the class)."""
        now = time.monotonic()
        with self._lock:
            acc = {k: dict(v) for k, v in self._acc.items()}
            elapsed, unseen = self._elapsed, self._unseen_s
            if self._state is not None:
                self._charge(acc, now - self._t_state)
                elapsed += now - self._t_start
        drained: Dict[str, Any] = {k: _with_total(acc[k])
                                   for k in ("with_work", "no_work")}
        drained.update(total=drained["with_work"]["total"],
                       unseen=round(unseen, 6),
                       by_region=_rounded(acc["by_region"]))
        return {"sched_thread_s": dict(_rounded(acc["state"]),
                                       elapsed=round(elapsed, 6)),
                "sched_starved_s": _with_total(acc["starved"]),
                "sched_drained_s": drained}

    def _switch(self, state: Optional[str], now: float) -> Optional[str]:
        with self._lock:
            prev = self._state
            self._settle(now)
            self._state, self._t_state = state, now
        return prev

    def start(self) -> None:
        """The scheduler thread (or task) begins."""
        now = time.monotonic()
        if self._switch("other", now) is None:
            self._t_start = now

    def stop(self) -> None:
        now = time.monotonic()
        if self._switch(None, now) is not None:
            with self._lock:
                self._elapsed += now - self._t_start

    def note_live(self, live: bool) -> None:
        """Whether any slot is seated (``EngineSpans.note_slots``)."""
        if live != self._live:
            with self._lock:
                self._settle(time.monotonic())
                self._live = live

    def _note_device(self, done: bool, now: float) -> None:
        """What a look found of the newest launch (lock held, settled)."""
        if not done:
            self._drained, self._t_busy = False, now
            self._fetch_s_at_busy = self._acc["state"]["fetch_wait"]
        elif not self._drained:
            self._drained = True
            if self._state is None:     # the warm-up's: nobody's seconds
                return
            waited = self._acc["state"]["fetch_wait"] - self._fetch_s_at_busy
            since = max(self._t_busy, self._t_start)
            self._unseen_s += max(now - since - waited, 0.0)

    def note_pipe(self, chunks: int, done: Optional[bool] = None) -> float:
        """One look: the number of chunk programs in flight and whether
        the device is done with the newest launch (None: not asked).
        Returns, when the pipe goes from empty to holding one, the ms it
        stood empty with work at hand (the growth of the starved total
        since it drained), else 0."""
        now = time.monotonic()
        with self._lock:
            self._settle(now)
            if done is not None:
                self._note_device(done, now)
            was, self._pipe_chunks = self._pipe_chunks, chunks
            if (chunks == 0) == (was == 0):
                return 0.0
            total = sum(self._acc["starved"].values())
            if chunks == 0:
                self._starved_at_empty = total
                return 0.0
            return (total - self._starved_at_empty) * 1000.0

    def look(self) -> float:
        """Ask ``probe`` and note its answer (``note_pipe``)."""
        return self.note_pipe(*self.probe()) if self.probe is not None else 0.0

    def note_launch(self, prev_done: bool) -> None:
        """A device program is out. ``prev_done``: the device was done
        with the launch before it, so it had stood with nothing to run
        for a stretch of which no look saw the end: ``unseen``, whole."""
        now = time.monotonic()
        with self._lock:
            self._settle(now)
            if prev_done:
                self._note_device(True, now)
            self._note_device(False, now)

    def drained_ms(self) -> float:
        """The ms the device had nothing to run with work at hand since
        the last call: a dispatch's ``drained_ms``, one chunk period's."""
        with self._lock:
            self._settle(time.monotonic())
            total = sum(self._acc["with_work"].values())
            was, self._drained_at_mark = self._drained_at_mark, total
        return (total - was) * 1000.0

    @contextmanager
    def region(self, state: Optional[str], name: Optional[str] = None,
               chunk: Optional[int] = None, totals: Iterable[str] = (),
               **fields):
        """Yields the ring entry's field dict: the caller adds what it
        learns inside the interval (``n_alive`` after a fetch) and, once
        the region has closed, reads its length back as ``["ms"]``.
        ``totals`` names fields that also run as totals beside
        ``total_ms`` in ``SpanStats``: ``tokens`` as ``tokens_total``,
        ``call_ms`` as ``call_total_ms``. ``state`` None (``child``)
        keeps the enclosing state."""
        t0, wall0 = time.monotonic(), time.time()
        if state is not None:
            prev = self._switch(state, t0)
        else:       # what ran so far is the enclosing region's
            with self._lock:
                self._settle(t0)
        outer = self._chunk, self._region
        self._chunk = chunk
        if name is not None:
            self._region = f"sched/{name}"
        try:
            if name is not None and self._annotate is not None:
                stat = {} if chunk is None else {"chunk": chunk}
                with self._annotate(f"sched/{name}", **stat):
                    yield fields
            else:
                yield fields
        finally:
            t1 = time.monotonic()
            if state is not None:
                # Back to the enclosing state — but only while a scheduler
                # is running: a region entered before start() charges
                # nothing.
                self._switch(prev, t1)
            else:
                self.look()     # an admission is many children
            self._chunk, self._region = outer
            fields["ms"] = (t1 - t0) * 1000.0
            if name is not None:
                self._stats.note(f"sched/{name}", fields["ms"], **{
                    (k[:-3] + "_total_ms" if k.endswith("_ms")
                     else k + "_total"): fields.get(k, 0) for k in totals})
                self._log.append({
                    "t": wall0, "t0": t0, "t1": t1, "event": name,
                    "span": f"sched/{name}", "chunk": chunk, **fields})

    def child(self, name: str, totals: Iterable[str] = (), **fields):
        """A named part of the region that runs it (see the class)."""
        if self._state is None:
            return nullcontext(fields)
        return self.region(None, name, chunk=self._chunk, totals=totals,
                           **fields)

    def mark(self, event: str, **fields) -> None:
        """A point in the ring (prune, health trip): no interval."""
        self._log.append({"t": time.time(), "t0": time.monotonic(),
                          "event": event, **fields})

    def snapshot(self) -> Dict[str, float]:
        return self.sections()["sched_thread_s"]

    def starved(self) -> Dict[str, float]:
        return self.sections()["sched_starved_s"]

    def drained(self) -> Dict[str, Any]:
        return self.sections()["sched_drained_s"]


def _rounded(parts: Dict[str, float]) -> Dict[str, float]:
    return {k: round(v, 6) for k, v in parts.items()}


def _with_total(parts: Dict[str, float]) -> Dict[str, float]:
    return dict(_rounded(parts), total=round(sum(parts.values()), 6))


def _on_device(buf: Any) -> bool:
    """Is the device still at work on this dispatched buffer? A
    ``jax.Array`` says so itself, without blocking; the fake engine's
    buffer is numpy, done when the scheduler fetches it."""
    ready = getattr(buf, "is_ready", None)
    return ready is None or not ready()


class EngineSpans:
    """Everything an engine with a scheduler keeps for its spans, so the
    batcher and its fake twin cannot drift: the totals (``stats``), the
    scheduler thread's spans over the engine's chunk ring (``sched``),
    ``slot_free_since`` — when the free-slot count last left 0 (``None``
    while no slot is free), the stamp ``queue_wait`` splits on — and what
    ``sched`` asks at every look: the engine's in-flight queue and the
    newest launch's handle."""

    def __init__(self, log: Deque[dict],
                 annotate: Optional[Callable[..., Any]] = None):
        self.stats = SpanStats()
        self.sched = SchedSpans(self.stats, log, annotate)
        self.sched.probe = self._probe
        self._inflight: List[tuple] = []
        self._newest: Any = None
        self.slot_free_since: Optional[float] = time.monotonic()

    def of(self, req) -> RequestSpans:
        """The request's ``RequestSpans``, made at first use so every
        constructor of a queued request gets one."""
        if req.spans is None:
            req.spans = RequestSpans(req.trace, self.stats,
                                     req.t_submit or time.monotonic())
        return req.spans

    def admitted(self, req, t_adm: float) -> RequestSpans:
        spans = self.of(req)
        spans.admitted(t_adm, self.slot_free_since)
        return spans

    def note_slots(self, slots: List[Any]) -> None:
        """Call wherever a slot is seated or freed, and once per
        scheduler iteration for paths that free slots wholesale."""
        if None in slots:
            if self.slot_free_since is None:
                self.slot_free_since = time.monotonic()
        else:
            self.slot_free_since = None
        self.sched.note_live(any(s is not None for s in slots))

    def note_pipe(self, inflight: List[tuple]) -> float:
        """Call wherever the engine's in-flight queue changes, and once
        per scheduler iteration for paths that clear it wholesale: one
        look (``SchedSpans.look``) at the pipe, the chunk programs among
        its entries that the device is not done with, and at the newest
        launch. The list is the engine's own, changed in place: the
        scheduler's child regions look again as they end."""
        self._inflight = inflight
        return self.sched.look()

    def _probe(self) -> Tuple[int, bool]:
        return self.pipe_chunks(), self._device_done()

    def pipe_chunks(self) -> int:
        """The chunk programs in flight that the device is not done with:
        ``first_chunk``'s ``chunks_unready`` when asked at a dispatch."""
        return sum(1 for e in self._inflight
                   if e[0] == "chunk" and _on_device(e[1]))

    def launched(self, buf: Any) -> None:
        """The scheduler thread has issued a device program of any kind
        (a chunk, an eager piece, an arm, a copy, a one-op slice): ``buf``
        is a handle of its output, the newest launch from now on. Hand in
        a SMALL output that no later program is given as a donated
        argument where there is one (the packed buffer, a token, a logits
        row); a donated one is read as busy once it is deleted, until the
        program that took it has told of itself here. Off the scheduler
        thread (the warm-up) it charges nothing."""
        prev_done = self._device_done()
        self._newest = buf
        self.sched.note_launch(prev_done)

    def _device_done(self) -> bool:
        """Is the device done with everything this thread has launched:
        with the newest launch, since it runs one stream in launch order.
        One ``is_ready()``, and none once it has said yes."""
        buf = self._newest
        if buf is None:
            return True
        if getattr(buf, "is_ready", None) is None:
            # the fake engine's numpy buffer: at work until it is fetched
            done = not any(e[1] is buf for e in self._inflight)
        else:
            deleted = getattr(buf, "is_deleted", None)
            done = not (deleted is not None and deleted()) and buf.is_ready()
        if done:
            self._newest = None
        return done

    def dispatched(self, inflight: List[tuple], buf: Any) -> Dict[str, float]:
        """A chunk program is out, ``buf`` its packed buffer, its entry
        appended to ``inflight``: the two fields its ``sched/dispatch``
        ring entry takes. ``pipe_empty_ms``: how long the pipe had stood
        empty with work at hand when it was issued; ``drained_ms``: how
        long, since the dispatch before, the device had nothing at all to
        run with work at hand."""
        self.launched(buf)
        return {"pipe_empty_ms": self.note_pipe(inflight),
                "drained_ms": self.sched.drained_ms()}

    def health(self, chunks_consumed: int) -> Dict[str, Any]:
        """The ``/health.spans`` section: cumulative ``{count, total_ms,
        max_ms}`` per span name (request spans and ``sched/*`` alike) and
        the scheduler thread's wall time by state, whole
        (``sched_thread_s``), with the pipe empty (``sched_starved_s``)
        and with the device done with all it was given
        (``sched_drained_s``) — cheap host counters."""
        out: Dict[str, Any] = self.stats.snapshot()
        out.update(self.sched.sections())
        out["sched_thread_s"]["chunks_consumed"] = chunks_consumed
        return out


# --------------------------------------------------------------- context

_CURRENT: contextvars.ContextVar[Optional[Trace]] = contextvars.ContextVar(
    "ai_agent_kubectl_tpu_trace", default=None
)


def current_trace() -> Optional[Trace]:
    return _CURRENT.get()


@contextmanager
def use_trace(trace: Trace):
    token = _CURRENT.set(trace)
    try:
        yield trace
    finally:
        _CURRENT.reset(token)


def trace_event(message: str, **meta) -> None:
    """Annotate the active trace, if any — the no-trace case (unit tests
    driving a component directly, background threads) is free."""
    t = _CURRENT.get()
    if t is not None:
        t.event(message, **meta)
