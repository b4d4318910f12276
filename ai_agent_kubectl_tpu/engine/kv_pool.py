"""Block-paged KV pool: the host-side allocator behind paged serving.

The tentpole of ISSUE 10: per-slot dense KV (every admitted request owning
an ``S_alloc``-row cache region) caps the decode batch at the HBM budget's
``bs × S_alloc`` product even though real sequences average a fraction of
``S_alloc``. The pool replaces per-slot regions with one shared
``[n_layers, n_blocks, page, KV, hd]`` cache plus per-slot *block tables*:
a slot owns exactly the pages its live positions span, so the same HBM
admits ~``S_alloc / avg_len`` times the slots.

This module is the HOST truth: a free-list allocator with per-block
refcounts. Device arrays never carry ownership — the scheduler thread (or
the fake engine's event loop) is the single writer, so no locking beyond
that discipline is needed. Sharing (radix-tree prefix reuse,
engine/radix_cache.py) and copy-on-write both reduce to refcount edges
here:

- a *shared* full block appears in several slots' tables at refcount
  ``holders`` — decode never writes positions below a slot's live length,
  so shared full pages are read-only by construction;
- a *partially-filled tail* block can NOT be shared (its owner keeps
  writing rows into it), so mapping a cached partial page copies the
  matched rows into a fresh block first (``cow_copies_total``).

The same object (numpy-only, no jax imports) runs under the real batcher
and ``FakeChunkedEngine``, so the leak/double-free invariants are
asserted in tier-1 on CPU against the exact refcount code production runs.

Two-tier extension (ISSUE 20): ``HostBlockStore`` is the pinned host-RAM
second tier behind the radix tree's demotion path. Cold cached pages are
*demoted* there (CRC32 stamped at demote) instead of discarded, and
``RadixCache.match`` transparently *onloads* them back — with checksum
verification, so a corrupt host copy can only ever cost a suffix
re-prefill, never a wrong transcript. The store is id-addressed (host
block ids are an independent namespace from device block ids) and, like
the pool, is host truth under the single-writer discipline; the ``check``
methods together assert exact balance across both tiers.

Recurrent state (ISSUE 33): ``StateStore`` is the host truth of a second
KIND of cache, for a model with state-space layers
(``ModelConfig.keeps_state``). Such a layer's state is one fixed block a
sequence whatever its length (12.8 MB at the benchmark's cut, the K/V of
6,250 tokens), so it cannot be kept at every block edge as K and V are:
every decode slot owns one LIVE state, and a store of fixed capacity
holds SNAPSHOTS, each hung on the radix node of the block edge it was
taken at. A prefix match is usable only as deep as the last snapshot on
its path (``RadixCache.match``); ``map_prefix`` seats the slot from it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import zlib
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence

import numpy as np

from ..models.families import SECTIONS, kinds_of


class PoolExhausted(RuntimeError):
    """No free block and nothing evictable — the caller decides policy
    (the batcher finishes the slot at its current length; admission
    retries after radix eviction)."""


def pages_for(n_tokens: int, page: int) -> int:
    """Blocks needed to hold ``n_tokens`` KV rows."""
    return -(-max(0, n_tokens) // page)


def no_region(name: str, totals: Sequence[str] = (), **fields):
    """The ``region=`` callback of an owner that keeps no spans. An engine
    passes ``obs.trace.SchedSpans.child``: ``region(name, totals, **meta)``
    is a context manager around one named part of the scheduler thread's
    work (``sched/<name>``) that yields the entry's field dict; the fields
    ``totals`` names run as totals in ``/health.spans``."""
    return contextlib.nullcontext(fields)


def alloc_with_evict(pool: "BlockPool", radix, n: int):
    """Allocate ``n`` blocks with radix-eviction backpressure: cached
    blocks are reclaimable capacity, so allocation only truly fails once
    the tree has nothing left to give back. Returns None on failure
    (caller policy: truncate the slot / fail the admission)."""
    try:
        return pool.alloc(n)
    except PoolExhausted:
        if radix is not None and radix.evict_for(n):
            try:
                return pool.alloc(n)
            except PoolExhausted:  # pragma: no cover - defensive
                return None
        return None


def map_prefix(pool: "BlockPool", radix, ids: Sequence[int], *,
               match_all: bool = False, cow=None, state=None,
               slot: Optional[int] = None, region=no_region):
    """Build one slot's block chain for token sequence ``ids`` — THE
    shared admission path (run verbatim by the jax batcher and the fake
    engine, so refcount behaviour can never diverge between them):

    1. radix-match the longest cached prefix; full blocks map SHARED
       (refcounted, read-only by the decode-writes-only-forward
       invariant),
    2. a matched partial tail copy-on-writes into a fresh private block
       (``cow(src, dst, rows)`` does the device copy; the fake passes
       None — its KV is fictional, only the accounting is real),
    3. fresh blocks cover the remaining pages.

    With ``state`` (a ``StateStore``: the model keeps a recurrent state)
    the match is usable only as deep as the last snapshot on its path —
    the tree returns the blocks up to there and the snapshot, K/V blocks
    past it are left to the tree and recomputed into fresh ones — and
    decode slot ``slot`` is seated from that snapshot, or from zero.

    Returns ``(blocks, m)``: the table blocks in page order and the
    count of tokens whose KV is already valid (prefill starts at m).
    Admissions pass match_all=False — the LAST token must run forward
    for its logits; replays pass True (the carry token is forced).
    Raises PoolExhausted with every ref released on failure. ``region``
    times the match (``sched/radix_match``)."""
    page = pool.page
    blocks: List[int] = []
    m = 0
    mr = None
    if radix is not None:
        upto = len(ids) if match_all else max(0, len(ids) - 1)
        with region("radix_match", totals=("tokens", "matched"),
                    tokens=upto) as matched:
            mr = radix.match(ids[:upto])
            matched["matched"] = mr.n_tokens
        blocks = list(mr.blocks)
        m = len(blocks) * page
        if mr.tail_block is not None:
            c = alloc_with_evict(pool, radix, 1)
            if c is None:
                pool.decref([mr.tail_block])
                if blocks:
                    pool.decref(blocks)
                raise PoolExhausted("kv pool exhausted (tail COW)")
            if cow is not None:
                cow(mr.tail_block, c[0], mr.tail_rows)
            pool.decref([mr.tail_block])
            pool.note_cow()
            blocks += c
            m += mr.tail_rows
    if state is not None:
        state.seat(slot, mr)
    grow = pages_for(len(ids), page) - len(blocks)
    if grow > 0:
        fresh = alloc_with_evict(pool, radix, grow)
        if fresh is None:
            if blocks:
                pool.decref(blocks)
            if state is not None:
                state.release(slot)
            raise PoolExhausted(f"kv pool exhausted ({grow} blocks short)")
        blocks += fresh
    return blocks, m


def state_cuts(state: "StateStore", slot: int, n_prompt: int, page: int,
               start: int) -> List[int]:
    """The block edges in (``start``, ``n_prompt`` - 1] at which an
    admission's prefill stops so that the state there exists to be saved
    (THE shared snapshot policy, run by the jax batcher and the fake):

    - the deepest edge the prompt's K/V matched in the tree, where that is
      past the snapshot it was seated from AND the node there has more
      than one child sequence already: other sequences branch there (two
      agents over a shared preamble leave the node, the third recomputes
      the preamble once more and leaves the snapshot every later one
      restores). A turn that outruns its own session's snapshot matches
      to a node with one child, the turn before, and leaves nothing there:
      nobody else will come that way;
    - the end of the prompt's last whole block, which the same prompt
      grown by a turn will match;
    - and the block edge before that one, which a prompt that DIVERGES
      from this one within its last page will match (a second question
      about the same pasted log: the questions differ, the log's blocks
      are shared, and the last whole block's edge may lie past where they
      part). It costs one more stop, of at most a page, and keeps a
      re-ask from recomputing the log for want of a state at its end."""
    last = (n_prompt - 1) // page * page
    edges = {state.branch_edge(slot), last, last - page}
    return sorted(e for e in edges if start < e <= last)


def span_window_counts(m: int, n: int, span: int) -> dict:
    """Prompt rows m .. n-1 prefilled, and the (query, key) pairs of those
    rows in one layer: row t has t + 1 keys before it in a full layer and
    min(t + 1, span) in a sliding one (the scheduler's arithmetic, run by
    the jax batcher and the fake alike)."""
    tri = lambda x: x * (x + 1) // 2
    return {"window_rows": n - m,
            "window_pairs_full": tri(n) - tri(m),
            "window_pairs_sliding": (tri(min(n, span)) - tri(min(m, span))
                                     + span * (max(n, span) - max(m, span)))}


class CacheCounters:
    """What the kinds of a configuration's cache (models/families.py)
    count, cumulative, on the scheduler's thread: the words their passes
    counted on the device, the arithmetic on the prompt lengths admitted,
    the forward passes dispatched. ``sections`` gives /health's."""

    def __init__(self, model_cfg):
        self.cfg = model_cfg
        self.kinds = kinds_of(model_cfg)
        self.counts = {
            kind.name: {"dev": [0] * kind.count_words,
                        "host": dict.fromkeys(kind.window_counts, 0),
                        "passes": 0}
            for kind in self.kinds}
        self.forward_passes = 0
        self.eager_passes = 0         # one-sequence prefill pieces run

    def note_admission(self, matched: int, n_prompt: int) -> None:
        """Prompt rows ``matched`` .. ``n_prompt`` - 1 are prefilled: each
        kind adds, under its own names, the rows and their pairs a layer."""
        rule = span_window_counts(matched, n_prompt,
                                  self.cfg.sliding_window)
        for kind in self.kinds:
            host = self.counts[kind.name]["host"]
            for own, name in kind.window_counts.items():
                host[own] += rule[name]

    def note_passes(self, passes: int, eager: bool = False) -> None:
        """Forward passes dispatched: a chunk program's steps (a spec
        chunk: its verifies), or one eager prefill piece."""
        self.forward_passes += passes
        if eager:
            self.eager_passes += passes

    def note_chunk(self, result, passes: int) -> None:
        """A fetched chunk (engine/protocol.py::ChunkResult) of ``passes``
        passes: the device's own count of what those read and kept."""
        at = {}                 # lane -> words already given to a kind
        for kind in self.kinds:
            words = getattr(result, kind.lane) if kind.lane else None
            if words is None:
                continue
            c = self.counts[kind.name]
            c["passes"] += passes
            if kind.count_shape:
                # the kinds of one lane lie end to end (models/families.py::
                # attention_counted)
                first = at.get(kind.lane, 0)
                at[kind.lane] = first + kind.count_words
                words = words[first:at[kind.lane]]
            for i, n in enumerate(words if kind.count_shape else (words,)):
                c["dev"][i] += n

    def sections(self, **facts) -> Dict[str, Optional[dict]]:
        """The kinds' /health sections, None where the configuration is of
        no kind that gives one. ``facts``: what only the engine knows
        (batch_size, widest_window, counts_experts, pool_bytes_per_token,
        ring_rows, store: StateStore.stats())."""
        facts.update(forward_passes=self.forward_passes,
                     eager_passes=self.eager_passes)
        out: Dict[str, Optional[dict]] = dict.fromkeys(SECTIONS)
        for kind in self.kinds:
            for section, body in kind.health.items():
                said = body(self.cfg, self.counts[kind.name], facts)
                # two kinds may fill one section (an expert share's picks
                # beside the experts read): the later adds its keys
                out[section] = ({**out[section], **said}
                                if out[section] and said else
                                said or out[section])
        return out


def take_snapshot(state: "StateStore", radix, slot: int,
                  ids: Sequence[int], edge: int) -> None:
    """Save slot ``slot``'s state, which stands at ``ids[:edge]``: on the
    tree at once where it has that node (a prefix others share), else
    pending on the slot until ``release_state`` finds the node."""
    handle = state.take(slot, edge)
    if handle is not None and radix is not None:
        radix.attach_snapshot(ids, edge, handle)


def release_state(state: "StateStore", radix, slot: int,
                  chain: Sequence[int], cached: bool) -> None:
    """A leaving slot's snapshots: those not yet on a node (the edge was
    beyond the tree when they were taken) are hung on the chain the
    release just inserted — or freed where the chain was not cached or
    the node holds one already — and every pin of the slot drops."""
    for edge, handle in state.pending(slot):
        if not (cached and radix is not None
                and radix.attach_snapshot(chain, edge, handle)):
            state.free(handle)
    state.release(slot)


@dataclasses.dataclass
class PoolStats:
    n_blocks: int
    page: int
    free: int
    live: int
    cached: int
    shared_mapped_total: int
    cow_copies_total: int
    exhausted_total: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class BlockPool:
    """Free-list block allocator with refcounts.

    Refcount semantics: one count per *holder* — each slot table that maps
    the block, plus (at most) one for the radix tree that caches it.
    ``alloc`` hands out blocks at refcount 1; ``incref`` adds holders;
    ``decref`` removes them and returns blocks that hit zero to the free
    list. Double-free and negative-refcount are hard errors, not warnings:
    an accounting bug here corrupts KV silently (a freed block re-issued
    while a stale table still maps it), so the invariant check must be
    louder than the symptom.
    """

    def __init__(self, n_blocks: int, page: int):
        if n_blocks < 1:
            raise ValueError("KV pool needs at least 1 block")
        if page < 1:
            raise ValueError("KV pool page must be >= 1")
        self.n_blocks = int(n_blocks)
        self.page = int(page)
        self._ref = np.zeros((self.n_blocks,), np.int64)
        self._free: deque = deque(range(self.n_blocks))
        # Counters (cumulative; delta-mirrored into Prometheus at scrape).
        self.shared_mapped_total = 0   # shared-block mappings handed out
        self.cow_copies_total = 0      # partial-tail copy-on-write copies
        self.exhausted_total = 0       # allocation failures (after evict)

    def carry_counters(self, prev: "BlockPool") -> None:
        """Inherit the cumulative counters from a previous pool
        generation (containment reset rebuilds the allocator world):
        the /metrics delta-mirror compares against last-seen totals, so
        a zeroed counter would freeze the Prometheus series until the
        new generation re-exceeded the old value."""
        self.shared_mapped_total = prev.shared_mapped_total
        self.cow_copies_total = prev.cow_copies_total
        self.exhausted_total = prev.exhausted_total

    # ------------------------------------------------------------ alloc

    @property
    def free_count(self) -> int:
        return len(self._free)

    def alloc(self, n: int = 1) -> List[int]:
        """Pop ``n`` free blocks at refcount 1. All-or-nothing: a partial
        grab under pressure would leak on the error path."""
        if n <= 0:
            return []
        if len(self._free) < n:
            self.exhausted_total += 1
            raise PoolExhausted(
                f"KV pool exhausted: want {n} blocks, {len(self._free)} "
                f"free of {self.n_blocks}")
        out = [self._free.popleft() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
        return out

    def incref(self, blocks: Iterable[int]) -> None:
        for b in blocks:
            if self._ref[b] <= 0:
                raise RuntimeError(
                    f"incref of free block {b} (use-after-free)")
            self._ref[b] += 1

    def decref(self, blocks: Iterable[int]) -> List[int]:
        """Drop one holder per block; returns the blocks that reached
        refcount 0 (now back on the free list)."""
        freed: List[int] = []
        for b in blocks:
            if self._ref[b] <= 0:
                raise RuntimeError(f"double free of block {b}")
            self._ref[b] -= 1
            if self._ref[b] == 0:
                self._free.append(b)
                freed.append(b)
        return freed

    def ref(self, block: int) -> int:
        return int(self._ref[block])

    def note_shared(self, n: int) -> None:
        if n > 0:
            self.shared_mapped_total += n

    def note_cow(self, n: int = 1) -> None:
        self.cow_copies_total += n

    # ------------------------------------------------------- accounting

    def stats(self, cached_blocks: Sequence[int] = ()) -> PoolStats:
        """State classification for the kv_pool_blocks{state} gauges:
        ``free`` (refcount 0), ``cached`` (held ONLY by the radix tree),
        ``live`` (held by at least one slot). ``cached_blocks`` is the
        tree's block set (the pool itself is holder-agnostic)."""
        cached = sum(1 for b in set(cached_blocks) if self._ref[b] == 1)
        free = len(self._free)
        return PoolStats(
            n_blocks=self.n_blocks,
            page=self.page,
            free=free,
            live=self.n_blocks - free - cached,
            cached=cached,
            shared_mapped_total=self.shared_mapped_total,
            cow_copies_total=self.cow_copies_total,
            exhausted_total=self.exhausted_total,
        )

    def check(self, holders: Dict[int, int], *,
              host: Optional["HostBlockStore"] = None,
              host_holders: Optional[Dict[int, int]] = None) -> None:
        """Assert the books balance exactly against an externally-computed
        holder count per block (slots' tables + tree references). Used by
        the tier-1 leak-invariant test after the chaos recovery matrix:
        every block is either free (refcount 0, on the free list once) or
        accounted for by exactly its holders — no leak, no double-free.

        Passing ``host``/``host_holders`` extends the exact-balance
        assertion across the second tier (ISSUE 20): every resident host
        block must be held by exactly one radix node and vice versa."""
        free_set = list(self._free)
        if len(free_set) != len(set(free_set)):
            raise AssertionError("free list holds a block twice")
        for b in range(self.n_blocks):
            want = int(holders.get(b, 0))
            have = int(self._ref[b])
            if have != want:
                raise AssertionError(
                    f"block {b}: refcount {have} != {want} holders")
            on_free = b in self._free
            if (have == 0) != on_free:
                raise AssertionError(
                    f"block {b}: refcount {have} but "
                    f"{'on' if on_free else 'off'} the free list")
        if host is not None:
            host.check(host_holders or {})


class HostBlockStore:
    """Pinned host-RAM second KV tier (ISSUE 20).

    Holds demoted radix pages as numpy payloads keyed by *host block id*
    (an id namespace independent of device block indices — a host id is
    never valid in a slot table). Every ``put`` stamps a CRC32 over the
    payload bytes; promotion verifies it before the page re-enters the
    device tier, so silent host-RAM corruption degrades to a counted
    suffix re-prefill instead of a wrong transcript.

    Ownership is exactly-one-holder: each resident id is held by exactly
    one radix node (``RadixCache`` keeps the reverse map). There is no
    refcounting here — host pages are cache-only, never slot-mapped.
    Counters are cumulative and delta-mirrored into Prometheus, same as
    the pool's.
    """

    #: closed cause set for onload_fail_total — the causes are metric
    #: labels, so the set must be bounded by construction.
    ONLOAD_FAIL_CAUSES = ("corrupt", "exhausted")

    def __init__(self, capacity: int):
        if capacity < 0:
            raise ValueError("host KV block capacity must be >= 0")
        self.capacity = int(capacity)
        self._data: Dict[int, np.ndarray] = {}
        self._crc: Dict[int, int] = {}
        self._ids = itertools.count(1)
        # Counters (cumulative; delta-mirrored at /metrics scrape).
        self.demoted_total = 0        # device -> host copies stored
        self.onloaded_total = 0       # host -> device promotes (verified)
        self.adopted_total = 0        # host copy superseded by an
        #                               insert-path device block (free
        #                               promotion — no onload needed)
        self.dropped_total = 0        # host-LRU drops + discarded demotes
        self.offload_fail_total = 0   # offload:fail drills / demote aborts
        self.onload_fail_total: Dict[str, int] = {
            c: 0 for c in self.ONLOAD_FAIL_CAUSES}

    def carry_counters(self, prev: "HostBlockStore") -> None:
        """Inherit cumulative counters across a containment reset (both
        tiers rebuild — see BlockPool.carry_counters for why totals must
        never go backwards under the delta-mirror)."""
        self.demoted_total = prev.demoted_total
        self.onloaded_total = prev.onloaded_total
        self.adopted_total = prev.adopted_total
        self.dropped_total = prev.dropped_total
        self.offload_fail_total = prev.offload_fail_total
        self.onload_fail_total = dict(prev.onload_fail_total)

    # ------------------------------------------------------------ storage

    @property
    def used(self) -> int:
        return len(self._data)

    @property
    def free_count(self) -> int:
        return self.capacity - len(self._data)

    def put(self, data: np.ndarray) -> int:
        """Store one demoted page; returns its host block id. The CRC is
        stamped over the exact bytes stored — the promote path recomputes
        it over what it reads back. Raises when full (the radix demote
        path makes room FIRST; a full put is an accounting bug)."""
        if self.free_count < 1:
            raise RuntimeError(
                f"host block store full ({self.used}/{self.capacity}); "
                f"demote must make room before putting")
        buf = np.ascontiguousarray(data)
        hbid = next(self._ids)
        self._data[hbid] = buf
        self._crc[hbid] = zlib.crc32(buf.tobytes())
        self.demoted_total += 1
        return hbid

    def get(self, hbid: int) -> np.ndarray:
        if hbid not in self._data:
            raise RuntimeError(
                f"host block {hbid} not resident (use-after-free)")
        return self._data[hbid]

    def verify(self, hbid: int, data: np.ndarray) -> bool:
        """Does ``data`` still match the checksum stamped at demote?"""
        return (zlib.crc32(np.ascontiguousarray(data).tobytes())
                == self._crc.get(hbid))

    def free(self, hbid: int) -> None:
        if hbid not in self._data:
            raise RuntimeError(f"double free of host block {hbid}")
        del self._data[hbid]
        del self._crc[hbid]

    # --------------------------------------------------------- accounting

    def note_dropped(self, n: int = 1) -> None:
        self.dropped_total += n

    def note_onload_fail(self, cause: str) -> None:
        if cause not in self.ONLOAD_FAIL_CAUSES:
            raise ValueError(
                f"unknown onload-fail cause {cause!r}; "
                f"valid: {self.ONLOAD_FAIL_CAUSES}")
        self.onload_fail_total[cause] += 1

    def stats(self) -> dict:
        """The /health ``host_tier`` subsection (cheap host counters,
        never a payload walk — same rule as PoolStats)."""
        return {
            "capacity": self.capacity,
            "used": self.used,
            "free": self.free_count,
            "demoted_total": self.demoted_total,
            "onloaded_total": self.onloaded_total,
            "adopted_total": self.adopted_total,
            "dropped_total": self.dropped_total,
            "offload_fail_total": self.offload_fail_total,
            "onload_fail_total": dict(self.onload_fail_total),
        }

    def check(self, holders: Dict[int, int]) -> None:
        """Exact-balance assertion for the host tier: every resident id
        is held by exactly one node, every held id is resident, and the
        CRC table tracks the payload table one-to-one."""
        held = {h for h, n in holders.items() if n > 0}
        for hbid, n in holders.items():
            if n <= 0:
                continue
            if n != 1:
                raise AssertionError(
                    f"host block {hbid}: {n} holders (exactly one radix "
                    f"node may hold a host block)")
            if hbid not in self._data:
                raise AssertionError(
                    f"host block {hbid} held but not resident "
                    f"(use-after-free)")
        extra = set(self._data) - held
        if extra:
            raise AssertionError(
                f"host blocks resident but unheld (leak): {sorted(extra)}")
        if set(self._data) != set(self._crc):
            raise AssertionError("host CRC table out of sync with payloads")
        if len(self._data) > self.capacity:
            raise AssertionError(
                f"host store over capacity: {len(self._data)} > "
                f"{self.capacity}")


class StateStore:
    """Recurrent-state snapshots: the host truth (ISSUE 33).

    ``capacity`` handles name the rows of a device store the engine owns
    (``snapshot_fn(slot, handle)`` copies a slot's live state into a row,
    ``restore_fn(slot, handle)`` a row into a slot, ``zero_fn(slot)``
    starts a slot from nothing; the fake engine passes none — its state
    has no bytes, the bookkeeping is all of it). A handle is FREE, or
    PENDING (taken by a live slot whose chain is not in the tree yet), or
    ATTACHED to exactly one radix node. Every live slot PINS the deepest
    two snapshots on its matched path (the one it was seated from, which
    a replay of it restores again, and the one before it) and the ones it
    took: eviction is LRU among attached snapshots nobody pins, the
    earlier turns' of a live session among them (pinning every one on the
    path held 8 x 6 x 2 rows of 54.7 MB for eight agents of six turns;
    ISSUE 45), independent of the blocks' LRU
    — but a node that loses its block loses its snapshot (``drop``). The
    host tier takes no states: a demoted page keeps its K/V and not this.

    Single-writer like the pool (the scheduler thread, or the fake's
    loop); ``stats()`` is the /health.ssm section, cheap counters only.
    """

    def __init__(self, capacity: int, n_slots: int, state_bytes: int = 0, *,
                 snapshot_fn=None, restore_fn=None, zero_fn=None,
                 region=no_region):
        if capacity < 1:
            raise ValueError("the state store needs at least 1 snapshot")
        self.capacity = int(capacity)
        self.state_bytes = int(state_bytes)
        self._snapshot_fn, self._restore_fn = snapshot_fn, restore_fn
        self._zero_fn = zero_fn
        self._region = region
        self._free: deque = deque(range(self.capacity))
        self._node: Dict[int, object] = {}      # attached handle -> node
        self._last: Dict[int, int] = {}         # held handle -> LRU stamp
        self._pins: Dict[int, int] = {}         # held handle -> live pinners
        self._slot_pins: List[List[int]] = [[] for _ in range(n_slots)]
        self._slot_pending: List[List[tuple]] = [[] for _ in range(n_slots)]
        self._edge: Dict[int, int] = {}         # held handle -> its edge
        self._branch_edge = [0] * n_slots
        self._clock = itertools.count(1)
        self.held_peak = 0
        self.restore_depth_peak = 0     # deepest LRU rank a restore found
        self.snapshots_taken = 0
        self.snapshots_evicted = 0
        self.snapshots_skipped = 0      # store full of pinned snapshots
        self.restores = 0
        self.prefix_tokens_matched = 0
        self.prefix_tokens_usable = 0
        self.prefix_tokens_recomputed = 0
        self.state_bytes_moved = 0

    COUNTERS = ("held_peak", "restore_depth_peak", "snapshots_taken",
                "snapshots_evicted",
                "snapshots_skipped", "restores", "prefix_tokens_matched",
                "prefix_tokens_usable", "prefix_tokens_recomputed",
                "state_bytes_moved")

    def carry_counters(self, prev: "StateStore") -> None:
        for name in self.COUNTERS:
            setattr(self, name, getattr(prev, name))

    # ------------------------------------------------------------ slots

    def _pin(self, slot: int, handle: int) -> None:
        self._pins[handle] = self._pins.get(handle, 0) + 1
        self._slot_pins[slot].append(handle)

    def seat(self, slot: int, mr) -> None:
        """Seat decode slot ``slot`` for a sequence whose match is ``mr``
        (``RadixCache.match``'s result, None with no tree): restore the
        snapshot the match ended at, or start from zero; pin the deepest
        two snapshots on the path until ``release``."""
        self.release(slot)
        handle = mr.snapshot if mr is not None else None
        self._branch_edge[slot] = (mr.kv_matched_edge
                                   if mr is not None and mr.kv_branching else 0)
        if handle is None:
            if mr is not None:
                self.prefix_tokens_matched += mr.kv_matched
                self.prefix_tokens_recomputed += mr.kv_matched
            if self._zero_fn is not None:
                with self._region("state_zero", slot=slot):
                    self._zero_fn(slot)
            return
        usable = mr.n_tokens
        self.prefix_tokens_matched += mr.kv_matched
        self.prefix_tokens_usable += usable
        self.prefix_tokens_recomputed += mr.kv_matched - usable
        for h in mr.path_snapshots[-2:]:
            self._pin(slot, h)
        # An LRU store is full in any long run, so ``held_peak`` reads its
        # capacity; what sizes it is how far down the LRU order a restore
        # reaches (1 = the newest): a store of that many and the live
        # slots' pins would have served every restore so far.
        stamp = self._last[handle]
        self.restore_depth_peak = max(
            self.restore_depth_peak,
            sum(1 for t in self._last.values() if t >= stamp))
        self._last[handle] = next(self._clock)
        with self._region("state_restore", slot=slot, tokens=usable):
            if self._restore_fn is not None:
                self._restore_fn(slot, handle)
        self.restores += 1
        self.state_bytes_moved += self.state_bytes

    def edge(self, handle: int) -> int:
        """The token a held snapshot stands at (a state whose bytes depend
        on the position, a sliding layer's last rows, is copied by it)."""
        return self._edge[handle]

    def branch_edge(self, slot: int) -> int:
        """How deep (tokens, a block edge) the slot's sequence matched
        K/V in the tree when it was seated — past its snapshot or not —
        where the node there is one that sequences branch from; else 0."""
        return self._branch_edge[slot]

    def take(self, slot: int, edge: int) -> Optional[int]:
        """Snapshot slot ``slot``'s live state, which stands at token
        ``edge`` of its sequence. The handle is pending (and pinned) on
        the slot until a node takes it. None when every snapshot is
        pinned: the state is simply not saved."""
        handle = self._alloc()
        if handle is None:
            self.snapshots_skipped += 1
            return None
        self._last[handle] = next(self._clock)
        self._edge[handle] = edge
        self._pin(slot, handle)
        self._slot_pending[slot].append((edge, handle))
        with self._region("state_snapshot", slot=slot, tokens=edge):
            if self._snapshot_fn is not None:
                self._snapshot_fn(slot, handle)
        self.snapshots_taken += 1
        self.state_bytes_moved += self.state_bytes
        self.held_peak = max(self.held_peak, self.held)
        return handle

    def pending(self, slot: int) -> List[tuple]:
        """(edge, handle) of the slot's snapshots no node holds yet."""
        return list(self._slot_pending[slot])

    def release(self, slot: int) -> None:
        """Drop every pin of ``slot`` (it leaves, or is re-seated)."""
        for h in self._slot_pins[slot]:
            n = self._pins.get(h, 0) - 1
            if n > 0:
                self._pins[h] = n
            else:
                self._pins.pop(h, None)
        self._slot_pins[slot] = []
        self._slot_pending[slot] = []

    # ---------------------------------------------------------- handles

    @property
    def held(self) -> int:
        return self.capacity - len(self._free)

    @property
    def on_nodes(self) -> int:
        """Snapshots a radix node holds (the rest of ``held`` is pending)."""
        return len(self._node)

    def _alloc(self) -> Optional[int]:
        if not self._free:
            victims = [h for h in self._node if not self._pins.get(h)]
            if not victims:
                return None
            victim = min(victims, key=self._last.__getitem__)
            self._node[victim].snap = None
            self.snapshots_evicted += 1
            self._forget(victim)
        return self._free.popleft()

    def _forget(self, handle: int) -> None:
        """Back to the free list, with every trace of its holders gone: a
        handle is reissued, and a stale pin would then be another's."""
        self._node.pop(handle, None)
        self._last.pop(handle, None)
        self._edge.pop(handle, None)
        if self._pins.pop(handle, None):
            for pins in self._slot_pins:
                pins[:] = [h for h in pins if h != handle]
        for pend in self._slot_pending:
            pend[:] = [(e, h) for e, h in pend if h != handle]
        self._free.append(handle)

    def attached(self, handle: int, node) -> None:
        """A radix node took a pending handle (``attach_snapshot``)."""
        self._node[handle] = node
        for pend in self._slot_pending:
            pend[:] = [(e, h) for e, h in pend if h != handle]

    def free(self, handle: int) -> None:
        """A pending handle nobody will hang anywhere."""
        if handle in self._last and handle not in self._node:
            self._forget(handle)

    def drop(self, handle: int) -> None:
        """The node holding ``handle`` lost its block (evicted, demoted,
        cleared): the snapshot goes with it, pinned or not — a slot that
        descends from it resumes from a shallower one, or from zero."""
        if handle in self._node:
            self.snapshots_evicted += 1
            self._forget(handle)

    # ------------------------------------------------------- accounting

    def stats(self) -> dict:
        body = {"snapshots_held": self.held, "capacity": self.capacity,
                "bytes": self.held * self.state_bytes,
                "state_bytes": self.state_bytes,
                "snapshots_pinned": len(self._pins)}
        body.update({name: getattr(self, name) for name in self.COUNTERS})
        return body

    def check(self) -> None:
        """Exact balance: every handle is free once, or held by exactly
        one node, or pending on exactly one slot; pins name held handles
        only and match the slots' lists."""
        free = list(self._free)
        if len(free) != len(set(free)):
            raise AssertionError("a snapshot handle is free twice")
        pending = [h for pend in self._slot_pending for _, h in pend]
        owned = list(self._node) + pending
        if len(owned) != len(set(owned)):
            raise AssertionError("a snapshot handle has two holders")
        if set(owned) & set(free) or len(owned) + len(free) != self.capacity:
            raise AssertionError(
                f"snapshot handles out of balance: {len(free)} free, "
                f"{len(self._node)} attached, {len(pending)} pending of "
                f"{self.capacity}")
        for h, node in self._node.items():
            if getattr(node, "snap", None) != h:
                raise AssertionError(f"snapshot {h}: its node does not hold it")
        want: Dict[int, int] = {}
        for pins in self._slot_pins:
            for h in pins:
                want[h] = want.get(h, 0) + 1
        want = {h: n for h, n in want.items() if h in self._last}
        have = {h: n for h, n in self._pins.items() if h in self._last}
        if want != have:
            raise AssertionError(f"snapshot pins {have} != slots' {want}")
