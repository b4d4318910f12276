"""The Engine protocol — the LLM-integration seam (reference app.py:106-122).

Everything above this seam (API, middleware, service, cache, exec) is
engine-agnostic; everything below it is a particular inference backend.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import AsyncIterator, List, Optional, Protocol, Tuple, runtime_checkable

import numpy as np


class EngineUnavailable(RuntimeError):
    """Engine not initialized / degraded mode → HTTP 503
    (reference app.py:179-180)."""


class EngineOverloaded(EngineUnavailable):
    """Admission rejected by overload protection (bounded queue / inflight
    cap) → fast HTTP 503 with ``Retry-After``. Raised at submit time so a
    doomed request is shed in microseconds instead of queueing until it
    times out at 504. ``retry_after`` is the engine's estimate (seconds)
    of when capacity frees, computed from the live queue drain rate."""

    def __init__(self, message: str, retry_after: float = 1.0):
        super().__init__(message)
        self.retry_after = max(0.0, float(retry_after))


class TenantOverloaded(EngineOverloaded):
    """Per-TENANT admission cap hit (QoS ring, engine/qos.py) → HTTP 429.

    Deliberately an ``EngineOverloaded`` subclass: to the fleet router
    one replica's tenant-cap shed is still backpressure (reroute, don't
    migrate), and to the breaker it still says nothing about engine
    health. The HTTP layer maps it to 429 instead of 503 — the flooding
    tenant is told to back off, everyone else keeps being served —
    with ``Retry-After`` priced from the shed lane's own drain rate."""

    def __init__(self, message: str, retry_after: float = 1.0,
                 tenant: str = "", lane: str = ""):
        super().__init__(message, retry_after=retry_after)
        self.tenant = tenant
        self.lane = lane


class GenerationTimeout(TimeoutError):
    """Generation exceeded the configured timeout → HTTP 504
    (reference app.py:189-191)."""


class RequestQuarantined(RuntimeError):
    """Terminal per-request failure from the fault-containment subsystem
    → HTTP 410 (Gone).

    The culprit-isolation pass (engine/containment.py) decided this
    request keeps poisoning decode steps (NaN/Inf logits, out-of-range
    token ids, or step-wide faults that bisect down to it) and its
    QUARANTINE_RETRY_BUDGET is spent. Deliberately NOT an
    ``EngineUnavailable`` subclass: the engine is healthy — retrying the
    same request elsewhere would just poison another batch, so this must
    not trip the circuit breaker, route to the degraded fallback, or
    invite a load-balancer retry the way a 503 does."""


# ---------------------------------------------------------------------------
# Packed chunk-result contract (decode pipeline seam) — v3
#
# A decode chunk returns ONE flat int32 buffer so tokens, termination,
# occupancy, AND per-slot health cross the host↔device link in a single
# fetch:
#
#     [ tokens (n_slots × chunk_len) | done_mask (n_slots)
#       | live_lengths (n_slots) | health (n_slots)
#       | spec_drafted (n_slots) | spec_accepted (n_slots)   (spec only)
#       | n_alive (1) ]
#
# - ``tokens[i]``: the chunk's sampled token ids for slot i (entries past
#   the slot's termination point repeat its last counted token — garbage
#   by contract, never emitted).
# - ``done_mask[i]``: slot i terminated (EOS or per-slot token budget) in
#   or before this chunk, among the slots the dispatcher asked to run.
# - ``live_lengths[i]``: slot i's CUMULATIVE completion-token count after
#   this chunk (device-resident occupancy fact; the consumer derives this
#   chunk's valid tokens as ``live_lengths[i] - already_emitted``).
# - ``health[i]``: bitmask of corruption the device detected in slot i
#   THIS chunk (v2 addition, SLOT_HEALTH_CHECK): HEALTH_NONFINITE = the
#   slot's logits contained NaN/Inf, HEALTH_TOKEN_RANGE = the sampled
#   token id fell outside [0, vocab). A tripped slot is frozen inside the
#   chunk (no further sampling/KV writes) and its garbage is never
#   counted in ``live_lengths`` — the scheduler's quarantine pass
#   (engine/containment.py) takes it from there. 0 = healthy.
# - ``spec_drafted`` / ``spec_accepted`` (v3, speculative decoding —
#   ISSUE 12): how many draft-model proposals this chunk drafted for
#   slot i and how many of them the verifier accepted (an accepted draft
#   = a transcript token that did NOT cost its own target forward). The
#   two lanes ride the packed buffer only when the chunk program runs
#   the draft/verify body — ``pack_chunk``/``unpack_chunk`` take
#   ``spec=True`` — so plain decode pays nothing for the contract
#   extension. Acceptance rate is derived host-side and billed into the
#   goodput ledger (rejected drafts are a first-class waste class).
# - ``n_alive``: slots still decoding after the chunk — the scheduler's
#   early-retirement signal.
#
# Both the jax batcher and the fake chunked engine build/consume exactly
# this layout (schema version ``PACKED_CHUNK_VERSION``), so pipeline tests
# on the fake engine exercise the real contract.
# ---------------------------------------------------------------------------

PACKED_CHUNK_VERSION = 3

#: health-word bits (per slot, OR-able). Device-side detection writes
#: them inside the jitted chunk scan; the fake engine's numpy twin writes
#: the same bits, so the quarantine pass is engine-agnostic.
HEALTH_OK = 0
HEALTH_NONFINITE = 1      # NaN/Inf in the slot's step logits
HEALTH_TOKEN_RANGE = 2    # sampled token id outside [0, vocab_size)
HEALTH_GRAMMAR_DEAD = 4   # grammar-constrained decode (ISSUE 11): the
                          # slot's FSM state admits NO legal token — a
                          # dead end the mask cannot sample out of. The
                          # slot freezes before emitting anything and
                          # rides the same quarantine lane as the other
                          # health trips.

_HEALTH_NAMES = ((HEALTH_NONFINITE, "nonfinite_logits"),
                 (HEALTH_TOKEN_RANGE, "token_out_of_range"),
                 (HEALTH_GRAMMAR_DEAD, "grammar_dead_end"))


def describe_health(word: int) -> str:
    """Human/metric label for a health bitmask (``"nonfinite_logits"``,
    ``"nonfinite_logits|token_out_of_range"``, ...)."""
    parts = [name for bit, name in _HEALTH_NAMES if word & bit]
    if int(word) and not parts:  # unknown future bit
        parts = [f"bit{int(word)}"]
    return "|".join(parts) or "ok"


def _sel_words(sel) -> int:
    """Words of the ``sel_rows`` lane: ``sel`` is how many (True: two)."""
    return 2 if sel is True else int(sel)


def packed_chunk_size(n_slots: int, chunk_len: int,
                      spec: bool = False, moe: bool = False,
                      sel=False) -> int:
    """Flat length of one packed chunk buffer (``spec`` adds the two
    per-slot drafted/accepted lanes of the v3 speculative contract,
    ``moe`` the one-word ``experts_read`` lane, ``sel`` the ``sel_rows``
    lane: True for its two words, or their number where the attention
    counts more — four for a model with sliding layers)."""
    return (n_slots * chunk_len + (5 if spec else 3) * n_slots + 1
            + (1 if moe else 0) + _sel_words(sel))


@dataclass
class ChunkResult:
    """Host-side view of one unpacked decode chunk."""

    tokens: np.ndarray      # [n_slots, chunk_len] int32
    done: np.ndarray        # [n_slots] bool
    lengths: np.ndarray     # [n_slots] int32 cumulative completion tokens
    health: np.ndarray      # [n_slots] int32 health bitmask (0 = healthy)
    n_alive: int
    #: speculative decoding (v3): draft tokens proposed / accepted for
    #: each slot THIS chunk. All-zero when the chunk ran plain decode.
    drafted: Optional[np.ndarray] = None   # [n_slots] int32
    accepted: Optional[np.ndarray] = None  # [n_slots] int32
    #: grouped expert GEMM (optional lane): experts whose weights the
    #: chunk's passes read, summed over layers and steps. None where the
    #: chunk program has no such path (every engine but the jax batcher
    #: serving ``ModelConfig.grouped_experts``; the fake never sets it).
    experts_read: Optional[int] = None
    #: key selection (optional lane): (keys the chunk's decode queries had
    #: before them, keys the selector kept of those), summed over layers
    #: and steps; a latent or a sliding configuration's own counts ride
    #: the same lane (models/families.py::attention_counted). None where the
    #: chunk program counts none.
    sel_rows: Optional[tuple] = None


def pack_chunk(tokens, done, lengths, n_alive, *, health=None,
               drafted=None, accepted=None, experts_read=None,
               sel_rows=None, xp=np):
    """Flatten one chunk's results into the single-fetch buffer.

    ``xp`` is the array namespace — ``numpy`` for the fake engine,
    ``jax.numpy`` inside the jitted chunk program (the concatenate then
    happens on device and the scheduler fetches one array). ``health``
    defaults to all-healthy for callers predating the v2 lane;
    ``drafted``/``accepted`` (v3) ride only when the chunk ran the
    speculative draft/verify body — pass both or neither.
    ``sel_rows`` (two words, or four) and ``experts_read`` (a scalar)
    ride, when given, in that order just before ``n_alive``."""
    done = done.astype(xp.int32)
    if health is None:
        health = xp.zeros_like(done)
    if (drafted is None) != (accepted is None):
        raise ValueError("spec lanes travel together: pass both "
                         "drafted and accepted, or neither")
    parts = [
        xp.reshape(tokens, (-1,)).astype(xp.int32),
        done,
        lengths.astype(xp.int32),
        health.astype(xp.int32),
    ]
    if drafted is not None:
        parts.append(drafted.astype(xp.int32))
        parts.append(accepted.astype(xp.int32))
    if sel_rows is not None:
        parts.append(xp.reshape(xp.asarray(sel_rows, dtype=xp.int32), (-1,)))
    if experts_read is not None:
        parts.append(xp.reshape(xp.asarray(experts_read, dtype=xp.int32),
                                (1,)))
    parts.append(xp.reshape(xp.asarray(n_alive, dtype=xp.int32), (1,)))
    return xp.concatenate(parts)


def unpack_chunk(buf, n_slots: int, chunk_len: int,
                 spec: bool = False, moe: bool = False,
                 sel=False) -> ChunkResult:
    """Inverse of ``pack_chunk`` (always numpy — this is the host side)."""
    buf = np.asarray(buf)
    want = packed_chunk_size(n_slots, chunk_len, spec=spec, moe=moe, sel=sel)
    if buf.shape != (want,):
        raise ValueError(
            f"packed chunk buffer has shape {buf.shape}, expected ({want},) "
            f"for n_slots={n_slots} chunk_len={chunk_len} spec={spec}"
            + (" moe=True" if moe else "") + (f" sel={sel}" if sel else ""))
    nt = n_slots * chunk_len
    drafted = accepted = None
    if spec:
        drafted = buf[nt + 3 * n_slots:nt + 4 * n_slots].astype(np.int32)
        accepted = buf[nt + 4 * n_slots:nt + 5 * n_slots].astype(np.int32)
    return ChunkResult(
        tokens=buf[:nt].reshape(n_slots, chunk_len),
        done=buf[nt:nt + n_slots].astype(bool),
        lengths=buf[nt + n_slots:nt + 2 * n_slots].astype(np.int32),
        health=buf[nt + 2 * n_slots:nt + 3 * n_slots].astype(np.int32),
        n_alive=int(buf[-1]),
        drafted=drafted,
        accepted=accepted,
        experts_read=int(buf[-2]) if moe else None,
        sel_rows=(tuple(int(n) for n in buf[
            len(buf) - (2 if moe else 1) - _sel_words(sel):
            len(buf) - (2 if moe else 1)]) if sel else None),
    )


def consume_chunk_row(tokens_row, done: bool, length: int,
                      already_emitted: int, chunk_len: int,
                      eos_ids) -> Tuple[List[int], Optional[str]]:
    """Consume one slot's row of a packed chunk under DEVICE-side
    termination. Returns ``(new_ids, finish)`` where ``finish`` is
    ``"stop"`` / ``"length"`` / ``None``.

    The device already decided termination; the host only recovers the
    valid token span (``length - already_emitted``) and the finish
    *reason*: a done slot whose next row entry is an EOS id stopped on
    EOS (the EOS itself is never emitted, matching the host-scan
    semantics); any other done slot exhausted its token budget. Shared by
    the jax batcher and the fake chunked engine so the two can never
    disagree on the contract."""
    v = max(0, min(int(length) - already_emitted, chunk_len))
    new_ids = [int(t) for t in tokens_row[:v]]
    finish = None
    if done:
        if v < chunk_len and int(tokens_row[v]) in eos_ids:
            finish = "stop"
        else:
            finish = "length"
    return new_ids, finish


def scan_chunk_row(tokens_row, already_emitted: int, eos_ids,
                   max_tokens: int) -> Tuple[List[int], Optional[str], int]:
    """Legacy HOST-side termination scan (``DEVICE_TERMINATION=false``):
    walk the row until EOS or the token budget. Returns
    ``(new_ids, finish, wasted_steps)`` — ``wasted_steps`` counts decode
    steps the device executed past the slot's termination point (the
    waste the device-resident done mask eliminates)."""
    new_ids: List[int] = []
    finish = None
    steps = 0
    for tid in tokens_row:
        steps += 1
        tid = int(tid)
        if tid in eos_ids:
            finish = "stop"
            break
        new_ids.append(tid)
        if already_emitted + len(new_ids) >= max_tokens:
            finish = "length"
            break
    wasted = len(tokens_row) - steps if finish is not None else 0
    return new_ids, finish, wasted


@dataclass
class RequestExport:
    """Live, host-readable view of one request's recoverable state.

    The fleet layer (engine/fleet.py) hands one of these to the engine
    when it submits a request; the engine's scheduler keeps ``ids``
    pointed at the generated-so-far token ids (a fresh list is assigned
    on every update, so a cross-thread reader always sees a consistent
    snapshot). Together with the per-request sampling seed this is the
    PORTABLE half of the PR 5 reset-and-replay contract: (prompt,
    generated-prefix ids, seed) is everything needed to re-splice the
    request onto a DIFFERENT engine replica and continue the transcript
    bit-identically — nothing recoverable is welded to one engine's
    slots. That holds for a model with a recurrent state too (ISSUE 33):
    the state is NOT exported; a resume (preemption, replay, quarantine
    re-splice, migration) is seated from the nearest snapshot its chain
    finds on the target's radix tree — in practice its prompt's last
    block edge — or from token 0, and re-runs what follows."""

    ids: List[int] = field(default_factory=list)
    #: block-paged KV pool (ISSUE 10): the pool block ids this request's
    #: table currently maps on its engine, updated at admission and at
    #: every table growth. Block ids are ENGINE-LOCAL (a migration
    #: target re-derives its own chain via its radix tree — shared
    #: prefixes re-map instead of re-prefilling); carried here so
    #: quarantine re-splice, preemption resume, and the debug surfaces
    #: can see a request's block footprint.
    blocks: List[int] = field(default_factory=list)
    #: set by the fleet BEFORE cancelling a losing hedge branch: tokens
    #: this dispatch emitted were never forwarded to the client (the
    #: winning branch's bytes were), so the engine's finish accounting
    #: must bill them as hedge_loser burn, not delivered goodput.
    discard: bool = False
    #: weight rollout (ISSUE 13): the checkpoint version of the engine
    #: that generated ``ids``, stamped at submit. A transcript is a
    #: function of the weights, so a cross-version re-splice of these
    #: ids cannot be byte-identical — the fleet router pins migration,
    #: hedging, and replay failover to same-version replicas only.
    weights_version: str = ""


@dataclass
class EngineResult:
    """One completed generation with phase timings."""

    text: str
    prompt_tokens: int = 0
    completion_tokens: int = 0
    queue_ms: float = 0.0
    # Engine-dependent: the single-sequence engine reports the device
    # prefill span; the continuous-batching engine reports admission
    # latency (admit → first token), which includes pipeline wait.
    prefill_ms: float = 0.0
    decode_ms: float = 0.0
    # Host-side detokenization time (token IDs → text pieces), accumulated
    # across the generation. Subset of decode_ms wall time on engines that
    # interleave detok with decode; 0 when the engine doesn't measure it.
    detok_ms: float = 0.0
    ttft_ms: float = 0.0
    prefix_cache_hit: bool = False
    finish_reason: str = "stop"  # stop | length | abort
    engine: str = ""
    # Weight rollout (ISSUE 13): the checkpoint version of the weights
    # that produced this text ("" for engines without versioning).
    weights_version: str = ""
    # Graceful degradation (ISSUE 20): True when the engine truncated
    # this generation short of a natural finish (KV pool starvation) —
    # the client must see the cut, not mistake it for a model stop.
    degraded: bool = False

    @property
    def tokens_per_sec(self) -> float:
        if self.decode_ms <= 0 or self.completion_tokens <= 0:
            return 0.0
        return self.completion_tokens / (self.decode_ms / 1000.0)


@runtime_checkable
class Engine(Protocol):
    """Async generation interface behind the service layer.

    ``generate`` returns the raw model text; output parsing/safety
    validation stay in the service layer (the reference put them inside the
    LangChain chain, app.py:118 — keeping them outside the engine lets every
    backend share one validator).
    """

    name: str

    @property
    def ready(self) -> bool:  # readiness-gated /health (SURVEY.md §3.3)
        ...

    async def start(self) -> None:
        """Load weights, compile, warm up. Must be called before generate."""
        ...

    async def stop(self, drain_secs: float = 0.0) -> None:
        """Graceful drain/shutdown.

        With ``drain_secs > 0`` the engine first stops accepting work
        (``ready`` drops, new ``generate`` calls raise EngineUnavailable)
        and waits up to that long for in-flight requests to finish before
        tearing down; 0 aborts them immediately."""
        ...

    async def generate(
        self,
        prompt: str,
        *,
        max_tokens: int = 128,
        temperature: float = 0.0,
        timeout: Optional[float] = None,
    ) -> EngineResult:
        ...

    def generate_stream(
        self,
        prompt: str,
        *,
        max_tokens: int = 128,
        temperature: float = 0.0,
        timeout: Optional[float] = None,
    ) -> AsyncIterator[str]:
        """Yield decoded text increments (for the streaming /execute agent
        loop, BASELINE config 5)."""
        ...
