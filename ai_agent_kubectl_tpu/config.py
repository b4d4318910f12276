"""Typed environment-variable configuration.

Rebuilds the reference's config layer (app.py:23-36, .env-sample:1-25) as a
frozen dataclass parsed once at startup. Every reference knob is preserved
verbatim (``API_AUTH_KEY``, ``CACHE_MAXSIZE``, ``CACHE_TTL``, ``LLM_TIMEOUT``,
``EXECUTION_TIMEOUT``, ``RATE_LIMIT``, ``LOG_LEVEL``, ``PORT``, ``HOST``).
The reference's ``OPENAI_*`` knobs are replaced by local-engine knobs
(``MODEL_NAME``, ``MODEL_PATH``, mesh/dtype/sequence/batch settings); an
OpenAI-compatible client engine is still available for parity with the
reference's remote path (``ENGINE=openai``, honouring ``OPENAI_BASE_URL``).

A minimal ``.env`` loader replaces python-dotenv (reference app.py:24): lines
of ``KEY=VALUE``, ``#`` comments, optional ``export`` prefix, single/double
quote stripping. Existing process env always wins (dotenv semantics).
"""

from __future__ import annotations

import os
import re
from dataclasses import dataclass, field, fields
from pathlib import Path
from typing import Optional, Tuple


#: Where the engine keeps XLA's persistent compilation cache when
#: JAX_COMPILATION_CACHE_DIR does not place it (engine/jax_engine.py::
#: _setup_compile_cache): one fixed, git-ignored directory at the root of
#: the checkout. Not a setting — a deployment moves the cache with JAX's
#: own variable.
DEFAULT_COMPILE_CACHE_DIR = str(
    Path(__file__).resolve().parent.parent / ".jax_cache")


def load_env_file(path: str | os.PathLike = ".env", *, override: bool = False) -> dict:
    """Parse a .env file into os.environ. Returns the parsed mapping.

    Missing file is not an error (matches dotenv behaviour the reference
    relies on at app.py:24).
    """
    parsed: dict[str, str] = {}
    p = Path(path)
    if not p.is_file():
        return parsed
    for raw in p.read_text().splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        if line.startswith("export "):
            line = line[len("export "):].lstrip()
        if "=" not in line:
            continue
        key, _, value = line.partition("=")
        key = key.strip()
        value = value.strip()
        if len(value) >= 2 and value[0] == value[-1] and value[0] in ("'", '"'):
            value = value[1:-1]
        else:
            # Strip trailing inline comment on unquoted values.
            value = value.split(" #", 1)[0].rstrip()
        if not key:
            continue
        parsed[key] = value
        if override or key not in os.environ:
            os.environ[key] = value
    return parsed


_RATE_RE = re.compile(
    r"^\s*(\d+)\s*(?:/|\s+per\s+)\s*(\d*)\s*(second|minute|hour|day)s?\s*$",
    re.IGNORECASE,
)

_PERIOD_SECONDS = {"second": 1, "minute": 60, "hour": 3600, "day": 86400}


def parse_rate_limit(spec: str) -> Tuple[int, float]:
    """Parse a slowapi-style rate string ("10/minute", "5 per 30 second")
    into (count, window_seconds). Reference default: "10/minute"
    (app.py:32)."""
    m = _RATE_RE.match(spec)
    if not m:
        raise ValueError(f"Invalid rate limit spec: {spec!r}")
    count = int(m.group(1))
    multiple = int(m.group(2)) if m.group(2) else 1
    window = multiple * _PERIOD_SECONDS[m.group(3).lower()]
    return count, float(window)


def _mesh_device_count(spec: str) -> int:
    """Device count a MESH_SHAPE/DCN_MESH_SHAPE spec asks for (product
    of its axis sizes; 1 for empty). A jax-free mirror of
    parallel/mesh.py::MeshConfig.parse's arithmetic — config validation
    must not import jax (the fake/openai deployments stay jax-free),
    and malformed axis names are the engine's error to raise, so
    unknown parts simply count their integer value."""
    total = 1
    for part in filter(None, (p.strip() for p in (spec or "").split(","))):
        _, _, val = part.replace(":", "=").partition("=")
        try:
            total *= max(1, int(val))
        except ValueError:
            continue
    return total


#: jax-free mirror of parallel/mesh.py::MeshConfig.parse's alias map —
#: config validation must not import jax (the fake/openai deployments
#: stay jax-free), but the spec-decode capability check (ISSUE 18)
#: needs to know WHICH axes a mesh spec scales, not just how many
#: devices it asks for.
_MESH_AXIS_ALIASES = {
    "dp": "data", "data": "data",
    "ep": "expert", "expert": "expert",
    "pp": "pipe", "pipe": "pipe",
    "sp": "seq", "seq": "seq",
    "tp": "model", "model": "model",
}

#: axes speculative decoding cannot serve under: the spec pool's blocks
#: are a shared cross-slot structure (never shard over data/pipe/seq)
#: and the draft stack rides the mesh whole (no pipeline split).
_SPEC_UNSHARDABLE_AXES = frozenset({"data", "pipe", "seq"})


def _mesh_unshardable_axes(spec: str) -> set:
    """Canonical names of >1 data/pipe/seq axes a MESH_SHAPE /
    DCN_MESH_SHAPE spec asks for — the combinations SPEC_DECODE refuses
    (ISSUE 18). Unknown axis names are the engine's error to raise and
    are ignored here, mirroring ``_mesh_device_count``."""
    out = set()
    for part in filter(None, (p.strip() for p in (spec or "").split(","))):
        name, _, val = part.replace(":", "=").partition("=")
        canon = _MESH_AXIS_ALIASES.get(name.strip().lower())
        try:
            size = int(val)
        except ValueError:
            continue
        if canon in _SPEC_UNSHARDABLE_AXES and size > 1:
            out.add(canon)
    return out


def _env_str(name: str, default: Optional[str]) -> Optional[str]:
    v = os.getenv(name)
    return v if v not in (None, "") else default


def _env_int(name: str, default: int) -> int:
    v = os.getenv(name)
    return int(v) if v not in (None, "") else default


def _env_float(name: str, default: float) -> float:
    v = os.getenv(name)
    return float(v) if v not in (None, "") else default


def _env_bool(name: str, default: bool) -> bool:
    v = os.getenv(name)
    if v in (None, ""):
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


@dataclass(frozen=True)
class ServiceConfig:
    """Everything the serving layer needs; reference knobs preserved."""

    # --- reference knobs, verbatim (app.py:27-33, 394-395) ---
    api_auth_key: Optional[str] = None      # API_AUTH_KEY; auth disabled if unset
    cache_maxsize: int = 100                # CACHE_MAXSIZE
    cache_ttl: float = 300.0                # CACHE_TTL seconds
    llm_timeout: float = 60.0               # LLM_TIMEOUT seconds
    execution_timeout: float = 30.0         # EXECUTION_TIMEOUT seconds
    rate_limit: str = "10/minute"           # RATE_LIMIT
    log_level: str = "INFO"                 # LOG_LEVEL
    # Log line format: "text" keeps the reference's human format; "json"
    # emits one JSON object per line, stamped with the active request ID
    # (obs/trace.py) so a flight-recorder lookup and a log grep meet on
    # the same key.
    log_format: str = "text"                # LOG_FORMAT: text | json
    host: str = "0.0.0.0"                   # HOST
    port: int = 8000                        # PORT
    # Honour X-Forwarded-For for rate-limit keying ONLY behind a trusted
    # proxy — a direct client could otherwise mint a fresh quota per request.
    trust_proxy_headers: bool = False       # TRUST_PROXY_HEADERS

    # --- engine selection (replaces OPENAI_* block, app.py:34-36) ---
    engine: str = "jax"                     # ENGINE: jax | jax-batched | fake | openai
                                            #   "jax" serves through the continuous-
                                            #   batching scheduler when
                                            #   DECODE_BATCH_SIZE > 1 (the default)
    model_name: str = "toy-8m"              # MODEL_NAME (registry key)
    model_path: Optional[str] = None        # MODEL_PATH (checkpoint dir)
    tokenizer_path: Optional[str] = None    # TOKENIZER_PATH

    # --- engine knobs ---
    dtype: str = "bfloat16"                 # DTYPE
    # Weight-only quantization: int8 (ops/quant.py) halves projection
    # weight bytes; int4 (ops/quant4.py, Pallas packed-nibble matmul,
    # group-wise scales) halves them again — decode is weight-read-bound,
    # so near-proportional throughput for large dense models. int4 is
    # single-chip only (falls back to int8 under a mesh). "" disables.
    quant: str = ""                         # QUANT: "" | int8 | int4
    # int8 KV cache (ops/quant.py::QuantKV): halves the KV pool and the
    # per-step decode-attention HBM read — on HBM-capped single-chip
    # serving (7B-class) this doubles the decode batch that fits beside
    # the weights. Composes with every mesh axis incl. pipe (the stage
    # bodies tree-map QuantKV); over the pool it serves the ``gather``
    # regime (the ragged kernel reads bf16 KV).
    kv_quant: str = ""                      # KV_QUANT: "" | int8
    max_seq_len: int = 1024                 # MAX_SEQ_LEN
    max_new_tokens: int = 128               # MAX_NEW_TOKENS
    decode_batch_size: int = 8              # DECODE_BATCH_SIZE (continuous batching slots)
    # Decode-chunk length: tokens generated per jitted chunk dispatch.
    # Larger chunks amortize dispatch overhead but admit new requests at
    # coarser granularity (TTFT under load). 16 came from an earlier chip
    # run (chunk 32 measured -15% throughput and 2x TTFT), not re-measured.
    chunk_len: int = 16                     # CHUNK_LEN
    # Decode chunks kept in flight: 2 = one running on the device, one
    # queued behind it. The queued chunk covers the host's work between
    # chunks (6-12 ms a chunk on a local v5e against a chunk period of
    # 234-453 ms; the scheduler thread is 96-98% blocked in the fetch).
    # A prompt staged after chunk N is consumed rides chunk N+depth, so
    # every chunk beyond the second costs a request one chunk period
    # before its first token and covers nothing there (both depths
    # measured on every benchmark cell: PERF.md section 5, PR 36). 3 is
    # for a chip behind a link whose round trip outlasts a chunk.
    # Outputs do not depend on the depth; with DEVICE_TERMINATION=false
    # each chunk beyond the first also wastes a chunk of steps per
    # finished request.
    chunk_pipe_depth: int = 2               # CHUNK_PIPE_DEPTH
    # Device-resident request termination: the decode chunk compares each
    # sampled token against the EOS set and the per-slot max_tokens
    # budget INSIDE the jitted scan, freezes finished slots mid-chunk
    # (no further sampling/KV writes), and returns one packed buffer
    # [tokens, done_mask, live_lengths, n_alive] per chunk — one fetch
    # carries tokens AND termination, so the scheduler retires slots at
    # consume time instead of after a host-side EOS scan. false restores
    # the host-scan path (A/B comparisons; wasted_decode_steps_total then
    # shows what the mask saves).
    device_termination: bool = True         # DEVICE_TERMINATION
    prefill_buckets: str = "64,128,256,512,1024"  # PREFILL_BUCKETS (padded prefill shapes)
    temperature: float = 0.0                # TEMPERATURE (0 == greedy, matches app.py:109)
    # Sampling filters (apply when TEMPERATURE > 0): TOP_K keeps the k
    # highest logits (0 disables); TOP_P nucleus sampling (1.0 disables).
    # Static service config — both engines sample from the same filtered
    # distribution at the same settings (engine/sampling.py).
    top_k: int = 0                          # TOP_K
    top_p: float = 1.0                      # TOP_P
    attn_impl: str = "auto"                 # ATTN_IMPL: auto | dense | flash (prefill kernel)
    # MoE dispatch: "auto" uses expert-parallel all-to-all dispatch when
    # the mesh has expert>1, dense all-experts otherwise; "ep" forces the
    # dispatch path (a 1-device expert mesh is built if needed — how one
    # chip serves the real EP program); "dense" forces all-experts.
    moe_impl: str = "auto"                  # MOE_IMPL: auto | ep | dense
    # --- block-paged KV pool + radix prefix sharing (ISSUE 10) ---
    # Replace per-slot dense KV (every request owning an S_alloc-row
    # region — the thing that capped the batch at bs=64 on 7B int8) with
    # one shared [n_blocks, page, KV, hd] pool per layer + per-slot
    # block tables: a slot holds only the pages its live span needs, so
    # the same HBM admits ~S_alloc/avg_len x the slots (bs≈192+ on the
    # 8B geometry). false = the dense KV ladder (A/B; also the automatic
    # fallback under a serving mesh — pool TP sharding is ROADMAP 4).
    kv_pool: bool = True                    # KV_POOL
    # Pool page (tokens per block). Must divide the 128-token kv-limit
    # tile so every gather width is a whole page count; on a TPU the
    # engine raises it to 64 (smaller pages are grid-overhead-bound;
    # engine/regime.py::resolve_attention_regime).
    kv_pool_page: int = 16                  # KV_POOL_PAGE
    # Total pool blocks. 0 = auto: batch_size x pages-per-slot — the
    # dense HBM envelope, which sharing then oversubscribes. Sizing it
    # below auto oversubscribes explicitly: admission keeps working
    # until genuinely out (radix eviction reclaims cached blocks first),
    # then slots truncate at their current length instead of corrupting.
    kv_pool_blocks: int = 0                 # KV_POOL_BLOCKS
    # Radix-tree prefix sharing over the pool (engine/radix_cache.py):
    # concurrent users share the system prompt's blocks copy-on-write,
    # multi-turn /execute loops re-map their whole history instead of
    # re-prefilling it. false = pool without sharing (A/B).
    radix_cache: bool = True                # RADIX_CACHE
    # LRU budget (blocks) the radix tree may keep cached. 0 = auto
    # (a quarter of the pool).
    radix_lru_blocks: int = 0               # RADIX_LRU_BLOCKS
    # Snapshots of a recurrent state the device holds beside the block
    # pool, for a model with state-space layers (0 = auto, 4 a decode
    # slot; engine/kv_pool.py::StateStore). Ignored by every other model.
    state_snapshots: int = 0                # STATE_SNAPSHOTS
    # --- two-tier KV: host-RAM block offload (ISSUE 20) ---
    # Capacity (blocks) of the pinned host-RAM second tier behind the
    # radix tree: eviction under HBM pressure DEMOTES cold chains there
    # (CRC32-stamped) instead of discarding them, and a returning
    # session's match transparently onloads them back — checksum
    # verified, falling back to ordinary suffix prefill on any failure.
    # 0 disables the tier (eviction discards, the single-tier world).
    host_kv_blocks: int = 0                 # HOST_KV_BLOCKS
    # --- grammar-constrained decoding (ISSUE 11; constrain/) ---
    # Compile the kubectl grammar against the tokenizer into a token
    # FSM, mask logits device-side so only grammar-legal tokens can be
    # sampled (unsafe commands become unrepresentable, not merely
    # rejected), and fast-forward forced runs (single-successor chains)
    # as one suffix prefill instead of decoding token-by-token.
    # Requires DEVICE_TERMINATION (the FSM state word rides the decode
    # chunk's carry). Default off: A/B parity with unconstrained decode
    # is the acceptance gate.
    grammar_decode: bool = False            # GRAMMAR_DECODE
    # Base grammar profile: "default" (read-only + mutating verbs),
    # "readonly" (observation only — also what a background-tier tenant
    # is clamped to per request), or "permissive" (mask-everything A/B:
    # grammar plumbing active, language unconstrained).
    grammar_profile: str = "default"        # GRAMMAR_PROFILE
    # Minimum NET forced-run length worth a fast-forward splice: the
    # scheduler only splices when the forced chain exceeds what the
    # in-flight speculative chunks would decode anyway (their compute
    # is sunk; discarding them must buy more than it costs).
    grammar_forced_run_min: int = 4         # GRAMMAR_FORCED_RUN_MIN
    # --- speculative decoding (ISSUE 12; engine/batcher.py) ---
    # Run a small draft model (the 2B) that proposes SPEC_DRAFT_K tokens
    # per slot per verify step; ONE 7B forward over the k+1-token window
    # then verifies them all — more transcript tokens per 7B weight
    # read, the remaining single-chip lever once decode is pinned at the
    # int8 weight-read floor. Verification is exact-match against the
    # 7B's own seeded sample, so transcripts are byte-identical to
    # SPEC_DECODE=false at any k (the acceptance gate). Requires
    # DEVICE_TERMINATION (the accept/reject fold rides the chunk carry)
    # and the KV pool (dense/mesh layouts fall back to plain decode).
    spec_decode: bool = False               # SPEC_DECODE
    # Draft tokens proposed per verify step (>= 1). Throughput =
    # accepted-rate-dependent; greedy kubectl outputs accept at very
    # high rates, and acceptance is a first-class /metrics signal
    # (spec_acceptance_ratio).
    spec_draft_k: int = 4                   # SPEC_DRAFT_K
    # Draft model registry name; must share the target's tokenizer /
    # vocab (validated at boot).
    spec_draft_model: str = "gemma-2b-it"   # SPEC_DRAFT_MODEL
    # Draft checkpoint dir (unset = random init, toy/dev mode only).
    spec_draft_path: Optional[str] = None   # SPEC_DRAFT_PATH
    hbm_prefix_cache: bool = True           # HBM_PREFIX_CACHE (system-prompt prefix KV)
    # Scheduler watchdog: if the batch scheduler makes no progress for this
    # long while work is in flight (hung device dispatch), the engine is
    # marked degraded and every waiting request is failed. 0 disables.
    engine_watchdog_secs: float = 120.0     # ENGINE_WATCHDOG_SECS
    # Cold-start grace for the watchdog: until the scheduler has consumed
    # its first decode-pipeline entry — and while an admission (the
    # lazy-compile site) is mid-flight — no-progress is judged against
    # max(ENGINE_WATCHDOG_SECS, this), so a >2-minute cold 7B compile is
    # not mis-read as a hung dispatch that degrades the engine and fails
    # waiting slots. Steady-state hangs still trip at the watchdog value.
    engine_startup_grace_secs: float = 900.0  # ENGINE_STARTUP_GRACE_SECS
    # HBM budget (MB) for batched-admission scratch KV: group sizes whose
    # kpad × suffix-depth scratch rows exceed it are dropped per shape
    # (groups split smaller / fall back to singles). Bounds the admission
    # transient that, with the old full-depth scratch, kept bs=64 from
    # fitting beside 7B int8 weights. 0 = uncapped.
    admit_scratch_mb: int = 512             # ADMIT_SCRATCH_MB

    # --- engine fleet (engine/fleet.py; ROADMAP item 5's router step) ---
    # Replicated engines behind one facade: N engine replicas with
    # health-aware routing, cross-replica migration (seeded replay makes
    # a migrated request's transcript bit-identical), zero-downtime
    # drains, and hedged re-dispatch. 1 = no fleet layer (the default:
    # single engine, zero overhead).
    fleet_size: int = 1                     # FLEET_SIZE
    # Hedged re-dispatch: if the chosen replica produces no event within
    # this budget, the same request (same seed — identical bytes) is
    # raced on a second replica. 0 disables.
    fleet_hedge_ms: float = 0.0             # FLEET_HEDGE_MS
    # Prefix-affinity routing: keep multi-turn /execute agent loops on
    # the replica already holding their KV prefix.
    fleet_affinity: bool = True             # FLEET_AFFINITY
    # How many times one request may migrate across replicas before its
    # error propagates (bounds pathological flapping).
    fleet_migration_budget: int = 3         # FLEET_MIGRATION_BUDGET
    # Auto-rejoin: restart an ejected replica after this many seconds
    # (each rejoin needs a successful engine start). 0 = manual rejoin
    # only (drain/eject leaves the replica down until an operator acts).
    fleet_rejoin_secs: float = 0.0          # FLEET_REJOIN_SECS

    # --- zero-downtime weight rollout (ISSUE 13; engine/rollout.py) ---
    # Fraction of FRESH traffic the router steers at the canary replica
    # while a rollout observes it. Clamped to (0, 0.5] at boot — the
    # canary must never be able to starve the stable cohort's
    # interactive lane.
    rollout_canary_share: float = 0.1       # ROLLOUT_CANARY_SHARE
    # How long the canary serves its bounded share before the promotion
    # gate's verdict: canary-vs-stable on SLO burn (fast window),
    # goodput ratio, quarantine/grammar-dead-end counters, breaker.
    rollout_observe_secs: float = 60.0      # ROLLOUT_OBSERVE_SECS
    # Burn-gate factor: the canary rolls back when its fast-window burn
    # reaches this multiple of max(1.0, the stable cohort's burn) — a
    # fleet already burning from ambient load must not auto-roll a
    # canary back for matching it. >= 1.
    rollout_burn_gate: float = 2.0          # ROLLOUT_BURN_GATE

    # --- QoS ring (ISSUE 7; engine/qos.py) ---
    # Tenant tiers: "tenantKey:lane,..." mapping a tenant key (the API
    # key a client presents, else its client IP) to the HIGHEST lane it
    # may claim (interactive | batch | background). An X-Priority header
    # can lower a request below its tier but never raise it above.
    # Unlisted tenants default to QOS_DEFAULT_LANE.
    tenant_tiers: str = ""                  # TENANT_TIERS
    # Lane a request runs in when neither TENANT_TIERS nor X-Priority
    # names one. "interactive" keeps single-tenant deployments exactly
    # as fast as before the QoS ring existed.
    qos_default_lane: str = "interactive"   # QOS_DEFAULT_LANE
    # WDRR lane weights: one saturated scheduling round serves this many
    # requests per lane ("interactive:8,batch:4,background:1").
    lane_weights: str = ""                  # LANE_WEIGHTS
    # Per-tenant in-queue cap: a tenant with this many requests already
    # waiting is shed with a fast 429 (the flooding tenant's problem,
    # not everyone's 503). 0 = no cap below MAX_QUEUE_DEPTH.
    tenant_max_queue: int = 0               # TENANT_MAX_QUEUE
    # Per-session token budget (ISSUE 20): once a session (X-Session-ID
    # header) has been delivered this many completion tokens, its later
    # requests classify into the background lane — the session keeps
    # working, it just stops outranking fresh interactive traffic.
    # Graceful by design: never a reject. 0 disables budgets.
    qos_session_token_budget: int = 0       # QOS_SESSION_TOKEN_BUDGET
    # Preemptive decode: once a higher-lane request has queue-waited
    # this long with every slot busy, the scheduler exports the
    # cheapest lower-lane victim (PR 6 RequestExport path), frees its
    # slot, and re-enqueues it at the head of its tenant queue for a
    # bit-identical seeded replay. 0 disables preemption.
    preempt_wait_ms: float = 500.0          # PREEMPT_WAIT_MS
    # How many times one request may be preempted before it becomes
    # un-preemptable (victim selection skips it) — bounds livelock.
    preempt_budget: int = 2                 # PREEMPT_BUDGET
    # Interactive queue-wait SLO driving the AIMD brownout controller:
    # when interactive p95 queue wait breaches this, background's slot
    # share halves first (then batch); recovery is additive, batch
    # first. 0 disables the controller.
    slo_interactive_ms: float = 2000.0      # SLO_INTERACTIVE_MS

    # --- overload protection / failure containment ---
    # Bounded admission: the batcher sheds work with a fast 503 +
    # Retry-After once this many requests are queued for a decode slot,
    # instead of queueing doomed work until it 504s at llm_timeout.
    # 0 = unbounded (the pre-containment behaviour). Enforced by the
    # continuous-batching engine (the default); single-sequence jax /
    # fake / openai deployments rely on MAX_INFLIGHT_REQUESTS instead.
    max_queue_depth: int = 64               # MAX_QUEUE_DEPTH
    # HTTP-layer cap on concurrently-processing generation requests
    # (/kubectl-command + /kubectl-command/stream); excess sheds with a
    # fast 503 + Retry-After before touching the engine. 0 = unlimited.
    max_inflight_requests: int = 256        # MAX_INFLIGHT_REQUESTS
    # Serve rule-based FallbackEngine responses (degraded: true, HTTP 200)
    # instead of 503 while the circuit breaker is open / the engine fails.
    degraded_fallback: bool = False         # DEGRADED_FALLBACK
    # Circuit breaker around the engine: opens after this many engine
    # failures within breaker_window_secs (0 disables); after
    # breaker_recovery_secs one half-open probe re-closes it on success.
    breaker_threshold: int = 5              # BREAKER_THRESHOLD
    breaker_window_secs: float = 30.0       # BREAKER_WINDOW_SECS
    breaker_recovery_secs: float = 15.0     # BREAKER_RECOVERY_SECS
    # --- blast-radius containment (the INNER ring; engine/containment.py)
    # Device-side per-slot health detection in the decode chunk: NaN/Inf
    # logits and out-of-range sampled token ids trip a health word in the
    # packed chunk buffer, freezing the slot mid-chunk and feeding the
    # quarantine pass. false drops detection (step-exception containment
    # stays).
    slot_health_check: bool = True          # SLOT_HEALTH_CHECK
    # How many times one request may be solo-implicated in a poisoned
    # step (health bit, or isolated by bisection) and still be replayed;
    # past this it fails terminally with HTTP 410. 0 = quarantine on
    # first trip.
    quarantine_retry_budget: int = 1        # QUARANTINE_RETRY_BUDGET
    # Engine reset-and-replay rate limit (per rolling minute): past it
    # the engine stops resetting and fails the affected requests fast —
    # the errors feed the circuit breaker, which is the outer ring's
    # job. 0 = unlimited.
    engine_reset_max_per_min: int = 12      # ENGINE_RESET_MAX_PER_MIN
    # Fault-injection harness (testing/faults.py):
    # "admit:error:0.5,chunk:hang,generate:delay:2.0" — plus the
    # containment drills "decode:nan:<p>", "decode:poison_step",
    # "scheduler:die". Empty disables.
    fault_points: str = ""                  # FAULT_POINTS

    # --- observability ---
    # Flight recorder: keep the full span timeline of the last N requests
    # (including shed/degraded/errored) for /debug/requests lookups.
    flight_recorder_size: int = 256         # FLIGHT_RECORDER_SIZE
    # Goodput ledger (obs/ledger.py): classify every device decode step
    # delivered | replayed | preempted | hedge_loser | wasted_masked |
    # quarantine_burn, per lane (metrics) and per hashed tenant
    # (/debug/ledger only). false disables the accounting (the waste
    # counters it mirrors keep working).
    ledger_enable: bool = True              # LEDGER_ENABLE
    # TTFT SLO target (ms) for the burn-rate engine (obs/slo.py): a
    # finished request whose first token took longer than this breaches.
    # 0 disables the TTFT slo (queue-wait burn still runs off
    # SLO_INTERACTIVE_MS).
    slo_ttft_ms: float = 5000.0             # SLO_TTFT_MS
    # Turn-N TTFT SLO for returning sessions (ISSUE 20): judged ONLY
    # for radix-warm re-admissions (the match covered at least one full
    # page), so it prices exactly what the two-tier KV cache exists for
    # — a warm agent turn must start streaming this fast. 0 disables.
    slo_session_ttft_ms: float = 0.0        # SLO_SESSION_TTFT_MS
    # Burn-rate windows (seconds, ascending, at most 4 — each is a
    # metric label value): the classic fast/slow multi-window pair.
    slo_windows: str = "300,3600"           # SLO_WINDOWS
    # Success-rate objective the error budget is priced from: at 0.99,
    # 1% of samples may breach before burn rate 1.0.
    slo_objective: float = 0.99             # SLO_OBJECTIVE
    # --- perf-regression sentinel (ISSUE 15; obs/steptime.py) ---
    # Baseline envelope file for the step-time sentinel: JSON with a
    # step_time_ms table ({phase: {bucket|"default": ms}}) an operator
    # measured on their own chips (obs/steptime.py::load_baselines says
    # the format). Empty = no file; every digest then self-calibrates
    # from its first SENTINEL_MIN_SAMPLES samples. A set-but-unloadable
    # path refuses to boot.
    perf_baselines: str = ""                # PERF_BASELINES
    # Master switch for the always-on step-time digests + breach
    # detection (the digests are a bounded ring per (phase, bucket) —
    # the cost of leaving this on is one deque append per chunk cycle).
    sentinel_enable: bool = True            # SENTINEL_ENABLE
    # Samples kept per (phase, bucket) digest (the p50/p95/p99 window).
    sentinel_window: int = 256              # SENTINEL_WINDOW
    # Breach rule: recent p99 > factor x baseline trips the sentinel.
    sentinel_factor: float = 2.0            # SENTINEL_FACTOR
    # Samples required before a digest may breach (also the
    # self-calibration window when no file baseline covers the key).
    sentinel_min_samples: int = 16          # SENTINEL_MIN_SAMPLES
    # Incident-watcher evaluation period (seconds): a background task
    # polls the cheap health views for firing triggers this often.
    # 0 = no background watcher (triggers still evaluate at /metrics
    # scrapes and /debug/incidents reads).
    sentinel_eval_secs: float = 2.0         # SENTINEL_EVAL_SECS
    # --- incident capture (ISSUE 15; obs/incidents.py) ---
    # How many incident bundles the /debug/incidents ring retains.
    incident_ring: int = 8                  # INCIDENT_RING
    # Per-trigger cooldown: within it further firings of the same
    # trigger are counted suppressed but assemble NOTHING — capture
    # overhead can never cascade during the incident it is observing.
    incident_cooldown_secs: float = 60.0    # INCIDENT_COOLDOWN_SECS
    # Fast-window SLO burn at or above this fires the slo_fast_burn
    # trigger. 0 disables the burn trigger.
    incident_burn_threshold: float = 2.0    # INCIDENT_BURN_THRESHOLD
    # Attach a rate-limited jax.profiler capture of this many seconds
    # to each new bundle (jax engines only). 0 = off (the default —
    # captures are tens of MB and cost real device time).
    incident_profile_secs: float = 0.0      # INCIDENT_PROFILE_SECS
    # host_tier_thrash trigger sensitivity (ISSUE 20): both the demote
    # AND onload deltas since the last evaluation must reach this many
    # blocks to file a churn incident (one-way flow is warmup/drain,
    # not thrash). 0 disables the trigger.
    incident_thrash_min_blocks: int = 8     # INCIDENT_THRASH_MIN_BLOCKS
    # Optional canary-vs-stable step-time verdict in the weight-rollout
    # promotion gate: the canary rolls back when its decode p95 reaches
    # this multiple of the stable cohort's. 0 = off; >= 1 otherwise.
    rollout_steptime_gate: float = 0.0      # ROLLOUT_STEPTIME_GATE
    # Debug-endpoint token: when set, /debug/* additionally requires
    # X-Debug-Token (profiler captures and request timelines are
    # operator-facing, not client-facing). Unset = only API-key auth
    # (when enabled) guards them.
    debug_token: Optional[str] = None       # DEBUG_TOKEN
    # Graceful shutdown: stop accepting new requests, wait up to this long
    # for in-flight generations to finish, then abort what remains.
    drain_timeout_secs: float = 10.0        # DRAIN_TIMEOUT_SECS
    # --- parallelism knobs ---
    mesh_shape: str = ""                    # MESH_SHAPE e.g. "data:1,model:8"
    dcn_mesh_shape: str = ""                # DCN_MESH_SHAPE for multi-slice
    distributed_init: bool = False          # DISTRIBUTED_INIT (jax.distributed.initialize)
    coordinator_address: Optional[str] = None   # COORDINATOR_ADDRESS
    num_processes: int = 1                  # NUM_PROCESSES
    process_id: int = 0                     # PROCESS_ID

    # --- openai-compat engine (reference parity path, app.py:34-36) ---
    openai_api_key: Optional[str] = None    # OPENAI_API_KEY
    openai_model: str = "gpt-3.5-turbo"     # OPENAI_MODEL
    openai_base_url: Optional[str] = None   # OPENAI_BASE_URL

    # derived
    rate_limit_count: int = field(init=False, default=10)
    rate_limit_window: float = field(init=False, default=60.0)

    def __post_init__(self):
        count, window = parse_rate_limit(self.rate_limit)
        object.__setattr__(self, "rate_limit_count", count)
        object.__setattr__(self, "rate_limit_window", window)
        # Validate the QoS specs at boot — a typo'd tier or weight must
        # refuse to start, not silently skew the scheduler. (Lazy import:
        # config is the base layer; engine.qos only pulls stdlib +
        # engine.protocol.)
        self.tenant_tier_map
        self.lane_weight_map
        # SLO knobs (ISSUE 8): a typo'd window list or an objective
        # outside (0,1) must refuse to boot, not serve meaningless burn
        # rates.
        self.slo_window_list
        if not 0.0 < self.slo_objective < 1.0:
            raise ValueError(
                f"SLO_OBJECTIVE must be in (0, 1), got {self.slo_objective}")
        if self.slo_ttft_ms < 0:
            raise ValueError(
                f"SLO_TTFT_MS must be >= 0, got {self.slo_ttft_ms}")
        # Perf-regression sentinel + incident knobs (ISSUE 15): a
        # typo'd factor/window or an unloadable baselines file must
        # refuse to boot, not silently disarm the regression trigger.
        if self.sentinel_window < 8:
            raise ValueError(
                f"SENTINEL_WINDOW must be >= 8 samples, "
                f"got {self.sentinel_window}")
        if self.sentinel_factor < 1.0:
            raise ValueError(
                f"SENTINEL_FACTOR must be >= 1 (a factor below 1 would "
                f"trip on every healthy step), got {self.sentinel_factor}")
        if self.sentinel_min_samples < 1:
            raise ValueError(
                f"SENTINEL_MIN_SAMPLES must be >= 1, "
                f"got {self.sentinel_min_samples}")
        if self.sentinel_eval_secs < 0:
            raise ValueError(
                f"SENTINEL_EVAL_SECS must be >= 0 (0 = scrape-driven "
                f"only), got {self.sentinel_eval_secs}")
        if self.incident_ring < 1:
            raise ValueError(
                f"INCIDENT_RING must be >= 1, got {self.incident_ring}")
        if self.incident_cooldown_secs < 0:
            raise ValueError(
                f"INCIDENT_COOLDOWN_SECS must be >= 0, "
                f"got {self.incident_cooldown_secs}")
        if self.incident_burn_threshold < 0:
            raise ValueError(
                f"INCIDENT_BURN_THRESHOLD must be >= 0 (0 disables), "
                f"got {self.incident_burn_threshold}")
        if not 0.0 <= self.incident_profile_secs <= 30.0:
            raise ValueError(
                f"INCIDENT_PROFILE_SECS must be in [0, 30] (captures "
                f"are tens of MB each), got {self.incident_profile_secs}")
        if self.rollout_steptime_gate != 0.0 \
                and self.rollout_steptime_gate < 1.0:
            raise ValueError(
                f"ROLLOUT_STEPTIME_GATE must be 0 (off) or >= 1 (a "
                f"factor below 1 would roll back every healthy canary), "
                f"got {self.rollout_steptime_gate}")
        if self.perf_baselines:
            from .obs.steptime import load_baselines

            try:
                load_baselines(self.perf_baselines)
            except (OSError, ValueError, KeyError) as e:
                raise ValueError(
                    f"PERF_BASELINES {self.perf_baselines!r} failed to "
                    f"load: {e}") from e
        # KV pool knobs (ISSUE 10): the page must divide the 128-token
        # kv-limit tile (kv buckets are 128-tiled, so every attention
        # gather width must be a whole page count) and the prefill-chunk
        # alignment rides the same tile. A bad page must refuse to boot,
        # not mis-index the pool.
        if self.kv_pool_page < 1 or 128 % self.kv_pool_page:
            raise ValueError(
                f"KV_POOL_PAGE must divide the 128-token chunk/kv-limit "
                f"tile (8|16|32|64|128), got {self.kv_pool_page}")
        if self.kv_pool_blocks < 0:
            raise ValueError(
                f"KV_POOL_BLOCKS must be >= 0 (0 = auto), "
                f"got {self.kv_pool_blocks}")
        if self.state_snapshots < 0:
            raise ValueError(
                f"STATE_SNAPSHOTS must be >= 0 (0 = auto), "
                f"got {self.state_snapshots}")
        if self.radix_lru_blocks < 0:
            raise ValueError(
                f"RADIX_LRU_BLOCKS must be >= 0 (0 = auto), "
                f"got {self.radix_lru_blocks}")
        # Two-tier KV + session knobs (ISSUE 20): negative capacities
        # and budgets must refuse to boot, and the host tier only means
        # something over the block pool + radix tree it demotes from.
        if self.host_kv_blocks < 0:
            raise ValueError(
                f"HOST_KV_BLOCKS must be >= 0 (0 disables the host "
                f"tier), got {self.host_kv_blocks}")
        if self.host_kv_blocks > 0 and not (self.kv_pool
                                            and self.radix_cache):
            raise ValueError(
                "HOST_KV_BLOCKS requires KV_POOL=true and "
                "RADIX_CACHE=true (the host tier is the radix tree's "
                "demotion target — without the tree there is nothing "
                "to demote)")
        if self.slo_session_ttft_ms < 0:
            raise ValueError(
                f"SLO_SESSION_TTFT_MS must be >= 0 (0 disables), "
                f"got {self.slo_session_ttft_ms}")
        if self.qos_session_token_budget < 0:
            raise ValueError(
                f"QOS_SESSION_TOKEN_BUDGET must be >= 0 (0 disables), "
                f"got {self.qos_session_token_budget}")
        if self.incident_thrash_min_blocks < 0:
            raise ValueError(
                f"INCIDENT_THRASH_MIN_BLOCKS must be >= 0 (0 disables), "
                f"got {self.incident_thrash_min_blocks}")
        # Grammar knobs (ISSUE 11): a typo'd profile or an impossible
        # mode combination must refuse to boot, not silently serve
        # unconstrained output behind a knob that says otherwise.
        from .constrain.runtime import PROFILES

        if self.grammar_profile not in PROFILES:
            raise ValueError(
                f"GRAMMAR_PROFILE must be one of {PROFILES}, "
                f"got {self.grammar_profile!r}")
        if self.grammar_forced_run_min < 1:
            raise ValueError(
                f"GRAMMAR_FORCED_RUN_MIN must be >= 1, "
                f"got {self.grammar_forced_run_min}")
        if self.grammar_decode and not self.device_termination:
            raise ValueError(
                "GRAMMAR_DECODE requires DEVICE_TERMINATION=true (the "
                "FSM state word rides the decode chunk's carry)")
        if self.grammar_decode:
            # Boot-time cross-check (defense-in-depth satellite): every
            # safety-blocked verb must be absent from every profile.
            from .constrain import assert_safety_consistent

            assert_safety_consistent()
        # Weight-rollout knobs (ISSUE 13): a canary share outside
        # (0, 0.5] either disables the observe phase silently or lets
        # the canary starve the stable cohort — both refuse to boot.
        if not 0.0 < self.rollout_canary_share <= 0.5:
            raise ValueError(
                f"ROLLOUT_CANARY_SHARE must be in (0, 0.5] (the canary "
                f"may never take more fresh traffic than the stable "
                f"cohort), got {self.rollout_canary_share}")
        if self.rollout_observe_secs < 0:
            raise ValueError(
                f"ROLLOUT_OBSERVE_SECS must be >= 0, "
                f"got {self.rollout_observe_secs}")
        if self.rollout_burn_gate < 1.0:
            raise ValueError(
                f"ROLLOUT_BURN_GATE must be >= 1 (a factor below the "
                f"sustainable burn rate would roll back every healthy "
                f"canary), got {self.rollout_burn_gate}")
        # Speculative-decode knobs (ISSUE 12): an impossible combination
        # or an unknown/mismatched draft model must refuse to boot, not
        # silently serve plain decode behind a knob that says otherwise.
        if self.spec_decode:
            if not self.device_termination:
                raise ValueError(
                    "SPEC_DECODE requires DEVICE_TERMINATION=true (the "
                    "accept/reject fold rides the decode chunk's carry)")
            if self.spec_draft_k < 1:
                raise ValueError(
                    f"SPEC_DRAFT_K must be >= 1, got {self.spec_draft_k}")
            from .models.config import get_config as _get_model_config

            try:
                draft = _get_model_config(self.spec_draft_model)
            except KeyError:
                raise ValueError(
                    f"SPEC_DRAFT_MODEL {self.spec_draft_model!r} is not "
                    f"a known model registry name") from None
            try:
                target = _get_model_config(self.model_name)
            except KeyError:
                target = None   # MODEL_NAME errors are the engine's job
            if (target is not None
                    and draft.vocab_size != target.vocab_size):
                raise ValueError(
                    f"SPEC_DRAFT_MODEL {self.spec_draft_model!r} "
                    f"(vocab {draft.vocab_size}) does not share "
                    f"{self.model_name!r}'s vocab ({target.vocab_size}) "
                    f"— draft and verifier must use one tokenizer")
            # ISSUE 18: the draft world is mesh-native under tp/ep —
            # draft cache/params shard per parallel/sharding.py's
            # draft_cache_specs and the spec chunk compiles against the
            # mesh — so SPEC_DECODE + MESH_SHAPE now composes. What
            # remains genuinely unshardable is the spec pool's
            # requirement (blocks never shard over data/pipe/seq) plus
            # the draft's whole-stack ride of the mesh: refuse only a
            # >1 data/pipe/seq axis (the engine re-checks at start for
            # direct construction; the capability check stays jax-free).
            bad = sorted(
                _mesh_unshardable_axes(self.mesh_shape)
                | _mesh_unshardable_axes(self.dcn_mesh_shape))
            if bad:
                raise ValueError(
                    f"SPEC_DECODE does not compose with a mesh that has "
                    f"a >1 {'/'.join(bad)} axis (MESH_SHAPE="
                    f"{self.mesh_shape!r} DCN_MESH_SHAPE="
                    f"{self.dcn_mesh_shape!r}): the spec KV pool's "
                    f"blocks and the draft verify window shard over "
                    f"tp/ep only — use a tensor/expert-parallel mesh or "
                    f"disable one of them")

    @property
    def tenant_tier_map(self) -> dict:
        from .engine.qos import LANES, parse_tenant_tiers

        if self.qos_default_lane not in LANES:
            raise ValueError(
                f"QOS_DEFAULT_LANE must be one of {LANES}, "
                f"got {self.qos_default_lane!r}")
        return parse_tenant_tiers(self.tenant_tiers)

    @property
    def lane_weight_map(self) -> dict:
        from .engine.qos import parse_lane_weights

        return parse_lane_weights(self.lane_weights)

    @property
    def slo_window_list(self) -> Tuple[int, ...]:
        from .obs.slo import parse_slo_windows

        return parse_slo_windows(self.slo_windows)

    @property
    def auth_enabled(self) -> bool:
        return bool(self.api_auth_key)

    @property
    def prefill_bucket_list(self) -> Tuple[int, ...]:
        return tuple(sorted(int(b) for b in self.prefill_buckets.split(",") if b.strip()))

    @classmethod
    def from_env(cls, env_file: str | os.PathLike | None = ".env") -> "ServiceConfig":
        if env_file is not None:
            load_env_file(env_file)
        return cls(
            api_auth_key=_env_str("API_AUTH_KEY", None),
            cache_maxsize=_env_int("CACHE_MAXSIZE", 100),
            cache_ttl=_env_float("CACHE_TTL", 300.0),
            llm_timeout=_env_float("LLM_TIMEOUT", 60.0),
            execution_timeout=_env_float("EXECUTION_TIMEOUT", 30.0),
            rate_limit=_env_str("RATE_LIMIT", "10/minute"),
            log_level=(_env_str("LOG_LEVEL", "INFO") or "INFO").upper(),
            log_format=(_env_str("LOG_FORMAT", "text") or "text").lower(),
            host=_env_str("HOST", "0.0.0.0"),
            port=_env_int("PORT", 8000),
            # TRUST_PROXY is the conventional short alias (fronting
            # router tiers set it); TRUST_PROXY_HEADERS wins when both
            # are present.
            trust_proxy_headers=_env_bool(
                "TRUST_PROXY_HEADERS", _env_bool("TRUST_PROXY", False)),
            engine=(_env_str("ENGINE", "jax") or "jax").lower(),
            model_name=_env_str("MODEL_NAME", "toy-8m"),
            model_path=_env_str("MODEL_PATH", None),
            tokenizer_path=_env_str("TOKENIZER_PATH", None),
            dtype=_env_str("DTYPE", "bfloat16"),
            quant=(_env_str("QUANT", "") or "").lower(),
            kv_quant=(_env_str("KV_QUANT", "") or "").lower(),
            max_seq_len=_env_int("MAX_SEQ_LEN", 1024),
            max_new_tokens=_env_int("MAX_NEW_TOKENS", 128),
            decode_batch_size=_env_int("DECODE_BATCH_SIZE", 8),
            chunk_len=_env_int("CHUNK_LEN", 16),
            chunk_pipe_depth=_env_int("CHUNK_PIPE_DEPTH", 2),
            device_termination=_env_bool("DEVICE_TERMINATION", True),
            prefill_buckets=_env_str("PREFILL_BUCKETS", "64,128,256,512,1024"),
            temperature=_env_float("TEMPERATURE", 0.0),
            top_k=_env_int("TOP_K", 0),
            top_p=_env_float("TOP_P", 1.0),
            attn_impl=(_env_str("ATTN_IMPL", "auto") or "auto").lower(),
            moe_impl=(_env_str("MOE_IMPL", "auto") or "auto").lower(),
            kv_pool=_env_bool("KV_POOL", True),
            kv_pool_page=_env_int("KV_POOL_PAGE", 16),
            kv_pool_blocks=_env_int("KV_POOL_BLOCKS", 0),
            radix_cache=_env_bool("RADIX_CACHE", True),
            radix_lru_blocks=_env_int("RADIX_LRU_BLOCKS", 0),
            state_snapshots=_env_int("STATE_SNAPSHOTS", 0),
            host_kv_blocks=_env_int("HOST_KV_BLOCKS", 0),
            grammar_decode=_env_bool("GRAMMAR_DECODE", False),
            grammar_profile=(_env_str("GRAMMAR_PROFILE", "default")
                             or "default").lower(),
            grammar_forced_run_min=_env_int("GRAMMAR_FORCED_RUN_MIN", 4),
            spec_decode=_env_bool("SPEC_DECODE", False),
            spec_draft_k=_env_int("SPEC_DRAFT_K", 4),
            spec_draft_model=_env_str("SPEC_DRAFT_MODEL", "gemma-2b-it"),
            spec_draft_path=_env_str("SPEC_DRAFT_PATH", None),
            hbm_prefix_cache=_env_bool("HBM_PREFIX_CACHE", True),
            engine_watchdog_secs=_env_float("ENGINE_WATCHDOG_SECS", 120.0),
            engine_startup_grace_secs=_env_float(
                "ENGINE_STARTUP_GRACE_SECS", 900.0),
            admit_scratch_mb=_env_int("ADMIT_SCRATCH_MB", 512),
            fleet_size=_env_int("FLEET_SIZE", 1),
            fleet_hedge_ms=_env_float("FLEET_HEDGE_MS", 0.0),
            fleet_affinity=_env_bool("FLEET_AFFINITY", True),
            fleet_migration_budget=_env_int("FLEET_MIGRATION_BUDGET", 3),
            fleet_rejoin_secs=_env_float("FLEET_REJOIN_SECS", 0.0),
            rollout_canary_share=_env_float("ROLLOUT_CANARY_SHARE", 0.1),
            rollout_observe_secs=_env_float("ROLLOUT_OBSERVE_SECS", 60.0),
            rollout_burn_gate=_env_float("ROLLOUT_BURN_GATE", 2.0),
            tenant_tiers=_env_str("TENANT_TIERS", "") or "",
            qos_default_lane=(
                _env_str("QOS_DEFAULT_LANE", "interactive")
                or "interactive").lower(),
            lane_weights=_env_str("LANE_WEIGHTS", "") or "",
            tenant_max_queue=_env_int("TENANT_MAX_QUEUE", 0),
            qos_session_token_budget=_env_int(
                "QOS_SESSION_TOKEN_BUDGET", 0),
            preempt_wait_ms=_env_float("PREEMPT_WAIT_MS", 500.0),
            preempt_budget=_env_int("PREEMPT_BUDGET", 2),
            slo_interactive_ms=_env_float("SLO_INTERACTIVE_MS", 2000.0),
            max_queue_depth=_env_int("MAX_QUEUE_DEPTH", 64),
            max_inflight_requests=_env_int("MAX_INFLIGHT_REQUESTS", 256),
            degraded_fallback=_env_bool("DEGRADED_FALLBACK", False),
            breaker_threshold=_env_int("BREAKER_THRESHOLD", 5),
            breaker_window_secs=_env_float("BREAKER_WINDOW_SECS", 30.0),
            breaker_recovery_secs=_env_float("BREAKER_RECOVERY_SECS", 15.0),
            slot_health_check=_env_bool("SLOT_HEALTH_CHECK", True),
            quarantine_retry_budget=_env_int("QUARANTINE_RETRY_BUDGET", 1),
            engine_reset_max_per_min=_env_int("ENGINE_RESET_MAX_PER_MIN", 12),
            fault_points=_env_str("FAULT_POINTS", "") or "",
            flight_recorder_size=_env_int("FLIGHT_RECORDER_SIZE", 256),
            ledger_enable=_env_bool("LEDGER_ENABLE", True),
            slo_ttft_ms=_env_float("SLO_TTFT_MS", 5000.0),
            slo_session_ttft_ms=_env_float("SLO_SESSION_TTFT_MS", 0.0),
            slo_windows=_env_str("SLO_WINDOWS", "300,3600") or "300,3600",
            slo_objective=_env_float("SLO_OBJECTIVE", 0.99),
            perf_baselines=_env_str("PERF_BASELINES", "") or "",
            sentinel_enable=_env_bool("SENTINEL_ENABLE", True),
            sentinel_window=_env_int("SENTINEL_WINDOW", 256),
            sentinel_factor=_env_float("SENTINEL_FACTOR", 2.0),
            sentinel_min_samples=_env_int("SENTINEL_MIN_SAMPLES", 16),
            sentinel_eval_secs=_env_float("SENTINEL_EVAL_SECS", 2.0),
            incident_ring=_env_int("INCIDENT_RING", 8),
            incident_cooldown_secs=_env_float(
                "INCIDENT_COOLDOWN_SECS", 60.0),
            incident_burn_threshold=_env_float(
                "INCIDENT_BURN_THRESHOLD", 2.0),
            incident_profile_secs=_env_float(
                "INCIDENT_PROFILE_SECS", 0.0),
            incident_thrash_min_blocks=_env_int(
                "INCIDENT_THRASH_MIN_BLOCKS", 8),
            rollout_steptime_gate=_env_float(
                "ROLLOUT_STEPTIME_GATE", 0.0),
            debug_token=_env_str("DEBUG_TOKEN", None),
            drain_timeout_secs=_env_float("DRAIN_TIMEOUT_SECS", 10.0),
            mesh_shape=_env_str("MESH_SHAPE", "") or "",
            dcn_mesh_shape=_env_str("DCN_MESH_SHAPE", "") or "",
            distributed_init=_env_bool("DISTRIBUTED_INIT", False),
            coordinator_address=_env_str("COORDINATOR_ADDRESS", None),
            num_processes=_env_int("NUM_PROCESSES", 1),
            process_id=_env_int("PROCESS_ID", 0),
            openai_api_key=_env_str("OPENAI_API_KEY", None),
            openai_model=_env_str("OPENAI_MODEL", "gpt-3.5-turbo"),
            openai_base_url=_env_str("OPENAI_BASE_URL", None),
        )

    def describe(self) -> dict:
        """Loggable, secret-free view of the config."""
        d = {f.name: getattr(self, f.name) for f in fields(self) if f.init}
        for secret in ("api_auth_key", "openai_api_key", "debug_token"):
            if d.get(secret):
                d[secret] = "***"
        if d.get("tenant_tiers"):
            # Tenant keys are API keys; log only the lane assignments.
            d["tenant_tiers"] = ",".join(
                f"***:{lane}" for lane in self.tenant_tier_map.values())
        return d
