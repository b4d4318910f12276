"""HTTP API layer (reference app.py:130-138, 283-400) on aiohttp.

Endpoints (same contract and status codes as the reference):

- ``POST /kubectl-command`` — NL query → validated kubectl command
  (app.py:284-346). 200/401/422(unsafe)/429/500/503/504. Pydantic
  validation errors → 400 (invalid input query). Deliberate choice on quirk
  B1 (SURVEY.md §2.3): generation and execution remain fully separated — the
  hardcoded success-metadata stub is replaced by *real* generation-phase
  metadata, and ``execution_result``/``execution_error`` stay None here.
- ``POST /execute`` — run a validated kubectl command (app.py:356-389).
  200/400(unsafe)/401/429/500; execution errors are structured 200s with
  ``execution_error`` set (B2 fixed in executor.py).
- ``POST /kubectl-command/stream`` — TPU-native addition: streams generated
  tokens as SSE for the multi-turn agent loop (BASELINE config 5).
- ``GET /health`` — readiness-gated (fixes static health, app.py:348-354).
- ``GET /metrics`` — Prometheus (app.py:136-138).

Cross-cutting (middleware): per-IP sliding-window rate limit → 429 with
Retry-After; API-key auth via ``X-API-Key`` (app.py:140-151), disabled when
``API_AUTH_KEY`` unset; HTTP request counters/latency histograms.
"""

from __future__ import annotations

import asyncio
import hashlib
import hmac
import json
import logging
import time
from contextlib import nullcontext, suppress
from typing import Optional

from aiohttp import web
from pydantic import ValidationError

from ..config import ServiceConfig
from ..engine.fallback import FallbackEngine
from ..engine.protocol import (Engine, EngineOverloaded, EngineResult,
                               EngineUnavailable, GenerationTimeout,
                               RequestQuarantined, TenantOverloaded)
from ..engine.qos import classify, use_qos
from ..engine.prompts import render_prompt
from ..obs import (PHASES, FlightRecorder, IncidentManager, Trace,
                   current_trace, new_request_id, sanitize_request_id,
                   use_trace)
from ..obs import profiler as obs_profiler
from .breaker import STATE_CODES, CircuitBreaker
from .cache import CachedSingleFlight
from .executor import CommandExecutor, build_metadata, utcnow_iso
from .metrics import Metrics, WindowedRate
from .output_parser import UnsafeCommandError, parse_llm_output
from .ratelimit import SlidingWindowLimiter, ceil_seconds, client_key
from .sanitize import sanitize_query
from .schemas import (
    CommandResponse,
    EngineMetadata,
    ExecuteRequest,
    ExecutionMetadata,
    HealthResponse,
    Query,
)

logger = logging.getLogger(__name__)

RATE_LIMITED_ROUTES = {"/kubectl-command", "/kubectl-command/stream", "/execute"}
#: /debug/* is matched by prefix in auth_middleware (the flight-recorder
#: lookup route carries a path parameter, so exact-set membership can't
#: cover it).
AUTH_ROUTES = RATE_LIMITED_ROUTES
#: routes the MAX_INFLIGHT_REQUESTS overload gate covers (the ones that
#: occupy the engine).
GENERATE_ROUTES = {"/kubectl-command", "/kubectl-command/stream"}
#: paths the flight recorder skips: LB health probes and Prometheus
#: scrapes arrive several times a second and would flush every real
#: request out of the ring within a minute; recorder lookups recording
#: themselves would do the same.
UNRECORDED_PATHS = ("/health", "/metrics", "/debug/", "/openapi.json", "/docs")


def _retry_after_header(seconds: float) -> dict:
    return {"Retry-After": str(max(1, ceil_seconds(seconds)))}


def _span(name: str, **meta):
    """Span on the active trace, or a no-op when none is active (unit
    tests driving Service methods directly)."""
    trace = current_trace()
    if trace is not None:
        return trace.span(name, **meta)
    return nullcontext()


def _client_key(request: web.Request) -> str:
    """Remote-address key for rate limiting — the leftmost untrusted
    X-Forwarded-For hop when TRUST_PROXY(_HEADERS) is set (behind a
    fronting router tier every request shares one peer IP), the raw peer
    IP otherwise (ratelimit.client_key)."""
    svc: Service = request.app["service"]
    return client_key(request.remote,
                      request.headers.get("X-Forwarded-For"),
                      svc.cfg.trust_proxy_headers)


def _json_error(status: int, detail: str, headers: Optional[dict] = None) -> web.Response:
    return web.json_response({"detail": detail}, status=status, headers=headers or {})


class Service:
    """Bundles the app's long-lived components (the reference kept these as
    module globals, app.py:124-138)."""

    def __init__(self, cfg: ServiceConfig, engine: Engine,
                 executor: Optional[CommandExecutor] = None,
                 metrics: Optional[Metrics] = None):
        self.cfg = cfg
        self.engine = engine
        self.executor = executor or CommandExecutor(timeout=cfg.execution_timeout)
        self.metrics = metrics or Metrics()
        self.cache: CachedSingleFlight[str, str] = CachedSingleFlight(
            cfg.cache_maxsize, cfg.cache_ttl
        )
        self.limiter = SlidingWindowLimiter(cfg.rate_limit_count, cfg.rate_limit_window)
        # Failure containment: a rolling-window breaker around every engine
        # call, an optional rule-based degradation path behind it, and the
        # HTTP-layer inflight counter the overload middleware maintains.
        self.breaker = CircuitBreaker(
            threshold=cfg.breaker_threshold,
            window_secs=cfg.breaker_window_secs,
            recovery_secs=cfg.breaker_recovery_secs,
        )
        self.fallback: Optional[FallbackEngine] = (
            FallbackEngine() if cfg.degraded_fallback else None
        )
        self.inflight_requests = 0
        # Observability: the flight recorder keeps the last N request
        # timelines for /debug/requests; the windowed rate feeds the
        # engine_tokens_per_sec gauge at scrape time (see WindowedRate).
        self.recorder = FlightRecorder(cfg.flight_recorder_size)
        self.token_rate = WindowedRate()
        # Perf-regression sentinel (ISSUE 15): the incident manager
        # watches the engine's cheap health views for firing triggers
        # (step-time breach, burn spike, quarantine/dead-end spike,
        # pool exhaustion, breaker open) and files bounded evidence
        # bundles behind /debug/incidents. The config fingerprint rides
        # every bundle so "what exactly was this server running" is
        # answerable post-hoc (describe() is secret-free by contract).
        self.incidents = IncidentManager(
            ring=cfg.incident_ring,
            cooldown_secs=cfg.incident_cooldown_secs,
            burn_threshold=cfg.incident_burn_threshold,
            thrash_min_blocks=cfg.incident_thrash_min_blocks)
        self.config_fingerprint = hashlib.sha256(
            json.dumps(cfg.describe(), sort_keys=True,
                       default=repr).encode()).hexdigest()[:12]
        # QoS ring (ISSUE 7): the tenant→tier map is parsed once at
        # startup (a typo'd TENANT_TIERS already refused to boot in
        # ServiceConfig.__post_init__); the qos middleware classifies
        # every generation request against it.
        self.tenant_tiers = cfg.tenant_tier_map
        # Inner ring → outer ring: every engine reset-and-replay also
        # counts as a breaker failure, so a flapping engine (reset storm)
        # opens the breaker even while individual requests keep
        # recovering. The supervisor calls from the scheduler thread;
        # marshal onto the event loop when one has been seen (breaker
        # transitions are event-loop-only by design).
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        hook = getattr(engine, "set_reset_listener", None)
        if callable(hook):
            hook(self._on_engine_reset)
        # Zero-downtime weight rollout (ISSUE 13): the controller drives
        # drain → swap → warmup → rejoin → observe → promote-or-rollback
        # over the fleet (or, degenerately, one swap-capable engine).
        # Built against the UNWRAPPED engine — a generate-fault
        # ChaosEngine sits above the fleet facade, and lifecycle calls
        # must reach the real replicas.
        self.rollout = None
        target = getattr(engine, "inner", engine)
        # Capability check reaches the REPLICA engines: a fleet of
        # swap-less engines (ENGINE=fake FLEET_SIZE>1) must 404 the
        # admin surface, not accept a rollout that would drain and
        # eject a healthy replica before discovering the missing seam.
        if hasattr(target, "replicas"):
            swappable = all(
                callable(getattr(rep.engine, "swap_weights", None))
                for rep in target.replicas)
        else:
            swappable = callable(getattr(target, "swap_weights", None))
        if swappable:
            from ..engine.rollout import RolloutController

            self.rollout = RolloutController(
                target,
                canary_share=cfg.rollout_canary_share,
                observe_secs=cfg.rollout_observe_secs,
                burn_gate=cfg.rollout_burn_gate,
                steptime_gate=cfg.rollout_steptime_gate,
                drain_secs=cfg.drain_timeout_secs,
            )

    def _on_engine_reset(self, cause: str) -> None:
        loop = self._loop
        if loop is not None and not loop.is_closed():
            loop.call_soon_threadsafe(self.breaker.record_failure)
        else:  # pragma: no cover - pre-traffic reset
            self.breaker.record_failure()

    def retry_after_hint(self) -> float:
        """Retry-After for HTTP-layer sheds: the engine's drain-rate
        estimate when it has one, else a flat second."""
        fn = getattr(self.engine, "retry_after_hint", None)
        if callable(fn):
            try:
                return float(fn())
            except Exception:  # pragma: no cover - defensive
                pass
        return 1.0

    # -------------------------------- perf sentinel / incidents (ISSUE 15)

    def _engine_view(self, name: str) -> Optional[dict]:
        """One cheap engine health view, or None (absent/failing) — the
        incident plane must never take the serving path down."""
        try:
            return _engine_view(self.engine, name)
        except Exception:  # pragma: no cover - defensive
            return None

    def _quarantine_total(self) -> int:
        """Cumulative terminal quarantines across every replica's
        supervisor (cheap attribute reads — never stats(), which drains
        samples owed to the /metrics scrape)."""
        target = getattr(self.engine, "inner", self.engine)
        engines = ([rep.engine for rep in target.replicas]
                   if hasattr(target, "replicas") else [target])
        total = 0
        for eng in engines:
            sup = getattr(eng, "supervisor", None)
            if sup is not None:
                total += sum(getattr(sup, "quarantined", {}).values())
        return total

    def _chunk_rings(self, limit: int = 64) -> dict:
        """Per-replica tails of the scheduler chunk-event rings (the
        /debug/chunks evidence, frozen into the bundle). Deque copies
        retry on concurrent-mutation RuntimeError, same as the route."""
        target = getattr(self.engine, "inner", self.engine)
        engines = ([(str(rep.idx), rep.engine)
                    for rep in target.replicas]
                   if hasattr(target, "replicas")
                   else [("0", target)])
        out = {}
        for key, eng in engines:
            log = getattr(eng, "_chunk_log", None)
            if log is None:
                continue
            events: list = []
            for _ in range(5):
                try:
                    events = list(log)
                    break
                except RuntimeError:
                    continue
            out[key] = events[-limit:]
        return out

    def _incident_bundle(self) -> dict:
        """Assemble one bounded evidence bundle: flight-recorder
        snapshot, chunk rings, and every cheap health section, plus the
        config fingerprint and weights version. Called by the incident
        manager OUTSIDE its lock, at most once per trigger cooldown."""
        return {
            "weights_version": (str(getattr(self.engine,
                                            "weights_version", "") or "")
                                or None),
            "config_fingerprint": self.config_fingerprint,
            "breaker": self.breaker.state,
            "flight_recorder": self.recorder.list(limit=32),
            "chunks": self._chunk_rings(),
            "ledger": self._engine_view("ledger_snapshot"),
            "slo": self._engine_view("slo_health"),
            "qos": self._engine_view("qos_health"),
            "kv_pool": self._engine_view("kv_pool_health"),
            "sharding": self._engine_view("sharding_health"),
            "grammar": self._engine_view("grammar_health"),
            "spec": self._engine_view("spec_health"),
            "fleet": self._engine_view("fleet_health"),
            "steptime": self._engine_view("steptime_health"),
            "rollout": (self.rollout.health()
                        if self.rollout is not None else None),
        }

    def check_incidents(self) -> list:
        """One trigger-evaluation round (the background watcher, the
        /metrics scrape, and /debug/incidents reads all share it —
        cooldowns make redundant evaluation free). Returns NEW bundles."""
        views = {
            "steptime": self._engine_view("steptime_health"),
            "slo": self._engine_view("slo_health"),
            "kv_pool": self._engine_view("kv_pool_health"),
            "grammar": self._engine_view("grammar_health"),
            "breaker": self.breaker.state,
            "quarantined_total": self._quarantine_total(),
        }
        return self.incidents.evaluate(views, self._incident_bundle)

    async def run_engine(self, coro_fn):
        """One engine call under the circuit breaker: fail fast while the
        breaker is open (a half-open probe is the exception), count every
        engine failure, close on success. Overload sheds pass through
        untouched — a full queue is backpressure, not engine brokenness."""
        token = self.breaker.begin()
        if token is None:
            raise EngineUnavailable(
                f"circuit breaker {self.breaker.state}: engine calls "
                "suspended until a half-open probe succeeds"
            )
        # Every exit must either record an outcome or release the probe
        # slot: an overload shed or a client-cancelled call (CancelledError
        # is a BaseException) says nothing about engine health, but if it
        # was the half-open probe, leaving _probe_inflight set would wedge
        # the breaker half-open forever. The token fences stragglers: a
        # call outliving an open transition reports into a dead epoch.
        # Readiness is sampled BEFORE the call: "engine not started"
        # rejections during a restart's warm-up must not open the breaker
        # (it would extend the outage past the model load by up to
        # recovery_secs), while a watchdog trip mid-call — which drops
        # ready AFTER the call began — still counts as the engine failure
        # it is.
        was_ready = bool(getattr(self.engine, "ready", True))
        if self._loop is None:
            self._loop = asyncio.get_running_loop()
        decided = False
        try:
            result = await coro_fn()
        except RequestQuarantined:
            # Terminal per-REQUEST failure: the engine contained it and
            # is healthy — counting it as an engine failure would let one
            # hostile request pattern open the breaker for everyone. The
            # finally below releases the probe slot.
            raise
        except EngineOverloaded:
            # Counted here — once per actual engine shed — rather than in
            # the handlers, where every coalesced single-flight waiter
            # re-raising the shared exception would inflate the counter.
            self.metrics.queue_rejections.labels("engine").inc()
            trace = current_trace()
            if trace is not None:
                trace.shed = True
            raise
        except Exception:
            decided = True
            if was_ready:
                self.breaker.record_failure(token)
            else:
                self.breaker.release_probe(token)
            raise
        else:
            decided = True
            self.breaker.record_success(token)
            return result
        finally:
            if not decided:
                self.breaker.release_probe(token)

    async def degraded_command(self, sanitized_query: str,
                               cause: BaseException) -> tuple[str, EngineResult]:
        """Serve the query from the rule-based FallbackEngine (degraded
        path). Never touches the response cache: a rule-table answer must
        not shadow a real generation after recovery."""
        logger.warning(
            "Serving degraded fallback for query '%s' (breaker=%s): %s",
            sanitized_query, self.breaker.state, cause,
        )
        trace = current_trace()
        if trace is not None:
            trace.degraded = True
            trace.event(f"fallback: engine failed ({cause}); serving "
                        f"rule-based response (breaker={self.breaker.state})")
        with _span("fallback"):
            result = await self.fallback.generate(render_prompt(sanitized_query))
        command = parse_llm_output(result.text)
        self.metrics.degraded_responses.inc()
        # The request DID consult the response cache and miss before the
        # engine failure; count it so hit+miss keeps reconciling with
        # request totals during the outage window.
        self.metrics.cache_misses.inc()
        return command, result

    def cache_key(self, sanitized_query: str) -> str:
        """Response-cache key for one request. Under GRAMMAR_DECODE the
        key is scoped by the request's grammar identity (clamped
        profile + allowed-verbs) — without it, an interactive tenant's
        MUTATING cached command would be served verbatim to a
        readonly-clamped tenant, bypassing the grammar entirely. Off,
        the key is the plain query (pre-ISSUE-11 cache behaviour)."""
        if not self.cfg.grammar_decode:
            return sanitized_query
        from ..constrain import cache_scope, current_grammar
        from ..engine.qos import current_qos

        qctx = current_qos()
        return sanitized_query + cache_scope(
            self.cfg.grammar_profile,
            qctx.lane if qctx is not None else None,
            current_grammar())

    async def generate_command(
        self, sanitized_query: str
    ) -> tuple[str, bool, Optional[EngineResult], bool]:
        """Cache-or-generate; returns (command, from_cache, engine_result,
        degraded). Engine failures (including breaker-open fast-fails)
        degrade to rule-based responses when DEGRADED_FALLBACK is set;
        overload sheds and unsafe outputs always propagate."""
        last_result: list[Optional[EngineResult]] = [None]

        async def supplier() -> str:
            prompt = render_prompt(sanitized_query)
            result = await self.run_engine(lambda: self.engine.generate(
                prompt,
                max_tokens=self.cfg.max_new_tokens,
                temperature=self.cfg.temperature,
                timeout=self.cfg.llm_timeout,
            ))
            last_result[0] = result
            with _span("safety"):
                command = parse_llm_output(result.text)
            logger.info(
                "Engine generated command for query '%s': %s", sanitized_query, command
            )
            return command

        try:
            command, from_cache = await self.cache.get_or_create(
                self.cache_key(sanitized_query), supplier
            )
        except EngineOverloaded:
            raise
        except (EngineUnavailable, GenerationTimeout, asyncio.TimeoutError) as e:
            # Engine-path failure (unavailable / watchdog trip / timeout /
            # open breaker): the degradation target. Anything else — an
            # UnsafeCommandError (422) or a genuine programming bug (500)
            # — propagates; masking a bug as a 200 degraded answer would
            # keep it out of error rates forever (and the stream path
            # already scopes degradation to exactly these exceptions).
            if self.fallback is None:
                raise
            command, result = await self.degraded_command(sanitized_query, e)
            return command, False, result, True
        if from_cache:
            self.metrics.cache_hits.inc()
        else:
            self.metrics.cache_misses.inc()
        return command, from_cache, last_result[0], False


def _finalize_trace(svc: "Service", trace: Trace, status: int,
                    canonical_path: str) -> None:
    """Close out a request's trace: status, phase histograms, recorder.

    Runs for EVERY request — shed 503s, rate-limited 429s, auth 401s and
    unhandled 500s included — which is exactly what makes the flight
    recorder useful during an incident. Probe/scrape/debug paths stay out
    of the recorder (they would flush real traffic from the ring) but
    still feed the HTTP metrics.
    """
    trace.finish(status=status)
    for phase, ms in trace.phase_durations(children=True).items():
        # Every span, prefill's children included (the top level alone is
        # what Server-Timing sums). PHASES is a fixed allowlist: label
        # cardinality stays bounded no matter what span names a future
        # code path (or bug) produces.
        if phase in PHASES:
            svc.metrics.request_phase.labels(phase).observe(ms / 1000.0)
    # Unmatched-route 404s stay out too: they bypass the rate limiter
    # (it only covers the serving routes), so an anonymous scanner
    # walking random URLs could otherwise flush every real timeline out
    # of the ring in seconds. They still count in http_requests_total.
    if (canonical_path != "unmatched"
            and not canonical_path.startswith(UNRECORDED_PATHS)):
        svc.recorder.record(trace)


@web.middleware
async def observability_middleware(request: web.Request, handler):
    """Outermost middleware: request-ID minting, trace-context scope, HTTP
    metrics, Server-Timing, and the flight recorder. Wraps the overload/
    ratelimit/auth middlewares so even their rejections carry an
    X-Request-ID and land in the recorder."""
    svc: Service = request.app["service"]
    # Label by the matched route's canonical path, never the raw request
    # path: a scanner walking random 404 URLs would otherwise mint a new
    # Prometheus series per URL and grow /metrics without bound.
    resource = getattr(request.match_info.route, "resource", None)
    path = resource.canonical if resource is not None else "unmatched"
    # Honour a (safe) client-provided X-Request-ID so callers can
    # pre-correlate; mint otherwise. The raw request path goes on the
    # trace (it names ONE request, not a Prometheus series).
    rid = sanitize_request_id(request.headers.get("X-Request-ID")) \
        or new_request_id()
    trace = Trace(rid, request.method, request.path)
    request["trace"] = trace
    status = 500
    try:
        with use_trace(trace):
            response = await handler(request)
        status = response.status
        if not getattr(response, "prepared", False):
            # Headers are still mutable (json_response et al.). Streaming
            # responses sent their headers at prepare() time — the SSE
            # handler stamps X-Request-ID itself before preparing.
            response.headers["X-Request-ID"] = rid
            timing = trace.server_timing()
            if timing:
                response.headers["Server-Timing"] = timing
            # Weight rollout (ISSUE 13): every response echoes the
            # fleet-STABLE checkpoint version; per-replica truth (the
            # canary included) lives in /health's version table.
            ver = getattr(svc.engine, "weights_version", "")
            if ver:
                response.headers.setdefault("X-Model-Version", str(ver))
        return response
    except web.HTTPException as e:
        status = e.status
        e.headers["X-Request-ID"] = rid
        trace.error = type(e).__name__
        raise
    except Exception as e:
        trace.error = f"{type(e).__name__}: {e}"
        raise
    finally:
        elapsed = (time.monotonic() - trace.t0)
        svc.metrics.http_requests.labels(request.method, path, str(status)).inc()
        svc.metrics.http_latency.labels(request.method, path).observe(elapsed)
        _finalize_trace(svc, trace, status, path)


@web.middleware
async def overload_middleware(request: web.Request, handler):
    """HTTP-layer load shedding (MAX_INFLIGHT_REQUESTS): generation routes
    beyond the inflight cap get a fast 503 + Retry-After before any work
    is done — the server stays responsive under a flood instead of
    accumulating handlers that all time out."""
    svc: Service = request.app["service"]
    # <= 0 means unlimited (an operator's -1 must not shed everything).
    cap = svc.cfg.max_inflight_requests
    if cap <= 0 or request.path not in GENERATE_ROUTES:
        return await handler(request)
    if svc.inflight_requests >= cap:
        svc.metrics.queue_rejections.labels("http").inc()
        trace = current_trace()
        if trace is not None:
            trace.shed = True
            trace.event(f"overload: inflight cap reached "
                        f"({svc.inflight_requests}/{cap}); shedding")
        retry = svc.retry_after_hint()
        return _json_error(
            503,
            f"Server overloaded: {svc.inflight_requests} generation "
            f"requests in flight (cap {cap})",
            headers=_retry_after_header(retry),
        )
    svc.inflight_requests += 1
    try:
        return await handler(request)
    finally:
        svc.inflight_requests -= 1


@web.middleware
async def ratelimit_middleware(request: web.Request, handler):
    svc: Service = request.app["service"]
    if request.path in RATE_LIMITED_ROUTES:
        allowed, remaining, retry_after = svc.limiter.check(_client_key(request))
        if not allowed:
            svc.metrics.rate_limited.inc()
            trace = current_trace()
            if trace is not None:
                trace.shed = True
                trace.event("ratelimit: client over quota; rejecting")
            return _json_error(
                429,
                f"Rate limit exceeded: {svc.cfg.rate_limit}",
                headers=svc.limiter.headers(remaining, retry_after),
            )
    return await handler(request)


@web.middleware
async def auth_middleware(request: web.Request, handler):
    """X-API-Key auth (reference app.py:140-151); disabled when no key
    configured."""
    svc: Service = request.app["service"]
    if svc.cfg.auth_enabled and (request.path in AUTH_ROUTES
                                 or request.path.startswith("/debug/")
                                 or request.path.startswith("/admin/")):
        key = request.headers.get("X-API-Key")
        if not key:
            logger.warning("Missing X-API-Key header.")
            return _json_error(401, "Missing X-API-Key header")
        if key != svc.cfg.api_auth_key:
            logger.warning("Invalid API Key received.")
            return _json_error(401, "Invalid API Key")
    return await handler(request)


@web.middleware
async def qos_middleware(request: web.Request, handler):
    """QoS classification (ISSUE 7): every generation request gets a
    tenant key (its API key, else its rate-limit client IP) and a
    priority lane (X-Priority, clamped by the tenant's TENANT_TIERS
    tier), carried to the engine scheduler on a contextvar — the same
    cross-await channel the trace rides. Innermost middleware: only
    authenticated traffic is classified."""
    svc: Service = request.app["service"]
    if request.path not in GENERATE_ROUTES:
        return await handler(request)
    # The API key is the tenant key ONLY when the operator registered it
    # in TENANT_TIERS. A raw header would let a flooder mint a fresh
    # tenant per request (spoofed random keys dodge every per-tenant
    # cap and displace honest tenants as "dominant"), and under
    # single-key auth it would collapse every user into one bucket.
    # Unregistered traffic buckets by client IP — the same identity the
    # rate limiter uses.
    api_key = request.headers.get("X-API-Key")
    if api_key not in svc.tenant_tiers:
        api_key = None
    ctx = classify(
        api_key,
        _client_key(request),
        request.headers.get("X-Priority"),
        svc.tenant_tiers,
        svc.cfg.qos_default_lane,
        # Session identity (ISSUE 20): client-declared, namespaced under
        # the tenant by classify so sessions can't collide (or spend
        # each other's budget) across tenants.
        session=request.headers.get("X-Session-ID"),
    )
    trace = current_trace()
    if trace is not None:
        # The lane is safe to log; the tenant key may be an API key —
        # the trace records only which kind keyed it.
        trace.event(f"qos: lane={ctx.lane} "
                    f"(tenant={'tier-key' if api_key else 'client-ip'})")
    # Grammar intent (ISSUE 11): a request may LOWER itself to the
    # read-only grammar (X-Grammar-Profile) and/or narrow the verb set
    # (X-Allowed-Verbs, comma-separated) — validated HERE, at
    # admission: unknown verbs and verbs outside the request's clamped
    # profile are a 400, not a silent widening. Headers on a
    # GRAMMAR_DECODE=false deployment are a 400 too — a restriction
    # the engine cannot enforce must not be silently dropped.
    g_profile = request.headers.get("X-Grammar-Profile")
    g_verbs = request.headers.get("X-Allowed-Verbs")
    gctx = None
    if g_profile is not None or g_verbs is not None:
        from ..constrain import GrammarContext, validate_restriction

        if not svc.cfg.grammar_decode:
            return _json_error(
                400, "grammar restrictions require GRAMMAR_DECODE=true")
        verbs = None
        if g_verbs is not None:
            verbs = frozenset(
                v.strip().lower() for v in g_verbs.split(",")
                if v.strip())
        gctx = GrammarContext(
            profile=(g_profile or "").strip().lower() or None,
            allowed_verbs=verbs)
        # ONE validation rule, shared with the engine runtime
        # (constrain.validate_restriction): unknown profile, verbs
        # outside the request's CLAMPED profile, or any verb
        # restriction under the unenforceable permissive A/B profile —
        # all refused here, at admission, never silently dropped.
        err = validate_restriction(svc.cfg.grammar_profile, ctx.lane,
                                   gctx)
        if err is not None:
            return _json_error(400, err)
        if trace is not None:
            trace.event(
                f"grammar: request profile={gctx.profile or 'base'}"
                + (f", {len(verbs)} allowed verbs" if verbs else ""))
    with use_qos(ctx):
        if gctx is not None:
            from ..constrain import use_grammar

            with use_grammar(gctx):
                return await handler(request)
        return await handler(request)


async def handle_kubectl_command(request: web.Request) -> web.Response:
    """POST /kubectl-command (reference app.py:284-346)."""
    svc: Service = request.app["service"]
    trace: Optional[Trace] = request.get("trace")
    start_iso = utcnow_iso()
    t0 = time.monotonic()
    try:
        with _span("validate"):
            q = Query.model_validate(await request.json())
    except (ValidationError, ValueError) as e:
        return _json_error(400, f"Invalid input query: {e}")

    logger.info("Received query: '%s'", q.query)
    sanitized_query = sanitize_query(q.query)
    if len(sanitized_query) < 3:
        return _json_error(400, "Invalid input query: too short after sanitation")

    t_block0 = time.monotonic()
    try:
        command, from_cache, engine_result, degraded = await svc.generate_command(
            sanitized_query
        )
    except TenantOverloaded as e:
        # 429, not 503: the per-TENANT cap tripped — the flooding tenant
        # backs off while everyone else keeps being served; Retry-After
        # is priced from the shed lane's own drain rate.
        return _json_error(429, f"Tenant over queue quota: {e}",
                           headers=_retry_after_header(e.retry_after))
    except EngineOverloaded as e:
        return _json_error(503, f"Server overloaded: {e}",
                           headers=_retry_after_header(e.retry_after))
    except RequestQuarantined as e:
        # 410 Gone: the request itself poisoned decode steps past its
        # quarantine retry budget. Terminal by design — a retry would
        # just poison another batch, so no Retry-After and no fallback.
        logger.error("Request quarantined for query '%s': %s",
                     sanitized_query, e)
        return _json_error(410, f"Request quarantined: {e}")
    except EngineUnavailable as e:
        headers = None
        if svc.rollout is not None and svc.rollout.active:
            # Weight rollout (ISSUE 13): while a swap holds the only
            # capacity (the FLEET_SIZE=1 in-place swap runs WITHOUT a
            # fleet facade to price the shed), tell the LB when to
            # re-offer instead of returning a bare 503.
            hint = float(getattr(svc.engine, "swap_hint", 0.0) or 0.0)
            headers = _retry_after_header(
                hint or max(2.0, svc.rollout.drain_secs / 2.0))
        return _json_error(503, f"Engine not available: {e}",
                           headers=headers)
    except (GenerationTimeout, asyncio.TimeoutError):
        logger.error("Engine timed out after %ss for query: %s", svc.cfg.llm_timeout, sanitized_query)
        return _json_error(504, "LLM request timed out")
    except UnsafeCommandError as e:
        logger.error("Engine generated unsafe command: %s", e)
        svc.metrics.unsafe_commands.labels("llm").inc()
        return _json_error(422, f"LLM generated unsafe command: {e}")
    except Exception as e:
        logger.exception("Unexpected error processing query '%s'", sanitized_query)
        return _json_error(500, "Internal server error processing request")

    t_block1 = time.monotonic()
    duration_ms = (t_block1 - t0) * 1000.0
    engine_md = None
    if engine_result is not None:
        # Degraded rule-table responses stay out of the engine latency /
        # throughput series: their ~0 ms TTFT and 10^5 tok/s would paint
        # record-best dashboards during the exact outage the breaker
        # metrics are surfacing (degraded_responses_total tracks them).
        if not degraded:
            svc.metrics.ttft.observe(engine_result.ttft_ms / 1000.0)
            svc.metrics.gen_latency.observe(duration_ms / 1000.0)
            svc.metrics.tokens_generated.inc(max(engine_result.completion_tokens, 0))
            # Feeds the windowed engine_tokens_per_sec gauge (read at
            # scrape time) — the old per-request .set() only ever showed
            # the LAST finisher and was racy under concurrent decode.
            svc.token_rate.add(engine_result.completion_tokens)
            if engine_result.prefix_cache_hit:
                svc.metrics.prefix_cache_hits.inc()
        # The engine block's queue_wait/prefill/decode/detokenize spans
        # are already on the trace: the engine stamped them where the
        # work happened (obs/trace.py RequestSpans), on this route and
        # the streaming one alike. A degraded block carries its
        # "fallback" span (plus the failure event), and a cache hit its
        # "cache" span below.
        engine_md = EngineMetadata(
            queue_ms=engine_result.queue_ms,
            prefill_ms=engine_result.prefill_ms,
            decode_ms=engine_result.decode_ms,
            detok_ms=engine_result.detok_ms,
            ttft_ms=engine_result.ttft_ms,
            prompt_tokens=engine_result.prompt_tokens,
            completion_tokens=engine_result.completion_tokens,
            tokens_per_sec=engine_result.tokens_per_sec,
            prefix_cache_hit=engine_result.prefix_cache_hit,
            engine=engine_result.engine,
        )
    if trace is not None:
        trace.from_cache = from_cache
        if from_cache:
            trace.add_span("cache", t_block0, t_block1)
    with _span("respond"):
        timings = trace.phase_durations() if trace is not None else None
        body = CommandResponse(
            kubectl_command=command,
            execution_result=None,   # generation and execution are separate (B1, deliberate)
            execution_error=None,
            from_cache=from_cache,
            metadata=ExecutionMetadata(**build_metadata(start_iso, t0, True)),
            engine_metadata=engine_md,
            # Degraded is rule-table fallback OR an engine-side
            # starvation truncation (ISSUE 20) — either way the client
            # must not take the answer as full-fidelity.
            degraded=degraded or (engine_result is not None
                                  and engine_result.degraded),
            timings=timings,
        )
        payload = body.model_dump()
    return web.json_response(payload)


async def handle_kubectl_command_stream(request: web.Request) -> web.StreamResponse:
    """POST /kubectl-command/stream — SSE token stream (TPU-native addition
    for the agent loop, BASELINE config 5)."""
    svc: Service = request.app["service"]
    try:
        q = Query.model_validate(await request.json())
    except (ValidationError, ValueError) as e:
        return _json_error(400, f"Invalid input query: {e}")
    sanitized_query = sanitize_query(q.query)
    if len(sanitized_query) < 3:
        return _json_error(400, "Invalid input query: too short after sanitation")

    trace: Optional[Trace] = request.get("trace")
    resp = web.StreamResponse(
        status=200,
        headers={"Content-Type": "text/event-stream", "Cache-Control": "no-cache"},
    )
    if trace is not None:
        # Streaming commits headers at prepare() time, before any phase
        # has run — the middleware can't stamp them afterwards. The ID is
        # known now; Server-Timing (whose values aren't) stays JSON-only.
        resp.headers["X-Request-ID"] = trace.request_id
    # Weight rollout (ISSUE 13): the stream commits to the fleet-stable
    # version before the first byte; version pinning (engine/fleet.py)
    # then guarantees an established stream never silently crosses onto
    # other weights mid-flight.
    _ver = getattr(svc.engine, "weights_version", "")
    if _ver:
        resp.headers["X-Model-Version"] = str(_ver)
    await resp.prepare(request)

    def sse(payload: str, event: Optional[str] = None) -> bytes:
        # SSE framing: every payload line needs its own "data:" field —
        # naive interpolation would corrupt multi-line token pieces.
        lines = payload.split("\n") or [""]
        frame = (f"event: {event}\n" if event else "") + "".join(
            f"data: {line}\n" for line in lines
        ) + "\n"
        return frame.encode()

    # Everything goes through the SAME cache + single-flight as the
    # non-streaming endpoint (fixes the half-applied B4: concurrent
    # identical streams no longer each run a full generation). The flight
    # initiator streams tokens live; cache hits and coalesced waiters —
    # streaming or not — replay the final command as one event. As with
    # the non-streaming path, a disconnecting client does not cancel the
    # shared generation: it completes and fills the cache (the documented
    # SingleFlight semantics).
    write_ok = True

    async def write_safe(frame: bytes) -> None:
        nonlocal write_ok
        if not write_ok:
            return
        try:
            await resp.write(frame)
        except Exception:
            write_ok = False  # client went away mid-stream; stop writing

    # The supplier never touches the socket — it hands tokens to this
    # handler through a queue, and the handler writes them. A slow-reading
    # client therefore stalls only its own drain loop, never the shared
    # flight the coalesced waiters are blocked on.
    _DONE = object()
    token_q: asyncio.Queue = asyncio.Queue()

    async def supplier() -> str:
        async def run() -> str:
            pieces: list[str] = []
            stream = svc.engine.generate_stream(
                render_prompt(sanitized_query),
                max_tokens=svc.cfg.max_new_tokens,
                temperature=svc.cfg.temperature,
                timeout=svc.cfg.llm_timeout,
            )
            async for piece in stream:
                pieces.append(piece)
                token_q.put_nowait(piece)
            return "".join(pieces)

        try:
            # Same breaker accounting as the non-streaming path; parsing
            # stays outside so an unsafe output doesn't count as an
            # engine failure.
            text = await svc.run_engine(run)
            with _span("safety"):
                return parse_llm_output(text)
        finally:
            token_q.put_nowait(_DONE)

    try:
        flight = asyncio.ensure_future(
            svc.cache.get_or_create(svc.cache_key(sanitized_query),
                                    supplier)
        )
        # Drain live tokens while the flight runs. Only our own supplier
        # fills token_q; a cache hit or a coalesced flight leaves it empty
        # and we just wait for the flight's result.
        getter: Optional[asyncio.Future] = None
        try:
            while True:
                getter = asyncio.ensure_future(token_q.get())
                await asyncio.wait({getter, flight},
                                   return_when=asyncio.FIRST_COMPLETED)
                if getter.done():
                    piece = getter.result()
                    if piece is _DONE:
                        break
                    await write_safe(sse(piece))
                else:
                    break  # flight finished without our supplier running
        finally:
            if getter is not None and not getter.done():
                getter.cancel()
        command, from_cache = await flight
        if trace is not None:
            trace.from_cache = from_cache
        if from_cache:
            # A cache hit or another request's in-flight generation served
            # us; our supplier never streamed — replay the result.
            svc.metrics.cache_hits.inc()
            await write_safe(sse(command))
        else:
            svc.metrics.cache_misses.inc()
        await write_safe(sse(command, event="done"))
    except UnsafeCommandError as e:
        svc.metrics.unsafe_commands.labels("llm").inc()
        await write_safe(sse(str(e), event="error"))
    except TenantOverloaded as e:
        # In-band 429 analog: THIS tenant is over its queue quota.
        await write_safe(sse(f"tenant over queue quota: {e}",
                             event="error"))
    except EngineOverloaded as e:
        # Shedding stays an error even with the fallback enabled: the
        # client should back off, not be absorbed by the rule table.
        # (queue_rejections is counted inside run_engine, once per shed.)
        await write_safe(sse(f"engine overloaded: {e}", event="error"))
    except RequestQuarantined as e:
        # Terminal: this request poisons decode steps; never degraded,
        # never retried (410 analog for an already-committed stream).
        await write_safe(sse(f"request quarantined: {e}", event="error"))
    except (EngineUnavailable, GenerationTimeout, asyncio.TimeoutError) as e:
        if svc.fallback is not None:
            try:
                command, _result = await svc.degraded_command(
                    sanitized_query, e)
            except UnsafeCommandError as ue:
                # A rule template interpolated a query capture the safety
                # validator rejects ("logs of web;id") — same in-band 422
                # analog as the primary-path unsafe case.
                svc.metrics.unsafe_commands.labels("llm").inc()
                await write_safe(sse(str(ue), event="error"))
            else:
                # A "degraded" frame before "done" so agent loops that
                # only watch "done" keep working while aware clients can
                # tell.
                await write_safe(sse(command, event="degraded"))
                await write_safe(sse(command, event="done"))
        elif isinstance(e, EngineUnavailable):
            await write_safe(sse(f"engine unavailable: {e}", event="error"))
        else:
            await write_safe(sse("LLM request timed out", event="error"))
    except Exception:
        # The 200 status is already on the wire; the best we can do is a
        # structured error event rather than a silently truncated stream.
        logger.exception("Stream generation failed for query '%s'", sanitized_query)
        await write_safe(sse("internal error during generation", event="error"))
    try:
        await resp.write_eof()
    except Exception:
        pass  # client already gone; the stream is finished either way
    return resp


async def handle_execute(request: web.Request) -> web.Response:
    """POST /execute (reference app.py:356-389)."""
    svc: Service = request.app["service"]
    trace: Optional[Trace] = request.get("trace")
    try:
        with _span("validate"):
            req = ExecuteRequest.model_validate(await request.json())
    except (ValidationError, ValueError) as e:
        return _json_error(400, f"Invalid request: {e}")

    logger.info("Received execute request for command: '%s'", req.execute)
    from .safety import unsafe_reason

    with _span("safety"):
        reason = unsafe_reason(req.execute)
    if reason is not None:
        svc.metrics.unsafe_commands.labels("user").inc()
        return _json_error(400, f"Command failed safety checks: {reason}")

    with _span("execute"):
        execution_data = await svc.executor.execute(req.execute)
    outcome = "success" if execution_data["metadata"]["success"] else (
        execution_data["metadata"].get("error_type") or "error"
    )
    svc.metrics.executions.labels(outcome).inc()

    with _span("respond"):
        body = CommandResponse(
            kubectl_command=req.execute,
            execution_result=execution_data.get("execution_result"),
            execution_error=execution_data.get("execution_error"),
            from_cache=False,
            metadata=ExecutionMetadata(**execution_data["metadata"]),
            timings=trace.phase_durations() if trace is not None else None,
        )
        payload = body.model_dump()
    return web.json_response(payload)


def _device_info(app: web.Application) -> dict:
    """What JAX serves from — platform, device kind and count as
    ``jax.devices()`` reports them — enumerated once and cached on the
    app: LBs probe /health several times a second and re-importing jax +
    listing devices per probe is measurable work for an answer that never
    changes. The platform is here so a server that came up on the CPU
    (interpreted kernels) cannot pass for one on the chip."""
    info = app.get("_device_info")
    if info is None:
        try:
            import jax

            devs = jax.devices()
            info = {"devices": len(devs), "platform": devs[0].platform,
                    "device_kind": devs[0].device_kind}
        except Exception:
            # transient failure: don't cache; retry next probe
            return {"devices": 0, "platform": "", "device_kind": ""}
        app["_device_info"] = info
    return info


def _engine_view(engine, method: str):
    """What ``engine.<method>()`` gives, or None (no such method, or
    nothing to say)."""
    fn = getattr(engine, method, None)
    return (fn() or None) if callable(fn) else None


#: /health sections an engine gives through ``<section>_health()``.
_ENGINE_VIEWS = ("fleet", "qos", "slo", "kv_pool", "ragged", "sharding",
                 "grammar", "spec", "steptime", "spans")


async def handle_health(request: web.Request) -> web.Response:
    """GET /health — readiness-gated (SURVEY.md §3.3), with the breaker's
    state surfaced so operators can tell "engine down" from "engine up but
    circuit open / serving fallback"."""
    svc: Service = request.app["service"]
    ready = bool(getattr(svc.engine, "ready", False))
    breaker = svc.breaker.state
    # Inner-ring containment state: when the engine last reset its
    # decode state and why — read off the supervisor directly (NOT via
    # engine.stats(), which drains the fetch-latency samples owed to the
    # /metrics histogram; LBs probe /health several times a second).
    last_reset = last_cause = None
    sup = (getattr(svc.engine, "supervisor", None)
           or getattr(getattr(svc.engine, "inner", None), "supervisor",
                      None))
    if sup is not None and sup.last_reset_wall:
        last_reset = (time.strftime("%Y-%m-%dT%H:%M:%S",
                                    time.gmtime(sup.last_reset_wall)) + "Z")
        last_cause = sup.last_reset_cause
    # The engine's sections, each a cheap host view (counters, bounded
    # rings; NEVER stats(), which drains samples owed to the /metrics
    # scrape) read through a method the engine may not have: None then,
    # and where the engine has nothing to say. server/schemas.py::
    # HealthResponse says what each holds.
    views = {section: _engine_view(svc.engine, f"{section}_health")
             for section in _ENGINE_VIEWS}
    # The fleet's most-recent reset backfills the top-level fields.
    fleet = views["fleet"]
    if fleet is not None and last_reset is None:
        last_reset = fleet.get("last_reset")
        last_cause = fleet.get("last_reset_cause")
    # The cache kinds' sections (models/families.py::SECTIONS), all from
    # one method.
    families = {name: section or None
                for name, section in (_engine_view(
                    svc.engine, "family_health") or {}).items()}
    body = HealthResponse(
        status="healthy" if ready and breaker == "closed" else "degraded",
        engine=getattr(svc.engine, "name", "unknown"),
        engine_ready=ready,
        model=svc.cfg.model_name,
        **_device_info(request.app),
        breaker=breaker,
        degraded_fallback=svc.fallback is not None,
        last_reset=last_reset,
        last_reset_cause=last_cause,
        **views,
        **families,
        rollout=svc.rollout.health() if svc.rollout is not None else None,
        incidents=svc.incidents.snapshot(),
    )
    # The HTTP status tracks engine readiness alone: an open breaker with
    # the engine process alive still serves (fallback and/or cache), and
    # half-open probes need traffic to ever re-close it. A 503 carries
    # Retry-After priced from the FLEET-wide drain rate (the engine's
    # aggregate hint) so draining instances tell LBs when to re-probe.
    if ready:
        return web.json_response(body.model_dump(), status=200)
    return web.json_response(
        body.model_dump(), status=503,
        headers=_retry_after_header(svc.retry_after_hint()))


def _debug_forbidden(request: web.Request) -> Optional[web.Response]:
    """Token gate for /debug/*: when DEBUG_TOKEN is configured, require a
    matching X-Debug-Token header ON TOP of the API-key auth middleware.
    Debug surfaces (request timelines, profiler captures) are
    operator-facing — a leaked client API key must not open them."""
    token = request.app["service"].cfg.debug_token
    if not token:
        return None
    supplied = request.headers.get("X-Debug-Token", "")
    # Compare bytes: compare_digest on str raises TypeError for
    # non-ASCII input, and header values may legally carry 0x80-0xFF —
    # a garbage token must 403, not 500.
    if not hmac.compare_digest(
            supplied.encode("utf-8", "surrogateescape"), token.encode()):
        return _json_error(403, "Invalid or missing X-Debug-Token")
    return None


async def handle_debug_profile(request: web.Request) -> web.Response:
    """POST /debug/profile?seconds=N — capture a jax.profiler device trace
    while live traffic runs (SURVEY.md §5 tracing row; TensorBoard-
    loadable). ``python_tracer=1`` turns the profiler's Python tracer on
    (frames of every call, at the price of a slower host; off by default).
    Auth- and token-gated; one capture at a time; only the newest few
    captures are retained (obs/profiler.py). ``/debug/trace`` is the
    pre-rename alias."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    try:
        seconds = obs_profiler.clamp_seconds(
            float(request.query.get("seconds", 2.0)))
    except ValueError:
        return _json_error(400, "seconds must be a number")
    if request.app.get("_tracing"):
        return _json_error(409, "a trace is already in progress")
    request.app["_tracing"] = True
    sph = getattr(request.app["service"].engine, "spans_health", None)
    try:
        result = await obs_profiler.capture(
            seconds, probe=sph if callable(sph) else None,
            python_tracer=request.query.get("python_tracer", "0").lower()
            in ("1", "true", "yes", "on"))
    except Exception as e:  # pragma: no cover - backend-dependent
        logger.exception("trace capture failed")
        return _json_error(500, f"trace capture failed: {e}")
    finally:
        request.app["_tracing"] = False
    return web.json_response(result)


async def handle_debug_requests(request: web.Request) -> web.Response:
    """GET /debug/requests — newest-first flight-recorder index (summaries
    only; fetch a request_id's full timeline from the detail route)."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    try:
        limit = int(request.query.get("limit", 50))
    except ValueError:
        return _json_error(400, "limit must be an integer")
    return web.json_response({
        "size": svc.recorder.size,
        "recorded": svc.recorder.recorded,
        "requests": svc.recorder.list(limit=limit),
    })


async def handle_debug_request_detail(request: web.Request) -> web.Response:
    """GET /debug/requests/{id} — one request's full span timeline."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    rid = request.match_info["id"]
    entry = svc.recorder.get(rid)
    if entry is None:
        return _json_error(
            404,
            f"request {rid!r} not in the flight recorder (keeps the last "
            f"{svc.recorder.size}; is FLIGHT_RECORDER_SIZE large enough?)",
        )
    return web.json_response(entry)


async def handle_debug_chunks(request: web.Request) -> web.Response:
    """GET /debug/chunks — the decode pipeline's flight record: the
    scheduler thread's last N spans (``sched/admit|dispatch|fetch|
    consume`` intervals with their chunk number, on time.monotonic() as
    ``t0``/``t1`` and on the wall clock as ``t``; KV bucket, device
    n_alive, fetch latency) and prune/health-trip marks, straight off
    the scheduler's ring buffer, plus the live pipeline stats. The
    chunk-granular companion to /debug/requests when 'serving is slower
    than the device' needs a timeline, not a counter."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    try:
        limit = int(request.query.get("limit", 100))
    except ValueError:
        return _json_error(400, "limit must be an integer")
    # The scheduler thread appends to the ring while we copy; CPython
    # raises "deque mutated during iteration" rather than corrupting, so
    # retry the snapshot a few times instead of 500ing the one endpoint
    # meant for debugging a busy pipeline.
    log = getattr(svc.engine, "_chunk_log", ())
    events: list = []
    for _ in range(5):
        try:
            events = list(log)
            break
        except RuntimeError:
            continue
    stats_fn = getattr(svc.engine, "stats", None)
    stats = stats_fn() if callable(stats_fn) else {}
    if stats:
        # stats() drains the fetch-latency samples; forward them to the
        # histogram rather than dropping them on the floor.
        svc.metrics.observe_pipeline(stats)
    keys = ("pipe_depth", "pipe_inflight", "device_active_slots",
            "device_termination", "wasted_decode_steps",
            "chunks_dispatched", "chunks_consumed", "chunks_pruned")
    return web.json_response({
        "events": events[-limit:] if limit > 0 else [],
        "pipeline": {k: stats[k] for k in keys if k in stats},
    })


async def handle_debug_ledger(request: web.Request) -> web.Response:
    """GET /debug/ledger — the goodput ledger (obs/ledger.py): every
    device decode step classified delivered vs the waste classes, per
    lane AND per (hashed) tenant, with the conservation check. The
    tenant breakdown lives here and only here — tenants must never
    become metric labels (cardinality), and the keys are sha256 hashes
    (they may be API keys), the same form LOG_FORMAT=json stamps on log
    lines so the two surfaces join."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    fn = getattr(svc.engine, "ledger_snapshot", None)
    snap = fn() if callable(fn) else None
    if not snap:   # absent, or a wrapper forwarding to an engine without one
        return _json_error(
            404, "engine exposes no goodput ledger (telemetry plane is "
                 "wired into the chunked schedulers and the fleet)")
    return web.json_response(snap)


async def _attach_incident_profiles(app: web.Application, svc: Service,
                                    bundles: list) -> None:
    """Optionally attach a rate-limited jax.profiler capture to fresh
    bundles (INCIDENT_PROFILE_SECS > 0, jax engines only). Serialized
    against operator-requested captures via the same _tracing flag, and
    bounded by the trigger cooldowns that bounded the bundles."""
    secs = svc.cfg.incident_profile_secs
    if secs <= 0 or not bundles:
        return
    import sys

    if "jax" not in sys.modules:
        return   # fake/openai deployment: nothing to profile
    if app.get("_tracing"):
        bundles[0]["profile"] = {"skipped": "capture already running"}
        return
    app["_tracing"] = True
    try:
        result = await obs_profiler.capture(secs)
        bundles[0]["profile"] = result
    except Exception as e:  # pragma: no cover - backend-dependent
        bundles[0]["profile"] = {"error": str(e)}
    finally:
        app["_tracing"] = False


async def handle_debug_incidents(request: web.Request) -> web.Response:
    """GET /debug/incidents — the incident ring's newest-first index
    (ISSUE 15). Each entry is a bounded evidence bundle an anomaly
    trigger assembled automatically (step-time breach, SLO burn spike,
    quarantine/dead-end spike, pool exhaustion, breaker open); fetch a
    full bundle from /debug/incidents/{id}. Reading runs one trigger
    evaluation first, so a freshly-tripped sentinel files its bundle on
    the very request that comes looking for it."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    try:
        new = svc.check_incidents()
        await _attach_incident_profiles(request.app, svc, new)
    except Exception:   # pragma: no cover - defensive
        logger.exception("incident evaluation failed")
    return web.json_response({
        **svc.incidents.snapshot(),
        "incidents": svc.incidents.list(),
    })


async def handle_debug_incident_detail(request: web.Request
                                       ) -> web.Response:
    """GET /debug/incidents/{id} — one incident's full evidence bundle
    (flight recorder, chunk rings, ledger/SLO/pool/spec health
    snapshots, config fingerprint, weights version)."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    iid = request.match_info["id"]
    bundle = svc.incidents.get(iid)
    if bundle is None:
        return _json_error(
            404,
            f"incident {iid!r} not in the ring (keeps the newest "
            f"{svc.incidents.ring_size}; is INCIDENT_RING large "
            f"enough?)")
    return web.json_response(bundle)


def _rollout_unavailable(svc: Service) -> Optional[web.Response]:
    if svc.rollout is None:
        return _json_error(
            404, "engine has no weight-rollout support (rollouts are "
                 "wired into the fleet and the swap-capable engines)")
    return None


async def handle_admin_rollout_post(request: web.Request) -> web.Response:
    """POST /admin/rollout {"checkpoint": path} — begin a zero-downtime
    weight rollout (ISSUE 13): drain one canary replica, swap it to the
    versioned checkpoint, observe it under a bounded traffic share, then
    promote the rest or roll back automatically. Token-gated like the
    debug surfaces — weight changes are operator actions."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    unavailable = _rollout_unavailable(svc)
    if unavailable is not None:
        return unavailable
    try:
        body = await request.json()
    except Exception:
        return _json_error(400, "body must be JSON")
    checkpoint = (body or {}).get("checkpoint")
    if not isinstance(checkpoint, str) or not checkpoint.strip():
        return _json_error(400, "body needs a 'checkpoint' path string")
    from ..engine.rollout import RolloutError

    try:
        status = await svc.rollout.start_rollout(checkpoint.strip())
    except RolloutError as e:
        return _json_error(409, str(e))
    return web.json_response(status, status=202)


async def handle_admin_rollout_get(request: web.Request) -> web.Response:
    """GET /admin/rollout — the rollout state machine's full status:
    state, target/stable versions, canary + share, gate verdicts, the
    drain→swap→rejoin→promote timeline, and rollback history."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    unavailable = _rollout_unavailable(svc)
    if unavailable is not None:
        return unavailable
    return web.json_response(svc.rollout.status())


async def handle_admin_rollout_abort(request: web.Request) -> web.Response:
    """POST /admin/rollout/abort — roll the in-flight rollout back
    (cause ``aborted``); 409 when nothing is in flight."""
    denied = _debug_forbidden(request)
    if denied is not None:
        return denied
    svc: Service = request.app["service"]
    unavailable = _rollout_unavailable(svc)
    if unavailable is not None:
        return unavailable
    from ..engine.rollout import RolloutError

    try:
        status = await svc.rollout.abort()
    except RolloutError as e:
        return _json_error(409, str(e))
    return web.json_response(status)


#: stats() section -> the Metrics method that mirrors it at a scrape.
_SECTION_MIRRORS = {
    "fleet": "observe_fleet", "qos": "observe_qos",
    "ledger": "observe_ledger", "slo": "observe_slo",
    "kv_pool": "observe_kv_pool", "ssm": "observe_state_cache",
    "latent_attention": "observe_latent_attention",
    "sharding": "observe_sharding", "grammar": "observe_grammar",
    "spec": "observe_spec", "steptime": "observe_steptime"}


async def handle_metrics(request: web.Request) -> web.Response:
    svc: Service = request.app["service"]
    # Engine gauges are sampled at scrape time (live scheduler state, not a
    # push path the hot loop has to touch).
    stats_fn = getattr(svc.engine, "stats", None)
    stats = {}
    if callable(stats_fn):
        stats = stats_fn()
        svc.metrics.batch_occupancy.set(stats.get("batch_occupancy", 0))
        svc.metrics.queue_depth.set(stats.get("queue_depth", 0))
        svc.metrics.kv_pool_used.set(stats.get("kv_pages_used", 0))
        svc.metrics.kv_pool_total.set(stats.get("kv_pages_total", 0))
        # Decode-pipeline metrics (pipe occupancy, wasted decode steps,
        # chunk dispatch/consume/prune counts, fetch-latency histogram).
        svc.metrics.observe_pipeline(stats)
        # Containment counters (resets, quarantines, health trips,
        # replayed tokens) — same delta-mirror pattern.
        svc.metrics.observe_containment(stats)
        # The engine's sections, each delta-mirrored (or sampled) into
        # its own series by server/metrics.py where the engine has one.
        for section, observe in _SECTION_MIRRORS.items():
            if stats.get(section):
                getattr(svc.metrics, observe)(stats[section])
    # Incident plane (ISSUE 15): a scrape is also a trigger-evaluation
    # round (cooldowns make redundant evaluation free), so deployments
    # with SENTINEL_EVAL_SECS=0 still capture incidents at scrape
    # cadence; captured/suppressed totals delta-mirror by trigger.
    try:
        svc.check_incidents()
    except Exception:   # pragma: no cover - defensive
        logger.exception("incident evaluation failed at scrape")
    svc.metrics.observe_incidents(svc.incidents.snapshot())
    # Weight rollout (ISSUE 13): state gauge + per-version replica
    # counts + rollbacks{cause} — the controller sits ABOVE the engine
    # seam, so it mirrors from its own health view, not stats().
    if svc.rollout is not None:
        svc.metrics.observe_rollout(svc.rollout.health())
    # Windowed throughput gauge: the batcher's own scheduler-side window
    # when it reports one (counts every finish, including streams), else
    # the service-side window fed by the response handlers.
    svc.metrics.tokens_per_sec.set(
        stats.get("tokens_per_sec_window", svc.token_rate.rate())
    )
    svc.metrics.breaker_state.set(STATE_CODES[svc.breaker.state])
    return web.Response(body=svc.metrics.render(), content_type="text/plain")


def create_app(cfg: ServiceConfig, engine: Engine,
               executor: Optional[CommandExecutor] = None,
               metrics: Optional[Metrics] = None) -> web.Application:
    """App factory (reference module init, app.py:130-138)."""
    app = web.Application(
        middlewares=[observability_middleware, overload_middleware,
                     ratelimit_middleware, auth_middleware,
                     qos_middleware]
    )
    app["service"] = Service(cfg, engine, executor=executor, metrics=metrics)

    app.router.add_post("/kubectl-command", handle_kubectl_command)
    app.router.add_post("/kubectl-command/stream", handle_kubectl_command_stream)
    app.router.add_post("/execute", handle_execute)
    app.router.add_post("/debug/profile", handle_debug_profile)
    app.router.add_post("/debug/trace", handle_debug_profile)  # pre-rename alias
    app.router.add_get("/debug/requests", handle_debug_requests)
    app.router.add_get("/debug/requests/{id}", handle_debug_request_detail)
    app.router.add_get("/debug/chunks", handle_debug_chunks)
    app.router.add_get("/debug/ledger", handle_debug_ledger)
    app.router.add_get("/debug/incidents", handle_debug_incidents)
    app.router.add_get("/debug/incidents/{id}",
                       handle_debug_incident_detail)
    app.router.add_post("/admin/rollout", handle_admin_rollout_post)
    app.router.add_get("/admin/rollout", handle_admin_rollout_get)
    app.router.add_post("/admin/rollout/abort", handle_admin_rollout_abort)
    app.router.add_get("/health", handle_health)
    app.router.add_get("/metrics", handle_metrics)
    # /openapi.json + /docs — unauthenticated like the reference's
    # FastAPI-generated docs (app.py:131); see server/openapi.py.
    from .openapi import register as register_openapi

    register_openapi(app)

    async def _start_engine(app: web.Application) -> None:
        await app["service"].engine.start()
        # Warm the /health device-info cache, but only when the engine
        # already imported jax — a fake/openai deployment must not pay a
        # multi-second jax import before the socket binds (the first
        # health probe fills the cache lazily there instead).
        import sys

        if "jax" in sys.modules:
            _device_info(app)

    async def _stop_engine(app: web.Application) -> None:
        # The DRAIN_TIMEOUT_SECS drain itself runs at signal time in
        # server/__main__.py::_serve, while the socket still answers
        # health checks (aiohttp closes the socket before cleanup hooks
        # run, so a drain here could never 503 to the LB). This hook is
        # the final teardown — idempotent after a drain, and the only
        # stop for embedded/test usages that never send a signal.
        await app["service"].engine.stop()

    async def _start_sentinel_watcher(app: web.Application) -> None:
        # Incident watcher (ISSUE 15): a background evaluation loop, so
        # triggers fire even when nothing scrapes /metrics. 0 disables
        # it (scrape/read-driven evaluation only).
        svc: Service = app["service"]
        period = svc.cfg.sentinel_eval_secs
        if period <= 0:
            return

        async def watch() -> None:
            while True:
                await asyncio.sleep(period)
                try:
                    new = svc.check_incidents()
                    await _attach_incident_profiles(app, svc, new)
                except asyncio.CancelledError:   # teardown
                    raise
                except Exception:   # pragma: no cover - defensive
                    logger.exception("sentinel watcher failed")

        app["_sentinel_task"] = asyncio.create_task(watch())

    async def _stop_sentinel_watcher(app: web.Application) -> None:
        task = app.get("_sentinel_task")
        if task is not None:
            task.cancel()
            with suppress(asyncio.CancelledError):
                await task
            app["_sentinel_task"] = None

    app.on_startup.append(_start_engine)
    app.on_startup.append(_start_sentinel_watcher)
    app.on_cleanup.append(_stop_sentinel_watcher)
    app.on_cleanup.append(_stop_engine)
    return app
