"""On-demand ``jax.profiler`` capture for a live server.

``POST /debug/profile?seconds=N[&python_tracer=1]`` lands here: start a
device trace into a fresh directory, sleep N seconds while live traffic
keeps decoding, stop, and report the directory (TensorBoard-loadable,
``xprof`` readable). The
whole point is catching "why is decode slow *right now*" without
restarting the server with profiling baked in.

jax is imported lazily inside the capture — the obs package must stay
importable (and the fake/openai deployments must stay jax-free) when no
one ever asks for a profile.
"""

from __future__ import annotations

import asyncio
import logging
import os
import shutil
import tempfile
import time

from .trace import spans_growth

logger = logging.getLogger(__name__)

#: traces are tens of MB each; keep the newest few and reap the rest.
KEEP_TRACES = 4

#: capture length clamp (seconds): long enough for a few decode chunks,
#: short enough that an operator typo can't profile for an hour.
MIN_SECONDS = 0.1
MAX_SECONDS = 30.0


def clamp_seconds(seconds: float) -> float:
    return min(max(float(seconds), MIN_SECONDS), MAX_SECONDS)


def trace_base_dir() -> str:
    return os.path.join(tempfile.gettempdir(), "ai-agent-kubectl-tpu-traces")


def _reap_old(base: str) -> None:
    old = sorted(
        d for d in os.listdir(base) if os.path.isdir(os.path.join(base, d))
    )
    if len(old) > KEEP_TRACES:
        for d in old[:-KEEP_TRACES]:
            shutil.rmtree(os.path.join(base, d), ignore_errors=True)


async def capture(seconds: float, probe=None,
                  python_tracer: bool = False) -> dict:
    """Run one profiler capture; returns ``{"trace_dir", "seconds",
    "python_tracer", "clock_start", "clock_stop", "spans"}``. The two
    clock entries are ``[time.monotonic(), time.time_ns()]`` pairs taken
    as the capture starts and stops: the trace's own axis is the wall
    clock in nanoseconds, every span of this program (flight recorder,
    /debug/chunks) is stamped with ``time.monotonic()``, and the pair is
    what places one on the other by hand. The scheduler's sched/* spans
    need no such arithmetic: they are TraceAnnotations inside the trace.
    ``spans`` is what ``probe()`` (the engine's ``/health.spans``) grew by
    between the two stamps (None without a probe): the same regions'
    counts and ms, and the scheduler thread's three partitions
    (``sched_thread_s``, ``sched_starved_s``, ``sched_drained_s`` with
    its ``by_region``), so one capture yields the device's idle seconds
    from the trace and the program's own account of them over the same
    interval.

    The profiler's Python tracer is OFF unless ``python_tracer``: it
    hooks every call of every thread, the scheduler's among them, and
    made a radix walk twenty times longer inside a capture than outside
    (3.83 against 79.8 ms; chip run, PR 35), which the trace then showed
    as the device standing idle. Without it the capture holds the device
    planes, the runtime's own TraceMe events and the sched/* annotations
    on the scheduler thread's line (the host tracer's level is left as it
    is), no ``$file:line function`` frame, and the program runs as it does
    outside one. An operator who wants frames asks for them
    (``python_tracer=1``) and reads the host's times with that in mind;
    the answer says which it was.

    The caller serializes captures (one at a time) — jax.profiler has one
    global trace session and a second start_trace would raise.
    """
    import jax

    seconds = clamp_seconds(seconds)
    base = trace_base_dir()
    os.makedirs(base, exist_ok=True)
    _reap_old(base)
    trace_dir = tempfile.mkdtemp(
        prefix=f"{time.strftime('%Y%m%d-%H%M%S')}-", dir=base
    )
    logger.info("profiler: capturing %.1fs device trace into %s "
                "(python tracer %s)", seconds, trace_dir,
                "on" if python_tracer else "off")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = int(python_tracer)
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    clock_start = [time.monotonic(), time.time_ns()]
    before = probe() if probe is not None else None
    try:
        await asyncio.sleep(seconds)
    finally:
        after = probe() if probe is not None else None
        clock_stop = [time.monotonic(), time.time_ns()]
        # Writing the trace out takes several times the capture's length
        # and holds this event loop, so every handler of the server, for
        # it: 18 s for 3 s of one busy chip. From another thread it let
        # the server go on but took 54-72 s there and 209 s over four
        # chips, against the 300 s a benchmark run waits for this answer
        # (chip runs, PR 50): it stays here until that is understood.
        jax.profiler.stop_trace()
    spans = spans_growth(before, after)
    if spans:
        # the server's log keeps what the caller may not: the scheduler
        # thread's regions inside the capture (count, ms) and the seconds
        # the device had nothing to run
        logger.info("profiler: sched regions inside the capture: %s", {
            name: (e.get("count"), e.get("total_ms"))
            for name, e in spans.items() if name.startswith("sched/")})
        logger.info("profiler: sched_drained_s inside the capture: %s",
                    spans.get("sched_drained_s"))
    return {"trace_dir": trace_dir, "seconds": seconds,
            "python_tracer": python_tracer,
            "clock_start": clock_start, "clock_stop": clock_stop,
            "spans": spans}
