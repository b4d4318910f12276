"""Bench-trajectory perf gate: compare a fresh bench artifact against
a trajectory of earlier ones.

A checkpoint, config, or scheduler change that halved throughput would
sail through CI and only surface when a human next ran ``bench.py`` and
happened to compare by eye. This tool is the comparison, mechanized:

    python tools/perf_gate.py --artifact NEW.json \
        --trajectory OLD_1.json OLD_2.json ...

For every known metric the gate derives a **reference** from the
trajectory — the best value any trajectory artifact recorded (bench
throughput shows ~2x run-to-run variance, so the trajectory's best IS
the number of record; medians already happened inside each run) — and
judges the candidate against a per-phase tolerance band:

- throughput metrics (tok/s): pass at >= (1 - tolerance) x reference
- latency metrics (TTFT ms): pass at <= (1 + latency tolerance) x the
  trajectory's best (lowest)
- step-time digests (ms/step, once artifacts carry them): pass at
  <= (1 + step tolerance) x reference

Crucially the gate distinguishes **slower** from **absent**: a metric
the newest trajectory artifact records must exist in the candidate —
a phase that silently vanished (OOM, crash) fails as ``absent``, and a
phase the orchestrator recorded as ``{"status": "timeout"|"error"}``
(bench.py now writes those instead of omitting the phase) fails as
``timed_out``/``errored``. A gate that can only say "slower" reads a
dead phase as a pass.

Artifacts are accepted in either form: the raw ``bench.py`` orchestrator
dict (``{"metric", "value", "extra": {...}}``) or a driver-wrapped
one (``{"parsed": {...}}``).

Exit status: 0 = every judged metric passed; 1 = any failure; 2 = no
judgeable metric (an empty comparison must not read as a pass).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional, Tuple

#: metric table: (name, kind, path). ``kind`` picks direction and
#: tolerance band: "throughput" (higher better), "latency" / "steptime"
#: (lower better).
METRICS: Tuple[Tuple[str, str, Tuple[str, ...]], ...] = (
    ("tok_s", "throughput", ("value",)),
    ("gemma_7b.tok_s", "throughput",
     ("extra", "gemma_7b", "tokens_per_sec_per_chip")),
    ("gemma_7b.ttft_p50_ms", "latency",
     ("extra", "gemma_7b", "ttft_p50_ms")),
    ("gemma_7b.ttft_p99_ms", "latency",
     ("extra", "gemma_7b", "ttft_p99_ms")),
    ("ttft_p50_ms", "latency", ("extra", "single_stream_ttft_ms")),
    ("ttft_p99_ms", "latency",
     ("extra", "single_stream_ttft_p99_ms")),
    ("moe.tok_s", "throughput",
     ("extra", "mixtral_scaled_moe", "tokens_per_sec_per_chip")),
    # Step-time digests (ISSUE 15): bench phases now record the
    # sentinel's decode p50 into their artifacts; once two artifacts
    # carry it, regressions gate on ms/step directly.
    ("step_time.decode_p50_ms", "steptime",
     ("extra", "step_time", "decode_p50_ms")),
    ("gemma_7b.step_time.decode_p50_ms", "steptime",
     ("extra", "gemma_7b", "step_time", "decode_p50_ms")),
    # Spec×TP sweep (ISSUE 18): speculative decoding under the tp=8
    # mesh, keyed per-bs so the dict walk reaches each rung. Once a
    # trajectory artifact records these, the composition is REQUIRED —
    # a vanished or timed-out tp_spec7b phase fails as
    # absent/timed_out, never as a silent pass.
    ("gemma_7b.tp_spec.bs48.tok_s_chip", "throughput",
     ("extra", "gemma_7b", "tp_spec_sweep", "bs48", "tok_s_chip")),
    ("gemma_7b.tp_spec.bs192.tok_s_chip", "throughput",
     ("extra", "gemma_7b", "tp_spec_sweep", "bs192", "tok_s_chip")),
    ("gemma_7b.tp_spec.bs48.spec_step_ms", "steptime",
     ("extra", "gemma_7b", "tp_spec_sweep", "bs48", "spec_step_ms")),
    ("gemma_7b.tp_spec.bs192.spec_step_ms", "steptime",
     ("extra", "gemma_7b", "tp_spec_sweep", "bs192", "spec_step_ms")),
    # Ragged-kernel sweep (ISSUE 19): the mixed workload under the
    # single ragged paged kernel, keyed per bs. Required once a trajectory artifact records them
    # — a ragged rung that stops being served (kernel gate regressed to
    # the gather fallback and the phase crashed, or the phase vanished)
    # fails as absent/timed_out, never as a silent pass. The ragged
    # rungs' compiled-program counts gate as "steptime" (lower is
    # better): a ragged engine that starts compiling MORE programs than
    # it used to has lost the collapse the kernel exists for.
    ("gemma_7b.ragged.bs48.tok_s", "throughput",
     ("extra", "gemma_7b", "ragged_sweep", "bs48_ragged",
      "tokens_per_sec_per_chip")),
    ("gemma_7b.ragged.bs192.tok_s", "throughput",
     ("extra", "gemma_7b", "ragged_sweep", "bs192_ragged",
      "tokens_per_sec_per_chip")),
    ("gemma_7b.ragged.bs48.programs", "steptime",
     ("extra", "gemma_7b", "ragged_sweep", "bs48_ragged",
      "compiled_programs")),
    ("gemma_7b.ragged.bs192.programs", "steptime",
     ("extra", "gemma_7b", "ragged_sweep", "bs192_ragged",
      "compiled_programs")),
    # Two-tier agent sweep (ISSUE 20): turn-N TTFT of returning
    # sessions on an eviction-forcing pool, host tier off vs on.
    # Required once a trajectory artifact records them — a host-on rung
    # whose turn-3 TTFT regresses toward the host-off (full re-prefill)
    # number means the onload path stopped serving returning turns, and
    # a vanished agent7b phase fails as absent/timed_out, never as a
    # silent pass.
    ("gemma_7b.agent.host_on.ttft_turn2_ms", "latency",
     ("extra", "gemma_7b", "agent_sweep", "host_on", "ttft_turn2_ms")),
    ("gemma_7b.agent.host_on.ttft_turn3_ms", "latency",
     ("extra", "gemma_7b", "agent_sweep", "host_on", "ttft_turn3_ms")),
    ("gemma_7b.agent.host_off.ttft_turn3_ms", "latency",
     ("extra", "gemma_7b", "agent_sweep", "host_off", "ttft_turn3_ms")),
)


def load_artifact(path: str) -> dict:
    """Raw orchestrator dict, or the driver wrapper's ``parsed`` body."""
    with open(path) as f:
        data = json.load(f)
    if "parsed" in data and isinstance(data["parsed"], dict):
        return data["parsed"]
    return data


def lookup(artifact: dict, path: Tuple[str, ...]
           ) -> Tuple[Optional[float], Optional[str]]:
    """Walk ``path``; returns (value, None) on a number, (None, status)
    when the walk lands in an explicit failure entry (``{"status":
    "timeout"|"error"}`` — bench.py's phase-failure records), and
    (None, None) when simply absent."""
    node = artifact
    for key in path:
        if not isinstance(node, dict):
            return None, None
        if "status" in node and key not in node:
            return None, str(node["status"])
        node = node.get(key)
        if node is None:
            return None, None
    if isinstance(node, dict) and "status" in node:
        return None, str(node["status"])
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None, None
    return float(node), None


def judge(candidate: dict, trajectory: List[dict], *,
          tolerance: float, latency_tolerance: float,
          step_tolerance: float) -> List[dict]:
    """Per-metric verdicts. A metric is judged when the trajectory has
    a reference for it; it is REQUIRED when the newest trajectory
    artifact records it (absence is then a failure, not a skip)."""
    newest = trajectory[-1] if trajectory else {}
    verdicts: List[dict] = []
    for name, kind, path in METRICS:
        refs = []
        for art in trajectory:
            v, _status = lookup(art, path)
            if v is not None:
                refs.append(v)
        cand, status = lookup(candidate, path)
        required = lookup(newest, path)[0] is not None
        if not refs:
            if cand is not None:
                verdicts.append({"metric": name, "verdict": "new",
                                 "value": cand, "reference": None})
            continue
        higher = kind == "throughput"
        ref = max(refs) if higher else min(refs)
        if cand is None:
            if not required:
                continue
            verdict = {"timeout": "timed_out",
                       "error": "errored"}.get(status or "", "absent")
            verdicts.append({"metric": name, "verdict": verdict,
                             "value": None, "reference": ref,
                             "status": status})
            continue
        if higher:
            limit = (1.0 - tolerance) * ref
            ok = cand >= limit
        else:
            tol = (step_tolerance if kind == "steptime"
                   else latency_tolerance)
            limit = (1.0 + tol) * ref
            ok = cand <= limit
        verdicts.append({
            "metric": name,
            "verdict": "pass" if ok else "slower",
            "value": round(cand, 2),
            "reference": round(ref, 2),
            "limit": round(limit, 2),
            "ratio": round(cand / ref, 4) if ref else None,
        })
    return verdicts


def main() -> int:
    ap = argparse.ArgumentParser(
        description="Gate a bench artifact against the BENCH trajectory")
    ap.add_argument("--artifact", required=True,
                    help="fresh bench artifact (orchestrator JSON or "
                         "driver-wrapped one)")
    ap.add_argument("--trajectory", nargs="+", required=True,
                    help="trajectory artifacts, oldest first (the "
                         "newest defines which metrics are required)")
    ap.add_argument("--tolerance", type=float, default=0.25,
                    help="throughput band: pass at >= (1-t) x best "
                         "(default 0.25 — the chip shows ~2x "
                         "run-to-run variance; medians already "
                         "happened inside each artifact)")
    ap.add_argument("--latency-tolerance", type=float, default=0.5,
                    help="TTFT band: pass at <= (1+t) x best (default "
                         "0.5)")
    ap.add_argument("--step-tolerance", type=float, default=0.35,
                    help="step-time band: pass at <= (1+t) x best "
                         "(default 0.35)")
    ap.add_argument("--json", action="store_true",
                    help="emit the verdict table as JSON on stdout")
    args = ap.parse_args()

    candidate = load_artifact(args.artifact)
    trajectory = [load_artifact(p) for p in args.trajectory]
    verdicts = judge(candidate, trajectory,
                     tolerance=args.tolerance,
                     latency_tolerance=args.latency_tolerance,
                     step_tolerance=args.step_tolerance)
    judged = [v for v in verdicts if v["verdict"] != "new"]
    failures = [v for v in judged if v["verdict"] != "pass"]

    if args.json:
        print(json.dumps({"verdicts": verdicts,
                          "failures": len(failures),
                          "passed": not failures and bool(judged)}))
    else:
        print(f"perf_gate: {args.artifact} vs "
              f"{len(trajectory)} trajectory artifact(s)")
        print(f"  {'metric':<34} {'verdict':<10} {'value':>10} "
              f"{'reference':>10} {'limit':>10}")
        for v in verdicts:
            print(f"  {v['metric']:<34} {v['verdict']:<10} "
                  f"{v['value'] if v['value'] is not None else '-':>10} "
                  f"{v['reference'] if v['reference'] is not None else '-':>10} "
                  f"{v.get('limit', '-'):>10}")
    if not judged:
        print("perf_gate: NO judgeable metric (trajectory and artifact "
              "share nothing) — refusing to pass an empty comparison",
              file=sys.stderr)
        return 2
    if failures:
        for v in failures:
            print(f"perf_gate: FAIL {v['metric']}: {v['verdict']} "
                  f"(value={v['value']}, reference={v['reference']})",
                  file=sys.stderr)
        return 1
    print(f"perf_gate: PASS ({len(judged)} metric(s) judged, "
          f"{len(verdicts) - len(judged)} new)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
