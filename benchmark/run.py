#!/usr/bin/env python3
"""One run of one cell: ``python3 benchmark/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Compares the program with the configuration's plain reference
(benchmark/refcheck.py), starts the real server (benchmark/serve.py) once that
child has released the chip, waits for /health, offers a ramp of the
cell's traffic, measures for ``--seconds``, keeps offering load until the
last measured request has ended, stops the child, and prints one JSON object
as the last line of stdout. Everything that belongs to one configuration, one
mix, one cell or one metric is a data file found by the name in
BENCHMARK.json; see benchmark/README.md. This parent never imports jax while
a child lives.

Without a TPU the children fail at start and this exits non-zero with no
result. ``--rehearse`` is the one exception: toy-8m on JAX_PLATFORMS=cpu for
a few seconds, to exercise launcher, generator, readers and the last line's
shape; it prints no number under a metric's name.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse      # noqa: E402
import asyncio       # noqa: E402
import dataclasses   # noqa: E402
import functools     # noqa: E402
import importlib.util  # noqa: E402
import json          # noqa: E402
import math          # noqa: E402
import os            # noqa: E402
import re            # noqa: E402
import shutil        # noqa: E402
import signal        # noqa: E402
import socket        # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT))

from modelmap import (fields, key_map, mesh_of, mesh_problems,  # noqa: E402
                      rehearsal_mesh, sizes)

READY_TIMEOUT_S = 1100.0
GO_FILE = "chip_is_free"
TRACE_S = 3.0
REHEARSAL_ENV = {"DECODE_BATCH_SIZE": "4", "MAX_SEQ_LEN": "1024",
                 "PREFILL_BUCKETS": "64,256", "MAX_NEW_TOKENS": "12",
                 "KV_POOL_BLOCKS": "0"}
REHEARSAL_SCALE = 1.0 / 16.0
REHEARSAL_RATE = 1.5


class BenchFailure(Exception):
    """The run cannot produce a result (no chip, child died, bad data)."""


def say(**kw) -> None:
    """An earlier stdout line: what the last line may not carry."""
    print("bench: " + json.dumps(kw), flush=True)


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def http_json(url: str, method: str = "GET", timeout: float = 30.0):
    req = urllib.request.Request(url, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        body = e.read()
        try:
            return e.code, json.loads(body)
        except ValueError:
            return e.code, {"raw": body[:300].decode("utf-8", "replace")}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def child_env(cfg_file: dict, port: int, rehearse: bool) -> dict:
    """The caller's environment minus every setting of the service (each
    ServiceConfig field reads the variable of its upper-cased name), plus the
    configuration file's pins."""
    from ai_agent_kubectl_tpu.config import ServiceConfig

    knobs = {f.name.upper() for f in dataclasses.fields(ServiceConfig)}
    knobs |= {"TRUST_PROXY", "BENCH_RUN"}
    env = {k: v for k, v in os.environ.items() if k not in knobs}
    env.update(cfg_file["server_env"])
    env.update({"HOST": "127.0.0.1", "PORT": str(port), "MODEL_NAME": cfg_file["name"],
                "DRAIN_TIMEOUT_SECS": "2", "PYTHONUNBUFFERED": "1"})
    if rehearse:
        mesh = rehearsal_mesh(mesh_of(cfg_file))
        env.update(REHEARSAL_ENV)
        env.update({"JAX_PLATFORMS": "cpu",
                    "MODEL_NAME": cfg_file.get("rehearsal_model", "toy-8m")})
        if mesh:
            env.update({"MESH_SHAPE": ",".join(f"{a}:{n}" for a, n in mesh.items()),
                        "XLA_FLAGS": "--xla_force_host_platform_device_count="
                                     f"{math.prod(mesh.values())}"})
    else:
        env["JAX_PLATFORMS"] = "tpu"
        env["TOKENIZER_PATH"] = str(tokenizer_path())
    return env


def tokenizer_path() -> Path:
    return ROOT / "ai_agent_kubectl_tpu" / "assets" / "tokenizer-k8s.json"


def resolve_cell(bench: dict, workload: str):
    """(cell, its configuration's entry, configuration file, mix file), each
    found by the name BENCHMARK.json gives; StopIteration for an unknown name."""
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (cell, cfg_entry, load_json(ROOT / cfg_entry["file"]),
            load_json(HERE / "traffic" / f"{cell['traffic']}.json"))


def launch(cell: dict, cfg_entry: dict, cfg_file: dict, seed: int, tag: str, rehearse: bool):
    """A fresh run directory and the two children. Returns (children, base
    URL, the children's environment, run directory)."""
    run_dir = ROOT / "benchmark_out" / f"{cell['name']}.{tag}"
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    port = free_port()
    env = child_env(cfg_file, port, rehearse)
    children = start_children(cfg_entry["file"], seed, run_dir, env, rehearse)
    return children, f"http://127.0.0.1:{port}", env, run_dir


def start_children(cfg_path: str, seed: int, run_dir: Path, env: dict, rehearse: bool) -> list:
    """The comparison with the reference and the server, started together:
    the server does its imports and then waits, before it touches the chip,
    for the file that wait_ready() creates once the comparison's PROCESS has
    ended (its result file is not enough: a process that is still exiting
    holds the TPU's lock). Returns [comparison, server]."""
    common = ["--config", str(ROOT / cfg_path), "--seed", str(seed)]
    if rehearse:
        common.append("--rehearse")
    cmds = [("refcheck", [sys.executable, str(HERE / "refcheck.py"), *common,
                          "--out", str(run_dir / "refcheck.json")]),
            ("server", [sys.executable, str(HERE / "serve.py"), *common,
                        "--run-dir", str(run_dir), "--after", str(run_dir / GO_FILE)])]
    procs = []
    for name, cmd in cmds:
        with open(run_dir / f"{name}.log", "wb") as log:
            procs.append(subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=log,
                                          stderr=subprocess.STDOUT, start_new_session=True))
    return procs


def wait_ready(children: list, base: str, deadline: float) -> dict:
    check, proc = children
    go = Path(proc.args[proc.args.index("--after") + 1])
    while time.monotonic() < deadline:
        if check.poll() not in (None, 0):
            raise BenchFailure(f"the comparison with the reference exited with code "
                               f"{check.returncode}")
        if check.poll() == 0 and not go.exists():
            go.touch()      # the comparison's process is gone and the chip is free
        if proc.poll() is not None:
            raise BenchFailure(f"server exited with code {proc.returncode} before ready")
        try:
            status, health = http_json(base + "/health", timeout=5.0)
        except (urllib.error.URLError, OSError, ValueError):
            time.sleep(0.25)
            continue
        if status == 200 and health.get("engine_ready"):
            return health
        if health.get("engine") == "degraded":
            raise BenchFailure("engine construction failed; the server started degraded")
        time.sleep(0.25)
    raise BenchFailure("server not ready before the deadline")


def stop_child(proc) -> None:
    """SIGTERM, a short wait, then the whole group; nothing is left behind."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=8.0)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


@functools.lru_cache(maxsize=None)
def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(f"reader_{name}", HERE / "readers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


EVENT_RE = {
    "submitted": re.compile(r"^engine: submitted to batch scheduler"),
    "admitted": re.compile(r"^engine: (group-)?admitted to slot"),
    # the first token arrives alone ("first token") or, under ragged admission,
    # with its chunk ("chunk consumed (+16 tok, ...")
    "first": re.compile(r"^engine: (?:first token|chunk consumed \(\+([1-9]\d*) tok)"),
    "finished": re.compile(r"^engine: finished \((\w+), (\d+) tokens\)"),
}


def engine_record(detail: dict) -> dict:
    """queue/prefill/decode as the engine stamped them on the request's trace
    (what engine_metadata reports on the non-streaming route)."""
    t = {}
    tokens = finish = None
    with_first = 1
    for ev in detail.get("events", []):
        for key, rx in EVENT_RE.items():
            m = rx.match(ev["message"])
            if not m:
                continue
            if key == "finished":
                t[key] = ev["offset_ms"]
                finish, tokens = m.group(1), int(m.group(2))
            elif key not in t:
                t[key] = ev["offset_ms"]
                if key == "first" and m.group(1):
                    with_first = int(m.group(1))
    out = {"completion_tokens": tokens, "finish": finish}
    if all(k in t for k in EVENT_RE):
        out["queue_ms"] = t["admitted"] - t["submitted"]
        out["prefill_ms"] = t["first"] - t["admitted"]
        out["decode_ms"] = t["finished"] - t["first"]
        out["handler_ms"] = detail["duration_ms"] - (t["finished"] - t["submitted"])
        if tokens and tokens > with_first:
            out["decode_ms_per_tok"] = out["decode_ms"] / (tokens - with_first)
    return out


def cell_metrics(bench: dict, kind: str, cell: str) -> list:
    return [m for m in bench[kind] if "workloads" not in m or cell in m["workloads"]]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    if not (ROOT / "ai_agent_kubectl_tpu").is_dir():
        print("bench: the program (ai_agent_kubectl_tpu/) is not in this checkout",
              file=sys.stderr)
        return 2
    bench = load_json(ROOT / "BENCHMARK.json")
    try:
        cell, cfg_entry, cfg_file, mix = resolve_cell(bench, args.workload)
    except StopIteration:
        print(f"bench: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2
    cell_path = HERE / "cells" / f"{cell['name']}.json"
    cell_data = load_json(cell_path) if cell_path.exists() else {}
    if args.rehearse:
        cell_data["rate_rps"] = REHEARSAL_RATE
    peaks = load_json(HERE / "peaks.json")
    rules = load_json(HERE / "trace_categories.json")

    import loadgen
    import workgen

    sz = sizes(cfg_file)
    mesh = mesh_of(cfg_file)
    unfit = mesh_problems(cfg_file, cell["chips"])
    if unfit:       # before anything is launched
        print("bench: " + "; ".join(unfit), file=sys.stderr)
        return 2
    if args.rehearse:
        mesh = rehearsal_mesh(mesh)
    children, base, env, run_dir = launch(
        cell, cfg_entry, cfg_file, args.seed, f"seed{args.seed}.trace{args.trace}", args.rehearse)
    words = workgen.Words(None if args.rehearse else str(tokenizer_path()))
    plan = workgen.build(mix, cell_data, env, args.seed, args.seconds, words,
                         REHEARSAL_SCALE if args.rehearse else 1.0)

    try:
        health = wait_ready(children, base, T_START + READY_TIMEOUT_S)
        ready_s = time.monotonic() - T_START
        problems = check_health(health, cfg_file, peaks, cell, mesh, args.rehearse)
        if problems and not args.rehearse:
            raise BenchFailure("/health: " + "; ".join(problems))
        if args.rehearse:   # the platform and the kind are always among them
            say(rehearsal_health_problems=problems)
        refcheck = load_json(run_dir / "refcheck.json")
        model_cfg = load_json(run_dir / "model_config.json")
        say(ready_s=round(ready_s, 2), refcheck=refcheck, offered=plan.offered,
            model=model_cfg["name"], n_layers=model_cfg["n_layers"])

        samples, poll_stop = [], asyncio.Event()
        trace_info = {}

        async def poll_health() -> None:
            loop = asyncio.get_running_loop()
            while not poll_stop.is_set():
                try:
                    _, h = await loop.run_in_executor(None, http_json, base + "/health")
                    samples.append(h)
                except (urllib.error.URLError, OSError, ValueError):
                    pass
                try:
                    await asyncio.wait_for(poll_stop.wait(), 1.0)
                except asyncio.TimeoutError:
                    pass

        async def capture() -> None:
            loop = asyncio.get_running_loop()
            status, body = await loop.run_in_executor(
                None, lambda: http_json(f"{base}/debug/profile?seconds={TRACE_S}",
                                        "POST", 300.0))
            trace_info.update(body if status == 200 else {"error": body})

        async def go():
            poller = asyncio.ensure_future(poll_health()) if args.trace else None
            try:
                return await loadgen.drive(
                    plan, base, mix["endpoint"], args.seconds, f"b{args.seed % 10**9}",
                    after_measured=capture if args.trace else None)
            finally:
                poll_stop.set()
                if poller:
                    await poller

        _, health_before = http_json(base + "/health")
        records, t0 = asyncio.run(go())
        setup_s = t0 - T_START
        window_wall = time.time() + (t0 - time.monotonic())   # t0 on the wall clock
        _, health_after = http_json(base + "/health")
        _, index = http_json(base + "/debug/requests?limit=100000", timeout=60.0)
        measured = [r for r in records if r.measured]
        # The engine's record of every finished request: its token count
        # (the client sees one frame per decode chunk, not per token) and,
        # for the per-layer metrics, its phase times.
        engine = {}
        for r in records:
            if r.outcome != "done":
                continue
            status, detail = http_json(f"{base}/debug/requests/{r.rid}")
            if status == 200:
                rec = engine_record(detail)
                r.tokens = rec.get("completion_tokens")
                if r.measured:
                    engine[r.rid] = rec
        memory = load_json(run_dir / "memory.json") if (run_dir / "memory.json").exists() else {}
    except BenchFailure as e:
        print(f"bench: FAILED: {e}", file=sys.stderr)
        for name in ("refcheck.log", "server.log"):
            tail = (run_dir / name).read_text(errors="replace")[-4000:]
            print(f"--- tail of {run_dir / name} ---\n{tail}", file=sys.stderr)
        return 1
    finally:
        for child in children:
            stop_child(child)

    # ---- the trace
    trace = None
    if args.trace and trace_info.get("trace_dir"):
        import xtrace
        path = xtrace.find_xplane(trace_info["trace_dir"])
        if path:
            rep = xtrace.load(path)
            if os.environ.get("BENCH_DESCRIBE_TRACE"):
                (run_dir / "trace_description.txt").write_text(
                    xtrace.describe(xtrace.load(path, 600, all_stats=True), 600))
                (run_dir / "trace_small.json").write_text(
                    json.dumps(xtrace.excerpt(rep, 0.5, 0.12)))
            trace = xtrace.reduce(rep, rules, sz["num_hidden_layers"],
                                  math.prod(mesh.values()))
            shutil.rmtree(trace_info["trace_dir"], ignore_errors=True)

    # ---- correctness
    by_rid = {s["request_id"]: s for s in index.get("requests", [])}
    allow_cache = bool(mix.get("repeats_allowed", False))
    bad = []
    for r in measured:
        s = by_rid.get(r.rid, {})
        if r.outcome != "done" or not r.command.strip():
            bad.append((r.rid, r.outcome or "not finished"))
        elif s.get("degraded") or (s.get("from_cache") and not allow_cache):
            bad.append((r.rid, "degraded" if s.get("degraded") else "from_cache"))
    compiles = []
    log = run_dir / "compiles.log"
    if log.exists():
        compiles = [tuple(map(float, ln.split())) for ln in log.read_text().splitlines() if ln.strip()]
    in_window = [c for c in compiles
                 if window_wall <= c[0] <= window_wall + args.seconds + 1.0]
    pool_after = (health_after.get("kv_pool") or {})
    pool_before = (health_before.get("kv_pool") or {})
    starved = pool_after.get("starved_slots_total", 0) - pool_before.get("starved_slots_total", 0)
    faults = []
    if bad:
        faults.append(f"{len(bad)} measured requests did not end in a safe command: {bad[:5]}")
    if not measured:
        faults.append("no request fell due in the window")
    if in_window:
        faults.append(f"{len(in_window)} programs compiled inside the window")
    if starved:
        faults.append(f"the KV pool starved {starved} slots")
    cap = int(env["MAX_NEW_TOKENS"])
    off_cap = sorted({e["completion_tokens"] for e in engine.values()
                      if e.get("completion_tokens") not in (None, cap)})
    if off_cap:     # serve.py::answers_run_to_the_cap: the seed may not change the work
        faults.append(f"answers of {off_cap[:5]} tokens, not MAX_NEW_TOKENS = {cap}")
    if not refcheck.get("ok"):
        faults.append(f"the program disagrees with the plain reference: {refcheck}")
    if refcheck.get("mesh") != mesh:
        faults.append(f"the comparison ran over the mesh {refcheck.get('mesh')}, not {mesh}")
    if trace and not args.rehearse:     # the CPU has no device plane
        faults.extend(trace["problems"])
    correct = not faults
    if faults:      # the last line says only false; this says why
        print("bench: NOT CORRECT: " + "; ".join(faults), file=sys.stderr, flush=True)

    # ---- metrics
    kind = health.get("device_kind", "")
    ctx = {"records": measured, "all_records": records, "window": (t0, args.seconds),
           "setup_s": setup_s, "engine": engine, "health_before": health_before,
           "health_after": health_after, "health_samples": samples, "trace": trace,
           "trace_rules": rules, "sizes": sz, "fields": fields(sz, key_map(cfg_file)),
           "config": cfg_file, "mesh": mesh, "chips": cell["chips"],
           "peaks": peaks.get(kind, {})}
    values = {}
    for m in cell_metrics(bench, "per_layer" if args.trace else "end_to_end", cell["name"]):
        spec = load_json(HERE / "metrics" / f"{m['name']}.json")
        v = load_reader(spec["reader"]).read(ctx, spec.get("params", {}))
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}

    frames = [len(r.frames) for r in measured]
    unknown = sum(1 for r in records if r.tokens is None
                  and any(t0 <= t < t0 + args.seconds for t in r.frames))
    tokens = [e["completion_tokens"] for e in engine.values() if e.get("completion_tokens")]
    shortest = sorted((engine[r.rid].get("completion_tokens") or 0, engine[r.rid].get("finish"),
                       r.command[-60:]) for r in measured if r.rid in engine)[:3]
    say(measured=len(measured), unmeasured=len(records) - len(measured), failed=bad[:10],
        mean_token_frames=round(sum(frames) / max(1, len(frames)), 2),
        mean_completion_tokens=round(sum(tokens) / len(tokens), 2) if tokens else None,
        shortest_answers=shortest,
        frames_in_window_of_unknown_length=unknown,
        gen_late_max_ms=round(max((r.sent - r.due) * 1000.0 for r in measured), 3) if measured else None,
        compiles_total=len(compiles), compiles_in_window=len(in_window),
        cache_files=sum(len(f) for _, _, f in os.walk(
            os.environ.get("JAX_COMPILATION_CACHE_DIR") or ROOT / ".jax_cache")),
        pool_starved=starved, setup_s=round(setup_s, 2), ready_s=round(ready_s, 2),
        trace=({k: trace.get(k) for k in ("busy_s", "window_s", "forward_passes", "category_s")}
               if trace else trace_info.get("error")),
        memory=memory)
    device = {"platform": health.get("platform"), "kind": kind,
              "count": health.get("devices"),
              "memory_peak_bytes": memory.get("peak_bytes_in_use")}
    result = {"correct": correct, "attempted": len(measured), "failed": len(bad),
              "metrics": values, "device": device}
    if args.trace and trace and trace.get("devices"):
        device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
        result["breakdown"] = trace["breakdown"]
    if args.rehearse:
        say(rehearsal_values={k: v["value"] for k, v in values.items()})
        result["metrics"] = {}
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


def check_health(health: dict, cfg_file: dict, peaks: dict, cell: dict, mesh: dict,
                 rehearse: bool) -> list:
    """Why this server is not the cell: the wrong engine, model, platform or
    attention regime and, for a configuration with a mesh, anything in
    /health.sharding (engine/batcher.py::sharding_health) short of that mesh
    with the weights split and the pool sharded on it. A server that fell back
    to replicated weights or a gathered pool is another system."""
    problems = []
    if health.get("engine") != "jax-batched":
        problems.append(f"engine {health.get('engine')!r}")
    model = cfg_file.get("rehearsal_model", "toy-8m") if rehearse else cfg_file["name"]
    if health.get("model") != model:
        problems.append(f"model {health.get('model')!r}")
    if health.get("platform") != "tpu":
        problems.append(f"platform {health.get('platform')!r}, want 'tpu'")
    if health.get("device_kind") not in peaks:
        problems.append(f"device_kind {health.get('device_kind')!r} is not in peaks.json")
    if (health.get("devices") or 0) < cell["chips"]:
        problems.append(f"{health.get('devices')} devices, the cell asks for {cell['chips']}")
    regime = (health.get("kv_pool") or {}).get("attention_regime")
    if regime != "ragged":
        problems.append(f"attention_regime {regime!r}, want 'ragged'")
    if mesh:
        problems.extend(sharding_problems(health.get("sharding"), mesh))
    return problems


def sharding_problems(sharding, mesh: dict) -> list:
    if not sharding:
        return [f"/health reports no sharding; the configuration's mesh is {mesh}"]
    problems = []
    served = {a: n for a, n in (sharding.get("mesh") or {}).items() if n > 1}
    if served != mesh:
        problems.append(f"sharding.mesh {served}, want {mesh}")
    if sharding.get("devices") != math.prod(mesh.values()):
        problems.append(f"sharding.devices {sharding.get('devices')}, "
                        f"want {math.prod(mesh.values())}")
    share = 1.0 / mesh.get("model", 1)
    if abs((sharding.get("weights_shard_fraction") or 0.0) - share) > 1e-6:
        problems.append(f"weights_shard_fraction {sharding.get('weights_shard_fraction')}, "
                        f"want {share} (the weights did not split over the model axis)")
    if sharding.get("pool_sharded") is not True:
        problems.append("the KV pool is not sharded (pool_sharded)")
    if sharding.get("kv_pool_mesh_fallback") is not False:
        problems.append("the KV pool fell back off the mesh (kv_pool_mesh_fallback)")
    if sharding.get("attention_regime") != "ragged":
        problems.append(f"sharding.attention_regime {sharding.get('attention_regime')!r}, "
                        f"want 'ragged'")
    return problems


if __name__ == "__main__":
    sys.exit(main())
