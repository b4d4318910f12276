#!/usr/bin/env python3
"""chip_smoke.py — the serving path, end to end, on one TPU.

The quickest proof that the system still starts on the chip. It starts the
real server (``python -m ai_agent_kubectl_tpu.server``) as its ONE child, at
the full registered width and depth of Llama-3-8B-Instruct (int8 weights
random-initialised from a seed, bf16 KV, every shipped default: KV pool,
radix cache, CHUNK_LEN=16,
PREFILL_BUCKETS 64..1024, MAX_SEQ_LEN=1024), waits for /health to report
ready, then drives it over HTTP:

1. eight concurrent distinct short queries;
2. one repeat of the first (must come ``from_cache``);
3. one ``/kubectl-command/stream`` (must end ``event: done``);
4. one ~700-token query, whose suffix rides the 1,024-wide admission
   program instead of only compiling it.

Every response must be a 200 with a non-empty command and not ``degraded``;
/health must name platform ``tpu``, the device kind and count, engine
``jax-batched``, ready, and attention regime ``ragged`` (under
``MESH_SHAPE`` also every mesh device, the pool sharded, no mesh fallback,
one device holding 1/tp of the weights); /metrics must show at least one
generated token per engine-served request; SIGTERM must end the child
cleanly. Anything else — child died, engine degraded, a non-200,
another regime or platform, a deadline — is a non-zero exit with the
server log's tail on stderr and NO result on stdout.

This parent never imports jax: a chip belongs to one process, and the
child needs it. The child's JAX is pinned to the platform the smoke is
about to assert (``JAX_PLATFORMS=tpu``), so where JAX finds no chip the
engine fails at start instead of quietly serving from the CPU with
interpreted kernels. Of the service's own settings only ``MESH_SHAPE`` is
taken from the caller's environment (``MESH_SHAPE=tp=4 python
chip_smoke.py`` on a four-chip host); everything else is pinned below.

``--cpu-toy`` (never the default) runs the identical script at ``toy-8m``
with ``JAX_PLATFORMS=cpu`` and skips only the ``tpu``/``ragged``
assertions, so the command can be debugged where there is no chip.

On success stdout is two lines, each one JSON object. First the report:
model, quant, batch, regime, seconds to ready, compile-cache directory and
entry counts, requests, tokens, wall per request, downgrade lines seen
(also written to ``chiprun_out/chip_smoke_report.json``). Then, LAST, the
result and nothing else — exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device as the child's ``jax.devices()`` reports it.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import hashlib
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "chiprun_out"

MODEL = "llama-3-8b-instruct"
TOY_MODEL = "toy-8m"
#: Decode slots. The issue's starting point of 32 does not fit: beside
#: 8.0 GB of int8 weights the 1,024-wide admission program wants 18.4 GB
#: of a v5e's 15.75 (XLA's own figure; the layer scan holds a second copy
#: of the whole KV pool — PERF.md). 16 leaves ~1.5 GB of headroom. The
#: batch is what gives way, never a width.
BATCH = 16

SHORT_QUERIES = [
    "list all pods in the default namespace",
    "show deployments in namespace kube-system",
    "get nodes with wide output",
    "describe the service named frontend",
    "show logs for pod web-0",
    "list config maps across all namespaces",
    "get persistent volume claims in namespace data",
    "show events sorted by creation time",
]
STREAM_QUERY = "list stateful sets in namespace storage"
LONG_MIN_PROMPT_TOKENS = 650


def long_query(toy: bool) -> str:
    """A query whose prompt is ~700 tokens (58 pods: 717 under the in-repo
    k8s tokenizer; 16 pods: 846 under toy-8m's byte tokenizer): behind
    the radix-shared system prompt the suffix exceeds the 512 bucket, so
    it rides the 1,024-wide admission program, and prompt + MAX_NEW_TOKENS
    stays inside MAX_SEQ_LEN (a slot that runs out of KV answers
    ``degraded``)."""
    return "show the status of these pods one by one: " + ", ".join(
        f"pod api-{i} in namespace team-{i % 7}"
        for i in range(16 if toy else 58))


#: Server log lines worth repeating in the report: every attention or
#: device downgrade the engine takes is logged with one of these.
DOWNGRADE_RE = re.compile(
    r"fall(?:ing|s)? back|fallback|gather path|using dense|unsupported|"
    r"does not compose|not flash-tileable", re.IGNORECASE)


class SmokeFailure(Exception):
    pass


def child_env(toy: bool, port: int) -> dict:
    """The caller's environment minus every service setting except
    MESH_SHAPE (each ``ServiceConfig`` field reads the variable of its
    upper-cased name), plus the smoke's own configuration."""
    from ai_agent_kubectl_tpu.config import ServiceConfig

    knobs = {f.name.upper() for f in dataclasses.fields(ServiceConfig)}
    knobs.add("TRUST_PROXY")
    knobs.discard("MESH_SHAPE")
    env = {k: v for k, v in os.environ.items() if k not in knobs}
    env.update({
        "JAX_PLATFORMS": "cpu" if toy else "tpu",
        "ENGINE": "jax",
        "MODEL_NAME": TOY_MODEL if toy else MODEL,
        "QUANT": "int8",
        "DECODE_BATCH_SIZE": str(BATCH),
        # A random-weight model only passes server/safety.py under the
        # grammar; with it every answer is a 200 with a safe command.
        "GRAMMAR_DECODE": "true",
        "RATE_LIMIT": "100000/minute",
        "HOST": "127.0.0.1",
        "PORT": str(port),
    })
    if not toy:
        # toy-* models serve the byte tokenizer; a registered model needs
        # a tokenizer file, and the in-repo asset is the only one here.
        env["TOKENIZER_PATH"] = str(
            ROOT / "ai_agent_kubectl_tpu" / "assets" / "tokenizer-k8s.json")
    return env


def compile_cache_dir(toy: bool):
    """Where the child keeps its persistent compilation cache — the rule
    of engine/jax_engine.py::_setup_compile_cache (none on the CPU)."""
    if toy:
        return None
    from ai_agent_kubectl_tpu.config import DEFAULT_COMPILE_CACHE_DIR

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or DEFAULT_COMPILE_CACHE_DIR


def count_entries(path) -> int:
    if path is None or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 30.0):
    """(status, bytes). HTTP error statuses are returned, not raised."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def wait_ready(proc, base: str, deadline: float) -> None:
    """Poll /health until the engine is ready. The socket binds only after
    engine start, so "connection refused" means still starting; a 503
    from the degraded placeholder engine, or a dead child, is a failure
    now — not something to wait out."""
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure(
                f"server exited with code {proc.returncode} before ready")
        try:
            status, raw = http("GET", base + "/health", timeout=5.0)
        except (urllib.error.URLError, OSError):
            time.sleep(1.0)
            continue
        health = json.loads(raw)
        if status == 200 and health.get("engine_ready"):
            return
        if health.get("engine") == "degraded":
            raise SmokeFailure(
                "engine construction failed; the server started degraded")
        time.sleep(1.0)
    raise SmokeFailure("server not ready before the deadline")


def ask(base: str, query: str) -> dict:
    t0 = time.monotonic()
    status, raw = http("POST", base + "/kubectl-command", {"query": query},
                       timeout=180.0)
    if status != 200:
        raise SmokeFailure(f"{query[:40]!r}: HTTP {status}: {raw[:300]!r}")
    body = json.loads(raw)
    if not body.get("kubectl_command", "").strip():
        raise SmokeFailure(f"{query[:40]!r}: empty command")
    if body.get("degraded"):
        raise SmokeFailure(f"{query[:40]!r}: degraded response")
    body["_wall_s"] = time.monotonic() - t0
    return body


def ask_stream(base: str, query: str) -> str:
    status, raw = http("POST", base + "/kubectl-command/stream",
                       {"query": query}, timeout=180.0)
    text = raw.decode("utf-8", "replace")
    if status != 200:
        raise SmokeFailure(f"stream: HTTP {status}: {text[:300]!r}")
    events = re.findall(r"^event: (\w+)\ndata: (.*)$", text, re.MULTILINE)
    names = [name for name, _ in events]
    if not names or names[-1] != "done" or "error" in names \
            or "degraded" in names:
        raise SmokeFailure(f"stream: events {names}, want ... done")
    command = events[-1][1].strip()
    if not command:
        raise SmokeFailure("stream: empty command in the done event")
    return command


def drive(base: str, toy: bool):
    """The request phases; any miss raises. Returns ``(commands, stats)``:
    the answers in a fixed order, and the counts and timings the report
    line carries."""
    with concurrent.futures.ThreadPoolExecutor(len(SHORT_QUERIES)) as pool:
        futures = [pool.submit(ask, base, q) for q in SHORT_QUERIES]
        shorts = [f.result() for f in futures]
    if any(b["from_cache"] for b in shorts):
        raise SmokeFailure("a first-time query was served from the cache")

    repeat = ask(base, SHORT_QUERIES[0])
    if not repeat["from_cache"]:
        raise SmokeFailure("the repeated query was not served from_cache")
    if repeat["kubectl_command"] != shorts[0]["kubectl_command"]:
        raise SmokeFailure("the cached answer differs from the first one")

    streamed = ask_stream(base, STREAM_QUERY)

    long_ = ask(base, long_query(toy))
    prompt_tokens = long_["engine_metadata"]["prompt_tokens"]
    if prompt_tokens < LONG_MIN_PROMPT_TOKENS:
        raise SmokeFailure(
            f"long query was {prompt_tokens} prompt tokens; its suffix "
            f"must exceed the 512 bucket to ride the 1,024-wide program")

    generated = shorts + [long_]
    sent = len(generated) + 2               # + the repeat and the stream
    return [b["kubectl_command"] for b in generated] + [streamed], {
        "requests": {"sent": sent, "succeeded": sent},
        "engine_served": sent - 1,          # all but the cache hit
        "long_prompt_tokens": prompt_tokens,
        "wall_per_request_s": round(
            sum(b["_wall_s"] for b in generated) / len(generated), 3),
        "long_request_wall_s": round(long_["_wall_s"], 3),
    }


def check_health(health: dict, toy: bool, mesh_shape: str) -> dict:
    """What the server says it serves from. Returns the device triple."""
    want_model = TOY_MODEL if toy else MODEL
    problems = []
    # ENGINE=jax with DECODE_BATCH_SIZE > 1 is the continuous-batching
    # engine, which names itself "jax-batched".
    if (health.get("engine") != "jax-batched"
            or not health.get("engine_ready")):
        problems.append(f"engine {health.get('engine')!r} "
                        f"ready={health.get('engine_ready')}")
    if health.get("model") != want_model:
        problems.append(f"model {health.get('model')!r}")
    if not health.get("device_kind") or not health.get("devices"):
        problems.append("no device_kind/devices in /health")
    want_platform = "cpu" if toy else "tpu"
    if health.get("platform") != want_platform:
        problems.append(f"platform {health.get('platform')!r}, "
                        f"want {want_platform!r}")
    regime = (health.get("kv_pool") or {}).get("attention_regime")
    if not toy and regime != "ragged":
        problems.append(f"attention_regime {regime!r}, want 'ragged'")
    if mesh_shape:
        sh = health.get("sharding") or {}
        tp = (sh.get("mesh") or {}).get("model", 1)
        if (sh.get("devices") != health.get("devices")
                or not sh.get("pool_sharded")
                or sh.get("kv_pool_mesh_fallback")
                or sh.get("weights_shard_fraction") != 1.0 / tp
                or (not toy and sh.get("attention_regime") != "ragged")):
            problems.append(f"MESH_SHAPE={mesh_shape}: sharding {sh}")
    if problems:
        raise SmokeFailure("/health: " + "; ".join(problems))
    return {"platform": health["platform"], "kind": health["device_kind"],
            "count": health["devices"]}


def tokens_generated(base: str) -> int:
    status, raw = http("GET", base + "/metrics")
    if status != 200:
        raise SmokeFailure(f"/metrics: HTTP {status}")
    m = re.search(r"^engine_tokens_generated_total (\S+)$", raw.decode(),
                  re.MULTILINE)
    if m is None:
        raise SmokeFailure("/metrics: no engine_tokens_generated_total")
    return int(float(m.group(1)))


def kill(proc) -> None:
    """Leave nothing of the child's process group behind."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def stop(proc) -> None:
    """SIGTERM, then require a clean exit: the server drains and returns
    0."""
    proc.send_signal(signal.SIGTERM)
    try:
        proc.wait(timeout=60.0)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            "server did not exit within 60 s of SIGTERM") from None
    if proc.returncode != 0:
        raise SmokeFailure(f"server exit code {proc.returncode}")


def run(toy: bool, ready_timeout: float, log_path: Path) -> dict:
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    mesh_shape = os.environ.get("MESH_SHAPE", "")
    cache_dir = compile_cache_dir(toy)
    entries_before = count_entries(cache_dir)
    t0 = time.monotonic()
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "ai_agent_kubectl_tpu.server"],
            cwd=ROOT, env=child_env(toy, port), stdout=log,
            stderr=subprocess.STDOUT, start_new_session=True)
    try:
        wait_ready(proc, base, t0 + ready_timeout)
        seconds_to_ready = round(time.monotonic() - t0, 1)
        commands, stats = drive(base, toy)
        status, raw = http("GET", base + "/health")
        if status != 200:
            raise SmokeFailure(f"/health after the requests: HTTP {status}")
        health = json.loads(raw)
        device = check_health(health, toy, mesh_shape)
        tokens = tokens_generated(base)
        if tokens < stats["engine_served"]:
            raise SmokeFailure(
                f"{tokens} tokens generated for "
                f"{stats['engine_served']} engine-served requests")
        stop(proc)
    finally:
        kill(proc)
    (log_path.parent / "chip_smoke_commands.json").write_text(
        json.dumps(commands, indent=1))
    return {
        "device": device,
        "model": health["model"],
        "quant": "int8",
        "batch": BATCH,
        "mesh_shape": mesh_shape,
        "attention_regime": health["kv_pool"]["attention_regime"],
        "seconds_to_ready": seconds_to_ready,
        "compile_cache": {"dir": cache_dir,
                          "entries_before": entries_before,
                          "entries_after": count_entries(cache_dir)},
        **stats,
        "tokens_generated": tokens,
        # temperature 0: the same digest from a one-chip and a tp=4 run
        # means the sharded engine gave the same answers.
        "answers_sha256": hashlib.sha256(
            "\n".join(commands).encode()).hexdigest(),
        "downgrades": downgrades(log_path),
    }


def downgrades(log_path: Path) -> list:
    lines = log_path.read_text(errors="replace").splitlines()
    return [ln[-300:] for ln in lines
            if DOWNGRADE_RE.search(ln) and " - Config: " not in ln][:20]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-toy", action="store_true",
                    help="debug run: toy-8m on JAX_PLATFORMS=cpu; skips "
                         "only the tpu/ragged assertions")
    ap.add_argument("--ready-timeout", type=float, default=1000.0,
                    help="seconds the server may take to report ready "
                         "(weight init + every warm-up compile)")
    args = ap.parse_args()
    if not (ROOT / "ai_agent_kubectl_tpu").is_dir():
        print("chip_smoke: the ai_agent_kubectl_tpu package is not next to "
              "this script; nothing to run", file=sys.stderr)
        return 2
    OUT_DIR.mkdir(exist_ok=True)
    log_path = OUT_DIR / "chip_smoke_server.log"
    try:
        report = run(args.cpu_toy, args.ready_timeout, log_path)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        if log_path.exists():
            tail = log_path.read_text(errors="replace")[-6000:]
            print(f"--- tail of {log_path} ---\n{tail}", file=sys.stderr)
        return 1
    for line in report["downgrades"]:
        print(f"chip_smoke: server log: {line}", file=sys.stderr)
    (OUT_DIR / "chip_smoke_report.json").write_text(json.dumps(report))
    print(json.dumps(report))
    # The last line is the result, with exactly these keys; everything
    # else the run learned is in the report line above it.
    print(json.dumps({"ok": True, "device": report["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
