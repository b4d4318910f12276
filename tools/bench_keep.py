#!/usr/bin/env python3
"""One run of one cell through benchmark/run.py of the CURRENT directory (so a
parent checkout under .chipwork/ runs its own), with what the result line
does not carry:

    KEEP=chiprun_out/<tag>.health.json python3 <repo>/tools/bench_keep.py \
        --workload <cell> --seed <n> --seconds 50 --trace 0

- the ``health_spans`` per-layer metrics are printed in ``--trace 0`` runs too,
  where no profiler capture is inside the probes;
- ``KEEP`` receives the ``/health.spans`` of the first ready probe, of the
  probe before the load and of the one after its tail, and
  ``/debug/profile``'s answer (``spans``: the three partitions' growth inside
  the capture, ``python_tracer``).

Nothing under benchmark/ is edited: ``run.http_json`` and ``run.cell_metrics``
are wrapped from outside. It never imports jax: a chip belongs to one process."""
import importlib.util
import json
import os
import sys
import time

spec = importlib.util.spec_from_file_location(
    "run", os.path.join(os.getcwd(), "benchmark", "run.py"))
run = importlib.util.module_from_spec(spec)
sys.modules["run"] = run
spec.loader.exec_module(run)

kept = []
_http = run.http_json


def http_json(url, *a, **kw):
    status, body = _http(url, *a, **kw)
    if isinstance(body, dict):
        if url.endswith("/health") and body.get("engine_ready") and body.get("spans"):
            kept.append({"t": time.time(), "what": "health", "spans": body["spans"]})
        elif "/debug/profile" in url:
            kept.append({"t": time.time(), "what": "profile",
                         **{k: body.get(k) for k in ("seconds", "python_tracer", "clock_start",
                                                     "clock_stop", "spans", "error")}})
    return status, body


run.http_json = http_json
_cells = run.cell_metrics


def cell_metrics(bench, kind, cell):
    out = _cells(bench, kind, cell)
    if kind == "end_to_end":
        out = out + [
            m for m in _cells(bench, "per_layer", cell)
            if run.load_json(run.HERE / "metrics" / f"{m['name']}.json")
            ["reader"] == "health_spans"]
    return out


run.cell_metrics = cell_metrics
rc = run.main()
keep = os.environ.get("KEEP")
if keep:
    # in order: the probe that found the server ready, the one before the
    # load, (a traced run's own one-a-second samples,) the one after the tail
    health = [k for k in kept if k["what"] == "health"]
    out = health[:2] + health[-1:] + [k for k in kept if k["what"] == "profile"]
    with open(keep, "w") as f:
        json.dump(out, f)
sys.exit(rc)
