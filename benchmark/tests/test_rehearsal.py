"""``run.py --rehearse`` of every cell of BENCHMARK.json: launcher, comparison
with the reference, generator, readers and the last line's shape, on the CPU
at toy size (a configuration's mesh cut to 2 pretended devices). Values mean
nothing here; that each metric's reader finds something to read does."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
CELLS = [w["name"] for w in json.loads((BENCH.parent / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell):
    run = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell, "--seed", "3000000019",
         "--seconds", "4", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, timeout=900, cwd=BENCH.parent)
    assert run.returncode == 0, run.stderr[-3000:]
    lines = run.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["rehearsal"] is True and last["metrics"] == {}
    assert last["attempted"] > 0 and last["failed"] == 0
    said = [json.loads(ln[len("bench: "):]) for ln in lines if ln.startswith("bench: ")]
    values = next(s["rehearsal_values"] for s in said if "rehearsal_values" in s)
    refcheck = next(s["refcheck"] for s in said if "refcheck" in s)
    assert refcheck["ok"]
    if cell == "mixtral8x7b-tp4-chat-steady":      # the one cell over a mesh
        assert refcheck["mesh"] == {"model": 2} and last["device"]["count"] == 2
        problems = next(s["rehearsal_health_problems"] for s in said
                        if "rehearsal_health_problems" in s)
        # the CPU's platform, device count and attention regime are always
        # among them; the mesh, the weights' split and the pool may not be
        cpu_only = ("platform", "device_kind", "devices, the cell asks", "attention_regime")
        assert not [p for p in problems if not any(c in p for c in cpu_only)], problems
        # weights made sharded: timed, and the same bytes on either device
        assert values["weights_init_s"] > 0
        assert values["weights_bytes_chip_max_share"] == 1.0
    else:       # one device: no /health.sharding, so nothing to read
        assert "weights_init_s" not in values
