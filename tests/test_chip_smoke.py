"""chip_smoke.py — the contract the driver checks, as far as a machine
without a chip can check it: the script refuses to pass where JAX finds no
accelerator and where the package is missing (non-zero exit, no result on
stdout), and ``--cpu-toy`` drives the identical flow end to end on the
CPU. The real run is on the chip, through the chip tool.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(script, *args, timeout):
    return subprocess.run([sys.executable, str(script), *args],
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.skipif(os.environ.get("RUN_TPU_TESTS") == "1",
                    reason="on a chip the smoke would really start")
def test_chip_smoke_fails_without_an_accelerator():
    """The child's JAX is pinned to ``tpu``: here engine construction
    fails, the server starts degraded, and the smoke says so at once
    instead of polling a 503 until its deadline."""
    r = _run(SMOKE, "--ready-timeout", "120", timeout=180)
    assert r.returncode == 1, r.stderr[-2000:]
    assert r.stdout == ""
    assert "started degraded" in r.stderr
    assert "Unable to initialize backend 'tpu'" in r.stderr


def test_chip_smoke_fails_alone_in_a_directory(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SMOKE, alone)
    r = _run(alone, timeout=60)
    assert r.returncode == 2
    assert r.stdout == ""
    assert list(tmp_path.iterdir()) == [alone]      # and wrote nothing


@pytest.mark.slow
def test_chip_smoke_cpu_toy_end_to_end():
    r = _run(SMOKE, "--cpu-toy", timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    report, result = map(json.loads, r.stdout.splitlines())
    # The last line is the driver's contract: these keys and no others.
    assert result == {"ok": True, "device": report["device"]}
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"]["platform"] == "cpu"
    assert isinstance(result["device"]["kind"], str)
    assert isinstance(result["device"]["count"], int)
    assert report["model"] == "toy-8m"
    assert report["requests"] == {"sent": 11, "succeeded": 11}
    assert report["tokens_generated"] >= report["engine_served"]
    assert report["compile_cache"]["dir"] is None
