"""Test harness configuration.

- Forces JAX onto CPU with 8 virtual devices BEFORE jax imports, so real
  mesh/pjit/collective code runs without a TPU (SURVEY.md §4,
  distributed-without-a-cluster).
- Provides minimal async-test support (no pytest-asyncio in the image):
  ``async def test_*`` functions are run via ``asyncio.run``.
- ``fake_kubectl`` fixture: a scriptable kubectl stand-in exercising the
  executor (SURVEY.md §4, boundary 2).
"""

import asyncio
import inspect
import os
import stat
import sys
from pathlib import Path

# Tests force the CPU with eight virtual devices: on TPU "f32" matmuls run
# at bf16 MXU precision, so numerics tests would silently compare bf16
# against themselves. RUN_TPU_TESTS=1 opts out for the compiled-kernel
# parity tests (tests/test_tpu_kernels.py), which are run through the chip
# tool: one process, one pytest invocation, it owns the chip.
_ON_TPU = os.environ.get("RUN_TPU_TESTS") == "1"
if not _ON_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import jax  # noqa: E402

if not _ON_TPU:
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pytest  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Run coroutine test functions on a fresh event loop."""
    func = pyfuncitem.obj
    if inspect.iscoroutinefunction(func):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(func(**kwargs))
        return True
    return None


#: The long files (seconds on the CPU under six workers at PR 48: 489, 677,
#: 580, 523, 289, 317, 244, 178, 191, 249, 334; the next is 100). Under
#: ``--dist loadfile`` a file is one worker's. xdist hands files out by their
#: NUMBER of tests, most first, so a long file of few tests (the sliding
#: model against its reference: 4 tests, 388 s) started last, ~680 s into the
#: run, and the run waited for it (ROADMAP D10). Here they are handed out in
#: the order collected, and these are collected first; the first six start
#: together. (All longest first was tried at PR 48 and is slower: the
#: compiles of the heaviest files then contend from the first second, 1,227
#: s against 1,065.) At PR 57, by the driver's command's junit file on that
#: tree: 549, 816, 543, 566, 277, 288, 299, 291 (``test_gdn_moe.py``, PR 55's,
#: until then collected among the short files), 239, 336, 375, 506, 112
#: (``test_ssm_dense_hybrid.py``; the next is 81). The whole is 6,705 s of
#: tests over six workers, 1,118 s at best: the order is worth seconds now,
#: the work is the bound.
_LONG_FILES = ("test_sparse_attention.py", "test_tpu_aot.py",
               "test_kda_latent.py", "test_reference_logits_sliding.py",
               "test_sliding_attention.py", "test_latent_attention.py",
               "test_window_staging.py", "test_gdn_moe.py",
               "test_packed_window.py", "test_state_cache.py",
               "test_hybrid_model.py", "test_linear_attention.py",
               "test_ssm_dense_hybrid.py")
#: Files that hold the program to a clock (a 6 ms step against a 50 ms fault)
#: are collected last: they then run while the other workers are finishing
#: short files, not under a long file's compiles (three whole runs of three
#: at PR 48 tripped the sentinel's healthy phase under load).
#: (and the spans' partitions, whose 5-10 ms sleeps have a millisecond of room:
#: three of them overshot under PR 55's added files' compiles)
_LAST_FILES = ("test_engine_spans.py", "test_sentinel.py")


def pytest_configure(config):
    if hasattr(config.option, "loadscopereorder"):       # xdist is loaded
        config.option.loadscopereorder = False


def pytest_collection_modifyitems(items):
    """The same order in every worker (xdist requires it): a stable sort,
    so the order inside a file and among the other files is kept."""
    rank = {name: i for i, name in enumerate(_LONG_FILES)}
    rank.update({name: len(rank) + 1 for name in _LAST_FILES})
    items.sort(key=lambda item: rank.get(item.path.name, len(_LONG_FILES)))


FAKE_KUBECTL = r"""#!/usr/bin/env python3
# Scriptable kubectl stand-in for executor tests.
import os, sys, time

args = sys.argv[1:]
mode = os.environ.get("FAKE_KUBECTL_MODE", "table")

if mode == "table":
    sys.stdout.write(
        "NAME                     READY   STATUS    RESTARTS   AGE   NOMINATED NODE\n"
        "web-5d9c7b9df4-abcde     1/1     Running   0          2d    <none>\n"
        "db-0                     1/1     Running   3          40d   node a1\n"
    )
    sys.exit(0)
if mode == "raw":
    sys.stdout.write("pod/web-5d9c7b9df4-abcde created")
    sys.exit(0)
if mode == "json":
    sys.stdout.write('{"items": [{"kind": "Pod", "name": "web"}]}')
    sys.exit(0)
if mode == "error":
    sys.stderr.write('Error from server (NotFound): pods "nope" not found\n')
    sys.exit(1)
if mode == "slow":
    time.sleep(float(os.environ.get("FAKE_KUBECTL_SLEEP", "5")))
    sys.stdout.write("done")
    sys.exit(0)
sys.stdout.write("ok")
sys.exit(0)
"""


@pytest.fixture
def fake_kubectl(tmp_path, monkeypatch):
    """Writes a fake kubectl executable; returns its path. Select behaviour
    via the FAKE_KUBECTL_MODE env var (table|raw|json|error|slow)."""
    path = tmp_path / "kubectl"
    path.write_text(FAKE_KUBECTL)
    path.chmod(path.stat().st_mode | stat.S_IEXEC)
    return str(path)
