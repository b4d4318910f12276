"""True multi-process DCN initialization (VERDICT r3 item 8).

Spawns TWO separate OS processes, each with 2 virtual CPU devices, wires
them with ``jax.distributed`` through ``init_distributed``
(parallel/distributed.py — the COORDINATOR_ADDRESS / NUM_PROCESSES /
PROCESS_ID path ``server/__main__.py`` uses), builds the **hybrid
ICI × DCN mesh** (``build_mesh(..., dcn=...)``, parallel/mesh.py), and runs
a sharded toy-model forward whose batch axis crosses the process boundary —
the CPU stand-in for a 2-slice TPU deployment. Both processes must agree on
the result (SPMD out), proving the cross-process collective actually ran.

Gated: skipped when the platform can't complete distributed init in time
(sandboxes without localhost gRPC, etc.) — the negative single-process
test stays in tests/test_parallel.py.
"""

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, "@REPO@")

from ai_agent_kubectl_tpu.parallel.distributed import init_distributed

ok = init_distributed(
    coordinator_address="@COORD@",
    num_processes=2,
    process_id=int(sys.argv[1]),
)
assert ok and jax.process_count() == 2, (ok, jax.process_count())
assert len(jax.devices()) == 4 and len(jax.local_devices()) == 2

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import KVCache, forward, init_params
from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
from ai_agent_kubectl_tpu.parallel.sharding import shard_cache, shard_params

# ICI tp=2 inside each "slice" (process), DCN dp=2 across processes:
# the hybrid factorization server/__main__.py builds from
# MESH_SHAPE="tp=2" DCN_MESH_SHAPE="dp=2".
mesh = build_mesh(MeshConfig.parse("tp=2"), dcn=MeshConfig.parse("dp=2"))
assert dict(mesh.shape)["data"] == 2 and dict(mesh.shape)["model"] == 2

cfg = get_config("toy-8m")
params = shard_params(
    init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), mesh, cfg)

B, S = 4, 8
tokens = jnp.tile(jnp.arange(S, dtype=jnp.int32)[None], (B, 1))
positions = jnp.broadcast_to(jnp.arange(S), (B, S)).astype(jnp.int32)
tokens = jax.device_put(tokens, NamedSharding(mesh, P("data", None)))
positions = jax.device_put(positions, NamedSharding(mesh, P("data", None)))
cache = shard_cache(KVCache.zeros(cfg, B, 16, dtype=jnp.float32), mesh, cfg)

logits, _ = jax.jit(
    lambda p, t, pos, c: forward(p, cfg, t, pos, c, kv_limit=16)
)(params, tokens, positions, cache)
# Cross-process reduction: every process must see the same global value.
checksum = float(jnp.sum(jnp.abs(logits)))
print(f"CHECKSUM {checksum:.6f}", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_dcn_mesh_and_sharded_forward(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(
        WORKER.replace("@REPO@", str(REPO)).replace("@COORD@", coord)
    )

    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=str(tmp_path),
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=180)
            outs.append((p.returncode, out, err))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.skip("distributed init did not complete (no localhost gRPC?)")

    for rc, out, err in outs:
        if rc != 0 and "UNAVAILABLE" in err:
            pytest.skip(f"distributed backend unavailable here: {err[-300:]}")
        assert rc == 0, f"worker failed:\n{err[-2000:]}"

    sums = [o.split("CHECKSUM")[-1].strip() for _, o, _ in outs]
    assert sums[0] == sums[1], f"processes disagree: {sums}"
    assert float(sums[0]) > 0.0
