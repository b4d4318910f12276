"""Full residual-path TP sharding + the mesh-sharded KV block pool
(ISSUE 14).

The acceptance spine: the block-paged pool SERVES under a tensor-parallel
mesh (the old ``KV_POOL does not compose with a serving mesh`` fallback is
gone for tp/ep axes), with mesh-vs-single-chip and pool-vs-dense
transcripts BYTE-identical at temperature 0 and seeded 0.9 on the
8-virtual-device CPU mesh (conftest forces the device count). Around it:
the f≈1 residual sharding policy (norms/RoPE/sampling scratch batch-shard
across the TP group, collectives fused at the GEMM boundaries and kept
scan-resident), the loud dense fallback for data/pipe/seq meshes, the
SPEC_DECODE+mesh capability check (tp/ep compose since ISSUE 18;
data/pipe/seq refuse), replicated grammar tables, the sharding
/health + /metrics surfaces.
"""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
from ai_agent_kubectl_tpu.models.config import get_config

PROMPTS = ["list pods", "get nodes -o wide", "describe deployment web"]
TEMPS = [0.0, 0.9, 0.9]
SEEDS = [7, 123, 5]


def _mk(mesh_shape: str, **over) -> BatchedJaxEngine:
    kw = dict(
        tokenizer=ByteTokenizer(),
        dtype="float32",
        max_seq_len=128,
        prefill_buckets=(32, 64),
        attn_impl="dense",
        prefix_cache=False,
        mesh_shape=mesh_shape,
        batch_size=4,
        chunk_len=4,
    )
    kw.update(over)
    return BatchedJaxEngine(get_config("toy-8m"), **kw)


async def _serve(eng) -> list:
    await eng.start()
    try:
        outs = await asyncio.gather(*[
            eng.generate(p, max_tokens=10, temperature=t, seed=s)
            for p, t, s in zip(PROMPTS, TEMPS, SEEDS)
        ])
        return [r.text for r in outs]
    finally:
        await eng.stop()


# ------------------------------------------------- pool under the mesh


async def test_pool_serves_under_tp8_mesh_byte_identical():
    """THE acceptance test: the pool serves under tp=8 (no dense
    fallback), and transcripts — greedy AND seeded 0.9 — are
    byte-identical to the single-device pool engine."""
    ref = await _serve(_mk(""))

    eng = _mk("tp=8")
    await eng.start()
    try:
        assert eng._use_pool, "pool must SERVE under a tp mesh"
        assert not eng._kv_pool_mesh_fallback
        # The pool cache is genuinely distributed over all 8 devices.
        leaf = eng._cache.k
        assert len(leaf.sharding.device_set) == 8
        sh = eng.sharding_health()
        assert sh["devices"] == 8
        assert sh["pool_sharded"] is True
        assert sh["kv_pool_mesh_fallback"] is False
        # ... and so are the weights: one device holds an eighth of wq.
        assert sh["weights_shard_fraction"] == 1.0 / 8
        assert eng.stats()["sharding"] == sh

        outs = await asyncio.gather(*[
            eng.generate(p, max_tokens=10, temperature=t, seed=s)
            for p, t, s in zip(PROMPTS, TEMPS, SEEDS)
        ])
        assert [r.text for r in outs] == ref
    finally:
        await eng.stop()


async def test_pool_vs_dense_under_mesh_byte_identical_and_fused():
    """On one tp=2 mesh: pool-vs-dense transcripts byte-identical (temp
    0 and seeded 0.9), the pool cache placed KV-head-sharded, the f≈1
    residual policy active at the decode shape (batch 4 divides
    data×model=2), and the serving chunk program's TP collectives
    scan-resident — fused into the layer body, not 2 per unrolled
    layer."""
    dense = await _serve(_mk("tp=2", kv_pool=False))

    eng = _mk("tp=2")
    await eng.start()
    try:
        assert eng._use_pool
        # Fresh placement follows pool_cache_specs: KV heads (axis 3)
        # over ``model`` (toy-8m has 2 KV heads).
        spec = eng._new_pool_cache().k.sharding.spec
        assert spec[3] == "model", spec
        sh = eng.sharding_health()
        assert sh["residual_tp_fraction"] == 1.0

        bucket = eng._kv_buckets[0]
        N = eng.batch_size
        hlo = eng._batch_chunk_fns[bucket].lower(
            eng.params, eng._tok_d, eng._pos_d, eng._cache,
            eng._seeds_d, eng._temps_d, jnp.zeros((N,), jnp.bool_),
            eng._active_d, eng._ngen_d, eng._budget_d,
            eng._no_corrupt_d, eng._tables_d(eng._tables),
        ).compile().as_text()
        n_coll = sum(hlo.count(f"%{op}") for op in
                     ("all-reduce", "reduce-scatter", "all-gather"))
        assert n_coll >= 1, "expected fused TP collectives in the HLO"
        # The layer loop stays a lax.scan ("while" in HLO): the
        # residual collectives live ONCE in the scan body and execute
        # per layer (the measured comm share is the mesh cell's
        # ``collectives_dev_share``, benchmark/xtrace.py; an instruction
        # count here would pin XLA:CPU partitioner noise, not the
        # model).
        assert "while" in hlo, "layer scan must not be unrolled"

        outs = await asyncio.gather(*[
            eng.generate(p, max_tokens=10, temperature=t, seed=s)
            for p, t, s in zip(PROMPTS, TEMPS, SEEDS)
        ])
        assert [r.text for r in outs] == dense
    finally:
        await eng.stop()


async def test_pool_falls_back_dense_under_dp_mesh_loudly():
    """data/pipe/seq axes still force the dense ladder — but LOUDLY:
    the engine serves, _use_pool is off, and the fallback flag rides
    sharding_health/stats."""
    eng = _mk("dp=2")
    await eng.start()
    try:
        assert not eng._use_pool
        assert eng._kv_pool_mesh_fallback
        sh = eng.sharding_health()
        assert sh["pool_sharded"] is False
        assert sh["kv_pool_mesh_fallback"] is True
        r = await eng.generate("list pods", max_tokens=6, temperature=0.0)
        assert r.text  # serves (dense) rather than erroring
        assert eng.kv_pool_health() is None  # dense: no pool section
    finally:
        await eng.stop()


# ------------------------------- spec + mesh capability check (ISSUE 18)


def test_spec_decode_accepts_tp_mesh_refuses_unshardable_axes():
    """The ISSUE 14 blanket refusal is lifted: SPEC_DECODE composes
    with tensor/expert-parallel meshes (the draft world is sharded);
    only genuinely unshardable axes — data/pipe/seq, where the spec
    pool's shared blocks and the whole-stack draft can't follow —
    still refuse, at config AND at direct engine construction."""
    from ai_agent_kubectl_tpu.config import ServiceConfig

    # tp/ep meshes now validate (deep detailed checks are the
    # engine's, at start — config stays jax-free).
    ServiceConfig(spec_decode=True, mesh_shape="tp=8",
                  spec_draft_model="toy-8m")
    ServiceConfig(spec_decode=True, mesh_shape="tp=2,ep=2",
                  spec_draft_model="toy-8m")
    ServiceConfig(spec_decode=True, mesh_shape="tp=1",
                  spec_draft_model="toy-8m")
    # data/pipe/seq axes (any alias, either mesh knob) refuse loudly.
    for kw in (dict(mesh_shape="dp=2"), dict(mesh_shape="pp=2"),
               dict(mesh_shape="seq=2"), dict(mesh_shape="tp=2,dp=2"),
               dict(mesh_shape="tp=2", dcn_mesh_shape="dp=2")):
        with pytest.raises(ValueError, match="SPEC_DECODE.*mesh"):
            ServiceConfig(spec_decode=True, spec_draft_model="toy-8m",
                          **kw)


async def test_spec_decode_refuses_unshardable_mesh_at_start():
    eng = _mk("dp=2", spec_decode=True, spec_draft_model="toy-8m")
    with pytest.raises(ValueError, match="SPEC_DECODE"):
        await eng.start()


# -------------------------------------------- grammar tables on a mesh


async def test_grammar_tables_replicated_and_byte_identical_on_mesh():
    """GRAMMAR_DECODE composes with the mesh: the stacked tables are
    pinned fully replicated (a sharded/partitioner-chosen layout would
    tear the mask gather), and constrained output is byte-identical to
    the single-device grammar engine at temp 0 and seeded 0.9."""
    ref_eng = _mk("", grammar_decode=True, grammar_forced_run_min=2,
                  max_seq_len=192)
    ref = await _serve(ref_eng)

    eng = _mk("tp=2", grammar_decode=True, grammar_forced_run_min=2,
              max_seq_len=192)
    await eng.start()
    try:
        tc, ok, nx = eng._grammar_tables_d()
        for t in (tc, ok, nx):
            assert t.sharding.is_fully_replicated
            assert len(t.sharding.device_set) == 2
        outs = await asyncio.gather(*[
            eng.generate(p, max_tokens=10, temperature=t, seed=s)
            for p, t, s in zip(PROMPTS, TEMPS, SEEDS)
        ])
        assert [r.text for r in outs] == ref
        for r in outs:
            assert r.text.startswith("kubectl ")
    finally:
        await eng.stop()


# ------------------------------------------------ policy + surface units


def test_residual_spec_policy():
    from jax.sharding import PartitionSpec as P

    from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
    from ai_agent_kubectl_tpu.parallel.sharding import (
        logits_spec, residual_fraction, residual_spec)

    tp8 = build_mesh(MeshConfig(model=8), devices=jax.devices()[:8])
    # Decode shape, batch divides: batch-sharded over (data, model).
    assert residual_spec(tp8, (8, 1, 256)) == P(("data", "model"), None,
                                                None)
    assert residual_fraction(tp8, 8, 256) == 1.0
    # Batch does not divide: prefill's B=1 falls to the sequence axis...
    assert residual_spec(tp8, (1, 32, 256))[1] == "model"
    # ...and an indivisible decode batch keeps the classic layout.
    assert residual_spec(tp8, (3, 1, 256)) is None
    assert residual_fraction(tp8, 3, 256) == 0.0
    # Vocab shards when divisible, else None.
    assert logits_spec(tp8, 512) == P(None, None, "model")
    assert logits_spec(tp8, 513) is None
    # Expert/pipe meshes keep their own layouts.
    ep = build_mesh(MeshConfig(expert=2, model=2),
                    devices=jax.devices()[:4])
    assert residual_spec(ep, (8, 1, 256)) is None
    pp = build_mesh(MeshConfig(pipe=2, model=2),
                    devices=jax.devices()[:4])
    assert residual_spec(pp, (8, 1, 256)) is None
    assert residual_fraction(None, 8, 256) == 0.0


def test_config_mesh_device_count_parser():
    from ai_agent_kubectl_tpu.config import _mesh_device_count

    assert _mesh_device_count("") == 1
    assert _mesh_device_count("tp=8") == 8
    assert _mesh_device_count("dp=2,tp=4") == 8
    assert _mesh_device_count("data:2, model:2") == 4


def test_metrics_observe_sharding_renders_gauges():
    from ai_agent_kubectl_tpu.server.metrics import Metrics

    m = Metrics()
    m.observe_sharding({"devices": 8, "residual_tp_fraction": 1.0,
                        "kv_pool_mesh_fallback": True})
    text = m.render().decode() if isinstance(m.render(), bytes) \
        else m.render()
    if isinstance(text, bytes):  # pragma: no cover - render type guard
        text = text.decode()
    assert "mesh_devices 8.0" in text
    assert "sharding_residual_fraction 1.0" in text
    assert "kv_pool_mesh_fallback 1.0" in text


async def test_health_and_metrics_expose_sharding_section():
    """The /health sharding section and the mesh gauges ride the same
    duck-typed seam every engine surface uses (getattr sharding_health
    / stats()['sharding']) — exercised over real HTTP on the fake
    engine with the batcher's exact dict shape."""
    from aiohttp.test_utils import TestClient, TestServer

    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
    from ai_agent_kubectl_tpu.server.app import create_app
    from ai_agent_kubectl_tpu.server.executor import CommandExecutor

    cfg = ServiceConfig(engine="fake", model_name="fake", llm_timeout=5.0)
    engine = FakeChunkedEngine(batch_size=2, chunk_len=4)
    sh = {"mesh": {"data": 1, "expert": 1, "pipe": 1, "seq": 1,
                   "model": 8},
          "devices": 8, "residual_tp_fraction": 1.0,
          "pool_sharded": True, "kv_pool_mesh_fallback": False}
    engine.sharding_health = lambda: sh
    orig_stats = engine.stats
    engine.stats = lambda: {**orig_stats(), "sharding": sh}
    app = create_app(cfg, engine, executor=CommandExecutor(timeout=1.0))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        await engine.start()
        h = await client.get("/health")
        body = await h.json()
        assert body["sharding"] == sh
        m = await client.get("/metrics")
        text = await m.text()
        assert "mesh_devices 8.0" in text
        assert "sharding_residual_fraction 1.0" in text
        assert "kv_pool_mesh_fallback 0.0" in text
    finally:
        await client.close()
        await engine.stop()
