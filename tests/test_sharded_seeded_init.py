"""Seeded weights are born sharded (ISSUE 27): over a mesh of more than one
device the engine makes the seeded int8 tree with ONE compiled call whose
outputs carry ``shard_params``'s shardings, so no leaf is ever whole on one
device; without a mesh the generator is called exactly as it always was."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.jax_engine import JaxEngine
from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.ops import quant
from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
from ai_agent_kubectl_tpu.parallel.sharding import (param_specs, sanitize_spec,
                                                    shard_params)

TOY = get_config("toy-moe")                 # 2 KV heads: serves model:2
#: a copy with 4 KV heads and an expert width 4 divides: serves model:4
TOY_KV4 = dataclasses.replace(TOY, name="toy-moe-kv4", n_kv_heads=4,
                              mlp_hidden=512)
CASES = {"model2": (TOY, 2), "model4": (TOY_KV4, 4)}


def mesh_of(tp):
    return build_mesh(MeshConfig(model=tp), jax.devices()[:tp])


def leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def assert_same_tree(got, want):
    """Leaf for leaf: values, dtype and sharding."""
    for (path, g), (_, w) in zip(leaves(got), leaves(want), strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        assert g.sharding.is_equivalent_to(w.sharding, g.ndim), path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), str(path))


def policy_share(mesh, cfg, tree):
    """{leaf path: elements one device may hold}: the leaf's size over the
    mesh axes its sanitized spec names."""
    specs = param_specs(cfg)
    out = {}
    for path, leaf in leaves(tree):
        spec = specs
        for p in path:
            key = getattr(p, "key", getattr(p, "name", None))
            if key in ("q", "scale"):
                break           # payload and scales take the weight's spec
            spec = spec[key]
        split = 1
        for names in sanitize_spec(mesh, spec, leaf.shape):
            for axis in (names if isinstance(names, tuple) else (names,)):
                split *= mesh.shape[axis] if axis else 1
        out[path] = leaf.size // split
    return out


@pytest.mark.parametrize("case", CASES)
def test_sharded_generator_is_the_whole_tree_generator_placed(case):
    cfg, tp = CASES[case]
    mesh = mesh_of(tp)
    key = jax.random.PRNGKey(2_000_000_010)
    whole = quant.random_params_int8(key, cfg, dtype=jnp.bfloat16,
                                     quantize_embed=True)
    born = quant.random_params_int8_sharded(key, cfg, mesh, dtype=jnp.bfloat16,
                                            quantize_embed=True)
    assert_same_tree(born, shard_params(whole, mesh, cfg))
    share = policy_share(mesh, cfg, born)
    split_leaves = 0
    for path, leaf in leaves(born):
        assert len(leaf.addressable_shards) == tp, path
        for shard in leaf.addressable_shards:
            assert shard.data.size == share[path], (path, shard.device)
        split_leaves += share[path] < leaf.size
    # the policy really splits: the payload of all 7 projections, of the
    # embedding and of the head, and the scales of those split by columns
    # (wo's and w_down's scales are per out-channel of a row-parallel matrix)
    assert split_leaves == 9 + 7


def engine(cfg, mesh_shape, seed=41):
    return JaxEngine(cfg, tokenizer=ByteTokenizer(), dtype="bfloat16",
                     quant="int8", max_seq_len=128, prefill_buckets=(32,),
                     attn_impl="dense", mesh_shape=mesh_shape, seed=seed)


@pytest.mark.parametrize("case", CASES)
def test_load_and_dev_swap_over_a_mesh_never_hold_a_whole_leaf(case, monkeypatch):
    cfg, tp = CASES[case]
    eng = engine(cfg, f"model:{tp}")
    eng._setup_mesh()
    # the whole-tree generator on one device is what must NOT run
    monkeypatch.setattr(quant, "random_params_int8",
                        lambda *a, **k: pytest.fail("whole tree on one device"))
    eng._load()
    monkeypatch.undo()
    want = shard_params(quant.random_params_int8(
        jax.random.PRNGKey(41), cfg, dtype=jnp.bfloat16, quantize_embed=True),
        eng.mesh, cfg)
    assert_same_tree(eng.params, want)
    assert eng._weights_shard_fraction == 1 / tp
    health = eng.sharding_health()
    assert health["weights_init_sharded"] is True
    assert health["weights_init_s"] > 0
    per_device = health["weights_bytes_per_device"]
    assert len(per_device) == tp and len(set(per_device)) == 1
    total = sum(leaf.nbytes for _, leaf in leaves(eng.params))
    replicated = sum(leaf.nbytes for path, leaf in leaves(eng.params)
                     if leaf.addressable_shards[0].data.size == leaf.size)
    assert per_device[0] == (total - replicated) // tp + replicated

    # a roll-back onto the dev sentinel re-derives the same tree, sharded
    monkeypatch.setattr(quant, "random_params_int8",
                        lambda *a, **k: pytest.fail("whole tree on one device"))
    back = eng._load_swap_params(eng.checkpoint_path)
    monkeypatch.undo()
    assert eng.checkpoint_path == f"dev:{cfg.name}:seed=41:quant=int8"
    assert_same_tree(back, want)
    other = eng._load_swap_params(f"dev:{cfg.name}:seed=42:quant=int8")
    assert not np.array_equal(np.asarray(other["layers"]["wq"].q),
                              np.asarray(want["layers"]["wq"].q))


def test_one_device_load_calls_the_generator_as_it_always_did(monkeypatch):
    """Both shipped cells: no mesh, the same function with the same
    arguments (benchmark/serve.py::make_weights_in_one_call patches exactly
    this module attribute for Mixtral-l6), and no sharded call."""
    calls = []
    real = quant.random_params_int8

    def recorded(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    monkeypatch.setattr(quant, "random_params_int8", recorded)
    monkeypatch.setattr(quant, "random_params_int8_sharded",
                        lambda *a, **k: pytest.fail("sharded call without a mesh"))
    eng = engine(TOY, "", seed=9)
    eng._setup_mesh()
    eng._load()
    assert eng.mesh is None and eng.sharding_health() is None
    (args, kwargs), = calls
    key, cfg = args
    np.testing.assert_array_equal(np.asarray(key), np.asarray(jax.random.PRNGKey(9)))
    assert cfg is TOY
    assert kwargs == {"dtype": jnp.bfloat16, "quantize_embed": True, "int4": False}
    assert eng._weights_init["sharded"] is False
    eng._load_swap_params("dev:toy-moe:seed=3:quant=int8")
    assert len(calls) == 2 and calls[1][1] == kwargs
    np.testing.assert_array_equal(np.asarray(calls[1][0][0]),
                                  np.asarray(jax.random.PRNGKey(3)))


async def test_a_started_engine_over_a_mesh_made_weights_and_pool_sharded(monkeypatch):
    """The whole start over ``model:2``: beside a model that fills its chips
    neither the weights nor the KV pool may be made whole on one device and
    then moved (Mixtral-8x7B over model:4 ran out of memory on chip 0 in
    exactly that move of the pool; my chip run, PR 27)."""
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.parallel.sharding import pool_cache_shardings

    whole_on_one_device = []
    zeros = jnp.zeros

    def watched(shape, *args, **kwargs):
        out = zeros(shape, *args, **kwargs)
        if (isinstance(out, jax.Array) and not isinstance(out, jax.core.Tracer)
                and out.ndim == 5 and len(out.sharding.device_set) == 1):
            whole_on_one_device.append(out.shape)
        return out

    monkeypatch.setattr(jnp, "zeros", watched)
    eng = BatchedJaxEngine(TOY, tokenizer=ByteTokenizer(), dtype="bfloat16",
                           quant="int8", max_seq_len=128,
                           prefill_buckets=(32,), prefix_cache=False,
                           mesh_shape="model:2", batch_size=4, chunk_len=4,
                           seed=41)
    await eng.start()
    try:
        assert eng._use_pool and not whole_on_one_device
        want = pool_cache_shardings(eng._cache, eng.mesh, TOY)
        for leaf, sharding in ((eng._cache.k, want.k), (eng._cache.v, want.v)):
            assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
            assert leaf.addressable_shards[0].data.shape[3] == TOY.n_kv_heads // 2
        health = eng.sharding_health()
        assert health["weights_init_sharded"] and health["pool_sharded"]
        assert eng.spans_health()["weights_init"]["sharded"] == 1
        out = await eng.generate("list pods", max_tokens=6, temperature=0.0)
        assert out.text is not None
    finally:
        await eng.stop()


def test_copy_on_write_over_a_mesh_copies_the_same_rows():
    """The block form of the pool's copy-on-write (taken over a mesh, where
    the row form costs a copy of the whole leaf) against the row form one
    device keeps: the same pool, row for row, for an empty, a partial and a
    whole tail."""
    import types

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.transformer import KVCache
    from ai_agent_kubectl_tpu.parallel.sharding import shard_pool_cache

    cfg, page, n_blocks = TOY_KV4, 16, 6
    mesh = mesh_of(4)
    shape = (cfg.n_layers, n_blocks, page, cfg.n_kv_heads, cfg.head_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(3))

    def pool():
        return KVCache(k=jax.random.normal(kk, shape, jnp.bfloat16),
                       v=jax.random.normal(kv, shape, jnp.bfloat16),
                       lengths=jnp.zeros((n_blocks,), jnp.int32))

    def cow_of(m):
        return BatchedJaxEngine._pool_cow_fn.fget(
            types.SimpleNamespace(kv_pool_page=page, mesh=m))

    for rows in (0, 5, page):
        args = [jnp.asarray(x, jnp.int32) for x in (4, 1, rows)]
        one = cow_of(None)(pool(), *args)
        over = cow_of(mesh)(shard_pool_cache(pool(), mesh, cfg), *args)
        for a, b in ((one.k, over.k), (one.v, over.v)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            assert len(b.sharding.device_set) == 4
        np.testing.assert_array_equal(np.asarray(one.k[:, 1, :rows]),
                                      np.asarray(pool().k[:, 4, :rows]))
        np.testing.assert_array_equal(np.asarray(one.k[:, 1, rows:]),
                                      np.asarray(pool().k[:, 1, rows:]))
