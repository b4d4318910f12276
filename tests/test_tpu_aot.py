"""Compiles for a DESCRIBED TPU v5e (no chip attached; the TPU compiler is
installed here): what the interpreter and the CPU backend cannot show about
the serving path's programs — that Mosaic accepts a kernel, what XLA keeps in
HBM, which ops move pool-sized buffers. Nothing runs, so no test here says
anything about results or times.

Every compile for a described chip lives in THIS file, behind the fixtures
below: the process that describes the topology loads the TPU library and
keeps it, so it must happen inside a test, in one worker.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from ai_agent_kubectl_tpu.models.config import ModelConfig
from ai_agent_kubectl_tpu.models.transformer import KVCache, forward
from ai_agent_kubectl_tpu.ops.quant import random_params_int8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _engine_cache(arg, cfg, n_blocks: int, page: int, slots: int):
    """The pool engine's cache for ``cfg`` as the engine builds it
    (``KVCache.pool_zeros``), abstract: what the compiler is shown is what
    the engine holds. Prefill buckets up to 512; the grouped path counts."""
    made = jax.eval_shape(lambda: KVCache.pool_zeros(
        cfg, n_blocks=n_blocks, page=page, slots=slots,
        ring=cfg.sliding_ring(512, page), dtype=jnp.bfloat16,
        counts_experts=cfg.grouped_experts))
    return jax.tree_util.tree_map(lambda a: arg(a.shape, a.dtype), made)


def _instructions(hlo: str):
    """(result type text, op, the whole line) of every HLO instruction."""
    for line in hlo.splitlines():
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m:
            yield m.group(1), m.group(2), line


def _sizes(result: str) -> list:
    """Element counts of the arrays in an instruction's result type."""
    return [math.prod(int(d) for d in dims.split(","))
            for dims in re.findall(r"\w+\[([\d,]+)\]", result)]


def _results_of_size(hlo: str, sizes) -> list:
    """(op, result shape) of every HLO instruction whose result holds an
    array with one of ``sizes`` elements — moves of data only: parameters,
    tuples and their elements, bitcasts and loops name buffers, they do not
    fill them."""
    names = {"parameter", "get-tuple-element", "bitcast", "tuple", "while"}
    return [(op, dims) for result, op, _ in _instructions(hlo)
            if op not in names
            for dims in re.findall(r"\w+\[([\d,]+)\]", result)
            if math.prod(int(d) for d in dims.split(",")) in sizes]


def _loop_bodies(hlo: str) -> str:
    """The text of every computation a ``while`` of the program runs: its
    body and whatever that calls (fusions, nested loops), as HLO lines."""
    comps, name = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    seen = set()

    def walk(name):
        if name in seen or name not in comps:
            return
        seen.add(name)
        for line in comps[name]:
            for called in re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", line):
                walk(called)
            for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                for called in group.split(","):
                    walk(called.strip().lstrip("%"))

    for lines in list(comps.values()):
        for line in lines:
            if " while(" in line:
                walk(re.search(r"body=%?([\w.\-]+)", line).group(1))
    return "\n".join(line for name in seen for line in comps[name])


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` in a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield tuple(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_grids(sub)


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def _ragged_kv_buffers(jaxpr):
    """The shape of the ragged kernel's K buffer ring (its first VMEM
    scratch) in every ``pallas_call`` of a jaxpr that has a DMA semaphore
    among its scratch."""
    for eqn in _pallas_calls(jaxpr):
        n = eqn.params["grid_mapping"].num_scratch_operands
        scratch = [v.aval for v in eqn.params["jaxpr"].invars[-n:]] if n else []
        if any("dma" in str(a).lower() for a in scratch):
            yield tuple(scratch[0].shape)


def _grouped_kernel_calls(jaxpr):
    """(rows of a tile, grid steps, scoped VMEM asked for) of every call of
    the grouped expert kernel in a jaxpr."""
    for eqn in _pallas_calls(jaxpr):
        if eqn.params["name"] == "grouped_expert_ffn":
            gm = eqn.params["grid_mapping"]
            yield (gm.block_mappings[0].block_shape[0].block_size, gm.grid[0],
                   eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes)


@pytest.mark.parametrize("H,KV", [(32, 8), (8, 2)],
                         ids=["one-chip-32q8kv", "mesh-local-8q2kv"])
@pytest.mark.parametrize("W", [1, 64, 1024],
                         ids=["decode", "admission", "admission-1024"])
def test_pool_forward_moves_no_pool_sized_buffer_on_v5e(one_chip, W, H, KV,
                                                        monkeypatch):
    """ISSUE 25 at Mistral-7B's widths (2 layers, the benchmark's pool of
    320 blocks of 64, batch 16, the engine's 65-page table), and at the
    heads a chip holds of it over ``model:4`` (a pool of the same bytes): the compiled pool+ragged
    forward with the cache donated holds the Mosaic kernel, its only ops
    with a pool-sized or pool-layer-sized result are the two in-place row
    scatters, and its temporaries stay below one pool (as the scan's xs/ys
    the pool was sliced, copied and rebuilt every pass, and held twice).
    ISSUE 30: the kernel's page axis is ``cdiv(pages, P)`` for the P its
    shapes resolve, 8 pages a decode step and 4 beside a full query tile's
    scores: one page a step again (1,040 steps a decode call) fails here.
    ISSUE 32: its live blocks stream through a ring of ``stream_depth``
    buffers (4 or 3 at decode, 3 or 4 beside a full tile), row-tiled
    [pages, page*KV, hd] where a narrow tile reads a block as stored; a
    ring Mosaic cannot fit in scoped VMEM is refused here, not on the
    chip."""
    from jax.experimental import pallas as pl

    from ai_agent_kubectl_tpu.ops.ragged_attention import (_q_tile,
                                                           pages_per_step,
                                                           stream_depth)

    # ops/ragged_attention.py interprets the kernel off-TPU; this compile
    # is for the TPU, whatever backend the process runs on.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot", vocab_size=32000, dim=4096, n_layers=2,
                      n_heads=H, n_kv_heads=KV, head_dim=128,
                      mlp_hidden=14336, rope_theta=1e6, eos_ids=(2,),
                      tie_embeddings=False)
    # 320 blocks of 8 KV heads, 1,280 of 2 (the mesh cell holds 1,792 a
    # chip): a pool a quarter the size fits the compiler's alternate
    # memory, and it parks it there whatever the kernel does.
    B, page, n_blocks, pages = 16, 64, 320 * 8 // KV, 65

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    pool = (cfg.n_layers, n_blocks, page, cfg.n_kv_heads, cfg.head_dim)
    cache = KVCache(k=arg(pool, jnp.bfloat16), v=arg(pool, jnp.bfloat16),
                    lengths=arg((n_blocks,), jnp.int32))

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, page_size=page,
                       block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    pps = pages_per_step(pages, page, H, KV, cfg.head_dim, W)
    assert pps == 8 if W == 1 else pps in (4, 8), pps
    grid = (B, pl.cdiv(W, _q_tile(W, H, cfg.head_dim)), pl.cdiv(pages, pps))
    assert set(_pallas_grids(traced.jaxpr.jaxpr)) == {grid}
    depth = stream_depth(pages, page, H, KV, cfg.head_dim, W)
    assert depth == {(1, 8): 3, (64, 8): 3, (1024, 8): 3}.get((W, KV), 4)
    assert set(_ragged_kv_buffers(traced.jaxpr.jaxpr)) == {
        (depth, pps) + ((page * KV,) if W == 1 else (page, KV))
        + (cfg.head_dim,)}
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "the Mosaic kernel is not in the program"
    leaf = n_blocks * page * cfg.n_kv_heads * cfg.head_dim
    moved = _results_of_size(hlo, {leaf, cfg.n_layers * leaf})
    assert moved, "the row writes should be in the program"
    # each scatter shows twice: inside its fusion, and as the fusion
    assert {op for op, _ in moved} <= {"scatter", "fusion"}, moved
    assert len(moved) == 4, moved
    pool_bytes = 2 * 2 * cfg.n_layers * leaf        # K and V, bf16
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, "the pool is donated"
    if W <= 64:     # 1,024 columns' own activations outweigh a 2-layer pool
        assert mem.temp_size_in_bytes < pool_bytes, (
            mem.temp_size_in_bytes, pool_bytes)


# ----------------------------------------------- four chips (ISSUE 27)

MIXTRAL = dict(vocab_size=32000, dim=4096, n_heads=32, n_kv_heads=8,
               head_dim=128, mlp_hidden=14336, rope_theta=1e6, eos_ids=(2,),
               tie_embeddings=False, n_experts=8, experts_per_token=2)


@pytest.fixture(scope="module")
def mesh4(topo):
    from ai_agent_kubectl_tpu.parallel.mesh import MeshConfig, build_mesh
    return build_mesh(MeshConfig(model=4), topo.devices)


def test_seeded_init_over_model4_holds_a_chips_share_on_v5e(mesh4):
    """ISSUE 27 at Mixtral-8x7B's published sizes (32 layers, 46.7 GB of
    int8): the ONE program that makes the seeded tree sharded gives each of
    four chips its quarter, 11.69 GB, which a 16 GB chip holds, with next to
    no temporaries (the PRNG's 32-bit words stay inside the fusions). Whole
    on one device, as the engine made it before, it cannot exist."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ai_agent_kubectl_tpu.ops.quant import _random_params_int8
    from ai_agent_kubectl_tpu.parallel.sharding import param_shardings

    cfg = ModelConfig(name="aot", n_layers=32, **MIXTRAL)

    def make(k):
        return _random_params_int8(k, cfg, jnp.bfloat16, True, False,
                                   slices_in_one_op=True)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh4, P()))
    shapes = jax.eval_shape(make, key)
    whole = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert whole > 46e9
    compiled = jax.jit(make, out_shardings=param_shardings(
        shapes, mesh4, cfg)).lower(key).compile()
    mem = compiled.memory_analysis()
    # norms, router and the row-parallel scales are whole on every chip
    assert whole / 4 <= mem.output_size_in_bytes < whole / 4 + 2**24
    assert mem.output_size_in_bytes < 11.7e9
    assert mem.temp_size_in_bytes < 2**30


@pytest.mark.parametrize("W", [1, 64], ids=["decode", "admission"])
def test_expert_mlp_over_model4_reduces_after_the_mix_on_v5e(mesh4, W,
                                                             monkeypatch):
    """ISSUE 27: with the experts' inner width split over ``model``, each
    chip's down projection is a partial sum. The program reduces it on
    [B, S, D] after the experts are mixed (parallel/moe.py::
    _down_and_mix_sharded); the partitioner alone put the reduce on the
    projection's own result, E times the bytes: collective-permutes of
    bf16[8,4096,4,W] round the ring. No collective of the compiled pool+ragged
    forward may carry the expert axis next to the model width."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ai_agent_kubectl_tpu.parallel.sharding import (param_shardings,
                                                        pool_cache_specs,
                                                        sanitize_spec)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot", n_layers=2, **MIXTRAL)
    B, page, n_blocks, pages = 16, 64, 1040, 65
    rep = NamedSharding(mesh4, P())

    def arg(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shapes = jax.eval_shape(lambda k: random_params_int8(
        k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
        jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda x, s: arg(x.shape, x.dtype, s), shapes,
        param_shardings(shapes, mesh4, cfg))
    pool = (cfg.n_layers, n_blocks, page, cfg.n_kv_heads, cfg.head_dim)
    heads = NamedSharding(mesh4, sanitize_spec(
        mesh4, pool_cache_specs(cfg)["k"], pool))
    cache = KVCache(k=arg(pool, jnp.bfloat16, heads),
                    v=arg(pool, jnp.bfloat16, heads),
                    lengths=arg((n_blocks,), jnp.int32))

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", mesh=mesh4, token_mask=wmask,
                       write_mask=wmask, page_size=page,
                       block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1)

    hlo = jax.jit(step, donate_argnums=(3,)).lower(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo, "the Mosaic kernel is not in the program"
    collectives = re.findall(
        r"= (.*?) (all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(", hlo)
    assert collectives
    with_experts = [(op, shape) for shape, op in collectives
                    if re.search(r"\[(?:\d+,)*8,4096[,\]]|\[(?:\d+,)*4096,(?:\d+,)*8[,\]]"
                                 r"|\[8,4096,", shape)]
    assert not with_experts, with_experts
    # the experts' reduce itself: [B, W, D] (or its [B/4, W, D] scatter)
    assert any(op in ("all-reduce", "reduce-scatter")
               and re.search(rf"bf16\[(?:16|4),{W},4096\]", shape)
               for shape, op in collectives), collectives


def test_pool_copy_on_write_over_model4_is_in_place_on_v5e(mesh4):
    """ISSUE 27 at Mixtral-8x7B's pool over ``model:4`` (32 layers, 1,792
    blocks, 1.75 GiB a leaf a chip): the engine's copy-on-write program
    holds no leaf-sized temporary. The row form the partitioner turned into
    a copy of the whole leaf, which did not fit beside the weights (the
    server died loading ``jit_cow``: my chip run, PR 27)."""
    import types

    from jax.sharding import NamedSharding, PartitionSpec as P

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine

    shape = (32, 1792, 64, 8, 128)
    heads = NamedSharding(mesh4, P(None, None, None, "model", None))
    rep = NamedSharding(mesh4, P())
    cache = KVCache(k=jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=heads),
                    v=jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=heads),
                    lengths=jax.ShapeDtypeStruct((1792,), jnp.int32, sharding=rep))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)
    cow = BatchedJaxEngine._pool_cow_fn.fget(
        types.SimpleNamespace(kv_pool_page=64, mesh=mesh4))
    mem = cow.lower(cache, scalar, scalar, scalar).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 32 * 1792 * 64 * 2 * 128 * 2
    assert mem.temp_size_in_bytes < 2**24


# ------------------------------------ the grammar's mask (ISSUE 28)

MISTRAL = dict(vocab_size=32000, dim=4096, n_heads=32, n_kv_heads=8,
               head_dim=128, mlp_hidden=14336, rope_theta=1e6, eos_ids=(2,),
               tie_embeddings=False)
COLLECTIVE = (r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
              r"collective-permute)(?:-start)?")


def _grammar_chunk_hlo(mesh, rep) -> str:
    """The engine's ragged chunk program with the grammar on, compiled for
    the described chip(s): batch 16, vocabulary 32,000, 2 layers at
    Mistral-7B's widths, a 64-wide admission window, the shipped
    tokenizer's table shapes (6 profile slots x 848 states, 455 classes)."""
    return _chunk_program(mesh, rep, ModelConfig(name="aot", n_layers=2,
                                                 **MISTRAL)).as_text()


def _chunk_program(mesh, rep, cfg, W=64, n_blocks=320, pages=64, B=16,
                   engine_cache=False):
    """The engine's ragged chunk program for a ``W``-wide window, grammar
    on, compiled: its prologue's model call is the engine's
    (``batcher.py::ragged_forward_step_fn``: the window's valid rows packed
    into W + batch). ``W=0``: the plain program, 16 decode steps and no
    prologue, which is what three chunks of four run. ``engine_cache``:
    the cache is the pool engine's for ``cfg`` (its state and count leaves
    with it), not K and V alone."""
    from ai_agent_kubectl_tpu.engine.batcher import make_termination_chunk_fn
    from ai_agent_kubectl_tpu.parallel.sharding import (param_shardings,
                                                        pool_cache_specs,
                                                        sanitize_spec)
    from jax.sharding import NamedSharding

    page = 64
    n_prof, s_max, n_classes = 6, 848, 455

    def arg(shape, dtype, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    shapes = jax.eval_shape(lambda k: random_params_int8(
        k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
        jax.random.PRNGKey(0))
    pool = (cfg.n_layers, n_blocks, page, cfg.n_kv_heads, cfg.head_dim)
    if mesh is None:
        params = jax.tree_util.tree_map(lambda x: arg(x.shape, x.dtype),
                                        shapes)
        heads = rep
    else:
        params = jax.tree_util.tree_map(
            lambda x, s: arg(x.shape, x.dtype, s), shapes,
            param_shardings(shapes, mesh, cfg))
        heads = NamedSharding(mesh, sanitize_spec(
            mesh, pool_cache_specs(cfg)["k"], pool))
    cache = KVCache(k=arg(pool, jnp.bfloat16, heads),
                    v=arg(pool, jnp.bfloat16, heads),
                    lengths=arg((n_blocks,), jnp.int32))
    if engine_cache:
        cache = _engine_cache(arg, cfg, n_blocks, page, B)
    common = dict(kv_limit=pages * page, attn_impl="ragged", mesh=mesh,
                  page_size=page)

    def step(params, tok, pos, cache, live, tables):
        return forward(params, cfg, tok, pos, cache,
                       token_mask=live[:, None], write_mask=live,
                       block_tables=tables, **common)

    def rstep(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1,
                       packed_rows=sum(tok.shape), **common)

    chunk = make_termination_chunk_fn(
        step, 16, cfg.eos_ids, 0, 1.0, vocab_size=cfg.vocab_size,
        pool_tables=True, grammar=True, grammar_s_max=s_max, ragged_w=W,
        ragged_forward_step=rstep if W else None)
    i32, f32, b = jnp.int32, jnp.float32, jnp.bool_

    def vec(dtype):
        return arg((B,), dtype)

    # the staged windows' vectors: the plain program (W = 0) has none
    adm = (arg((B, W), i32), vec(i32), vec(i32), vec(i32), vec(i32), vec(i32),
           vec(f32), vec(i32)) if W else ()
    return jax.jit(chunk, donate_argnums=(1, 2, 3, 7, 8, 12)).lower(
        params, arg((B, 1), i32), arg((B, 1), i32), cache, vec(i32),
        vec(f32), vec(b), vec(b), vec(i32), vec(i32), vec(b),
        arg((B, pages), i32), vec(i32),
        arg((n_prof, cfg.vocab_size), i32),
        arg((n_prof * s_max, -(-n_classes // 32)), jnp.uint32),
        arg((n_prof * s_max, n_classes), i32), *adm).compile()


def test_grammar_mask_is_no_element_gather_on_v5e(one_chip, monkeypatch):
    """ISSUE 28: the legality mask over the vocabulary is bit tests on the
    slots' packed class rows. As ``take_along_axis(class_ok[gs], tc, 1)`` it
    compiled to a gather of 16 x 32,000 one-element slices behind a
    ``GatherScatterIndicesBitpacked`` call that built a two-column index for
    each of them, every step of the scan (5 ms a pass on the chip). The
    gathers that stay take rows (the hoisted token->class map: 16 slices of
    32,000; the packed rows: 16 of 15 words) or sixteen elements."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _grammar_chunk_hlo(None, one_chip)
    assert "tpu_custom_call" in hlo, "the Mosaic kernel is not in the program"
    gathers = [(shape, line) for shape, op, line in _instructions(hlo)
               if op == "gather"]
    assert any(shape.startswith("u32[16,15]") for shape, _ in gathers), (
        "the packed rows' gather should be in the program")
    for shape, line in gathers:
        if "[16,32000]" in shape:
            assert "slice_sizes={1,32000}" in line, line[:300]
    index_builds = [shape for shape, op, line in _instructions(hlo)
                    if "GatherScatterIndicesBitpacked" in line]
    assert not [s for s in index_builds if "32000" in s], index_builds


def test_grammar_mask_over_model4_stays_split_on_v5e(mesh4, monkeypatch):
    """ISSUE 28 over ``model:4``: the mask is made where the logits are,
    on each chip's quarter of the vocabulary. No chip holds a [16, 32000]
    array, and what crosses the mesh under the ``grammar_mask`` and
    ``sampling`` scopes is what a split vocabulary needs and the gather
    form crossed too: sixteen-element reductions (any legal token, the
    argmax's partial winners, the sampled token's class, the slots' packed
    rows) and the all-to-all that re-splits the argmax's index, which the
    partitioner builds batch-split."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    hlo = _grammar_chunk_hlo(mesh4, NamedSharding(mesh4, P()))
    assert "tpu_custom_call" in hlo, "the Mosaic kernel is not in the program"
    assert "[16,32000]" not in hlo
    assert "[16,8000]" in hlo
    crossed = []
    for shape, op, line in _instructions(hlo):
        scope = re.search(r'op_name="([^"]*)"', line)
        if (re.fullmatch(COLLECTIVE, op) and scope
                and re.search("grammar_mask|sampling", scope.group(1))):
            crossed.append((op, shape, scope.group(1)))
    assert crossed, "a split vocabulary reduces across the mesh"
    for op, shape, scope in crossed:
        if max(_sizes(shape), default=1) <= 16 * 128:
            continue
        assert op == "all-to-all" and shape.startswith("s32[4,4,8000]") \
            and "grammar_mask" not in scope, (op, shape, scope)


# ------------------------------- the window's valid rows, packed (ISSUE 39)


@pytest.mark.parametrize("widths,layers,W,n_blocks,pages,was_gib,limit_gib", [
    (MISTRAL, 32, 1024, 320, 64, 1.004, 0.30),
    (MIXTRAL, 6, 512, 1040, 65, 0.381, 0.30),
], ids=["mistral-1024-wide", "mixtral-l6-512-wide"])
def test_widest_chunk_programs_temporaries_follow_the_rows_on_v5e(
        one_chip, monkeypatch, widths, layers, W, n_blocks, pages, was_gib,
        limit_gib):
    """ISSUE 39: the prologue's residual is the window's valid rows, W + 16
    of them, so the widest chunk program's temporaries are no longer 16 x W
    rows of MLP (1.25 and 1.89 GiB: AOT, PR 25) but the mixers' [16, W] q/k/v
    and W + 16 rows of everything else: 1.004 and 0.381 GiB (``was_gib``: AOT,
    PR 39). ISSUE 43: of Mistral's, 0.75 GiB was there at any width and was no
    window's: the ``wq``, ``wk`` and ``wv`` stacks turned over whole (805 MB,
    151 MB for the six layers) for dots whose result carried the head split,
    a copy a chunk. With the split outside the dot: 0.254 and 0.240 GiB (AOT,
    PR 43), the 1,024-wide window's own."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot", n_layers=layers, **widths)
    compiled = _chunk_program(None, one_chip, cfg, W, n_blocks, pages)
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes / 2 ** 30
    assert temp < limit_gib < was_gib, temp


def test_packed_prologue_over_model4_moves_rows_not_the_window_on_v5e(
        mesh4, monkeypatch):
    """ISSUE 39 over ``model:4`` (Mixtral-8x7B's widths, the 512-wide chunk
    program): the packed residual [1, W + 16, D] takes the sequence-axis rule
    (``parallel/sharding.py::residual_spec``), q/k/v are unpacked head-split
    where the projections left them, and nothing the size of the [16, W, D]
    window, or of a chip's quarter of it, crosses the mesh: the slot x width
    form gathered bf16[16,512,4096] and scattered bf16[4,512,4096] a layer."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    N, W, D = 16, 512, 4096
    cfg = ModelConfig(name="aot", n_layers=2, **MIXTRAL)
    hlo = _chunk_program(mesh4, NamedSharding(mesh4, P()), cfg, W, 1792,
                         65).as_text()
    assert "tpu_custom_call" in hlo, "the Mosaic kernel is not in the program"
    crossed = [(op, shape) for shape, op, _ in _instructions(hlo)
               if re.fullmatch(COLLECTIVE, op)]
    assert any(op.startswith("all-gather") and f"[1,{W + N},{D}]" in shape
               for op, shape in crossed), crossed
    wide = [(op, shape) for op, shape in crossed
            if max(_sizes(shape), default=0) >= N * W * D // 8]
    assert not wide, wide
    # the kernel's operands stay a chip's heads: 8 of 32 Q, 2 of 8 KV
    assert f"bf16[{N},{W},8,128]" in hlo and f"bf16[{N},{W},32,128]" not in hlo


# ------------------ a projection's head split outside its dot (ISSUE 43)


def _staging_tool():
    """tools/aot_weight_staging.py: the decode ``forward`` compiled for a
    described chip, and the counts of a compiled module's int8 slices staged
    in VMEM by a blocking fusion, int8 copies and int8 ``copy-start``s."""
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "aot_weight_staging",
        Path(__file__).resolve().parents[1] / "tools" / "aot_weight_staging.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("widths,layers,n_blocks,program", [
    (MISTRAL, 2, 320, "forward"),
    (dict(MISTRAL, n_heads=8, n_kv_heads=2), 2, 1280, "forward"),
    (MIXTRAL, 6, 1040, "plain chunk"),
], ids=["one-chip-32q8kv", "mesh-local-8q2kv", "mixtral-l6-plain-chunk"])
def test_decode_projections_stream_their_weights_as_stored_on_v5e(
        one_chip, monkeypatch, widths, layers, n_blocks, program):
    """ISSUE 43: a decode pass (batch 16, one token a slot) reads ``wq``,
    ``wk`` and ``wv`` as ``wo`` and the MLP's are read, the layer's
    ``dynamic-slice`` inside the dot's fusion and the bytes streamed once from
    HBM. With the head split folded into the dot (``qmatmul(x, w).reshape(B,
    S, H, hd)``) the compiler gave the weight operand a contraction-minor
    layout and the layer loop's body held, for those three matrices alone, a
    blocking fusion that sliced the layer's matrix out to VMEM (an ``s8``
    result in ``S(1)`` from a fusion whose root is a ``dynamic-slice``) and a
    ``copy`` that turned it over: 3 and 3 at Mistral-7B's widths, 25.2 MB a
    layer a pass. ``ops/quant.py::qmatmul_heads`` keeps the split outside.

    The third case is Mixtral-8x7B's six-layer cut in the engine's PLAIN chunk
    program (16 decode steps, grammar on): there the turned-over copy was of
    the three whole stacks, hoisted out of the loops (151 MB of temporaries),
    and the 100.7 MB ``wq`` stack, small enough for the alternate memory, was
    parked there and copied out and back (two ``copy-start``s over ``s8``: PERF.md,
    Open question 15, 0.9 ms a pass on the chip). All of it goes with the
    fold."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    tool = _staging_tool()
    cfg = ModelConfig(name="aot", n_layers=layers, **widths)
    if program == "plain chunk":
        compiled = _chunk_program(None, one_chip, cfg, 0, n_blocks, 65)
    else:
        compiled = tool.decode_program(cfg, one_chip, n_blocks=n_blocks)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo, "the Mosaic kernel is not in the program"
    assert re.search(r"while\(", hlo), "the layer loop is not in the program"
    found = tool.weight_staging(hlo)
    assert (found["staged"], found["copies"]) == (0, 0), found
    if program == "plain chunk":
        # (a TWO-layer stack of w_down, 117 MB, fits the alternate memory
        # too and is parked there in the cases above once the staged slices
        # no longer fill it, as the parent's 8q2kv program parked it: the
        # depth of the test, not of a cell)
        assert found["bounces"] == 0, found
    # what the hoisted copies of the stacks held (151 MB) is no temporary
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 26


# ------------------------- key selection and grouped experts (ISSUE 31)

KEYE = dict(vocab_size=151936, dim=2048, n_heads=32, n_kv_heads=4,
            head_dim=128, mlp_hidden=768, rope_theta=1e7, eos_ids=(2,),
            tie_embeddings=False, n_experts=128, experts_per_token=8,
            qk_norm=True, index_topk=2048, index_heads=16,
            index_head_dim=64)


@pytest.mark.parametrize("W", [1, 512], ids=["decode", "window-512"])
def test_selecting_forward_compiles_at_published_widths_on_v5e(one_chip, W,
                                                                monkeypatch):
    """keye-vl-2.0-30b-a3b-l8's block (2 layers, every width as published,
    batch 16, the engine's 257-page table over a 512-block pool): Mosaic
    accepts the ragged kernel with its ``sel`` operand at 32Q/4KV heads
    (the [tq, span] mask broadcast over a KV group's 8 query heads) and the
    grouped expert kernel over int8 experts of 2048 x 768 (scoped VMEM
    raised for the three double-buffered matrices); the index-key leaf
    rides the donated cache; both branches of the selection are in the
    program. The pool write is in place for all three leaves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-keye", n_layers=2, **KEYE)
    B, page, n_blocks, pages = 16, 64, 512, 257

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    cache = _engine_cache(arg, cfg, n_blocks, page, B)

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    # ISSUE 32: both ragged calls of the program (with the mask and
    # without) stream their live blocks through 4 buffers: a decode row's
    # row-tiled, 8 pages of [64 * 4 KV, 128]; a window's 4 pages of
    # [64, 4, 128]
    assert set(_ragged_kv_buffers(traced.jaxpr.jaxpr)) == {
        (4, 8, 256, 128) if W == 1 else (4, 4, 64, 4, 128)}
    # ISSUE 34: the grouped expert kernel's tile rows and grid follow the
    # call's shapes (a decode pass: 16 rows, 129 steps for 128 pairs; the
    # 512-wide window of 16 slots: 256 rows); its scoped VMEM is stated: an
    # expert's three matrices as stored, double-buffered (9 MiB), converted
    # (9 MiB) and a tile's rows, in 64 MiB of a v5e core's 128
    assert set(_grouped_kernel_calls(traced.jaxpr.jaxpr)) == {
        (16, 129, 64 * 2 ** 20) if W == 1 else (256, 384, 64 * 2 ** 20)}
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    # the grouped expert kernel, and the ragged kernel in the dense branch
    # (and, for a window, again with its mask in the selecting branch)
    assert hlo.count('custom_call_target="tpu_custom_call"') >= (3 if W > 1 else 2)
    assert "conditional" in hlo, "selection is decided at run time"
    mem = compiled.memory_analysis()
    leaves = 2 * cfg.n_layers * n_blocks * page * (2 * 4 * 128 + 128)
    assert mem.alias_size_in_bytes >= leaves, "K, V and index keys are donated"
    # a window's index scores are tiled: the temporaries stay far under
    # the 16 x 512 x 16 heads x 16,448 float32 scores (8.6 GB) of one piece
    assert mem.temp_size_in_bytes < 4 * 2 ** 30, mem.temp_size_in_bytes


def test_the_grouped_expert_kernel_cannot_hold_a_mixtral_expert_on_v5e(one_chip,
                                                                       monkeypatch):
    """Why ``ModelConfig.grouped_experts`` leaves Mixtral on ``dense_moe``: the
    grouped kernel holds a tile's whole expert in VMEM (three matrices as
    stored, double-buffered), and three of 4096 x 14336 int8 are 337 MB against
    a v5e core's 128 MiB. When an F-tiled kernel makes this compile, the rule's
    threshold is to be measured on the chip (ROADMAP S2), not kept."""
    from ai_agent_kubectl_tpu.ops.quant import QuantInt8
    from ai_agent_kubectl_tpu.parallel.moe import grouped_moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    E, D, F = 8, 4096, 14336
    cfg = ModelConfig(name="aot-mixtral", vocab_size=32000, dim=D, n_layers=1,
                      n_heads=32, n_kv_heads=8, head_dim=128, mlp_hidden=F,
                      n_experts=E, experts_per_token=2)
    assert not cfg.grouped_experts

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def leaf(i, o):
        return QuantInt8(q=arg((E, i, o), jnp.int8),
                         scale=arg((E, 1, o), jnp.float32))

    lp = {"router": arg((D, E), jnp.bfloat16), "w_gate": leaf(D, F),
          "w_up": leaf(D, F), "w_down": leaf(F, D)}
    with pytest.raises(Exception, match="(?i)vmem"):
        jax.jit(lambda lp, x: grouped_moe(cfg, lp, x)).lower(
            lp, arg((16, 1, D), jnp.bfloat16)).compile()


# ------------------------------------ one mixer a layer (ISSUE 33)

NEMOTRON = dict(vocab_size=131072, dim=2688, n_heads=32, n_kv_heads=2,
                head_dim=128, mlp_hidden=1856, rms_eps=1e-5,
                activation="relu2", n_experts=128, experts_per_token=6,
                eos_ids=(2,), ssm_heads=64, ssm_head_dim=64, ssm_state=128,
                ssm_groups=8, ssm_conv=4, ssm_chunk=128,
                shared_mlp_hidden=3712, router="sigmoid_bias",
                router_scale=2.5, use_rope=False)


@pytest.mark.parametrize("B,W", [(16, 1), (16, 64), (1, 512)],
                         ids=["decode", "window-64", "eager-512"])
def test_patterned_forward_compiles_at_published_widths_on_v5e(one_chip, B, W,
                                                                monkeypatch):
    """nemotron-3-nano-30b-a3b-l13's three kinds (one layer each, every
    width as published, the engine's 257-page table over a 4,096-block
    pool): Mosaic accepts the grouped expert kernel over int8 experts of
    TWO matrices, 2688 x 1856 (1,856 is no multiple of 128: the TPU keeps
    that leaf with 2,688 minor-most, and the kernel takes the up stack as
    [F, D] so that no copy of the stack stands in front of it — as
    [D, F] a 638 MB copy a layer a pass did), and the ragged kernel at
    32Q/2KV without a rotary embedding; the state leaves ride the
    donated cache; nothing expert-stack-sized or pool-sized moves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-nemotron", n_layers=3, layer_pattern="ME*",
                      **NEMOTRON)
    page, n_blocks, pages = 64, 4096, 257

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    cache = _engine_cache(arg, cfg, n_blocks, page, B)
    pool, ssm = cache.k.shape, cache.ssm
    assert pool[0] == 1 and ssm.shape[1] == B

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    # ISSUE 34: a tile's rows and the grid from the call's shapes (24 rows
    # an expert in an eager piece get 32-row tiles, not two of 16); the
    # kernel's scoped VMEM is stated: two matrices as stored, double-
    # buffered (19 MiB), converted (19 MiB) and a tile's rows, in 64 MiB
    assert set(_grouped_kernel_calls(traced.jaxpr.jaxpr)) == {
        {1: (16, 97), 64: (64, 223), 512: (32, 221)}[W] + (64 * 2 ** 20,)}
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    # the grouped expert kernel, the ragged attention kernel and, in a decode
    # pass (ISSUE 49: it was 2 there too while the step ran in ``jnp``),
    # ops/ssd_scan.py's step kernel on the WHOLE aliased ``ssm`` leaf: a
    # row's 64 heads in two blocks [32, 64, 128], in and out double-buffered
    # inside the VMEM it asks for (this one-plane leaf, 33.5 MB, the compiler
    # prefetches whole with a ``copy-start``; the loop of two planes below,
    # and the cell's of six, it does not)
    # and in a window ops/ssd_scan.py's window kernel (ISSUE 54), the same way
    steps = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
             if eqn.params["name"] == "ssd_step"]
    windows = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
               if eqn.params["name"] == "ssd_window"]
    assert (len(steps), len(windows)) == ((1, 0) if W == 1 else (0, 1))
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    for eqn in steps:
        gm = eqn.params["grid_mapping"]
        assert gm.grid == (B, 2)
        blocks = sum(math.prod(b if isinstance(b, int) else b.block_size
                               for b in bm.block_shape) * 4
                     for bm in gm.block_mappings)
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert 2 * 32 * 64 * 128 * 4 < blocks and 2 * blocks < limit
    assert not _results_of_size(hlo, {128 * 2688 * 1856}), "an expert stack moved"
    # the pool: the row writes alone, in place (each scatter shows twice:
    # inside its fusion, and as the fusion)
    moved = _results_of_size(hlo, {math.prod(pool)})
    assert {op for op, _ in moved} <= {"scatter", "fusion"}, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (2 * math.prod(pool) * 2
                                       + math.prod(ssm.shape) * 4)
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes


def test_state_space_decode_loop_holds_no_copy_of_the_state_on_v5e(one_chip,
                                                                    monkeypatch):
    """The engine's ragged chunk program (a 64-wide prologue window, 16
    decode steps, the grammar on, the engine's cache) for a pattern with TWO
    state-space layers at nemotron-3-nano-30b-a3b-l13's widths, batch 16: the
    decode loop carries the state leaf [2, 16, 64, 64, 128] float32 and its
    body holds nothing of the leaf's size or of a plane's but the two step
    kernels' calls on the whole aliased leaf (ISSUE 49). With ``ssd_step`` in
    ``jnp`` from ``ssm[j]`` to ``ssm.at[j].set`` the body held, a layer, a
    plane-sized ``slice`` and a ``dynamic-update-slice`` of the leaf with the
    plane's multiplies, adds and broadcasts in their fusions and, at the
    cell's own 13 layers (six planes), two ``copy`` of the whole leaf a step
    (201 MB each: AOT, PR 49, of the parent; what the chip's trace showed at
    PR 33). The prologue's window is the window kernel's two calls on the
    same aliased leaf (ISSUE 54: it sliced its plane and set it, once a
    chunk): nothing of the leaf's size but the four kernels' results
    anywhere, no ``copy`` among them."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-nemotron", n_layers=4, layer_pattern="MEM*",
                      **NEMOTRON)
    hlo = _chunk_program(None, one_chip, cfg, 64, 4096, 257, B=16,
                         engine_cache=True).as_text()
    plane = 16 * 64 * 64 * 128
    in_loop = _results_of_size(_loop_bodies(hlo), {plane, 2 * plane})
    assert in_loop and {op for op, _ in in_loop} == {"custom-call"}, in_loop
    assert len(in_loop) == 2 and {dims for _, dims in in_loop} == {
        "2,16,64,64,128"}
    whole = _results_of_size(hlo, {2 * plane})
    assert {op for op, _ in whole} == {"custom-call"} and len(whole) == 4, whole


GRANITE = dict(vocab_size=100352, dim=2048, n_heads=32, n_kv_heads=8,
               head_dim=64, mlp_hidden=8192, dense_mlp_hidden=8192,
               rms_eps=1e-5, eos_ids=(2,), tie_embeddings=True,
               mixers_per_layer=2, ssm_heads=64, ssm_head_dim=64,
               ssm_state=128, ssm_groups=1, ssm_conv=4, ssm_chunk=256,
               use_rope=False, embed_multiplier=12, residual_multiplier=0.22,
               attention_multiplier=0.015625, logits_scaling=8)

#: temporaries of a window program: the parent's (``ssd_scan`` from and to a
#: sliced plane: AOT, PR 54, ``git archive aa8b46b``) and this tree's
_WINDOW_TEMPS = {("granite", 1): (3_762_688, 3_128_832),
                 ("granite", 16): (13_325_312, 4_129_280),
                 ("nemotron", 1): (57_098_240, 44_987_392),
                 ("nemotron", 16): (70_192_640, 3_096_576)}


@pytest.mark.parametrize("B,W,packed", [(1, 512, None), (16, 64, 80)],
                         ids=["eager-512", "prologue-64"])
@pytest.mark.parametrize("sizes", ["granite", "nemotron"])
def test_a_state_space_window_is_one_kernel_a_layer_on_the_aliased_leaf_on_v5e(
        one_chip, sizes, B, W, packed, monkeypatch):
    """A window program (an eager 512-wide piece of one sequence; a chunk
    program's 64-wide prologue over 16 slots, its valid rows packed) at
    granite-4.0-h-micro's widths (ONE group of 64 heads; two periods
    ``MDMD*D`` scanned, the layer a traced ordinal) and at nemotron-3-nano-
    30b-a3b-l13's (8 groups; ``MEM*`` unrolled): every Mamba-2 layer is ONE
    ``ssd_window`` call (ISSUE 54) on the WHOLE aliased ``ssm`` leaf, its
    grid (row, block of whole groups: 64 heads of one group, or 32 of eight,
    chunk of 128 or of the window's 64), its blocks inside the VMEM it asks
    for; under ``ssm/scan`` nothing float32 the size of a row's plane or more
    is sliced, copied or updated (the parent sliced the plane out, set it
    back and stacked each chunk's outputs with a ``dynamic-update-slice`` in
    a loop of its own); the temporaries are under the parent's."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = (ModelConfig(name="aot-granite", n_layers=6,
                       layer_pattern="MDMD*D" * 2, **GRANITE)
           if sizes == "granite" else
           ModelConfig(name="aot-nemotron", n_layers=4, layer_pattern="MEM*",
                       **NEMOTRON))
    page, n_blocks, pages = 64, 4096, 257

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    from ai_agent_kubectl_tpu.ops.ragged_attention import lane_heads
    cache = jax.tree_util.tree_map(
        lambda a: arg(a.shape, a.dtype), jax.eval_shape(
            lambda: KVCache.pool_zeros(
                cfg, n_blocks=n_blocks, page=page, slots=B,
                counts_experts=cfg.grouped_experts,
                lane_heads=lane_heads(cfg.head_dim, cfg.kv_heads_paged))))
    leaf = cache.ssm.shape
    assert leaf == (cfg.n_of("M"), B, 64, 64, 128)

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1,
                       packed_rows=packed)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    calls = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
             if eqn.params["name"] == "ssd_window"]
    # (two layers unrolled, or a period's two in the scan's body, traced once)
    assert len(calls) == 2 and cfg.n_of("M") == (4 if sizes == "granite" else 2)
    for eqn in calls:
        gm = eqn.params["grid_mapping"]
        assert gm.grid == (B, 1 if sizes == "granite" else 2, W // min(W, 128))
        assert eqn.params["input_output_aliases"] == ((10, 1),)
        assert eqn.invars[10].aval.shape == leaf == eqn.outvars[1].aval.shape
        # (counted at 4 B an element: the inputs come in bf16)
        blocks = sum(math.prod(b if isinstance(b, int) else b.block_size
                               for b in bm.block_shape) * 4
                     for bm in gm.block_mappings)
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert 2 * 64 * 64 * 128 * 4 // gm.grid[1] < blocks
        assert 2 * blocks < limit
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    moved = [(op, result) for result, op, line in _instructions(hlo)
             if op in ("dynamic-update-slice", "dynamic-slice", "copy",
                       "copy-start", "while")
             and re.search(r'op_name="[^"]*ssm/scan', line)
             and any(n >= 64 * 64 * 128 for n in _sizes(
                 " ".join(re.findall(r"f32\[[\d,]+\]", result))))]
    assert not moved, moved
    before, pinned = _WINDOW_TEMPS[sizes, B]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= pinned * 1.05 < before, temp


# ------------------- latent attention and a share of the experts (ISSUE 38)

MISTRAL4 = dict(vocab_size=131072, dim=4096, n_heads=32, n_kv_heads=32,
                head_dim=128, mlp_hidden=2048, eos_ids=(2,), n_experts=32,
                experts_per_token=4, router_width=128, shared_mlp_hidden=2048,
                dense_mlp_hidden=12288, q_lora_rank=1024, kv_lora_rank=256,
                qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
                rope_interleave=True, rope_factor=128.0, rope_original_max=8192,
                rope_mscale=1.0, rope_mscale_all_dim=1.0, q_scale_beta=0.1)


@pytest.mark.parametrize("B,W", [(16, 1), (16, 64), (1, 512)],
                         ids=["decode", "window-64", "eager-512"])
def test_latent_forward_compiles_at_published_widths_on_v5e(one_chip, B, W,
                                                             monkeypatch):
    """mistral-small-4-119b-2603-l9's block (2 layers, every width as
    published, the engine's 513-page table over the cell's 8,192-block pool):
    Mosaic accepts the ragged kernel in its latent form — ONE leaf of 640
    lanes a pair of tokens streamed through the ring, 32 query heads over one
    key row, a decode row and a 16-column prefill tile — and the grouped
    expert kernel over the 32 int8 experts of 4096 x 2048 this chip holds of
    the 128 the router scores; the latent leaf rides the donated cache and
    is the whole of it (640 B a token a layer, not a lane more); the write
    is one gather of the window's rows and one in-place scatter; nothing
    pool-sized or expert-stack-sized moves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-mistral4", n_layers=2, **MISTRAL4)
    page, n_blocks, pages = 64, 8192, 513

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    cache = _engine_cache(arg, cfg, n_blocks, page, B)
    leaf = cache.lat.shape
    assert leaf == (cfg.n_layers, n_blocks, page // 2, 640)  # bytes a token a layer
    assert cache.k is None and cache.v is None

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    # the latent ring: 4 buffers of 8 pages of [32 pair rows, 640 lanes]
    assert set(_ragged_kv_buffers(traced.jaxpr.jaxpr)) == {(4, 8, 32, 640)}
    # a quarter of the pairs are expected here: 16 rows a tile at decode, 32
    # where 16 x 64 x 4 / 4 pairs over 32 experts are 32 rows an expert
    calls = set(_grouped_kernel_calls(traced.jaxpr.jaxpr))
    assert {c[0] for c in calls} == {{1: 16, 64: 48, 512: 32}[W]}, calls
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert not _results_of_size(hlo, {32 * 4096 * 2048}), "an expert stack moved"
    moved = _results_of_size(hlo, {math.prod(leaf), math.prod(leaf[1:])})
    assert {op for op, _ in moved} <= {"scatter", "fusion"}, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(leaf) * 2
    assert mem.temp_size_in_bytes < 2 ** 30, mem.temp_size_in_bytes
    print(f"\nAOT mistral4 B={B} W={W}: temp {mem.temp_size_in_bytes / 2**20:.0f} MiB, "
          f"arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"aliased {mem.alias_size_in_bytes / 2**30:.2f} GiB")


def test_latent_copy_on_write_is_in_place_on_v5e(one_chip):
    """The cell's pool (9 layers, 8,192 blocks: 2.81 GiB): the engine's
    copy-on-write program holds no leaf-sized temporary. The row form's
    scatter across the layers made the compiler lay the leaf out layer-
    innermost: a copy of the pool in and one out, 5.0 GiB that did not fit
    beside the weights (the server died loading ``jit_cow``: my chip run,
    PR 38)."""
    import types

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf = (9, 8192, 32, 640)
    cache = KVCache(k=None, v=None, lengths=arg((8192,), jnp.int32),
                    lat=arg(leaf, jnp.bfloat16), lat_rows=arg((2,), jnp.int32),
                    experts_read=arg((), jnp.int32))
    cow = BatchedJaxEngine._pool_cow_fn.fget(
        types.SimpleNamespace(kv_pool_page=64, mesh=None))
    scalar = arg((), jnp.int32)
    mem = cow.lower(cache, scalar, scalar, scalar).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(leaf) * 2
    assert mem.temp_size_in_bytes < 2**24


# ------------- attention of two kinds and a ring beside the pool (ISSUE 40)

LAGUNA = dict(vocab_size=100352, dim=3072, n_heads=48, n_kv_heads=8,
              head_dim=128, mlp_hidden=1024, dense_mlp_hidden=12288,
              eos_ids=(2,), n_experts=64, experts_per_token=10,
              router_width=256, router_scale=2.5, shared_mlp_hidden=1024,
              mixers_per_layer=2, sliding_window=512, sliding_n_heads=72,
              sliding_rope_theta=10000.0, rope_theta=500000.0,
              rope_partial=0.5, rope_factor=128.0, rope_original_max=8192,
              rope_attention_factor=1.4852030263919618,
              attn_gate="per-head")


@pytest.mark.parametrize("B,W,packed", [(16, 1, None), (16, 64, 80),
                                        (16, 512, 528), (1, 512, None)],
                         ids=["decode", "window-64", "window-512", "eager-512"])
def test_sliding_forward_compiles_at_published_widths_on_v5e(one_chip, B, W,
                                                             packed,
                                                             monkeypatch):
    """laguna-s-2.1-l12's four mixers (layer 0, full attention then the
    dense MLP, and layer 1, sliding attention then experts; every width as
    published, the engine's 257-page table over a 4,096-block pool and a
    ring of 1,024 rows a slot): Mosaic accepts the ragged kernel at 6 and
    at 9 query heads a KV head — a decode row of the sliding kind is 72 x 8
    = 576 score elements a key, past the flat form's 512, so it takes the
    transposed form with 9 rows a KV head — with the page stream's lower
    bound, reading the ring through a computed table; the pool holds the
    full layer's rows alone; the rings ride the donated cache and are
    written in place; nothing pool-sized or expert-stack-sized moves."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-laguna", n_layers=2, layer_pattern="*DSE",
                      **LAGUNA)
    page, n_blocks, pages = 64, 4096, 257

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    cache = _engine_cache(arg, cfg, n_blocks, page, B)
    pool, sk = cache.k.shape, cache.sk
    assert pool[0] == 1 and sk.shape == (1, B, 1024, 8, 128)

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1,
                       packed_rows=packed)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    # the sliding layer's grid visits a span's blocks, not the table's 33
    # (a decode row: 2 blocks of 8 pages against the table's 33; a 16-column
    # tile of 72 heads: 4 blocks of 4 pages against 65)
    full, sliding = sorted((g for g in _pallas_grids(traced.jaxpr.jaxpr)
                            if len(g) == 3), key=lambda g: -g[2])
    assert full[0] == sliding[0] == B
    assert (sliding[2], full[2]) == ((2, 33) if W == 1 else (4, 65))
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    # two attention kernels and the grouped expert GEMM
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    assert not _results_of_size(hlo, {64 * 3072 * 1024}), "an expert stack moved"
    moved = _results_of_size(hlo, {math.prod(pool)})
    assert {op for op, _ in moved} <= {"scatter", "fusion"}, moved
    # the rings: written in place; no synchronous copy of one (this one-layer
    # leaf is small enough that the compiler may prefetch it whole into
    # VMEM for the kernel, an async copy-start; nine layers' is not)
    ring_ops = {op for op, _ in _results_of_size(hlo, {math.prod(sk.shape)})}
    assert "scatter" in ring_ops and not ring_ops & {"copy", "transpose"}, ring_ops
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * 2 * (math.prod(pool)
                                               + math.prod(sk.shape))
    assert mem.temp_size_in_bytes < 2 ** 29, mem.temp_size_in_bytes


# ------ linear attention by the gated delta rule beside full (ISSUE 45)

OLMO = dict(vocab_size=100352, dim=3840, n_heads=30, n_kv_heads=30,
            head_dim=128, dense_mlp_hidden=11008, eos_ids=(2,),
            mixers_per_layer=2, layer_pattern="LDLDLD*D" * 8,
            lin_key_heads=30, lin_value_heads=30, lin_key_dim=96,
            lin_value_dim=192, lin_conv=4, lin_neg_eigval=True,
            post_norm=True, qk_norm_whole=True, use_rope=False)


#: custom calls that are the compiler's notes to itself (a buffer, a layout,
#: an index known to be in bounds) and take no time on the device
_XLA_NOTES = {"AllocateBuffer", "ConcatBitcast", "AssumeGatherIndicesInBound",
              "GatherScatterIndicesBitpacked"}


@pytest.mark.parametrize("B,W,packed", [(8, 1, None), (8, 512, 520),
                                        (1, 512, None)],
                         ids=["decode", "window-512", "eager-512"])
def test_linear_forward_compiles_at_published_widths_on_v5e(one_chip, B, W,
                                                            packed,
                                                            monkeypatch):
    """olmo-hybrid-7b's first two periods (each three linear layers, one
    full, four dense MLPs: the pattern repeats, so one period's mixers are
    the body of a scan over the two; every width as published, the cell's
    64-page table over its 448-block pool): Mosaic accepts the ragged kernel at MHA's 30 KV heads
    with ONE query head each because a pool row holds 32 (it refused to
    slice 30 of the tile's 32: ``ModelConfig.kv_heads_paged``); the matrix
    state [6, B, 96, 30 x 192] rides the donated cache (the scan's carry)
    in whole lane tiles and is written in place a layer, at a traced plane,
    never copied or turned over whole; the
    chunked scan's unit-triangular inverse (ISSUE 47: by blocks, ops/
    gated_delta.py::unit_lower_inverse) compiles to fusions and one small
    loop: XLA's own ``triangular_solve`` came out as a custom call of its
    own, ``InvertDiagBlocksLowerTriangular``, the profile's ``custom-call``
    and 77-80% of a window's scan on the chip, and a window program holds
    neither now. A decode pass (ISSUE 46) runs
    ops/gated_delta.py's step kernel a linear layer on the WHOLE aliased
    leaf: Mosaic accepts it ([96, 1,920] blocks, a key broadcast down a
    head's lanes), its blocks fit the VMEM it asks for, and no ``dynamic-
    slice``, ``copy`` or ``dynamic-update-slice`` the size of the leaf or of
    one of its planes is left in the program. A window (ISSUE 56) runs
    ops/gated_delta_window.py's kernel a linear layer the same way (grid
    (rows, 3 blocks of 10 heads, chunks of 64): a 192-wide head with its
    neighbour, 384 lanes), and moves no plane either: what the scan made for
    every chunk at once ([B, n, H, 64, 64] float32, 1.07 GiB of temporaries
    at 8 x 512) is gone."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-olmo", n_layers=8, **OLMO)
    page, n_blocks, pages = 64, 448, 64

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    cache = _engine_cache(arg, cfg, n_blocks, page, B)
    pool, lin = cache.k.shape, cache.lin.shape
    assert pool == (2, n_blocks, page, 32, 128) and lin == (6, B, 96, 5760)
    assert cache.lconv.shape == (6, B, 3, 11520) and cache.lin_rows.shape == (6,)

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1,
                       packed_rows=packed)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    # one attention kernel, its K ring's rows 32 heads of 128 lanes
    ring, = set(_ragged_kv_buffers(traced.jaxpr.jaxpr))
    assert ring[-2:] == (32, 128), ring
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    # the kernels of the program, in the scan: the period's one full layer's
    # and, at decode, its three linear layers' steps
    names = [eqn.params["name"] for eqn in _pallas_calls(traced.jaxpr.jaxpr)]
    steps = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
             if eqn.params["name"] == "gated_delta_step"]
    windows = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
               if eqn.params["name"] == "gated_delta_window"]
    assert (len(steps), len(windows)) == ((3, 0) if W == 1 else (0, 3)), names
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1 + 3
    assert " while(" in hlo
    # no solve of XLA's is left (it was a custom call, not a loop), and no
    # custom call that takes device time but the kernels'
    assert "triangular" not in hlo.lower()
    targets = set(re.findall(r'custom_call_target="([^"]+)"', hlo))
    assert targets - _XLA_NOTES == {"tpu_custom_call"}, targets
    carried = {"scatter", "fusion", "while", "parameter", "tuple",
               "get-tuple-element", "bitcast"}
    moved = {op for op, _ in _results_of_size(hlo, {math.prod(pool)})}
    assert moved <= carried, moved
    # the whole state leaf: carried, and by a window written in place (a
    # dynamic-update-slice a layer, each in its fusion); this six-layer leaf is
    # small enough that the compiler may prefetch it whole (an async
    # copy-start), which 24 layers' it does not: the whole model's decode
    # program has 1.9 MB of temporaries (AOT, PR 45)
    state_ops = {op for op, _ in _results_of_size(hlo, {math.prod(lin)})}
    # a kernel alone names the leaf (this six-layer leaf of ONE row is small
    # enough that the compiler prefetches it whole, an async copy or slice,
    # which 24 layers' it does not), and nothing moves a plane
    assert state_ops <= carried | {"custom-call"} | (
        {"copy-start", "copy-done", "slice-start", "slice-done"}
        if B == 1 else set()), state_ops
    assert B == 1 or not _results_of_size(hlo, {math.prod(lin[1:])})
    for eqn in windows:
        gm = eqn.params["grid_mapping"]
        assert gm.grid == (B, 3, W // 64)
        limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
        assert 4 * 96 * 1920 * 4 < limit
    if W == 1:
        for eqn in steps:
            gm = eqn.params["grid_mapping"]
            assert gm.grid == (B, 3)
            blocks = sum(math.prod(b if isinstance(b, int) else b.block_size
                                   for b in bm.block_shape) * 4
                         for bm in gm.block_mappings)
            limit = eqn.params["compiler_params"]["mosaic_tpu"].vmem_limit_bytes
            assert 2 * 96 * 1920 * 4 < blocks and 2 * blocks < limit
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (2 * math.prod(pool) * 2
                                       + math.prod(lin) * 4)
    # a 512-wide window's convolution over 8 slots' columns and the
    # projections' results (the scan's every chunk's solve at once was 1.07
    # GiB here, 1.87 for the whole model)
    assert mem.temp_size_in_bytes < 2 ** 29, mem.temp_size_in_bytes


def test_linear_chunk_program_and_copy_on_write_compile_on_v5e(one_chip,
                                                               monkeypatch):
    """The engine's own programs at olmo-hybrid-7b's widths (two periods,
    batch 8, the cell's 448 blocks): the 512-wide ragged chunk program with
    the grammar on, its cache the engine's (state and count leaves carried
    through the decode loop), and ``jit_cow`` over the 32-head pool."""
    import types

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-olmo", n_layers=8, **OLMO)
    compiled = _chunk_program(None, one_chip, cfg, 512, 448, 64, B=8,
                              engine_cache=True)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30, mem.temp_size_in_bytes

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    whole = ModelConfig(name="aot-olmo-whole", n_layers=32, **OLMO)
    cache = _engine_cache(arg, whole, 448, 64, 8)
    assert cache.k.shape == (8, 448, 64, 32, 128)
    cow = BatchedJaxEngine._pool_cow_fn.fget(
        types.SimpleNamespace(kv_pool_page=64, mesh=None))
    scalar = arg((), jnp.int32)
    mem = cow.lower(cache, scalar, scalar, scalar).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * math.prod(cache.k.shape) * 2
    assert mem.temp_size_in_bytes < 2 ** 24


# ------ Kimi delta attention beside latent attention in one pattern (ISSUE 48)

LING = dict(vocab_size=157184, dim=2560, n_heads=32, n_kv_heads=32,
            head_dim=128, mlp_hidden=768, dense_mlp_hidden=6144,
            shared_mlp_hidden=768, n_experts=128, router_width=512,
            experts_per_token=8, router="sigmoid_bias", router_scale=2.5,
            n_group=8, topk_group=4, eos_ids=(2,), mixers_per_layer=2,
            layer_pattern="LDLDLELELE*E" + "LELELELELE*E",
            lin_key_heads=32, lin_value_heads=32, lin_key_dim=128,
            lin_value_dim=128, lin_conv=4, lin_channel_decay=True,
            lin_decay_floor=-5.0, lin_out_gate="sigmoid", kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            rope_theta=6e6, attn_gate="head_wise")


@pytest.mark.parametrize("layers,B,W,packed", [(12, 16, 1, None),
                                               (6, 16, 512, 528),
                                               (6, 1, 512, None)],
                         ids=["decode-full-depth", "window-512", "eager-512"])
def test_kda_latent_forward_compiles_at_published_widths_on_v5e(
        one_chip, layers, B, W, packed, monkeypatch):
    """ling-3.0-flash-vl-l12 (ten KDA layers, two latent ones, two dense MLPs,
    ten expert layers of 128 held of 512; every width as published, the cell's
    512-page table over its 8,192-block pool; the decode program at its full
    depth, a window's at one whole period): Mosaic accepts the latent kernel
    at rows of 576 values (a pair of tokens a 1,152-lane leaf row, queries of
    768 lanes), the step kernel with a decay a key channel ([128, 2,048]
    blocks of 16 heads, the decay a third run of columns beside keys and
    queries), and the grouped expert kernel at 128 held experts of 768. The
    latent leaf [2, 8192, 32, 1152] has a plane a LATENT layer and rides the
    donated cache with the matrix state [10, B, 128, 4096]; a decode pass
    names the state leaf in its ten step kernels alone and moves no plane of
    it; a window writes a plane in place. The 16 x 512 window's temporaries
    stay under 1.5 GiB because the scan with a decay a key channel takes four
    rows at a time (ops/gated_delta.py::_ROWS_AT_ONCE: a dozen float32
    [B,n,H,C,dk] arrays at once were 1.6 GiB at full depth beside 10.1 GiB of
    arguments; 1.23 now, the file's ``sizing`` has the full-depth readings)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-ling", n_layers=layers, **LING)
    page, n_blocks, pages = 64, 8192, 512
    nL, nA, nE = cfg.n_of("L"), cfg.n_of("*"), cfg.n_of("E")
    assert (nL, nA, nE) == ((10, 2, 10) if layers == 12 else (5, 1, 4))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    assert params["layers"]["lin_wf"].q.shape == (nL, 2560, 4096)
    assert params["layers"]["wq"].q.shape == (nA, 2560, 32 * 192)
    assert params["layers"]["router_bias"].shape == (nE, 512)
    cache = _engine_cache(arg, cfg, n_blocks, page, B)
    lat, lin = cache.lat.shape, cache.lin.shape
    assert cache.k is None and lat == (nA, n_blocks, 32, 1152)
    assert lin == (nL, B, 128, 4096) and cache.lconv.shape == (nL, B, 3, 12288)

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1,
                       packed_rows=packed)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    steps = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
             if eqn.params["name"] == "gated_delta_step"]
    assert len(steps) == (nL if W == 1 else 0)
    # ... beside a latent kernel a latent layer and a grouped one an expert layer
    assert hlo.count('custom_call_target="tpu_custom_call"') == (
        len(steps) + nA + nE)
    assert "triangular" not in hlo.lower()
    carried = {"scatter", "fusion", "while", "parameter", "tuple",
               "get-tuple-element", "bitcast"}
    moved = {op for op, _ in _results_of_size(hlo, {math.prod(lat)})}
    assert moved <= carried | {"custom-call"}, moved
    state_ops = {op for op, _ in _results_of_size(hlo, {math.prod(lin)})}
    if W == 1:
        assert state_ops <= carried | {"custom-call"}, state_ops
        assert not _results_of_size(hlo, {math.prod(lin[1:])})
        for eqn in steps:
            gm = eqn.params["grid_mapping"]
            assert gm.grid == (B, 2)            # 16 of the 32 heads a block
            shapes = [tuple(b if isinstance(b, int) else b.block_size
                            for b in bm.block_shape)
                      for bm in gm.block_mappings]
            # keys, queries and decays a column each; v and beta; the state
            assert shapes[:3] == [(1, 1, 128, 48), (1, 2, 2048),
                                  (1, 1, 128, 2048)], shapes
    else:
        assert "dynamic-update-slice" in state_ops, state_ops
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(lat) * 2 + math.prod(lin) * 4
    assert mem.temp_size_in_bytes < (1.5 * 2 ** 30 if B * W > 512
                                     else 2 ** 28), mem.temp_size_in_bytes


def test_kda_latent_chunk_program_and_copy_on_write_compile_on_v5e(
        one_chip, monkeypatch):
    """The engine's own programs at ling-3.0-flash-vl-l12's widths: the
    512-wide ragged chunk program with the grammar on over one whole period,
    batch 16, the cell's 8,192 blocks, its cache the engine's (the latent
    leaf, the state and BOTH kinds' count leaves carried through the decode
    loop, the count lane ten words wide), and ``jit_cow`` over the full
    depth's latent leaf: a block's 32 pair rows copied in place."""
    import types

    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.models.families import attention_words

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-ling", n_layers=6, **LING)
    assert attention_words(cfg) == 6 + 2 + 2
    compiled = _chunk_program(None, one_chip, cfg, 512, 8192, 512, B=16,
                              engine_cache=True)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30, mem.temp_size_in_bytes

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    whole = ModelConfig(name="aot-ling-whole", n_layers=12, **LING)
    cache = _engine_cache(arg, whole, 8192, 64, 16)
    assert cache.lat.shape == (2, 8192, 32, 1152) and cache.k is None
    cow = BatchedJaxEngine._pool_cow_fn.fget(
        types.SimpleNamespace(kv_pool_page=64, mesh=None))
    scalar = arg((), jnp.int32)
    mem = cow.lower(cache, scalar, scalar, scalar).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= math.prod(cache.lat.shape) * 2
    assert mem.temp_size_in_bytes < 2 ** 24


# --- the gated delta rule with fewer key heads than value heads beside gated
# attention on 256-wide heads and a gated shared expert (ISSUE 55)

QWEN3_NEXT = dict(vocab_size=151936, dim=2048, n_heads=16, n_kv_heads=2,
                  head_dim=256, mlp_hidden=512, dense_mlp_hidden=5120,
                  shared_mlp_hidden=512, shared_expert_gate=True,
                  n_experts=128, router_width=512, experts_per_token=10,
                  eos_ids=(2,), mixers_per_layer=2,
                  layer_pattern="LELELE*E" * 3, lin_key_heads=16,
                  lin_value_heads=32, lin_key_dim=128, lin_value_dim=128,
                  lin_conv=4, rms_offset=1.0, qk_norm=True, rope_theta=1e7,
                  rope_partial=0.25, attn_gate="elementwise")


@pytest.mark.parametrize("layers,B,W,packed", [(12, 16, 1, None),
                                               (4, 16, 512, 528)],
                         ids=["decode-full-depth", "window-512"])
def test_gdn_moe_forward_compiles_at_published_widths_on_v5e(
        one_chip, layers, B, W, packed, monkeypatch):
    """qwen3-next-80b-a3b-instruct-l12 (nine delta-rule layers of 16 key heads
    for 32 value heads, three gated attention layers, twelve expert layers of
    128 held of 512; every width as published, the cell's 257-page table over
    its 6,144-block pool; the decode program at its full depth, a window's at
    one whole period): Mosaic accepts the ragged kernel at 256-wide heads, 8
    query heads a KV head over 2 KV heads a 512-lane row (``lane_heads`` 1),
    and the step kernel at ONE lane tile a head ([128, 2,048] blocks of 16 of
    the 32 value heads, a value head's own column of keys and queries: 32
    columns a block). ``W_q`` is 8,192 columns for 4,096 of queries and there
    is no gate leaf. A decode pass names the state leaf [9, 16, 128, 4096] in
    its nine step kernels alone and moves no plane of it; a window (ISSUE
    56) in a period's three window kernels alone (ops/gated_delta_window.py:
    grid (rows, 2 blocks of 16 heads, 8 chunks of 64), a key head's two
    value heads a pair)."""
    from ai_agent_kubectl_tpu.ops.ragged_attention import lane_heads

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = ModelConfig(name="aot-qwen3-next", n_layers=layers, **QWEN3_NEXT)
    page, n_blocks, pages = 64, 6144, 257
    nL, nA, nE = cfg.n_of("L"), cfg.n_of("*"), cfg.n_of("E")
    assert (nL, nA, nE) == ((9, 3, 12) if layers == 12 else (3, 1, 4))
    assert lane_heads(cfg.head_dim, cfg.kv_heads_paged) == 1

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: arg(x.shape, x.dtype),
        jax.eval_shape(lambda k: random_params_int8(
            k, cfg, dtype=jnp.bfloat16, quantize_embed=True),
            jax.random.PRNGKey(0)))
    assert params["layers"]["wq"].q.shape == (nA, 2048, 16 * 2 * 256)
    assert params["layers"]["lin_in"].q.shape == (nL, 2048, 8192 + 4096)
    assert params["layers"]["shared_expert_gate"].shape == (nE, 2048, 1)
    assert "wg" not in params["layers"]
    cache = _engine_cache(arg, cfg, n_blocks, page, B)
    lin = cache.lin.shape
    assert cache.k.shape == (nA, n_blocks, page, 2, 256)
    assert lin == (nL, B, 128, 4096) and cache.lconv.shape == (nL, B, 3, 8192)
    assert cache.expert_picks.shape == (2,)

    def step(params, tok, pos, cache, wmask, tables, q_lens):
        return forward(params, cfg, tok, pos, cache, kv_limit=pages * page,
                       attn_impl="ragged", token_mask=wmask,
                       write_mask=wmask, block_tables=tables, q_lens=q_lens,
                       logits_at=jnp.maximum(q_lens, 1) - 1,
                       packed_rows=packed)

    traced = jax.jit(step, donate_argnums=(3,)).trace(
        params, arg((B, W), jnp.int32), arg((B, W), jnp.int32), cache,
        arg((B, W), jnp.bool_), arg((B, pages), jnp.int32),
        arg((B,), jnp.int32))
    compiled = traced.lower().compile()
    hlo = compiled.as_text()
    steps = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
             if eqn.params["name"] == "gated_delta_step"]
    windows = [eqn for eqn in _pallas_calls(traced.jaxpr.jaxpr)
               if eqn.params["name"] == "gated_delta_window"]
    assert (len(steps), len(windows)) == ((nL, 0) if W == 1 else (0, nL))
    # ... beside a ragged kernel an attention layer and a grouped one an
    # expert layer
    assert hlo.count('custom_call_target="tpu_custom_call"') == nL + nA + nE
    carried = {"scatter", "fusion", "while", "parameter", "tuple",
               "get-tuple-element", "bitcast"}
    # (float32: W_out's int8 [4096, 2048] has a plane's element count, a
    # layer's W_in [2048, 12288] a three-layer leaf's)
    floats = lambda size: [(op, line) for result, op, line in _instructions(hlo)
                           if op not in carried and size in _sizes(
                               " ".join(re.findall(r"f32\[[\d,]+\]", result)))]
    assert {op for op, _ in floats(math.prod(lin))} <= {"custom-call"}
    assert not floats(math.prod(lin[1:]))
    for eqn in windows:
        assert eqn.params["grid_mapping"].grid == (B, 2, W // 64)
    if W == 1:
        for eqn in steps:
            gm = eqn.params["grid_mapping"]
            assert gm.grid == (B, 2)            # 16 of the 32 heads a block
            shapes = [tuple(b if isinstance(b, int) else b.block_size
                            for b in bm.block_shape)
                      for bm in gm.block_mappings]
            # a value head's key and query a column each; v, alpha and beta;
            # the state
            assert shapes[:3] == [(1, 1, 128, 32), (1, 3, 2048),
                                  (1, 1, 128, 2048)], shapes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= (
        2 * math.prod(cache.k.shape) * 2 + math.prod(lin) * 4)
    assert mem.temp_size_in_bytes < (1.5 * 2 ** 30 if B * W > 512
                                     else 2 ** 28), mem.temp_size_in_bytes
