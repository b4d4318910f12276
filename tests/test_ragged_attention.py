"""One ragged paged-attention kernel for prefill, decode, and spec
verify (ISSUE 19).

The acceptance spine: ``ops/ragged_attention.py`` is the ONE program
the serving loop dispatches over the block pool — per-slot query length
1 = decode, k+1 = spec verify, prompt-span = (suffix) prefill — and
NOTHING about the transcript may show it. Ragged-on equals the legacy
program ladder byte-for-byte at temp 0 AND seeded 0.9, spec k∈{2,4},
single chip and under the tp mesh (tp=2 shards the kernel, tp=8 serves
the LOUD gather fallback — still byte-identical). Around it: the
interpret-mode kernel vs a dense gather reference at mixed query
lengths over shared and dead-clamped block tables, the mixed
admission+decode chunk landing as ONE dispatch with the pool books
balanced, the compiled-program ledger collapsing strictly below the
``(bucket, kv_limit)`` ladder and surviving containment reset + warm
weight swap without a re-trace (the PR 13 id()/_cache_size()
technique), the ``attention_regime`` health/gauge field, and the table
of ``engine/regime.py::resolve_attention_regime``, the one place that
decides which attention serves.

The engine-building tests are slow-marked (each compiles a program set
on the CPU backend); the CI "Ragged-kernel parity smoke" step runs
this file with NO marker filter, so every one still gates every run.
"""

import asyncio
import os
from types import SimpleNamespace

import numpy as np
import pytest

from ai_agent_kubectl_tpu.engine.fake import FakeChunkedEngine
from ai_agent_kubectl_tpu.engine.protocol import RequestQuarantined
from ai_agent_kubectl_tpu.ops.ragged_attention import (
    ragged_attention_pool, ragged_attention_pool_sharded, ragged_supported)
from ai_agent_kubectl_tpu.testing.faults import FaultInjector

PROMPTS = ["list pods", "get nodes -o wide", "describe deployment web"]
TEMPS = [0.0, 0.9, 0.9]
SEEDS = [7, 123, 5]


# ---------------------------------------------------------------- helpers

def _mk(**kw):
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine
    from ai_agent_kubectl_tpu.engine.tokenizer import ByteTokenizer
    from ai_agent_kubectl_tpu.models.config import get_config

    defaults = dict(dtype="float32", max_seq_len=192,
                    prefill_buckets=(32, 64), prefix_cache=False,
                    batch_size=4, chunk_len=4)
    defaults.update(kw)
    return BatchedJaxEngine(get_config("toy-8m"), tokenizer=ByteTokenizer(),
                            **defaults)


def _mk_ragged(**kw):
    return _mk(force_ragged=True, **kw)


def _books(eng) -> None:
    holders: dict = {}
    for slot in list(eng._slots) + list(eng._parked):
        if slot is not None and slot.blocks:
            for b in slot.blocks:
                holders[b] = holders.get(b, 0) + 1
    if eng._radix is not None:
        for b, n in eng._radix._held.items():
            holders[b] = holders.get(b, 0) + n
    eng._pool.check(holders)


async def _serve(eng) -> list:
    outs = await asyncio.gather(*[
        eng.generate(p, max_tokens=16, temperature=t, seed=s)
        for p, t, s in zip(PROMPTS, TEMPS, SEEDS)
    ])
    return [r.text for r in outs]


def _program_total(eng) -> int:
    """Every compiled attention-bearing program the engine owns."""
    return (len(eng._batch_chunk_fns) + len(eng._spec_chunk_fns)
            + len(eng._ragged_chunk_fns) + len(eng._pool_prefill_fns))


# ----------------------------------------------- kernel units (tier-1)
#
# Interpret mode runs the SAME Pallas program the TPU compiles, so the
# reference comparison here is the semantic ground truth for every
# engine-level byte-identity test below.

def _reference(q, k, v, q_lens, positions, tables, page, sel=None):
    """Dense gather reference: per slot, gather kv rows 0..pos+q_len-1
    through the block table, softmax per (query column, head) with the
    causal-in-window rule (column j attends kv <= pos+j), over the keys
    ``sel[n, j]`` names when it is given."""
    N, W, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    out = np.zeros((N, W, H, hd), np.float32)
    scale = hd ** -0.5
    for n in range(N):
        qn = int(q_lens[n])
        if qn == 0:
            continue
        pos = int(positions[n])
        total = pos + qn
        ks = np.stack([k[tables[n, t // page], t % page]
                       for t in range(total)])      # [total, KV, hd]
        vs = np.stack([v[tables[n, t // page], t % page]
                       for t in range(total)])
        for j in range(qn):
            kj = pos + j + 1
            for h in range(H):
                g = h // G
                s = (ks[:kj, g] @ q[n, j, h]) * scale
                if sel is not None:
                    s = np.where(sel[n, j, :kj], s, -np.inf)
                s = s - s.max()
                w = np.exp(s)
                w /= w.sum()
                out[n, j, h] = w @ vs[:kj, g]
    return out


def _mixed_case():
    """Four slots exercising every query shape the serving loop emits,
    over a pool with a SHARED prefix page (block 7), the unmapped-page
    sentinel (99 >= n_blocks), and a NaN-poisoned dead block that must
    never leak into any output."""
    rng = np.random.default_rng(0)
    page, n_blocks, KV, H, hd, W = 8, 12, 2, 4, 16, 8
    k = rng.standard_normal((n_blocks, page, KV, hd)).astype(np.float32)
    v = rng.standard_normal((n_blocks, page, KV, hd)).astype(np.float32)
    k[11] = np.nan          # dead block: nothing live maps it
    v[11] = np.nan
    q = rng.standard_normal((4, W, H, hd)).astype(np.float32)
    #        decode  verify(k+1=5)  prefill-span  frozen
    q_lens = np.array([1, 5, 8, 0], np.int32)
    positions = np.array([19, 11, 0, 19], np.int32)
    tables = np.array([
        [7, 2, 9, 99],      # 20 live tokens -> pages 0..2
        [7, 5, 99, 99],     # shares page-0 block 7 with slot 0
        [0, 99, 99, 99],    # fresh prompt, page 0 only
        [7, 2, 9, 99],      # frozen slot still holds its pages
    ], np.int32)
    return q, k, v, q_lens, positions, tables, page


def test_ragged_kernel_matches_gather_reference_mixed_q_lens():
    """THE kernel unit: one call carrying decode + verify + prefill +
    frozen rows matches the dense gather reference, dead/sentinel pages
    clamp (the NaN block never leaks), and q_len=0 rows are zeros."""
    q, k, v, q_lens, positions, tables, page = _mixed_case()
    out = np.asarray(ragged_attention_pool(
        q, k, v, q_lens, positions, tables, page_size=page))
    assert not np.isnan(out).any(), "dead/NaN pages leaked into outputs"
    ref = _reference(q, k, v, q_lens, positions, tables, page)
    for n, qn in enumerate(q_lens):
        np.testing.assert_allclose(out[n, :qn], ref[n, :qn],
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"slot {n} (q_len={qn})")
    assert np.all(out[3] == 0.0), "frozen slot rows must be zeros"
    # Padded columns past q_len are zeros too (never read, still pinned).
    assert np.all(out[0, 1:] == 0.0)


def test_ragged_kernel_query_tiles_match_reference():
    """Windows wider than one query tile (PR 21: the grid's tile axis
    keeps VMEM use independent of W). At Llama-3-8B head geometry the
    tile is 64 columns, so a 160-wide window is three tiles with the
    last one padded: a full span, a span ending mid-tile behind live
    context (its third tile wholly dead), and a decode row riding in
    tile 0 all match the gather reference, and rows past q_len stay
    zeros across every tile."""
    from ai_agent_kubectl_tpu.ops.ragged_attention import _q_tile

    rng = np.random.default_rng(1)
    page, n_blocks, KV, H, hd, W = 64, 9, 8, 32, 128, 160
    assert _q_tile(W, H, hd) == 64
    k = rng.standard_normal((n_blocks, page, KV, hd)).astype(np.float32)
    v = rng.standard_normal((n_blocks, page, KV, hd)).astype(np.float32)
    k[8] = np.nan           # dead block behind the sentinel clamp
    v[8] = np.nan
    q = rng.standard_normal((3, W, H, hd)).astype(np.float32)
    q_lens = np.array([160, 70, 1], np.int32)
    positions = np.array([0, 30, 100], np.int32)
    tables = np.array([[0, 1, 2], [3, 4, 99], [5, 6, 99]], np.int32)
    out = np.asarray(ragged_attention_pool(
        q, k, v, q_lens, positions, tables, page_size=page))
    assert not np.isnan(out).any(), "dead/NaN pages leaked into outputs"
    ref = _reference(q, k, v, q_lens, positions, tables, page)
    for n, qn in enumerate(q_lens):
        np.testing.assert_allclose(out[n, :qn], ref[n, :qn],
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"slot {n} (q_len={qn})")
        assert np.all(out[n, qn:] == 0.0)


#: Cases for the page-block axis (ISSUE 30) and the live-block stream (ISSUE
#: 32): name -> (H, KV, W, tables' width, [(position, q_len), ...]) at page
#: 64, where the kernel resolves 8 pages a step (so 11 pages are two blocks,
#: the second of three pages) and a ring of 4 buffers. A name ending in
#: "+sel" also hands the kernel a ``sel`` operand; one starting with "tiles:"
#: runs with the query tile cut to 8 columns, so a 24-wide window is three
#: tiles of 256 rows (the form that transposes a block), where every other
#: case here is one narrow tile (the form that reads a block as stored).
_BLOCK_CASES = {
    # the block does not divide the table; spans end in both blocks,
    # one on the table's last page
    "table-not-divided": (4, 2, 4, 11, [(560, 4), (240, 1), (690, 4)]),
    # the last live page is the LAST of block 0 (7), the FIRST of
    # block 1 (8), the first page of all (0) and the table's last (15)
    "block-edges": (4, 2, 1, 16, [(511, 1), (512, 1), (0, 1), (1023, 1)]),
    # every slot frozen but one, which is neither first nor last
    "all-frozen-but-one": (4, 2, 4, 16,
                           [(70, 0), (70, 0), (616, 3), (70, 0)]),
    # Mixtral-8x7B over model:4: a chip holds 8Q/2KV
    "mesh-local-8q-2kv": (8, 2, 6, 11,
                          [(640, 1), (136, 6), (0, 5), (320, 0)]),
    # a slot with more live blocks (6, then 7) than the ring has buffers:
    # every buffer is reused while the cursor is ahead
    "more-blocks-than-ring": (4, 2, 1, 56,
                              [(2900, 1), (70, 1), (3500, 1)]),
    # two live blocks in the whole call: the ring is never full, and the
    # cursor runs off the end of the grid while priming
    "fewer-blocks-than-ring": (4, 2, 1, 16, [(100, 0), (300, 1), (30, 1)]),
    # Keye's heads at decode, three blocks a slot, a frozen slot last
    "32q-4kv-decode": (32, 4, 1, 24, [(1400, 1), (0, 1), (777, 1), (64, 0)]),
    # ... with the selector's mask
    "32q-4kv-decode+sel": (32, 4, 1, 24,
                           [(1400, 1), (0, 1), (777, 1), (64, 0)]),
    # ... and a verify window of 4 (128 rows: the widest narrow tile)
    "32q-4kv-verify+sel": (32, 4, 4, 24, [(1400, 4), (510, 2), (64, 0),
                                          (777, 4)]),
    # frozen slots and tiles past q_len BETWEEN live ones, each live slot
    # with more blocks than the cursor's lead: it has to step over them
    "tiles:dead-between-live": (32, 4, 24, 40, [
        (1500, 24), (70, 0), (900, 3), (70, 0), (2000, 20)]),
    # a window's tiles, then decode rows in the same call
    "tiles:window-then-decode-rows+sel": (32, 4, 24, 40, [
        (1100, 24), (2400, 1), (0, 1), (1999, 1)]),
}

#: ``ragged_attention_pool``'s outputs for ``_BLOCK_CASES`` at the parent
#: of ISSUE 32 (commit 883044c: two buffers, one block ahead, every tile
#: through the transposing form), written by running this file (bottom).
_PARENT_OUTPUTS = os.path.join(os.path.dirname(__file__), "data",
                               "ragged_kernel_outputs_883044c.npz")


def _block_inputs(name):
    """(q, k, v, q_lens, positions, tables, sel or None) of a case, from a
    fixed seed. Every block the tables do not map is NaN."""
    H, KV, W, pages, spans = _BLOCK_CASES[name]
    page, hd, N = 64, 16, len(spans)
    rng = np.random.default_rng(5)
    n_blocks = N * pages + 1
    k = np.full((n_blocks, page, KV, hd), np.nan, np.float32)
    v = np.full_like(k, np.nan)
    tables = np.full((N, pages), n_blocks + 3, np.int32)
    for n, (pos, q_len) in enumerate(spans):
        live = -(-(pos + max(q_len, 1)) // page)
        tables[n, :live] = n * pages + np.arange(live)
        k[tables[n, :live]] = rng.standard_normal((live, page, KV, hd))
        v[tables[n, :live]] = rng.standard_normal((live, page, KV, hd))
    q = rng.standard_normal((N, W, H, hd)).astype(np.float32)
    positions = np.array([s[0] for s in spans], np.int32)
    q_lens = np.array([s[1] for s in spans], np.int32)
    sel = None
    if name.endswith("+sel"):
        # two keys in five, and always a column's own row
        sel = rng.random((N, W, pages * page)) < 0.4
        for n, (pos, _q_len) in enumerate(spans):
            sel[n, np.arange(W), pos + np.arange(W)] = True
    return q, k, v, q_lens, positions, tables, sel


def _run_block_case(ra, name):
    """The case through ``ra.ragged_attention_pool`` (this checkout's
    module, or the parent's when the saved outputs are written)."""
    q, k, v, q_lens, positions, tables, sel = _block_inputs(name)
    elems = ra._Q_TILE_ELEMS
    if name.startswith("tiles:"):
        ra._Q_TILE_ELEMS = 8 * q.shape[2] * q.shape[3]
    try:
        # the undecorated function: the jit's cache does not know the tile
        kw = {} if sel is None else {"sel": sel}
        return np.asarray(ra.ragged_attention_pool.__wrapped__(
            q, k, v, q_lens, positions, tables, page_size=64,
            interpret=True, **kw))
    finally:
        ra._Q_TILE_ELEMS = elems


@pytest.mark.parametrize("name", list(_BLOCK_CASES))
def test_ragged_kernel_page_blocks_match_reference(name):
    """The kernel fetches and attends a block of pages a grid step, its
    live blocks streamed through a ring of buffers by a cursor that runs
    ahead: spans that end anywhere in a block, a table the block does not
    divide, frozen neighbours, more blocks than buffers and fewer, dead
    tiles under the cursor, the selector's mask — all match the gather
    reference. Every block the tables do not map is NaN, so a page copied
    past the live span, a buffer read before its copy lands, or a
    never-copied buffer page meeting probability 0, poisons the output.
    Against the parent's saved outputs: a tile that transposes the block
    does the parent's arithmetic and gives its bits; a narrow tile reads
    the block as stored (sums over the same keys in another order) and
    agrees to float32 rounding."""
    from ai_agent_kubectl_tpu.ops import ragged_attention as ra

    H, KV, W, pages, spans = _BLOCK_CASES[name]
    tq = 8 if name.startswith("tiles:") else W
    shape = (pages, 64, H, KV, 16, tq, 4)
    assert ra.pages_per_step(*shape) == 8
    assert ra.stream_depth(*shape) == 4
    q, k, v, q_lens, positions, tables, sel = _block_inputs(name)
    out = _run_block_case(ra, name)
    assert not np.isnan(out).any(), "an unmapped or uncopied page leaked"
    ref = _reference(q, k, v, q_lens, positions, tables, 64, sel)
    for n, qn in enumerate(q_lens):
        np.testing.assert_allclose(out[n, :qn], ref[n, :qn],
                                   atol=2e-5, rtol=2e-5,
                                   err_msg=f"{name}: slot {n} (q_len={qn})")
        assert np.all(out[n, qn:] == 0.0)
    with np.load(_PARENT_OUTPUTS) as saved:
        parent = saved[name]
    if ra._flat(tq, H, KV):
        np.testing.assert_allclose(out, parent, atol=2e-6, rtol=2e-6)
    else:
        np.testing.assert_array_equal(out, parent)


@pytest.mark.parametrize("shape,pages,depth,flat", [
    # (table pages, page, H, KV, hd, W, itemsize): the four cells' chunk
    # programs, bf16 KV at page 64
    ((65, 64, 32, 8, 128, 1, 2), 8, 3, True),       # Mistral / Mixtral-l6 decode
    ((65, 64, 32, 8, 128, 2, 2), 8, 3, True),       # ... a verify window of 2
    ((65, 64, 32, 8, 128, 4, 2), 8, 4, False),      # ... of 4: 1,024 a key
    ((65, 64, 32, 8, 128, 64, 2), 4, 3, False),     # ... one full query tile
    ((65, 64, 32, 8, 128, 1024, 2), 4, 3, False),   # ... its widest admission
    ((65, 64, 8, 2, 128, 1, 2), 8, 4, True),        # a chip of model:4, decode
    ((65, 64, 8, 2, 128, 512, 2), 4, 4, False),     # ... a 256-column tile
    ((257, 64, 32, 4, 128, 1, 2), 8, 4, True),      # Keye-l8 decode
    ((257, 64, 32, 4, 128, 64, 2), 4, 4, False),    # ... its riding window
    ((257, 64, 32, 4, 128, 512, 2), 4, 4, False),   # ... an eager piece
    ((4, 16, 4, 2, 32, 1, 4), 4, 4, True),          # the toy engine's table
    ((1, 64, 32, 8, 128, 1, 2), 1, 4, True),        # never wider than the table
], ids=lambda x: "-".join(map(str, x)) if isinstance(x, tuple) else None)
def test_pages_per_step_and_stream_depth_from_shapes(shape, pages, depth,
                                                     flat):
    """What a call resolves from its shapes, and nothing else decides: the
    pages a grid step attends, the buffers its live blocks stream through
    (2 .. 4: what ``_KV_VMEM_BYTES`` holds beside the score tiles), and
    which form a tile's rows take. Within the budget every time."""
    from ai_agent_kubectl_tpu.ops import ragged_attention as ra

    n_pages, page, H, KV, hd, W, itemsize = shape
    assert ra.pages_per_step(*shape) == pages
    assert ra.stream_depth(*shape) == depth
    tq = ra._q_tile(W, H, hd)
    assert ra._flat(tq, H, KV) is flat
    ring = depth * 2 * pages * page * KV * hd * itemsize
    scores = pages * ra._score_bytes_per_page(page, H, KV, tq)
    assert ring + scores <= ra._KV_VMEM_BYTES


def test_ragged_kernel_decode_column_equals_own_window():
    """Window invariance: the LAST column of a 5-wide verify window over
    positions p..p+4 equals a 1-wide decode call at position p+4 — the
    property that lets spec verify and decode share one program."""
    q, k, v, _q_lens, _pos, tables, page = _mixed_case()
    wide = np.asarray(ragged_attention_pool(
        q, k, v, np.array([5, 5, 5, 5], np.int32),
        np.array([11, 11, 11, 11], np.int32), tables, page_size=page))
    narrow_q = np.zeros_like(q)
    narrow_q[:, 0] = q[:, 4]
    narrow = np.asarray(ragged_attention_pool(
        narrow_q, k, v, np.array([1, 1, 1, 1], np.int32),
        np.array([15, 15, 15, 15], np.int32), tables, page_size=page))
    np.testing.assert_allclose(wide[:, 4], narrow[:, 0],
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layer", [0, 2, 4], ids=["first", "middle", "last"])
@pytest.mark.parametrize("W", [1, 4, 24], ids=["decode", "verify", "prefill"])
def test_ragged_kernel_stacked_pool_layer_index_equals_layer_slice(W, layer):
    """ISSUE 25: the kernel reads a layer of the STACKED pool through its
    index map. The 5-D pool + layer-index call equals the 4-D call on
    ``pool[layer]`` bit for bit — the layer scan hands the kernel the
    carried pool whole, so no ``pool[layer]`` copy stands in front of
    it. The prefill width is three query tiles at this head geometry;
    every other layer is NaN, so a wrong layer cannot pass."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    L, page, n_blocks, KV, H, hd, N = 5, 8, 12, 2, 4, 16, 3
    pool_k = np.full((L, n_blocks, page, KV, hd), np.nan, np.float32)
    pool_v = np.full_like(pool_k, np.nan)
    pool_k[layer] = rng.standard_normal(pool_k.shape[1:])
    pool_v[layer] = rng.standard_normal(pool_v.shape[1:])
    q = rng.standard_normal((N, W, H, hd)).astype(np.float32)
    q_lens = np.array([W, max(W - 1, 1), 0], np.int32)
    positions = np.array([19, 3, 7], np.int32)
    tables = np.array([[7, 2, 9, 4, 1, 99], [7, 5, 3, 0, 99, 99],
                       [6, 8, 99, 99, 99, 99]], np.int32)
    stacked = np.asarray(ragged_attention_pool(
        q, pool_k, pool_v, q_lens, positions, tables,
        jnp.int32(layer), page_size=page))
    sliced = np.asarray(ragged_attention_pool(
        q, pool_k[layer], pool_v[layer], q_lens, positions, tables,
        page_size=page))
    assert not np.isnan(stacked).any(), "another layer's rows leaked"
    np.testing.assert_array_equal(stacked, sliced)
    with pytest.raises(ValueError, match="takes a layer index"):
        ragged_attention_pool(q, pool_k, pool_v, q_lens, positions,
                              tables, page_size=page)


def test_ragged_kernel_sharded_parity_and_head_divisibility():
    """tp=2 divides KV=2/H=4: the shard_mapped kernel is bitwise the
    single-device call. tp=8 does not: a LOUD ValueError (engine
    startup resolves such meshes to the gather path before ever
    reaching the kernel)."""
    import jax
    from jax.sharding import Mesh

    q, k, v, q_lens, positions, tables, page = _mixed_case()
    base = np.asarray(ragged_attention_pool(
        q, k, v, q_lens, positions, tables, page_size=page))
    devs = np.array(jax.devices()[:2]).reshape(1, 2)
    mesh2 = Mesh(devs, ("data", "model"))
    sharded = np.asarray(ragged_attention_pool_sharded(
        q, k, v, q_lens, positions, tables, mesh2, page_size=page))
    np.testing.assert_allclose(sharded, base, atol=2e-5, rtol=2e-5)
    # The stacked pool passes its layer axis unsharded (ISSUE 25).
    stacked = np.asarray(ragged_attention_pool_sharded(
        q, np.stack([np.zeros_like(k), k]), np.stack([np.zeros_like(v), v]),
        q_lens, positions, tables, mesh2, 1, page_size=page))
    np.testing.assert_array_equal(stacked, sharded)

    devs8 = np.array(jax.devices()[:8]).reshape(1, 8)
    mesh8 = Mesh(devs8, ("data", "model"))
    with pytest.raises(ValueError, match="divisible by the model axis"):
        ragged_attention_pool_sharded(q, k, v, q_lens, positions,
                                      tables, mesh8, page_size=page)


# The single-query decode read (q_len = 1 on every slot) against
# ops/attention.py::dense_attention — what the deleted single-query
# paged kernel's file pinned, as cases of the one kernel that stays.

def _decode_case(N, S, H, KV, hd, page, seed, dtype=np.float32):
    """Per-slot contiguous caches [N, S, KV, hd] laid into a pool as
    consecutive blocks, with the identity table; q is one column."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (N, 1, H, hd), dtype)
    k = jax.random.normal(ks[1], (N, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (N, S, KV, hd), dtype)
    tables = jnp.arange(N * S // page, dtype=jnp.int32).reshape(N, -1)
    blocks = (N * S // page, page, KV, hd)
    return q, k, v, k.reshape(blocks), v.reshape(blocks), tables


def _dense_decode_ref(q, k, v, positions):
    """dense_attention over each slot's whole cache, decode causal
    mask, in float32."""
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.ops.attention import dense_attention

    mask = jnp.arange(k.shape[1])[None, None, :] <= positions[:, None, None]
    return np.asarray(dense_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), mask))


def _decode(q, kp, vp, positions, tables, page):
    import jax.numpy as jnp

    return np.asarray(ragged_attention_pool(
        q, kp, vp, jnp.ones((q.shape[0],), jnp.int32), positions, tables,
        page_size=page).astype(jnp.float32))


@pytest.mark.parametrize("kv_heads", [1, 2], ids=["mqa", "gqa"])
def test_ragged_decode_matches_dense_at_differing_positions(kv_heads):
    """Per-slot positions that differ, on the page-boundary edges: a
    single live token, exactly page-1, exactly page, mid-cache."""
    import jax.numpy as jnp

    q, k, v, kp, vp, tables = _decode_case(4, 128, 4, kv_heads, 64, 16, 0)
    positions = jnp.asarray([0, 15, 16, 77], jnp.int32)
    np.testing.assert_allclose(
        _decode(q, kp, vp, positions, tables, 16),
        _dense_decode_ref(q, k, v, positions), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_heads", [1, 2], ids=["mqa", "gqa"])
def test_ragged_decode_shared_and_sentinel_blocks_match_dense(kv_heads):
    """Slots read scattered pool blocks by table indirection; a block
    shared by two tables (radix sharing) and sentinel entries past the
    live span must not change the math vs dense attention over the
    gathered per-slot view."""
    import jax
    import jax.numpy as jnp

    N, n_blocks, page, H, hd = 3, 10, 16, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (N, 1, H, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (n_blocks, page, kv_heads, hd))
    vp = jax.random.normal(ks[2], (n_blocks, page, kv_heads, hd))
    tables = jnp.asarray([[7, 2, 9, 10], [7, 5, 10, 10], [0, 1, 3, 4]],
                         jnp.int32)
    positions = jnp.asarray([40, 17, 63], jnp.int32)
    idx = jnp.clip(tables, 0, n_blocks - 1)
    kg = kp[idx].reshape(N, 4 * page, kv_heads, hd)
    vg = vp[idx].reshape(N, 4 * page, kv_heads, hd)
    np.testing.assert_allclose(
        _decode(q, kp, vp, positions, tables, page),
        _dense_decode_ref(q, kg, vg, positions), rtol=2e-5, atol=2e-5)


def test_ragged_decode_full_table_last_row_of_last_page():
    """A full table: one slot's row is the last row of the last page,
    another's the first row of the last page."""
    import jax.numpy as jnp

    S, page = 64, 16
    q, k, v, kp, vp, tables = _decode_case(2, S, 4, 2, 64, page, 1)
    positions = jnp.asarray([S - 1, S - page], jnp.int32)
    np.testing.assert_allclose(
        _decode(q, kp, vp, positions, tables, page),
        _dense_decode_ref(q, k, v, positions), rtol=2e-5, atol=2e-5)


def test_ragged_decode_bf16_inputs():
    import jax.numpy as jnp

    q, k, v, kp, vp, tables = _decode_case(2, 64, 4, 1, 128, 16, 2,
                                           dtype=jnp.bfloat16)
    positions = jnp.asarray([33, 5], jnp.int32)
    np.testing.assert_allclose(
        _decode(q, kp, vp, positions, tables, 16),
        _dense_decode_ref(q, k, v, positions), rtol=2e-2, atol=2e-2)


def test_pool_forward_refuses_kv_limit_that_is_not_whole_pages():
    """The pool read is page-granular: ``forward`` refuses a kv_limit
    that is not a whole page count, and the kernel a pool whose page is
    not the one it was told."""
    import jax
    import jax.numpy as jnp

    from ai_agent_kubectl_tpu.models.config import get_config
    from ai_agent_kubectl_tpu.models.transformer import (KVCache, forward,
                                                         init_params)

    cfg = get_config("toy-8m")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    page, n_blocks = 16, 4
    leaf = jnp.zeros((cfg.n_layers, n_blocks, page, cfg.n_kv_heads,
                      cfg.head_dim), jnp.float32)
    cache = KVCache(k=leaf, v=leaf, lengths=jnp.zeros((n_blocks,),
                                                      jnp.int32))
    tok = jnp.zeros((1, 1), jnp.int32)
    tables = jnp.arange(n_blocks, dtype=jnp.int32)[None]
    with pytest.raises(ValueError, match="not a multiple of page"):
        forward(params, cfg, tok, tok, cache, kv_limit=60,
                attn_impl="ragged", block_tables=tables)
    q = jnp.zeros((1, 1, cfg.n_heads, cfg.head_dim), jnp.float32)
    with pytest.raises(ValueError, match="pool page 16 != page_size 8"):
        ragged_attention_pool(q, leaf[0], leaf[0], jnp.ones((1,), jnp.int32),
                              jnp.zeros((1,), jnp.int32), tables,
                              page_size=8)


def test_ragged_supported_gate():
    """Compiled-kernel tiling constraints (interpret mode skips them —
    the CPU tests above run hd=16 on purpose)."""
    assert ragged_supported(page_size=128, head_dim=256, n_pages=4)
    assert ragged_supported(page_size=8, head_dim=128, n_pages=1)
    assert not ragged_supported(page_size=128, head_dim=64, n_pages=4)
    assert not ragged_supported(page_size=4, head_dim=128, n_pages=4)
    assert not ragged_supported(page_size=128, head_dim=128, n_pages=0)


# ------------------------------------------- regime table + fake (tier-1)

# The three numbers the decision reads from a model config.
_MISTRAL = SimpleNamespace(n_heads=32, n_kv_heads=8, head_dim=128)
# a head_dim the compiled kernel refuses
_TOY = SimpleNamespace(n_heads=4, n_kv_heads=2, head_dim=32)
# heads that model:2 does not divide
_ODD = SimpleNamespace(n_heads=6, n_kv_heads=3, head_dim=128)
_ON_TPU = dict(backend="tpu", mesh_shape=None, kv_quant="", kv_pool=True,
               device_termination=True, pool_page=64)


@pytest.mark.parametrize("cfg,over,regime,page,reason", [
    (_MISTRAL, {}, "ragged", 64, "TPU backend"),
    (_MISTRAL, {"mesh_shape": {"data": 1, "model": 4}}, "ragged", 64,
     "TPU backend"),
    (_MISTRAL, {"pool_page": 16}, "ragged", 64, "TPU backend"),
    (_MISTRAL, {"pool_page": 128}, "ragged", 128, "TPU backend"),
    (_MISTRAL, {"kv_quant": "int8"}, "gather", 64, "KV_QUANT=int8"),
    (_MISTRAL, {"device_termination": False}, "gather", 64,
     "DEVICE_TERMINATION=false"),
    (_ODD, {"mesh_shape": {"model": 2}}, "gather", 64,
     "do not divide the model axis (2)"),
    (_TOY, {}, "gather", 64, "does not support page=64 head_dim=32"),
    (_MISTRAL, {"backend": "cpu", "pool_page": 16}, "gather", 16,
     "backend cpu is not a TPU"),
    (_TOY, {"backend": "cpu", "pool_page": 16, "force_ragged": True},
     "ragged", 16, "force_ragged"),
    (_TOY, {"backend": "cpu", "force_ragged": True, "kv_quant": "int8"},
     "gather", 64, "KV_QUANT=int8"),
    (None, {"backend": "fake", "pool_page": 16}, "gather", 16,
     "backend fake is not a TPU"),
    (None, {"backend": "fake", "pool_page": 16, "force_ragged": True},
     "ragged", 16, "force_ragged"),
    (_MISTRAL, {"kv_pool": False, "pool_page": 16}, "dense", 16,
     "KV_POOL=false"),
    (_MISTRAL, {"mesh_shape": {"data": 2, "model": 2}, "pool_page": 16},
     "dense", 16, "data mesh axis"),
    (_MISTRAL, {"mesh_shape": {"pipe": 2, "seq": 2}, "force_ragged": True},
     "dense", 64, "pipe/seq mesh axis"),
], ids=["tpu", "tpu-tp4", "tpu-page-floor", "tpu-page-128", "int8-kv",
        "host-termination", "heads-not-dividing", "page-or-head-dim",
        "cpu", "cpu-forced", "cpu-forced-int8", "fake", "fake-forced",
        "pool-off", "data-mesh", "pipe-seq-mesh"])
def test_attention_regime_table(cfg, over, regime, page, reason):
    """One row per branch of the decision: what the engine can observe
    in, (regime, pool page, reason) out. ``force_ragged`` stands in for
    the TPU alone — int8 KV and the mesh still decide first — and the
    page floor applies on a TPU only."""
    from ai_agent_kubectl_tpu.engine.regime import resolve_attention_regime

    got = resolve_attention_regime(cfg, **{**_ON_TPU, **over})
    assert got[:2] == (regime, page)
    assert reason in got[2], got[2]


def test_no_setting_reaches_force_ragged(monkeypatch):
    """``force_ragged`` is a constructor argument for tests: no field
    of ServiceConfig names it, and an engine built from the environment
    never has it, whatever the environment says."""
    import dataclasses

    from ai_agent_kubectl_tpu.config import ServiceConfig
    from ai_agent_kubectl_tpu.engine.batcher import BatchedJaxEngine

    monkeypatch.setenv("MODEL_NAME", "toy-8m")
    monkeypatch.setenv("FORCE_RAGGED", "true")
    cfg = ServiceConfig.from_env(env_file=None)
    assert not [f.name for f in dataclasses.fields(cfg)
                if "ragged" in f.name]
    assert BatchedJaxEngine.from_config(cfg).force_ragged is False


async def test_fake_ragged_parity_and_regime():
    """The fake mirror: ragged transcripts equal gather's byte for byte
    (the admission restructure, not the kernel, is what the fake
    models), and regime and reason come from the function the batcher
    calls."""
    on = FakeChunkedEngine(batch_size=4, chunk_len=4, force_ragged=True)
    off = FakeChunkedEngine(batch_size=4, chunk_len=4)
    await on.start()
    await off.start()
    try:
        assert on._use_ragged and not off._use_ragged
        assert on.kv_pool_health()["attention_regime"] == "ragged"
        health = off.kv_pool_health()
        assert health["attention_regime"] == "gather"
        assert "not a TPU" in health["attention_regime_reason"]
        dense = FakeChunkedEngine(batch_size=4, chunk_len=4,
                                  kv_pool=False, force_ragged=True)
        assert dense._attention_regime == "dense"
        for prompt, temp, seed in zip(PROMPTS, TEMPS, SEEDS):
            a = await on.generate(prompt, max_tokens=12,
                                  temperature=temp, seed=seed)
            b = await off.generate(prompt, max_tokens=12,
                                   temperature=temp, seed=seed)
            assert a.text == b.text, (prompt, temp)
    finally:
        await on.stop()
        await off.stop()


# --------------------------------------------- jax engine (CI step; slow)

@pytest.mark.slow
async def test_jax_ragged_vs_ladder_byte_identity_one_dispatch():
    """THE acceptance test: ragged-on vs the legacy program ladder on
    identical concurrent traffic — byte-identical at temp 0 and seeded
    0.9, the mixed admission+decode chunk lands as ONE dispatch (a
    chunk-log entry carries admissions>0 AND already-decoding slots),
    health/regime fields report, and the pool books balance after."""
    ragged = _mk_ragged()
    ladder = _mk()
    await ragged.start()
    ladder.tokenizer = ragged.tokenizer
    await ladder.start()
    try:
        assert ragged._use_ragged and not ladder._use_ragged
        # Single-chip deployments read the regime from kv_pool_health
        # (sharding_health is None without a mesh).
        assert ragged.kv_pool_health()["attention_regime"] == "ragged"
        assert ladder.kv_pool_health()["attention_regime"] == "gather"
        # Stagger a second wave so admissions stage into chunks that
        # already carry decoding slots.
        async def wave(eng):
            first = asyncio.gather(*[
                eng.generate(p, max_tokens=16, temperature=t, seed=s)
                for p, t, s in zip(PROMPTS, TEMPS, SEEDS)])
            await asyncio.sleep(0.05)
            second = eng.generate("rollout status web", max_tokens=16,
                                  temperature=0.9, seed=99)
            r1, r2 = await asyncio.gather(first, second)
            return [r.text for r in r1] + [r2.text]

        got = await wave(ragged)
        want = await wave(ladder)
        assert got == want
        mixed = [e for e in ragged._chunk_log
                 if e.get("event") == "dispatch"
                 and e.get("admissions", 0) > 0 and e.get("slots", 0) > 1]
        assert mixed, "no chunk carried admissions alongside decoders"
        _books(ragged)
        _books(ladder)
    finally:
        await asyncio.gather(ragged.stop(), ladder.stop())


@pytest.mark.slow
@pytest.mark.parametrize("k", [2, 4])
async def test_jax_ragged_spec_byte_identity(k):
    """Spec verify rides the ragged chunk: spec-on under ragged equals
    spec-off under ragged byte-for-byte (identical-draft => every token
    accepted => the verify window is pure pipelining), and the spec
    ragged programs exist as their own (width, spec=True) keys."""
    plain = _mk_ragged()
    spec = _mk_ragged(spec_decode=True, spec_draft_k=k,
                      spec_draft_model="toy-8m", spec_draft_seed=1234)
    await plain.start()
    spec.tokenizer = plain.tokenizer
    await spec.start()
    try:
        assert spec._use_spec and spec._use_ragged
        assert any(s for (_w, s) in spec._ragged_chunk_fns)
        ref = await _serve(plain)
        got = await _serve(spec)
        assert got == ref, f"spec k={k} diverged under ragged"
        _books(spec)
    finally:
        await asyncio.gather(plain.stop(), spec.stop())


@pytest.mark.slow
async def test_jax_ragged_tp_parity_and_gather_fallback():
    """tp=2 shards the ragged kernel (toy KV=2/H=4 divide), tp=8 can't
    — the engine resolves to the LOUD gather fallback — and neither may
    change a byte of the transcript vs single-chip ragged."""
    single = _mk_ragged()
    await single.start()
    engines = [single]
    try:
        ref = await _serve(single)
        for mesh, want_regime in (("tp=2", "ragged"), ("tp=8", "gather")):
            eng = _mk_ragged(mesh_shape=mesh)
            eng.tokenizer = single.tokenizer
            await eng.start()
            engines.append(eng)
            assert eng.sharding_health()["attention_regime"] \
                == want_regime, mesh
            assert eng._use_ragged is (want_regime == "ragged")
            got = await _serve(eng)
            assert got == ref, (mesh, want_regime)
            _books(eng)
    finally:
        await asyncio.gather(*[e.stop() for e in engines])


@pytest.mark.slow
async def test_jax_ragged_program_collapse_and_warm_swap():
    """The perf clause: ragged's compiled-program set is CLOSED at
    warmup (serving adds no keys, no fn re-traces) and strictly below
    the legacy ``(bucket, kv_limit)`` ladder — both its defined size
    and its lazily-grown compiled total after identical multi-rung
    traffic. A warm weight swap keeps every ragged program object and
    its trace cache (PR 13's id()/_cache_size() technique)."""
    ragged = _mk_ragged()
    ladder = _mk()
    await ragged.start()
    ladder.tokenizer = ragged.tokenizer
    await ladder.start()
    try:
        # Warmup ledger: one chunk fn (no kv ladder under ragged), one
        # ragged program per admission width, prefill pinned at the
        # single S_alloc kv rung (warmup warms the smallest bucket;
        # the rest fill in lazily but the RUNG axis never grows).
        S = ragged._S_alloc
        assert ragged._kv_buckets == (S,)
        assert set(ragged._ragged_chunk_fns) == {(32, False), (64, False)}
        assert set(ragged._pool_prefill_fns) == {(32, S)}
        ladder_defined = (len(ladder.prefill_buckets)
                          * len(ladder._pool_prefill_kv_buckets)
                          + len(ladder._kv_buckets))
        # The ragged set's CEILING: every chunk/ragged program plus one
        # prefill per bucket — still strictly under the ladder's zoo.
        ragged_ceiling = (len(ragged._batch_chunk_fns)
                          + len(ragged._ragged_chunk_fns)
                          + len(ragged.prefill_buckets))
        assert ragged_ceiling < ladder_defined, (ragged_ceiling,
                                                 ladder_defined)

        fn_sets = lambda eng: {  # noqa: E731
            "chunk": dict(eng._batch_chunk_fns),
            "ragged": dict(eng._ragged_chunk_fns),
            "prefill": dict(eng._pool_prefill_fns)}
        snap = lambda eng: {  # noqa: E731
            grp: {key: (id(f), f._cache_size())
                  for key, f in fns.items()}
            for grp, fns in fn_sets(eng).items()}
        warm = snap(ragged)

        # Multi-rung traffic: prompts landing in both buckets at both
        # legacy kv rungs (a >128-token prompt's tail chunk prefills at
        # the 192 rung) — the ladder engine must lazily grow its
        # (bucket, kv_limit) zoo; the ragged engine adds at most the
        # second bucket's prefill, pinned at the same single rung.
        prompts = ["list pods",                          # (32, 128)
                   "describe the deployment named web",  # (64, 128)
                   "x" * 150,                            # tail (32, 192)
                   "y" * 180]                            # tail (64, 192)
        for eng in (ragged, ladder):
            for p in prompts:
                await eng.generate(p, max_tokens=8, temperature=0.0)
        after = snap(ragged)
        assert after["chunk"] == warm["chunk"], "chunk fn re-traced"
        assert after["ragged"] == warm["ragged"], \
            "serving re-traced or grew the ragged program set"
        assert set(ragged._pool_prefill_fns) == {(32, S), (64, S)}
        assert all(f._cache_size() == 1
                   for f in ragged._pool_prefill_fns.values())
        steady_total = _program_total(ragged)
        assert steady_total == ragged_ceiling
        grown = _program_total(ladder)
        assert len(ladder._pool_prefill_fns) \
            > len(ladder.prefill_buckets), dict(ladder._pool_prefill_fns)
        assert steady_total < grown, (steady_total, grown)
        warm = after

        # Warm swap: different weights, same programs, same trace
        # caches — byte streams change, the ledger does not.
        t1 = (await ragged.generate("get pods", max_tokens=8)).text
        await ragged.stop()
        ragged.swap_weights("/tmp/ragged-dev-ckpt-v2")
        await ragged.start()
        assert snap(ragged) == warm, "the swap re-traced a program"
        t2 = (await ragged.generate("get pods", max_tokens=8)).text
        assert t2 != t1, "weights did not actually swap"
        assert snap(ragged) == warm
    finally:
        await asyncio.gather(ragged.stop(), ladder.stop())


@pytest.mark.slow
async def test_jax_ragged_containment_reset_keeps_programs_warm():
    """decode:nan mid-batch under ragged: the poisoned request 410s,
    bystanders replay byte-identically through the SAME ragged programs
    (containment reset must not re-trace), and the books balance."""
    base = _mk_ragged()
    await base.start()
    prompts = ["poison target x", "bystander a", "bystander b"]
    want = {}
    for p in prompts[1:]:
        want[p] = (await base.generate(p, max_tokens=8,
                                       temperature=0.0)).text
    await base.stop()

    inj = FaultInjector()
    inj.set("decode", "nan")
    inj.target_substr = "poison target"
    eng = _mk_ragged(faults=inj)
    await eng.start()
    try:
        warm = {key: (id(f), f._cache_size())
                for key, f in eng._ragged_chunk_fns.items()}
        results = await asyncio.gather(
            *[eng.generate(p, max_tokens=8, temperature=0.0)
              for p in prompts],
            return_exceptions=True)
        assert isinstance(results[0], RequestQuarantined)
        for p, r in zip(prompts[1:], results[1:]):
            assert r.text == want[p], f"victim {p!r} transcript changed"
        assert {key: (id(f), f._cache_size())
                for key, f in eng._ragged_chunk_fns.items()} == warm, \
            "containment reset re-traced the ragged programs"
        _books(eng)
    finally:
        await eng.stop()


if __name__ == "__main__":
    # PYTHONPATH=<parent checkout> python tests/test_ragged_attention.py \
    #     <parent checkout>
    # writes _PARENT_OUTPUTS from THAT checkout's kernel.
    import sys

    sys.path.insert(0, os.path.abspath(sys.argv[1]))
    from ai_agent_kubectl_tpu.ops import ragged_attention as _parent_ra

    assert os.path.abspath(sys.argv[1]) in _parent_ra.__file__
    os.makedirs(os.path.dirname(_PARENT_OUTPUTS), exist_ok=True)
    np.savez_compressed(_PARENT_OUTPUTS, **{
        name: _run_block_case(_parent_ra, name) for name in _BLOCK_CASES})
