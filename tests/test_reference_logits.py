"""The program against the plain reference, logit for logit, on the CPU at toy
size (ROADMAP D4: until PR 27 only the chip runs compared them).

The reference is the new four-chip configuration's own file,
``benchmark/configs/mixtral-8x7b-instruct-v0.1.reference.py``, loaded by path
as ``benchmark/refcheck.py`` loads it; the program side is the serving path:
``models/transformer.py::forward`` through the block-paged pool and the ragged
kernel (interpreted here), one ragged prefill window over two prompts of
unequal length, then decode steps through the cache; on one device and over a
``model:4`` mesh with params and pool placed by the program's own sharding
policy (so ``dense_moe``'s reduce over the mesh is in it).
"""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.transformer import KVCache, forward
from ai_agent_kubectl_tpu.ops.quant import random_params_int8

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "benchmark"))

import refcheck  # noqa: E402  (benchmark/refcheck.py: weights for the reference, placement)

#: toy-moe with 4 KV heads and an expert width that 4 divides, so that
#: ``model:4`` splits heads, expert width and vocabulary as it does Mixtral's.
CFG = dataclasses.replace(get_config("toy-moe"), name="toy-moe-kv4", dim=128,
                          n_heads=4, n_kv_heads=4, head_dim=32, mlp_hidden=256)
#: the reference's view of the same sizes, under the source's names
SIZES = {"num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 32,
         "rope_theta": CFG.rope_theta, "rms_norm_eps": CFG.rms_eps,
         "num_local_experts": CFG.n_experts, "num_experts_per_tok": 2}
LENS, W, STEPS, PAGE = (100, 61), 128, 3, 64

#: Worst |logit - reference| over the reference logits' standard deviation.
#: Program and reference both compute in float32 here (activations follow the
#: float32 norms and router; the int8 weights are exact in either), so what
#: is left is summation order: 2.9e-6 on one device, 2.5e-6 over the mesh
#: (seed 27). A reference whose float32 weights are rounded to bf16 reads
#: 1.4e-2, and one that mixes one expert instead of two reads 0.52 (both
#: asserted below), so 1e-4 stands 35 times above a sound program and 140
#: times below the smaller fault.
TOLERANCE = 1e-4


def load_reference(name="mixtral-8x7b-instruct-v0.1"):
    spec = importlib.util.spec_from_file_location(
        "plain_reference",
        ROOT / f"benchmark/configs/{name}.reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_logits(params, axes, CFG=CFG, LENS=LENS, PAGE=PAGE):
    """Every position's logits of the two sequences, as refcheck.run takes
    them: the window, then STEPS single-token steps through the pool."""
    B = len(LENS)
    rng = np.random.default_rng(5)
    toks = rng.integers(3, CFG.vocab_size, size=(B, max(LENS) + STEPS),
                        dtype=np.int32)
    pages = -(-(W + STEPS) // PAGE)
    pool = (CFG.n_layers, B * pages, PAGE, CFG.n_kv_heads, CFG.head_dim)
    cache = KVCache(k=jnp.zeros(pool, jnp.float32), v=jnp.zeros(pool, jnp.float32),
                    lengths=jnp.zeros((B * pages,), jnp.int32))
    tables = jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    mesh = None
    if axes:
        mesh, params, cache, tables = refcheck.place_on_mesh(
            axes, CFG, params, cache, tables)

    @jax.jit
    def step(params, tok, pos, cache, wmask, q_lens):
        return forward(params, CFG, tok, pos, cache, kv_limit=pages * PAGE,
                       attn_impl="ragged", mesh=mesh, token_mask=wmask,
                       write_mask=wmask, page_size=PAGE, block_tables=tables,
                       q_lens=q_lens)

    cols = np.arange(W)[None, :]
    q_lens = np.asarray(LENS, np.int32)
    win = np.zeros((B, W), np.int32)
    for b, n in enumerate(LENS):
        win[b, :n] = toks[b, :n]
    logits, cache = step(params, jnp.asarray(win),
                         jnp.asarray(np.broadcast_to(cols, (B, W)).astype(np.int32)),
                         cache, jnp.asarray(cols < q_lens[:, None]), jnp.asarray(q_lens))
    got = [[np.asarray(logits[b, :n])] for b, n in enumerate(LENS)]
    for s in range(STEPS):
        tok = np.stack([toks[b, n + s] for b, n in enumerate(LENS)])[:, None]
        logits, cache = step(params, jnp.asarray(tok),
                             jnp.asarray((q_lens + s)[:, None].astype(np.int32)), cache,
                             jnp.ones((B, 1), bool), jnp.ones((B,), jnp.int32))
        for b in range(B):
            got[b].append(np.asarray(logits[b, :1]))
    return toks, [np.concatenate(g, axis=0) for g in got]


def worst_rel_err(ref, sizes, weights, toks, got, LENS=LENS):
    worst, stds = 0.0, []
    for b, n in enumerate(LENS):
        want, _ = ref.forward(sizes, weights, jnp.asarray(toks[b]))
        want = np.asarray(want)[:n + STEPS]
        worst = max(worst, float(np.abs(got[b] - want).max()))
        stds.append(float(want.std()))
    return worst / float(np.mean(stds))


@pytest.fixture(scope="module")
def seeded():
    params = random_params_int8(jax.random.PRNGKey(27), CFG, dtype=jnp.float32,
                                quantize_embed=True)
    return params, refcheck.reference_weights(params, CFG.n_layers)


@pytest.mark.parametrize("axes", [{}, {"model": 4}], ids=["one_device", "model4"])
def test_program_logits_match_the_plain_reference(seeded, axes):
    params, weights = seeded
    ref = load_reference()
    toks, got = program_logits(params, axes)
    assert worst_rel_err(ref, SIZES, weights, toks, got) < TOLERANCE


def test_the_tolerance_tells_a_fault_from_rounding(seeded):
    """What TOLERANCE must refuse: bf16 standing in for float32 inside the
    reference, and a mixture that drops the second expert."""
    params, weights = seeded
    ref = load_reference()
    toks, got = program_logits(params, {})
    rounded = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), weights)
    assert worst_rel_err(ref, SIZES, rounded, toks, got) > 100 * TOLERANCE
    top1 = dict(SIZES, num_experts_per_tok=1)
    assert worst_rel_err(ref, top1, weights, toks, got) > 1000 * TOLERANCE


def test_the_selecting_configuration_matches_its_plain_reference():
    """keye-vl-2.0-30b-a3b-l8's reference (QK-norm, the lightning indexer and
    top-k key selection, 16 experts top-2 here through the grouped expert GEMM)
    against ``toy-sparse-moe`` through the pool, the index-key leaf made by
    ``forward`` as on the chip: 100 and 61 tokens, both past index_topk = 48,
    so window rows and decode rows select. What the tolerance must refuse:
    every key attended, and the selector keeping one key too few."""
    cfg = get_config("toy-sparse-moe")
    sizes = {"num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
             "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
             "rms_norm_eps": cfg.rms_eps, "num_experts_per_tok": cfg.experts_per_token,
             "indexer_num_heads": cfg.index_heads, "indexer_head_dim": cfg.index_head_dim,
             "topk": cfg.index_topk, "q_chunk_size": 64}
    params = random_params_int8(jax.random.PRNGKey(31), cfg, dtype=jnp.float32,
                                quantize_embed=True)
    ref = load_reference("keye-vl-2.0-30b-a3b-l8")
    weights = refcheck.weights_function(ref)(params, cfg.n_layers)
    toks, got = program_logits(params, {}, CFG=cfg, PAGE=16)
    assert worst_rel_err(ref, sizes, weights, toks, got) < TOLERANCE
    assert worst_rel_err(ref, dict(sizes, topk=10 ** 6), weights, toks, got) > 1000 * TOLERANCE
    assert worst_rel_err(ref, dict(sizes, topk=47), weights, toks, got) > 10 * TOLERANCE
