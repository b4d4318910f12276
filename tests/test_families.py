"""One description a cache kind (ISSUE 44; models/families.py): what a
configuration's cache holds, counts, refuses and reports is said there,
and the scheduler, the wire and the HTTP layer read it.

The frozen lists below were taken from the parent commit's
``engine/batcher.py`` (``_new_pool_cache``, the three refusal lists, the
five ``*_health`` methods) before those went: the same leaves, the same
messages, the same /health keys.
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from ai_agent_kubectl_tpu.engine.kv_pool import CacheCounters, StateStore
from ai_agent_kubectl_tpu.engine.protocol import ChunkResult
from ai_agent_kubectl_tpu.engine.regime import cache_refusal
from ai_agent_kubectl_tpu.models.config import get_config
from ai_agent_kubectl_tpu.models.families import (CACHE_KINDS, OBSTACLES,
                                                  SECTIONS, attention_words,
                                                  kinds_of, long_prompts)
from ai_agent_kubectl_tpu.models.transformer import KVCache

REPO = Path(__file__).resolve().parent.parent

#: a toy of each kind; plain GQA is of none
TOYS = {"gqa": "toy-8m", "selecting": "toy-sparse-moe",
        "recurrent": "toy-hybrid-moe", "sliding": "toy-sliding-moe",
        "latent": "toy-mla-moe", "linear": "toy-linear-hybrid",
        # of two kinds on one count lane: a matrix state beside latent rows
        "kda": "toy-kda-mla-moe"}

# ------------------------------------------------ what a kind cannot ride

#: obstacle -> (regime, mesh_shape, kv_quant, spec_decode)
STARTS = {"dense": ("dense", None, "", False),
          "kv_quant": ("gather", None, "int8", False),
          "mesh": ("ragged", {"model": 2}, "", False),
          "spec": ("ragged", None, "", True)}

_SELECTS = "toy-sparse-moe selects its keys (index_topk=48) and is not served here: "
_RECURS = ("toy-hybrid-moe keeps a recurrent state (layer_pattern 'ME*ME*') and is not "
           "served here: ")
_SLIDES = ("toy-sliding-moe keeps a sliding-attention state (layer_pattern "
           "'*DSE*ESESE*E') and is not served here: ")
_LINEAR = ("toy-linear-hybrid keeps a linear-attention state (layer_pattern "
           "'LDLDLD*DLDLDLD*D') and is not served here: ")
_LATENT = "toy-mla-moe keeps a latent cache (kv_lora_rank=32) and is not served here: "
_KDA = ("toy-kda-mla-moe keeps a linear-attention state (layer_pattern "
        "'LDLE*ELELE*E') and is not served here: ")
_NO_STATE = ("the dense per-slot KV ladder keeps no bounded state a sequence, recurrent "
             "or sliding, and would attend a sliding layer to every key (KV_POOL=false, "
             "or a mesh axis the pool refuses)")
_NO_RULE = ("MESH_SHAPE {'model': 2}: parallel/sharding.py has no rule for the "
            "state-space and per-kind leaves; the family is served on one device")
_NO_REWIND = "SPEC_DECODE: a rejected draft position would have advanced the recurrent state"

REFUSALS = {
    ("gqa", "dense"): None, ("gqa", "kv_quant"): None,
    ("gqa", "mesh"): None, ("gqa", "spec"): None,
    ("selecting", "dense"): _SELECTS + (
        "the dense per-slot KV ladder has no index-key leaf (KV_POOL=false, or a mesh "
        "axis the pool refuses)"),
    ("selecting", "kv_quant"): _SELECTS + "KV_QUANT=int8: key selection reads a bf16 pool",
    ("selecting", "mesh"): _SELECTS + (
        "MESH_SHAPE {'model': 2}: the index-key leaf and the selected-row fetch are not "
        "sharded"),
    ("selecting", "spec"): None,
    ("recurrent", "dense"): _RECURS + _NO_STATE,
    ("recurrent", "kv_quant"): None,
    ("recurrent", "mesh"): _RECURS + _NO_RULE,
    ("recurrent", "spec"): _RECURS + _NO_REWIND,
    ("sliding", "dense"): _SLIDES + _NO_STATE,
    ("sliding", "kv_quant"): _SLIDES + (
        "KV_QUANT=int8: the sliding layers' rings are bf16 rows beside the pool"),
    ("sliding", "mesh"): _SLIDES + _NO_RULE,
    ("sliding", "spec"): _SLIDES + _NO_REWIND,
    ("linear", "dense"): _LINEAR + _NO_STATE,
    ("linear", "kv_quant"): None,
    ("linear", "mesh"): _LINEAR + _NO_RULE,
    ("linear", "spec"): _LINEAR + _NO_REWIND,
    ("latent", "dense"): _LATENT + (
        "the dense per-slot KV ladder has no latent leaf (KV_POOL=false, or a mesh axis "
        "the pool refuses)"),
    ("latent", "kv_quant"): _LATENT + "KV_QUANT=int8: the latent rows are kept in bf16",
    ("latent", "mesh"): _LATENT + (
        "MESH_SHAPE {'model': 2}: the latent leaf has no KV-head axis to shard and its "
        "projections no rule in parallel/sharding.py"),
    ("latent", "spec"): _LATENT + (
        "SPEC_DECODE: draft/verify windows are untried over latent rows"),
    # the union of its two kinds' lists, the linear kind's heard first
    ("kda", "dense"): _KDA + _NO_STATE,
    ("kda", "kv_quant"): (
        "toy-kda-mla-moe keeps a latent cache (kv_lora_rank=32) and is not served here: "
        "KV_QUANT=int8: the latent rows are kept in bf16"),
    ("kda", "mesh"): _KDA + _NO_RULE,
    ("kda", "spec"): _KDA + _NO_REWIND,
}


@pytest.mark.parametrize("kind,obstacle", list(REFUSALS))
def test_what_cannot_carry_a_kind_refuses_the_model(kind, obstacle):
    cfg = get_config(TOYS[kind])
    assert cache_refusal(cfg, *STARTS[obstacle]) == REFUSALS[kind, obstacle]
    # nothing in the way: a one-device pool in bf16, no speculation
    assert cache_refusal(cfg, "ragged", {"model": 1}, "", False) is None


def test_a_kind_tests_its_obstacles_in_its_own_order():
    """A configuration refused for two reasons hears the one it heard:
    the latent list asks for the int8 pool before the mesh, the state
    lists for the mesh first."""
    order = {k.name: tuple(k.refuses) for k in CACHE_KINDS}
    assert order == {"experts": (), "selecting": ("dense", "kv_quant", "mesh"),
                     "recurrent": ("dense", "mesh", "spec"), "recurrent_window": (),
                     "sliding": ("dense", "mesh", "spec", "kv_quant"),
                     "linear": ("dense", "mesh", "spec"), "linear_window": (),
                     "latent": ("dense", "kv_quant", "mesh", "spec"),
                     "expert_share": ()}
    assert all(set(k.refuses) <= set(OBSTACLES) for k in CACHE_KINDS)
    both = ("ragged", {"model": 2}, "int8", False)
    assert "KV_QUANT=int8" in cache_refusal(get_config("toy-mla-moe"), *both)
    assert "MESH_SHAPE" in cache_refusal(get_config("toy-sliding-moe"), *both)


# ----------------------------------------------------- what the cache holds

_BF16, _I32 = "bfloat16", "int32"
_GEOMETRY = dict(n_blocks=12, page=16, slots=3)

#: leaf -> (shape, dtype) of the pool engine's cache, 12 blocks of 16 rows,
#: 3 decode slots, prefill buckets up to 64
POOLS = {
    ("gqa", ""): {".k": ((4, 12, 16, 2, 64), _BF16), ".v": ((4, 12, 16, 2, 64), _BF16),
                  ".lengths": ((12,), _I32)},
    ("gqa", "int8"): {".k.q": ((4, 12, 16, 2, 64), "int8"),
                      ".k.s": ((4, 12, 16, 2), "float32"),
                      ".v.q": ((4, 12, 16, 2, 64), "int8"),
                      ".v.s": ((4, 12, 16, 2), "float32"),
                      ".lengths": ((12,), _I32)},
    ("selecting", ""): {".k": ((2, 12, 16, 2, 64), _BF16),
                        ".v": ((2, 12, 16, 2, 64), _BF16), ".lengths": ((12,), _I32),
                        ".ik": ((2, 12, 16, 128), _BF16), ".experts_read": ((), _I32),
                        ".sel_rows": ((2,), _I32)},
    ("recurrent", ""): {".k": ((2, 12, 16, 2, 32), _BF16),
                        ".v": ((2, 12, 16, 2, 32), _BF16), ".lengths": ((12,), _I32),
                        ".experts_read": ((), _I32),
                        ".ssm": ((2, 3, 8, 16, 32), "float32"),
                        ".conv": ((2, 3, 3, 256), _BF16), ".ssm_rows": ((1,), _I32),
                        ".ssm_window": ((3,), _I32)},
    ("latent", ""): {".lengths": ((12,), _I32), ".experts_read": ((), _I32),
                     ".lat": ((2, 12, 8, 96), _BF16), ".lat_rows": ((2,), _I32)},
    ("sliding", ""): {".k": ((3, 12, 16, 2, 32), _BF16), ".v": ((3, 12, 16, 2, 32), _BF16),
                      ".lengths": ((12,), _I32), ".experts_read": ((), _I32),
                      ".sk": ((3, 3, 96, 2, 32), _BF16), ".sv": ((3, 3, 96, 2, 32), _BF16),
                      ".span_rows": ((4,), _I32)},
    ("linear", ""): {".k": ((2, 12, 16, 4, 32), _BF16), ".v": ((2, 12, 16, 4, 32), _BF16),
                     ".lengths": ((12,), _I32),
                     ".lin": ((6, 3, 24, 160), "float32"),
                     ".lconv": ((6, 3, 3, 352), _BF16), ".lin_rows": ((6,), _I32),
                     ".lin_window": ((4,), _I32)},
    # no K, no V: a plane a LATENT layer (2 of the 6) beside a plane a linear one
    ("kda", ""): {".lengths": ((12,), _I32), ".experts_read": ((), _I32),
                  ".lat": ((2, 12, 8, 96), _BF16), ".lat_rows": ((2,), _I32),
                  ".lin": ((4, 3, 24, 160), "float32"),
                  ".lconv": ((4, 3, 3, 352), _BF16), ".lin_rows": ((6,), _I32),
                  ".expert_picks": ((2,), _I32)},
}
#: a snapshot store's leaves, 5 rows
SNAPSHOTS = {"recurrent": {"ssm": ((2, 5, 8, 16, 32), "float32"),
                           "conv": ((2, 5, 3, 256), _BF16)},
             "sliding": {"sk": ((3, 5, 24, 2, 32), _BF16),
                         "sv": ((3, 5, 24, 2, 32), _BF16)},
             "linear": {"lin": ((6, 5, 24, 160), "float32"),
                        "lconv": ((6, 5, 3, 352), _BF16)},
             "kda": {"lin": ((4, 5, 24, 160), "float32"),
                     "lconv": ((4, 5, 3, 352), _BF16)}}


def _leaves(tree):
    return {jax.tree_util.keystr(path): (a.shape, str(a.dtype))
            for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("kind,kv_quant", list(POOLS))
def test_the_pool_cache_is_built_beside_the_model(kind, kv_quant):
    cfg = get_config(TOYS[kind])
    make = lambda: KVCache.pool_zeros(
        cfg, **_GEOMETRY, ring=cfg.sliding_ring(64, 16), dtype=jnp.bfloat16,
        kv_quant=kv_quant, counts_experts=cfg.grouped_experts)
    assert _leaves(make()) == POOLS[kind, kv_quant]
    # under eval_shape (tests/test_tpu_aot.py, tools/aot_weight_staging.py)
    assert _leaves(jax.eval_shape(make)) == POOLS[kind, kv_quant]
    if kind in SNAPSHOTS:
        snap = KVCache.state_leaves_zeros(cfg, 5, ring=cfg.sliding_window,
                                          dtype=jnp.bfloat16)
        assert {n: (a.shape, str(a.dtype)) for n, a in snap.items()} == SNAPSHOTS[kind]
    # the count lane of the packed chunk is as wide as the kind's leaf
    # (a chip's share of the experts under a group limit adds its two; the
    # latent and the sliding toy hold 4 of 16 under a plain top-k router and
    # keep their lanes; two kinds on the lane lie end to end)
    words = {"gqa": 0, "selecting": 2, "recurrent": 1 + 3, "sliding": 4,
             "latent": 2, "linear": 6 + 4, "kda": 6 + 2 + 2}
    assert attention_words(cfg) == words[kind]
    assert long_prompts(cfg) == (kind != "gqa")


# ---------------------------------------------------- what /health reports

_STORE = ["snapshots_held", "capacity", "bytes", "state_bytes", "snapshots_pinned",
          *StateStore.COUNTERS]
_SSM = [*_STORE, "forward_passes", "eager_prefill_passes", "live_rows", "layer_passes"]

#: section -> (the toy that gives it, its keys)
HEALTH = {
    "moe": ("selecting", ["experts_read", "layer_passes", "experts_held", "first_expert",
                          "router_width", "n_group", "topk_group", "kernel"]),
    "sparse_attention": ("selecting", ["index_rows_scanned", "window_rows",
                                       "forward_passes", "decode_rows_live",
                                       "decode_rows_selected"]),
    "latent_attention": ("latent", ["row_bytes", "layers", "decode_rows",
                                    "latent_rows_read", "window_rows_absorbed",
                                    "window_rows_expanded", "window_pairs",
                                    "forward_passes"]),
    "sliding_attention": ("sliding", [
        "span", "ring_rows", "snapshot_rows", "layers_sliding", "layers_full",
        "heads_sliding", "heads_full", "decode_rows_sliding", "sliding_keys_read",
        "decode_rows_full", "full_keys_read", "window_rows", "window_pairs_sliding",
        "window_pairs_full", "forward_passes"]),
    "linear_attention": ("linear", [
        "layers_linear", "layers_full", "key_heads", "value_heads",
        "state_bytes_per_sequence", "decode_rows_linear",
        "window_rows_linear", "chunks_scanned", "decode_rows_still", "decode_rows_full",
        "full_keys_read", "forward_passes",
        # (a scalar decay a head: what its window kernel updated and passed over)
        "window_rows_moved", "window_rows_still", "window_chunks_skipped",
        "window_rows_stepped"]),
    # (the recurrent kind's own words: the rows its step kernel passed over;
    # the rows its window kernel updated and passed over, the chunks it skipped)
    "ssm": ("recurrent", [*_SSM, "decode_rows_still", "window_rows_moved",
                          "window_rows_still", "window_chunks_skipped"]),
}


def _sections(kind: str) -> dict:
    """A served engine's sections for the toy of ``kind``: one admission
    of a 40-token prompt 16 deep in the tree, one chunk of 4 passes."""
    cfg = get_config(TOYS[kind])
    counts = CacheCounters(cfg)
    counts.note_admission(16, 40)
    counts.note_passes(1, eager=True)
    counts.note_passes(4)
    counts.note_chunk(ChunkResult(
        tokens=None, done=None, lengths=None, health=None, n_alive=1,
        experts_read=7 if cfg.grouped_experts else None,
        sel_rows=tuple(range(10, 10 + attention_words(cfg))) or None), 4)
    store = StateStore(4, 3, cfg.state_bytes()) if cfg.keeps_state else None
    return counts.sections(
        batch_size=3, widest_window=64, counts_experts=cfg.grouped_experts,
        pool_bytes_per_token=384.0, ring_rows=96,
        store=store.stats() if store is not None else None)


@pytest.mark.parametrize("section", list(HEALTH))
def test_a_section_has_the_keys_it_had(section):
    kind, keys = HEALTH[section]
    got = _sections(kind)
    assert set(got) == set(SECTIONS) == set(HEALTH)
    assert list(got[section]) == keys
    # a configuration of no kind reports none of them
    assert _sections("gqa") == dict.fromkeys(SECTIONS)


def test_the_sections_count_what_the_scheduler_counted():
    sel = _sections("selecting")
    # window rows 16 .. 39, row t over its t + 1 keys; 10 live keys and 11
    # kept over 2 layers
    assert sel["sparse_attention"] == {
        "index_rows_scanned": (40 * 41 - 16 * 17) // 2 + 5, "window_rows": 24,
        "forward_passes": 5, "decode_rows_live": 5, "decode_rows_selected": 5}
    assert sel["moe"]["experts_read"] == 7 and sel["moe"]["layer_passes"] == 4 * 2
    lat = _sections("latent")["latent_attention"]
    assert (lat["window_rows_absorbed"], lat["window_pairs"]) == (24, 684)
    assert (lat["decode_rows"], lat["latent_rows_read"], lat["row_bytes"]) == (5, 11, 384.0)
    sl = _sections("sliding")
    assert [sl["sliding_attention"][k] for k in (
        "decode_rows_sliding", "sliding_keys_read", "decode_rows_full",
        "full_keys_read")] == [10, 11, 12, 13]
    assert sl["sliding_attention"]["window_pairs_full"] == 684
    # the sliding state rides the same store, with the store's keys alone
    assert sl["ssm"]["eager_prefill_passes"] == 1 and sl["ssm"]["live_rows"] == 3
    assert list(sl["ssm"]) == _SSM
    # the state-space layers' one word: the rows their step kernel passed over
    assert _sections("recurrent")["ssm"]["decode_rows_still"] == 10
    # and the window kernel's three, end to end behind it on the lane
    assert [_sections("recurrent")["ssm"][k] for k in (
        "window_rows_moved", "window_rows_still", "window_chunks_skipped")] == [11, 12, 13]
    assert _sections("recurrent")["ssm"]["layer_passes"] == {
        "ssm": 10, "experts": 10, "attention": 10, "sliding": 0, "dense_mlp": 0,
        "linear": 0}
    # the linear layers' state rides the same store; their six words
    lin = _sections("linear")
    assert [lin["linear_attention"][k] for k in (
        "decode_rows_linear", "window_rows_linear", "chunks_scanned", "decode_rows_full",
        "full_keys_read", "decode_rows_still")] == [10, 11, 12, 13, 14, 15]
    # and the window kernel's four behind them (a leaf of its own)
    assert [lin["linear_attention"][k] for k in (
        "window_rows_moved", "window_rows_still", "window_chunks_skipped",
        "window_rows_stepped")] == [16, 17, 18, 19]
    assert lin["ssm"]["layer_passes"]["linear"] == 5 * 6
    assert lin["ssm"]["state_bytes"] == get_config("toy-linear-hybrid").state_bytes()
    # a chip's share of the experts under a plain top-k router counts no
    # picks (its programs are the parent's); under a group limit, see below
    assert "picks" not in _sections("latent")["moe"]
    # two kinds on the lane: the linear kind's six words, the latent kind's
    # two (over its 2 latent layers), the share's two
    kda = _sections("kda")
    assert [kda["linear_attention"][k] for k in (
        "decode_rows_linear", "full_keys_read", "decode_rows_still")] == [10, 14, 15]
    # (a decay a key channel runs no window kernel and keeps its six words)
    assert "window_rows_moved" not in kda["linear_attention"]
    assert [kda["latent_attention"][k] for k in ("layers", "decode_rows", "latent_rows_read")] \
        == [2, 16 // 2, 17]
    assert [kda["moe"][k] for k in ("picks", "picks_held", "n_group", "topk_group")] \
        == [18, 19, 4, 2]
    assert kda["ssm"]["layer_passes"] == {"ssm": 0, "experts": 25, "attention": 10,
                                          "sliding": 0, "dense_mlp": 5, "linear": 20}


def _health_paths():
    """Every (file, /health path) that a metric of the benchmark names in
    its ``params`` under one of the kinds' sections."""
    found = []

    def walk(name, x):
        if isinstance(x, list) and x and all(isinstance(e, str) for e in x):
            if x[0] in SECTIONS:
                found.append((name, tuple(x)))
        elif isinstance(x, list):
            for e in x:
                walk(name, e)
        elif isinstance(x, dict):
            for v in x.values():
                walk(name, v)

    for path in sorted((REPO / "benchmark" / "metrics").glob("*.json")):
        walk(path.stem, json.loads(path.read_text()).get("params"))
    return found


def test_every_health_path_the_benchmark_reads_resolves():
    """A per-layer metric of a cell reads /health.<section>.<key>: each
    such path a file under benchmark/metrics/ names is in the section the
    table gives the toy of that family (``held_peak`` among them, which is
    the store's own counter)."""
    paths = _health_paths()
    assert {p[0] for _, p in paths} == set(SECTIONS)
    assert ("state_snapshots_held_peak", ("ssm", "held_peak")) in paths
    for metric, (section, *keys) in paths:
        body = _sections(HEALTH[section][0])[section]
        if keys[0] not in body:     # a chip's share adds its keys to /health.moe
            body = _sections("kda")[section]
        for key in keys:
            assert key in body, f"{metric}: /health.{section}.{key} is gone"
            body = body[key]
        assert isinstance(body, (int, float)), (metric, section, keys)


def test_the_selector_says_what_it_resolved_at_start():
    from ai_agent_kubectl_tpu.models.families import kernel_heads, resolved_at_start

    got = resolved_at_start(get_config("toy-sparse-moe"), "ragged")
    assert list(got) == ["attention_selects_keys"]
    assert got["attention_selects_keys"] == {
        "index_topk": 48, "index_heads": 4, "index_head_dim": 32,
        "rows": "exact top-k of the index scores as a per-row mask on the ragged "
                "path's causal scores, decode and window rows alike",
        "dense_while_ctx_at_most": 48}
    assert resolved_at_start(get_config("toy-8m"), "ragged") == {
        "attention_selects_keys": None}
    # the ragged kernel's head geometry: a latent configuration's one key row
    # of kv_lora_rank + 4 x qk_rope_head_dim lanes for all heads
    mla = get_config("toy-mla-moe")
    assert kernel_heads(mla, 1) == (mla.n_heads, 1, 32 + 4 * mla.qk_rope_head_dim)
    assert kernel_heads(get_config("toy-8m"), 2) == (2, 1, 64)


# ------------------------------------------------------------------ the seam

_NAMES_A_FAMILY = re.compile(
    r"\.latent\b|\.slides\b|\.selects_keys|has_ssm|has_linear|index_topk|kv_lora_rank"
    r"|lin_rows|linear_attention")
_GONE = ("state_refusal", "latent_refusal", "selection_refusal", "attention_words",
         "_attention_rows", "_state_leaves_zeros", "moe_health", "ssm_health",
         "sliding_attention_health", "latent_attention_health",
         "sparse_attention_health")


@pytest.mark.parametrize("module", ["engine/batcher.py", "engine/fake.py",
                                    "engine/regime.py", "engine/protocol.py",
                                    "server/app.py"])
def test_the_scheduler_the_wire_and_the_http_layer_name_no_family(module):
    """The next family adds a row to models/families.py and nothing here
    (30 such lines in batcher.py before ISSUE 44)."""
    text = (REPO / "ai_agent_kubectl_tpu" / module).read_text()
    hits = [f"{module}:{n}: {line.strip()}"
            for n, line in enumerate(text.splitlines(), 1)
            if _NAMES_A_FAMILY.search(line)]
    assert not hits, "\n".join(hits)
    defined = [name for name in _GONE if re.search(rf"def {name}\b", text)]
    assert not defined, f"{module} defines {defined} again"
    if module == "server/app.py":
        assert not [name for name in _GONE if name.endswith("_health") and name in text]


def test_the_description_imports_without_jax():
    """The fake scheduler and server/ read it."""
    import subprocess
    import sys

    code = ("import sys; import ai_agent_kubectl_tpu.models.families, "
            "ai_agent_kubectl_tpu.engine.regime, ai_agent_kubectl_tpu.engine.kv_pool; "
            "sys.exit('jax' in sys.modules)")
    assert subprocess.run([sys.executable, "-c", code], cwd=REPO).returncode == 0
    assert [k.name for k in kinds_of(get_config("toy-sliding-moe"))] == ["experts", "sliding"]
    assert [k.name for k in kinds_of(get_config("toy-kda-mla-moe"))] == [
        "experts", "linear", "latent", "expert_share"]
