"""What a configuration's cache is, said once.

A family differs from plain GQA in what its cache holds beside (or in
place of) the paged K and V, what its passes count on the device, which
of the engine's obstacles it cannot ride, and what it reports under
/health. ``CACHE_KINDS`` holds that as data, one entry a kind; the cache
(models/transformer.py::KVCache.pool_zeros), the counters and /health
sections (engine/kv_pool.py::CacheCounters), the refusal at start
(engine/regime.py::cache_refusal) and the scheduler (engine/batcher.py:
the count lane, the buckets warmed, the kernel's head geometry) read it.
A new family adds an entry here; none of those modules names it.

Jax-free: the fake scheduler and server/ import it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

from .config import ModelConfig

#: What an engine may be started with that a kind may not ride: the dense
#: per-slot KV ladder, an int8 pool, a mesh axis over 1, speculation.
OBSTACLES = ("dense", "kv_quant", "mesh", "spec")

_LAYER_PASS_KINDS = (("ssm", "M"), ("experts", "E"), ("attention", "*"),
                     ("sliding", "S"), ("dense_mlp", "D"), ("linear", "L"))


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One kind of cache content. ``of``: whether a configuration is of
    the kind; ``says`` names it in a refusal. ``count_leaf``: its leaf of
    ``KVCache.COUNTS`` (``count_shape`` int32 words), ``lane`` the field
    of engine/protocol.py::ChunkResult the words ride to the host in.
    ``refuses``: obstacle -> reason, in the order tested ({kv_quant} and
    {mesh} filled in). ``window_counts``: the kind's own name for a count
    of prompt rows -> engine/kv_pool.py::span_window_counts's. ``health``:
    /health section -> ``fn(cfg, counts, facts)``, ``counts`` the kind's
    {"dev": device words, "host": window counts, "passes": passes of the
    chunks that brought words}, ``facts`` CacheCounters.sections's.
    ``long_prompts``: served with prompts past the widest bucket.
    ``kernel_heads``: (query heads, KV heads, lanes) the ragged kernel is
    sized by, where not the configuration's own. ``resolves``: block of
    /health.kv_pool resolved at start -> ``fn(cfg, regime)``."""

    name: str
    of: Callable[[ModelConfig], bool]
    says: Callable[[ModelConfig], str] = lambda cfg: ""
    count_leaf: Optional[str] = None
    count_shape: Tuple[int, ...] = ()
    lane: Optional[str] = None
    refuses: Mapping[str, str] = dataclasses.field(default_factory=dict)
    window_counts: Mapping[str, str] = dataclasses.field(default_factory=dict)
    health: Mapping[str, Callable] = dataclasses.field(default_factory=dict)
    long_prompts: bool = True
    kernel_heads: Optional[Callable[[ModelConfig], Tuple[int, int, int]]] = None
    resolves: Mapping[str, Callable] = dataclasses.field(default_factory=dict)

    @property
    def count_words(self) -> int:
        return math.prod(self.count_shape) if self.count_leaf else 0


def _moe_section(cfg, counts, facts) -> Optional[dict]:
    """/health.moe: experts whose weights the grouped expert path read and
    the layer passes they were read in, over the chunk programs' passes
    (None where another MoE path serves); what the kernel resolves from
    shapes for a decode pass, the widest window and an eager piece."""
    if not facts["counts_experts"]:
        return None
    from ..parallel.moe import grouped_kernel_shape

    rows, wide = facts["batch_size"], facts["widest_window"]
    return {"experts_read": counts["dev"][0],
            "layer_passes": counts["passes"] * cfg.n_of("E"),
            # a chip's share: the experts this tree holds, the first of
            # them, and how many the router scores
            "experts_held": cfg.n_experts,
            "first_expert": cfg.first_expert,
            "router_width": cfg.experts_scored,
            # a group-limited choice: groups, and how many of them stay
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            # what the kernel resolves from a call's shapes (ISSUE 34)
            "kernel": {
                "decode": grouped_kernel_shape(cfg, rows),
                "widest_window": grouped_kernel_shape(cfg, rows + wide),
                "eager_piece": grouped_kernel_shape(cfg, wide)}}


def _picks_section(cfg, counts, facts) -> Optional[dict]:
    """/health.moe of a chip's share of the experts, beside ``_moe_section``'s
    keys: the picks the chunk programs' live rows made among all the experts
    scored and those that fell on an expert held here (counted on the
    device, summed over the expert layers)."""
    if not facts["counts_experts"]:
        return None
    picks, held = counts["dev"]
    return {"picks": picks, "picks_held": held}


def _sparse_section(cfg, counts, facts) -> dict:
    """/health.sparse_attention. ``decode_rows_live`` / ``_selected``: the
    keys the chunk programs' decode queries had before them and those the
    selector's mask kept, counted on the device in every layer, given per
    layer. ``window_rows`` and the window part of ``index_rows_scanned``:
    arithmetic on prompt lengths (window row t scans t + 1 index keys)."""
    live, kept = (n // cfg.n_layers for n in counts["dev"])
    host = counts["host"]
    return {"index_rows_scanned": host["index_rows_scanned"] + live,
            "window_rows": host["window_rows"],
            "forward_passes": facts["forward_passes"],
            "decode_rows_live": live, "decode_rows_selected": kept}


def _latent_section(cfg, counts, facts) -> dict:
    """/health.latent_attention. ``row_bytes``: what a token keeps in the
    pool, all latent layers (``layers``: every layer of a uniform block, a
    pattern's ``*`` layers). ``decode_rows``: decode queries the chunk
    programs ran (counted once whatever the depth); ``latent_rows_read``:
    the cached rows they had before them, summed over the layers (both
    counted on the device). ``window_rows_absorbed`` / ``_expanded``:
    prompt rows prefilled, by the form that attended them (the expanded
    form serves none); ``window_pairs``: their (query, cached row) pairs
    in one layer."""
    queries, rows = counts["dev"]
    return {"row_bytes": facts["pool_bytes_per_token"],
            "layers": cfg.n_of("*"),
            "decode_rows": queries // cfg.n_of("*"),
            "latent_rows_read": rows,
            "window_rows_absorbed": counts["host"]["window_rows_absorbed"],
            "window_rows_expanded": 0,
            "window_pairs": counts["host"]["window_pairs"],
            "forward_passes": facts["forward_passes"]}


def _sliding_section(cfg, counts, facts) -> dict:
    """/health.sliding_attention. ``decode_rows_sliding`` /
    ``sliding_keys_read``: the decode queries the sliding layers ran and
    the keys they had inside their span; ``decode_rows_full`` /
    ``full_keys_read``: the same for the full layers (all four counted on
    the device, beside the mask). ``window_*``: prompt rows prefilled and
    their pairs in ONE layer of each kind. ``ring_rows``: what a decode
    slot keeps a sliding layer, ``snapshot_rows`` what a snapshot does."""
    rows_s, keys_s, rows_f, keys_f = counts["dev"]
    return {"span": cfg.sliding_window,
            "ring_rows": facts["ring_rows"],
            "snapshot_rows": cfg.sliding_window,
            "layers_sliding": cfg.n_of("S"), "layers_full": cfg.n_of("*"),
            "heads_sliding": cfg.heads_of("S"),
            "heads_full": cfg.heads_of("*"),
            "decode_rows_sliding": rows_s, "sliding_keys_read": keys_s,
            "decode_rows_full": rows_f, "full_keys_read": keys_f,
            **counts["host"],
            "forward_passes": facts["forward_passes"]}


def _linear_section(cfg, counts, facts) -> dict:
    """/health.linear_attention. ``state_bytes_per_sequence``: what a
    snapshot keeps, every linear layer. ``decode_rows_linear`` /
    ``window_rows_linear`` / ``chunks_scanned``: the decode rows and the
    prompt rows the linear layers ran and the chunks their scans ran over
    (padding included); ``decode_rows_full`` / ``full_keys_read``: the
    decode queries the full-attention layers ran and the keys those had
    before them; ``decode_rows_still``: the rows of decode passes that did
    not move (dead slots), whose state the step kernel neither read nor
    wrote (all six counted on the device, summed over the layers of the
    kind); ``key_heads`` / ``value_heads``: a key head serves value_heads /
    key_heads value heads. Where the decay is a scalar a head,
    ``_linear_window_section`` adds ``window_rows_moved`` /
    ``window_rows_still`` / ``window_chunks_skipped`` /
    ``window_rows_stepped``."""
    rows_l, window_l, chunks, rows_f, keys_f, still = counts["dev"]
    return {"layers_linear": cfg.n_of("L"), "layers_full": cfg.n_of("*"),
            "key_heads": cfg.lin_key_heads,
            "value_heads": cfg.lin_value_heads,
            "state_bytes_per_sequence": cfg.state_bytes(),
            "decode_rows_linear": rows_l, "window_rows_linear": window_l,
            "chunks_scanned": chunks, "decode_rows_still": still,
            "decode_rows_full": rows_f, "full_keys_read": keys_f,
            "forward_passes": facts["forward_passes"]}


def _linear_window_section(cfg, counts, facts) -> dict:
    """/health.linear_attention of a configuration whose delta rule decays by
    a scalar a head, beside ``_linear_section``'s keys: the rows of WINDOW
    passes (a chunk program's prologue; an eager piece counts no words)
    whose state the window kernel (ops/gated_delta_window.py) updated, those
    it neither read nor wrote because they brought no token, the chunks it
    passed over past a moving row's ``q_len``, and of the rows it updated
    those that rode the window with ONE token (a live decode row) and took
    the step's arithmetic inside it (counted on the device, summed over the
    linear layers)."""
    moved, still, skipped, stepped = counts["dev"]
    return {"window_rows_moved": moved, "window_rows_still": still,
            "window_chunks_skipped": skipped, "window_rows_stepped": stepped}


def _state_section(cfg, counts, facts) -> Optional[dict]:
    """/health.ssm (None until the engine has its snapshot store): the
    store's counters (engine/kv_pool.py::StateStore.stats) and forward
    passes dispatched (chunk steps and eager pieces) times the layers of
    each kind."""
    if facts["store"] is None:
        return None
    passes = facts["forward_passes"]
    return {**facts["store"],
            "forward_passes": passes,
            "eager_prefill_passes": facts["eager_passes"],
            "live_rows": facts["batch_size"],
            "layer_passes": {name: passes * cfg.n_of(kind)
                             for name, kind in _LAYER_PASS_KINDS}}


def _recurrent_section(cfg, counts, facts) -> Optional[dict]:
    """/health.ssm of a configuration with state-space layers:
    ``_state_section``'s keys and ``decode_rows_still``, the rows of decode
    passes that did not move (dead slots), whose state the step kernel
    neither read nor wrote (counted on the device, summed over the
    state-space layers)."""
    said = _state_section(cfg, counts, facts)
    return said and {**said, "decode_rows_still": counts["dev"][0]}


def _window_section(cfg, counts, facts) -> Optional[dict]:
    """/health.ssm of such a configuration, beside ``_recurrent_section``'s
    keys: the rows of WINDOW passes (a chunk program's prologue; an eager
    piece counts no words) whose state the window kernel updated, those it
    neither read nor wrote because they brought no token, and the chunks it
    passed over past a moving row's ``q_len`` (counted on the device,
    summed over the state-space layers)."""
    if facts["store"] is None:
        return None
    moved, still, skipped = counts["dev"]
    return {"window_rows_moved": moved, "window_rows_still": still,
            "window_chunks_skipped": skipped}


def _selection_resolved(cfg, regime: str) -> dict:
    """How many keys a query keeps, and how each form reads them."""
    return {"index_topk": cfg.index_topk,
            "index_heads": cfg.index_heads,
            "index_head_dim": cfg.index_head_dim,
            "rows": f"exact top-k of the index scores as a per-row "
                    f"mask on the {regime} path's "
                    f"causal scores, decode and window rows alike",
            "dense_while_ctx_at_most": cfg.index_topk}


_STATE_SAYS = "keeps {state} (layer_pattern {pattern!r})"
_STATE_REFUSES = {
    "dense": "the dense per-slot KV ladder keeps no bounded state a "
             "sequence, recurrent or sliding, and would attend a sliding "
             "layer to every key (KV_POOL=false, or a mesh axis the pool "
             "refuses)",
    "mesh": "MESH_SHAPE {mesh}: parallel/sharding.py has no "
            "rule for the state-space and per-kind leaves; the family "
            "is served on one device",
    "spec": "SPEC_DECODE: a rejected draft position would have advanced "
            "the recurrent state",
}

#: In the order a configuration of two kinds is refused by, and in which the
#: words of two kinds on one count lane lie.
CACHE_KINDS: Tuple[CacheKind, ...] = (
    # The grouped expert path counts the experts it read; whether it
    # serves is the engine's fact (models/transformer.py::serves_grouped).
    CacheKind(
        name="experts",
        of=lambda cfg: cfg.grouped_experts,
        count_leaf="experts_read", count_shape=(), lane="experts_read",
        health={"moe": _moe_section},
        long_prompts=False),
    # Its index keys are a leaf of the block pool, one device's whole.
    CacheKind(
        name="selecting",
        of=lambda cfg: cfg.selects_keys,
        says=lambda cfg: f"selects its keys (index_topk={cfg.index_topk})",
        count_leaf="sel_rows", count_shape=(2,), lane="sel_rows",
        refuses={
            "dense": "the dense per-slot KV ladder has no index-key leaf "
                     "(KV_POOL=false, or a mesh axis the pool refuses)",
            "kv_quant": "KV_QUANT={kv_quant}: key selection reads a bf16 "
                        "pool",
            "mesh": "MESH_SHAPE {mesh}: the index-key leaf and the "
                    "selected-row fetch are not sharded"},
        window_counts={"index_rows_scanned": "window_pairs_full",
                       "window_rows": "window_rows"},
        health={"sparse_attention": _sparse_section},
        resolves={"attention_selects_keys": _selection_resolved}),
    # Its bounded state is a leaf of the pool engine's cache, a row a
    # decode slot, with snapshots on the radix tree; its decode passes
    # count the rows they passed over.
    CacheKind(
        name="recurrent",
        of=lambda cfg: cfg.has_ssm,
        says=lambda cfg: _STATE_SAYS.format(
            state="a recurrent state", pattern="".join(cfg.layer_kinds)),
        count_leaf="ssm_rows", count_shape=(1,), lane="sel_rows",
        refuses=_STATE_REFUSES,
        health={"ssm": _recurrent_section}),
    # Its window passes count the rows and chunks they passed over too.
    CacheKind(
        name="recurrent_window",
        of=lambda cfg: cfg.has_ssm,
        count_leaf="ssm_window", count_shape=(3,), lane="sel_rows",
        health={"ssm": _window_section}),
    # The same, and a sliding layer's ring holds bf16 rows.
    CacheKind(
        name="sliding",
        of=lambda cfg: cfg.slides,
        says=lambda cfg: _STATE_SAYS.format(
            state="a sliding-attention state",
            pattern="".join(cfg.layer_kinds)),
        count_leaf="span_rows", count_shape=(4,), lane="sel_rows",
        refuses={**_STATE_REFUSES,
                 "kv_quant": "KV_QUANT={kv_quant}: the sliding layers' "
                             "rings are bf16 rows beside the pool"},
        window_counts={"window_rows": "window_rows",
                       "window_pairs_sliding": "window_pairs_sliding",
                       "window_pairs_full": "window_pairs_full"},
        health={"sliding_attention": _sliding_section,
                "ssm": _state_section}),
    # The same store; the state is a float32 matrix a head (the gated
    # delta rule's) and its passes count their rows beside the full
    # layers' keys.
    CacheKind(
        name="linear",
        of=lambda cfg: cfg.has_linear,
        says=lambda cfg: _STATE_SAYS.format(
            state="a linear-attention state",
            pattern="".join(cfg.layer_kinds)),
        count_leaf="lin_rows", count_shape=(6,), lane="sel_rows",
        refuses=_STATE_REFUSES,
        health={"linear_attention": _linear_section,
                "ssm": _state_section}),
    # Where the decay is a scalar a head its window passes run a kernel, and
    # count the rows and chunks that passed over (a leaf of its own: the
    # per-channel family shares ``linear`` and keeps its six words).
    CacheKind(
        name="linear_window",
        of=lambda cfg: cfg.has_linear and not cfg.lin_channel_decay,
        count_leaf="lin_window", count_shape=(4,), lane="sel_rows",
        health={"linear_attention": _linear_window_section}),
    # Its cache is ONE leaf of the block pool with no head axis.
    CacheKind(
        name="latent",
        of=lambda cfg: cfg.latent,
        says=lambda cfg: f"keeps a latent cache (kv_lora_rank="
                         f"{cfg.kv_lora_rank})",
        count_leaf="lat_rows", count_shape=(2,), lane="sel_rows",
        refuses={
            "dense": "the dense per-slot KV ladder has no latent leaf "
                     "(KV_POOL=false, or a mesh axis the pool refuses)",
            "kv_quant": "KV_QUANT={kv_quant}: the latent rows are kept in "
                        "bf16",
            "mesh": "MESH_SHAPE {mesh}: the latent leaf has no "
                    "KV-head axis to shard and its projections no rule in "
                    "parallel/sharding.py",
            "spec": "SPEC_DECODE: draft/verify windows are untried over "
                    "latent rows"},
        window_counts={"window_rows_absorbed": "window_rows",
                       "window_pairs": "window_pairs_full"},
        health={"latent_attention": _latent_section},
        # one key row for all heads; the kernel's query is
        # ops/ragged_attention.py::latent_query's
        kernel_heads=lambda cfg: (
            cfg.n_heads, 1, cfg.kv_lora_rank + 4 * cfg.qk_rope_head_dim)),
    # A chip's share of the experts under a group-limited choice or beside
    # a gated shared expert: how many of the picks land here (its two words
    # the last of the lane). The two shares under a plain top-k router that
    # came before the count keep the programs they had
    # (``ModelConfig.counts_picks``).
    CacheKind(
        name="expert_share",
        of=lambda cfg: cfg.counts_picks,
        count_leaf="expert_picks", count_shape=(2,), lane="sel_rows",
        health={"moe": _picks_section},
        long_prompts=False),
)

#: The /health sections the kinds give (server/schemas.py::HealthResponse).
SECTIONS = tuple(dict.fromkeys(s for k in CACHE_KINDS for s in k.health))


def kinds_of(cfg: ModelConfig) -> Tuple[CacheKind, ...]:
    return tuple(k for k in CACHE_KINDS if k.of(cfg))


def attention_words(cfg: ModelConfig) -> int:
    """Words of the packed chunk's ``sel_rows`` lane for this model: those of
    every kind of its cache that rides the lane, one after another."""
    return sum(k.count_words for k in kinds_of(cfg) if k.lane == "sel_rows")


def attention_counted(cache):
    """Those words on the device: the count leaves of ``cache`` (a KVCache)
    that ride the ``sel_rows`` lane, end to end in ``CACHE_KINDS``' order
    (a leaf is there for each kind the configuration is of: ``KVCache.
    pool_zeros``), or None."""
    leaves = [leaf for k in CACHE_KINDS if k.lane == "sel_rows"
              and (leaf := getattr(cache, k.count_leaf)) is not None]
    if len(leaves) < 2:
        return leaves[0] if leaves else None
    # (this module stays jax-free for the fake scheduler: the one call that
    # needs an array library is reached from a chunk program alone)
    import jax.numpy as jnp

    return jnp.concatenate(leaves)


def long_prompts(cfg: ModelConfig) -> bool:
    """Served with prompts far past the widest bucket (their heads are
    prefilled eagerly, and a head's last piece may be any bucket)."""
    return any(k.long_prompts for k in kinds_of(cfg))


def kernel_heads(cfg: ModelConfig, tp: int = 1,
                 lane_heads: int = 1) -> Tuple[int, int, int]:
    """(query heads, KV heads, lanes a head) the ragged kernel is sized by
    on one of ``tp`` model-axis shards, ``lane_heads`` KV heads of the
    pool's rows sharing a lane tile (ops/ragged_attention.py::lane_heads)."""
    for kind in kinds_of(cfg):
        if kind.kernel_heads is not None:
            return kind.kernel_heads(cfg)
    # (a cache row's spare KV heads, 32 held for 30, run with their queries)
    spare = cfg.kv_heads_paged - cfg.n_kv_heads
    return ((cfg.n_heads + spare * cfg.q_per_kv) // tp,
            cfg.kv_heads_paged // tp // lane_heads,
            cfg.head_dim * lane_heads)


def resolved_at_start(cfg: ModelConfig, regime: str) -> Dict[str, Any]:
    """The blocks of /health.kv_pool that a kind resolves at start; null
    for a configuration not of the kind."""
    return {key: fn(cfg, regime) if kind.of(cfg) else None
            for kind in CACHE_KINDS for key, fn in kind.resolves.items()}
