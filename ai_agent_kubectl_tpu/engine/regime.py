"""Which attention serves a batch: the one place that decides.

Three regimes, each selected by what the engine can observe at start
(no setting names one):

- ``ragged``: the block pool read by ops/ragged_attention.py — decode,
  spec verify and admission prefill in ONE kernel, so a mixed chunk is
  one dispatch. Every cell of the benchmark serves it.
- ``gather``: the block pool read through ``_pool_gather`` +
  ``dense_attention`` (models/transformer.py) with separately compiled
  prefill programs. What the pool serves wherever ``ragged`` cannot; on
  the CPU that is every engine test that does not pass
  ``force_ragged``, because an engine test under the interpreted kernel
  costs 10-23 s and tier-1 would not fit its limit through it.
- ``dense``: per-slot dense KV and the KV-bucket ladder, when the pool
  is not used.

Jax-free: the fake scheduler calls it too.
"""

from __future__ import annotations

from typing import Mapping, Optional, Sequence, Tuple

from ..models.families import OBSTACLES, kinds_of

RAGGED, GATHER, DENSE = "ragged", "gather", "dense"
REGIMES = (RAGGED, GATHER, DENSE)

#: Smallest pool page a TPU serves. The ragged kernel pays a fixed cost
#: per (slot, page) grid step, so pages below 64 are grid-overhead-bound
#: (ROADMAP S3); 64 still divides the 128-token kv-limit tile.
TPU_MIN_POOL_PAGE = 64

#: Mesh axes the pool does not compose with: its block axis is shared
#: across slots (no slots-over-``data`` partition exists) and the pipe
#: stage body has no table plumbing.
_POOL_REFUSES_AXES = ("data", "pipe", "seq")


def resolve_attention_regime(
    model_cfg, *,
    backend: str,
    mesh_shape: Optional[Mapping[str, int]],
    kv_quant: str,
    kv_pool: bool,
    device_termination: bool,
    pool_page: int,
    force_ragged: bool = False,
) -> Tuple[str, int, str]:
    """``(regime, pool_page, reason)`` for an engine about to start.

    ``model_cfg`` supplies ``n_heads``/``n_kv_heads``/``head_dim`` (read
    only under a >1 ``model`` axis or a TPU backend, so the fake passes
    None); ``backend`` is ``jax.default_backend()`` or ``"fake"``;
    ``mesh_shape`` maps axis name to size (None off-mesh); ``kv_pool``
    is the operator's KV_POOL. ``force_ragged`` is the tests' way to run
    the interpreted kernel where no TPU is: it stands in for the backend
    condition alone — every other condition still applies.

    ``reason`` names the first condition that decided, for the one log
    line at start and for /health.
    """
    mesh = dict(mesh_shape or {})
    if not kv_pool:
        return DENSE, pool_page, "KV_POOL=false"
    refused = [a for a in _POOL_REFUSES_AXES if mesh.get(a, 1) > 1]
    if refused:
        return DENSE, pool_page, (
            f"the KV pool does not compose with a >1 "
            f"{'/'.join(refused)} mesh axis")
    if backend == "tpu":
        pool_page = max(pool_page, TPU_MIN_POOL_PAGE)
    tp = mesh.get("model", 1)
    if kv_quant:
        return GATHER, pool_page, (
            f"KV_QUANT={kv_quant}: the ragged kernel reads bf16 KV")
    if not device_termination:
        return GATHER, pool_page, (
            "DEVICE_TERMINATION=false: staged admissions arm inside the "
            "device-termination chunk carry")
    if tp > 1 and (model_cfg.n_kv_heads % tp or model_cfg.n_heads % tp):
        return GATHER, pool_page, (
            f"KV heads ({model_cfg.n_kv_heads}) and heads "
            f"({model_cfg.n_heads}) do not divide the model axis ({tp})")
    if backend != "tpu":
        if force_ragged:
            return RAGGED, pool_page, (
                f"force_ragged: the interpreted kernel on backend "
                f"{backend}")
        return GATHER, pool_page, (
            f"backend {backend} is not a TPU (the kernel would run "
            f"interpreted)")
    from ..ops.ragged_attention import lane_heads, ragged_supported

    # KV heads a row of the pool holds (a ModelConfig says; the tests'
    # stand-ins give the model's own count)
    kv_row = getattr(model_cfg, "kv_heads_paged", model_cfg.n_kv_heads)
    if tp > 1 and lane_heads(model_cfg.head_dim, kv_row) != 1:
        return GATHER, pool_page, (
            f"head_dim={model_cfg.head_dim} is no whole lane tile: the "
            f"kernel pairs such heads on one device only (model axis {tp})")
    if not ragged_supported(pool_page, model_cfg.head_dim, 1, kv_row):
        return GATHER, pool_page, (
            f"the compiled kernel does not support page={pool_page} "
            f"head_dim={model_cfg.head_dim} over {kv_row} KV heads a row")
    return RAGGED, pool_page, (
        "block pool, bf16 KV, device termination, TPU backend")


def cache_refusal(model_cfg, regime: str,
                  mesh_shape: Optional[Mapping[str, int]], kv_quant: str,
                  spec_decode: bool) -> Optional[str]:
    """Why an engine about to start cannot serve this configuration, or
    None: the first obstacle (models/families.py::OBSTACLES), in the
    kind's own order, that a kind of the configuration's cache cannot
    ride. What cannot carry a kind's leaf refuses the model at start
    (server: engine "degraded", this reason)."""
    mesh = dict(mesh_shape or {})
    present = dict(zip(OBSTACLES, (
        regime == DENSE, bool(kv_quant),
        any(n > 1 for n in mesh.values()), bool(spec_decode))))
    for kind in kinds_of(model_cfg):
        for obstacle, why in kind.refuses.items():
            if present[obstacle]:
                return (f"{model_cfg.name} {kind.says(model_cfg)} and is "
                        f"not served here: "
                        + why.format(kv_quant=kv_quant, mesh=mesh))
    return None


def stage_window(lengths: Sequence[int], buckets: Sequence[int]
                 ) -> Tuple[int, int]:
    """The staging rule of a mixed chunk's window (ISSUE 39), both
    schedulers': ``(taken, width)``. Of the staged suffixes waiting, whose
    ``lengths`` come in arrival order, the next chunk carries the first
    ``taken``: as many as SUM to at most the widest bucket, so that the
    window's valid rows fit the width + batch rows its program computes.
    ``width`` is the smallest bucket that covers the sum (0 with nothing
    waiting). The rest wait for the chunk after: order is kept, and the
    head of the line always rides (a staged suffix is at most the widest
    bucket long: ``batcher.staged_suffix_len``), so nothing starves."""
    total = taken = 0
    for n in lengths:
        if taken and total + n > buckets[-1]:
            break
        total += n
        taken += 1
    if not taken:
        return 0, 0
    return taken, next(b for b in buckets if b >= total)
