"""Device-mesh construction — the TPU-native communication substrate.

The reference has no distributed code at all (SURVEY.md §2.4: no NCCL, no
MPI, no multi-device anything). On TPU the entire comm layer is: build a
``jax.sharding.Mesh`` whose axes map onto the ICI torus, annotate arrays
with ``NamedSharding`` PartitionSpecs, and let XLA insert all-gather /
reduce-scatter / all-to-all over ICI (and DCN for multi-slice). This module
owns the first step.

Axis conventions (fixed order, used by every PartitionSpec in the repo):

- ``data``   — batch / DP.              all-reduce-free inference scaling
- ``expert`` — MoE expert parallelism.  all-to-all dispatch/combine
- ``pipe``   — pipeline stages (layer-stack sharding, ppermute hand-off)
- ``seq``    — sequence/context (ring attention, long prefill)
- ``model``  — tensor parallelism.      all-gather / reduce-scatter per layer

``create_device_mesh`` (mesh_utils) is used on real TPU topologies so mesh
axes ride ICI rings; on CPU/host-emulated devices a plain reshape is fine.
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

logger = logging.getLogger(__name__)

AXES = ("data", "expert", "pipe", "seq", "model")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. Product must equal the device count in use."""

    data: int = 1
    expert: int = 1
    pipe: int = 1
    seq: int = 1
    model: int = 1

    @classmethod
    def parse(cls, spec: str) -> "MeshConfig":
        """Parse ``"dp=2,tp=4"`` / ``"data:2,model:4"`` style strings —
        ``=`` and ``:`` separators both accepted (the MESH_SHAPE env knob;
        empty string = single device)."""
        alias = {"dp": "data", "ep": "expert", "pp": "pipe", "sp": "seq",
                 "tp": "model", "data": "data", "expert": "expert",
                 "pipe": "pipe", "seq": "seq", "model": "model"}
        kwargs = {}
        for part in filter(None, (p.strip() for p in spec.split(","))):
            key, _, val = part.replace(":", "=").partition("=")
            key = key.strip().lower()
            if key not in alias:
                raise ValueError(
                    f"Unknown mesh axis {key!r} in {spec!r}; "
                    f"use dp/ep/pp/sp/tp or {'/'.join(AXES)}"
                )
            kwargs[alias[key]] = int(val)
        return cls(**kwargs)

    @property
    def shape(self) -> tuple:
        return (self.data, self.expert, self.pipe, self.seq, self.model)

    @property
    def n_devices(self) -> int:
        return math.prod(self.shape)

    def describe(self) -> str:
        return ",".join(f"{a}={s}" for a, s in zip(AXES, self.shape) if s > 1) \
            or "single-device"


def build_mesh(cfg: MeshConfig, devices: Optional[Sequence] = None,
               dcn: Optional[MeshConfig] = None) -> Mesh:
    """Build a Mesh with the canonical axis names.

    On TPU, ``mesh_utils.create_device_mesh`` lays logical axes onto the
    physical ICI torus (so per-layer TP collectives ride the fastest links);
    anywhere else (CPU emulation, single device) a reshape of
    ``jax.devices()`` is used.

    ``dcn`` (DCN_MESH_SHAPE) adds a multi-slice outer factorization: each
    logical axis sized ``ici × dcn``, with the dcn component crossing slice
    boundaries via ``create_hybrid_device_mesh`` — collectives on an axis
    with a dcn factor ride DCN, pure-ICI axes stay on-slice. Requires
    ``jax.distributed`` to be up (process-sliced devices).
    """
    if devices is None:
        devices = jax.devices()
    total = cfg.n_devices * (dcn.n_devices if dcn is not None else 1)
    if total != len(devices):
        raise ValueError(
            f"Mesh {cfg.describe()}"
            + (f" × dcn {dcn.describe()}" if dcn is not None else "")
            + f" wants {total} devices, got {len(devices)}"
        )
    if dcn is not None and dcn.n_devices > 1:
        from jax.experimental import mesh_utils

        # TPU multi-slice devices carry distinct slice_index values (the
        # DCN granule); CPU multi-process emulation reports one slice (or
        # none) for every device — there the process IS the granule (one
        # "slice" per host), which is also the correct grouping for the
        # 2-process DCN test rig.
        slice_ids = {getattr(d, "slice_index", None) for d in devices}
        by_process = len(slice_ids) <= 1
        dev_array = mesh_utils.create_hybrid_device_mesh(
            cfg.shape, dcn.shape, devices=devices,
            process_is_granule=by_process,
        )
    elif devices[0].platform == "tpu" and len(devices) > 1:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(cfg.shape, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(cfg.shape)
    mesh = Mesh(dev_array, AXES)
    logger.info("Mesh built: %s over %d %s device(s)", cfg.describe(),
                len(devices), devices[0].platform)
    return mesh


def single_device_mesh() -> Mesh:
    """A 1×1×1×1 mesh on the first device — lets all sharded code paths run
    unchanged on one chip."""
    return build_mesh(MeshConfig(), devices=jax.devices()[:1])
