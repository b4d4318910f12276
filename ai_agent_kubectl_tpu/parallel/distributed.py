"""Multi-host initialization (DCN) — the jax.distributed wrapper.

Reference analog: none (single process, SURVEY.md §2.4). TPU-native design:
for multi-host slices (v5p-16 and up), every host runs the same SPMD
program; ``jax.distributed.initialize`` wires the hosts over DCN, after
which ``jax.devices()`` is global and the same Mesh/NamedSharding code as
single-host runs unchanged — there is no separate transport to manage.

Env knobs (mirroring the framework's env-first config, SURVEY.md §5):

- ``COORDINATOR_ADDRESS`` — host:port of process 0 (absent ⇒ single host)
- ``NUM_PROCESSES`` / ``PROCESS_ID`` — explicit ranks; on TPU pods JAX can
  usually infer both from the runtime environment, so they are optional.

Serving topology (SURVEY.md §7 hard part "multi-host serving"): HTTP
ingress runs on process 0 only; the SPMD decode loop runs on all hosts, so
process 0 broadcasts request batches by virtue of jit's SPMD semantics
(same program, same global arrays). That logic lives in the engine; here we
only establish the process group.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

logger = logging.getLogger(__name__)

_initialized = False


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    *,
    require: bool = False,
) -> bool:
    """Initialize jax.distributed if configured. Returns True when running
    multi-host, False for plain single-host operation. Idempotent.

    ``require=True`` (DISTRIBUTED_INIT=true) initializes even without a
    coordinator address — on TPU pods JAX auto-configures the process
    group from the runtime environment; silently skipping would leave
    jax.devices() local and make the later DCN mesh build fail with a
    confusing device-count error."""
    global _initialized
    if _initialized:
        return True
    coordinator_address = coordinator_address or os.getenv("COORDINATOR_ADDRESS")
    if not coordinator_address and not require:
        return False

    import jax

    kwargs = {}
    if coordinator_address:
        kwargs["coordinator_address"] = coordinator_address
    num_processes = num_processes or _int_env("NUM_PROCESSES")
    process_id = process_id if process_id is not None else _int_env("PROCESS_ID")
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
    _initialized = True
    logger.info(
        "jax.distributed up: process %d/%d, %d global / %d local devices",
        jax.process_index(), jax.process_count(),
        len(jax.devices()), len(jax.local_devices()),
    )
    return True


def _int_env(name: str) -> Optional[int]:
    v = os.getenv(name)
    return int(v) if v else None
