"""JaxEngine — the local TPU inference engine behind the service seam.

This replaces the reference's remote ChatCompletion call (app.py:117,184)
with an in-process engine (SURVEY.md §3.1 "TPU-native equivalent stack"):

    tokenize → bucketed jit prefill → jit decode loop → detokenize

Design:
- **Bucketed prefill**: prompts are padded to the next bucket length
  (PREFILL_BUCKETS) so jit sees a handful of static shapes; first request
  per bucket pays compilation, everything after hits the cache.
- **On-device decode chunks**: the hot loop is a jitted ``lax.scan`` that
  generates CHUNK tokens (forward + sample) per dispatch, so the host↔device
  round trip is paid once per chunk, not once per token (one XLA program,
  no per-token dispatch overhead). The KV cache is
  donated (``donate_argnums``) so XLA updates it in place in HBM rather
  than copying ~GBs per token.
- **Speculative chunk pipelining**: the next chunk is dispatched (chained
  on device arrays, no host read) before the current chunk's tokens are
  pulled, hiding transfer latency behind compute. On EOS the in-flight
  chunk is abandoned — wasted FLOPs, never wasted wall-clock.
- **Blocking JAX work runs on a worker thread** (``asyncio.to_thread``)
  so the event loop keeps serving /health and /metrics during generation;
  an asyncio.Lock serializes requests (the continuous-batching scheduler
  in engine/batcher.py lifts this to admit-at-step concurrency).
- Greedy decode at temperature=0 (reference parity, app.py:109).

The single-sequence path here is also the numerical baseline the batched
scheduler and Pallas-kernel paths are tested against.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from functools import partial
from typing import AsyncIterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.config import ModelConfig, get_config
from ..models.transformer import KVCache, forward, init_params
from ..obs.trace import RequestSpans, current_trace, trace_event
from .protocol import EngineResult, EngineUnavailable, GenerationTimeout
from .sampling import sample_token_traced
from .tokenizer import StreamDecoder, Tokenizer, load_tokenizer

logger = logging.getLogger(__name__)


def _dtype_from_str(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def kv_bucket_ladder(top: int, start: int = 128) -> tuple:
    """Pow2 KV-span ladder topped by ``top``: decode programs compile per
    bucket so attention cost tracks live lengths, not the cache size.
    Shared by the single-sequence and batched engines (their tops differ:
    max_seq vs the slot caches' S_alloc)."""
    ladder, b = [], start
    while b < top:
        ladder.append(b)
        b *= 2
    return tuple(ladder) + (top,)


class JaxEngine:
    name = "jax"

    def __init__(
        self,
        model_cfg: ModelConfig,
        *,
        tokenizer: Optional[Tokenizer] = None,
        model_path: Optional[str] = None,
        tokenizer_path: Optional[str] = None,
        dtype: str = "bfloat16",
        quant: str = "",
        kv_quant: str = "",
        max_seq_len: int = 1024,
        prefill_buckets: tuple = (64, 128, 256, 512, 1024),
        top_k: int = 0,
        top_p: float = 1.0,
        attn_impl: str = "auto",
        moe_impl: str = "auto",
        prefix_cache: bool = True,
        mesh_shape: str = "",
        dcn_mesh_shape: str = "",
        seed: int = 0,
    ):
        self.model_cfg = model_cfg
        self.model_path = model_path
        self.tokenizer_path = tokenizer_path
        self.dtype = _dtype_from_str(dtype)
        if quant not in ("", "int8", "int4"):
            raise ValueError(
                f"QUANT must be ''|int8|int4, got {quant!r}")
        self.quant = quant
        if kv_quant not in ("", "int8"):
            raise ValueError(
                f"KV_QUANT must be '' or 'int8', got {kv_quant!r}")
        self.kv_quant = kv_quant
        self.max_seq_len = min(max_seq_len, model_cfg.max_seq_len)
        self.prefill_buckets = tuple(
            b for b in sorted(prefill_buckets) if b <= self.max_seq_len
        ) or (self.max_seq_len,)
        if attn_impl not in ("auto", "dense", "flash"):
            raise ValueError(
                f"ATTN_IMPL must be auto|dense|flash, got {attn_impl!r}"
            )
        if attn_impl == "auto":
            # Flash avoids materializing S×S logits in HBM; prefer it on
            # TPU. Off-TPU the kernel would run interpreted — use XLA dense.
            attn_impl = "flash" if jax.default_backend() == "tpu" else "dense"
        self.attn_impl = attn_impl
        if moe_impl not in ("auto", "ep", "dense"):
            raise ValueError(
                f"MOE_IMPL must be auto|ep|dense, got {moe_impl!r}")
        self.moe_impl = moe_impl
        self.use_prefix_cache = prefix_cache
        self.mesh_shape = mesh_shape
        self.dcn_mesh_shape = dcn_mesh_shape
        self.mesh = None               # built in _start_blocking
        self._weights_shard_fraction = 1.0   # measured in _load
        self._weights_init = {"s": None, "sharded": False}   # seeded init
        self._weights_bytes_per_device: list = []   # measured in _load
        self.seed = seed

        self.tokenizer = tokenizer
        self.params = None
        # Weight rollout (ISSUE 13): the checkpoint version this engine
        # serves (content fingerprint — engine/rollout.py), stamped into
        # /health per replica, echoed as X-Model-Version, and the pin
        # key for cross-replica migration (cross-version replay cannot
        # be byte-identical). checkpoint_path tracks the path the live
        # params came from so a rollback knows what to restore.
        self.weights_version = ""
        self.checkpoint_path = model_path
        self._ready = False
        self._shutdown = False
        self._ladder_thread: Optional[threading.Thread] = None
        self._lock: Optional[asyncio.Lock] = None
        self._gen_inflight = 0       # accepted requests incl. lock waiters
                                     # (stop()'s drain obligation)
        self._prefill_fns = {}
        self._suffix_prefill_fns = {}  # (bucket, kv_limit) -> jitted prefill
        self._ring_prefill_fns = {}    # S_pad -> jitted ring prefill
        self._chunk_fns = {}   # (chunk_len, kv_limit) -> jitted decode chunk
        # Subset of _chunk_fns that has EXECUTED at least once (compile
        # done). Dispatch consults only this dict, so a live request can
        # never pick up a program the background ladder warm has built but
        # not yet compiled and stall on its compile mid-request (the
        # batcher's _batch_ready pattern, ADVICE r3 medium).
        self._warm_chunk_fns = {}
        # Decode-attention cost tracks the live KV span, not max_seq:
        # dispatch picks the smallest ladder bucket covering the positions
        # a chunk can reach (kv_bucket_ladder; batcher has its own ladder
        # topped by S_alloc).
        self._kv_buckets = kv_bucket_ladder(self.max_seq_len)
        # top-k / top-p are STATIC service config (changing them
        # recompiles — the right trade; engine/sampling.py) applied
        # identically by this engine and the batched scheduler.
        if top_k < 0 or not (0.0 < top_p <= 1.0):
            raise ValueError(
                f"TOP_K must be >= 0 and TOP_P in (0, 1], got "
                f"{top_k}/{top_p}")
        self.top_k = top_k
        self.top_p = top_p
        self._sample_fn = jax.jit(partial(
            sample_token_traced, top_k=top_k, top_p=top_p))
        self._prefix = None            # PrefixKV once built
        self._splice_prefix_fn = None

    #: decode chunk sizes (tokens per device dispatch), largest first. The
    #: scheduler greedily decomposes the remaining budget over these, so a
    #: 20-token request runs 8+8+1+1+1+1 rather than a 32-step chunk whose
    #: tail it would block on and throw away.
    CHUNK_SIZES = (32, 8, 1)

    @classmethod
    def from_config(cls, cfg) -> "JaxEngine":
        model_cfg = get_config(cfg.model_name)
        return cls(
            model_cfg,
            model_path=cfg.model_path,
            tokenizer_path=cfg.tokenizer_path,
            dtype=cfg.dtype,
            quant=cfg.quant,
            kv_quant=cfg.kv_quant,
            max_seq_len=cfg.max_seq_len,
            prefill_buckets=cfg.prefill_bucket_list,
            top_k=cfg.top_k,
            top_p=cfg.top_p,
            attn_impl=cfg.attn_impl,
            moe_impl=cfg.moe_impl,
            prefix_cache=cfg.hbm_prefix_cache,
            mesh_shape=cfg.mesh_shape,
            dcn_mesh_shape=cfg.dcn_mesh_shape,
        )

    # ------------------------------------------------------------ startup

    @property
    def ready(self) -> bool:
        return self._ready

    async def start(self) -> None:
        self._shutdown = False   # allow stop() → start() restarts
        await asyncio.to_thread(self._start_blocking)
        self._lock = asyncio.Lock()
        self._ready = True
        # One full generation through the real serving path: catches every
        # lazily-compiled helper (key splits, sliced-logits sampling, ...)
        # that the targeted warmups miss, so the first user request runs at
        # steady-state TTFT. _ready must already be True here (generate()
        # gates on it); start() just doesn't return until warmup is done,
        # and the server awaits start() before accepting traffic.
        # _warming marks the warm-up for QoS fault drills: a one-shot
        # tenant:flood must fire on the first REAL submission, not be
        # consumed (and drained) by the engine's own warm-up request.
        # An engine that cannot generate is not ready: a failure here
        # propagates, so the server exits non-zero instead of reporting a
        # healthy engine whose every request then fails.
        self._warming = True
        try:
            await self.generate("warmup: list pods", max_tokens=2,
                                temperature=0.0)
        except BaseException:
            self._ready = False
            raise
        finally:
            self._warming = False

    def _setup_compile_cache(self) -> None:
        """Keep XLA's persistent compilation cache on for the TPU
        programs, so a warm restart reuses every serving program instead
        of re-compiling it. The directory is placed from outside: where
        JAX_COMPILATION_CACHE_DIR is set JAX has already read it and no
        directory is set here; otherwise one fixed directory inside the
        checkout (config.DEFAULT_COMPILE_CACHE_DIR) — the path is part
        of how a cache entry is found again, so never a home, temp, pid
        or time-stamped name. Also logs the backend once: every kernel
        and attention-regime choice below keys off it."""
        devs = jax.devices()
        logger.info("JAX backend: platform=%s device_kind=%s devices=%d",
                    devs[0].platform, devs[0].device_kind, len(devs))
        # CPU compiles are fast and XLA:CPU AOT artifacts are brittle
        # across flag/feature contexts (observed SIGILL-class crashes when
        # a cached CPU executable is loaded under different XLA flags);
        # the win is the TPU programs, so persist only off-CPU.
        if jax.default_backend() == "cpu":
            return
        import os

        from ..config import DEFAULT_COMPILE_CACHE_DIR

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_COMPILE_CACHE_DIR)
        # Default threshold skips sub-second compiles; serving has many
        # small programs whose aggregate dominates startup.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
        logger.info("Persistent compilation cache: %s",
                    jax.config.jax_compilation_cache_dir)

    def _setup_mesh(self) -> None:
        """Build the serving mesh from MESH_SHAPE (VERDICT r2 item 1).

        Empty spec or a 1-device mesh keeps ``self.mesh = None`` — every
        program then compiles exactly as on a plain single chip (strict
        no-op parity). A multi-device spec builds the mesh over the first
        ``n`` devices; params, caches, and scheduler state are then placed
        with the PartitionSpec policy in parallel/sharding.py, and every
        jitted serving program inherits those shardings (XLA inserts the
        TP/EP collectives over ICI)."""
        from ..parallel.mesh import MeshConfig, build_mesh

        spec = (self.mesh_shape or "").strip()
        dcn_spec = (self.dcn_mesh_shape or "").strip()
        force_ep_mesh = self.moe_impl == "ep" and self.model_cfg.is_moe
        if not spec and not dcn_spec and not force_ep_mesh:
            return
        mesh_cfg = MeshConfig.parse(spec)
        dcn_cfg = MeshConfig.parse(dcn_spec) if dcn_spec else None
        total = mesh_cfg.n_devices * (dcn_cfg.n_devices if dcn_cfg else 1)
        if total == 1 and not force_ep_mesh:
            return
        if total == 1:
            # MOE_IMPL=ep on a single device: build the 1-device mesh the
            # dispatch path needs — the all_to_alls degenerate to local
            # copies, so the REAL expert-parallel program (not the dense
            # all-experts evaluation) serves and gets benched on one chip
            # (VERDICT r4 item 3).
            logger.info("MOE_IMPL=ep: building 1-device expert mesh")
        n_pipe = mesh_cfg.pipe * (dcn_cfg.pipe if dcn_cfg else 1)
        if n_pipe > 1 and self.model_cfg.n_layers % n_pipe:
            raise ValueError(
                f"MESH_SHAPE pipe={n_pipe} does not divide "
                f"{self.model_cfg.name}'s {self.model_cfg.n_layers} layers"
            )
        if n_pipe > 1 and self.model_cfg.is_moe and self.moe_impl == "ep":
            # The operator explicitly forced the dispatch path; serving
            # the dense evaluation instead would be a silent lie.
            raise ValueError(
                "MOE_IMPL=ep does not compose with a pipe mesh axis: the "
                "EP all-to-all dispatch can't nest under the pipeline "
                "stage shard_map. Use ep×tp without pp (MoE models "
                "shard better over expert+model than pipe), or drop "
                "MOE_IMPL to auto to accept dense per-stage experts."
            )
        if n_pipe > 1 and self.model_cfg.is_moe and mesh_cfg.expert > 1:
            # Inside a pipeline stage MoE layers evaluate densely (the EP
            # all-to-all dispatch doesn't nest under the pipe shard_map):
            # ~n_experts/top_k × the routed MLP FLOPs. Loud, not silent.
            logger.warning(
                "pipe>1 disables expert-parallel MoE dispatch: MoE layers "
                "run dense (all experts) inside each pipeline stage; "
                "prefer ep×tp without pp for MoE serving"
            )
        devices = jax.devices()
        if total > len(devices):
            raise ValueError(
                f"MESH_SHAPE={spec!r} DCN_MESH_SHAPE={dcn_spec!r} wants "
                f"{total} devices; only {len(devices)} present"
            )
        self.mesh = build_mesh(mesh_cfg, devices[:total], dcn=dcn_cfg)
        if (n_pipe > 1 and jax.default_backend() == "cpu"
                and self.dtype == jnp.bfloat16):
            # XLA:CPU hard-aborts ("Invalid binary instruction opcode
            # copy", hlo_instruction.cc) compiling the pipelined stage body
            # with emulated bf16. CPU + pipe is a dev/emulation config
            # only — force f32 there instead of crashing the process; on
            # TPU bf16 is native and unaffected.
            logger.warning(
                "CPU emulation of a pipe mesh cannot compile bf16; "
                "forcing float32 params for this dev configuration"
            )
            self.dtype = jnp.float32

    def sharding_health(self) -> Optional[dict]:
        """Cheap sharding view for /health (ISSUE 14): mesh shape,
        device count, and the residual TP fraction at this engine's
        decode shape. The single-sequence engine decodes B=1 (the
        residual can't batch-shard), has no pool and therefore no
        fallback to report; the batched engine overrides with the pool
        flags."""
        if self.mesh is None:
            return None
        from ..parallel.sharding import residual_fraction

        return {
            "mesh": {a: int(s) for a, s in self.mesh.shape.items()},
            "devices": int(self.mesh.size),
            "residual_tp_fraction": residual_fraction(
                self.mesh, 1, self.model_cfg.dim),
            "weights_shard_fraction": self._weights_shard_fraction,
            **self._weights_health(),
            "pool_sharded": False,
            "kv_pool_mesh_fallback": False,
            "draft_sharded": False,
            "draft_kv_fallback": False,
        }

    @staticmethod
    def _to_host_async(arr) -> None:
        """Start the device→host copy of ``arr`` without blocking. The
        blocking read that eventually consumes it then finds the data
        local: the DMA overlaps device compute and other transfers.
        Best-effort: a backend without the API just pays at read time."""
        try:
            arr.copy_to_host_async()
        except Exception:  # pragma: no cover - backend-dependent
            pass

    def _fetch(self, arr) -> np.ndarray:
        """THE device→host read. Every consumed pipeline entry performs
        exactly one of these — the batcher's packed chunk buffers exist
        so tokens, termination, and occupancy share it (tests assert the
        one-fetch-per-chunk invariant by counting calls here)."""
        return np.asarray(arr)

    def _new_cache(self, batch: int, max_seq: Optional[int] = None) -> KVCache:
        """Fresh KV cache, placed per the mesh policy when sharded serving
        is on (batch over ``data``, KV heads over ``model``)."""
        cache = KVCache.zeros(self.model_cfg, batch, max_seq or self.max_seq_len,
                              dtype=self.dtype, kv_quant=self.kv_quant)
        if self.mesh is not None:
            from ..parallel.sharding import shard_cache

            cache = shard_cache(cache, self.mesh, self.model_cfg)
        return cache

    @property
    def _quantize_embed(self) -> bool:
        """int8 embedding (per-row scales) rides with QUANT=int8/int4. On
        tied-embedding models (Gemma) this halves the LM head's per-step
        weight read; on all models it halves embedding HBM. Under a mesh
        the QuantInt8 leaf shards exactly like the bf16 embedding
        (vocab rows over ``model``; shard_params sanitizes the [V, 1]
        scale with the same spec). The embedding stays int8 under
        QUANT=int4: the gather is row-wise and the tied head wants one
        scale per vocab row — both per-row-int8-shaped concerns."""
        return self.quant in ("int8", "int4")

    def _load(self) -> None:
        """Tokenizer + weights (checkpoint or random init). Shared by the
        single-sequence and batched engines."""
        if (self.quant == "int4" and self.mesh is not None
                and self.mesh.size > 1):
            # The packed-nibble matmul is a pallas_call, which XLA can't
            # auto-partition under a MULTI-device mesh (the paged kernel
            # needed an explicit shard_map for the same reason). int4 is
            # the single-chip density lever; sharded serving falls back
            # to int8 — already half bytes per shard, and the TP weight
            # split divides the stream further. A 1-device mesh (e.g. the
            # forced MOE_IMPL=ep expert mesh) runs int4 fine: nothing is
            # actually partitioned.
            logger.warning("QUANT=int4 does not compose with a multi-"
                           "device mesh; serving int8 weights instead")
            self.quant = "int8"
        if self.kv_quant and self.attn_impl == "flash":
            # flash_attention_cached is a pallas_call: its operands must be
            # materialized arrays, so an int8 context would be dequantized
            # into a full [B, kv_limit, KV, hd] bf16 copy per layer per
            # prefill chunk — exactly the HBM transient int8 KV exists to
            # avoid. XLA dense attention fuses the convert+scale into the
            # score matmul's operand read instead, and at the short
            # single-chip buckets int8-KV serving uses, dense prefill is
            # not the bottleneck.
            logger.info("KV_QUANT=int8: prefill attention uses dense "
                        "(fusable dequant) instead of flash")
            self.attn_impl = "dense"
        if self.tokenizer is None:
            self.tokenizer = load_tokenizer(self.model_cfg, self.tokenizer_path)
        if self.params is None:
            if self.model_path:
                from ..models.convert import convert_hf_checkpoint

                logger.info("Loading checkpoint from %s (quant=%s)",
                            self.model_path, self.quant or "-")
                # Quantization happens DURING the streaming load (one
                # layer at a time): a 7B bf16 tree (~17 GB) would OOM the
                # chip before a post-hoc quantize could run.
                self.params = convert_hf_checkpoint(
                    self.model_cfg, self.model_path, dtype=self.dtype,
                    quant=self.quant,
                    quantize_embed=self._quantize_embed,
                )
                if self.quant:
                    self._quantized = True
            else:
                logger.warning(
                    "No MODEL_PATH; random-initializing %s (toy/dev mode)",
                    self.model_cfg.name,
                )
                if self.quant in ("int8", "int4"):
                    # A 7B-class bf16 init (~17 GB) would OOM the chip
                    # before quantization ever runs; init directly in
                    # quantized form on device (_seeded_quantized_params:
                    # same tree structure/shapes as a quantized
                    # checkpoint, no full-precision materialization
                    # anywhere, and over a mesh no leaf whole on one
                    # device).
                    self.params = self._seeded_quantized_params(self.seed)
                    self._quantized = True
                else:
                    self.params = init_params(
                        jax.random.PRNGKey(self.seed), self.model_cfg,
                        dtype=self.dtype,
                    )
        if (self.quant in ("int8", "int4")
                and not getattr(self, "_quantized", False)):
            if self.quant == "int4":
                from ..ops.quant4 import quantize_params_int4 as _qp
            else:
                from ..ops.quant import quantize_params_int8 as _qp

            self.params = _qp(
                self.params, quantize_embed=self._quantize_embed)
            self._quantized = True
            logger.info(
                "Weights quantized to %s (weight-only%s)", self.quant,
                "; embedding per-row int8" if self._quantize_embed else "")
        if self.mesh is not None:
            from ..parallel.sharding import shard_params

            self.params = shard_params(self.params, self.mesh, self.model_cfg)
            # The share of the stacked query projection one device holds
            # — 1/tp when the Megatron split really placed the weights,
            # 1.0 when they replicated. Read off the live array, not the
            # policy; /health reports it (sharding.weights_shard_fraction).
            wq = self.params["layers"]["wq"]
            leaf = getattr(wq, "q", wq)
            self._weights_shard_fraction = (
                leaf.addressable_shards[0].data.size / leaf.size)
            logger.info("Params sharded over mesh %s (one device holds "
                        "%.4f of wq)", dict(self.mesh.shape),
                        self._weights_shard_fraction)
            self._note_weights_placement()
        if not self.weights_version:
            # Version the weights we ended up serving: checkpoint paths
            # fingerprint by content manifest; dev random-init versions
            # by (model, seed) so two toy replicas built alike share a
            # version (cross-replica byte-identity holds). A swap that
            # already stamped a version keeps it across restarts. The
            # dev sentinel doubles as a RESTORABLE checkpoint path —
            # _load_swap_params parses its seed back out, so a rollback
            # onto it re-derives the exact original random init.
            from .rollout import checkpoint_version

            dev_id = (f"dev:{self.model_cfg.name}:seed={self.seed}"
                      f":quant={self.quant}")
            if not self.checkpoint_path:
                self.checkpoint_path = self.model_path or dev_id
            self.weights_version = checkpoint_version(
                self.model_path or dev_id)

    def swap_weights(self, path: str, *, version: Optional[str] = None
                     ) -> str:
        """Swap the served checkpoint IN PLACE on a stopped (drained)
        engine — the rollout tentpole's mechanism (engine/rollout.py).

        The swap is ATOMIC and program-preserving:

        - the new params load fully (and are validated against the live
          tree's structure/shapes/dtypes) BEFORE the old tree is
          released — any failure raises :class:`CheckpointCorrupt` and
          the engine keeps serving the prior weights on restart;
        - only ``self.params`` changes. Every compiled program set
          (prefill buckets, decode chunks, splice/arm/COW) takes params
          as a traced argument of unchanged shape, so the restart after
          a swap re-executes warm programs — zero re-trace, no
          multi-second first-request compile (asserted in
          tests/test_rollout.py).

        A path that exists loads through the normal checkpoint
        converter; a path that does not exist serves random-init
        weights keyed on the path (toy/dev mode, mirroring _load's
        MODEL_PATH-less behaviour) so rollout drills run without a real
        17 GB checkpoint on disk."""
        from .rollout import CheckpointCorrupt, RolloutError, SwapFailed, \
            checkpoint_version

        if self._ready:
            raise RolloutError(
                "swap_weights requires a stopped (drained) engine")
        version = version or checkpoint_version(path)
        faults = getattr(self, "faults", None)
        if faults is not None and hasattr(faults, "checkpoint_corrupt") \
                and faults.checkpoint_corrupt():
            raise CheckpointCorrupt(
                f"checkpoint {path!r} failed integrity validation "
                f"(injected checkpoint:corrupt drill)")
        old = self.params
        try:
            new_params = self._load_swap_params(path)
        except CheckpointCorrupt:
            raise
        except Exception as e:
            raise CheckpointCorrupt(
                f"checkpoint {path!r} failed to load: "
                f"{type(e).__name__}: {e}") from e
        if old is not None:
            try:
                import jax as _jax

                match = _jax.tree_util.tree_all(_jax.tree_util.tree_map(
                    lambda a, b: (getattr(a, "shape", None)
                                  == getattr(b, "shape", None)
                                  and getattr(a, "dtype", None)
                                  == getattr(b, "dtype", None)),
                    old, new_params))
            except (ValueError, TypeError):
                match = False
            if not match:
                # Wrong model/geometry: swapping it in would invalidate
                # every compiled program (and likely OOM). Reject at
                # load — the serving tree is untouched.
                raise CheckpointCorrupt(
                    f"checkpoint {path!r} does not match the serving "
                    f"model's parameter tree "
                    f"({self.model_cfg.name}, quant={self.quant or '-'})")
        if faults is not None and hasattr(faults, "swap_fail") \
                and faults.swap_fail():
            # Mid-swap death: in a real buffer-donating swap the old
            # tree is already released here. Model that honestly — the
            # replica has NO servable weights until re-swapped, and its
            # version/path stamps are cleared WITH the params: a later
            # restart re-loads from MODEL_PATH and re-stamps truthfully
            # in _load, instead of serving those bytes under the stale
            # pre-swap version (which would let version-pinned failover
            # splice established streams onto the wrong weights).
            self.params = None
            self.weights_version = ""
            self.checkpoint_path = None
            raise SwapFailed(
                "injected swap:fail — replica died mid-swap")
        if self.mesh is not None:
            from ..parallel.sharding import shard_params

            new_params = shard_params(new_params, self.mesh,
                                      self.model_cfg)
        self.params = new_params
        if self.mesh is not None:
            self._note_weights_placement()
        self.weights_version = version
        self.checkpoint_path = path
        logger.info("weights swapped: %s now serves version %s (%s)",
                    self.model_cfg.name, version, path)
        return version

    def _load_swap_params(self, path: str):
        """Load (or dev-init) a parameter tree for ``swap_weights``
        without touching the live ``self.params``."""
        import os
        import zlib as _zlib

        import jax as _jax

        if not path or not str(path).strip():
            from .rollout import CheckpointCorrupt

            raise CheckpointCorrupt("swap needs a checkpoint path")
        path = str(path)
        if os.path.exists(path):
            from ..models.convert import convert_hf_checkpoint

            logger.info("Loading swap checkpoint from %s (quant=%s)",
                        path, self.quant or "-")
            return convert_hf_checkpoint(
                self.model_cfg, path, dtype=self.dtype,
                quant=self.quant,
                quantize_embed=self._quantize_embed)
        # Dev/toy mode: a named-but-absent checkpoint serves random-init
        # weights keyed on the path, so "swap to v2" is reproducible and
        # genuinely different from v1 — the same contract _load applies
        # to a missing MODEL_PATH.
        logger.warning(
            "Swap checkpoint %s does not exist; random-initializing %s "
            "keyed on the path (toy/dev mode)", path,
            self.model_cfg.name)
        # A "dev:...:seed=N:..." sentinel (what _load records for a
        # MODEL_PATH-less start) re-derives the EXACT original init —
        # rolling back onto it is byte-identical restoration; any other
        # absent path keys its init on the path string.
        import re as _re

        m = _re.search(r":seed=(\d+)", path) \
            if path.startswith("dev:") else None
        seed = (int(m.group(1)) if m
                else _zlib.crc32(path.encode("utf-8", "surrogatepass"))
                & 0x7FFFFFFF)
        if self.quant in ("int8", "int4"):
            return self._seeded_quantized_params(seed)
        return init_params(_jax.random.PRNGKey(seed), self.model_cfg,
                           dtype=self.dtype)

    def _seeded_quantized_params(self, seed: int):
        """The seeded int8/int4 tree of ``_load`` and of a swap onto a
        ``dev:...:seed=N`` sentinel (ops/quant.py::random_params_int8 —
        the same values for the same seed on either path).

        Without a mesh: the generator as it always ran, leaf by leaf on
        the one device. Over a mesh of more than one device: ONE compiled
        call whose outputs are born with ``shard_params``'s shardings
        (``random_params_int8_sharded``), so a device makes and holds
        only its share — a tree that does not fit one chip never passes
        through one. Closes a ``weights_init`` span (wall ms; ``sharded``
        counts the sharded ones) and keeps the seconds for
        /health.sharding. The sharded call is waited for; the one-device
        call is not (its device work overlaps the warm-up compiles, as
        it always did), so there the span is the host's dispatch time."""
        import time as _time

        from ..ops.quant import random_params_int8

        key = jax.random.PRNGKey(seed)
        kw = dict(dtype=self.dtype, quantize_embed=self._quantize_embed,
                  int4=(self.quant == "int4"))
        sharded = self.mesh is not None and self.mesh.size > 1
        t0 = _time.monotonic()
        if sharded:
            from ..ops.quant import random_params_int8_sharded

            params = jax.block_until_ready(random_params_int8_sharded(
                key, self.model_cfg, self.mesh, **kw))
        else:
            params = random_params_int8(key, self.model_cfg, **kw)
        seconds = _time.monotonic() - t0
        self._weights_init = {"s": round(seconds, 3), "sharded": sharded}
        spans = getattr(self, "_spans", None)
        if spans is not None:
            spans.stats.note("weights_init", seconds * 1e3,
                             sharded=int(sharded))
        logger.info("Seeded %s weights made in %.1f s (%s)", self.quant,
                    seconds, "sharded over the mesh" if sharded
                    else "on one device")
        return params

    def _note_weights_placement(self) -> None:
        """Bytes of the live param tree each device of the mesh holds,
        read off the arrays' addressable shards (not the policy): what
        /health.sharding reports as ``weights_bytes_per_device``. Even
        over the ``model`` axis when the Megatron split placed every
        leaf; a replicated leaf counts whole on each device."""
        held = {d.id: 0 for d in self.mesh.devices.flat}
        for leaf in jax.tree_util.tree_leaves(self.params):
            for shard in getattr(leaf, "addressable_shards", ()):
                if shard.device.id in held:
                    held[shard.device.id] += int(shard.data.nbytes)
        self._weights_bytes_per_device = list(held.values())

    def _weights_health(self) -> dict:
        """The weights' part of /health.sharding (host attributes only):
        how long the seeded tree took to make and whether it was made
        sharded (None / False for a checkpoint), and each device's bytes."""
        return {
            "weights_init_s": self._weights_init["s"],
            "weights_init_sharded": self._weights_init["sharded"],
            "weights_bytes_per_device": self._weights_bytes_per_device,
        }

    def _prefill_impl_for(self, q_len: int, kv_len: int) -> str:
        """attn impl for a prefill shape, with per-shape dense fallback
        when the flash kernel can't tile it (e.g. PREFILL_BUCKETS=192 or
        head_dim 64)."""
        from ..ops.flash_attention import flash_supported

        impl = self.attn_impl
        if impl == "flash" and not flash_supported(
            q_len, kv_len, self.model_cfg.head_dim
        ):
            logger.warning(
                "Prefill %dq/%dkv: shapes not flash-tileable, using dense",
                q_len, kv_len,
            )
            impl = "dense"
        return impl

    def _build_prefill_fns(self) -> None:
        cfg = self.model_cfg

        def prefill(params, tokens, positions, cache, mask, *, kv_limit, impl):
            # mask [1, bucket]: 1 for prompt tokens, 0 for bucket padding —
            # padding must never consume MoE expert capacity. Its row sums
            # also locate the last valid token, so the LM head projects
            # only that position ([B, 1, vocab] out — see forward()).
            last = jnp.maximum(mask.sum(axis=1).astype(jnp.int32) - 1, 0)
            return forward(params, cfg, tokens, positions, cache,
                           kv_limit=kv_limit, attn_impl=impl, mesh=self.mesh,
                           moe_impl=self.moe_impl,
                           token_mask=mask, logits_at=last)

        self._prefill_raw = prefill
        for b in self.prefill_buckets:
            if b in self._prefill_fns:
                # stop() → start() restarts (weight swaps, fleet
                # rejoins) keep the already-jitted program: params are a
                # traced argument of unchanged shape, so reuse means the
                # first post-swap request never re-compiles.
                continue
            impl = self._prefill_impl_for(b, b)
            self._prefill_fns[b] = jax.jit(
                partial(prefill, kv_limit=b, impl=impl), donate_argnums=(3,)
            )
            # The (bucket, kv_limit=bucket) suffix program is semantically
            # the standard prefill — share the compiled program so chunked
            # prefill's first chunk never re-compiles it.
            self._suffix_prefill_fns[(b, b)] = self._prefill_fns[b]

    def _get_suffix_prefill_fn(self, bucket: int, kv_limit: int):
        """Prefill program for a prefix-cache suffix: queries are one
        ``bucket`` of suffix tokens at offset positions, attending over
        ``[0, kv_limit)`` (prefix + suffix span, tile-rounded)."""
        key = (bucket, kv_limit)
        fn = self._suffix_prefill_fns.get(key)
        if fn is None:
            impl = self._prefill_impl_for(bucket, kv_limit)
            fn = jax.jit(
                partial(self._prefill_raw, kv_limit=kv_limit, impl=impl),
                donate_argnums=(3,),
            )
            self._suffix_prefill_fns[key] = fn
        return fn

    def _init_prefix_cache(self) -> None:
        """Prefill the shared system prompt once and keep its KV in HBM
        (engine/prefix_cache.py; the TTLCache analog of app.py:124-125).
        Called from _start_blocking after the prefill programs exist."""
        if not self.use_prefix_cache:
            return
        from .prefix_cache import PrefixKV, round_kv_limit
        from .prompts import SYSTEM_PROMPT

        cfg = self.model_cfg
        ids = self.tokenizer.encode(SYSTEM_PROMPT)
        P = len(ids)
        if P + self.prefill_buckets[0] > self.max_seq_len:
            logger.warning(
                "Prefix cache disabled: system prompt is %d tokens; no room "
                "for a suffix bucket within max_seq %d",
                P, self.max_seq_len,
            )
            return
        bucket = next((b for b in self.prefill_buckets if b >= P), None)
        if bucket is not None:
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :P] = ids
            positions = np.broadcast_to(np.arange(bucket),
                                        (1, bucket)).astype(np.int32)
            cache = self._new_cache(1)
            mask = (np.arange(bucket) < P)[None, :].astype(np.float32)
            _, cache = self._prefill_fns[bucket](
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                cache, jnp.asarray(mask),
            )
        else:
            # System prompt exceeds the largest bucket (byte-level
            # tokenizers): build the prefix in sequential chunks — the
            # round-2 "silent no-op" case, now served.
            _, cache, _ = self._prefill_chunked(list(ids))
        # Trim to the true prefix length: the padding slots' garbage K/V is
        # never copied into request caches. (tree-mapped helpers: the K/V
        # blocks are plain arrays or QuantKV, ops/quant.py.)
        from ..ops.quant import kv_prefix_trim, kv_tokens, kv_update_slice

        self._prefix = PrefixKV(ids=list(ids), k=kv_prefix_trim(cache.k, P),
                                v=kv_prefix_trim(cache.v, P))

        def splice_prefix(cache, pk, pv):
            # named_scope: the decode-step/TTFT attribution (obs/
            # attribution.py) bills this dispatch as kv_write_splice.
            with jax.named_scope("kv_splice"):
                k = kv_update_slice(cache.k, pk)
                v = kv_update_slice(cache.v, pv)
                lengths = jnp.full_like(cache.lengths, kv_tokens(pk))
            return KVCache(k=k, v=v, lengths=lengths)

        if self._splice_prefix_fn is None:   # restarts keep the program
            self._splice_prefix_fn = jax.jit(splice_prefix,
                                             donate_argnums=(0,))

        # Warm the smallest suffix program — it is the TTFT path for every
        # cache-hitting request.
        sbucket = self.prefill_buckets[0]
        kv_limit = round_kv_limit(P + sbucket, self.max_seq_len)
        if kv_limit is not None:
            scratch = self._new_cache(1)
            scratch = self._splice_prefix_fn(scratch, self._prefix.k,
                                             self._prefix.v)
            spos = np.broadcast_to(P + np.arange(sbucket),
                                   (1, sbucket)).astype(np.int32)
            logits, _ = self._get_suffix_prefill_fn(sbucket, kv_limit)(
                self.params, jnp.zeros((1, sbucket), jnp.int32),
                jnp.asarray(spos), scratch,
                jnp.ones((1, sbucket), jnp.float32),
            )
            logits.block_until_ready()
        logger.info("Prefix-KV cache ready: %d tokens resident in HBM", P)

    def _start_blocking(self) -> None:
        t0 = time.monotonic()
        self._setup_compile_cache()
        self._setup_mesh()
        self._load()
        self._build_prefill_fns()
        self._init_prefix_cache()
        cfg = self.model_cfg

        # Warm-up compile on the smallest bucket so the first request
        # doesn't pay full compilation (SURVEY.md §3.3: init is where the
        # heavy lifting moves).
        b = self.prefill_buckets[0]
        tokens = jnp.zeros((1, b), jnp.int32)
        positions = jnp.broadcast_to(jnp.arange(b), (1, b))
        cache = self._new_cache(1)
        _, cache = self._prefill_fns[b](self.params, tokens, positions, cache,
                                        jnp.ones((1, b), jnp.float32))
        step_tokens = jnp.zeros((1, 1), jnp.int32)
        step_pos = jnp.full((1, 1), b, jnp.int32)
        key = jax.random.PRNGKey(0)
        # Warm every chunk size at the TOP KV bucket (temperature is
        # traced — one compile per shape serves all temperatures, so no
        # first-request compile stall). The top-bucket program is always a
        # correct fallback for any live span; the smaller ladder variants
        # compile in a background thread (_warm_ladder_chunks) so cold
        # start stays at 3 decode compiles, not 3 × |ladder|.
        temp0 = jnp.asarray(0.0, jnp.float32)
        for chunk_len in self.CHUNK_SIZES:
            fn = self._get_chunk_fn(chunk_len, self.max_seq_len)
            toks, _, _, cache, _, _ = fn(self.params, step_tokens,
                                         step_pos, cache, key, temp0,
                                         jnp.asarray(False))
        # Warm the first-token sampler too — it sits on the TTFT path.
        self._sample_fn(
            jnp.zeros((1, cfg.vocab_size), jnp.float32), key, temp0
        ).block_until_ready()
        toks.block_until_ready()
        # Everything above has now compiled AND executed — publish the
        # top-bucket programs for dispatch (the always-warm fallback).
        for chunk_len in self.CHUNK_SIZES:
            key_top = (chunk_len, self.max_seq_len)
            self._warm_chunk_fns[key_top] = self._chunk_fns[key_top]
        self._ladder_thread = threading.Thread(
            target=self._warm_ladder_chunks, name="ladder-warm", daemon=True
        )
        self._ladder_thread.start()
        logger.info(
            "Engine ready: %s (%.1fM params, %s, buckets=%s) in %.1fs",
            cfg.name, cfg.param_count() / 1e6, np.dtype(self.dtype).name,
            self.prefill_buckets, time.monotonic() - t0,
        )

    def _warm_ladder_chunks(self) -> None:
        """Background-compile the sub-top KV-ladder decode programs (one
        chunk of garbage decode each on scratch state — negligible device
        time). Each variant is published to ``_warm_chunk_fns`` only after
        its first execution completes, so dispatch can never pick up a
        still-cold program and block on its compile mid-request. Until a
        variant lands, dispatch falls back to the always-warm top-bucket
        program, which is numerically identical (masked lanes contribute
        exact zeros), just wider."""
        try:
            cache = self._new_cache(1)
            tok = jnp.zeros((1, 1), jnp.int32)
            pos = jnp.zeros((1, 1), jnp.int32)
            key = jax.random.PRNGKey(1)
            temp0 = jnp.asarray(0.0, jnp.float32)
            for kv_b in self._kv_buckets[:-1]:
                for chunk_len in self.CHUNK_SIZES:
                    if self._shutdown:
                        return
                    fn = self._get_chunk_fn(chunk_len, kv_b)
                    toks, _, _, cache, _, _ = fn(self.params, tok, pos, cache,
                                                 key, temp0, jnp.asarray(False))
                    toks.block_until_ready()
                    self._warm_chunk_fns[(chunk_len, kv_b)] = fn
            self._warm_chunked_prefill_offsets()
        except Exception:  # pragma: no cover - warm is best-effort
            logger.exception("ladder warm failed; top-bucket fallback stays")

    def _warm_chunked_prefill_offsets(self) -> None:
        """Background-compile the prefill programs startup skips: the
        non-smallest standard buckets (startup eagerly warms only
        ``prefill_buckets[0]``; a first mid-size prompt otherwise pays a
        several-second compile) and the multi-offset suffix programs
        ``_prefill_chunked`` dispatches for long prompts. Cold, a
        4k-token request measured ~19 s of serial compiles (r4, 2B @
        max_seq 4096); warmed, it pays device time only (~270 ms).
        Called from BOTH background warm threads (the single-sequence
        ladder warm and the batcher's admission warm — the batched engine
        does not run the former). Concurrent foreground compiles of the
        same shape are safe (jit compiles once)."""
        from .prefix_cache import round_kv_limit

        scratch = self._new_cache(1)
        for bucket in self.prefill_buckets[1:]:
            if self._shutdown:
                return
            logits, scratch = self._prefill_fns[bucket](
                self.params, jnp.zeros((1, bucket), jnp.int32),
                jnp.broadcast_to(jnp.arange(bucket),
                                 (1, bucket)).astype(jnp.int32),
                scratch, jnp.ones((1, bucket), jnp.float32))
            logits.block_until_ready()
        big = self.prefill_buckets[-1]
        if big >= self.max_seq_len:
            return
        tokens = jnp.zeros((1, big), jnp.int32)
        mask = jnp.ones((1, big), jnp.float32)
        # Two offset ladders: plain chunked prefill starts at 0; the
        # default prefix-cache path continues from start=P, whose
        # kv_limits are P-shifted and therefore DIFFERENT compiled
        # programs (round_kv_limit tiles at 128). Only a final
        # partial chunk whose remainder picks a smaller bucket stays
        # cold — one compile instead of the whole ladder.
        starts = {0}
        if self._prefix is not None:
            starts.add(self._prefix.n)
        for start in sorted(starts):
            for offset in range(start + big if start == 0 else start,
                                self.max_seq_len, big):
                if self._shutdown:
                    return
                kvl = (round_kv_limit(offset + big, self.max_seq_len)
                       or self.max_seq_len)
                positions = jnp.broadcast_to(
                    offset + jnp.arange(big), (1, big)).astype(jnp.int32)
                logits, scratch = self._get_suffix_prefill_fn(big, kvl)(
                    self.params, tokens, positions, scratch, mask)
                logits.block_until_ready()

    async def stop(self, drain_secs: float = 0.0) -> None:
        self._ready = False          # new generate() calls now 503
        if drain_secs > 0 and self._lock is not None:
            # Drain on the waiter/in-flight COUNT, not _lock.locked():
            # requests already accepted and queued on the lock are part of
            # the drain obligation, and polling the lock could sample a
            # release→acquire handoff gap and end the drain while waiters
            # remain (ADVICE r4). A concurrent stop(0) — the second-signal
            # force path — sets _shutdown and short-circuits the wait.
            deadline = time.monotonic() + drain_secs
            while (self._gen_inflight > 0 and not self._shutdown
                   and time.monotonic() < deadline):
                await asyncio.sleep(0.05)
        self._shutdown = True
        if self._ladder_thread is not None:
            # A compile in flight at interpreter teardown aborts the
            # process; wait it out (flag stops the loop at the next shape).
            await asyncio.to_thread(self._ladder_thread.join, 60.0)
            self._ladder_thread = None

    # ----------------------------------------------------------- generate

    def _bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"Prompt of {n} tokens exceeds the largest prefill bucket "
            f"{self.prefill_buckets[-1]}"
        )

    def _get_chunk_fn(self, chunk_len: int, kv_limit: Optional[int] = None):
        """Jitted on-device decode chunk: ``lax.scan`` over ``chunk_len``
        steps (forward one token → sample next), cache donated, attending
        over ``cache[:, :kv_limit]`` (a KV-ladder bucket; default max_seq).

        - **EOS chunk-skip on device**: the scan runs under a ``lax.cond``
          on the incoming ``done`` flag, and ``done`` is recomputed from the
          chunk's outputs — so a speculatively-dispatched chunk that follows
          an EOS costs ~nothing, while the active path keeps full ``scan``
          speed (a dynamic-trip-count ``while_loop`` here measured ~40%
          slower: it defeats XLA's cross-iteration pipelining).
        - **Temperature is traced** (sampling.sample_token_traced): one
          compile per chunk length serves every temperature.

        Returns ``(toks [B, T] (all -1 when skipped), tok [B,1], pos [B,1],
        cache, key, done)``. Tokens after a mid-chunk EOS are garbage the
        host discards — only the cross-chunk ``done`` flag matters.

        Single-sequence only (B == 1, asserted at trace time): ``done`` is a
        scalar, so a batched caller would have one sequence's EOS cancel the
        whole batch. The continuous-batching scheduler has its own step fn
        with per-slot done masking."""
        if kv_limit is None:
            kv_limit = self.max_seq_len
        fn = self._chunk_fns.get((chunk_len, kv_limit))
        if fn is not None:
            return fn
        cfg = self.model_cfg
        eos_arr = jnp.asarray(cfg.eos_ids, jnp.int32)

        def decode_chunk(params, tok, pos, cache, key, temperature, done):
            assert tok.shape[0] == 1, "chunk fn is single-sequence (B==1)"
            def run(operand):
                tok, pos, cache, key = operand

                def body(carry, _):
                    tok, pos, cache, key = carry
                    logits, cache = forward(params, cfg, tok, pos, cache,
                                            kv_limit=kv_limit,
                                            attn_impl="dense", mesh=self.mesh,
                                            moe_impl=self.moe_impl)
                    key, sub = jax.random.split(key)
                    nxt = sample_token_traced(logits[:, 0], sub,
                                              temperature,
                                              top_k=self.top_k,
                                              top_p=self.top_p)
                    return (nxt[:, None], pos + 1, cache, key), nxt

                (tok, pos, cache, key), toks = jax.lax.scan(
                    body, (tok, pos, cache, key), None, length=chunk_len
                )
                new_done = jnp.any(toks[..., None] == eos_arr)
                return jnp.swapaxes(toks, 0, 1), tok, pos, cache, key, new_done

            def skip(operand):
                tok, pos, cache, key = operand
                toks = jnp.full((tok.shape[0], chunk_len), -1, jnp.int32)
                return toks, tok, pos, cache, key, jnp.asarray(True)

            return jax.lax.cond(done, skip, run, (tok, pos, cache, key))

        fn = jax.jit(decode_chunk, donate_argnums=(3,))
        self._chunk_fns[(chunk_len, kv_limit)] = fn
        return fn

    def _prefill_prompt(self, prompt_ids, max_tokens: int):
        """Prefill one prompt into a fresh single-slot cache. Returns
        (last_logits [1, V], cache, n_prompt, prefix_hit). Shared by the
        single-sequence path and the batcher's admissions.

        Routing (VERDICT r2 item 5 — no truncation below cache capacity):
        - prompt extends the cached system prefix → suffix-only prefill;
        - fits one bucket → single bucketed prefill;
        - beyond the largest bucket, ``seq`` mesh axis available → ring-
          attention sequence-parallel prefill (one pass, O(S/n) per device);
        - beyond the largest bucket otherwise → chunked sequential prefill
          at absolute offsets (multiple bucket passes).
        Only prompts exceeding the KV capacity itself (max_seq − budget)
        are still left-truncated (the query tail is the informative part).
        """
        max_prompt = self.max_seq_len - max(1, max_tokens)
        if len(prompt_ids) > max_prompt:
            prompt_ids = prompt_ids[-max_prompt:]
        n_prompt = len(prompt_ids)
        if self._prefix is not None and self._prefix.matches(prompt_ids):
            out = self._prefill_suffix(prompt_ids)
            if out is not None:
                return out
        if n_prompt > self.prefill_buckets[-1]:
            if self.mesh is not None and self.mesh.shape["seq"] > 1:
                out = self._prefill_ring(prompt_ids)
                if out is not None:
                    return out
            logits, cache, n = self._prefill_chunked(prompt_ids)
            return logits, cache, n, False
        bucket = self._bucket_for(n_prompt)

        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :n_prompt] = prompt_ids
        # Padding slots keep their natural arange positions: their K/V lands
        # in slots >= n_prompt, which decode steps overwrite before any
        # query can attend to them (mask is kv_pos <= q_pos).
        positions = np.broadcast_to(np.arange(bucket), (1, bucket)).astype(np.int32)

        cache = self._new_cache(1)
        mask = (np.arange(bucket) < n_prompt)[None, :].astype(np.float32)
        logits, cache = self._prefill_fns[bucket](
            self.params, jnp.asarray(tokens), jnp.asarray(positions), cache,
            jnp.asarray(mask),
        )
        # forward() records lengths from max(positions); restore the true
        # prompt length so downstream consumers (batcher, prefix cache) see
        # only valid context.
        cache = KVCache(k=cache.k, v=cache.v,
                        lengths=jnp.full((1,), n_prompt, jnp.int32))
        # Next-token logits sit at the last *valid* prompt position.
        return logits[:, 0], cache, n_prompt, False

    def _suffix_plan(self, prompt_ids):
        """Static parameters of the suffix-prefill program for a prefix-
        matched prompt: (sbucket, kv_limit, n_suffix), or None when the
        suffix doesn't fit one bucket (chunked suffix path instead). THE
        single source of suffix-path routing — the batcher's admission
        grouping uses the same plan, so grouped and single admissions can
        never diverge."""
        from .prefix_cache import round_kv_limit

        n_suffix = len(prompt_ids) - self._prefix.n
        sbucket = next((b for b in self.prefill_buckets if b >= n_suffix),
                       None)
        if sbucket is None:
            return None
        kv_limit = round_kv_limit(self._prefix.n + sbucket, self.max_seq_len)
        if kv_limit is None:
            return None
        return sbucket, kv_limit, n_suffix

    def _prefill_suffix(self, prompt_ids):
        """Prefix-cache hit path: splice the resident system-prompt KV,
        prefill only the suffix at offset positions. Returns the same tuple
        as _prefill_prompt, or None when no suffix program fits (caller
        falls back to full prefill)."""
        prefix = self._prefix
        plan = self._suffix_plan(prompt_ids)
        if plan is None:
            # Suffix longer than the largest bucket: still reuse the
            # resident prefix KV, then consume the suffix in chunks.
            cache = self._new_cache(1)
            cache = self._splice_prefix_fn(cache, prefix.k, prefix.v)
            logits, cache, n = self._prefill_chunked(prompt_ids, cache=cache,
                                                     start=prefix.n)
            return logits, cache, n, True
        sbucket, kv_limit, n_suffix = plan
        suffix = prompt_ids[prefix.n:]
        n_prompt = prefix.n + n_suffix

        cache = self._new_cache(1)
        cache = self._splice_prefix_fn(cache, prefix.k, prefix.v)
        tokens = np.zeros((1, sbucket), np.int32)
        tokens[0, :n_suffix] = suffix
        positions = np.broadcast_to(
            prefix.n + np.arange(sbucket), (1, sbucket)
        ).astype(np.int32)
        mask = (np.arange(sbucket) < n_suffix)[None, :].astype(np.float32)
        logits, cache = self._get_suffix_prefill_fn(sbucket, kv_limit)(
            self.params, jnp.asarray(tokens), jnp.asarray(positions), cache,
            jnp.asarray(mask),
        )
        cache = KVCache(k=cache.k, v=cache.v,
                        lengths=jnp.full((1,), n_prompt, jnp.int32))
        return logits[:, 0], cache, n_prompt, True

    def _prefill_chunked(self, prompt_ids, cache=None, start: int = 0):
        """Sequential multi-bucket prefill at absolute offsets: consume the
        prompt in largest-bucket chunks, each attending over the KV span
        written so far (the same offset machinery the prefix-cache suffix
        path uses — a chunk IS a suffix of everything before it). Handles
        prompts beyond the largest bucket, and prefix-cache builds whose
        system prompt exceeds one bucket. ``cache``/``start`` continue from
        already-populated context (prefix splice). Returns
        (last_logits [1, V], cache, n_prompt)."""
        from .prefix_cache import round_kv_limit

        n = len(prompt_ids)
        big = self.prefill_buckets[-1]
        if cache is None:
            cache = self._new_cache(1)
        offset, L, logits = start, 0, None
        while offset < n:
            L = min(big, n - offset)
            bucket = next(b for b in self.prefill_buckets if b >= L)
            # Attend over [0, offset + bucket), tile-rounded for the flash
            # kernel, clamped to the cache (the tail beyond the written
            # span is masked by kv_pos <= q_pos). The first chunk reuses
            # the warmed standard prefill program.
            if offset == 0:
                kv_limit = bucket
            else:
                kv_limit = (round_kv_limit(offset + bucket, self.max_seq_len)
                            or self.max_seq_len)
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :L] = prompt_ids[offset:offset + L]
            positions = np.broadcast_to(
                offset + np.arange(bucket), (1, bucket)
            ).astype(np.int32)
            mask = (np.arange(bucket) < L)[None, :].astype(np.float32)
            logits, cache = self._get_suffix_prefill_fn(bucket, kv_limit)(
                self.params, jnp.asarray(tokens), jnp.asarray(positions),
                cache, jnp.asarray(mask),
            )
            offset += L
        cache = KVCache(k=cache.k, v=cache.v,
                        lengths=jnp.full((1,), n, jnp.int32))
        return logits[:, 0], cache, n

    def _get_ring_prefill_fn(self, s_pad: int):
        """Jitted sequence-parallel prefill over the ``seq`` mesh axis
        (parallel/ring_attention.py): the whole prompt in one pass, each
        device holding S/n positions, K/V blocks rotating over ICI."""
        fn = self._ring_prefill_fns.get(s_pad)
        if fn is None:
            cfg = self.model_cfg

            def ring_prefill(params, tokens, positions, cache, mask):
                last = jnp.maximum(mask.sum(axis=1).astype(jnp.int32) - 1, 0)
                return forward(params, cfg, tokens, positions, cache,
                               kv_limit=s_pad, attn_impl="ring",
                               mesh=self.mesh, moe_impl=self.moe_impl,
                               token_mask=mask,
                               logits_at=last)

            fn = jax.jit(ring_prefill, donate_argnums=(3,))
            self._ring_prefill_fns[s_pad] = fn
        return fn

    def _prefill_ring(self, prompt_ids):
        """Ring-attention prefill for prompts beyond the largest bucket
        when a ``seq`` mesh axis exists. Returns the _prefill_prompt tuple,
        or None when the padded length can't shard over the axis (caller
        falls back to chunked sequential prefill)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = len(prompt_ids)
        sp = self.mesh.shape["seq"]
        s_pad = max(sp, 1 << (n - 1).bit_length())   # next pow2 >= n
        if s_pad > self.max_seq_len:
            s_pad = self.max_seq_len
        if s_pad < n or s_pad % sp:
            return None

        tokens = np.zeros((1, s_pad), np.int32)
        tokens[0, :n] = prompt_ids
        positions = np.broadcast_to(np.arange(s_pad), (1, s_pad)).astype(np.int32)
        mask = (np.arange(s_pad) < n)[None, :].astype(np.float32)
        seq_sharding = NamedSharding(self.mesh, P(None, "seq"))
        cache = self._new_cache(1)
        logits, cache = self._get_ring_prefill_fn(s_pad)(
            self.params,
            jax.device_put(jnp.asarray(tokens), seq_sharding),
            jax.device_put(jnp.asarray(positions), seq_sharding),
            cache,
            jax.device_put(jnp.asarray(mask), seq_sharding),
        )
        cache = KVCache(k=cache.k, v=cache.v,
                        lengths=jnp.full((1,), n, jnp.int32))
        return logits[:, 0], cache, n, False

    def _generate_blocking(self, prompt: str, max_tokens: int,
                           temperature: float, deadline: Optional[float],
                           cancel: Optional["threading.Event"] = None,
                           seed: Optional[int] = None,
                           spans: Optional[RequestSpans] = None):
        """Runs on a worker thread. Yields (event, payload) tuples:
        ("token", text_piece) ... ("done", EngineResult). ``spans`` gets
        the phase boundaries as this thread crosses them."""
        cfg = self.model_cfg
        t_start = time.monotonic()

        # Clamp generation budget so the prompt always keeps >= 1 slot and
        # decode positions can never run past the KV cache.
        max_tokens = max(1, min(max_tokens, self.max_seq_len - 1))

        t_prefill0 = time.monotonic()
        last_logits, cache, n_prompt, prefix_hit = self._prefill_prompt(
            self.tokenizer.encode(prompt), max_tokens
        )

        # Per-request sampling seed (ISSUE 5 satellite): an explicit seed
        # pins the whole RNG stream, making this engine's transcripts
        # deterministic per seed; the legacy derivation (engine seed +
        # prompt length) stays the default so existing per-config
        # transcripts don't shift. NOTE the key schedule here is split-
        # chained through the compiled chunk programs — NOT the batched
        # engine's fold_in(PRNGKey(seed), g) — so the same seed yields a
        # different (but equally pinned) transcript than BatchedJaxEngine;
        # offline reproduction must use the engine class that recorded it.
        key = jax.random.PRNGKey(self.seed + n_prompt if seed is None
                                 else int(seed) & 0x7FFFFFFF)
        key, chunk_key = jax.random.split(key)
        temp_d = jnp.asarray(temperature, jnp.float32)

        detok = StreamDecoder(self.tokenizer)  # detok.ids = generated tokens
        detok_ms = 0.0                         # host detok time, accumulated
        t_first = None
        t_decode0 = time.monotonic()
        prefill_ms = (t_decode0 - t_prefill0) * 1000.0
        finish = "length"

        # First token: sampled from the prefill logits, pulled to host
        # immediately — this IS time-to-first-token.
        next_tok = self._sample_fn(last_logits, key, temp_d)
        first_id = int(next_tok[0])
        t_first = time.monotonic()
        if spans is not None:
            # No host admission work apart from the prefill program: the
            # whole of prefill is its one "chunk".
            spans.staged(t_prefill0, chunks_ahead=0,
                         prefill=dict(prompt_tokens=n_prompt))
            spans.first_token(t_first)
        stopped = False
        if first_id in cfg.eos_ids:
            finish = "stop"
            stopped = True
        else:
            t_dk = time.monotonic()
            piece = detok.push(first_id)
            detok_ms += (time.monotonic() - t_dk) * 1000.0
            if piece is not None:
                yield ("token", piece)
            if max_tokens <= 1:
                stopped = True

        # Hot loop: on-device decode chunks, pipelined two deep. Each chunk
        # is one dispatch; the next chunk is chained on device arrays before
        # the current one's tokens are pulled, so transfer latency
        # overlaps device compute. Chunk sizes greedily
        # decompose the remaining budget (CHUNK_SIZES) — never overshooting
        # max_tokens or the KV capacity, so an early-EOS abandon wastes at
        # most one in-flight chunk.
        if not stopped:
            from collections import deque

            tok_d = next_tok[:, None].astype(jnp.int32)
            pos_d = jnp.full((1, 1), n_prompt, jnp.int32)
            key_d = chunk_key
            done_d = jnp.asarray(False)
            budget = max_tokens - len(detok.ids)
            sched = 0                # tokens scheduled via chunks
            sched_pos = n_prompt     # KV slot the next chunk writes first
            inflight: deque = deque()

            while True:
                while len(inflight) < 2 and sched < budget:
                    chunk_len = next(
                        (s for s in self.CHUNK_SIZES
                         if s <= budget - sched
                         and sched_pos + s <= self.max_seq_len),
                        0,
                    )
                    if chunk_len == 0:
                        break  # KV capacity exhausted
                    # Smallest KV bucket covering every position this chunk
                    # can reach: decode cost tracks the live span. Only
                    # EXECUTED programs (_warm_chunk_fns) are eligible —
                    # before the background ladder warm lands a variant,
                    # fall back to the eagerly-warmed top bucket rather
                    # than compiling mid-request.
                    kv_b = next(b for b in self._kv_buckets
                                if b >= sched_pos + chunk_len)
                    fn = (self._warm_chunk_fns.get((chunk_len, kv_b))
                          or self._warm_chunk_fns.get(
                              (chunk_len, self.max_seq_len))
                          or self._get_chunk_fn(chunk_len, kv_b))
                    toks_d, tok_d, pos_d, cache, key_d, done_d = fn(
                        self.params, tok_d, pos_d, cache, key_d, temp_d, done_d
                    )
                    self._to_host_async(toks_d)
                    inflight.append(toks_d)
                    sched += chunk_len
                    sched_pos += chunk_len
                if not inflight:
                    break
                # Deadline/cancel granularity is one chunk (≤ CHUNK_SIZES[0]
                # token-steps): a timeout or disconnect can overshoot by at
                # most one chunk's decode time — the price of keeping the
                # hot loop on-device.
                if deadline is not None and time.monotonic() > deadline:
                    raise GenerationTimeout("generation exceeded timeout")
                # _shutdown: a force stop (second signal) must interrupt
                # the RUNNING generation too, not just drain waiters —
                # without this check "stopping now" would still decode to
                # max_tokens (code review r5).
                if (cancel is not None and cancel.is_set()) or self._shutdown:
                    finish = "abort"
                    break
                chunk_ids = np.asarray(inflight.popleft())[0]
                new_ids = []
                for tid in chunk_ids:
                    tid = int(tid)
                    if tid < 0:  # early-exit padding: chunk ended at EOS
                        break
                    if tid in cfg.eos_ids:
                        finish = "stop"
                        stopped = True
                        break
                    new_ids.append(tid)
                    if len(detok.ids) + len(new_ids) >= max_tokens:
                        stopped = True
                        break
                t_dk = time.monotonic()
                piece = detok.push(*new_ids) if new_ids else None
                detok_ms += (time.monotonic() - t_dk) * 1000.0
                if piece is not None:
                    yield ("token", piece)
                if stopped:
                    break

        # Flush any held-back tail (genuinely invalid bytes stay U+FFFD).
        t_dk = time.monotonic()
        piece = detok.flush()
        detok_ms += (time.monotonic() - t_dk) * 1000.0
        if piece is not None:
            yield ("token", piece)

        t_end = time.monotonic()
        decode_ms = (t_end - t_decode0) * 1000.0
        if spans is not None:
            spans.finished(t_end, tokens=len(detok.ids), finish=finish)
        result = EngineResult(
            text=detok.text,
            prompt_tokens=n_prompt,
            completion_tokens=len(detok.ids),
            prefill_ms=prefill_ms,
            decode_ms=decode_ms,
            detok_ms=detok_ms,
            ttft_ms=((t_first or t_end) - t_start) * 1000.0,
            prefix_cache_hit=prefix_hit,
            finish_reason=finish,
            engine=self.name,
            weights_version=self.weights_version,
        )
        yield ("done", result)

    async def generate(
        self,
        prompt: str,
        *,
        max_tokens: int = 128,
        temperature: float = 0.0,
        timeout: Optional[float] = None,
        seed: Optional[int] = None,
    ) -> EngineResult:
        result: Optional[EngineResult] = None
        async for event, payload in self._stream_events(
            prompt, max_tokens=max_tokens, temperature=temperature,
            timeout=timeout, seed=seed
        ):
            if event == "done":
                result = payload
        assert result is not None
        return result

    async def generate_stream(
        self,
        prompt: str,
        *,
        max_tokens: int = 128,
        temperature: float = 0.0,
        timeout: Optional[float] = None,
        seed: Optional[int] = None,
    ) -> AsyncIterator[str]:
        async for event, payload in self._stream_events(
            prompt, max_tokens=max_tokens, temperature=temperature,
            timeout=timeout, seed=seed
        ):
            if event == "token":
                yield payload

    async def stream_events(self, prompt: str, *, max_tokens: int = 128,
                            temperature: float = 0.0,
                            timeout: Optional[float] = None,
                            seed: Optional[int] = None,
                            resume_ids=None, export=None):
        """Fleet-facing event stream (engine/fleet.py). The
        single-sequence engine has no cross-replica import/export: a
        migrated-in request replays from scratch under its pinned seed
        (same bytes — the fleet relay suppresses the re-emitted prefix)
        and nothing is exported (migration off this engine also replays
        from scratch). The batcher overrides this with the full
        resume/export contract."""
        del resume_ids, export
        async for ev in self._stream_events(
                prompt, max_tokens=max_tokens, temperature=temperature,
                timeout=timeout, seed=seed):
            yield ev

    async def _stream_events(self, prompt: str, *, max_tokens: int,
                             temperature: float, timeout: Optional[float],
                             seed: Optional[int] = None):
        if not self._ready:
            raise EngineUnavailable("JaxEngine not started")
        if seed is not None:
            trace_event(
                f"engine: submitted to single-sequence engine "
                f"(sampling seed {int(seed)})")
        else:
            trace_event("engine: submitted to single-sequence engine")
        t_queue0 = time.monotonic()
        deadline = (t_queue0 + timeout) if timeout else None
        # Count this request as in flight from acceptance, INCLUDING the
        # lock wait: stop(drain_secs)'s poll sees queued waiters and lets
        # them finish instead of 503ing accepted work (ADVICE r4). The
        # counter is only touched on the event loop thread. ONE generator
        # on purpose: finalization of an abandoned stream must run the
        # inner cleanup (cancel.set/gen.close), release the lock, and
        # decrement the counter in that order, in one finalizer pass — a
        # split outer/inner generator pair would release the lock before
        # the abandoned generation's cleanup ran (code review r5).
        self._gen_inflight += 1
        try:
            async with self._lock:
                # Re-check under the lock: only a completed SHUTDOWN
                # (drain deadline passed or force-stop) rejects a drained
                # waiter — _ready alone is False for the whole drain
                # window, during which queued requests finish.
                if self._shutdown:
                    raise EngineUnavailable("engine stopped")
                t_adm = time.monotonic()
                queue_ms = (t_adm - t_queue0) * 1000.0
                # One sequence at a time: the whole wait was for the slot.
                spans = RequestSpans(current_trace(), None, t_queue0)
                spans.admitted(t_adm, t_adm)
                loop = asyncio.get_running_loop()
                cancel = threading.Event()
                gen = self._generate_blocking(prompt, max_tokens,
                                              temperature, deadline, cancel,
                                              seed=seed, spans=spans)
                try:
                    while True:
                        fut = loop.run_in_executor(None, next, gen, None)
                        try:
                            item = await fut
                        except asyncio.CancelledError:
                            # The worker thread may still be inside
                            # next(gen); closing now would raise
                            # "generator already executing" and leak the
                            # running generation. Signal the decode loop
                            # and wait for the in-flight step.
                            cancel.set()
                            try:
                                await asyncio.shield(fut)
                            except BaseException:
                                pass
                            raise
                        if item is None:
                            break
                        event, payload = item
                        if event == "done":
                            payload.queue_ms = queue_ms
                            spans.resumed(
                                time.monotonic(),
                                detok_host_ms=round(payload.detok_ms, 3))
                        yield (event, payload)
                finally:
                    cancel.set()
                    try:
                        gen.close()  # generator is suspended here — safe
                    except ValueError:  # pragma: no cover - defensive
                        pass
        finally:
            self._gen_inflight -= 1
