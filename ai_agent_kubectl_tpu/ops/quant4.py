"""Weight-only int4 quantization with a Pallas packed-nibble matmul.

The round-4 profile proved 7B decode sits at the int8 weight-byte floor
(earlier chip run, not re-measured: 37.5 ms/step at bs=48 vs a
30.5 ms int8 read floor; W8A8
measured a no-op because the floor is the DMA stream, not the convert).
The only remaining single-chip lever is fewer bytes — int4 halves them
again. Replaces: /root/reference/app.py:184 (the remote forward this
framework serves locally).

Why a Pallas kernel and not XLA-native s4: measured on the round-5 chip,

- the platform's jit dispatch rejects s4 *inputs* outright (a
  RecursionError in the dispatch path), and
- the bitcast-from-int8 workaround compiles but materializes the full s4
  tensor plus a layout copy (HLO inspected: ``fusion -> s4[16384,16384]``
  + u8 transpose copy), streaming at ~25 GB/s vs int8's ~172 on the same
  shape — 7x slower than the bytes it was meant to save.

So the unpack must live where XLA can't un-fuse it: inside the matmul
kernel. HBM traffic is then exactly the packed bytes + scales.

**Storage format** (fixed at quantize time, carried as pytree metadata):

- ``q``: int8 ``[..., IN, OUT/2]`` — two 4-bit values per byte, packed
  along the OUTPUT axis in ``block_out``-column blocks: for out-block
  ``n``, byte column ``n*block_out/2 + j`` holds original column
  ``n*block_out + j`` in its low nibble and column
  ``n*block_out + block_out/2 + j`` in its high nibble. The halves of a
  block unpack into DISJOINT column ranges, so the kernel runs two
  half-width dots into adjacent accumulator slices — no nibble
  interleave, no shuffle, nothing for Mosaic to materialize.
- ``scale``: f32 ``[..., IN/group_in, OUT]`` — group-wise symmetric
  scales over the contraction axis (group = ``group_in`` input rows).
  Group-wise (not per-channel) bounds the int4 error: the absmax that
  sets each scale is taken over ``group_in`` weights, not the whole
  column. The scale multiply rides the per-group accumulation step, so
  it is free in the kernel's epilogue.

Values are clipped to the symmetric range [-7, 7] (15 levels) so +/-
magnitudes quantize identically.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:  # pltpu imports fine on CPU; guard for safety
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

#: format defaults — every 7B/70B-class projection dim divides these
#: (3072/4096/8192/14336/24576/28672; vocab heads that don't divide fall
#: back to int8 leaves in quantize_params)
GROUP_IN = 512
BLOCK_OUT = 512


@dataclasses.dataclass
class QuantInt4:
    """Packed int4 weight (see module docstring for the byte layout).

    q:     int8 [..., IN, OUT/2] — packed payload
    scale: f32  [..., IN/group_in, OUT]
    group_in / block_out: the format constants the payload was packed
    with (pytree METADATA — static under jit, so a compiled program is
    specialized to one format).
    """

    q: jnp.ndarray
    scale: jnp.ndarray
    group_in: int = GROUP_IN
    block_out: int = BLOCK_OUT

    @property
    def shape(self):
        """Logical (unpacked) weight shape."""
        return self.q.shape[:-1] + (self.q.shape[-1] * 2,)

    @property
    def nbytes(self):
        return self.q.nbytes + self.scale.nbytes


jax.tree_util.register_dataclass(
    QuantInt4, data_fields=("q", "scale"),
    meta_fields=("group_in", "block_out"))


def int4_supported(in_dim: int, out_dim: int, group_in: int = GROUP_IN,
                   block_out: int = BLOCK_OUT) -> bool:
    """Whether (in, out) packs into the compiled kernel's format: the
    contraction axis must tile into scale groups that fill bf16 sublanes,
    and the output axis into blocks whose halves fill the 128 lanes."""
    return (in_dim % group_in == 0 and out_dim % block_out == 0
            and group_in % 128 == 0 and (block_out // 2) % 128 == 0)


def pick_format(in_dim: int, out_dim: int):
    """Largest kernel-tileable (group_in, block_out) for a weight shape,
    or None when it can't tile (the caller then falls back to int8).
    Prefers the 512/512 default (fewer, larger DMA blocks); smaller
    formats admit narrow projections (e.g. a 2-KV-head wk with out 256)."""
    group = next((g for g in (GROUP_IN, 256, 128) if in_dim % g == 0), None)
    block = next((b for b in (BLOCK_OUT, 256) if out_dim % b == 0), None)
    if group is None or block is None:
        return None
    return group, block


def quantize_int4(w: jnp.ndarray, group_in: int = GROUP_IN,
                  block_out: int = BLOCK_OUT) -> QuantInt4:
    """[..., IN, OUT] float -> QuantInt4 (group-wise symmetric, [-7, 7]).

    Stacked leaves ([L, IN, OUT]) quantize one leading index at a time:
    the f32 working copy is 1/L of the leaf (a one-shot f32 view of a 7B
    MLP stack is ~8.5 GB — an HBM OOM on its own next to the bf16
    source)."""
    *lead, IN, OUT = w.shape
    if IN % group_in or OUT % block_out:
        raise ValueError(
            f"weight [{IN}, {OUT}] does not tile into group_in={group_in}"
            f" x block_out={block_out}")
    if lead:
        parts = [quantize_int4(w[i], group_in, block_out)
                 for i in range(w.shape[0])]
        return QuantInt4(
            q=jnp.stack([p.q for p in parts]),
            scale=jnp.stack([p.scale for p in parts]),
            group_in=group_in, block_out=block_out,
        )
    G = IN // group_in
    wf = w.astype(jnp.float32).reshape(G, group_in, OUT)
    absmax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 7.0, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -7, 7).astype(jnp.int8)
    q = q.reshape(IN, OUT // block_out, block_out)
    half = block_out // 2
    lo, hi = q[..., :half], q[..., half:]
    packed = ((lo.astype(jnp.int32) & 0xF)
              | ((hi.astype(jnp.int32) & 0xF) << 4)).astype(jnp.uint8)
    packed = jax.lax.bitcast_convert_type(packed, jnp.int8)
    return QuantInt4(
        q=packed.reshape(IN, OUT // 2),
        scale=scale.reshape(G, OUT).astype(jnp.float32),
        group_in=group_in, block_out=block_out,
    )


def _unpack_nibbles(packed: jnp.ndarray):
    """int8 [..., half] -> (lo, hi) int32 [..., half], sign-extended."""
    pi = packed.astype(jnp.int32)
    lo = jax.lax.shift_right_arithmetic(
        jax.lax.shift_left(pi, 28), jnp.int32(28))
    hi = jax.lax.shift_right_arithmetic(pi, jnp.int32(4))
    return lo, hi


def unpack_int4(w: QuantInt4) -> jnp.ndarray:
    """Packed payload -> int8 [..., IN, OUT] (the raw [-7, 7] values)."""
    *lead, IN, OH = w.q.shape
    bo = w.block_out
    half = bo // 2
    p = w.q.reshape(*lead, IN, OH // half, half)
    lo, hi = _unpack_nibbles(p)
    full = jnp.concatenate([lo, hi], axis=-1)           # [..., NO, bo]
    return full.reshape(*lead, IN, OH * 2).astype(jnp.int8)


def dequantize_int4(w: QuantInt4, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Materialize the bf16 weight (tests / one-off use — the serving
    path never calls this; the kernel reads packed bytes)."""
    *lead, IN, _ = w.q.shape
    G = IN // w.group_in
    q = unpack_int4(w).astype(jnp.float32)
    q = q.reshape(*lead, G, w.group_in, q.shape[-1])
    return (q * w.scale[..., :, None, :]).reshape(
        *lead, IN, q.shape[-1]).astype(dtype)


# ------------------------------------------------------------ the kernel

def _int4_matmul_kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, *,
                        block_out: int, n_blk: int):
    """One (T-block, out-group, k-block) program over ``n_blk``
    consecutive pack-blocks.

    x_ref [bt, bk] bf16; p_ref [bk, n_blk*bo/2] packed int8 (wider DMA:
    one pack-block's 256-byte minor dim starves the HBM stream — n_blk
    of them per program was the measured difference between losing and
    beating the XLA int8 path); s_ref [G, n_blk*bo] f32 (ALL k-groups'
    scales for this out-group — Mosaic wants full-dim or 8-divisible
    leading block dims, and G f32 rows are tiny); acc_ref
    [bt, n_blk*bo] f32 scratch. Within each pack-block the two
    half-width dots write disjoint accumulator slices (see module
    docstring: nibble halves are disjoint column ranges by
    construction). The j-loop unrolls at trace time.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    s = s_ref[k, :]
    half = block_out // 2
    dot = functools.partial(
        jax.lax.dot_general,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    for j in range(n_blk):
        lo, hi = _unpack_nibbles(p_ref[:, j * half:(j + 1) * half])
        base = j * block_out
        # int -> bf16 converts are exact for [-7, 7]; the MXU runs bf16
        # at full rate with f32 accumulation.
        acc_ref[:, base:base + half] += (
            dot(x, lo.astype(x.dtype)) * s[base:base + half])
        acc_ref[:, base + half:base + block_out] += (
            dot(x, hi.astype(x.dtype)) * s[base + half:base + block_out])

    @pl.when(k == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pick_row_block(T: int, cap: int = 256) -> int:
    for bt in (cap, 128, 64, 32, 16, 8):
        if T % bt == 0:
            return bt
    return T  # < 8 rows: caller padded to a multiple of 8 already


def _pick_n_blk(n_out_blocks: int, cap: int = 4) -> int:
    for n in range(cap, 0, -1):
        if n_out_blocks % n == 0:
            return n
    return 1


def _int4_matmul_2d(x: jnp.ndarray, w: QuantInt4,
                    interpret: bool) -> jnp.ndarray:
    """[T, IN] @ packed [IN, OUT/2] -> [T, OUT]; T % 8 == 0."""
    T, IN = x.shape
    OUT = w.q.shape[-1] * 2
    bk, bo = w.group_in, w.block_out
    bt = _pick_row_block(T)
    n_blk = _pick_n_blk(OUT // bo)
    wide = n_blk * bo
    grid = (T // bt, OUT // wide, IN // bk)
    kernel = functools.partial(_int4_matmul_kernel, block_out=bo,
                               n_blk=n_blk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bk), lambda t, o, k: (t, k)),
            pl.BlockSpec((bk, wide // 2), lambda t, o, k: (k, o)),
            pl.BlockSpec((IN // bk, wide), lambda t, o, k: (0, o)),
        ],
        out_specs=pl.BlockSpec((bt, wide), lambda t, o, k: (t, o)),
        out_shape=jax.ShapeDtypeStruct((T, OUT), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, wide), jnp.float32)] if pltpu
        else [],
        interpret=interpret,
    )(x, w.q, w.scale)


def _xla_int4_matmul(x: jnp.ndarray, w: QuantInt4) -> jnp.ndarray:
    """XLA fallback mirroring the kernel's numerics exactly: per-group
    f32-accumulated dots scaled then summed (used off-TPU and for
    non-tileable shapes; it materializes the unpacked weight, so it is a
    correctness path, not a bandwidth path)."""
    *lead_x, IN = x.shape
    G = IN // w.group_in
    q = unpack_int4(w)                                   # [IN, OUT] int8
    OUT = q.shape[-1]
    qg = q.reshape(G, w.group_in, OUT).astype(x.dtype)
    xg = x.reshape(*lead_x, G, w.group_in)
    partial_ = jnp.einsum("...gi,gio->...go", xg, qg,
                          preferred_element_type=jnp.float32)
    y = jnp.sum(partial_ * w.scale, axis=-2)
    return y.astype(x.dtype)


def qmatmul4(x: jnp.ndarray, w: QuantInt4,
             interpret: Optional[bool] = None) -> jnp.ndarray:
    """x @ w for a packed int4 weight; x [..., IN] any leading dims.

    TPU: the Pallas kernel streams only packed bytes + scales. Off-TPU
    the default is the XLA fallback (identical group-wise math, far
    faster than the interpreter); pass ``interpret=True`` explicitly to
    run the actual kernel through the Pallas interpreter (kernel-parity
    tests). Shapes that don't tile the kernel format always take the XLA
    fallback.
    """
    on_tpu = jax.default_backend() == "tpu"
    IN = x.shape[-1]
    OUT = w.q.shape[-1] * 2
    lead = x.shape[:-1]
    T = 1
    for d in lead:
        T *= d
    if not int4_supported(IN, OUT, w.group_in, w.block_out):
        return _xla_int4_matmul(x, w)
    if interpret is None and not on_tpu:
        return _xla_int4_matmul(x, w)
    x2 = x.reshape(T, IN)
    pad = (-T) % 8
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    y = _int4_matmul_2d(x2, w, interpret=bool(interpret) or not on_tpu)
    if pad:
        y = y[:T]
    return y.reshape(*lead, OUT)


# ------------------------------------------------- param-tree quantizers

def quantize_params_int4(params, quantize_embed: bool = False):
    """Quantize the dense projection weights of a
    models/transformer.py::init_params tree to packed int4; leaves whose
    dims don't tile the kernel format (e.g. a 128256-vocab LM head) fall
    back to per-channel int8 — a mixed tree serves fine, qmatmul
    dispatches per leaf. The embedding stays per-row int8
    (ops/quant.py::quantize_embed_int8): its gather is row-wise and the
    tied head's epilogue wants one scale per vocab row, both int8-shaped
    concerns."""
    from .quant import _QUANT_KEYS, quantize_embed_int8, quantize_int8

    def q4_or_q8(w):
        # MoE expert stacks (rank 4) stay int8: the int4 kernel serves 2D
        # per-layer slices, and the MoE einsum epilogues (parallel/moe.py)
        # are int8-shaped.
        fmt = (pick_format(w.shape[-2], w.shape[-1])
               if w.ndim <= 3 else None)
        if fmt is None:
            return quantize_int8(w)
        return quantize_int4(w, group_in=fmt[0], block_out=fmt[1])

    out = dict(params)
    layers = dict(params["layers"])
    for key in _QUANT_KEYS:
        if key in layers and layers[key].ndim in (3, 4):
            layers[key] = q4_or_q8(layers[key])
    out["layers"] = layers
    if "lm_head" in params:
        out["lm_head"] = q4_or_q8(params["lm_head"])
    if quantize_embed:
        out["embed"] = quantize_embed_int8(params["embed"])
    return out


def random_params_int4(key, cfg, dtype=None,
                       quantize_embed: bool = False):
    """Random-init a param tree DIRECTLY in packed-int4 form (bench/dev
    twin of ops/quant.py::random_params_int8 — no full-precision OR
    full-int8 materialization anywhere; the tree structure/shapes/dtypes
    match ``quantize_params_int4(init_params(...))`` exactly, so every
    jitted serving program compiles identically to a real int4
    checkpoint). Nibbles are uniform random bytes; scales carry the init
    magnitude. Non-tileable leaves stay int8, as in
    quantize_params_int4."""
    from .quant import random_params_int8

    return random_params_int8(key, cfg, dtype=dtype,
                              quantize_embed=quantize_embed, int4=True)


def qmatmul4_interpret(x: jnp.ndarray, w: QuantInt4) -> jnp.ndarray:
    """The kernel through the Pallas interpreter (CPU kernel-parity
    tests)."""
    return qmatmul4(x, w, interpret=True)
