"""How a Pallas kernel visits a recurrent-state leaf in place.

The four recurrence kernels (ops/ssd_scan.py's step and window,
ops/gated_delta.py's step, ops/gated_delta_window.py's window) are one frame
around bodies that share nothing: each takes the WHOLE leaf ``[layers, B,
...]``, aliased input to output, and the layer as a prefetched scalar of its
index maps, so no plane is sliced out in front of the call and none set back
behind it; its grid is (row, block of heads) for a decode step and (row,
block of heads, chunk of tokens) for a window, the chunks innermost. The rows
that move take the grid's first steps (``moving_rows_first``), and every step
past them, and in a window every chunk past a row's last token, NAMES THE
BLOCK OF THE STEP BEFORE IT AGAIN: the last moving row's last block, at its
last chunk that held a token. The pipeline fetches a block only where its
index changes and writes one back only when it is left, so a step that is
passed over costs no byte of the state. Where no row moves every step names
ONE block (row ``order[0]``, the last block), which the body copies through
at the grid's first step, so that what is written back at the end is what
came.

This module holds that decision once: the order of the rows, the index maps
of a step that is passed over, the three conditions a body asks about its own
step, the call (the prefetched scalars, the leaf last among the inputs and
the outputs, the alias between the two, the compiler's parameters), and the
two pieces around a window's call that do not know the recurrence (a row's
tokens counted from its gates where the caller gave no ``q_lens``; the
padding cut and the columns past ``q_len`` zeroed). No kernel's body lives
here: a compile cache's key holds a kernel's source LINES, so an edit to the
frame moves none of them. tests/test_state_leaf.py evaluates the maps as
plain functions; tests/test_tpu_aot.py reads them back from the calls'
``grid_mapping`` against a described v5e.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def moving_rows_first(moves):
    """(order int32 [B], n_live int32 [1]) of ``moves`` [B] bool: the rows
    that move first, in their order, then the others; how many move. A
    kernel's grid takes the rows in this order, so that the rows it passes
    over are its last steps. A stable argsort, as comparisons: a sort of 8 is
    a program of its own a layer."""
    B = moves.shape[0]
    m = moves.astype(jnp.int32)
    n_live = jnp.sum(m).reshape(1)
    before = jnp.tril(jnp.ones((B, B), jnp.int32), -1)
    place = jnp.where(moves, before @ m, n_live + before @ (1 - m))
    rows = jnp.arange(B, dtype=jnp.int32)
    order = jnp.sum(jnp.where(place[None, :] == rows[:, None], rows[None, :],
                              0), axis=1)
    return order, n_live


def any_gate(*live):
    """bool [...]: where any head of any of ``live`` (bool [..., H] each: a
    gate that is not 0) is set; the tokens that move their row's state."""
    first, *others = live
    for other in others:
        first = jnp.logical_or(first, other)
    return jnp.any(first, axis=-1)


def tokens_brought(*live):
    """int [B], a window's ``q_lens`` where the caller gave none: a row's
    columns up to its last token that moves (``live``: bool [B, S, H] each,
    as ``any_gate``'s)."""
    S = live[0].shape[1]
    return jnp.max(jnp.where(any_gate(*live), jnp.arange(1, S + 1), 0), axis=1)


def whole_chunks(a, pad: int):
    """``a`` [B, S, ...] with ``pad`` columns of zeros behind it."""
    return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))


def window_rows(y, q_lens, S: int):
    """A window kernel's outputs ``y`` [B, S + pad, lanes] less the padding,
    zeros past each row's ``q_len``: what no step wrote (a row's columns past
    its last chunk with tokens, a row that brought none) holds whatever the
    buffer held."""
    real = jnp.arange(S)[None, :] < q_lens[:, None]
    return jnp.where(real[..., None], y[:, :S], 0)


def step_block(nb: int):
    """The index map ``at(i, c, lyr, order, n_live) -> (layer, row, block)`` of
    the leaf in a step kernel's grid (row, block of ``nb``): its own row and
    block while ``i < n_live``; past the rows that move, the last of them and
    its last block. (With the layer: in a step kernel the leaf alone is
    passed over; the other operands are a row's few KB and take ``order[i]``.)
    """
    def at(i, c, lyr, order, n_live):
        last = jnp.maximum(n_live[0] - 1, 0)
        return (lyr[0], order[jnp.minimum(i, last)],
                jnp.where(i < n_live[0], c, nb - 1))
    return at


def window_block(nb: int, live_chunks):
    """The index map ``at(i, c, k, lyr, order, n_live, extra) -> (row, block,
    chunk)`` of a window kernel's grid (row, block of ``nb``, chunk): its own
    while the row moves and the chunk holds tokens of it, else the last that
    did (the last moving row, its last block, its last chunk with a token).
    ``live_chunks(extra, row)``: the chunks that hold a token of ``row``, from
    the kernel's own fourth prefetched scalar. Every operand of a window
    kernel takes its (row, block, chunk) from this map."""
    def at(i, c, k, lyr, order, n_live, extra):
        moving = i < n_live[0]
        row = order[jnp.minimum(i, jnp.maximum(n_live[0] - 1, 0))]
        last = jnp.maximum(live_chunks(extra, row) - 1, 0)
        return (row, jnp.where(moving, c, nb - 1),
                jnp.where(moving, jnp.minimum(k, last), last))
    return at


def passed_over(n_live):
    """In a step kernel's body: this step's row does not move (its output is
    the body's to zero; its state block is the step before's, untouched)."""
    return pl.program_id(0) >= n_live


def none_moves(n_live):
    """In a step kernel's body: no row moves and this is the grid's first
    step, whose block is the one every step names: it goes out as it came."""
    return (n_live == 0) & (pl.program_id(0) + pl.program_id(1) == 0)


def fetched(i, k, n_live):
    """In a window kernel's body at row step ``i``, chunk ``k``: the state
    block was fetched for this step (a moving row's first chunk; where no row
    moves, the grid's first step), so the output block is filled from it."""
    return ((i < n_live) & (k == 0)
            | (n_live == 0) & (i + pl.program_id(1) + k == 0))


def visit(body, *, name: str, grid, layer, order, n_live, extra=None, at,
          in_specs, out_specs, out_shape, scratch_shapes=(), leaf, block,
          plane, vmem_limit_bytes: int, interpret: bool):
    """The ``pallas_call`` of ``body`` over ``grid`` on the whole ``leaf``
    in place, as a function of the other operands: ``visit(...)(*operands)``
    returns the outputs of ``out_shape``, then the leaf.

    ``(layer, order, n_live[, extra])`` are prefetched (``layer`` a Python int
    or a traced scalar; ``order, n_live`` from ``moving_rows_first``;
    ``extra`` a window kernel's own), so ``body`` and every index map take
    them after the grid's indices. The leaf goes last among the inputs and
    last among the outputs, aliased one to the other, a ``block`` a step at
    ``plane(layer, row, block)`` (the leaf's layout: where the three go among
    its axes) of ``at``'s (``step_block`` for a grid of two, ``window_block``
    for a grid of three). Every axis of the grid is ``arbitrary``: the steps
    run in order on one core, which is what lets a step name the block before
    it."""
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), order, n_live)
    if extra is not None:
        scalars += (extra,)

    if len(grid) == 2:
        def leaf_map(*s):
            return plane(*at(*s))
    else:
        def leaf_map(*s):
            row, c, _ = at(*s)
            return plane(s[len(grid)][0], row, c)

    spec = pl.BlockSpec(block, leaf_map)
    call = pl.pallas_call(
        body,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=grid,
            in_specs=[*in_specs, spec], out_specs=[*out_specs, spec],
            scratch_shapes=scratch_shapes),
        out_shape=[*out_shape, jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)],
        input_output_aliases={len(scalars) + len(in_specs): len(out_shape)},
        interpret=interpret,
        name=name,
        **({} if interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=vmem_limit_bytes)}),
    )
    return lambda *operands: call(*scalars, *operands, leaf)
