"""ops/state_leaf.py: how a kernel visits a recurrent-state leaf in place
(ISSUE 57). The index maps evaluated as plain functions, the alias the call
works out, the order of the rows, and the seam: the three kernel modules hold
bodies and operands, the frame is this module's alone. The kernels' own tests
(test_hybrid_model.py, test_linear_attention.py, test_kda_latent.py) hold the
frame through whole interpreted runs, tests/test_tpu_aot.py against a described
v5e; nothing here starts an engine."""

from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from ai_agent_kubectl_tpu.ops import state_leaf

OPS = Path(__file__).resolve().parent.parent / "ai_agent_kubectl_tpu" / "ops"
KERNEL_MODULES = ("ssd_scan.py", "gated_delta.py", "gated_delta_window.py")
i32 = lambda *a: np.asarray(a, np.int32)


@pytest.mark.parametrize("moves", [
    [False] * 5, [True] * 5, [False, True, False, True, True],
    [True, False, False, False, False], [False, False, False, False, True],
    [True], [False]], ids=lambda m: "".join("x" if x else "." for x in m))
def test_moving_rows_first_is_a_stable_partition(moves):
    order, n_live = state_leaf.moving_rows_first(jnp.asarray(moves))
    rows = np.arange(len(moves))
    want = np.concatenate([rows[np.asarray(moves)], rows[~np.asarray(moves)]])
    assert order.dtype == jnp.int32 and n_live.shape == (1,)
    assert order.tolist() == want.tolist() and int(n_live[0]) == sum(moves)


def test_a_step_past_the_moving_rows_names_the_last_ones_last_block():
    """Grid (row, block) of 5 rows x 3 blocks, rows 3 and 1 moving: steps 0 and
    1 are their own; every later step is (row 1, block 2), the block the step
    before it left in VMEM, whatever its own block index."""
    at = state_leaf.step_block(3)
    order, n_live, lyr = i32(3, 1, 0, 2, 4), i32(2), i32(7)
    got = [[tuple(int(x) for x in at(i, c, lyr, order, n_live))
            for c in range(3)] for i in range(5)]
    assert got[0] == [(7, 3, 0), (7, 3, 1), (7, 3, 2)]
    assert got[1] == [(7, 1, 0), (7, 1, 1), (7, 1, 2)]
    assert {x for step in got[2:] for x in step} == {(7, 1, 2)}


@pytest.mark.parametrize("grid", ["step", "window"])
def test_where_no_row_moves_every_step_names_one_block(grid):
    order, n_live, lyr, lens = i32(0, 1, 2, 3), i32(0), i32(1), i32(0, 0, 0, 0)
    if grid == "step":
        at = state_leaf.step_block(2)
        named = {tuple(int(x) for x in at(i, c, lyr, order, n_live))
                 for i in range(4) for c in range(2)}
        assert named == {(1, 0, 1)}
    else:
        at = state_leaf.window_block(2, lambda lens, row: (lens[row] + 7) // 8)
        named = {tuple(int(x) for x in at(i, c, k, lyr, order, n_live, lens))
                 for i in range(4) for c in range(2) for k in range(3)}
        assert named == {(0, 1, 0)}


def test_a_window_step_past_a_rows_tokens_names_its_last_live_chunk():
    """Grid (row, block, chunk) of 4 rows x 2 blocks x 4 chunks of 8 tokens;
    row 2 brought 20 tokens (3 chunks), row 0 brought 3 (1 chunk), rows 1 and
    3 none. A moving row's chunks past its tokens name its last live chunk
    again; a row past the moving ones names the last moving row's last block
    at ITS last live chunk."""
    lens = i32(3, 0, 20, 0)
    at = state_leaf.window_block(2, lambda lens, row: (lens[row] + 7) // 8)
    order, n_live, lyr = i32(2, 0, 1, 3), i32(2), i32(0)
    step = lambda i, c, k: tuple(
        int(x) for x in at(i, c, k, lyr, order, n_live, lens))
    assert [step(0, 1, k) for k in range(4)] == [
        (2, 1, 0), (2, 1, 1), (2, 1, 2), (2, 1, 2)]
    assert [step(1, 0, k) for k in range(4)] == [(0, 0, 0)] * 4
    assert {step(i, c, k) for i in (2, 3) for c in range(2)
            for k in range(4)} == {(0, 1, 0)}
    # the order the grid runs in: a block index changes only where a block
    # is fetched (a moving row's blocks, once each)
    blocks = [step(i, c, k)[:2] for i in range(4) for c in range(2)
              for k in range(4)]
    changes = 1 + sum(a != b for a, b in zip(blocks, blocks[1:]))
    assert changes == 2 * 2


def _tiny_call(extra):
    """A frame around a body that copies, at a leaf [3, 2, 8, 128]; the
    ``pallas_call`` equation of its jaxpr."""
    leaf = jnp.zeros((3, 2, 8, 128), jnp.float32)
    x = jnp.ones((2, 8, 128), jnp.float32)

    def body(*refs):
        x_ref, _, y_ref, s_out_ref = refs[-4:]
        y_ref[...] = x_ref[...]
        s_out_ref[...] = s_out_ref[...]

    def call(x, leaf, layer, moves):
        order, n_live = state_leaf.moving_rows_first(moves)
        if extra:
            grid = (2, 1, 1)
            at = state_leaf.window_block(1, lambda lens, row: lens[row])
            mine = lambda *s: (at(*s)[0], 0, 0)
            lens = moves.astype(jnp.int32)
        else:
            grid, at, lens = (2, 1), state_leaf.step_block(1), None
            mine = lambda i, c, lyr, order, n_live: (order[i], 0, 0)
        return state_leaf.visit(
            body, name="tiny", grid=grid, layer=layer, order=order,
            n_live=n_live, extra=lens, at=at,
            in_specs=[pl.BlockSpec((1, 8, 128), mine)],
            out_specs=[pl.BlockSpec((1, 8, 128), mine)],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)],
            leaf=leaf, block=(1, 1, 8, 128),
            plane=lambda layer, row, c: (layer, row, 0, c),
            vmem_limit_bytes=2 ** 20, interpret=True)(x)

    args = (x, leaf, jnp.int32(1), jnp.asarray([False, True]))
    eqn, = (e for e in jax.make_jaxpr(call)(*args).eqns
            if e.primitive.name == "pallas_call")
    return eqn, call(*args)


@pytest.mark.parametrize("extra", [False, True],
                         ids=["three-scalars", "four-scalars"])
def test_the_call_aliases_the_leaf_last_in_to_last_out(extra):
    eqn, (y, leaf) = _tiny_call(extra)
    gm = eqn.params["grid_mapping"]
    n = 4 if extra else 3
    assert gm.num_index_operands == n
    # (the leaf: behind the scalars and the one operand; the second output)
    assert tuple(eqn.params["input_output_aliases"]) == ((n + 1, 1),)
    assert eqn.invars[n + 1].aval.shape == eqn.outvars[1].aval.shape == (
        3, 2, 8, 128)
    assert y.shape == (2, 8, 128) and leaf.shape == (3, 2, 8, 128)
    assert float(y[1].min()) == 1.0 and not leaf.any()


def test_tokens_brought_counts_up_to_a_rows_last_moving_token():
    g = np.zeros((3, 6, 2), np.float32)
    beta = np.zeros((3, 6, 2), np.float32)
    g[0, :4, 1], beta[2, 0, 0] = -0.5, 1.0
    assert state_leaf.tokens_brought(g != 0, beta != 0).tolist() == [4, 0, 1]
    assert state_leaf.any_gate(g[:, 0] != 0).tolist() == [True, False, False]
    y = jnp.ones((3, 8, 4))
    cut = state_leaf.window_rows(y, jnp.asarray([4, 0, 1]), 6)
    assert cut.shape == (3, 6, 4)
    assert cut.sum(axis=(1, 2)).tolist() == [16.0, 0.0, 4.0]


@pytest.mark.parametrize("module", KERNEL_MODULES)
def test_the_kernel_modules_hold_bodies_and_the_frame_is_state_leafs(module):
    """The next kernel on a state leaf writes a body and its operands: the
    call, the maps of a passed-over step and the order of the rows are
    ops/state_leaf.py's (four copies in three modules before ISSUE 57)."""
    text = (OPS / module).read_text()
    code = "\n".join(line.split("#")[0] for line in text.splitlines())
    for call in ("pallas_call(", "PrefetchScalarGridSpec(",
                 "input_output_aliases", "def moving_rows_first"):
        assert call not in code, f"{module} has a {call} of its own"
    frame = set(vars(state_leaf)) - {"jax", "jnp", "pl", "pltpu", "annotations"}
    others = [m[:-3] for m in KERNEL_MODULES if m != module]
    for other in others:
        for names in re.findall(rf"from \.{other} import (\([^)]*\)|[^\n]*)",
                                text):
            taken = set(re.findall(r"\w+", names))
            assert not taken & frame, f"{module} takes the frame from {other}"
    assert "state_leaf" in code
    assert "pallas_call(" in (OPS / "state_leaf.py").read_text()
