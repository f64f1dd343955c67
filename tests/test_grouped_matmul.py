"""The grouped product of the dropless expert layer: the Pallas kernel in
interpret mode on the CPU against ``jax.lax.ragged_dot``, the walk it is
handed, and the dispatcher's off-TPU branch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import ops
from gofr_tpu.ops.grouped_matmul import (
    col_tile,
    grouped_matmul_tpu,
    row_tile,
    work_items,
)

D, F, TM, ROWS = 256, 128, 16, 64

# (sizes of the call's groups, groups in the stack, the first group's index)
CASES = {
    "every-group-empty": ([0, 0, 0, 0], 4, None),
    "one-group-holds-every-row": ([0, ROWS, 0, 0], 4, None),
    # 1 + 2 + 3 rows, then 40 that start inside the first row tile and end
    # inside the third; 18 rows behind the last group
    "small-groups-beside-one-across-tiles": ([1, 2, 3, 40], 4, None),
    "rows-behind-the-last-group": ([5, 0, 7, 0], 4, None),
    "a-group-ends-on-a-tile-edge": ([16, 16, 0, 5], 4, None),
    "layer-first": ([1, 2, 3, 40], 12, 0),
    "layer-middle": ([1, 2, 3, 40], 12, 4),
    "layer-last": ([1, 2, 3, 40], 12, 8),
}


def _operands(k, n, n_experts, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    xs = jax.random.normal(kx, (ROWS, k), jnp.float32).astype(jnp.bfloat16)
    w = (jax.random.normal(kw, (n_experts, k, n), jnp.float32)
         * k ** -0.5).astype(jnp.bfloat16)
    return xs, w


def _ragged_dot(xs, w, sizes, first):
    """The reference: the call's groups placed in a stack-wide vector."""
    groups = jnp.zeros((w.shape[0],), jnp.int32).at[
        (first or 0) + jnp.arange(sizes.shape[0])].set(sizes)
    return jax.lax.ragged_dot(xs, w, groups)


@pytest.mark.parametrize("k,n", [(D, 2 * F), (F, D)],
                         ids=["gate_up", "down"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_ragged_dot(case, k, n):
    sizes, n_experts, first = CASES[case]
    xs, w = _operands(k, n, n_experts)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_matmul_tpu(
        xs, w, sizes, None if first is None else jnp.int32(first), tm=TM,
        interpret=True)
    want = _ragged_dot(xs, w, sizes, first)
    held = int(sizes.sum())
    assert got.shape == want.shape and got.dtype == jnp.bfloat16
    # bfloat16 results of float32 sums taken in another order: an ulp
    np.testing.assert_allclose(np.asarray(got[:held], np.float32),
                               np.asarray(want[:held], np.float32),
                               atol=2e-2, rtol=0)
    # what lies behind the last group inside a tile that was visited is
    # zero; tiles no group touches are not written at all
    visited = -(-held // TM) * TM if held else TM
    assert not np.asarray(got[held:visited], np.float32).any()


@pytest.mark.parametrize("sizes", [[0] * 6, [3, 0, 0, 30, 1, 0], [64] + [0] * 5,
                                   [0] * 5 + [64], [10] * 6],
                         ids=["empty", "mixed", "first-all", "last-all",
                              "even"])
def test_work_items_cover_each_group_once_and_nothing_else(sizes):
    """Every row of every group lies in exactly one live item, with that
    group's expert; the items are in row order; the tail repeats the last
    item with an empty range, so no block index changes behind it."""
    first, n_experts = 6, 12
    expert, tile, lo, hi = (np.asarray(a) for a in work_items(
        jnp.asarray(sizes, jnp.int32), jnp.int32(first), ROWS, TM, n_experts))
    assert len(expert) == len(sizes) + ROWS // TM - 1
    owner = np.full(ROWS, -1)
    for e, t, a, b in zip(expert, tile, lo, hi):
        assert 0 <= a <= b <= TM and 0 <= t < ROWS // TM
        assert (owner[t * TM + a:t * TM + b] == -1).all()
        owner[t * TM + a:t * TM + b] = e
    want = np.repeat(np.arange(len(sizes)) + first, sizes)
    np.testing.assert_array_equal(owner[:len(want)], want)
    assert (owner[len(want):] == -1).all()
    live = hi > lo
    n_live = int(live.sum())
    assert live[:n_live].all() and (np.diff(tile[:n_live]) >= 0).all()
    touched = int((np.asarray(sizes) > 0).sum())
    assert n_live <= touched + ROWS // TM - 1 and (n_live > 0) == (touched > 0)
    last = max(n_live - 1, 0)
    assert (expert[last:] == expert[last]).all()
    assert (tile[last:] == tile[last]).all()


def test_tiles_follow_the_static_shape():
    """The row tile is the largest that divides the call's rows (the
    hybrid cell's pairs are multiples of 1,280); a weight block is an
    expert's whole matrix at that cell's widths."""
    assert row_tile(128 * 10) == row_tile(2048 * 10) == 128
    assert row_tile(24 * 4) == 32 and row_tile(3 * 16) == 16
    assert row_tile(7 * 2) is None
    assert col_tile(2048, 1024, 2) == 1024 and col_tile(512, 2048, 2) == 2048
    assert col_tile(4096, 2048, 2) == 512 and col_tile(64, 96, 2) is None


@pytest.mark.parametrize("first", [None, 4])
def test_dispatcher_off_the_chip_is_ragged_dot_and_says_so(first):
    sizes = jnp.asarray([1, 2, 3, 40], jnp.int32)
    n_experts = 4 if first is None else 12
    xs, w = _operands(D, 2 * F, n_experts, seed=1)
    got = jax.jit(lambda f: ops.grouped_matmul(xs, w, sizes, first=f))(first)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(_ragged_dot(xs, w, sizes, first), np.float32))
    assert ops.kernel_branches()[ops.branch_key("grouped_matmul", xs, w)] == "xla"
