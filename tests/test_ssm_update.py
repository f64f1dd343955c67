"""The Mamba-1 decode update: the Pallas kernel of ``ops/selective_state.py``
in interpret mode on the CPU against ``jamba.selective_scan_step``, the
blocks it takes, and the dispatcher ``ops.ssm_update``'s off-TPU branch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import ops
from gofr_tpu.models import jamba
from gofr_tpu.ops.selective_state import block_rows, ssm_update_tpu

LAYERS = 4


def _operands(rows, n, di, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (LAYERS, rows, n, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (rows, di)) - 2.0)
    x = jax.random.normal(ks[2], (rows, di))
    B = jax.random.normal(ks[3], (rows, n))
    C = jax.random.normal(ks[4], (rows, n))
    A = -jnp.exp(jax.random.normal(ks[5], (n, di)))
    return state, dt, x, B, C, A


def _check(operands, layer, got):
    state, dt, x, B, C, A = operands
    got_state, got_y = got
    want_S, want_y = jamba.selective_scan_step(state[layer], dt, x, B, C, A)
    # the same float32 operations; only the order of the sum over N differs
    np.testing.assert_allclose(got_state[layer], want_S, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    for other in range(LAYERS):
        if other != layer:   # bit for bit: the stack is updated in place
            np.testing.assert_array_equal(got_state[other], state[other])


# (rows, N, Di, rows a block (None: ``block_rows``'s), layer)
CASES = {
    "two-blocks-middle-layer": (16, 16, 512, 8, 2),
    "three-blocks-last-layer": (48, 8, 1024, 16, LAYERS - 1),
    "one-block-of-16": (16, 16, 512, None, 1),
    "rows-that-no-block-divides": (12, 16, 512, None, 2),
    "first-layer": (8, 16, 512, None, 0),
}


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_selective_scan_step(case):
    rows, n, di, r, layer = CASES[case]
    operands = _operands(rows, n, di)
    got = ssm_update_tpu(operands[0], jnp.int32(layer), *operands[1:],
                         rows_a_block=r, interpret=True)
    _check(operands, layer, got)


@pytest.mark.parametrize("rows,n,di,want", [
    (128, 16, 5120, 16),   # the registered cell
    (256, 16, 5120, 16),
    (8, 16, 5120, 8),
    (12, 16, 5120, 12),    # no block divides: one block of all rows
    (100, 16, 5120, None),  # no block divides and all rows do not fit
    (16, 5, 512, None),    # N not whole sublane tiles
    (16, 16, 24, None),    # Di not whole pieces of lanes
])
def test_block_rows(rows, n, di, want):
    assert block_rows(rows, n, di) == want


@pytest.mark.parametrize("on_tpu,rows,n,di", [
    (False, 16, 16, 512), (False, 3, 5, 24), (True, 3, 5, 24)],
    ids=["cpu-tiles", "cpu-no-tiling", "tpu-no-tiling"])
def test_dispatcher_falls_back_and_records(monkeypatch, on_tpu, rows, n, di):
    """Off a TPU, and on one where the widths do not tile, ``ssm_update``
    runs ``selective_scan_step`` on the layer's slice and says ``xla``."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    operands = _operands(rows, n, di, seed=1)
    got = jax.jit(ops.ssm_update)(operands[0], jnp.int32(1), *operands[1:])
    _check(operands, 1, got)
    key = ops.branch_key("ssm_update", operands[0], operands[2])
    assert ops.kernel_branches()[key] == "xla"
