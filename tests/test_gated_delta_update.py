"""The Gated DeltaNet decode update: the Pallas kernel of
``ops/gated_delta.py`` in interpret mode on the CPU against
``gated_delta_step``, the widths it tiles, and the dispatcher
``ops.gated_delta_update``'s branches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import ops
from gofr_tpu.ops.gated_delta import (
    gated_delta_step,
    gated_delta_update_tpu,
    tiles,
)

LAYERS = 4


def _operands(rows, heads, dk, dv, seed=0):
    """A stack of states and one token's operands as the model makes them:
    unit keys, queries scaled by dk^-1/2, ``g`` < 0, ``beta`` in (0, 1)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (LAYERS, rows, heads, dk, dv),
                              jnp.float32)
    k = jax.random.normal(ks[1], (rows, heads, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(ks[2], (rows, heads, dk)) * dk ** -0.5
    v = jax.random.normal(ks[3], (rows, heads, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[4], (rows, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (rows, heads)))
    return state, q, k, v, g, beta


def _check(operands, layer, got):
    state, q, k, v, g, beta = operands
    got_state, got_o = got
    want_S, want_o = gated_delta_step(state[layer], q, k, v, g, beta)
    # the same float32 operations; only the order of the sums over dk differs
    np.testing.assert_allclose(got_state[layer], want_S, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-5, atol=1e-5)
    for other in range(LAYERS):
        if other != layer:   # bit for bit: the stack is updated in place
            np.testing.assert_array_equal(got_state[other], state[other])


# (rows, heads, dk, dv, layer); a grid step takes one row
CASES = {
    "one-row-first-layer": (1, 8, 16, 128, 0),
    "eight-rows-middle-layer": (8, 8, 16, 128, 2),
    "six-rows-last-layer": (6, 8, 8, 128, LAYERS - 1),
    "twelve-rows-wide-values": (12, 8, 16, 256, 1),
    "the-cell's-head-widths": (3, 8, 128, 128, 2),
}


@pytest.mark.parametrize("case", CASES)
def test_kernel_matches_gated_delta_step(case):
    rows, heads, dk, dv, layer = CASES[case]
    operands = _operands(rows, heads, dk, dv)
    got = gated_delta_update_tpu(operands[0], jnp.int32(layer),
                                 *operands[1:], interpret=True)
    _check(operands, layer, got)


@pytest.mark.parametrize("heads,dk,dv,want", [
    (32, 128, 128, True),    # the registered cell
    (8, 16, 256, True),
    (8, 8, 128, True),       # one tile of each
    (16, 64, 384, True),
    (32, 128, 64, False),    # dv under one lane tile
    (32, 12, 128, False),    # dk not whole sublane tiles
    (4, 128, 128, False),    # heads not whole sublane tiles
    (32, 128, 96, False),    # dv not whole lanes
])
def test_tiles(heads, dk, dv, want):
    assert tiles(heads, dk, dv) is want


@pytest.mark.parametrize("on_tpu,rows,heads,dk,dv,branch", [
    (False, 4, 8, 16, 128, "xla"),
    (False, 3, 2, 4, 24, "xla"),
    (True, 3, 2, 4, 24, "xla"),
    (True, 2, 8, 16, 128, "pallas"),   # a shape of its own: jit caches
], ids=["cpu-tiles", "cpu-no-tiling", "tpu-no-tiling", "tpu-tiles"])
def test_dispatcher_chooses_and_records(monkeypatch, on_tpu, rows, heads,
                                        dk, dv, branch):
    """Off a TPU, and on one where the widths do not tile,
    ``gated_delta_update`` runs ``gated_delta_step`` on the layer's slice
    and says ``xla``; where they tile on a TPU it says ``pallas`` and takes
    the kernel (here in interpret mode, which is all the CPU can run)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    if branch == "pallas":
        from gofr_tpu.ops import gated_delta
        monkeypatch.setattr(
            gated_delta, "gated_delta_update_tpu",
            lambda *a: gated_delta_update_tpu(*a, interpret=True))
    operands = _operands(rows, heads, dk, dv, seed=1)
    got = jax.jit(ops.gated_delta_update)(operands[0], jnp.int32(1),
                                          *operands[1:])
    _check(operands, 1, got)
    key = ops.branch_key("gated_delta_update", operands[0], operands[1])
    assert ops.kernel_branches()[key] == branch
