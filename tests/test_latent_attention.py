"""Absorbed latent decode attention: the Pallas kernel of
``ops/latent_attention.py`` in interpret mode on the CPU against
``latent_decode_attention_ref``, the widths it tiles, the dispatcher's
branch, and the absorbed form against the expanded attention it stands
for."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import ops
from gofr_tpu.ops.latent_attention import (
    block_positions,
    latent_decode_attention_ref,
    latent_decode_attention_tpu,
)

LAYERS, HEADS, WIDTH, VALUES, S = 4, 8, 256, 128, 1024


def _operands(rows, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (rows, HEADS, WIDTH), jnp.float32)
    cache = jax.random.normal(ks[1], (LAYERS, rows, S, WIDTH), jnp.float32)
    # lengths across block edges: 1, a block, a block and one, the end
    lens = jnp.asarray([1, 512, 513, S, 700, 3, 1000, 511] * 8)[:rows]
    return q.astype(dtype), cache.astype(dtype), lens.astype(jnp.int32)


@pytest.mark.parametrize("rows", [1, 3, 64])
@pytest.mark.parametrize("layer", [0, LAYERS // 2, LAYERS - 1],
                         ids=["first", "middle", "last"])
def test_kernel_matches_the_plain_computation(rows, layer):
    q, cache, lens = _operands(rows, seed=rows + layer)
    scale = WIDTH ** -0.5
    got = latent_decode_attention_tpu(q, cache, lens, layer, scale=scale,
                                      value_width=VALUES, interpret=True)
    want = latent_decode_attention_ref(q, cache, lens, layer, scale=scale,
                                       value_width=VALUES)
    assert got.shape == (rows, HEADS, VALUES)
    # float32 throughout; only the order of the online softmax's sums
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_reference_is_softmax_attention_over_the_rows():
    """The plain computation is what it says, row by row and head by
    head, with the values the rows' first ``value_width`` entries."""
    q, cache, lens = _operands(3)
    got = latent_decode_attention_ref(q, cache, lens, 1, scale=0.1,
                                      value_width=VALUES)
    for b in range(3):
        rows = np.asarray(cache[1, b, :int(lens[b])], np.float64)
        for h in range(HEADS):
            s = rows @ np.asarray(q[b, h], np.float64) * 0.1
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(got[b, h], p @ rows[:, :VALUES],
                                       rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("s_max,width,values,expect", [
    (8192, 640, 512, 512),   # the cell's plane
    (256, 256, 128, 256),    # a short plane is one block
    (8192, 576, 512, None),  # a row of 576 is not whole lanes
    (1000, 640, 512, None),  # S not a whole number of blocks
    (8192, 640, 448, None),  # values not whole lanes
])
def test_widths_the_kernel_tiles(s_max, width, values, expect):
    assert block_positions(s_max, width, values, 2) == expect


def test_dispatcher_records_the_plain_branch_off_the_chip():
    q, cache, lens = _operands(3)
    out = ops.latent_decode_attention(q, cache, lens, layer=2, scale=0.1,
                                      value_width=VALUES)
    np.testing.assert_allclose(
        out, latent_decode_attention_ref(q, cache, lens, 2, scale=0.1,
                                         value_width=VALUES), rtol=1e-6)
    key = ops.branch_key("latent_decode_attention", q, cache)
    assert ops.kernel_branches()[key] == "xla"


def test_absorbed_equals_expanded_attention():
    """Scoring ``[q_nope W_uk^T | q_pe]`` against the latent rows and
    applying ``W_uv`` to what they give is the attention of the expanded
    keys ``[c W_uk | k_pe]`` and values ``c W_uv``."""
    H, dn, dr, dv, r, T = 4, 16, 8, 24, 32, 40
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    c = jax.random.normal(ks[0], (T, r))
    k_pe = jax.random.normal(ks[1], (T, dr))
    w_uk = jax.random.normal(ks[2], (H, dn, r)) * r ** -0.5
    w_uv = jax.random.normal(ks[3], (H, r, dv)) * r ** -0.5
    q_nope = jax.random.normal(ks[4], (H, dn))
    q_pe = jax.random.normal(ks[5], (H, dr))
    scale = (dn + dr) ** -0.5
    with jax.default_matmul_precision("highest"):
        k = jnp.concatenate([jnp.einsum("tc,hdc->thd", c, w_uk),
                             jnp.broadcast_to(k_pe[:, None], (T, H, dr))], -1)
        v = jnp.einsum("tc,hcv->thv", c, w_uv)
        q = jnp.concatenate([q_nope, q_pe], -1)
        p = jax.nn.softmax(jnp.einsum("hd,thd->ht", q, k) * scale, -1)
        expanded = jnp.einsum("ht,thv->hv", p, v)

        q_lat = jnp.einsum("hd,hdc->hc", q_nope, w_uk)
        rows = jnp.concatenate([c, k_pe], -1)[None, None]   # [1, 1, T, W]
        u = latent_decode_attention_ref(
            jnp.concatenate([q_lat, q_pe], -1)[None], rows,
            jnp.asarray([T]), 0, scale=scale, value_width=r)[0]
        absorbed = jnp.einsum("hc,hcv->hv", u, w_uv)
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-5, atol=1e-5)
