"""Pallas TPU selective-state update for a Mamba-1 decode step.

One token a row moves every row's state ``S`` [N, Di] float32:
``S' = exp(dt A) S + (dt x) B``, and the mixer reads ``y = sum_n S'[n] C[n]``.
In a decode step of the Jamba cell (128 rows, N = 16, Di = 5,120) a
layer's state is 41.9 MB, and XLA sweeps it three times: it cannot fuse
the readout, a reduction over the state, into the fusion whose root is
the in-place ``dynamic-update-slice``, so it reads the state once for the
update and once more for ``y``. This kernel reads it once and writes it
once:

- the state stays one stack ``[layers, rows, N, Di]`` as it lies in the
  cache, aliased from input to output; the layer is named through scalar
  prefetch in the blocks' index maps, as ``grouped_matmul.py`` names an
  expert. No layer's slice is cut out before the call or put back after
  it: a slice handed to a custom call is a copy in and a copy out.
- a grid step takes a block of whole rows' ``[N, Di]`` tiles (contiguous
  in HBM), computes the decay, the update and the readout's reduction
  over N (the sublanes) in VMEM a piece of lanes at a time, and writes
  the block back; the pipeline fetches the next block under that work.
- ``B`` and ``C`` come as ``[rows, N, 1]``: a row's ``[N, 1]`` column
  broadcasts along the lanes with no transpose in the kernel.

The convolution window's shift stays with XLA: folded into this kernel as
a second aliased operand it cost the served step more than the two small
fusions it replaced (``PERF.md`` §6, PR 38).

Elementwise float32 as ``selective_scan_step``, the plain recurrence that
any other shape runs; only the order of the readout's sum over N may
differ from XLA's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan_step", "ssm_update_tpu", "block_rows"]

# rows a block, largest first: a block of 16 rows of N = 16, Di = 5,120 is
# 5.2 MB, and the pipeline holds two of it in and two out (alone on a v5e
# at 128 rows, blocks of 8, 16 and 32 rows and pieces of 256, 512 and
# 1,024 lanes read within 2.5 % of one another)
_ROW_BLOCKS = (16, 8)
_BLOCK_BYTES = 6 * 2**20
_LANES = 512   # lanes of a row worked at once: (16, 512) float32 is 8 vregs


def selective_scan_step(S, dt, x, B, C, A):
    """One token of the selective state-space recurrence, any leading
    axes. ``S`` [..., N, Di] float32; ``dt``, ``x`` [..., Di]; ``B``, ``C``
    [..., N]; ``A`` [N, Di] (negative). ``S <- exp(dt A) S + (dt x) B``;
    ``y = S C``. Elementwise float32, so no operand is rounded."""
    S = (jnp.exp(dt[..., None, :] * A) * S
         + (dt * x)[..., None, :] * B[..., :, None])
    return S, jnp.sum(S * C[..., :, None], axis=-2)


def block_rows(rows: int, n: int, di: int) -> int | None:
    """Rows a block for a state of ``rows`` x [n, di] float32: the largest
    of ``_ROW_BLOCKS`` that divides ``rows``, else all rows where they fit
    one block; None where the widths are not whole tiles (N of whole
    sublane tiles, Di of whole pieces of lanes) or nothing fits."""
    if n % 8 or di % _LANES:
        return None
    for r in _ROW_BLOCKS:
        if rows % r == 0:
            return r
    return rows if rows * n * di * 4 <= _BLOCK_BYTES else None


def _kernel(layer_ref, s_ref, dt_ref, x_ref, b_ref, c_ref, a_ref,
            s_out, y_out):
    del layer_ref  # the state's index maps read it
    rows, _, di = s_ref.shape

    def row(r, carry):
        b, c = b_ref[r], c_ref[r]                      # [N, 1]
        for j in range(di // _LANES):
            lanes = pl.ds(j * _LANES, _LANES)
            dt = dt_ref[pl.ds(r, 1), lanes]            # [1, L]
            x = x_ref[pl.ds(r, 1), lanes]
            s = (jnp.exp(dt * a_ref[:, lanes]) * s_ref[r, :, lanes]
                 + (dt * x) * b)                       # [N, L]
            s_out[r, :, lanes] = s
            y_out[pl.ds(r, 1), lanes] = jnp.sum(s * c, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


@functools.partial(jax.jit, static_argnames=("rows_a_block", "interpret"))
def ssm_update_tpu(state, layer, dt, x, B, C, A, *,
                   rows_a_block: int | None = None, interpret: bool = False):
    """``state`` [L, rows, N, Di] float32, updated in place at layer
    ``layer`` (traced); ``dt``, ``x`` [rows, Di]; ``B``, ``C`` [rows, N];
    ``A`` [N, Di]. Returns ``(state, y)``, ``y`` [rows, Di] float32.
    ``rows_a_block`` is ``block_rows``'s where None (the tests pass a
    small one so that a layer takes several blocks)."""
    _, rows, n, di = state.shape
    r = block_rows(rows, n, di) if rows_a_block is None else rows_a_block
    if r is None or rows % r or n % 8 or di % _LANES:
        raise ValueError(f"no tiling for a state of {state.shape}")
    f32 = jnp.float32
    dt, x, A = dt.astype(f32), x.astype(f32), A.astype(f32)
    B, C = B.astype(f32)[..., None], C.astype(f32)[..., None]
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))

    state_spec = pl.BlockSpec((None, r, n, di), lambda i, l: (l[0], i, 0, 0))
    row_spec = pl.BlockSpec((r, di), lambda i, l: (i, 0))
    col_spec = pl.BlockSpec((r, n, 1), lambda i, l: (i, 0, 0))
    # the state's block in and out, the row vectors and A, each
    # double-buffered, and room
    vmem = 2 * (2 * r * n * di + 3 * r * di + n * di) * 4 + 8 * 2**20
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(rows // r,),
            in_specs=[state_spec, row_spec, row_spec, col_spec, col_spec,
                      pl.BlockSpec((n, di), lambda i, l: (0, 0))],
            out_specs=[state_spec, row_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((rows, di), f32)],
        # operand 1 (after the layer) is output 0: the stack in place
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="ssm_update_tpu",
    )(layer, state, dt, x, B, C, A)
