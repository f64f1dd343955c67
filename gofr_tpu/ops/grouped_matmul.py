"""Pallas TPU grouped matmul for the dropless expert layer.

``xs`` [M, K] holds its rows sorted by group; group ``g`` (``sizes[g]``
rows) meets ``w[first + g]`` [K, N]. In a decode step of the Qwen3-Next
cell a layer touches ~118 of its 128 held experts with 2.5 rows each, so
the product is a sweep of the touched experts' weights and nothing else
counts: XLA's ``ragged_dot`` read them at 46 % of the HBM roof. This
kernel reads each touched expert once, and nothing of an untouched one:

- the weights stay one stack ``[layers * held, K, N]`` as they lie; a
  group's block is named by its absolute row ``first + g`` through scalar
  prefetch, as ``paged_attention.py`` names a page by its table entry. No
  layer's slice is cut out before the call.
- the grid walks a list of work items built by ``work_items`` with a few
  device operations: one item for every (touched group, row tile it
  overlaps), in row order. An empty group has no item, so it costs neither
  a DMA nor a product. The list has a static length (groups + row tiles
  - 1); what is left of it repeats the last item with an empty row range,
  so the pipeline sees the same block indices, fetches nothing, and the
  step's body is skipped.
- an item is ``xs[tile] [tm, K] x w[expert] [K, tn]`` in the operands'
  dtype with float32 accumulation; the rows of the tile that are the
  group's own are stored, the others kept (a row tile is shared by the
  groups that meet inside it and stays in VMEM between their items).
  Consecutive items of one group name the same weight block, which is
  then not fetched again. The pipeline double-buffers the weights: an
  expert's DMA runs under the product of the one before.
- the row tile follows from the static shape the call sees
  (``row_tile``): the largest of 128, 64, 32, 16 that divides the rows.
  Alone on a v5e the kernel is bound by its weight DMA whatever the tile
  (a decode step's 2.5 rows a group read 880 us a gate|up call at 16 rows
  a tile and 840-860 at 32, 64 and 128; the product of an item hides
  under the next expert's 4 MB), so what a small tile saves on the MXU
  buys nothing and its longer walk costs a grid step's 0.35 us an item.

Rows behind the last group belong to no group: what the result holds
there is not specified (tiles no item visits are never written).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_matmul_tpu", "row_tile", "col_tile", "work_items"]

_ROW_TILES = (128, 64, 32, 16)
# bytes of one weight block: two of them are in flight, beside the row
# tiles, inside the 16 MiB a kernel may scope on a v5e
_BLOCK_BYTES = 4 * 2**20


def row_tile(rows: int) -> int | None:
    """Rows a tile for ``rows`` sorted rows: the largest tile that divides
    them, or None where none does."""
    return next((tm for tm in _ROW_TILES if rows % tm == 0), None)


def col_tile(k: int, n: int, itemsize: int) -> int | None:
    """Columns of a weight block ``[k, tn]``: all ``n`` where that fits
    ``_BLOCK_BYTES``, else the widest whole-lane divisor of ``n`` that
    does; None where the widths are not whole lanes."""
    if k % 128 or n % 128:
        return None
    for parts in range(1, n // 128 + 1):
        tn = n // parts
        if n % parts == 0 and tn % 128 == 0 and k * tn * itemsize <= _BLOCK_BYTES:
            return tn
    return None


def work_items(sizes: jnp.ndarray, first, rows: int, tm: int, n_experts: int):
    """The kernel's walk: for every (non-empty group, row tile it
    overlaps), in row order, the expert's absolute index, the tile, and
    the tile's rows ``[lo, hi)`` that are the group's. int32 arrays of the
    static length ``groups + rows // tm - 1``; the tail repeats the last
    item with ``lo == hi``. Every index is clamped into its array: a walk
    that leaves it would be a DMA out of bounds."""
    g = sizes.shape[0]
    tiles = rows // tm
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    tile0 = starts // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - tile0 + 1, 0)
    upto = jnp.cumsum(n_tiles)
    item = jnp.arange(g + tiles - 1, dtype=jnp.int32)
    live = item < upto[-1]
    item = jnp.minimum(item, jnp.maximum(upto[-1] - 1, 0))
    # the group of an item: how many groups' items end at or before it
    # (a comparison, not ``searchsorted``: that would be a loop a call)
    grp = jnp.minimum(jnp.sum(upto[None, :] <= item[:, None], axis=1,
                              dtype=jnp.int32), g - 1)
    # a group's first tile less the items before it: one gather, not three
    tile = jnp.clip((tile0 - upto + n_tiles)[grp] + item, 0, tiles - 1)
    lo = jnp.where(live, jnp.clip(starts[grp] - tile * tm, 0, tm), 0)
    hi = jnp.where(live, jnp.clip(ends[grp] - tile * tm, 0, tm), 0)
    expert = jnp.clip(grp + (0 if first is None else first), 0, n_experts - 1)
    return tuple(a.astype(jnp.int32) for a in (expert, tile, lo, hi))


def _kernel(expert_ref, tile_ref, lo_ref, hi_ref, x_ref, w_ref, o_ref):
    del expert_ref  # the weight block's index map reads it
    i = pl.program_id(1)
    lo, hi = lo_ref[i], hi_ref[i]

    # a tile's first item finds in the buffer what an earlier tile left
    @pl.when((i == 0) | (tile_ref[i] != tile_ref[jnp.maximum(i - 1, 0)]))
    def _fresh():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(hi > lo)
    def _item():
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        o_ref[...] = jnp.where((row >= lo) & (row < hi),
                               acc.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul_tpu(xs, w, sizes, first=None, *, tm: int | None = None,
                       interpret: bool = False):
    """``xs`` [M, K] sorted by group, ``w`` [E, K, N], ``sizes`` [G] int32
    rows a group (their sum at most M), ``first`` the (traced) index in
    ``w`` of group 0's weights (0 where None; ``first + G <= E``). ``tm``
    is the row tile, ``row_tile``'s where None (the tests pass a small one
    so that small groups cross a tile's edge).

    Returns [M, N] in ``xs.dtype``: row ``r`` of group ``g`` is
    ``xs[r] @ w[first + g]``, accumulated in float32. ``row_tile`` and
    ``col_tile`` say which shapes the kernel takes; callers fall back to
    ``jax.lax.ragged_dot`` otherwise.
    """
    m, k = xs.shape
    n_experts, _, n = w.shape
    tm = row_tile(m) if tm is None else tm
    tn = col_tile(k, n, w.dtype.itemsize)
    if tm is None or tn is None or m % tm:
        raise ValueError(f"no tiling for {xs.shape} x {w.shape}")
    items = work_items(jnp.asarray(sizes, jnp.int32), first, m, tm, n_experts)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        # columns outermost: a row tile's revisits are consecutive items
        grid=(n // tn, items[0].shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, i, e, t, lo, hi: (t[i], 0)),
            pl.BlockSpec((None, k, tn), lambda j, i, e, t, lo, hi: (e[i], 0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, e, t, lo, hi: (t[i], j)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="grouped_matmul_tpu",
    )(*items, xs, w)
