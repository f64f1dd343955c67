"""Pallas TPU decode attention that walks the page table.

The paged layout keeps keys and values in one pool ``[L, N, page_s, KV, D]``
and gives each row a table of page ids. Gathering every row's whole
virtual sequence (``jnp.take`` of ``P_max`` pages, whatever the row's
length) and masking it afterwards moved 4.3 GB a step at DeepSeek-7B
widths; this kernel reads the pages where they lie, and only the live
ones:

- grid = (B,), one cell a row, as the dense kernel
  (``decode_attention.py``); the pool stays whole in HBM
  (``memory_space=ANY``), the layer index, the table and ``kv_len`` ride
  scalar prefetch. No slab of the pool is sliced out before the call.
- a ``fori_loop`` over blocks of whole pages whose trip count follows
  ``kv_len[b]``; a block is one ``make_async_copy`` a live page for K and
  one for V into a double buffer, the next block in flight while this one
  is reduced. Pages past the row's length are neither fetched nor
  computed.
- the pool is seen as ``[L, N, page_s * KV, D]`` (the same bytes), so a
  block is a plain ``[rows, D]`` matrix whose row ``t * KV + g`` is token
  ``t``, KV head ``g``. All heads meet it in ONE product,
  ``q [H, D] x block^T -> [H, rows]``, on the MXU in the planes' dtype;
  the entries where row ``h`` meets a head that is not its own
  (``lane % KV != h // n_rep``) are masked to -inf, so after the softmax
  they are zeros and the second product ``p [H, rows] x block_v`` needs
  no per-head slicing either. Grouped heads stay grouped: there is no
  ``repeat_kv``, and MHA (``n_rep == 1``) is the same code.
- online softmax in float32, carried over the blocks.

A block is as many whole pages as come nearest ``_BLOCK_ROWS`` rows, so
it follows from ``page_s * KV``; callers choose nothing. On a v5e blocks
of 2,048 rows (0.5 MB a plane at head size 128) read faster than the
8,192 that would fill the scoped VMEM: the first block of every row is
fetched with nothing to overlap it, and that bubble is one block long.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# rows (token x KV head) a block: [H, 2048] float32 logits of 32 heads are
# the vector register file, and the chip read no other size faster
_BLOCK_ROWS = 2048

__all__ = ["paged_decode_attention_tpu", "block_pages", "walk_pages"]


def block_pages(page_s: int, kv_heads: int, head_dim: int, itemsize: int
                ) -> int | None:
    """Pages a block for a pool of these widths, or None where the
    kernel's tiling does not take them: a page must be whole sublane
    tiles, a block whole lane tiles."""
    page_rows = page_s * kv_heads
    if head_dim % 128 or page_rows % (32 // itemsize):
        return None
    pages = max(1, _BLOCK_ROWS // page_rows)
    return pages if (pages * page_rows) % 128 == 0 else None


def walk_pages(kvlen, page_at, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf,
               own_ref, k_sem, v_sem, *, page_s: int, kv_heads: int,
               n_rep: int):
    """One batch row: pipelined sweep of its ``kvlen`` live positions, a
    page at a time. ``page_at(first, j)`` indexes either plane down to the
    ``[page_s * KV, D]`` slice that holds the row's page ``first + j``: a
    look-up in the page table for the pool, arithmetic for a dense row
    (``decode_attention.py``).

    q_ref/o_ref: [H, D] VMEM; k_buf/v_buf: [2, block_rows, D] double
    buffers; own_ref: [H, block_rows] float32, 0 where a lane's KV head is
    the row's own and -inf elsewhere.
    """
    h, d = q_ref.shape
    page_rows = page_s * kv_heads
    block_rows = k_buf.shape[1]
    n_block_pages = block_rows // page_rows
    n_pages = pl.cdiv(kvlen, page_s)
    n_blocks = pl.cdiv(n_pages, n_block_pages)
    live_rows = kvlen * kv_heads

    @pl.when(pl.program_id(0) == 0)
    def _init():
        # a page that is not fetched leaves its rows of the buffer as they
        # were: masked out of the softmax, but 0 x NaN is NaN in the second
        # product, so the V buffers start finite and stay so
        v_buf[...] = jnp.zeros_like(v_buf)
        lane = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, own_ref.shape, 0)
        own_ref[...] = jnp.where(
            jax.lax.rem(lane, kv_heads) == row // n_rep, 0.0, NEG_INF)

    def block_copies(slot, idx, fn):
        """``fn`` on the K and V copy of every live page of block ``idx``."""
        first = idx * n_block_pages
        live = jnp.minimum(n_block_pages, n_pages - first)

        def one(j, carry):
            page = page_at(first, j)
            dst = pl.ds(pl.multiple_of(j * page_rows, page_rows), page_rows)
            fn(pltpu.make_async_copy(k_hbm.at[page],
                                     k_buf.at[slot, dst], k_sem.at[slot]))
            fn(pltpu.make_async_copy(v_hbm.at[page],
                                     v_buf.at[slot, dst], v_sem.at[slot]))
            return carry

        jax.lax.fori_loop(0, live, one, 0)

    block_copies(0, 0, lambda copy: copy.start())

    q = q_ref[...]
    scale = d ** -0.5

    def body(i, carry):
        acc, m, l = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            block_copies(1 - slot, i + 1, lambda copy: copy.start())

        block_copies(slot, i, lambda copy: copy.wait())

        k = k_buf[slot]                                  # [block_rows, D]
        v = v_buf[slot]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, block_rows]
        lane = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(lane < live_rows - i * block_rows,
                           logits + own_ref[...], NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        return acc, m_new, l

    acc0 = jnp.zeros((h, d), jnp.float32)
    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc, _m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _paged_kernel(kvlen_ref, layer_ref, table_ref, q_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, own_ref, k_sem, v_sem, *,
                  p_max: int, **widths):
    """A row's pages are where its row of the table says; k_hbm/v_hbm:
    [L, N, page_s * KV, D] in HBM."""
    b = pl.program_id(0)
    kvlen = kvlen_ref[b]
    layer = layer_ref[0]

    def page_at(first, j):
        return layer, table_ref[b * p_max + first + j]

    walk_pages(kvlen, page_at, q_ref, k_hbm, v_hbm, o_ref, k_buf,
               v_buf, own_ref, k_sem, v_sem, **widths)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_tpu(q, k_pool, v_pool, table, kv_len, *, layer,
                               interpret: bool = False):
    """q: [B, 1, H, D]; pools: the stacked ``[L, N, page_s, KV, D]`` planes
    with ``layer`` the (traced) index to read; table: [B, P_max] int32 page
    ids in virtual order; kv_len: [B] int32, clamped here to
    ``[1, P_max * page_s]`` so that the walk never leaves the table.

    Returns [B, 1, H, D] in q.dtype. ``block_pages`` says which widths the
    kernel takes; callers fall back to the gather otherwise.
    """
    b, tq, h, d = q.shape
    n_layers, n_pool, page_s, kv, _ = k_pool.shape
    p_max = table.shape[1]
    if tq != 1:
        raise ValueError(f"decode kernel takes one query token, got Tq={tq}")
    n_block_pages = block_pages(page_s, kv, d, k_pool.dtype.itemsize)
    if n_block_pages is None:
        raise ValueError(
            f"no block for page_s={page_s}, KV={kv}, D={d}, {k_pool.dtype}")
    page_rows = page_s * kv
    block_rows = n_block_pages * page_rows
    kv_len = jnp.clip(jnp.asarray(kv_len, jnp.int32), 1, p_max * page_s)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    table = jnp.asarray(table, jnp.int32).reshape(-1)
    # [page_s, KV, D] -> [page_s * KV, D]: the same bytes, so that a page
    # lands in the buffer as rows of one matrix
    k_pool = k_pool.reshape(n_layers, n_pool, page_rows, d)
    v_pool = v_pool.reshape(n_layers, n_pool, page_rows, d)

    kernel = functools.partial(
        _paged_kernel, p_max=p_max, page_s=page_s, kv_heads=kv, n_rep=h // kv)
    row_spec = pl.BlockSpec((None, h, d), lambda bi, *_: (bi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            row_spec,
            pl.BlockSpec(memory_space=pl.ANY),  # k pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),  # v pool stays in HBM
        ],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, block_rows, d), k_pool.dtype),
            pltpu.VMEM((2, block_rows, d), v_pool.dtype),
            pltpu.VMEM((h, block_rows), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        interpret=interpret,
    )(kv_len, layer, table, q[:, 0], k_pool, v_pool)
    return out[:, None]
