"""Pallas TPU decode attention over a latent cache (multi-head latent
attention, absorbed).

A latent-attention layer (DeepSeek-V3's MLA) caches one row a token for
all its heads: the normed latent ``c_kv`` and the rotated key part
``k_pe``, ``[c_kv | k_pe]``, 576 values at the published widths. The
chip tiles an array's last axis in whole lanes of 128, so a plane of 576
costs 640 in HBM whatever the program does, and a block of it cannot be
copied at 576: the cache stores the row at whole lanes, ``W = 640``, its
last 64 values zero (as does the query), and the kernel reads W. Decode
absorbs the key up-projection into the query (``q_lat = q_nope W_uk^T``)
and the value up-projection into the output, so that every head scores
``[q_lat | q_pe] . [c_kv | k_pe]`` against the same row and reads back
``softmax(.) c_kv``: the row's first ``value_width`` values are the
values. This kernel reads each row's live positions once for all heads:

- grid = (B,), one cell a row; the cache stays one stack ``[L, B, S, W]``
  in HBM (``memory_space=ANY``), the layer and ``kv_len`` ride scalar
  prefetch, as ``gated_delta.py`` and ``selective_state.py`` name a layer.
  No layer's slab is cut out before the call.
- a ``fori_loop`` over blocks of positions whose trip count follows
  ``kv_len[b]``; a block is one ``make_async_copy`` into a double buffer,
  the next block in flight while this one is reduced. Positions past the
  row's length are neither fetched nor computed (but for the tail of the
  last block, which is masked).
- all heads meet a block in two products on the MXU in the cache's dtype:
  ``q [H, W] x block^T -> [H, positions]`` and ``p [H, positions] x
  block[:, :value_width]``; online softmax in float32, carried over the
  blocks.

Bytes a decoded token and layer: ``context x 576 x itemsize`` of the
row's own values (640 as stored); operations ``H x context x (2 x 576 +
2 value_width)``: at 64 heads about 121 FLOP a byte, under the v5e's
ridge, so the kernel is bandwidth-bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# positions a block: [64, 512] float32 logits, and the first block of a
# row (fetched with nothing to overlap it) stays short
_BLOCK = 512

__all__ = ["latent_decode_attention_ref", "latent_decode_attention_tpu",
           "block_positions"]


def block_positions(s_max: int, width: int, value_width: int,
                    itemsize: int) -> int | None:
    """Positions a block for a cache of these widths, or None where the
    kernel's tiling does not take them: a block of whole sublane tiles
    that divides ``S``, a row and its values of whole lanes."""
    block = min(_BLOCK, s_max)
    if (s_max % block or block % (32 // itemsize) or width % 128
            or value_width % 128 or value_width > width):
        return None
    return block


def latent_decode_attention_ref(q, cache, kv_len, layer, *, scale: float,
                                value_width: int):
    """The plain computation: ``q`` [B, H, W]; ``cache`` [L, B, S, W];
    ``kv_len`` [B]; ``layer`` a (traced) index. Returns [B, H,
    value_width] in ``q.dtype``."""
    rows = jax.lax.dynamic_index_in_dim(cache, layer, 0, keepdims=False)
    logits = jnp.einsum("bhw,bsw->bhs", q, rows,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(rows.shape[1])[None, :] < kv_len[:, None]
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhs,bsv->bhv", p.astype(rows.dtype),
                     rows[..., :value_width],
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def _kernel(kvlen_ref, layer_ref, q_ref, c_hbm, o_ref, buf, sem, *,
            scale: float, value_width: int):
    b = pl.program_id(0)
    kvlen = kvlen_ref[b]
    layer = layer_ref[0]
    block = buf.shape[1]
    n_blocks = pl.cdiv(kvlen, block)

    def copy(slot, i):
        start = pl.multiple_of(i * block, block)
        return pltpu.make_async_copy(
            c_hbm.at[layer, b, pl.ds(start, block)], buf.at[slot],
            sem.at[slot])

    copy(0, 0).start()
    q = q_ref[...]                                       # [H, W]

    def body(i, carry):
        acc, m, l = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            copy(1 - slot, i + 1).start()

        copy(slot, i).wait()
        rows = buf[slot]                                 # [block, W]
        logits = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [H, block]
        pos = i * block + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(pos < kvlen, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(rows.dtype), rows[:, :value_width],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        return acc, m_new, l

    h = q.shape[0]
    acc0 = jnp.zeros((h, value_width), jnp.float32)
    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc, _m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "value_width", "interpret"))
def latent_decode_attention_tpu(q, cache, kv_len, layer, *, scale: float,
                                value_width: int, interpret: bool = False):
    """``q`` [B, H, W] (``[q_lat | q_pe]``); ``cache`` the whole stack
    [L, B, S, W] with ``layer`` the (traced) index to read; ``kv_len`` [B]
    int32, clamped here to ``[1, S]``. Returns [B, H, value_width] in
    ``q.dtype``: ``softmax(scale q . row) row[:value_width]`` over each
    row's first ``kv_len`` positions."""
    b, h, w = q.shape
    s_max = cache.shape[2]
    block = block_positions(s_max, w, value_width, cache.dtype.itemsize)
    if block is None or cache.shape[-1] != w:
        raise ValueError(f"no tiling for a cache of {cache.shape} "
                         f"{cache.dtype} under a query of {q.shape}")
    kv_len = jnp.clip(jnp.asarray(kv_len, jnp.int32), 1, s_max)
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    kernel = functools.partial(_kernel, scale=float(scale),
                               value_width=value_width)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((None, h, w), lambda bi, *_: (bi, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # the stack stays in HBM
            ],
            out_specs=pl.BlockSpec((None, h, value_width),
                                   lambda bi, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, block, w), cache.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, value_width), q.dtype),
        interpret=interpret,
        name="latent_decode_attention_tpu",
    )(kv_len, layer, q, cache)
