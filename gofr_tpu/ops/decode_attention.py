"""Pallas TPU grouped-query decode attention over a padded KV cache.

The decode step's cost is one sweep of the KV cache per layer; with a
padded [B, S_max, KV, D] cache, XLA reads and masks all S_max positions
even when a row holds a 100-token conversation in a 2048-slot cache. The
kernels here make the sweep proportional to the VALID length instead:

- grid = (B,): ONE cell per batch row (a first version gridded over
  (B, S-blocks) and lost everything to per-cell overhead — 256 tiny
  sequential cells per layer; this shape has 32).
- the caches stay in HBM (``memory_space=ANY``); the kernel issues its own
  double-buffered ``make_async_copy`` inside a ``fori_loop`` whose trip
  count follows ``kv_len[b]`` (scalar prefetch) — the padded tail is
  neither DMA'd nor computed, so cost tracks the live prefix, not S_max
  (guide: "DMA Pipeline Pattern").
- online softmax (running max / sum / accumulator carried in f32 through
  the loop, as in flash_attention.py).

Two bodies, chosen by how the cache lies in HBM:

- **One matrix a block** (``_dense_kernel``): a full-precision cache whose
  KV heads are whole sublane tiles (``KV % 8 == 0``: Mistral's 8,
  DeepSeek's 32) is the page pool's easy case. ``[S_max, KV, D]`` and
  ``[S_max * KV, D]`` are then the same bytes (row ``t * KV + g`` is token
  ``t``, KV head ``g``), and the row is read by
  ``paged_attention.walk_pages``, the body the paged layout's kernel runs,
  with arithmetic where that one looks a page up in its table: the row's
  ``j``-th "page" is its ``j``-th chunk of ``row_tiling``'s tokens. So
  every head meets a block of 2,048 rows in ONE product on the MXU in the
  cache's dtype with float32 accumulation, an additive own-head mask
  standing in for per-head slices (MHA and any group size are the same
  code), and the block that holds the row's last token is fetched chunk
  by chunk up to the live length, not whole.
- **A product a KV head** (``_decode_kernel``): the int8 cache (flat
  ``[S_max, KV * D]`` values with seq-minor scales, dequantised in VMEM
  so that HBM only ever moves int8), and a full-precision cache with
  fewer KV heads than a sublane tile holds (Qwen3-Next's 2 of 256): there
  the heads of one token lie packed inside one 32-bit word of a (2, 128)
  tile, the flat view would be a copy of the cache (2 GB a layer call at
  that cell's size), and putting the block's matrix together in VMEM from
  a strided slice a head read eight times slower on the chip than this
  body (``PERF.md`` §6, PR 33). It fetches whole blocks of ``block_s``
  positions and contracts each KV head's ``n_rep`` query rows against the
  un-expanded chunk in float32 — no ``repeat_kv`` inside VMEM either.

Reference has no counterpart (pure-Go, no ML — SURVEY §2.10); this is the
serving-path analogue of the prefill flash kernel, needed to hold the
BASELINE.md config-#4 token rate at large slot counts and caches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import NEG_INF, block_pages, walk_pages

# rows (token x KV head) a chunk, the unit a row's tail is fetched in: the
# tail over-reads half a chunk a row in the mean (8 tokens at 8 KV heads).
# On the chip chunks of 128, 256 and 512 rows read alike and a whole block
# 5 % slower at short rows (PERF.md §6, PR 33)
_CHUNK_ROWS = 128

__all__ = ["gqa_decode_attention_tpu", "row_tiling"]


def row_tiling(s_max: int, kv_heads: int, head_dim: int, itemsize: int,
               flat: bool = False) -> tuple[int, int] | None:
    """(tokens a chunk, rows a block) for a full-precision cache that
    ``_dense_kernel`` takes, or None where it does not: the KV heads must
    be whole sublane tiles (else the flat view is a copy) unless the cache
    is stored ``flat`` already, a row whole chunks, and a chunk must do as
    a page of ``paged_attention``'s walk, whose block of pages is then the
    block."""
    chunk_s = max(1, _CHUNK_ROWS // kv_heads)
    chunks = block_pages(chunk_s, kv_heads, head_dim, itemsize)
    if ((kv_heads % 8 and not flat) or s_max % chunk_s or chunks is None):
        return None
    return chunk_s, chunks * chunk_s * kv_heads


def _dense_kernel(kvlen_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                  v_buf, own_ref, k_sem, v_sem, *, page_s: int, kv_heads: int,
                  n_rep: int):
    """A row's pages lie one behind the other in its own row of the cache;
    k_hbm/v_hbm: [L, B, S_max * KV, D] in HBM."""
    b = pl.program_id(0)
    layer = layer_ref[0]
    page_rows = page_s * kv_heads

    def page_at(first, j):
        return layer, b, pl.ds(
            pl.multiple_of((first + j) * page_rows, page_rows), page_rows)

    walk_pages(kvlen_ref[b], page_at, q_ref, k_hbm, v_hbm, o_ref, k_buf,
               v_buf, own_ref, k_sem, v_sem, page_s=page_s, kv_heads=kv_heads,
               n_rep=n_rep)



def _decode_kernel(kvlen_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                   v_buf, k_sem, v_sem, *, block_s: int, kv_heads: int,
                   n_rep: int, ks_hbm=None, vs_hbm=None, ks_buf=None,
                   vs_buf=None, ks_sem=None, vs_sem=None):
    """One batch row: pipelined chunk sweep of its live cache prefix.

    q_ref/o_ref: [H, D] VMEM; k_hbm/v_hbm: [L, B, S_max, KV, D] in HBM
    (the layer to read is the scalar ``layer_ref[0]``);
    k_buf/v_buf: [2, block_s, KV, D] VMEM double buffers. With an int8
    cache the ks/vs refs carry the [L, B, S_max, KV] bf16 scales (1/D-th
    the data) and dequantization happens here in VMEM — HBM only ever
    moves int8.
    """
    b = pl.program_id(0)
    kvlen = kvlen_ref[b]
    layer = layer_ref[0]
    n_blocks = pl.cdiv(kvlen, block_s)  # >= 1: a live row has len >= 1
    h, d = q_ref.shape
    scale = d ** -0.5
    quantized = ks_hbm is not None

    def copy_in(hbm, buf, sem, slot, idx):
        return pltpu.make_async_copy(
            hbm.at[layer, b, pl.ds(idx * block_s, block_s)], buf.at[slot],
            sem.at[slot])

    def copy_scale(hbm, buf, sem, slot, idx):
        # scales are [L, B, KV, S] (seq minor): the [KV, block_s] slice
        # keeps the DMA's minor dim 128-aligned
        return pltpu.make_async_copy(
            hbm.at[layer, b, :, pl.ds(idx * block_s, block_s)], buf.at[slot],
            sem.at[slot])

    def start_block(slot, idx):
        copy_in(k_hbm, k_buf, k_sem, slot, idx).start()
        copy_in(v_hbm, v_buf, v_sem, slot, idx).start()
        if quantized:
            copy_scale(ks_hbm, ks_buf, ks_sem, slot, idx).start()
            copy_scale(vs_hbm, vs_buf, vs_sem, slot, idx).start()

    def wait_block(slot, idx):
        copy_in(k_hbm, k_buf, k_sem, slot, idx).wait()
        copy_in(v_hbm, v_buf, v_sem, slot, idx).wait()
        if quantized:
            copy_scale(ks_hbm, ks_buf, ks_sem, slot, idx).wait()
            copy_scale(vs_hbm, vs_buf, vs_sem, slot, idx).wait()

    start_block(0, 0)

    q = q_ref[:].astype(jnp.float32) * scale  # [H, D]

    def body(i, carry):
        acc, m, l = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < n_blocks)
        def _prefetch():
            start_block(nxt, i + 1)

        wait_block(slot, i)

        kpos = i * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_s), 1)
        valid = kpos < kvlen  # [1, block_s]
        accs, ms, ls = [], [], []
        for g in range(kv_heads):  # static unroll: KV is small (e.g. 8)
            r0 = g * n_rep
            if quantized:
                # flat int8 buf [block_s, KV*D]: head g is a static,
                # 128-aligned column slice; dequant in VMEM
                k = k_buf[slot, :, g * d:(g + 1) * d].astype(jnp.float32)
                v = v_buf[slot, :, g * d:(g + 1) * d].astype(jnp.float32)
                k = k * ks_buf[slot, g, :].astype(jnp.float32)[:, None]
                v = v * vs_buf[slot, g, :].astype(jnp.float32)[:, None]
            else:
                k = k_buf[slot, :, g, :].astype(jnp.float32)  # [block_s, D]
                v = v_buf[slot, :, g, :].astype(jnp.float32)
            logits = jax.lax.dot_general(
                q[r0:r0 + n_rep], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [n_rep, block_s]
            logits = jnp.where(valid, logits, NEG_INF)
            m_prev = m[r0:r0 + n_rep]
            l_prev = l[r0:r0 + n_rep]
            a_prev = acc[r0:r0 + n_rep]
            m_cur = jnp.max(logits, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(logits - m_new)
            alpha = jnp.exp(m_prev - m_new)
            accs.append(a_prev * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            ms.append(m_new)
            ls.append(alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True))
        return (jnp.concatenate(accs, axis=0),
                jnp.concatenate(ms, axis=0),
                jnp.concatenate(ls, axis=0))

    acc0 = jnp.zeros((h, d), jnp.float32)
    m0 = jnp.full((h, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((h, 1), jnp.float32)
    acc, _m, l = jax.lax.fori_loop(0, n_blocks, body, (acc0, m0, l0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _decode_kernel_quant(kvlen_ref, layer_ref, q_ref, k_hbm, v_hbm, ks_hbm,
                         vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, k_sem,
                         v_sem, ks_sem, vs_sem, *, block_s: int,
                         kv_heads: int, n_rep: int):
    """Positional-ref wrapper for the int8 variant (pallas passes refs in
    in_specs/scratch order, so the two layouts need two entry points)."""
    _decode_kernel(kvlen_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf,
                   v_buf, k_sem, v_sem, block_s=block_s, kv_heads=kv_heads,
                   n_rep=n_rep, ks_hbm=ks_hbm, vs_hbm=vs_hbm, ks_buf=ks_buf,
                   vs_buf=vs_buf, ks_sem=ks_sem, vs_sem=vs_sem)


@functools.partial(jax.jit,
                   static_argnames=("block_s", "interpret", "flat_kv_heads"))
def gqa_decode_attention_tpu(q, k_cache, v_cache, kv_len, *, layer=None,
                             k_scale=None, v_scale=None,
                             flat_kv_heads: int | None = None,
                             block_s: int = 256, interpret: bool = False):
    """q: [B, 1, H, D]; caches: [B, S_max, KV, D] per-layer, or the full
    stacked [L, B, S_max, KV, D] with ``layer`` the (traced) index to read;
    kv_len: [B] int32. Optional ``k_scale``/``v_scale`` ([..., KV, S_max]
    bf16, seq minor) mark an int8 cache: dequantization happens in VMEM.

    Returns [B, 1, H, D] in q.dtype. A full-precision cache that has a
    tiling (``row_tiling``) is read as one matrix a block, whose size
    follows from the cache's widths, with ``kv_len`` clamped to
    ``[1, S_max]`` so that the walk never leaves the row. Every other cache
    is read a product a KV head in blocks of ``block_s`` positions, which
    S_max must divide by (serving caches are power-of-two sized; callers
    fall back to the XLA path otherwise) and ``kv_len`` must not pass.

    ``flat_kv_heads``: the full-precision cache is stored as the matrix the
    first body reads, ``[L, B, S_max * KV, D]`` (row ``t * KV + g`` is
    token ``t``, KV head ``g``), so fewer KV heads than a sublane tile
    holds cost their own bytes in HBM and take that body too (its query
    heads padded to a multiple of 8 where they are none).
    """
    b, tq, h, d = q.shape
    quantized = k_scale is not None
    flat = flat_kv_heads is not None
    per_layer_ndim = 3 if quantized or flat else 4  # both kinds lie FLAT
    if k_cache.ndim == per_layer_ndim:
        k_cache, v_cache = k_cache[None], v_cache[None]
        if quantized:
            k_scale, v_scale = k_scale[None], v_scale[None]
        layer = 0
    if layer is None:
        raise ValueError("stacked caches require a layer index")
    n_layers = k_cache.shape[0]
    if flat:
        kv = flat_kv_heads
        s_max = k_cache.shape[2] // kv
    else:
        s_max = k_cache.shape[2]
        kv = k_scale.shape[2] if quantized else k_cache.shape[3]
    if tq != 1:
        raise ValueError(f"decode kernel takes one query token, got Tq={tq}")
    n_rep = h // kv
    kv_len = jnp.asarray(kv_len, jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    tiling = None if quantized else row_tiling(
        s_max, kv, d, k_cache.dtype.itemsize, flat=flat)
    if flat and tiling is None:
        raise ValueError(f"no tiling for a flat cache of S_max={s_max}, "
                         f"KV={kv}, D={d}, {k_cache.dtype}")

    # the one-matrix body wants the query heads in whole sublane tiles (20
    # heads on one KV head are not): a padded row has no KV head of its
    # own, so the own-head mask hides every lane from it, and it is cut off
    hp = h + (-h % 8 if tiling is not None else 0)
    q_rows = q[:, 0]
    if hp != h:
        q_rows = jnp.pad(q_rows, ((0, 0), (0, hp - h), (0, 0)))
    row_spec = pl.BlockSpec((None, hp, d), lambda bi, kvlen, lyr: (bi, 0, 0))
    in_specs = [
        row_spec,
        pl.BlockSpec(memory_space=pl.ANY),  # k cache stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),  # v cache stays in HBM
    ]
    sems = [pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))]
    if tiling is not None:
        chunk_s, block_rows = tiling
        kernel = functools.partial(
            _dense_kernel, page_s=chunk_s, kv_heads=kv, n_rep=n_rep)
        scratch = [pltpu.VMEM((2, block_rows, d), k_cache.dtype),
                   pltpu.VMEM((2, block_rows, d), v_cache.dtype),
                   pltpu.VMEM((hp, block_rows), jnp.float32)]
        # [S_max, KV, D] -> [S_max * KV, D]: the same bytes (a cache stored
        # flat is that already), so that a chunk lands in the buffer as
        # rows of one matrix
        args = [jnp.clip(kv_len, 1, s_max), layer, q_rows,
                k_cache.reshape(n_layers, b, s_max * kv, d),
                v_cache.reshape(n_layers, b, s_max * kv, d)]
    else:
        block_s = min(block_s, s_max)
        if s_max % block_s:
            raise ValueError(f"S_max {s_max} must divide block_s {block_s}")
        buf_shape = (2, block_s, kv * d) if quantized else (2, block_s, kv, d)
        scratch = [
            pltpu.VMEM(buf_shape, k_cache.dtype),
            pltpu.VMEM(buf_shape, v_cache.dtype),
        ]
        args = [kv_len, layer, q_rows, k_cache, v_cache]
        if quantized:
            kernel = functools.partial(
                _decode_kernel_quant, block_s=block_s, kv_heads=kv,
                n_rep=n_rep)
            in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                         pl.BlockSpec(memory_space=pl.ANY)]
            scratch += [pltpu.VMEM((2, kv, block_s), k_scale.dtype),
                        pltpu.VMEM((2, kv, block_s), v_scale.dtype)]
            sems += [pltpu.SemaphoreType.DMA((2,)),
                     pltpu.SemaphoreType.DMA((2,))]
            args += [k_scale, v_scale]
        else:
            kernel = functools.partial(
                _decode_kernel, block_s=block_s, kv_heads=kv, n_rep=n_rep)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=scratch + sems,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hp, d), q.dtype),
        interpret=interpret,
    )(*args)
    return out[:, None, :h] if hp != h else out[:, None]
