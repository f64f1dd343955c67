"""Pallas TPU flash attention (causal, BSHD layout).

The hot op of BASELINE.md configs #3/#4. Online-softmax attention that never
materializes the [Tq, Tk] logits matrix in HBM: for each (batch*head,
q-block) grid cell the kernel streams K/V blocks through VMEM, keeping a
running max / sum / accumulator in f32.

Kernel shape notes (pallas_guide.md):
- blocks are (block_q, head_dim) and (block_k, head_dim) with head_dim
  last (lane dim, multiple of 128) — MXU-friendly without transposes.
- logits/accumulator stay f32 in VMEM; inputs arrive bf16.
- causal skip: K blocks entirely above the diagonal are not even read
  (grid dimension is masked with ``when``), halving FLOPs and DMA traffic.

Tested in interpret mode on CPU (tests/test_ops.py), compiled for a
described v5e in tests/test_tpu_compile.py, and run on the chip by every
prefill of the benchmark's cells (benchmark/run.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, *, block_k: int,
                 causal: bool, q_offset: int, seq_k: int, has_kvlen: bool,
                 n_heads: int):
    """One (batch*head, q_block) cell: loop K blocks with online softmax."""
    block_q, head_dim = q_ref.shape
    q = q_ref[:].astype(jnp.float32) * (head_dim ** -0.5)
    q_block_idx = pl.program_id(1)
    q_start = q_block_idx * block_q + q_offset

    n_kblocks = pl.cdiv(seq_k, block_k)
    # the whole [B] length vector rides SMEM (a per-cell (1,) block would
    # violate the rank-1 block tiling rule for B > 1); index our row here
    kvlen = kvlen_ref[pl.program_id(0) // n_heads] if has_kvlen else seq_k

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k_start = kb * block_k
        k = k_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(k_start, block_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
            logits = jnp.where(kpos <= qpos, logits, NEG_INF)
        if has_kvlen:  # mask padded cache slots beyond the row's true length
            logits = jnp.where(kpos < kvlen, logits, NEG_INF)
        m_cur = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(logits - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc_new, m_new, l_new

    # skip K blocks that contribute nothing: past the causal diagonal and
    # past the row's valid length (both DMA + FLOP savings)
    if causal:
        last_q = q_start + block_q - 1
        n_needed = jnp.minimum(n_kblocks, pl.cdiv(last_q + 1, block_k))
    else:
        n_needed = n_kblocks
    if has_kvlen:
        n_needed = jnp.minimum(n_needed, pl.cdiv(kvlen, block_k))

    acc0 = jnp.zeros((block_q, head_dim), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, _, l = jax.lax.fori_loop(0, n_needed, body, (acc0, m0, l0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "q_offset", "block_q", "block_k", "interpret")
)
def flash_attention_tpu(q, k, v, kv_len=None, *, causal: bool = True,
                        q_offset: int = 0, block_q: int = 256,
                        block_k: int = 256, interpret: bool = False):
    """q: [B, Tq, H, D]; k, v: [B, Tk, H, D] (GQA already expanded);
    kv_len: optional [B] int32 valid K/V lengths (padded-prompt masking).

    Returns [B, Tq, H, D] in q.dtype. Tq/Tk are padded to block multiples by
    the caller (model code buckets sequence lengths anyway).
    """
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    if tq % block_q or tk % block_k:
        raise ValueError(f"seq lens ({tq},{tk}) must divide blocks ({block_q},{block_k})")

    # Fold (B, H) into one grid axis; move seq next to head_dim per cell.
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)

    has_kvlen = kv_len is not None
    if not has_kvlen:
        kv_len = jnp.zeros((b,), jnp.int32)  # placeholder, unread
    kv_len = jnp.asarray(kv_len, jnp.int32)

    kernel = functools.partial(
        _attn_kernel, block_k=block_k, causal=causal, q_offset=q_offset,
        seq_k=tk, has_kvlen=has_kvlen, n_heads=h,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, tq // block_q),
        in_specs=[
            # full [B] valid-length vector in SMEM for every cell
            pl.BlockSpec((b,), lambda i, j: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, tk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, tk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        interpret=interpret,
    )(kv_len, qr, kr, vr)
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
