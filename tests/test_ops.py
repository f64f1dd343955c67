"""Ops: reference attention invariants + pallas kernel parity (interpret).

Mirrors the reference's table-driven colocated unit tests (SURVEY §4) —
hermetic, no hardware: the Pallas kernel runs in interpreter mode on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ops import (
    apply_rope,
    attention,
    decode_attention,
    repeat_kv,
    rms_norm,
    rope_table,
)
from gofr_tpu.ops.flash_attention import flash_attention_tpu


def test_rms_norm_unit_scale():
    x = jnp.ones((2, 4, 8), jnp.bfloat16) * 3.0
    out = rms_norm(x, jnp.ones((8,)))
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.0, atol=1e-2)


def test_rope_preserves_norm_and_zero_position_identity():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 16))
    cos, sin = rope_table(jnp.arange(4)[None, :], 16, theta=10_000.0)
    rq = apply_rope(q, cos, sin)
    # rotation preserves per-head norms
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(q), axis=-1),
        np.linalg.norm(np.asarray(rq), axis=-1),
        rtol=1e-5,
    )
    # position 0 has angle 0 -> identity
    np.testing.assert_allclose(np.asarray(q[:, 0]), np.asarray(rq[:, 0]), atol=1e-6)


def test_repeat_kv_expands_heads():
    kv = jnp.arange(2 * 3 * 2 * 4).reshape(2, 3, 2, 4).astype(jnp.float32)
    out = repeat_kv(kv, 3)
    assert out.shape == (2, 3, 6, 4)
    np.testing.assert_array_equal(np.asarray(out[:, :, 0]), np.asarray(out[:, :, 2]))


def test_attention_causal_ignores_future():
    """Changing a future token must not change earlier outputs."""
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, (1, 8, 2, 16)) for kk in jax.random.split(key, 3))
    out1 = attention(q, k, v, causal=True)
    k2 = k.at[:, -1].set(99.0)
    v2 = v.at[:, -1].set(99.0)
    out2 = attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), rtol=1e-5)
    assert not np.allclose(np.asarray(out1[:, -1]), np.asarray(out2[:, -1]))


def test_attention_kv_len_masks_padding():
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(kk, (2, 6, 2, 8)) for kk in jax.random.split(key, 3))
    out_full = attention(q[:, :4], k[:, :4], v[:, :4], causal=True)
    # same, but with 2 garbage padded positions masked by kv_len
    k_pad = k.at[:, 4:].set(7.0)
    v_pad = v.at[:, 4:].set(7.0)
    out_pad = attention(q[:, :4], k_pad, v_pad, causal=True,
                        kv_len=jnp.array([4, 4]))
    np.testing.assert_allclose(np.asarray(out_full), np.asarray(out_pad), rtol=1e-5)


def test_decode_attention_matches_full():
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (1, 5, 2, 8)) for kk in jax.random.split(key, 3))
    full = attention(q, k, v, causal=True)
    # last token via decode path over a padded cache
    pad = jnp.zeros((1, 3, 2, 8))
    kc = jnp.concatenate([k, pad], axis=1)
    vc = jnp.concatenate([v, pad], axis=1)
    dec = decode_attention(q[:, 4:5], kc, vc, kv_len=jnp.array([5]))
    np.testing.assert_allclose(np.asarray(full[:, 4]), np.asarray(dec[:, 0]), rtol=1e-5)


def test_gqa_decode_attention_matches_expanded():
    """Grouped decode == decode over repeat_kv-expanded caches, exactly the
    same math without materializing the expansion."""
    from gofr_tpu.ops import gqa_decode_attention, repeat_kv

    key = jax.random.PRNGKey(7)
    kq, kk, kv_ = jax.random.split(key, 3)
    B, S, KV, n_rep, D = 3, 16, 2, 4, 8
    q = jax.random.normal(kq, (B, 1, KV * n_rep, D))
    kc = jax.random.normal(kk, (B, S, KV, D))
    vc = jax.random.normal(kv_, (B, S, KV, D))
    kv_len = jnp.array([5, 16, 1])
    want = decode_attention(q, repeat_kv(kc, n_rep), repeat_kv(vc, n_rep),
                            kv_len=kv_len)
    got = gqa_decode_attention(q, kc, vc, kv_len=kv_len)
    # contraction order differs -> tiny f32 reassociation noise
    np.testing.assert_allclose(np.asarray(want), np.asarray(got),
                               rtol=1e-4, atol=1e-6)
    # MHA fallthrough (n_rep == 1)
    got_mha = gqa_decode_attention(q[:, :, :KV], kc, vc, kv_len=kv_len)
    want_mha = decode_attention(q[:, :, :KV], kc, vc, kv_len=kv_len)
    np.testing.assert_allclose(np.asarray(want_mha), np.asarray(got_mha), rtol=1e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_reference(causal):
    key = jax.random.PRNGKey(4)
    q, k, v = (jax.random.normal(kk, (2, 256, 2, 64), jnp.float32)
               for kk in jax.random.split(key, 3))
    ref = attention(q, k, v, causal=causal)
    out = flash_attention_tpu(q, k, v, causal=causal, block_q=128, block_k=128,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-2, rtol=2e-2)


def test_flash_kernel_bf16():
    key = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 64), jnp.float32).astype(jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    ref = attention(q, k, v, causal=True)
    out = flash_attention_tpu(q, k, v, causal=True, block_q=128, block_k=128,
                              interpret=True)
    np.testing.assert_allclose(
        np.asarray(ref, np.float32), np.asarray(out, np.float32), atol=5e-2, rtol=5e-2
    )


def test_flash_kernel_kv_len_masks_padding():
    """Kernel kv_len masking == reference kv_len masking (serving prefill)."""
    key = jax.random.PRNGKey(6)
    q, k, v = (jax.random.normal(kk, (2, 256, 2, 64), jnp.float32)
               for kk in jax.random.split(key, 3))
    kv_len = jnp.array([100, 256], jnp.int32)
    ref = attention(q, k, v, causal=True, kv_len=kv_len)
    out = flash_attention_tpu(q, k, v, kv_len, causal=True, block_q=128,
                              block_k=128, interpret=True)
    # rows past a sequence's kv_len see only masked keys -> compare valid area
    np.testing.assert_allclose(np.asarray(ref[0, :100]), np.asarray(out[0, :100]),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(ref[1]), np.asarray(out[1]),
                               atol=2e-2, rtol=2e-2)


def test_rope_scaling_llama3_bands():
    """llama3 NTK-by-parts (transformers _compute_llama3_parameters
    behavior): high-frequency bands untouched, low-frequency bands slowed
    by ``factor``, the middle interpolated strictly between."""
    from gofr_tpu.ops import scale_rope_freqs

    half = 64
    freqs = 1.0 / (500_000.0 ** (jnp.arange(0, half, dtype=jnp.float32)
                                 / half))
    sc = {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
          "high_freq_factor": 4.0,
          "original_max_position_embeddings": 8192}
    out = np.asarray(scale_rope_freqs(freqs, sc))
    base = np.asarray(freqs)
    wavelen = 2 * np.pi / base
    hi = wavelen < 8192 / 4.0
    lo = wavelen > 8192 / 1.0
    mid = ~hi & ~lo
    np.testing.assert_allclose(out[hi], base[hi])
    np.testing.assert_allclose(out[lo], base[lo] / 8.0, rtol=1e-6)
    assert np.all(out[mid] < base[mid])
    assert np.all(out[mid] > base[mid] / 8.0)
    # and the table itself changes where it must: position past the
    # original context rotates differently under scaling
    c0, _ = rope_table(jnp.asarray([[9000]]), 128, 500_000.0)
    c1, _ = rope_table(jnp.asarray([[9000]]), 128, 500_000.0, scaling=sc)
    assert not np.allclose(np.asarray(c0), np.asarray(c1))


def test_rope_scaling_linear_and_unsupported():
    from gofr_tpu.ops import scale_rope_freqs

    freqs = jnp.asarray([1.0, 0.5, 0.25], jnp.float32)
    out = scale_rope_freqs(freqs, {"type": "linear", "factor": 4.0})
    np.testing.assert_allclose(np.asarray(out), np.asarray(freqs) / 4.0)
    with pytest.raises(ValueError, match="rope_scaling"):
        scale_rope_freqs(freqs, {"rope_type": "yarn", "factor": 2.0})


# ------------------------------------------------- dense decode attention
DENSE_S = 768   # whole blocks of the per-head kernel, whole chunks of the other


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize(
    "lens", ["short", "block_edges", "chunk_edges", "ragged", "capacity"])
@pytest.mark.parametrize("heads,kv_heads,head_dim",
                         [(32, 8, 128), (32, 32, 128), (16, 2, 256)],
                         ids=["gqa32x8", "mha32", "gqa16x2-d256"])
def test_dense_kernel_matches_einsum(heads, kv_heads, head_dim, lens, dtype,
                                     tol):
    """The dense layout's full-precision kernel (interpret mode) against
    the XLA grouped einsum, the dispatcher's branch off the TPU, on a
    stacked cache read at layer 1: Mistral's and DeepSeek's widths take the
    one-matrix body (a row walked like pages), Qwen3-Next's 2 KV heads of
    256 the per-head body. One token, and an idle row whose stale length
    still counts; exactly a block, one past it and one short of it; the
    same around a chunk, the unit the tail is fetched in; ragged rows; a
    row at capacity, which the one-matrix body is handed one more than
    (``pos + 1`` with ``pos`` pinned) and must clamp, never reading past
    the row."""
    from gofr_tpu.ops import gqa_decode_attention
    from gofr_tpu.ops.decode_attention import (
        gqa_decode_attention_tpu,
        row_tiling,
    )

    tiling = row_tiling(DENSE_S, kv_heads, head_dim, jnp.dtype(dtype).itemsize)
    assert (tiling is not None) == (kv_heads % 8 == 0)
    if tiling is None:   # the per-head body: whole blocks of 256, no chunks,
        block, chunk, past = 256, 64, 0    # and its callers do the clamping
    else:
        chunk, block, past = tiling[0], tiling[1] // kv_heads, 1
    assert block + 1 <= DENSE_S
    handed = {
        "short": [1, 17, 500],
        "block_edges": [block, block + 1, block - 1],
        "chunk_edges": [chunk + 1, chunk - 1, 3 * chunk],
        "ragged": [5, 300, 77, DENSE_S - 3],
        "capacity": [DENSE_S + past, DENSE_S, 3],
    }[lens]
    b = len(handed)
    keys = jax.random.split(jax.random.PRNGKey(heads + kv_heads), 3)
    q = jax.random.normal(keys[0], (b, 1, heads, head_dim), dtype)
    k_cache, v_cache = (
        jax.random.normal(kk, (2, b, DENSE_S, kv_heads, head_dim), dtype)
        for kk in keys[1:])
    attended = jnp.asarray(np.minimum(handed, DENSE_S), jnp.int32)
    want = gqa_decode_attention(q, k_cache[1], v_cache[1], kv_len=attended)
    got = gqa_decode_attention_tpu(
        q, k_cache, v_cache, jnp.asarray(handed, jnp.int32), layer=1,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("s_max,kv_heads,head_dim,dtype,chunk", [
    (2048, 8, 128, jnp.bfloat16, 16),     # mistral7b-batch
    (2048, 32, 128, jnp.bfloat16, 4),     # DeepSeek on the dense layout
    (4096, 2, 256, jnp.bfloat16, None),   # qwen3next: heads packed in a tile
    (2048, 8, 64, jnp.bfloat16, None),    # toy head size: lanes not whole
    (2056, 8, 128, jnp.bfloat16, None),   # a row that is not whole chunks
    (768, 8, 128, jnp.float32, 16),
])
def test_dense_row_tiling(s_max, kv_heads, head_dim, dtype, chunk):
    """Which full-precision caches the one-matrix body takes, and that a
    block of what it takes is whole chunks, whole lane tiles and small
    beside the scoped VMEM (two planes, double-buffered)."""
    from gofr_tpu.ops.decode_attention import row_tiling

    itemsize = jnp.dtype(dtype).itemsize
    tiling = row_tiling(s_max, kv_heads, head_dim, itemsize)
    assert (tiling and tiling[0]) == chunk
    if tiling is not None:
        block_rows = tiling[1]
        assert block_rows % (chunk * kv_heads) == 0 and block_rows % 128 == 0
        assert 4 * block_rows * head_dim * itemsize <= 4 * 2**20


def test_dense_dispatcher_records_einsum_off_tpu():
    from gofr_tpu import ops

    q = jnp.ones((2, 1, 32, 128), jnp.float32)
    cache = jnp.ones((1, 2, 256, 8, 128), jnp.float32)
    ops.cached_decode_attention(q, cache, cache, jnp.asarray([40, 3]),
                                layer=0)
    key = ops.branch_key("decode_attention", q, cache)
    assert ops.kernel_branches()[key] == "xla"


# ------------------------------------------------- paged decode attention
PAGE_S, P_MAX, HEAD_D = 16, 40, 128   # a row's table holds 640 positions


def _paged_case(heads, kv_heads, lens, dtype=jnp.float32, idle=()):
    """q, a two-layer pool whose pages a row's table names in shuffled,
    non-contiguous order, and the table: entries past a row's length are
    scratch page 0, as are all of an idle row's."""
    b = len(lens)
    n_pool = b * P_MAX + 1
    keys = jax.random.split(jax.random.PRNGKey(heads + kv_heads), 3)
    q = jax.random.normal(keys[0], (b, 1, heads, HEAD_D), dtype)
    k_pool, v_pool = (
        jax.random.normal(kk, (2, n_pool, PAGE_S, kv_heads, HEAD_D), dtype)
        for kk in keys[1:])
    table = (np.random.RandomState(7).permutation(n_pool - 1)[:b * P_MAX]
             + 1).reshape(b, P_MAX).astype(np.int32)
    for row, n in enumerate(lens):
        table[row, -(-min(n, P_MAX * PAGE_S) // PAGE_S):] = 0
    for row in idle:
        table[row] = 0
    return q, k_pool, v_pool, jnp.asarray(table)


def _block_tokens(kv_heads, dtype=jnp.float32):
    from gofr_tpu.ops.paged_attention import block_pages

    return PAGE_S * block_pages(PAGE_S, kv_heads, HEAD_D,
                                jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("lens", ["short", "block_edges", "ragged", "capacity"])
@pytest.mark.parametrize("heads,kv_heads", [(32, 32), (32, 8)],
                         ids=["mha32", "gqa32x8"])
def test_paged_kernel_matches_gather(heads, kv_heads, lens):
    """The kernel that walks the page table (interpret mode) against the
    gather of every row's whole virtual sequence, the dispatcher's branch
    off the TPU: one token and an idle row; exactly a block, one past it
    and one short of it; ragged rows; a row at capacity handed one more
    than capacity (``pos + 1`` with ``pos`` pinned), which the kernel must
    clamp and never walk past the table."""
    from gofr_tpu.ops import paged_decode_attention
    from gofr_tpu.ops.paged_attention import paged_decode_attention_tpu

    block = _block_tokens(kv_heads)
    capacity = P_MAX * PAGE_S
    assert block + 1 <= capacity
    handed = {
        "short": [1, 17, 1],
        "block_edges": [block, block + 1, block - 1],
        "ragged": [5, 300, 77, capacity - 3],
        "capacity": [capacity + 1, capacity, 3],
    }[lens]
    q, k_pool, v_pool, table = _paged_case(
        heads, kv_heads, handed, idle=(2,) if lens == "short" else ())
    attended = jnp.asarray(np.minimum(handed, capacity), jnp.int32)
    want = paged_decode_attention(q, k_pool, v_pool, table, attended, layer=1)
    got = paged_decode_attention_tpu(
        q, k_pool, v_pool, table, jnp.asarray(handed, jnp.int32), layer=1,
        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_bf16_pool():
    """The serving dtype: products in bfloat16, softmax and sums float32."""
    from gofr_tpu.ops import paged_decode_attention
    from gofr_tpu.ops.paged_attention import paged_decode_attention_tpu

    block = _block_tokens(8, jnp.bfloat16)
    lens = jnp.asarray([block // 2 + 3, 9, min(block + 40, P_MAX * PAGE_S)],
                       jnp.int32)
    q, k_pool, v_pool, table = _paged_case(32, 8, lens.tolist(), jnp.bfloat16)
    want = paged_decode_attention(q, k_pool, v_pool, table, lens, layer=0)
    got = paged_decode_attention_tpu(q, k_pool, v_pool, table, lens, layer=0,
                                     interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("page_s,kv_heads,head_dim,dtype,takes", [
    (16, 32, 128, jnp.bfloat16, True),    # deepseek7b-sessions
    (16, 8, 128, jnp.bfloat16, True),     # Mistral through the paged layout
    (8, 2, 256, jnp.bfloat16, True),      # a page of one packed tile
    (16, 2, 16, jnp.float32, False),      # toy head size: lanes not whole
    (4, 2, 128, jnp.bfloat16, False),     # a page smaller than a tile
])
def test_paged_block_pages(page_s, kv_heads, head_dim, dtype, takes):
    """Which pools the kernel takes, and that a block of what it takes is
    whole pages, whole lane tiles, and small beside the scoped VMEM."""
    from gofr_tpu.ops.paged_attention import block_pages

    itemsize = jnp.dtype(dtype).itemsize
    pages = block_pages(page_s, kv_heads, head_dim, itemsize)
    assert (pages is not None) == takes
    if takes:
        block_rows = pages * page_s * kv_heads
        assert block_rows % 128 == 0
        assert 4 * block_rows * head_dim * itemsize <= 4 * 2**20


def test_paged_dispatcher_records_gather_off_tpu():
    from gofr_tpu import ops

    q, k_pool, v_pool, table = _paged_case(32, 8, [40, 3])
    ops.paged_decode_attention(q, k_pool, v_pool, table,
                               jnp.asarray([40, 3], jnp.int32), layer=0)
    key = ops.branch_key("paged_decode_attention", q, k_pool)
    assert ops.kernel_branches()[key] == "xla"


def test_paged_decode_step_matches_dense_and_hands_over_live_lengths(
        monkeypatch):
    """``paged_decode_step`` through the dispatcher's gather serves what
    the dense layout serves from the same rows (shuffled pages, rows at
    different lengths), and the length it hands the dispatcher stops at
    the table's capacity for a row whose ``len`` is pinned there and is 1
    for a freed slot (its row of the table all scratch page 0, its ``len``
    still counting): the kernel's cost follows what it is handed."""
    from gofr_tpu import ops
    from gofr_tpu.models import llama

    cfg = llama.tiny_llama(use_flash=False, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    page_s, p_max, b = 8, 4, 4
    capacity = page_s * p_max
    lens = np.array([capacity, 13, 0, 20], np.int32)
    freed = 3
    dense = llama.init_cache(cfg, b, capacity)
    keys = jax.random.split(jax.random.PRNGKey(1), 2)
    dense["k"], dense["v"] = (
        jax.random.normal(kk, dense["k"].shape, dense["k"].dtype)
        for kk in keys)
    dense["len"] = jnp.asarray(lens)
    table = (np.random.RandomState(3).permutation(b * p_max) + 1
             ).reshape(b, p_max).astype(np.int32)
    paged = llama.init_paged_cache(cfg, b, b * p_max + 1, page_s)
    for name in ("k", "v"):   # [L, B, S, KV, D] -> pages [L, N, ps, KV, D]
        rows = np.asarray(dense[name]).reshape(
            cfg.n_layers, b * p_max, page_s, cfg.n_kv_heads, cfg.head_dim)
        pool = np.array(paged[name])
        pool[:, table.reshape(-1)] = rows
        paged[name] = jnp.asarray(pool)
    paged["len"] = jnp.asarray(lens)
    table[freed] = 0

    seen = []
    inner = ops.paged_decode_attention

    def spy(q, k_pool, v_pool, tbl, kv_len, **kw):
        jax.debug.callback(lambda v: seen.append(np.asarray(v)), kv_len)
        return inner(q, k_pool, v_pool, tbl, kv_len, **kw)

    monkeypatch.setattr(ops, "paged_decode_attention", spy)
    tokens = np.array([5, 9, 2, 7], np.int32)
    got, new = jax.jit(lambda p, t, c, tb: llama.paged_decode_step(
        p, t, c, tb, cfg))(params, tokens, paged, jnp.asarray(table))
    jax.effects_barrier()
    assert len(seen) == cfg.n_layers
    for handed in seen:
        np.testing.assert_array_equal(handed, [capacity, 14, 1, 1])
    np.testing.assert_array_equal(np.asarray(new["len"]),
                                  [capacity, 14, 1, 21])
    want, _ = llama.decode_step(params, tokens, dense, cfg)
    # (the row at capacity drops its token's write in both layouts and
    # attends the same full table; the freed slot's output is no one's)
    np.testing.assert_allclose(np.asarray(got[:freed]),
                               np.asarray(want[:freed]),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_array_equal(np.argmax(got[:freed], -1),
                                  np.argmax(want[:freed], -1))
