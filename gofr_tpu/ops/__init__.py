"""Core tensor ops for the model zoo.

The reference has no compute ops at all (it is a Go microservice framework,
SURVEY §2.10); these are the TPU-native primitives its "datasource driver"
slot maps onto for the ``ml`` runtime. Two tiers:

- pure-jnp reference implementations (this file): always correct, run on any
  backend, and are what XLA fuses on CPU test meshes;
- Pallas TPU kernels (``flash_attention.py``): the hot-path attention used
  on real chips, selected by ``use_flash`` / backend detection.

Everything is shaped [batch, seq, heads, head_dim] ("BSHD") so sequence and
head axes line up with the mesh's ``sp``/``tp`` axes without transposes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# (loaded before the functions of the same names are defined: a submodule
# loaded later would take the package's name ``grouped_matmul`` or
# ``flash_attention`` from it, and a model module imported after that would
# bind the module)
from . import flash_attention as _flash
from . import gated_delta as _delta
from . import grouped_matmul as _grouped
from . import latent_attention as _latent
from . import selective_state as _state

__all__ = [
    "rms_norm",
    "layer_norm",
    "rope_table",
    "scale_rope_freqs",
    "yarn_mscale",
    "apply_rope",
    "repeat_kv",
    "attention",
    "decode_attention",
    "gqa_decode_attention",
    "cached_decode_attention",
    "paged_decode_attention",
    "latent_decode_attention",
    "grouped_matmul",
    "ssm_update",
    "gated_delta_update",
    "quantize_kv",
    "dequantize_kv",
    "quantize_kv4",
    "dequantize_kv4",
    "quantize_weight",
    "swiglu",
    "flash_attention",
    "branch_key",
    "record_branch",
    "kernel_branches",
]

# Which implementation each attention dispatcher chose, by op and shape:
# "<op>[<shape key>]" -> "pallas" | "xla". The choice is made silently at
# trace time from platform and shape; this table is its record, written
# once per distinct shape (``runtime_fingerprint`` and ``chip_smoke.py``
# read it).
_BRANCHES: dict[str, str] = {}


def branch_key(op: str, *arrays) -> str:
    """The table's key for ``op`` on operands of these shapes (arrays or
    ``ShapeDtypeStruct``s; the first operand's dtype stands for all)."""
    shapes = ",".join("x".join(map(str, a.shape)) for a in arrays)
    return f"{op}[{shapes},{arrays[0].dtype}]"


def record_branch(op: str, pallas: bool, *arrays) -> None:
    """Note at trace time which branch ``op`` took for these operands."""
    _BRANCHES[branch_key(op, *arrays)] = "pallas" if pallas else "xla"


def kernel_branches() -> dict[str, str]:
    """A copy of the dispatch record, sorted by key."""
    return dict(sorted(_BRANCHES.items()))


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    """RMSNorm in float32 accumulation (bf16 inputs lose too much in the
    mean-of-squares), cast back to the input dtype for the next matmul."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               eps: float = 1e-12) -> jnp.ndarray:
    """LayerNorm with f32 statistics (BERT-family encoders)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention scale for a context stretched ``factor`` times:
    ``0.1 mscale ln(factor) + 1``, and 1 where nothing is stretched.
    DeepSeek-V3 multiplies its softmax scale by the square of it at
    ``mscale_all_dim``, and its cos and sin by the ratio of the one at
    ``mscale`` to the one at ``mscale_all_dim``."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _yarn_freqs(freqs: jnp.ndarray, scaling: dict, theta: float
                ) -> jnp.ndarray:
    """YaRN (arXiv:2309.00071) as transformers' ``_compute_yarn_parameters``
    computes it: the pairs that turn more than ``beta_fast`` times over the
    original context keep their frequency, those that turn fewer than
    ``beta_slow`` times are slowed ``factor``-fold, and a linear ramp over
    the pairs between blends the two."""
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    fast = float(scaling.get("beta_fast") or 32)
    slow = float(scaling.get("beta_slow") or 1)
    dim = 2 * freqs.shape[-1]

    def pair(turns):  # the pair whose wavelength fits ``turns`` times
        return (dim * math.log(orig / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(fast)), 0)
    high = min(math.ceil(pair(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def scale_rope_freqs(freqs: jnp.ndarray, scaling: dict,
                     theta: float | None = None) -> jnp.ndarray:
    """Apply a HF ``rope_scaling`` spec to the base rotary frequencies.

    Supports ``llama3`` (Llama-3.1/3.2's NTK-by-parts: low-frequency bands
    are slowed by ``factor``, high-frequency bands kept, the middle smoothly
    interpolated — reference behavior: transformers'
    ``_compute_llama3_parameters``), ``linear`` (all bands divided by
    ``factor``) and ``yarn`` (``_yarn_freqs``; it needs the rotary base
    ``theta``). Anything else raises at trace/load time rather than
    silently mis-rotating (ADVICE r4 #2: Llama-3.1 checkpoints specify
    llama3 scaling; ignoring it degrades every generation with no error).
    """
    rtype = str(scaling.get("rope_type") or scaling.get("type") or "").lower()
    if rtype == "linear":
        return freqs / float(scaling["factor"])
    if rtype == "yarn":
        if theta is None:
            raise ValueError("yarn rope_scaling needs the rotary base theta")
        return _yarn_freqs(freqs, scaling, float(theta))
    if rtype != "llama3":
        raise ValueError(
            f"unsupported rope_scaling type {rtype!r}; "
            "supported: 'llama3', 'linear', 'yarn'")
    factor = float(scaling.get("factor", 8.0))
    low_ff = float(scaling.get("low_freq_factor", 1.0))
    high_ff = float(scaling.get("high_freq_factor", 4.0))
    orig = float(scaling.get("original_max_position_embeddings", 8192))
    wavelen = 2.0 * jnp.pi / freqs
    # smooth in [0, 1]: 1 at the high-frequency boundary, 0 at the low one
    smooth = (orig / wavelen - low_ff) / (high_ff - low_ff)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    scaled = (1.0 - smooth) * freqs / factor + smooth * freqs
    return jnp.where(wavelen < orig / high_ff, freqs,
                     jnp.where(wavelen > orig / low_ff, freqs / factor,
                               scaled))


def rope_table(positions: jnp.ndarray, head_dim: int, theta: float = 500_000.0,
               scaling: dict | None = None):
    """cos/sin tables for rotary embeddings at the given positions.

    positions: int array [...]; returns (cos, sin) of shape [..., head_dim//2]
    in float32 — rotation is numerically sensitive, done in f32 then cast.
    ``scaling`` is an optional HF ``rope_scaling`` dict (see
    ``scale_rope_freqs``).
    """
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    if scaling is not None:
        freqs = scale_rope_freqs(freqs, scaling, theta)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """Rotate pairs (x[..., :half], x[..., half:]) — the "rotate_half"
    convention. x: [..., seq, heads, head_dim]; cos/sin: [..., seq, half]."""
    dtype = x.dtype
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    c = cos[..., None, :]  # broadcast over heads
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(dtype)


def repeat_kv(kv: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """GQA: expand [B, S, n_kv, D] -> [B, S, n_kv*n_rep, D]."""
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_offset: int | jnp.ndarray = 0,
    kv_len: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Reference softmax attention, BSHD layout, f32 logits.

    q: [B, Tq, H, D]; k, v: [B, Tk, H, D] (call repeat_kv first for GQA).
    ``q_offset`` is the absolute position of q[0] (cache decoding) — a
    scalar, or a [B] vector when rows sit at different positions
    (continuous-batching speculative windows);
    ``kv_len`` masks out cache slots beyond the valid length, per batch row.
    """
    scale = q.shape[-1] ** -0.5
    # inputs stay in their native dtype (bf16 on the serving path) with f32
    # MXU accumulation — casting k/v to f32 first would double the HBM
    # traffic of every KV-cache sweep
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits *= scale
    tq, tk = q.shape[1], k.shape[1]
    mask = None
    if causal:
        kpos = jnp.arange(tk)
        if getattr(q_offset, "ndim", 0) == 1:  # per-row offsets [B]
            qpos = q_offset[:, None] + jnp.arange(tq)[None, :]  # [B, Tq]
            mask = (kpos[None, None, :] <= qpos[:, :, None])[:, None]  # [B,1,Tq,Tk]
        else:
            qpos = jnp.arange(tq) + q_offset
            mask = (kpos[None, :] <= qpos[:, None])[None, None]  # [1,1,Tq,Tk]
    if kv_len is not None:
        valid = jnp.arange(tk)[None, :] < kv_len[:, None]  # [B, Tk]
        valid = valid[:, None, None, :]
        mask = valid if mask is None else jnp.logical_and(mask, valid)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def decode_attention(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray, kv_len: jnp.ndarray
) -> jnp.ndarray:
    """Single-token decode attention over a padded KV cache.

    q: [B, 1, H, D]; caches: [B, S_max, H, D]; kv_len: [B] valid lengths
    (the new token's slot already written). Bandwidth-bound: a plain einsum
    lets XLA fuse the mask+softmax into the cache sweep.
    """
    return attention(q, k_cache, v_cache, causal=False, kv_len=kv_len)


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-vector int8 quantization over the last (head_dim) axis:
    returns (int8 values, bf16 scales with the last axis dropped). Halves
    KV-cache HBM traffic — the decode roofline at large slot counts — for
    <0.5% attention-output error (the scale is per token per KV head, so
    outliers only compress their own vector).
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray,
                  dtype=jnp.bfloat16) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def quantize_kv4(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray,
                                          jnp.ndarray]:
    """Asymmetric per-vector int4 quantization over the last (head_dim)
    axis, two codes packed per byte: returns (packed uint8 with the last
    axis HALVED, bf16 scales, bf16 zero points — both with the last axis
    dropped). Asymmetric (KIVI-style min/max affine, codes 0..15) because
    int4's 16 levels are too few to waste half the range on a sign bit;
    the zero point costs one extra bf16 per vector, the packed values
    halve the dominant HBM term again over int8 — twice the KV pages per
    HBM byte, twice the effective host tier."""
    xf = x.astype(jnp.float32)
    lo = jnp.min(xf, axis=-1)
    hi = jnp.max(xf, axis=-1)
    scale = jnp.maximum(hi - lo, 1e-6) / 15.0
    codes = jnp.clip(jnp.round((xf - lo[..., None]) / scale[..., None]),
                     0, 15).astype(jnp.uint8)
    packed = codes[..., ::2] | (codes[..., 1::2] << 4)
    return packed, scale.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def dequantize_kv4(packed: jnp.ndarray, scale: jnp.ndarray,
                   zero: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Inverse of ``quantize_kv4``: unpack the nibbles (last axis doubles
    back) and apply the affine ``code * scale + zero``."""
    lo = packed & jnp.uint8(0xF)
    hi = packed >> 4
    codes = jnp.stack([lo, hi], axis=-1).reshape(
        *packed.shape[:-1], packed.shape[-1] * 2)
    return (codes.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]
            + zero.astype(jnp.float32)[..., None]).astype(dtype)


def gqa_decode_attention(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray, kv_len: jnp.ndarray
) -> jnp.ndarray:
    """Grouped-query decode attention straight off the un-expanded cache.

    q: [B, Tq, H, D]; caches: [B, S_max, KV, D]; kv_len: [B]. The query
    heads are folded to [KV, n_rep] and contracted against the grouped
    cache — the [B, S_max, H, D] ``repeat_kv`` expansion (r1 VERDICT: 2× KV
    HBM traffic plus a large per-layer temp, the decode-step bottleneck)
    never materializes. Exact same math as
    ``decode_attention(q, repeat_kv(k), repeat_kv(v))``.
    """
    b, tq, h, d = q.shape
    kv = k_cache.shape[2]
    if h == kv:
        return attention(q, k_cache, v_cache, causal=False, kv_len=kv_len)
    n_rep = h // kv
    qg = q.reshape(b, tq, kv, n_rep, d)
    logits = jnp.einsum("bqkrd,bskd->bkrqs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    logits *= d ** -0.5
    valid = jnp.arange(k_cache.shape[1])[None, :] < kv_len[:, None]  # [B, S]
    logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrqs,bskd->bqkrd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, tq, h, d).astype(q.dtype)


def cached_decode_attention(q, k_cache, v_cache, kv_len, *, layer=None,
                            use_kernel: bool = True,
                            k_scale=None, v_scale=None,
                            flat_kv_heads: int | None = None):
    """Decode-attention dispatcher: a Pallas length-skipping kernel on TPU
    when shapes allow, the XLA grouped einsum everywhere else.

    Caches may be per-layer [B, S, KV, D] or the FULL stacked
    [L, B, S, KV, D] with ``layer`` a traced index — the kernel reads the
    layer's slab straight from HBM, and the XLA path relies on the
    dynamic-index fusing into the einsum. A full-precision cache whose
    widths have a tiling (``decode_attention.row_tiling``: KV heads and
    chunks of whole sublane tiles, a head size of whole lanes) is read as
    the paged layout reads pages: every head in one product in the cache's
    dtype, the row's tail fetched to its live length. Any other cache takes
    the kernel that works a KV head at a time where S is a multiple of its
    block of 256: a Llama-family ``[S, KV, D]`` cache with fewer KV heads
    than a tile holds, and int8 caches, for which pass
    ``k_scale``/``v_scale`` ([L, B, KV, S] or [B, KV, S]; seq minor for
    DMA alignment; dequantized in VMEM after the halved HBM read).

    ``flat_kv_heads``: a full-precision stacked cache stored as
    ``[L, B, S * KV, D]`` (row ``t * KV + g`` is token ``t``, KV head
    ``g``), the form in which fewer KV heads than a sublane tile holds
    cost their own bytes on the chip and need no relayout to be read as
    one matrix (``[S, 1, 128]`` bfloat16 pads its last two axes to a whole
    tile, 16 times the bytes; ``[S, 2, 256]`` packs a token's two heads
    into one word). It is read as one matrix a block whatever the number
    of KV heads: Jamba and Qwen3-Next store their caches so.
    """
    if flat_kv_heads is not None:
        return _flat_decode_attention(q, k_cache, v_cache, kv_len, layer,
                                      use_kernel, flat_kv_heads)
    quantized = k_scale is not None
    # quantized caches are FLAT [L?, B, S, KV*D] (int8 tiling, see
    # models/llama.init_cache); fp caches are [L?, B, S, KV, D]
    stacked = k_cache.ndim == (4 if quantized else 5)
    s_max = k_cache.shape[2] if stacked else k_cache.shape[1]
    kernel = use_kernel and _on_tpu() and q.shape[1] == 1
    if kernel:
        # (imported here, on a TPU only: the submodule takes the package's
        # name ``decode_attention`` from the function above)
        from .decode_attention import gqa_decode_attention_tpu, row_tiling

        kernel = s_max % 256 == 0 or (
            not quantized and row_tiling(
                s_max, *k_cache.shape[-2:], k_cache.dtype.itemsize) is not None)
    record_branch("decode_attention", kernel, q, k_cache)
    if kernel:
        return gqa_decode_attention_tpu(q, k_cache, v_cache, kv_len,
                                        layer=layer, k_scale=k_scale,
                                        v_scale=v_scale)
    if stacked:
        idx = lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0,
                                                     keepdims=False)
        k_cache, v_cache = idx(k_cache), idx(v_cache)
        if quantized:
            k_scale, v_scale = idx(k_scale), idx(v_scale)
    if quantized:
        # unflatten [B, S, KV*D] and broadcast the seq-minor [B, KV, S]
        # scales; XLA fuses the dequant into the attention einsum, so the
        # fp cache never materializes in HBM
        b_, s_, kv_ = k_cache.shape[0], k_cache.shape[1], k_scale.shape[1]
        unflat = lambda a: a.reshape(b_, s_, kv_, -1)
        k_cache = dequantize_kv(unflat(k_cache),
                                k_scale.transpose(0, 2, 1), q.dtype)
        v_cache = dequantize_kv(unflat(v_cache),
                                v_scale.transpose(0, 2, 1), q.dtype)
    return gqa_decode_attention(q, k_cache, v_cache, kv_len=kv_len)


def _flat_decode_attention(q, k_cache, v_cache, kv_len, layer, use_kernel,
                           kv: int):
    """``cached_decode_attention`` for a cache stored flat (see there)."""
    n_layers, b, rows, d = k_cache.shape
    kernel = use_kernel and _on_tpu() and q.shape[1] == 1
    if kernel:
        from .decode_attention import gqa_decode_attention_tpu, row_tiling

        kernel = row_tiling(rows // kv, kv, d, k_cache.dtype.itemsize,
                            flat=True) is not None
    record_branch("decode_attention", kernel, q, k_cache)
    if kernel:
        return gqa_decode_attention_tpu(q, k_cache, v_cache, kv_len,
                                        layer=layer, flat_kv_heads=kv)
    idx = lambda a: jax.lax.dynamic_index_in_dim(
        a, layer, 0, keepdims=False).reshape(b, rows // kv, kv, d)
    return gqa_decode_attention(q, idx(k_cache), idx(v_cache), kv_len=kv_len)


def paged_decode_attention(q, k_pool, v_pool, table, kv_len, *, layer):
    """Decode attention for the paged layout: one query token a row
    against the row's pages of the full-precision pool.

    q: [B, 1, H, D]; pools: the stacked ``[L, N, page_s, KV, D]`` planes
    with ``layer`` a traced index; table: [B, P_max] page ids in virtual
    order; kv_len: [B]. On a TPU, where the widths meet the kernel's
    tiling, the Pallas kernel reads the live pages where they lie
    (``ops/paged_attention.py``); everywhere else each row's whole virtual
    sequence is gathered and masked.
    """
    from .paged_attention import block_pages, paged_decode_attention_tpu

    page_s, kv, d = k_pool.shape[2:]
    kernel = (_on_tpu() and q.shape[1] == 1
              and jnp.issubdtype(k_pool.dtype, jnp.floating)
              and block_pages(page_s, kv, d, k_pool.dtype.itemsize) is not None)
    record_branch("paged_decode_attention", kernel, q, k_pool)
    if kernel:
        return paged_decode_attention_tpu(q, k_pool, v_pool, table, kv_len,
                                          layer=layer)
    b, n_rep = q.shape[0], q.shape[2] // kv
    k_l = jax.lax.dynamic_index_in_dim(k_pool, layer, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(v_pool, layer, 0, keepdims=False)
    # virtual sequence: gather this row's pages in table order
    k_virt = jnp.take(k_l, table, axis=0).reshape(b, -1, kv, d)
    v_virt = jnp.take(v_l, table, axis=0).reshape(b, -1, kv, d)
    return attention(q, repeat_kv(k_virt, n_rep), repeat_kv(v_virt, n_rep),
                     causal=False, kv_len=kv_len)


def latent_decode_attention(q, cache, kv_len, *, layer, scale: float,
                            value_width: int):
    """Decode attention over a latent cache (absorbed multi-head latent
    attention, ``ops/latent_attention.py``): ``q`` [B, H, W] against
    layer ``layer`` (a traced index) of the whole stack ``cache``
    [L, B, S, W], each row's first ``kv_len`` positions, the row's first
    ``value_width`` values read back. Returns [B, H, value_width].

    On a TPU, where the widths tile, the Pallas kernel reads each row's
    live positions once for all heads; everywhere else the plain
    computation on the layer's slice.
    """
    kernel = (_on_tpu() and _latent.block_positions(
        cache.shape[2], cache.shape[3], value_width,
        cache.dtype.itemsize) is not None)
    record_branch("latent_decode_attention", kernel, q, cache)
    run = (_latent.latent_decode_attention_tpu if kernel
           else _latent.latent_decode_attention_ref)
    return run(q, cache, kv_len, layer, scale=scale, value_width=value_width)


def grouped_matmul(xs, w, sizes, *, first=None):
    """The grouped product of a dropless expert layer: ``xs`` [M, K] with
    its rows sorted by group, group ``g`` (``sizes[g]`` rows, [G] int32)
    against ``w[first + g]`` of the stack ``w`` [E, K, N]; ``first`` is a
    traced index (a layer's offset into every layer's experts) or None
    for 0. Operands in their dtype, float32 accumulation, the result
    [M, N] in ``xs.dtype``; rows behind the last group hold nothing a
    caller may use.

    On a TPU, where the shapes have a tiling, the Pallas kernel streams
    each touched group's weights once (``ops/grouped_matmul.py``; its row
    tile follows from M); everywhere else ``jax.lax.ragged_dot``
    over the whole stack, which finds the other groups empty.
    """
    kernel = (_on_tpu() and xs.dtype == w.dtype
              and _grouped.row_tile(xs.shape[0]) is not None
              and _grouped.col_tile(*w.shape[1:], w.dtype.itemsize) is not None)
    record_branch("grouped_matmul", kernel, xs, w)
    if kernel:
        return _grouped.grouped_matmul_tpu(xs, w, sizes, first)
    if first is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w.shape[0],), jnp.int32), sizes, (first,))
    return jax.lax.ragged_dot(xs, w, sizes)


def ssm_update(state, layer, dt, x, B, C, A):
    """One decode step of a Mamba-1 layer's selective state:
    ``S' = exp(dt A) S + (dt x) B`` and ``y = sum_n S'[n] C[n]``, all
    float32, on layer ``layer`` (a traced index) of the whole stack
    ``state`` [L, rows, N, Di]; ``dt``, ``x`` [rows, Di]; ``B``, ``C``
    [rows, N]; ``A`` [N, Di]. Returns the stack with that layer alone
    changed, and ``y`` [rows, Di].

    On a TPU, where the widths tile, the Pallas kernel reads the layer's
    state once and writes it once, in place (``ops/selective_state.py``);
    everywhere else ``selective_scan_step`` on the layer's slice, put back
    with a dynamic update.
    """
    rows, n, di = state.shape[1:]
    kernel = _on_tpu() and _state.block_rows(rows, n, di) is not None
    record_branch("ssm_update", kernel, state, x)
    if kernel:
        return _state.ssm_update_tpu(state, layer, dt, x, B, C, A)
    S, y = _state.selective_scan_step(
        jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False),
        dt, x, B, C, A)
    return jax.lax.dynamic_update_index_in_dim(state, S, layer, 0), y


def gated_delta_update(state, layer, q, k, v, g, beta):
    """One decode step of a Gated DeltaNet layer's state: the gated delta
    rule (``gated_delta.gated_delta_step``) on layer ``layer`` (a traced
    index) of the whole stack ``state`` [L, rows, Hv, dk, dv] float32;
    ``q``, ``k`` [rows, Hv, dk]; ``v`` [rows, Hv, dv]; ``g``, ``beta``
    [rows, Hv]. Returns the stack with that layer alone changed, and ``o``
    [rows, Hv, dv] float32.

    On a TPU, where the widths tile, the Pallas kernel reads the layer's
    state once and writes it once, in place (``ops/gated_delta.py``);
    everywhere else ``gated_delta_step`` on the layer's slice, put back
    with a dynamic update.
    """
    kernel = _on_tpu() and _delta.tiles(*state.shape[2:])
    record_branch("gated_delta_update", kernel, state, q)
    if kernel:
        return _delta.gated_delta_update_tpu(state, layer, q, k, v, g, beta)
    S, o = _delta.gated_delta_step(
        jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False),
        q, k, v, g, beta)
    return jax.lax.dynamic_update_index_in_dim(state, S, layer, 0), o


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU MLP: silu(x @ w_gate) * (x @ w_up) @ w_down."""
    g = jax.nn.silu(x @ w_gate)
    return (g * (x @ w_up)) @ w_down


def quantize_weight(w: jnp.ndarray, eps: float = 1e-8
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-output-channel int8 weight quantization (w8a16).

    The scale reduces over the CONTRACTION axis (second-to-last), so for
    ``y = x @ W`` it commutes out of the dot: ``y = (x @ Wq) * s`` — HBM
    streams the int8 tensor while the matmul still runs in bf16 on the
    MXU (the widening convert fuses into the operand read). Decode at
    large slot counts is weight-bandwidth-bound, so this is ~2x less
    weight traffic per step.
    """
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 127.0
    s = jnp.maximum(s, eps)
    q = jnp.round(wf / s).astype(jnp.int8)
    return q, jnp.squeeze(s, -2)


@functools.cache
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_diff(q, k, v, kv_len, causal, q_offset, block_q, block_k):
    """Differentiable wrapper over the Pallas kernel: the kernel has no JVP
    rule (pallas_call + program_id cannot be traced by autodiff), so the
    backward pass recomputes attention with the XLA reference path and
    takes ITS vjp — flash forward speed, standard-attention backward. The
    logits matrix does materialize during backward; training long
    sequences pairs this with LlamaConfig(remat=True)."""
    return _flash.flash_attention_tpu(q, k, v, kv_len, causal=causal,
                                      q_offset=q_offset, block_q=block_q,
                                      block_k=block_k)


def _flash_diff_fwd(q, k, v, kv_len, causal, q_offset, block_q, block_k):
    out = _flash_diff(q, k, v, kv_len, causal, q_offset, block_q, block_k)
    return out, (q, k, v, kv_len)


def _flash_diff_bwd(causal, q_offset, block_q, block_k, res, g):
    q, k, v, kv_len = res
    _, vjp = jax.vjp(
        lambda q, k, v: attention(q, k, v, causal=causal, q_offset=q_offset,
                                  kv_len=kv_len), q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


def flash_attention(q, k, v, *, causal: bool = True, q_offset=0, kv_len=None,
                    block_q: int = 256, block_k: int = 256):
    """Fused attention: Pallas kernel on TPU, reference path elsewhere.

    The kernel (ops/flash_attention.py) streams K/V blocks through VMEM with
    an online softmax so the [Tq, Tk] logits matrix never materializes in
    HBM — the standard memory-bound win for long sequences. Differentiable
    (training uses it too): see _flash_diff for the backward story.
    """
    tq, tk = q.shape[1], k.shape[1]
    bq, bk = min(block_q, tq), min(block_k, tk)
    kernel = _on_tpu() and tq >= 128 and tq % bq == 0 and tk % bk == 0
    record_branch("flash_attention", kernel, q, k)
    if kernel:
        return _flash_diff(q, k, v, kv_len, causal, q_offset, block_q,
                           block_k)
    return attention(q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len)
