"""Qwen3-Next hybrid decoder: Gated DeltaNet beside gated attention, with
sparse experts in every layer.

Layer ``i`` of a period of ``full_attention_interval`` (4) is gated softmax
attention where ``(i + 1) % 4 == 0`` and Gated DeltaNet (arXiv:2412.06464)
otherwise; every layer's feed-forward is a routed expert layer with a
gated shared expert (``models/moe.py``). Norms are zero-centred
(``x * rsqrt(mean(x^2) + eps) * (1 + w)``), but DeltaNet's output norm,
which takes a plain weight.

Built for the dense slot layout of ``ml/generate.py``: ``init_cache``,
``prefill_into`` and ``decode_step`` have ``models/llama.py``'s
signatures, and the cache holds two kinds of per-slot state side by side:

- ``k``/``v`` ``[periods, B, S_max * KV, head_dim]`` and ``len`` ``[B]``
  for the attention layers, stored flat as Jamba's are
  (``models/slot_state.py``): row ``t * KV + g`` is token ``t``, KV head
  ``g``;
- ``state`` ``[3 * periods, B, Hv, dk, dv]`` float32 and ``conv``
  ``[3 * periods, B, kernel - 1, channels]`` for the DeltaNet layers: no
  length axis. ``prefill_into`` computes both from zero, so a slot's
  reuse is its reset, and padded positions leave them as the last real
  token did (``beta = 0, g = 0``, and the window is cut at the length);
- ``moe_counts``: what routing did, summed on the device
  (``MOE_COUNTERS``), read only when ``Generator.pool_stats()`` is asked.

The stack is scanned a period at a time (the period's four layers are
unrolled in the body), so a decode step has one loop. The routed experts'
weights are not scanned: they lie beside the periods as one stack
``[layers * held, ...]`` that every layer's grouped product takes whole
(``moe.dropless_experts``, ``layer=``). Prefill runs
DeltaNet in its chunk-parallel form (a triangular solve inside a chunk of
64, a scan of the state across chunks); decode is one recurrent update.

Weights are stored ``x @ w``. Where the published checkpoint interleaves
(``in_proj_qkvz`` and ``in_proj_ba`` by key-head group, ``q_proj`` as
``[head, (q | gate)]``), this tree keeps ``q_proj``'s layout and stores
the DeltaNet projections as flat ``[q | k | v | z]`` and ``[b | a]``
blocks: an importer would permute columns, the mathematics is the same.
The checkpoint's multi-token-prediction head is not part of plain serving
and is not here.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..ops import (
    apply_rope,
    attention,
    cached_decode_attention,
    flash_attention,
    gated_delta_update,
    repeat_kv,
    rms_norm,
    rope_table,
)
from . import moe
from .slot_state import UNSUPPORTED  # what ``Generator`` refuses

__all__ = ["Qwen3NextConfig", "init_params", "init_cache", "prefill_into",
           "decode_step", "gated_delta_chunked", "MOE_COUNTERS",
           "UNSUPPORTED"]

CHUNK = 64  # DeltaNet's prefill chunk, as published

# rows of the cache's ``moe_counts``
MOE_COUNTERS = ("expert_pairs_routed", "expert_pairs_held", "experts_touched")

class Qwen3NextConfig:
    """Sizes under their published (HF ``config.json``) names. ``held`` is
    ``(first, count)``: the experts whose weights this chip holds; the
    router is ``num_experts`` wide whatever is held."""

    def __init__(
        self,
        vocab_size: int = 151_936,
        hidden_size: int = 2048,
        num_hidden_layers: int = 48,
        num_attention_heads: int = 16,
        num_key_value_heads: int = 2,
        head_dim: int = 256,
        linear_num_key_heads: int = 16,
        linear_num_value_heads: int = 32,
        linear_key_head_dim: int = 128,
        linear_value_head_dim: int = 128,
        linear_conv_kernel_dim: int = 4,
        moe_intermediate_size: int = 512,
        shared_expert_intermediate_size: int = 512,
        num_experts: int = 512,
        num_experts_per_tok: int = 10,
        full_attention_interval: int = 4,
        partial_rotary_factor: float = 0.25,
        rope_theta: float = 10_000_000.0,
        rms_norm_eps: float = 1e-6,
        max_position_embeddings: int = 262_144,
        held: tuple[int, int] | None = None,
        dtype: Any = jnp.bfloat16,
        use_flash: bool = True,
        kv_bits: int = 16,
    ) -> None:
        if num_hidden_layers % full_attention_interval:
            raise ValueError(
                f"{num_hidden_layers} layers are not whole periods of "
                f"{full_attention_interval}")
        if linear_num_value_heads % linear_num_key_heads:
            raise ValueError("value heads must be a multiple of key heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_expert_intermediate_size = shared_expert_intermediate_size
        self.num_experts = num_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.full_attention_interval = full_attention_interval
        self.partial_rotary_factor = partial_rotary_factor
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.held = (0, num_experts) if held is None else tuple(held)
        first, count = self.held
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"held {self.held} lies outside the router's "
                             f"{num_experts} experts")
        self.dtype = dtype
        self.use_flash = use_flash
        self.kv_bits = int(kv_bits)

    @property
    def n_periods(self) -> int:
        return self.num_hidden_layers // self.full_attention_interval

    @property
    def n_linear(self) -> int:
        """DeltaNet layers a period."""
        return self.full_attention_interval - 1

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim


def init_params(cfg: Qwen3NextConfig, key) -> dict:
    """Seeded random weights in the serving tree (tests and examples; the
    benchmark draws its own). ``A_log`` and ``dt_bias`` as the published
    initialisation draws them; zero-centred norm weights near 0, the
    DeltaNet output norm's near 1."""
    D, V = cfg.hidden_size, cfg.vocab_size
    P, NL, I = cfg.n_periods, cfg.n_linear, cfg.full_attention_interval
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    F, Fs = cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size
    E, Eh = cfg.num_experts, cfg.held[1]
    keys = iter(jax.random.split(key, 128))

    def dense(*shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    def near(centre, *shape):
        return centre + 0.1 * jax.random.normal(next(keys), shape,
                                                jnp.float32)

    def lin():
        dt = jnp.exp(jax.random.uniform(next(keys), (P, Hv), jnp.float32,
                                        jnp.log(1e-3), jnp.log(0.1)))
        return {
            "norm": near(0.0, P, D),
            "w_qkvz": dense(P, D, 2 * cfg.key_dim + 2 * cfg.value_dim,
                            fan_in=D),
            "w_ba": dense(P, D, 2 * Hv, fan_in=D),
            "conv": dense(P, cfg.linear_conv_kernel_dim, cfg.conv_dim,
                          fan_in=cfg.linear_conv_kernel_dim),
            "A_log": jnp.log(jax.random.uniform(
                next(keys), (P, Hv), jnp.float32, 1e-3, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "o_norm": near(1.0, P, dv),
            "w_out": dense(P, cfg.value_dim, D, fan_in=cfg.value_dim),
        }

    def expert_layer():
        return {
            "norm": near(0.0, P, D),
            "router": dense(P, D, E, fan_in=D),
            "s_gate_up": dense(P, D, 2 * Fs, fan_in=D),
            "s_down": dense(P, Fs, D, fan_in=Fs),
            "s_mix": dense(P, D, fan_in=D),
        }

    return {
        "embed": dense(V, D, fan_in=D),
        "final_norm": near(0.0, D),
        "lm_head": dense(D, V, fan_in=D),
        # layer l's held experts are rows l * held ... (l + 1) * held - 1
        "experts": {
            "w_gate_up": dense(P * I * Eh, D, 2 * F, fan_in=D),
            "w_down": dense(P * I * Eh, F, D, fan_in=F),
        },
        # a period's layers side by side, each stacked over the periods, so
        # the scan hands every layer its own slice (a [3, ...] slab of a
        # period would be cut out, copied, before it is cut again)
        "periods": {
            "lin": [lin() for _ in range(NL)],
            "attn": {
                "norm": near(0.0, P, D),
                "wq": dense(P, D, H * 2 * hd, fan_in=D),
                "wk": dense(P, D, KV * hd, fan_in=D),
                "wv": dense(P, D, KV * hd, fan_in=D),
                "q_norm": near(0.0, P, hd),
                "k_norm": near(0.0, P, hd),
                "wo": dense(P, H * hd, D, fan_in=H * hd),
            },
            "moe": [expert_layer() for _ in range(I)],
        },
    }


def init_cache(cfg: Qwen3NextConfig, batch: int,
               max_seq: int | None = None) -> dict:
    S = max_seq or cfg.max_position_embeddings
    P, NL = cfg.n_periods, cfg.n_periods * cfg.n_linear
    kv = (P, batch, S * cfg.num_key_value_heads, cfg.head_dim)  # flat
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "len": jnp.zeros((batch,), jnp.int32),
        "state": jnp.zeros((NL, batch, cfg.linear_num_value_heads,
                            cfg.linear_key_head_dim,
                            cfg.linear_value_head_dim), jnp.float32),
        "conv": jnp.zeros((NL, batch, cfg.linear_conv_kernel_dim - 1,
                           cfg.conv_dim), cfg.dtype),
        # (low, high) 32-bit words of each of MOE_COUNTERS
        "moe_counts": jnp.zeros((len(MOE_COUNTERS), 2), jnp.uint32),
    }


def _norm(x, w, eps):
    """Zero-centred RMSNorm: the weight is stored as its distance from 1."""
    return rms_norm(x, 1.0 + w.astype(jnp.float32), eps)


def _count(counts, adds):
    """``counts`` [n, 2] uint32 (low, high words) plus ``adds`` [n]: 64-bit
    sums out of 32-bit adds (a busy server passes 2**32 pairs in hours)."""
    low = counts[:, 0] + adds.astype(jnp.uint32)
    high = counts[:, 1] + (low < counts[:, 0]).astype(jnp.uint32)
    return jnp.stack([low, high], axis=-1)


# ------------------------------------------------------------ Gated DeltaNet
def gated_delta_chunked(q, k, v, g, beta, chunk: int = CHUNK):
    """The same rule over a whole sequence from a zero state, in its
    chunk-parallel form. ``q``, ``k`` [T, H, dk]; ``v`` [T, H, dv]; ``g``,
    ``beta`` [T, H], all float32, ``T`` a multiple of ``chunk``. Returns
    ``o`` [T, H, dv] and the last state [H, dk, dv]. Inside a chunk the
    tokens' corrections solve one unit lower-triangular system; across
    chunks the state is scanned."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    N = T // chunk
    hi = jax.lax.Precision.HIGHEST

    def chunks(a):  # [T, H, ...] -> [N, H, chunk, ...]
        return jnp.moveaxis(a.reshape(N, chunk, *a.shape[1:]), 1, 2)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                       # decay since chunk start
    kb, vb = k * beta[..., None], v * beta[..., None]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp only where it is kept: above the diagonal gc_i - gc_j is positive
    # and may overflow
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    M = jnp.einsum("nhid,nhjd->nhij", kb, k, precision=hi) * decay
    M = jnp.where(jnp.tril(lower, -1), M, 0.0)
    eye = jnp.eye(chunk, dtype=jnp.float32)
    Tm = jax.scipy.linalg.solve_triangular(
        eye + M, jnp.broadcast_to(eye, M.shape), lower=True,
        unit_diagonal=True)
    u = jnp.einsum("nhij,nhjd->nhid", Tm, vb, precision=hi)
    w = jnp.einsum("nhij,nhjd->nhid", Tm, kb * jnp.exp(gc)[..., None],
                   precision=hi)
    local = jnp.einsum("nhid,nhjd->nhij", q, k, precision=hi) * decay
    q_in = q * jnp.exp(gc)[..., None]                 # what the old state gives
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]  # what reaches the end
    g_end = jnp.exp(gc[..., -1])

    def step(S, xs):
        u_i, w_i, local_i, q_i, k_i, g_i = xs
        v_new = u_i - jnp.einsum("hid,hde->hie", w_i, S, precision=hi)
        o = (jnp.einsum("hid,hde->hie", q_i, S, precision=hi)
             + jnp.einsum("hij,hje->hie", local_i, v_new, precision=hi))
        S = (S * g_i[:, None, None]
             + jnp.einsum("hid,hie->hde", k_i, v_new, precision=hi))
        return S, o

    S, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                        (u, w, local, q_in, k_out, g_end))
    return jnp.moveaxis(o, 1, 2).reshape(T, H, dv), S


def _delta_inputs(cfg, lp, h):
    """Projections of the normed stream ``h`` [..., D]: the convolution's
    input ``mixed`` [..., channels] (q | k | v), the output gate ``z``
    [..., Hv, dv], and float32 ``beta``, ``g`` [..., Hv]."""
    Hv = cfg.linear_num_value_heads
    qkvz = h @ lp["w_qkvz"]
    ba = (h @ lp["w_ba"]).astype(jnp.float32)
    mixed, z = qkvz[..., :cfg.conv_dim], qkvz[..., cfg.conv_dim:]
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + lp["dt_bias"].astype(jnp.float32))
    return mixed, z.reshape(*z.shape[:-1], Hv, -1), beta, g


def _delta_heads(cfg, conved):
    """``silu(conv)`` [..., channels] -> float32 ``q``, ``k`` [..., Hv, dk]
    (L2-normalised per head, each key head repeated for its value heads,
    ``q`` scaled) and ``v`` [..., Hv, dv]."""
    Hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    Hv = cfg.linear_num_value_heads
    x = jax.nn.silu(conved.astype(jnp.float32))
    lead = x.shape[:-1]
    q = x[..., :cfg.key_dim].reshape(*lead, Hk, dk)
    k = x[..., cfg.key_dim:2 * cfg.key_dim].reshape(*lead, Hk, dk)
    v = x[..., 2 * cfg.key_dim:].reshape(*lead, Hv, -1)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q), Hv // Hk, axis=-2) * dk ** -0.5
    k = jnp.repeat(unit(k), Hv // Hk, axis=-2)
    return q, k, v


def _delta_out(cfg, lp, o, z, dtype):
    """Per-head gated norm (plain weight) and the output projection."""
    o = rms_norm(o, lp["o_norm"], cfg.rms_norm_eps)
    o = (o * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    return o.reshape(*o.shape[:-2], cfg.value_dim) @ lp["w_out"]


def _delta_prefill(cfg, lp, x, n):
    """One DeltaNet mixer over a prompt ``x`` [T, D] of ``n`` real tokens.
    Returns the mixer's output [T, D], the state after token ``n - 1`` and
    the convolution window of the last ``kernel - 1`` real tokens."""
    T = x.shape[0]
    K = cfg.linear_conv_kernel_dim
    mixed, z, beta, g = _delta_inputs(cfg, lp, x)
    real = jnp.arange(T) < n
    beta = jnp.where(real[:, None], beta, 0.0)
    g = jnp.where(real[:, None], g, 0.0)
    # causal depthwise convolution: tap i meets the token K - 1 - i back
    padded = jnp.pad(mixed, ((K - 1, 0), (0, 0)))
    conved = sum(padded[i:i + T].astype(jnp.float32)
                 * lp["conv"][i].astype(jnp.float32) for i in range(K))
    window = jax.lax.dynamic_slice_in_dim(padded, n, K - 1, axis=0)
    q, k, v = _delta_heads(cfg, conved)
    pad = -T % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                            for a in (q, k, v, g, beta))
    o, S = gated_delta_chunked(q, k, v, g, beta)
    return _delta_out(cfg, lp, o[:T], z, x.dtype), S, window


def _delta_decode(cfg, lp, x, state, i, window):
    """One token a row: ``x`` [B, D], ``state`` the whole stack
    [DeltaNet layers, B, Hv, dk, dv] of which layer ``i`` moves, ``window``
    [B, kernel - 1, channels]."""
    mixed, z, beta, g = _delta_inputs(cfg, lp, x)
    taps = lp["conv"].astype(jnp.float32)
    conved = (jnp.einsum("bic,ic->bc", window.astype(jnp.float32), taps[:-1])
              + mixed.astype(jnp.float32) * taps[-1])
    window = jnp.concatenate([window[:, 1:], mixed[:, None]], axis=1)
    q, k, v = _delta_heads(cfg, conved)
    state, o = gated_delta_update(state, i, q, k, v, g, beta)
    return _delta_out(cfg, lp, o, z, x.dtype), state, window


# ----------------------------------------------------------- gated attention
def _attn_inputs(cfg, lp, h, cos, sin):
    """``h`` [b, s, D] -> q [b, s, H, hd], its gate [b, s, H * hd], k and v
    [b, s, KV, hd]: per-head norms, then rotary on the first
    ``rotary_dim`` dimensions."""
    b, s, _ = h.shape
    H, KV, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                 cfg.head_dim)
    qg = (h @ lp["wq"]).reshape(b, s, H, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:].reshape(b, s, H * hd)
    k = (h @ lp["wk"]).reshape(b, s, KV, hd)
    v = (h @ lp["wv"]).reshape(b, s, KV, hd)
    q = _norm(q, lp["q_norm"], cfg.rms_norm_eps)
    k = _norm(k, lp["k_norm"], cfg.rms_norm_eps)
    r = cfg.rotary_dim

    def rot(a):
        return jnp.concatenate(
            [apply_rope(a[..., :r], cos, sin), a[..., r:]], axis=-1)

    return rot(q), gate, rot(k), v


def _attn_out(lp, o, gate):
    return (o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)
            ) @ lp["wo"]


# ------------------------------------------------------------------ experts
def _experts(cfg, mp, stack, layer, x, valid, counts):
    """Layer ``layer``'s expert layer on ``x`` [N, D]: this chip's share of
    the routed experts (``stack``: every layer's, see the module's
    docstring) plus the whole shared expert. ``valid`` [N] marks real
    tokens (a prompt's padding is neither computed nor counted)."""
    h = _norm(x, mp["norm"], cfg.rms_norm_eps)
    with jax.named_scope("router"):
        w, idx = moe.route_top_k(h, mp["router"], cfg.num_experts_per_tok)
    with jax.named_scope("experts"):
        y, stats = moe.dropless_experts(
            h, w, idx, stack["w_gate_up"], stack["w_down"], cfg.held,
            valid=valid, layer=layer)
        y = y + moe.gated_shared_expert(h, mp["s_gate_up"], mp["s_down"],
                                        mp["s_mix"])
    return x + y, _count(counts, jnp.stack(stats))


# --------------------------------------------------------------- serving API
def prefill_into(params: dict, tokens: jnp.ndarray, seq_lens: jnp.ndarray,
                 cfg: Qwen3NextConfig, cache: dict, slot: jnp.ndarray,
                 mesh=None) -> tuple[jnp.ndarray, dict]:
    """Prefill ONE prompt [1, S_pad] into row ``slot`` of the shared
    cache: keys and values of its positions, and the recurrent state and
    convolution window as token ``seq_lens[0] - 1`` left them, computed
    from zero (the slot's last occupant leaves no trace). Returns the last
    real token's logits [1, V] and the cache."""
    del mesh
    T = tokens.shape[1]
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    rows_max = cache["k"].shape[2]
    if T * KV > rows_max:
        raise ValueError(f"prompt bucket {T} exceeds cache length "
                         f"{rows_max // KV}")
    n = seq_lens[0]
    valid = jnp.arange(T) < n
    x = params["embed"][tokens[0]].astype(cfg.dtype)
    cos, sin = rope_table(jnp.arange(T)[None, :], cfg.rotary_dim,
                          cfg.rope_theta)
    NL, I = cfg.n_linear, cfg.full_attention_interval
    stack = params["experts"]

    def body(carry, pp):
        x, counts, p = carry
        states, windows = [], []
        for j in range(NL):
            lp = pp["lin"][j]
            with jax.named_scope("linear_attention"):
                h = _norm(x, lp["norm"], cfg.rms_norm_eps)
                y, S, window = _delta_prefill(cfg, lp, h, n)
                x = x + y
            states.append(S)
            windows.append(window)
            x, counts = _experts(cfg, pp["moe"][j], stack, p * I + j,
                                 x, valid, counts)
        lp = pp["attn"]
        with jax.named_scope("attention"):
            h = _norm(x, lp["norm"], cfg.rms_norm_eps)[None]
            q, gate, k, v = _attn_inputs(cfg, lp, h, cos, sin)
            rep = cfg.num_attention_heads // cfg.num_key_value_heads
            attend = flash_attention if cfg.use_flash else attention
            o = attend(q, repeat_kv(k, rep), repeat_kv(v, rep), causal=True,
                       kv_len=seq_lens)
            x = x + _attn_out(lp, o.reshape(1, T, -1), gate)[0]
        x, counts = _experts(cfg, pp["moe"][NL], stack, p * I + NL,
                             x, valid, counts)
        return (x, counts, p + 1), (jnp.stack(states), jnp.stack(windows),
                                    k[0].reshape(T * KV, hd),
                                    v[0].reshape(T * KV, hd))

    (x, counts, _), (states, windows, ks, vs) = jax.lax.scan(
        body, (x, cache["moe_counts"], jnp.int32(0)), params["periods"])
    last = _norm(x, params["final_norm"], cfg.rms_norm_eps)[n - 1][None]
    with jax.named_scope("lm_head"):
        logits = (last @ params["lm_head"]).astype(jnp.float32)

    def put(name, rows):
        return jax.lax.dynamic_update_index_in_dim(
            cache[name], rows.astype(cache[name].dtype), slot, axis=1)

    grow = ((0, 0), (0, rows_max - T * KV), (0, 0))
    return logits, {
        "k": put("k", jnp.pad(ks, grow)),
        "v": put("v", jnp.pad(vs, grow)),
        "len": cache["len"].at[slot].set(n),
        "state": put("state", states.reshape(-1, *states.shape[2:])),
        "conv": put("conv", windows.reshape(-1, *windows.shape[2:])),
        "moe_counts": counts,
    }


def decode_step(params: dict, tokens: jnp.ndarray, cache: dict,
                cfg: Qwen3NextConfig, mesh=None
                ) -> tuple[jnp.ndarray, dict]:
    """One token per row: tokens [B] -> (logits [B, V], updated cache).
    Every row's recurrent state moves, a dead row's too (its next
    occupant's prefill overwrites it); the attention layers write each
    row at its own ``len`` as llama's do."""
    del mesh
    b = tokens.shape[0]
    KV = cfg.num_key_value_heads
    pos = cache["len"]
    S_max = cache["k"].shape[2] // KV
    kv_len = jnp.minimum(pos + 1, S_max)
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = rope_table(pos[:, None], cfg.rotary_dim, cfg.rope_theta)
    rows = jnp.arange(b)[:, None]
    kv_rows = pos[:, None] * KV + jnp.arange(KV)[None, :]   # [B, KV]
    valid = jnp.ones((b,), bool)
    NL, I = cfg.n_linear, cfg.full_attention_interval
    stack = params["experts"]

    def at(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)

    def put(a, i, new):
        return jax.lax.dynamic_update_index_in_dim(a, new.astype(a.dtype),
                                                   i, 0)

    # weights stream through scan xs; the whole state rides the carry with
    # the period's number, so every update aliases in place
    def body(carry, pp):
        x, st, p = carry
        for j in range(NL):
            li = p * NL + j
            lp = pp["lin"][j]
            with jax.named_scope("linear_attention"):
                h = _norm(x, lp["norm"], cfg.rms_norm_eps)
                y, state, window = _delta_decode(cfg, lp, h, st["state"], li,
                                                 at(st["conv"], li))
                x = x + y
            st = {**st, "state": state, "conv": put(st["conv"], li, window)}
            x, counts = _experts(cfg, pp["moe"][j], stack, p * I + j,
                                 x, valid, st["moe_counts"])
            st = {**st, "moe_counts": counts}
        lp = pp["attn"]
        with jax.named_scope("attention"):
            h = _norm(x, lp["norm"], cfg.rms_norm_eps)[:, None]
            q, gate, k, v = _attn_inputs(cfg, lp, h, cos, sin)
            st = {**st, "k": st["k"].at[p, rows, kv_rows].set(k[:, 0]),
                  "v": st["v"].at[p, rows, kv_rows].set(v[:, 0])}
            o = cached_decode_attention(q, st["k"], st["v"], kv_len, layer=p,
                                        use_kernel=cfg.use_flash,
                                        flat_kv_heads=KV)
            x = x + _attn_out(lp, o.reshape(b, 1, -1), gate)[:, 0]
        x, counts = _experts(cfg, pp["moe"][NL], stack, p * I + NL,
                             x, valid, st["moe_counts"])
        return (x, {**st, "moe_counts": counts}, p + 1), None

    st0 = {key: cache[key] for key in cache if key != "len"}
    (x, st, _), _ = jax.lax.scan(body, (x, st0, jnp.int32(0)),
                                 params["periods"])
    x = _norm(x, params["final_norm"], cfg.rms_norm_eps)
    with jax.named_scope("lm_head"):
        logits = (x @ params["lm_head"]).astype(jnp.float32)
    # a row at capacity keeps decoding garbage: its cache writes fall
    # outside and are dropped, its length stays at the end
    return logits, {**st, "len": kv_len}
