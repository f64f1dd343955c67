"""Jamba hybrid decoder: Mamba-1 selective state-space layers beside a few
attention layers, a plain gated MLP in every layer, a tied head.

Layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset`` (layers 7 and 21 of AI21-Jamba2-3B's 28) and Mamba
otherwise. Every layer is ``x += mixer(norm(x))``, ``x += mlp(norm(x))``
with plain-weight RMSNorms; ``num_experts`` is 1, so the feed-forward is
llama's SwiGLU.

- Mamba mixer (arXiv:2312.00752, with Jamba's norms on ``dt``, ``B`` and
  ``C``): ``[u | z] = h W_in``; ``c = silu(b + causal depthwise conv_K(u))``;
  ``[d | B | C] = c W_x``, each RMS-normed; ``dt = softplus(d W_dt + b_dt)``;
  per channel and state, ``S <- exp(dt A) S + dt c B``, ``y = S C + D c``;
  out ``(y silu(z)) W_out``. Everything from ``dt`` on is float32.
- Attention mixer: llama's block (``llama._block``) with the rotary left
  out -- the family has no positional term of any kind; the Mamba layers
  carry the order -- and ``num_attention_heads`` query heads on
  ``num_key_value_heads`` (20 on 1) KV heads.

Built for the dense slot layout of ``ml/generate.py`` under
``models/llama.py``'s signatures, and under the contract of
``models/slot_state.py``. The cache holds, side by side:

- ``k``/``v`` ``[attention layers, B, S_max * KV, head_dim]`` and ``len``
  ``[B]``. Keys and values lie FLAT (row ``t * KV + g`` is token ``t``, KV
  head ``g``; ``models/slot_state.py``): one KV head as ``[S_max, 1, 128]``
  bfloat16 would be padded on the chip to a whole (16, 128) tile in its
  last two axes, sixteen times its bytes; flat it costs its own, and
  ``ops.cached_decode_attention`` reads it as one matrix a block
  (``flat_kv_heads``).
- ``state`` ``[Mamba layers, B, N, d_inner]`` float32 and ``conv``
  ``[Mamba layers, K - 1, B, d_inner]``: no length axis. The state's
  ``N = 16`` and the window's ``K - 1 = 3`` stand second to last for the
  same reason: as the last axis, or before it beside a small one, the tile
  would pad them (8 and 5 times).

The stack is ONE scan over the layers whose body takes its branch by the
layer's kind (``lax.cond``): the Mamba layers' weights lie stacked in one
tree, the attention layers' in another, each branch cuts its own layer out
of its stack, and both kinds of state ride the scan's carry, so a decode
step has one loop and every update aliases in place. Prefill runs the
selective scan in chunks of ``CHUNK`` tokens: a chunk's decays and inputs
are computed at once and its dependent multiply-adds unrolled into one
pass over the ``[N, d_inner]`` state, a scan carries the state from chunk
to chunk, and no ``[T, T]`` or ``[T, d_inner, N]`` term exists. Decode is
one recurrent update a step.

Weights are stored ``x @ w``. An importer of the published checkpoint
(``ml/hf_import.py``, once its files are in the repository) would map
``mamba.in_proj.weight`` -> ``w_in`` (transposed), ``conv1d.weight``
``[d_inner, 1, K]`` -> ``conv_w`` ``[K, d_inner]``, ``conv1d.bias`` ->
``conv_b``, ``x_proj`` -> ``w_x``, ``dt_proj`` -> ``w_dt``/``b_dt``,
``A_log`` ``[d_inner, N]`` -> ``A_log`` ``[N, d_inner]``, ``D``,
``out_proj`` -> ``w_out``, ``dt_layernorm``/``b_layernorm``/
``c_layernorm`` -> ``dt_norm``/``b_norm``/``c_norm``,
``input_layernorm`` -> ``norm`` (Mamba) or ``attn_norm``,
``pre_ff_layernorm`` -> ``mlp_norm``, ``feed_forward.{gate,up,down}_proj``
-> ``w_gate``/``w_up``/``w_down``, ``embed_tokens`` -> ``embed`` (the head
is its transpose), ``final_layernorm`` -> ``final_norm``.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import (attention, cached_decode_attention, repeat_kv, rms_norm,
                   ssm_update)
from ..ops.selective_state import selective_scan_step
from . import llama
from .slot_state import UNSUPPORTED  # what ``Generator`` refuses

__all__ = ["JambaConfig", "init_params", "init_cache", "prefill_into",
           "decode_step", "selective_scan_chunked", "selective_scan_step",
           "UNSUPPORTED"]

CHUNK = 16  # tokens a chunk of the prefill scan


class JambaConfig:
    """Sizes under their published (HF ``config.json``) names."""

    def __init__(
        self,
        vocab_size: int = 65_536,
        hidden_size: int = 2560,
        intermediate_size: int = 8192,
        num_hidden_layers: int = 28,
        num_attention_heads: int = 20,
        num_key_value_heads: int = 1,
        head_dim: int | None = None,
        attn_layer_period: int = 14,
        attn_layer_offset: int = 7,
        mamba_d_state: int = 16,
        mamba_d_conv: int = 4,
        mamba_expand: int = 2,
        mamba_dt_rank: int = 160,
        num_experts: int = 1,
        rms_norm_eps: float = 1e-6,
        max_position_embeddings: int = 262_144,
        dtype: Any = jnp.bfloat16,
        use_flash: bool = True,
        kv_bits: int = 16,
    ) -> None:
        if num_experts != 1:
            raise ValueError(f"num_experts {num_experts}: the family's "
                             f"routed feed-forward is not written")
        if num_attention_heads % num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads
        self.head_dim = head_dim or hidden_size // num_attention_heads
        self.attn_layer_period = attn_layer_period
        self.attn_layer_offset = attn_layer_offset
        self.mamba_d_state = mamba_d_state
        self.mamba_d_conv = mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_dt_rank = mamba_dt_rank
        self.num_experts = num_experts
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.dtype = dtype
        self.use_flash = use_flash
        self.kv_bits = int(kv_bits)
        if not self.attn_layers or not self.mamba_layers:
            raise ValueError(
                f"period {attn_layer_period}, offset {attn_layer_offset} "
                f"over {num_hidden_layers} layers leave one kind of layer "
                f"out: the stack holds both")

    @property
    def attn_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if i % self.attn_layer_period == self.attn_layer_offset)

    @property
    def mamba_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if i % self.attn_layer_period != self.attn_layer_offset)

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    # what ``llama._block`` and ``llama._head`` read of a configuration
    @property
    def n_heads(self) -> int:
        return self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps


def init_params(cfg: JambaConfig, key) -> dict:
    """Seeded random weights in the serving tree (tests and examples; the
    benchmark draws its own). ``A_log``, ``D`` and ``b_dt`` as the model
    family's initialisation draws them; norm weights near 1."""
    D, V, F = cfg.hidden_size, cfg.vocab_size, cfg.intermediate_size
    Di, N, R, K = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                   cfg.mamba_d_conv)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Lm, La = len(cfg.mamba_layers), len(cfg.attn_layers)
    keys = iter(jax.random.split(key, 64))

    def dense(*shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    def near(centre, *shape):
        return centre + 0.1 * jax.random.normal(next(keys), shape,
                                                jnp.float32)

    def mlp(L):
        return {"mlp_norm": near(1.0, L, D),
                "w_gate": dense(L, D, F, fan_in=D),
                "w_up": dense(L, D, F, fan_in=D),
                "w_down": dense(L, F, D, fan_in=F)}

    dt = jnp.exp(jax.random.uniform(next(keys), (Lm, Di), jnp.float32,
                                    jnp.log(1e-3), jnp.log(0.1)))
    return {
        "embed": dense(V, D, fan_in=D),
        "final_norm": near(1.0, D),
        "mamba": {
            "norm": near(1.0, Lm, D),
            "w_in": dense(Lm, D, 2 * Di, fan_in=D),
            "conv_w": dense(Lm, K, Di, fan_in=K),
            "conv_b": near(0.0, Lm, Di),
            "w_x": dense(Lm, Di, R + 2 * N, fan_in=Di),
            "dt_norm": near(1.0, Lm, R),
            "b_norm": near(1.0, Lm, N),
            "c_norm": near(1.0, Lm, N),
            "w_dt": dense(Lm, R, Di, fan_in=R),
            "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1.0, N + 1.0))[None, :, None], (Lm, N, Di)),
            "D": jnp.ones((Lm, Di), jnp.float32),
            "w_out": dense(Lm, Di, D, fan_in=Di),
            **mlp(Lm),
        },
        "attn": {
            "attn_norm": near(1.0, La, D),
            "wq": dense(La, D, H * hd, fan_in=D),
            "wk": dense(La, D, KV * hd, fan_in=D),
            "wv": dense(La, D, KV * hd, fan_in=D),
            "wo": dense(La, H * hd, D, fan_in=H * hd),
            **mlp(La),
        },
    }


def init_cache(cfg: JambaConfig, batch: int,
               max_seq: int | None = None) -> dict:
    S = max_seq or cfg.max_position_embeddings
    Lm, La = len(cfg.mamba_layers), len(cfg.attn_layers)
    kv = (La, batch, S * cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "len": jnp.zeros((batch,), jnp.int32),
        "state": jnp.zeros((Lm, batch, cfg.mamba_d_state, cfg.d_inner),
                           jnp.float32),
        "conv": jnp.zeros((Lm, cfg.mamba_d_conv - 1, batch, cfg.d_inner),
                          cfg.dtype),
    }


# ------------------------------------------------------------ selective scan
def selective_scan_chunked(dt, x, B, C, A, chunk: int = CHUNK, S0=None):
    """The same recurrence over a whole sequence from ``S0`` (zero if not
    given), a chunk at a time. ``dt``, ``x`` [T, Di]; ``B``, ``C`` [T, N];
    ``A`` [N, Di]; all float32. Returns ``y`` [T, Di] and the last state
    [N, Di]. A token with ``dt = 0`` leaves the state as it was (decay 1,
    input 0), which is how the sequence is padded to whole chunks."""
    T, Di = x.shape
    N = B.shape[-1]
    pad = -T % chunk
    if pad:
        dt, x, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (dt, x, B, C))
    n = (T + pad) // chunk
    xs = tuple(a.reshape(n, chunk, a.shape[-1]) for a in (dt, x, B, C))

    def step(S, xs):
        dt_q, x_q, B_q, C_q = xs
        # the chunk's decays and inputs at once, [chunk, N, Di]
        decay = jnp.exp(dt_q[:, None, :] * A)
        drive = (dt_q * x_q)[:, None, :] * B_q[:, :, None]
        ys = []
        for t in range(chunk):
            S = decay[t] * S + drive[t]
            ys.append(jnp.sum(S * C_q[t][:, None], axis=0))
        return S, jnp.stack(ys)

    if S0 is None:
        S0 = jnp.zeros((N, Di), jnp.float32)
    S, y = jax.lax.scan(step, S0, xs)
    return y.reshape(n * chunk, Di)[:T], S


def _project(a, w):
    """A small projection whose result stays float32."""
    return jnp.dot(a.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _ssm_inputs(cfg, lp, c):
    """``c`` [..., Di] float32 (convolved, activated) -> float32 ``dt``
    [..., Di], ``B``, ``C`` [..., N]."""
    R, N, eps = cfg.mamba_dt_rank, cfg.mamba_d_state, cfg.rms_norm_eps
    dbc = _project(c, lp["w_x"])
    delta = rms_norm(dbc[..., :R], lp["dt_norm"], eps)
    B = rms_norm(dbc[..., R:R + N], lp["b_norm"], eps)
    C = rms_norm(dbc[..., R + N:], lp["c_norm"], eps)
    dt = jax.nn.softplus(_project(delta, lp["w_dt"])
                         + lp["b_dt"].astype(jnp.float32))
    return dt, B, C


def _ssm_out(lp, y, c, z, dtype):
    y = y + lp["D"].astype(jnp.float32) * c
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype) @ lp["w_out"]


def _mamba_prefill(cfg, lp, h, n):
    """One Mamba mixer over a prompt ``h`` [T, D] (normed) of ``n`` real
    tokens. Returns the mixer's output [T, D], the state after token
    ``n - 1`` and the window of the last ``K - 1`` real tokens."""
    T, K, Di = h.shape[0], cfg.mamba_d_conv, cfg.d_inner
    uz = h @ lp["w_in"]
    u, z = uz[:, :Di], uz[:, Di:]
    with jax.named_scope("ssm_conv"):
        # causal depthwise convolution: tap i meets the token K - 1 - i back
        padded = jnp.pad(u, ((K - 1, 0), (0, 0)))
        taps = lp["conv_w"].astype(jnp.float32)
        c = jax.nn.silu(lp["conv_b"].astype(jnp.float32) + sum(
            padded[i:i + T].astype(jnp.float32) * taps[i] for i in range(K)))
        window = jax.lax.dynamic_slice_in_dim(padded, n, K - 1, axis=0)
    dt, B, C = _ssm_inputs(cfg, lp, c)
    dt = jnp.where((jnp.arange(T) < n)[:, None], dt, 0.0)
    with jax.named_scope("ssm_scan"):
        y, S = selective_scan_chunked(
            dt, c, B, C, -jnp.exp(lp["A_log"].astype(jnp.float32)), CHUNK)
    return _ssm_out(lp, y, c, z, h.dtype), S, window


def _mamba_decode(cfg, lp, h, state, i, window):
    """One token a row: ``h`` [B, D] (normed), ``state`` the whole stack
    [Mamba layers, B, N, Di] of which layer ``i`` moves, ``window``
    [K - 1, B, Di]."""
    Di = cfg.d_inner
    uz = h @ lp["w_in"]
    u, z = uz[:, :Di], uz[:, Di:]
    with jax.named_scope("ssm_conv"):
        taps = lp["conv_w"].astype(jnp.float32)
        c = jax.nn.silu(
            lp["conv_b"].astype(jnp.float32)
            + jnp.einsum("kbd,kd->bd", window.astype(jnp.float32), taps[:-1])
            + u.astype(jnp.float32) * taps[-1])
        window = jnp.concatenate([window[1:], u[None]], axis=0)
    dt, B, C = _ssm_inputs(cfg, lp, c)
    with jax.named_scope("ssm_scan"):
        state, y = ssm_update(
            state, i, dt, c, B, C, -jnp.exp(lp["A_log"].astype(jnp.float32)))
    return _ssm_out(lp, y, c, z, h.dtype), state, window


# --------------------------------------------------------------- the stack
def _at(tree, i):
    """Layer ``i`` of a stacked tree (or array)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def _put(a, i, new):
    return jax.lax.dynamic_update_index_in_dim(a, new.astype(a.dtype), i, 0)


def _touch(a):
    """``a`` with its first element read and written back behind a
    barrier. A branch of ``lax.cond`` that hands an array through
    untouched makes XLA copy it into the conditional's result (2.4 GB of
    state and windows at each attention layer of a 256-row decode step);
    one that updates it in place does not, so the branch that does not
    need the array updates one element of it with itself."""
    first = (0,) * a.ndim
    one = jax.lax.optimization_barrier(
        jax.lax.dynamic_slice(a, first, (1,) * a.ndim))
    return jax.lax.dynamic_update_slice(a, one, first)


def _stack(cfg, params, x, st, mamba_mixer, attend_at, pick):
    """The frame both programs share: one scan over the layers, the branch
    taken by the layer's kind, final norm and the tied head on ``pick``'s
    rows. ``x`` [b, s, D]; ``st`` is every array that rides the carry.
    ``mamba_mixer(lp, h, st, i) -> (y, st)`` is the Mamba mixer on the
    normed stream for Mamba layer ``i``; ``attend_at(st, i)`` gives
    ``llama._block``'s ``attend`` for attention layer ``i``."""
    L = cfg.num_hidden_layers
    is_attn = np.isin(np.arange(L), cfg.attn_layers)
    # a layer's number within its own kind's stack
    own = np.where(is_attn, np.cumsum(is_attn) - 1, np.cumsum(~is_attn) - 1)

    def mamba_layer(x, st, i):
        lp = _at(params["mamba"], i)
        with jax.named_scope("ssm"):
            y, st = mamba_mixer(lp, rms_norm(x, lp["norm"], cfg.norm_eps),
                                st, i)
            x = x + y
        with jax.named_scope("mlp"):
            x = x + llama._swiglu(
                rms_norm(x, lp["mlp_norm"], cfg.norm_eps), lp)
        return x, st

    def attn_layer(x, st, i):
        # llama's block with the rotary left out
        x, st = llama._block(cfg, x, _at(params["attn"], i), None, None,
                             attend_at(st, i))
        return x, {**st, "state": _touch(st["state"]),
                   "conv": _touch(st["conv"])}

    def body(carry, xs):
        kind, i = xs
        return jax.lax.cond(kind, attn_layer, mamba_layer, *carry, i), None

    (x, st), _ = jax.lax.scan(
        body, (x, st), (jnp.asarray(is_attn), jnp.asarray(own, jnp.int32)))
    head = {"final_norm": params["final_norm"], "lm_head": params["embed"].T}
    return llama._head(head, cfg, x, pick), st


# --------------------------------------------------------------- serving API
def prefill_into(params: dict, tokens: jnp.ndarray, seq_lens: jnp.ndarray,
                 cfg: JambaConfig, cache: dict, slot: jnp.ndarray,
                 mesh=None) -> tuple[jnp.ndarray, dict]:
    """Prefill ONE prompt [1, S_pad] into row ``slot`` of the shared
    cache: keys and values of its positions, and the state and window as
    token ``seq_lens[0] - 1`` left them, computed from zero (the slot's
    last occupant leaves no trace). Returns the last real token's logits
    [1, V] and the cache."""
    del mesh
    T = tokens.shape[1]
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    rows_max = cache["k"].shape[2]
    if T * KV > rows_max:
        raise ValueError(f"prompt bucket {T} exceeds cache length "
                         f"{rows_max // KV}")
    n = seq_lens[0]
    x = params["embed"][tokens].astype(cfg.dtype)
    Lm, La = len(cfg.mamba_layers), len(cfg.attn_layers)
    # the prompt's own row of every kind of state, filled layer by layer
    st = {"k": jnp.zeros((La, T * KV, hd), cfg.dtype),
          "v": jnp.zeros((La, T * KV, hd), cfg.dtype),
          "state": jnp.zeros((Lm, cfg.mamba_d_state, cfg.d_inner),
                             jnp.float32),
          "conv": jnp.zeros((Lm, cfg.mamba_d_conv - 1, cfg.d_inner),
                            cfg.dtype)}

    def mamba_mixer(lp, h, st, i):
        y, S, window = _mamba_prefill(cfg, lp, h[0], n)
        return y[None], {**st, "state": _put(st["state"], i, S),
                         "conv": _put(st["conv"], i, window)}

    def attend_at(st, i):
        def attend(q, k, v):
            rep = cfg.n_heads // KV
            # (llama's binding: once the kernel's submodule of the same
            # name is imported, ``ops.flash_attention`` is that module)
            full = llama.flash_attention if cfg.use_flash else attention
            o = full(q, repeat_kv(k, rep), repeat_kv(v, rep), causal=True,
                        kv_len=seq_lens)
            return o, {**st, "k": _put(st["k"], i, k.reshape(T * KV, hd)),
                       "v": _put(st["v"], i, v.reshape(T * KV, hd))}
        return attend

    logits, st = _stack(cfg, params, x, st, mamba_mixer, attend_at,
                        lambda x: x[:, n - 1])

    def put(name, rows, axis):
        return jax.lax.dynamic_update_index_in_dim(
            cache[name], rows.astype(cache[name].dtype), slot, axis=axis)

    grow = ((0, 0), (0, rows_max - T * KV), (0, 0))
    return logits, {
        "k": put("k", jnp.pad(st["k"], grow), 1),
        "v": put("v", jnp.pad(st["v"], grow), 1),
        "len": cache["len"].at[slot].set(n),
        "state": put("state", st["state"], 1),
        "conv": put("conv", st["conv"], 2),
    }


def decode_step(params: dict, tokens: jnp.ndarray, cache: dict,
                cfg: JambaConfig, mesh=None) -> tuple[jnp.ndarray, dict]:
    """One token per row: tokens [B] -> (logits [B, V], updated cache).
    Every row's state moves, an idle row's too (its next occupant's
    prefill overwrites it); the attention layers write each row at its own
    ``len`` as llama's do."""
    del mesh
    b = tokens.shape[0]
    KV = cfg.n_kv_heads
    pos = cache["len"]
    S_max = cache["k"].shape[2] // KV
    kv_len = jnp.minimum(pos + 1, S_max)
    x = params["embed"][tokens][:, None, :].astype(cfg.dtype)
    rows = jnp.arange(b)[:, None]
    at = pos[:, None] * KV + jnp.arange(KV)[None, :]   # [B, KV] cache rows

    def mamba_mixer(lp, h, st, i):
        y, state, window = _mamba_decode(cfg, lp, h[:, 0], st["state"], i,
                                         _at(st["conv"], i))
        return y[:, None], {**st, "state": state,
                            "conv": _put(st["conv"], i, window)}

    def attend_at(st, i):
        def attend(q, k, v):
            # a row at capacity keeps decoding garbage: its writes fall
            # outside and are dropped, its length stays at the end
            new = {**st, "k": st["k"].at[i, rows, at].set(k[:, 0]),
                   "v": st["v"].at[i, rows, at].set(v[:, 0])}
            o = cached_decode_attention(q, new["k"], new["v"], kv_len,
                                        layer=i, use_kernel=cfg.use_flash,
                                        flat_kv_heads=KV)
            return o, new
        return attend

    st0 = {key: cache[key] for key in cache if key != "len"}
    logits, st = _stack(cfg, params, x, st0, mamba_mixer, attend_at,
                        lambda x: x[:, 0])
    return logits, {**st, "len": kv_len}
