"""The plain reference: a dense pre-norm decoder (RMSNorm, rotary
positions in the rotate-half convention, grouped-query causal attention,
SwiGLU) in straightforward ``jax.numpy`` and float32, as the Mistral-7B
and DeepSeek-LLM-7B model cards describe it. No kernel, no cache, no
batching, and nothing imported from the program: it gets the benchmark's
own weights, one prompt with the tokens the program served after it, and
returns the logits at the served positions.

``precision``:
- ``"float32"``: matmuls at ``highest`` (on a TPU a float32 matmul is
  otherwise one bf16 pass). What ``correct`` compares against.
- ``"int8"``: the control. Every linear layer's weights (per output
  channel) and activations (per row), and every key and value vector, are
  rounded to 8-bit integers before they are multiplied: the step below
  the bfloat16 the configurations state, and the one a later PR would be
  tempted by (the chip's int8 peak is twice its bf16 peak).
"""

from __future__ import annotations

import functools


def _fq(x, axis):
    """Symmetric 8-bit rounding along ``axis`` (fake-quantised: the
    integers times their scale, in float32)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _linear(x, w, int8: bool):
    import jax.numpy as jnp

    w = w.astype(jnp.float32)
    if int8:
        x, w = _fq(x, -1), _fq(w, 0)
    return x @ w


def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [T, heads, hd]; rotates the pair (x[:half], x[half:])."""
    import jax.numpy as jnp

    T, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.lru_cache(maxsize=None)
def _program(sizes_key: tuple, T: int, K: int, int8: bool):
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes_key)
    H, KV, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]

    def layer(x, lp):
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = _rope(_linear(h, lp["wq"], int8).reshape(T, H, hd), theta)
        k = _rope(_linear(h, lp["wk"], int8).reshape(T, KV, hd), theta)
        v = _linear(h, lp["wv"], int8).reshape(T, KV, hd)
        if int8:
            k, v = _fq(k, -1), _fq(v, -1)
        g = H // KV
        qg = q.reshape(T, KV, g, hd)
        scores = jnp.einsum("tkgd,skd->kgts", qg, k) * (hd ** -0.5)
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(T, H * hd)
        x = x + _linear(o, lp["wo"], int8)
        h = _rms_norm(x, lp["mlp_norm"], eps)
        gate = jax.nn.silu(_linear(h, lp["w_gate"], int8))
        x = x + _linear(gate * _linear(h, lp["w_up"], int8),
                        lp["w_down"], int8)
        return x, None

    @jax.jit
    def logits_at(params, tokens, positions):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            x, _ = jax.lax.scan(layer, x, params["layers"])
            x = _rms_norm(x, params["final_norm"], eps)[positions]
            return _linear(x, params["lm_head"], int8)

    return logits_at


_SHAPE_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "rms_norm_eps", "rope_theta")


def logits_at(params, sizes: dict, ids, positions, *, precision="float32",
              pad_to: int = 512, max_positions: int = 256):
    """Float32 logits ``[len(positions), vocab]`` of the sequence ``ids``
    at ``positions``. The sequence is right-padded to a multiple of
    ``pad_to`` (causal, so no checked row changes) and the positions to
    ``max_positions``, so a run compiles few shapes."""
    import numpy as np

    if precision not in ("float32", "int8"):
        raise ValueError(f"unknown reference precision {precision!r}")
    n, k = len(ids), len(positions)
    if k > max_positions:
        raise ValueError(f"{k} positions asked, {max_positions} at most")
    T = -(-n // pad_to) * pad_to
    tokens = np.zeros((T,), np.int32)
    tokens[:n] = ids
    pos = np.zeros((max_positions,), np.int32)
    pos[:k] = positions
    fn = _program(tuple((key, sizes[key]) for key in _SHAPE_KEYS), T,
                  max_positions, precision == "int8")
    return np.asarray(fn(params, tokens, pos))[:k]


def gaps(params, sizes: dict, prompt, served, *, control: bool = False,
         pad_to: int = 512, max_positions: int = 256) -> dict:
    """For one request: at each served position, how far the served
    token's reference logit lies below the reference's best (``gaps``).
    With ``control``, also how far the token that the int8 computation
    puts first lies below it (``control_gaps``): the control need not
    decode, it is read at the same prompts and tokens."""
    import numpy as np

    ids = list(prompt) + list(served)
    positions = np.arange(len(prompt) - 1, len(ids) - 1)
    served = np.asarray(served, np.int64)
    ref = logits_at(params, sizes, ids, positions, pad_to=pad_to,
                    max_positions=max_positions)
    rows = np.arange(len(served))
    best = ref.max(-1)
    out = {"gaps": best - ref[rows, served]}
    if control:
        low = logits_at(params, sizes, ids, positions, precision="int8",
                        pad_to=pad_to, max_positions=max_positions)
        out["control_gaps"] = best - ref[rows, low.argmax(-1)]
    return out
