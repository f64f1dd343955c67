"""The plain reference of the Jamba block: a hybrid pre-norm decoder in
straightforward ``jax.numpy`` and float32, after the model's public
``config.json`` (AI21-Jamba2-3B, ``model_type: jamba``) and the Mamba
paper (arXiv:2312.00752). No kernel, no cache, no batching, no chunked
form, and nothing imported from the program: it gets the benchmark's own
weights, one prompt with the tokens the program served after it, and
returns the logits at the served positions.

Layer ``i``: ``x += mixer(n(x; w_in))``, ``x += mlp(n(x; w_ff))``, with
``n(x; w) = x rsqrt(mean(x^2) + eps) w`` and ``mlp(h) = (silu(h W_gate)
(h W_up)) W_down``; the mixer is attention where ``i % attn_layer_period
== attn_layer_offset``, else Mamba. After the last layer ``n(x;
w_final)`` and the tied head ``x E^T``.

- Mamba: ``[u | z] = h W_in``; ``c_t = silu(b_conv + sum_k w_conv[k]
  u_{t-K+1+k})`` (causal, depthwise, zeros before the sequence); ``[d | B
  | C] = c W_x``, each through its own ``n``; ``dt = softplus(d W_dt +
  b_dt)``; ``A = -exp(A_log)``; token by token from ``S = 0``: ``S <-
  exp(dt A) S + (dt c) B``, ``y = S C + D c``; out ``(y silu(z)) W_out``.
  The recurrence runs token by token (``lax.scan``).
- Attention: ``num_attention_heads`` query heads of ``head_dim`` on
  ``num_key_value_heads`` KV heads, causal softmax of ``q k^T /
  sqrt(head_dim)``, **no rotary and no other positional term**; ``W_o``.

Departures from the published checkpoint, all of layout: weights are
stored ``x @ w``, the convolution's taps as ``[K, d_inner]`` and ``A_log``
as ``[N, d_inner]`` (the transposes of the checkpoint's), the Mamba
layers stacked in one tree and the attention layers in another.

``precision``:
- ``"float32"``: matmuls at ``highest`` (on a TPU a float32 matmul is
  otherwise one bf16 pass). What ``correct`` compares against.
- ``"int8"``: the control. Every linear layer's weights (per output
  channel) and activations (per row), every key and value vector, and the
  ``dt``, ``c``, ``B`` and ``C`` vectors that enter the recurrent state
  are rounded to 8-bit integers before they are multiplied.
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


def _norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


def selective_scan(dt, c, B, C, A):
    """The selective state-space recurrence, token by token from a zero
    state. ``dt``, ``c`` [T, Di]; ``B``, ``C`` [T, N]; ``A`` [N, Di].
    Returns ``y`` [T, Di] and the last state [N, Di]."""
    import jax
    import jax.numpy as jnp

    def token(S, x):
        dt_t, c_t, B_t, C_t = x
        S = (jnp.exp(dt_t[None, :] * A) * S
             + (dt_t * c_t)[None, :] * B_t[:, None])
        return S, jnp.einsum("nd,n->d", S, C_t)

    S0 = jnp.zeros((B.shape[1], c.shape[1]), jnp.float32)
    S, y = jax.lax.scan(token, S0, (dt, c, B, C))
    return y, S


def _layer(tree, i):
    import jax

    return jax.tree.map(lambda a: a[i], tree)


def layer_kinds(sizes: dict) -> list:
    """True where layer ``i`` is attention."""
    return [i % sizes["attn_layer_period"] == sizes["attn_layer_offset"]
            for i in range(sizes["num_hidden_layers"])]


@functools.lru_cache(maxsize=None)
def _program(sizes_key: tuple, T: int, K: int, int8: bool):
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes_key)
    eps = sizes["rms_norm_eps"]
    H, KV, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    Di = sizes["mamba_expand"] * sizes["hidden_size"]
    N, R, taps = (sizes["mamba_d_state"], sizes["mamba_dt_rank"],
                  sizes["mamba_d_conv"])

    def mlp(x, lp):
        h = _norm(x, lp["mlp_norm"], eps)
        gate = jax.nn.silu(_linear(h, lp["w_gate"], int8))
        return x + _linear(gate * _linear(h, lp["w_up"], int8),
                           lp["w_down"], int8)

    def mamba(x, lp):
        uz = _linear(_norm(x, lp["norm"], eps), lp["w_in"], int8)
        u, z = uz[:, :Di], uz[:, Di:]
        w = lp["conv_w"].astype(jnp.float32)            # [taps, Di]
        past = jnp.pad(u, ((taps - 1, 0), (0, 0)))
        c = jax.nn.silu(lp["conv_b"].astype(jnp.float32)
                        + sum(past[i:i + T] * w[i] for i in range(taps)))
        dbc = _linear(c, lp["w_x"], int8)
        d = _norm(dbc[:, :R], lp["dt_norm"], eps)
        B = _norm(dbc[:, R:R + N], lp["b_norm"], eps)
        C = _norm(dbc[:, R + N:], lp["c_norm"], eps)
        dt = jax.nn.softplus(_linear(d, lp["w_dt"], int8)
                             + lp["b_dt"].astype(jnp.float32))
        s_in = c
        if int8:
            dt, s_in, B, C = _fq(dt, -1), _fq(c, -1), _fq(B, -1), _fq(C, -1)
        y, _ = selective_scan(dt, s_in, B, C,
                              -jnp.exp(lp["A_log"].astype(jnp.float32)))
        y = y + lp["D"].astype(jnp.float32) * c
        return mlp(x + _linear(y * jax.nn.silu(z), lp["w_out"], int8), lp)

    def attention(x, lp):
        h = _norm(x, lp["attn_norm"], eps)
        q = _linear(h, lp["wq"], int8).reshape(T, KV, H // KV, hd)
        k = _linear(h, lp["wk"], int8).reshape(T, KV, hd)
        v = _linear(h, lp["wv"], int8).reshape(T, KV, hd)
        if int8:
            k, v = _fq(k, -1), _fq(v, -1)
        scores = jnp.einsum("tkgd,skd->kgts", q, k) * (hd ** -0.5)
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(T, H * hd)
        return mlp(x + _linear(o, lp["wo"], int8), lp)

    def embed(params, tokens):
        return params["embed"][tokens].astype(jnp.float32)

    def head(params, x, positions):
        x = _norm(x, params["final_norm"], eps)[positions]
        return _linear(x, params["embed"].T, int8)

    def at_highest(fn):
        def run(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return jax.jit(run)

    # a program a kind of layer, not one of the whole stack: the layers
    # are walked in Python, each handed its own slice of its kind's tree
    return {"embed": jax.jit(embed), "mamba": at_highest(mamba),
            "attention": at_highest(attention), "head": at_highest(head)}


_SHAPE_KEYS = ("num_hidden_layers", "hidden_size", "num_attention_heads",
               "num_key_value_heads", "head_dim", "attn_layer_period",
               "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
               "mamba_expand", "mamba_dt_rank", "rms_norm_eps")


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
    fns = _program(tuple((key, sizes[key]) for key in _SHAPE_KEYS), T,
                   max_positions, precision == "int8")
    x = fns["embed"](params, tokens)
    n_attn = n_mamba = 0
    for is_attn in layer_kinds(sizes):   # in the stack's order
        if is_attn:
            x = fns["attention"](x, _layer(params["attn"], n_attn))
            n_attn += 1
        else:
            x = fns["mamba"](x, _layer(params["mamba"], n_mamba))
            n_mamba += 1
    return np.asarray(fns["head"](params, x, pos))[:k]


def gaps(params, sizes: dict, prompt, served, *, control: bool = False,
         pad_to: int = 512, max_positions: int = 256) -> dict:
    """For one request: at each served position, how far the served
    token's reference logit lies below the reference's best (``gaps``).
    With ``control``, also how far the token that the int8 computation
    puts first lies below it (``control_gaps``): the control need not
    decode, it is read at the same prompts and tokens. The logits are
    computed ``max_positions`` served positions at a time, so the
    ``[positions, vocab]`` block of the longest answer fits beside the
    weights."""
    import numpy as np

    ids = list(prompt) + list(served)
    positions = np.arange(len(prompt) - 1, len(ids) - 1)
    served = np.asarray(served, np.int64)
    out = {"gaps": []}
    if control:
        out["control_gaps"] = []
    for a in range(0, len(served), max_positions):
        sl = slice(a, a + max_positions)
        ref = logits_at(params, sizes, ids, positions[sl], pad_to=pad_to,
                        max_positions=max_positions)
        rows = np.arange(ref.shape[0])
        best = ref.max(-1)
        out["gaps"].append(best - ref[rows, served[sl]])
        if control:
            low = logits_at(params, sizes, ids, positions[sl],
                            precision="int8", pad_to=pad_to,
                            max_positions=max_positions)
            out["control_gaps"].append(best - ref[rows, low.argmax(-1)])
    return {key: np.concatenate(v) for key, v in out.items()}
