"""The plain reference of the Qwen3-Next block: a hybrid pre-norm decoder
in straightforward ``jax.numpy`` and float32, after the model's public
``config.json`` and modelling code (Qwen3-Next-80B-A3B-Instruct) and the
Gated Delta Networks paper (arXiv:2412.06464). No kernel, no cache, no
batching, no chunked form, and nothing imported from the program: it gets
the benchmark's own weights, one prompt with the tokens the program
served after it, and returns the logits at the served positions.

Layer ``i`` of a period of ``full_attention_interval``: ``h += mixer(n(h))``,
``h += experts(n(h))``, with ``n(x) = x rsqrt(mean(x^2) + eps) (1 + w)``;
the mixer is gated attention where ``(i + 1) % interval == 0``, else Gated
DeltaNet.

- Gated DeltaNet: ``[q|k|v|z] = x W_qkvz``, ``[b|a] = x W_ba``; ``[q|k|v]``
  through a causal depthwise convolution (kernel 4, no bias) and silu;
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; q and k
  L2-normalised per head, each key head repeated for its value heads,
  ``q / sqrt(dk)``. Per head and token, with the state ``S`` (key x
  value): ``S <- exp(g) S``; ``delta = beta (v - S^T k)``; ``S <- S + k
  delta^T``; ``o = S^T q``. Output ``rmsnorm(o) w silu(z)`` per head (plain
  weight), then ``W_out``. The recurrence runs token by token.
- Gated attention: ``[q|gate]`` per head from ``W_q``; q and k normed per
  head (``1 + w``); rotary (rotate-half) on the first ``head_dim *
  partial_rotary_factor`` dimensions; causal softmax, grouped queries;
  ``o sigmoid(gate)``; ``W_o``.
- Experts: ``p = softmax(x W_r)`` over ``router_width``; top-k; ``w = p /
  sum p``; ``y = sum_e w_e down_e(silu(gate_e x) up_e x) + sigmoid(x . s)
  shared(x)``.

Departures from the published model, all of layout or of the stated cut,
none of the mathematics: (1) the share: ``num_experts`` of the
``router_width`` experts (the first ones) are held; routing, top-k and the
renormalisation run over all of them, only held experts' terms are added,
and the partial sum goes on; the vocabulary is the slice the weights have.
(2) ``W_qkvz`` and ``W_ba`` are stored as flat blocks, where the checkpoint
interleaves them by key-head group, and each expert's gate and up
projections as one matrix ``[gate | up]``: a permutation of columns. (3)
The checkpoint's multi-token-prediction head is not run by plain serving
and is not here.

``precision``:
- ``"float32"``: matmuls at ``highest`` (on a TPU a float32 matmul is
  otherwise one bf16 pass). What ``correct`` compares against.
- ``"int8"``: the control. Every linear layer's weights (per output
  channel) and activations (per row), every key and value vector of the
  attention layers and every q, k and v vector that enters the recurrent
  state are rounded to 8-bit integers before they are multiplied. The
  router stays in float32: an int8 deployment keeps it so, and the control
  then differs by rounding alone, not by another choice of experts.
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


def _norm(x, w, eps, centre=1.0):
    """RMSNorm; the stack's norms store their weight's distance from 1."""
    import jax
    import jax.numpy as jnp

    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (centre + w.astype(jnp.float32)))


def _rope(x, theta, dims):
    """x: [T, heads, hd]; rotates the pair (x[:half], x[half:dims]) of the
    first ``dims`` dimensions and leaves the rest."""
    import jax.numpy as jnp

    T = x.shape[0]
    half = dims // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:dims]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., dims:]],
                           -1)


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token from a zero state. ``q``, ``k``
    [T, H, dk]; ``v`` [T, H, dv]; ``g``, ``beta`` [T, H]. Returns ``o``
    [T, H, dv] and the last state [H, dk, dv]."""
    import jax
    import jax.numpy as jnp

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[:, None, None]
        delta = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    S, o = jax.lax.scan(token, S0, (q, k, v, g, beta))
    return o, S


@functools.lru_cache(maxsize=None)
def _program(sizes_key: tuple, T: int, K: int, int8: bool):
    import jax
    import jax.numpy as jnp

    sizes = dict(sizes_key)
    eps, theta = sizes["rms_norm_eps"], sizes["rope_theta"]
    H, KV, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    rot = int(hd * sizes["partial_rotary_factor"])
    Hk, Hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    taps = sizes["linear_conv_kernel_dim"]
    interval = sizes["full_attention_interval"]
    top_k, held = sizes["num_experts_per_tok"], sizes["num_experts"]
    F = sizes["moe_intermediate_size"]
    Fs = sizes["shared_expert_intermediate_size"]

    def delta_net(x, lp):
        qkvz = _linear(x, lp["w_qkvz"], int8)
        ba = _linear(x, lp["w_ba"], int8)
        kd, vd = Hk * dk, Hv * dv
        mixed, z = qkvz[:, :2 * kd + vd], qkvz[:, 2 * kd + vd:]
        conv = lp["conv"].astype(jnp.float32)           # [taps, channels]
        past = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
        mixed = jax.nn.silu(sum(past[i:i + T] * conv[i] for i in range(taps)))
        q = mixed[:, :kd].reshape(T, Hk, dk)
        k = mixed[:, kd:2 * kd].reshape(T, Hk, dk)
        v = mixed[:, 2 * kd:].reshape(T, Hv, dv)
        unit = lambda a: a * jax.lax.rsqrt(
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        q = jnp.repeat(unit(q), Hv // Hk, axis=1) * dk ** -0.5
        k = jnp.repeat(unit(k), Hv // Hk, axis=1)
        if int8:
            q, k, v = _fq(q, -1), _fq(k, -1), _fq(v, -1)
        beta = jax.nn.sigmoid(ba[:, :Hv])
        g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[:, Hv:] + lp["dt_bias"].astype(jnp.float32))
        o, _ = delta_rule(q, k, v, g, beta)
        o = _norm(o, lp["o_norm"], eps, centre=0.0)
        o = o * jax.nn.silu(z.reshape(T, Hv, dv))
        return _linear(o.reshape(T, vd), lp["w_out"], int8)

    def attention(x, lp):
        qg = _linear(x, lp["wq"], int8).reshape(T, H, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:].reshape(T, H * hd)
        k = _linear(x, lp["wk"], int8).reshape(T, KV, hd)
        v = _linear(x, lp["wv"], int8).reshape(T, KV, hd)
        q = _rope(_norm(q, lp["q_norm"], eps), theta, rot)
        k = _rope(_norm(k, lp["k_norm"], eps), theta, rot)
        if int8:
            k, v = _fq(k, -1), _fq(v, -1)
        qg = q.reshape(T, KV, H // KV, hd)
        scores = jnp.einsum("tkgd,skd->kgts", qg, k) * (hd ** -0.5)
        mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("kgts,skd->tkgd", probs, v).reshape(T, H * hd)
        return _linear(o * jax.nn.sigmoid(gate), lp["wo"], int8)

    def swiglu(x, w_gate_up, w_down, width):
        h = _linear(x, w_gate_up, int8)
        return _linear(jax.nn.silu(h[:, :width]) * h[:, width:], w_down, int8)

    def experts(x, mp, w_gate_up, w_down):
        probs = jax.nn.softmax(x @ mp["router"].astype(jnp.float32), -1)
        vals, idx = jax.lax.top_k(probs, top_k)
        vals = vals / vals.sum(-1, keepdims=True)
        # [T, held]: a token's weight on each held expert, 0 where it was
        # not chosen; experts past ``held`` live on other chips
        chosen = idx[:, :, None] == jnp.arange(held)[None, None, :]
        weight = jnp.sum(jnp.where(chosen, vals[:, :, None], 0.0), axis=1)

        def one(y, e):
            w_gu, w_d, w_e = e
            return y + w_e[:, None] * swiglu(x, w_gu, w_d, F), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (w_gate_up, w_down, weight.T))
        mix = jax.nn.sigmoid(x @ mp["s_mix"].astype(jnp.float32))
        return y + mix[:, None] * swiglu(x, mp["s_gate_up"], mp["s_down"], Fs)

    def period(x, xs):
        pp, stack = xs  # the period's layers; its experts [interval, held, ..]
        for i in range(interval):
            if (i + 1) % interval:
                lp = pp["lin"][i]
                x = x + delta_net(_norm(x, lp["norm"], eps), lp)
            else:
                x = x + attention(_norm(x, pp["attn"]["norm"], eps),
                                  pp["attn"])
            mp = pp["moe"][i]
            x = x + experts(_norm(x, mp["norm"], eps), mp,
                            stack["w_gate_up"][i], stack["w_down"][i])
        return x, None

    @jax.jit
    def logits_at(params, tokens, positions):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            # the routed experts lie as one stack [layers * held, ...],
            # layer by layer
            periods = jax.tree.leaves(params["periods"])[0].shape[0]
            stack = jax.tree.map(
                lambda a: a.reshape(periods, interval, held, *a.shape[1:]),
                params["experts"])
            x, _ = jax.lax.scan(period, x, (params["periods"], stack))
            x = _norm(x, params["final_norm"], eps)[positions]
            return _linear(x, params["lm_head"], int8)

    return logits_at


_SHAPE_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "partial_rotary_factor", "rms_norm_eps", "rope_theta",
               "linear_num_key_heads", "linear_num_value_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim", "full_attention_interval",
               "num_experts_per_tok", "num_experts", "moe_intermediate_size",
               "shared_expert_intermediate_size")


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
