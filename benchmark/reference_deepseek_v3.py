"""The plain reference of the DeepSeek-V3 block (GigaChat3.1-702B-A36B's
architecture): a pre-norm decoder with multi-head latent attention in
straightforward ``jax.numpy`` and float32, after the model's public
``config.json`` and transformers' ``DeepseekV3`` modelling code. No
kernel, no cache, no batching, no absorbed form, and nothing imported
from the program: it gets the benchmark's own weights, one prompt with the
tokens the program served after it, and returns the logits at the served
positions.

Layer ``i``: ``h += attn(n(h))``, ``h += ffn(n(h))``, ``n(x) = x
rsqrt(mean(x^2) + eps) w``.

- Attention: ``q = n_q(x W_dq) W_uq``, per head ``[q_nope | q_pe]``;
  ``[c | k_pe] = x W_dkv``, ``c = n_kv(c)``; per head ``k_nope = c W_uk``,
  ``v = c W_uv``; ``q_pe`` and ``k_pe`` (one for all heads) rotated with
  YaRN's frequencies, their pairs interleaved; ``softmax(q . k s)`` causal
  over ``k = [k_nope | k_pe]``, ``s = (d_nope + d_rope)^-1/2 m^2`` with
  ``m = 0.1 mscale_all_dim ln(factor) + 1``; ``o W_o``.
- Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the others ``shared(x) + sum_e w_e expert_e(x)``:
  ``s = sigmoid(x W_g)``, ``b = s + bias``; a group's score is the sum of
  its two best ``b``, the ``topk_group`` best groups stay, the
  ``num_experts_per_tok`` best ``b`` in them are chosen (the rest read 0);
  ``w`` the chosen ``s`` over their sum, times ``routed_scaling_factor``.

Departures from the published model, all of layout or of the stated cut,
none of the mathematics: (1) the share: ``n_routed_experts`` of the
``router_width`` experts (the first ones) are held; routing runs over all
of them, only held experts' terms are added, the shared expert is counted
once, and the partial sum goes on; the vocabulary is the slice the weights
have. (2) ``kv_b_proj`` is stored as two per-head blocks ``w_uk`` [H,
d_nope, r_kv] and ``w_uv`` [H, r_kv, d_v], and each expert's gate and up
projections as one matrix ``[gate | up]``: a permutation of the
checkpoint's entries. (3) The multi-token-prediction layer is a draft for
speculation and is not run by plain serving.

Attention is computed a block of queries at a time, so that a context of
8,192 positions needs no [heads, T, T] array.

``precision``:
- ``"float32"``: matmuls at ``highest`` (on a TPU a float32 matmul is
  otherwise one bf16 pass). What ``correct`` compares against.
- ``"int8"``: the control. Every linear layer's weights (per output
  channel) and activations (per row), and every cached latent row
  ``[c | k_pe]`` (per token), are rounded to 8-bit integers before they
  are multiplied. The router stays in float32: an int8 deployment keeps it
  so, and the control then differs by rounding alone.
"""

from __future__ import annotations

import functools
import math

Q_BLOCK = 256  # query positions a block of the attention


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


def yarn_inv_freq(dim: int, base: float, scaling: dict | None):
    """Rotary frequencies of ``dim`` dimensions (``dim / 2`` pairs), YaRN's
    where ``scaling`` says so, line by line as transformers'
    ``_compute_yarn_parameters`` writes them; numpy float64."""
    import numpy as np

    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling or (scaling.get("rope_type") or scaling.get("type")) \
            != "yarn":
        return 1.0 / pos_freqs
    factor = scaling["factor"]
    orig = scaling["original_max_position_embeddings"]
    beta_fast = scaling.get("beta_fast") or 32
    beta_slow = scaling.get("beta_slow") or 1

    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation_factor = 1 - ramp
    inv_freq_extrapolation = 1.0 / pos_freqs
    inv_freq_interpolation = 1.0 / (factor * pos_freqs)
    return (inv_freq_interpolation * (1 - extrapolation_factor)
            + inv_freq_extrapolation * extrapolation_factor)


def yarn_get_mscale(scale: float, mscale: float) -> float:
    """transformers' ``yarn_get_mscale``."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def _scales(sizes: dict) -> tuple:
    """(softmax scale, factor on cos and sin) as DeepSeek-V3 sets them."""
    scale = (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5
    rs = sizes.get("rope_scaling") or {}
    if (rs.get("rope_type") or rs.get("type")) != "yarn":
        return scale, 1.0
    factor = rs["factor"]
    m_all = yarn_get_mscale(factor, rs.get("mscale_all_dim") or 0)
    return (scale * m_all * m_all,
            yarn_get_mscale(factor, rs.get("mscale") or 0) / m_all)


@functools.lru_cache(maxsize=None)
def _program(sizes_key: tuple, T: int, K: int, int8: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    sizes = {k: (dict(v) if isinstance(v, tuple) else v)
             for k, v in sizes_key}
    eps = sizes["rms_norm_eps"]
    H = sizes["num_attention_heads"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    rkv = sizes["kv_lora_rank"]
    n_dense = sizes["first_k_dense_replace"]
    held, E = sizes["n_routed_experts"], sizes["router_width"]
    top_k, n_group = sizes["num_experts_per_tok"], sizes["n_group"]
    topk_group = sizes["topk_group"]
    F, Fm = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    Fs = Fm * sizes["n_shared_experts"]
    scale, rope_factor = _scales(sizes)
    q_block = math.gcd(T, Q_BLOCK)
    inv_freq = yarn_inv_freq(dr, sizes["rope_theta"], sizes.get("rope_scaling"))
    angles = np.arange(T, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.cos(angles) * rope_factor, jnp.float32)
    sin = jnp.asarray(np.sin(angles) * rope_factor, jnp.float32)

    def rope(x):
        """x [T, heads, dr] with its pairs interleaved (x[2i], x[2i+1])."""
        even, odd = x[..., 0::2], x[..., 1::2]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([even * c - odd * s, odd * c + even * s], -1)

    def attention(x, lp):
        c_q = _norm(_linear(x, lp["w_dq"], int8), lp["q_norm"], eps)
        q = _linear(c_q, lp["w_uq"], int8).reshape(T, H, dn + dr)
        kv = _linear(x, lp["w_dkv"], int8)
        c = _norm(kv[:, :rkv], lp["kv_norm"], eps)
        k_pe = rope(kv[:, None, rkv:rkv + dr])
        if int8:  # the cached row, per token
            row = _fq(jnp.concatenate([c, k_pe[:, 0]], -1), -1)
            c, k_pe = row[:, :rkv], row[:, None, rkv:]
        w_uk = lp["w_uk"].astype(jnp.float32)     # [H, dn, rkv]
        w_uv = lp["w_uv"].astype(jnp.float32)     # [H, rkv, dv]
        if int8:  # per output channel: over r_kv, which both contract
            w_uk, w_uv = _fq(w_uk, -1), _fq(w_uv, -2)
        k = jnp.concatenate(
            [jnp.einsum("tc,hdc->thd", c, w_uk),
             jnp.broadcast_to(k_pe, (T, H, dr))], -1)
        v = jnp.einsum("tc,hcv->thv", c, w_uv)
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], -1)

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, q_block, 0)
            scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
            mask = (q0 + jnp.arange(q_block))[:, None] >= jnp.arange(T)
            scores = jnp.where(mask[None], scores, -jnp.inf)
            return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(scores, -1), v)

        o = jax.lax.map(block, jnp.arange(0, T, q_block))
        return _linear(o.reshape(T, -1), lp["wo"], int8)

    def swiglu(x, w_gate_up, w_down, width):
        h = _linear(x, w_gate_up, int8)
        return _linear(jax.nn.silu(h[:, :width]) * h[:, width:], w_down, int8)

    def route(x, mp):
        s = jax.nn.sigmoid(x @ mp["router"].astype(jnp.float32))
        b = s + mp["bias"].astype(jnp.float32)
        group = jax.lax.top_k(b.reshape(T, n_group, -1), 2)[0].sum(-1)
        kept_groups = jax.lax.top_k(group, topk_group)[1]
        kept = jnp.any(kept_groups[:, :, None] == jnp.arange(n_group),
                       axis=1)                                 # [T, G]
        choice = jnp.where(jnp.repeat(kept, E // n_group, axis=1), b, 0.0)
        idx = jax.lax.top_k(choice, top_k)[1]
        w = jnp.take_along_axis(s, idx, axis=1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20) * sizes[
            "routed_scaling_factor"]
        # [T, held]: a token's weight on each held expert, 0 where it was
        # not chosen; experts past ``held`` live on other chips
        chosen = idx[:, :, None] == jnp.arange(held)[None, None, :]
        return jnp.sum(jnp.where(chosen, w[:, :, None], 0.0), axis=1)

    def experts(x, mp, w_gate_up, w_down):
        weight = route(x, mp)

        def one(y, e):
            w_gu, w_d, w_e = e
            return y + w_e[:, None] * swiglu(x, w_gu, w_d, Fm), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                            (w_gate_up, w_down, weight.T))
        return y + swiglu(x, mp["s_gate_up"], mp["s_down"], Fs)

    def layer_of(tree, i):
        return jax.tree.map(lambda a: a[i], tree)

    @jax.jit
    def logits_at(params, tokens, positions):
        with jax.default_matmul_precision("highest"):
            x = params["embed"][tokens].astype(jnp.float32)
            L = params["attn"]["norm"].shape[0]
            stack = jax.tree.map(
                lambda a: a.reshape(L - n_dense, held, *a.shape[1:]),
                params["experts"])
            for i in range(L):
                lp = layer_of(params["attn"], i)
                x = x + attention(_norm(x, lp["norm"], eps), lp)
                h = _norm(x, lp["ffn_norm"], eps)
                if i < n_dense:
                    dp = layer_of(params["dense"], i)
                    x = x + swiglu(h, dp["w_gate_up"], dp["w_down"], F)
                else:
                    j = i - n_dense
                    ep = layer_of(stack, j)
                    x = x + experts(h, layer_of(params["moe"], j),
                                    ep["w_gate_up"], ep["w_down"])
            x = _norm(x, params["final_norm"], eps)[positions]
            return _linear(x, params["lm_head"], int8)

    return logits_at


_SHAPE_KEYS = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
               "rms_norm_eps", "rope_theta", "rope_scaling",
               "first_k_dense_replace", "n_routed_experts", "router_width",
               "n_shared_experts", "num_experts_per_tok", "n_group",
               "topk_group", "routed_scaling_factor", "intermediate_size",
               "moe_intermediate_size")


def _key(sizes: dict) -> tuple:
    return tuple((k, tuple(sorted(sizes[k].items()))
                  if isinstance(sizes[k], dict) else sizes[k])
                 for k in _SHAPE_KEYS)


def logits_at(params, sizes: dict, ids, positions, *, precision="float32",
              pad_to: int = 2048, max_positions: int = 256):
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
    fn = _program(_key(sizes), T, max_positions, precision == "int8")
    return np.asarray(fn(params, tokens, pos))[:k]


def gaps(params, sizes: dict, prompt, served, *, control: bool = False,
         pad_to: int = 2048, max_positions: int = 256) -> dict:
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
