"""Mixture-of-Experts layer with expert parallelism over the ``ep`` axis.

EP is the last parallelism family the reference lacks (SURVEY §2.10).
GShard-style capacity-based dispatch, formulated as dense einsums because
the MXU wants batched matmuls, not per-token gathers:

- router: top-k softmax over expert logits, f32;
- dispatch: each (token, choice) claims a capacity slot in its expert via a
  cumulative-sum position (deterministic, leftmost-first; overflowing
  tokens are DROPPED — their residual path carries them, the standard
  GShard/Switch behavior);
- experts: stacked [E, ...] SwiGLU weights, one batched einsum per
  projection. Sharding rule ``P("ep", ...)`` puts experts on their own mesh
  axis and the dispatch/combine einsums become XLA all_to_alls over ICI;
- combine: weighted scatter back, zeros for dropped tokens.

Serving uses the dropless layer beside it (a router, ``route_top_k`` or
``route_group_limited``; ``dropless_experts``; a shared expert,
``gated_shared_expert`` or ``shared_expert``): no capacity, no dropped
token. It is told which experts this chip ``held``: the router keeps its
full width, top-k and the renormalisation run over every expert, and only
the held experts' terms are computed and added (one chip's share of an
expert-parallel layer, without the exchange). Tokens are sorted by expert
and each projection is one grouped product over the held experts
(``ops.grouped_matmul``: on a TPU a Pallas kernel that streams each touched
expert's weights once, ``ops/grouped_matmul.py``; ``jax.lax.ragged_dot``
everywhere else).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..ops import grouped_matmul
from ..parallel import P, constrain

__all__ = ["MoEConfig", "init_moe_params", "moe_layer", "MOE_SHARDING_RULES",
           "route_top_k", "route_group_limited", "dropless_experts",
           "gated_shared_expert", "shared_expert", "MOE_COUNTERS",
           "add_counts"]

# the routing counters a serving family with held experts keeps in its
# cache (``moe_counts``), read only when ``Generator.pool_stats()`` asks
MOE_COUNTERS = ("expert_pairs_routed", "expert_pairs_held", "experts_touched")


class MoEConfig:
    def __init__(self, dim: int, ffn_dim: int, n_experts: int = 8,
                 top_k: int = 2, capacity_factor: float = 1.25,
                 dtype: Any = jnp.bfloat16) -> None:
        self.dim = dim
        self.ffn_dim = ffn_dim
        self.n_experts = n_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.dtype = dtype


MOE_SHARDING_RULES = (
    (r"router", P(None, None)),
    (r"experts/(w_gate|w_up)", P("ep", None, "tp")),
    (r"experts/w_down", P("ep", "tp", None)),
)


def init_moe_params(cfg: MoEConfig, key) -> dict:
    E, D, F = cfg.n_experts, cfg.dim, cfg.ffn_dim
    ks = jax.random.split(key, 4)

    def dense(key, *shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)
                ).astype(cfg.dtype)

    return {
        "router": jax.random.normal(ks[0], (D, E), jnp.float32) * (D ** -0.5),
        "experts": {
            "w_gate": dense(ks[1], E, D, F, fan_in=D),
            "w_up": dense(ks[2], E, D, F, fan_in=D),
            "w_down": dense(ks[3], E, F, D, fan_in=F),
        },
    }


def _dispatch_combine(probs: jnp.ndarray, top_k: int, capacity: int):
    """probs [N, E] -> (dispatch [N, E, C] 0/1, combine [N, E, C] weights,
    aux_loss). Deterministic leftmost-first slot assignment; choice k=0
    claims slots before k=1 (GShard priority)."""
    n, e = probs.shape
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)          # [N, k]
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)                # renormalize

    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)    # [N, k, E]
    # priority order: all k=0 choices (token order), then all k=1 ...
    flat = onehot.transpose(1, 0, 2).reshape(top_k * n, e)     # [kN, E]
    pos = jnp.cumsum(flat, axis=0) - flat                      # slot index
    pos = (pos * flat).sum(-1)                                 # [kN]
    kept = (pos < capacity) & (flat.sum(-1) > 0)
    pos = pos.reshape(top_k, n).transpose(1, 0)                # [N, k]
    kept = kept.reshape(top_k, n).transpose(1, 0)              # [N, k]

    slot_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # [N,k,C]
    seat = onehot[..., None] * slot_onehot[:, :, None, :]      # [N,k,E,C]
    seat = seat * kept[:, :, None, None]
    dispatch = seat.sum(1)                                     # [N, E, C]
    combine = (seat * gate_vals[:, :, None, None]).sum(1)      # [N, E, C]

    # load-balancing auxiliary loss (Switch): E * sum(frac_tokens * frac_probs)
    me = probs.mean(0)                                         # [E]
    ce = onehot[:, 0, :].mean(0)                               # top-1 assignment
    aux = (me * ce).sum() * e
    return dispatch, combine, aux


def moe_layer(params: dict, x: jnp.ndarray, cfg: MoEConfig
              ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x [B, S, D] -> (y [B, S, D], aux_loss). Call from a transformer block
    in place of the dense MLP; add aux_loss (weighted ~1e-2) to the task
    loss during training."""
    b, s, d = x.shape
    n = b * s
    capacity = max(1, int(cfg.capacity_factor * n * cfg.top_k / cfg.n_experts))

    xf = x.reshape(n, d)
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    dispatch, combine, aux = _dispatch_combine(probs, cfg.top_k, capacity)

    dt = cfg.dtype
    # [N,D] x [N,E,C] -> [E,C,D]: the all_to_all boundary when ep > 1
    expert_in = jnp.einsum("nd,nec->ecd", xf.astype(jnp.float32),
                           dispatch).astype(dt)
    expert_in = constrain(expert_in, P("ep", None, None))
    ex = params["experts"]
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, ex["w_gate"]))
    u = jnp.einsum("ecd,edf->ecf", expert_in, ex["w_up"])
    out = jnp.einsum("ecf,efd->ecd", g * u, ex["w_down"])
    out = constrain(out, P("ep", None, None))
    y = jnp.einsum("ecd,nec->nd", out.astype(jnp.float32), combine)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ------------------------------------------------------- dropless (serving)
def route_top_k(x: jnp.ndarray, router: jnp.ndarray, top_k: int):
    """x [N, D], router [D, E] -> (weights [N, k] float32 summing to 1,
    experts [N, k] int32). Logits and softmax in float32 at full matmul
    precision: with weights this close, a bfloat16 pass flips members of
    the top-k."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    vals, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    return vals / vals.sum(-1, keepdims=True), idx


def route_group_limited(x: jnp.ndarray, router: jnp.ndarray,
                        bias: jnp.ndarray, top_k: int, n_group: int,
                        topk_group: int, scale: float):
    """DeepSeek-V3's ``noaux_tc`` routing: x [N, D], router [D, E], bias
    [E] (``e_score_correction_bias``) -> (weights [N, k] float32 summing
    to ``scale``, experts [N, k] int32), ``route_top_k``'s form.

    ``s = sigmoid(x W)`` in float32 at full matmul precision; the choice
    reads ``b = s + bias``: a group of ``E / n_group`` experts scores the
    sum of its two best ``b``, the ``topk_group`` best groups stay, and the
    ``top_k`` best ``b`` among their experts are chosen (the others' read
    0, as transformers' ``masked_fill`` leaves them). The weights are the
    chosen experts' unbiased ``s``, renormalised and times ``scale``
    (``routed_scaling_factor``)."""
    n, e = x.shape[0], router.shape[1]
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    b = s + bias.astype(jnp.float32)
    grouped = b.reshape(n, n_group, e // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)          # [N, G]
    _, keep = jax.lax.top_k(group_score, topk_group)
    kept = jnp.zeros((n, n_group), bool).at[
        jnp.arange(n)[:, None], keep].set(True)
    choice = jnp.where(jnp.repeat(kept, e // n_group, axis=1), b, 0.0)
    _, idx = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, idx, axis=1)
    return w / (w.sum(-1, keepdims=True) + 1e-20) * scale, idx


def dropless_experts(x: jnp.ndarray, weights: jnp.ndarray, idx: jnp.ndarray,
                     w_gate_up: jnp.ndarray, w_down: jnp.ndarray,
                     held: tuple[int, int], valid: jnp.ndarray | None = None,
                     layer=None):
    """The held experts' part of ``sum_e w_e * down_e(silu(gate_e x) *
    up_e x)``. ``x`` [N, D]; ``weights``, ``idx`` [N, k] from
    ``route_top_k``; ``w_gate_up`` [held, D, 2F] (gate | up), ``w_down``
    [held, F, D]; ``held`` = (first, count) of the router's experts whose
    weights these are; ``valid`` [N] leaves padding out. With ``layer`` (a
    traced index) the weights are a whole stack's, [layers * held, ...]:
    the grouped product takes the stack as it lies and names the layer's
    experts by their rows in it (``first = layer * held``), where a slice
    of one layer would first be copied out: the product is a custom call,
    on the chip the Pallas kernel, and a slice does not fuse into one (a
    layer's 0.8 GB a call at the benchmark's size). Returns
    ``y`` [N, D] and int32 counts (pairs routed, pairs that fell on held
    experts, held experts with at least one token)."""
    n, k = idx.shape
    first, count = held
    pairs = n * k
    local = idx.reshape(pairs) - first
    real = (jnp.ones((n,), bool) if valid is None else valid)
    mine = (local >= 0) & (local < count) & jnp.repeat(real, k)
    # pairs sorted by held expert; everything else goes behind the last
    key = jnp.where(mine, local, count)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    first = None if layer is None else layer * count
    xs = x[order // k]
    h = grouped_matmul(xs, w_gate_up, sizes, first=first)
    f = w_down.shape[1]
    y = grouped_matmul(jax.nn.silu(h[:, :f]) * h[:, f:], w_down, sizes,
                       first=first)
    # rows behind the last group belong to no expert: whatever the grouped
    # product left there is not a term of the sum
    n_mine = jnp.sum(sizes)
    y = jnp.where((jnp.arange(pairs) < n_mine)[:, None], y, 0)
    back = jnp.zeros((pairs,), jnp.int32).at[order].set(jnp.arange(pairs))
    y = jnp.einsum("nkd,nk->nd", y[back].reshape(n, k, -1).astype(jnp.float32),
                   weights * mine.reshape(n, k))
    stats = (jnp.sum(real, dtype=jnp.int32) * k, n_mine,
             jnp.sum(sizes > 0, dtype=jnp.int32))
    return y.astype(x.dtype), stats


def gated_shared_expert(x: jnp.ndarray, s_gate_up: jnp.ndarray,
                        s_down: jnp.ndarray, s_mix: jnp.ndarray):
    """``sigmoid(x . s_mix) * down(silu(gate x) * up x)``: the expert every
    token meets, computed whole on every chip."""
    h = x @ s_gate_up
    f = s_down.shape[0]
    y = (jax.nn.silu(h[:, :f]) * h[:, f:]) @ s_down
    mix = jax.nn.sigmoid(x.astype(jnp.float32) @ s_mix.astype(jnp.float32))
    return (y * mix[:, None].astype(y.dtype)).astype(x.dtype)


def shared_expert(x: jnp.ndarray, s_gate_up: jnp.ndarray,
                  s_down: jnp.ndarray):
    """``down(silu(gate x) * up x)``, ungated: the expert every token meets,
    computed whole on every chip. ``s_gate_up`` [D, 2F] (gate | up),
    ``s_down`` [F, D]."""
    h = x @ s_gate_up
    f = s_down.shape[0]
    return ((jax.nn.silu(h[:, :f]) * h[:, f:]) @ s_down).astype(x.dtype)


def add_counts(counts, adds):
    """``counts`` [n, 2] uint32 (low, high words) plus ``adds`` [n]: 64-bit
    sums out of 32-bit adds (a busy server passes 2**32 pairs in hours)."""
    low = counts[:, 0] + adds.astype(jnp.uint32)
    high = counts[:, 1] + (low < counts[:, 0]).astype(jnp.uint32)
    return jnp.stack([low, high], axis=-1)
