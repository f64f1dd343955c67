"""DeepSeek-V3 block (``model_type: deepseek_v3``; GigaChat3.1 is one):
multi-head latent attention, a few leading dense layers, then routed
experts behind a plain shared expert, sigmoid group-limited routing.

Every layer is ``x += attn(n(x))``, ``x += ffn(n(x))`` with plain-weight
RMSNorms. The feed-forward of the first ``first_k_dense_replace`` layers
is a SwiGLU of ``intermediate_size``; every later one is ``shared(x) +
sum_e w_e expert_e(x)``, routed by ``moe.route_group_limited``.

Latent attention (widths at GigaChat3.1's: 64 heads, ``d_nope`` 128,
``d_rope`` 64, ``d_v`` 192, ``r_q`` 1536, ``r_kv`` 512):

- ``q = RMSNorm(x W_dq) W_uq`` per head ``[q_nope | q_pe]``;
  ``[c_kv | k_pe] = x W_dkv``, ``c_kv`` RMS-normed, ``k_pe`` one vector
  shared by the heads; ``q_pe`` and ``k_pe`` rotated (YaRN frequencies,
  the pairs interleaved as the published checkpoint lays them);
- ``[k_nope | v] = c_kv W_ukv`` per head, ``k = [k_nope | k_pe]``,
  ``o = softmax(q k s) v W_o`` with ``s = (d_nope + d_rope)^-1/2 m^2``,
  ``m`` YaRN's scale at ``mscale_all_dim``.

Built for the dense slot layout of ``ml/generate.py`` under
``models/llama.py``'s signatures. The cache holds:

- ``latent`` ``[L, B, S_max, W]``: one row a token and layer,
  ``[c_kv | k_pe]`` (576 values), stored at whole lanes (``W`` 640, the
  rest zero), which is what the chip's tiling costs a 576-wide plane
  anyway (``ops/latent_attention.py``); ``len`` ``[B]``;
- ``moe_counts``: what routing did, summed on the device
  (``MOE_COUNTERS``), read only when ``Generator.pool_stats()`` is asked.

``prefill_into`` computes attention expanded, through
``ops.flash_attention`` (``q``, ``k`` and ``v`` all ``d_nope + d_rope`` =
``d_v`` wide, ``m^2`` folded into ``q`` so the kernel's own ``d^-1/2``
holds), and writes the prompt's latent rows. ``decode_step`` absorbs:
``q_lat = q_nope W_uk^T`` per head scores ``[q_lat | q_pe]`` against the
rows (``ops.latent_decode_attention``, one read of a row's positions for
all heads) and ``o = (softmax . c_kv) W_uv``.

The stack is ONE scan over the layers; the feed-forward takes its branch
by the layer's kind (``lax.cond``), the dense layers' weights in one
stacked tree, the expert layers' in another, as ``jamba._stack`` does.
The routed experts this chip holds lie as one stack ``[expert layers *
held, ...]`` that every layer's grouped product takes whole
(``moe.dropless_experts``, ``layer=``).

Weights are stored ``x @ w``. An importer of the published checkpoint
would map ``q_a_proj`` -> ``w_dq``, ``q_a_layernorm`` -> ``q_norm``,
``q_b_proj`` -> ``w_uq``, ``kv_a_proj_with_mqa`` -> ``w_dkv``,
``kv_a_layernorm`` -> ``kv_norm``, ``kv_b_proj`` ``[H (d_nope + d_v),
r_kv]`` -> ``w_uk`` ``[H, d_nope, r_kv]`` and ``w_uv`` ``[H, r_kv, d_v]``,
``o_proj`` -> ``wo``, ``gate.weight`` -> ``router``,
``gate.e_score_correction_bias`` -> ``bias``, each expert's gate and up
projections -> one ``[gate | up]`` matrix (the shared expert's too). The
checkpoint's multi-token-prediction layer is a draft for speculation, not
part of plain serving, and is not here.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..ops import (
    apply_rope,
    attention,
    flash_attention,
    latent_decode_attention,
    rms_norm,
    rope_table,
    yarn_mscale,
)
from . import moe
from .moe import MOE_COUNTERS

__all__ = ["DeepseekV3Config", "init_params", "init_cache", "prefill_into",
           "decode_step", "MOE_COUNTERS", "UNSUPPORTED"]

LANES = 128

# what ``Generator`` refuses for the family, each with what it would take
UNSUPPORTED = {
    "page_size": "the latent rows have no page pool yet: the paged layout, "
                 "the prefix cache, kv_offload and kv_transport need pages "
                 "of [c_kv | k_pe] rows and a paged latent decode kernel",
    "sp": "sequence-parallel prefill would have to shard the latent rows "
          "and combine the absorbed attention across shards",
    "spec_k": "speculation needs a verify window over the latent cache "
              "(and the model's own draft head, which is not served)",
    "kv_bits": "the latent rows are served in the model's dtype only (no "
               "int8/int4 planes)",
    "prefill_chunk": "a prompt's segments would have to attend to the "
                     "latent rows of the ones before (expanded, or "
                     "absorbed with many query positions)",
    "mesh": "the family has no sharding rules (shard_cache and "
            "tensor-parallel replicas need them, and an expert exchange "
            "where it routes)",
}


class DeepseekV3Config:
    """Sizes under their published (HF ``config.json``) names, defaults as
    GigaChat3.1-702B-A36B publishes them. ``held`` is ``(first, count)``:
    the routed experts whose weights this chip holds; the router is
    ``n_routed_experts`` wide whatever is held."""

    def __init__(
        self,
        vocab_size: int = 128_256,
        hidden_size: int = 7168,
        intermediate_size: int = 18_432,
        moe_intermediate_size: int = 2048,
        num_hidden_layers: int = 64,
        num_attention_heads: int = 64,
        q_lora_rank: int = 1536,
        kv_lora_rank: int = 512,
        qk_nope_head_dim: int = 128,
        qk_rope_head_dim: int = 64,
        v_head_dim: int = 192,
        n_routed_experts: int = 256,
        n_shared_experts: int = 1,
        num_experts_per_tok: int = 8,
        n_group: int = 8,
        topk_group: int = 4,
        routed_scaling_factor: float = 2.5,
        norm_topk_prob: bool = True,
        topk_method: str = "noaux_tc",
        scoring_func: str = "sigmoid",
        first_k_dense_replace: int = 3,
        rope_theta: float = 100_000.0,
        rope_scaling: dict | None = None,
        rms_norm_eps: float = 1e-6,
        max_position_embeddings: int = 262_144,
        held: tuple[int, int] | None = None,
        dtype: Any = jnp.bfloat16,
        use_flash: bool = True,
        kv_bits: int = 16,
    ) -> None:
        if (topk_method, scoring_func, norm_topk_prob) != (
                "noaux_tc", "sigmoid", True):
            raise ValueError(
                f"routing {topk_method}/{scoring_func}/norm "
                f"{norm_topk_prob}: only noaux_tc over sigmoid scores, "
                f"renormalised, is written")
        if not 0 <= first_k_dense_replace < num_hidden_layers:
            raise ValueError(f"{first_k_dense_replace} leading dense layers "
                             f"of {num_hidden_layers} leave no expert layer")
        if n_routed_experts % n_group or not 0 < topk_group <= n_group:
            raise ValueError(f"{n_routed_experts} experts in {n_group} "
                             f"groups, {topk_group} kept")
        if qk_nope_head_dim + qk_rope_head_dim != v_head_dim:
            raise ValueError("prefill's attention kernel takes q, k and v "
                             "of one width: d_nope + d_rope must be d_v")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.n_routed_experts = n_routed_experts
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.n_group = n_group
        self.topk_group = topk_group
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.first_k_dense_replace = first_k_dense_replace
        self.rope_theta = float(rope_theta)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.rms_norm_eps = rms_norm_eps
        self.max_position_embeddings = max_position_embeddings
        self.held = (0, n_routed_experts) if held is None else tuple(held)
        first, count = self.held
        if first < 0 or count < 1 or first + count > n_routed_experts:
            raise ValueError(f"held {self.held} lies outside the router's "
                             f"{n_routed_experts} experts")
        self.dtype = dtype
        self.use_flash = use_flash
        self.kv_bits = int(kv_bits)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Values of a cache row: ``[c_kv | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """A cache row as stored: the latent width at whole lanes."""
        return -(-self.latent_width // LANES) * LANES

    @property
    def n_expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    def _yarn(self, key: str) -> float:
        """YaRN's scale at ``rope_scaling[key]``; 1 without YaRN."""
        s = self.rope_scaling or {}
        if (s.get("rope_type") or s.get("type")) != "yarn":
            return 1.0
        return yarn_mscale(float(s["factor"]), float(s.get(key) or 0.0))

    @property
    def softmax_scale(self) -> float:
        """``(d_nope + d_rope)^-1/2``, times YaRN's ``m^2`` at
        ``mscale_all_dim``."""
        return self.qk_head_dim ** -0.5 * self._yarn("mscale_all_dim") ** 2

    @property
    def rope_factor(self) -> float:
        """What YaRN multiplies cos and sin by: its scale at ``mscale``
        over its scale at ``mscale_all_dim`` (1 where they agree)."""
        return self._yarn("mscale") / self._yarn("mscale_all_dim")


def init_params(cfg: DeepseekV3Config, key) -> dict:
    """Seeded random weights in the serving tree (tests and examples; the
    benchmark draws its own). Norm weights near 1; the routing bias 0.05
    wide, enough to move some of a token's choices."""
    D, V = cfg.hidden_size, cfg.vocab_size
    L, Ld, Le = (cfg.num_hidden_layers, cfg.first_k_dense_replace,
                 cfg.n_expert_layers)
    H, rq, rkv = cfg.num_attention_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fm = cfg.intermediate_size, cfg.moe_intermediate_size
    Fs = Fm * cfg.n_shared_experts
    E, Eh = cfg.n_routed_experts, cfg.held[1]
    keys = iter(jax.random.split(key, 32))

    def dense(*shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * fan_in ** -0.5).astype(cfg.dtype)

    def near_one(*shape):
        return 1.0 + 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

    return {
        "embed": dense(V, D, fan_in=D),
        "final_norm": near_one(D),
        "lm_head": dense(D, V, fan_in=D),
        "attn": {
            "norm": near_one(L, D),
            "w_dq": dense(L, D, rq, fan_in=D),
            "q_norm": near_one(L, rq),
            "w_uq": dense(L, rq, H * (dn + dr), fan_in=rq),
            "w_dkv": dense(L, D, rkv + dr, fan_in=D),
            "kv_norm": near_one(L, rkv),
            "w_uk": dense(L, H, dn, rkv, fan_in=rkv),
            "w_uv": dense(L, H, rkv, dv, fan_in=rkv),
            "wo": dense(L, H * dv, D, fan_in=H * dv),
            "ffn_norm": near_one(L, D),
        },
        "dense": {
            "w_gate_up": dense(Ld, D, 2 * F, fan_in=D),
            "w_down": dense(Ld, F, D, fan_in=F),
        },
        "moe": {
            "router": dense(Le, D, E, fan_in=D),
            "bias": 0.05 * jax.random.normal(next(keys), (Le, E),
                                             jnp.float32),
            "s_gate_up": dense(Le, D, 2 * Fs, fan_in=D),
            "s_down": dense(Le, Fs, D, fan_in=Fs),
        },
        # expert layer l's held experts are rows l * held ... (l + 1) * held - 1
        "experts": {
            "w_gate_up": dense(Le * Eh, D, 2 * Fm, fan_in=D),
            "w_down": dense(Le * Eh, Fm, D, fan_in=Fm),
        },
    }


def init_cache(cfg: DeepseekV3Config, batch: int,
               max_seq: int | None = None) -> dict:
    S = max_seq or cfg.max_position_embeddings
    return {
        "latent": jnp.zeros((cfg.num_hidden_layers, batch, S, cfg.row_width),
                            cfg.dtype),
        "len": jnp.zeros((batch,), jnp.int32),
        # (low, high) 32-bit words of each of MOE_COUNTERS
        "moe_counts": jnp.zeros((len(MOE_COUNTERS), 2), jnp.uint32),
    }


def _at(tree, i):
    """Layer ``i`` of a stacked tree."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


# ------------------------------------------------------- latent attention
def _rope(cfg, x, cos, sin):
    """Rotary on ``x`` [N, heads, d_rope] whose pairs lie interleaved
    (``x[2i], x[2i + 1]``), as transformers' DeepSeek-V3 takes them: the
    pairs are gathered into halves first. q and k go through the same
    permutation, so their products are the checkpoint's."""
    n, h, d = x.shape
    halves = x.reshape(n, h, d // 2, 2).swapaxes(-1, -2).reshape(n, h, d)
    return apply_rope(halves, cos * cfg.rope_factor, sin * cfg.rope_factor)


def _mla_inputs(cfg, lp, h, cos, sin):
    """The normed stream ``h`` [N, D] -> ``q_nope`` [N, H, d_nope], the
    rotated ``q_pe`` [N, H, d_rope] and the cache row [N, W] (``[c_kv |
    k_pe]`` normed and rotated, zero to whole lanes)."""
    n = h.shape[0]
    H, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim
    rkv, eps = cfg.kv_lora_rank, cfg.rms_norm_eps
    c_q = rms_norm(h @ lp["w_dq"], lp["q_norm"], eps)
    q = (c_q @ lp["w_uq"]).reshape(n, H, cfg.qk_head_dim)
    kv = h @ lp["w_dkv"]
    c_kv = rms_norm(kv[:, :rkv], lp["kv_norm"], eps)
    k_pe = _rope(cfg, kv[:, None, rkv:], cos, sin)[:, 0]
    row = jnp.concatenate([c_kv, k_pe.astype(c_kv.dtype)], axis=-1)
    row = jnp.pad(row, ((0, 0), (0, cfg.row_width - cfg.latent_width)))
    return q[..., :dn], _rope(cfg, q[..., dn:], cos, sin), row


def _expanded(cfg, lp, q_nope, q_pe, row, seq_lens):
    """Prefill's attention over the prompt itself, every head's key and
    value expanded from the latent rows. Returns [T, H * d_v]."""
    T = row.shape[0]
    H, rkv = cfg.num_attention_heads, cfg.kv_lora_rank
    c_kv, k_pe = row[:, :rkv], row[:, rkv:cfg.latent_width]
    k_nope = jnp.einsum("tc,hdc->thd", c_kv, lp["w_uk"])
    v = jnp.einsum("tc,hcv->thv", c_kv, lp["w_uv"])
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None], (T, H, k_pe.shape[-1]))],
        axis=-1)
    # the attention takes d^-1/2 of its own; YaRN's m^2 rides on q
    scale = cfg.softmax_scale * cfg.qk_head_dim ** 0.5
    q = (jnp.concatenate([q_nope, q_pe], axis=-1) * scale).astype(k.dtype)
    full = flash_attention if cfg.use_flash else attention
    o = full(q[None], k[None], v[None], causal=True, kv_len=seq_lens)[0]
    return o.reshape(T, H * cfg.v_head_dim)


def _absorbed(cfg, lp, q_nope, q_pe, latent, layer, kv_len):
    """Decode's attention: ``W_uk`` absorbed into the query, the rows read
    once for all heads, ``W_uv`` applied to what they give. Returns
    [B, H * d_v]."""
    b = q_nope.shape[0]
    q_lat = jnp.einsum("bhd,hdc->bhc", q_nope, lp["w_uk"])
    pad = cfg.row_width - cfg.latent_width
    q = jnp.concatenate(
        [q_lat, q_pe.astype(q_lat.dtype),
         jnp.zeros((b, q_lat.shape[1], pad), q_lat.dtype)], axis=-1)
    with jax.named_scope("latent_decode"):
        u = latent_decode_attention(q, latent, kv_len, layer=layer,
                                    scale=cfg.softmax_scale,
                                    value_width=cfg.kv_lora_rank)
    o = jnp.einsum("bhc,hcv->bhv", u, lp["w_uv"])
    return o.reshape(b, -1)


# --------------------------------------------------------------- the stack
def _stack(cfg, params, x, rows, counts, mix, valid):
    """The frame both programs share: ONE scan over the layers (attention
    weights as the scan's input), the feed-forward's branch taken by the
    layer's kind, then the final norm. ``x`` [N, D]; ``rows`` the latent
    rows that ride the carry, ``counts`` the routing counters.
    ``mix(lp, h, rows, i) -> (o, rows)`` is layer ``i``'s attention on the
    normed stream, before ``W_o``. Returns ``(x, rows, counts)``."""
    L, Ld = cfg.num_hidden_layers, cfg.first_k_dense_replace
    eps, stack = cfg.rms_norm_eps, params["experts"]

    def dense_ffn(h, counts, j):
        # a leading dense layer's SwiGLU has the shared expert's form
        lp = _at(params["dense"], j)
        return moe.shared_expert(h, lp["w_gate_up"], lp["w_down"]), counts

    def expert_ffn(h, counts, j):
        mp = _at(params["moe"], j)
        with jax.named_scope("router"):
            w, idx = moe.route_group_limited(
                h, mp["router"], mp["bias"], cfg.num_experts_per_tok,
                cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor)
        with jax.named_scope("experts"):
            y, stats = moe.dropless_experts(
                h, w, idx, stack["w_gate_up"], stack["w_down"], cfg.held,
                valid=valid, layer=j)
            y = y + moe.shared_expert(h, mp["s_gate_up"], mp["s_down"])
        return y, moe.add_counts(counts, jnp.stack(stats))

    def body(carry, xs):
        x, rows, counts = carry
        lp, i = xs
        with jax.named_scope("mla"):
            o, rows = mix(lp, rms_norm(x, lp["norm"], eps), rows, i)
            x = x + (o @ lp["wo"])
        h = rms_norm(x, lp["ffn_norm"], eps)
        if Ld:
            y, counts = jax.lax.cond(i >= Ld, expert_ffn, dense_ffn, h,
                                     counts, jnp.where(i >= Ld, i - Ld, i))
        else:
            y, counts = expert_ffn(h, counts, i)
        return (x + y, rows, counts), None

    (x, rows, counts), _ = jax.lax.scan(
        body, (x, rows, counts),
        (params["attn"], jnp.arange(L, dtype=jnp.int32)))
    return rms_norm(x, params["final_norm"], eps), rows, counts


def _head(params, x):
    with jax.named_scope("lm_head"):
        return (x @ params["lm_head"]).astype(jnp.float32)


def _tables(cfg, positions):
    return rope_table(positions, cfg.qk_rope_head_dim, cfg.rope_theta,
                      cfg.rope_scaling)


# --------------------------------------------------------------- serving API
def prefill_into(params: dict, tokens: jnp.ndarray, seq_lens: jnp.ndarray,
                 cfg: DeepseekV3Config, cache: dict, slot: jnp.ndarray,
                 mesh=None) -> tuple[jnp.ndarray, dict]:
    """Prefill ONE prompt [1, S_pad] into row ``slot`` of the shared
    cache: the latent rows of its positions in every layer. Returns the
    last real token's logits [1, V] and the cache."""
    del mesh
    T = tokens.shape[1]
    S_max = cache["latent"].shape[2]
    if T > S_max:
        raise ValueError(f"prompt bucket {T} exceeds cache length {S_max}")
    n = seq_lens[0]
    x = params["embed"][tokens[0]].astype(cfg.dtype)
    cos, sin = _tables(cfg, jnp.arange(T))

    def mix(lp, h, rows, i):
        q_nope, q_pe, row = _mla_inputs(cfg, lp, h, cos, sin)
        o = _expanded(cfg, lp, q_nope, q_pe, row, seq_lens)
        return o, jax.lax.dynamic_update_index_in_dim(rows, row, i, 0)

    rows0 = jnp.zeros((cfg.num_hidden_layers, T, cfg.row_width), cfg.dtype)
    x, rows, counts = _stack(cfg, params, x, rows0, cache["moe_counts"],
                             mix, jnp.arange(T) < n)
    logits = _head(params, x[n - 1][None])
    latent = jax.lax.dynamic_update_slice(
        cache["latent"], rows[:, None].astype(cache["latent"].dtype),
        (0, slot, 0, 0))
    return logits, {"latent": latent, "len": cache["len"].at[slot].set(n),
                    "moe_counts": counts}


def decode_step(params: dict, tokens: jnp.ndarray, cache: dict,
                cfg: DeepseekV3Config, mesh=None
                ) -> tuple[jnp.ndarray, dict]:
    """One token per row: tokens [B] -> (logits [B, V], updated cache).
    Each layer writes every row's new latent row at its own ``len``, then
    attends to it; a row at capacity keeps decoding garbage, its write
    falls outside and is dropped, its length stays at the end."""
    del mesh
    b = tokens.shape[0]
    pos = cache["len"]
    S_max = cache["latent"].shape[2]
    kv_len = jnp.minimum(pos + 1, S_max)
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = _tables(cfg, pos)
    rows = jnp.arange(b)

    def mix(lp, h, latent, i):
        q_nope, q_pe, row = _mla_inputs(cfg, lp, h, cos, sin)
        latent = latent.at[i, rows, pos].set(row.astype(latent.dtype))
        return _absorbed(cfg, lp, q_nope, q_pe, latent, i, kv_len), latent

    x, latent, counts = _stack(cfg, params, x, cache["latent"],
                               cache["moe_counts"], mix, jnp.ones((b,), bool))
    return _head(params, x), {"latent": latent, "len": kv_len,
                              "moe_counts": counts}
