"""Llama-3-family decoder — the flagship serving model (BASELINE.md #4).

Green-field for this framework (the reference nidhey27/gofr has no ML at
all, SURVEY §2.10); designed TPU-first rather than ported:

- layers are STACKED (leading [n_layers] axis on every weight) and the
  forward pass is one ``lax.scan`` — one XLA layer body compiled once, not
  n_layers inlined copies (compile time and code size stay flat as the
  model deepens).
- weights are bf16 and land on the mesh via declarative regex sharding
  rules (gofr_tpu.parallel.specs_from_rules): Megatron-style TP — qkv/gate/up
  column-sharded on ``tp``, wo/down row-sharded — so each layer needs one
  psum, inserted by GSPMD, riding ICI.
- activations carry ``P("dp", "sp", None)``: batch on data-parallel, sequence
  on sequence-parallel. Attention itself sees the full sequence (XLA
  all-gathers around it); ring attention over ``sp`` lives in
  gofr_tpu.parallel.ring for the long-context path.
- KV cache is a padded [L, B, S_max, KV, D] ring per layer with per-row
  valid lengths, written with batched ``.at[rows, pos]`` scatters so
  continuous batching can decode rows at different positions in one step.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import (
    apply_rope,
    attention,
    cached_decode_attention,
    dequantize_kv,
    dequantize_kv4,
    flash_attention,
    quantize_kv,
    quantize_kv4,
    repeat_kv,
    rms_norm,
    rope_table,
)
from ..parallel import P, constrain

__all__ = ["LlamaConfig", "Llama", "llama3_8b", "tiny_llama"]


class LlamaConfig:
    def __init__(
        self,
        vocab_size: int = 128_256,
        dim: int = 4096,
        n_layers: int = 32,
        n_heads: int = 32,
        n_kv_heads: int = 8,
        ffn_dim: int = 14_336,
        max_seq_len: int = 8192,
        rope_theta: float = 500_000.0,
        norm_eps: float = 1e-5,
        dtype: Any = jnp.bfloat16,
        use_flash: bool = True,
        remat: bool = False,
        attn_impl: str = "auto",
        kv_quant: bool = False,
        kv_bits: int | None = None,
        w8: bool = False,
        rope_scaling: dict | None = None,
    ) -> None:
        self.vocab_size = vocab_size
        self.dim = dim
        self.n_layers = n_layers
        self.n_heads = n_heads
        self.n_kv_heads = n_kv_heads
        self.head_dim = dim // n_heads
        self.ffn_dim = ffn_dim
        self.max_seq_len = max_seq_len
        self.rope_theta = rope_theta
        # HF rope_scaling dict (llama3 / linear) — Llama-3.1+ checkpoints
        # require it for correct long-context rotations (ops.scale_rope_freqs)
        self.rope_scaling = rope_scaling
        self.norm_eps = norm_eps
        self.dtype = dtype
        self.use_flash = use_flash
        self.remat = remat
        # "auto" (single-device flash/dense), "ring" or "ulysses": sequence-
        # parallel attention over the sp mesh axis — the long-context path.
        # Selecting one requires passing ``mesh=`` to forward/prefill/
        # decode_step (the Generator does this when built with a mesh).
        if attn_impl not in ("auto", "ring", "ulysses"):
            raise ValueError(f"unknown attn_impl {attn_impl!r}")
        self.attn_impl = attn_impl
        # int8 KV cache (ops.quantize_kv): halves decode's KV HBM traffic —
        # the serving roofline at large slot counts. Composes with
        # sequence-parallel decode: each sp shard dequantizes its own
        # int8 slice before the pmax/psum combine (parallel/ring.py).
        # ``kv_bits`` selects the precision below fp: 8 (default when
        # kv_quant, symmetric per-vector int8) or 4 (asymmetric per-vector
        # int4, two codes packed per byte — ops.quantize_kv4). 16 means
        # the fp cache; setting 4 or 8 implies kv_quant. int4 is a
        # paged-cache precision (the Generator enforces page_size > 0).
        if kv_bits is None:
            kv_bits = 8 if kv_quant else 16
        kv_bits = int(kv_bits)
        if kv_bits not in (4, 8, 16):
            raise ValueError(f"kv_bits must be 4, 8 or 16, got {kv_bits}")
        if kv_bits == 16 and kv_quant:
            raise ValueError("kv_quant=True contradicts kv_bits=16")
        if kv_bits == 4 and self.head_dim % 2:
            raise ValueError(
                f"int4 packing needs an even head_dim, got {self.head_dim}")
        self.kv_bits = kv_bits
        self.kv_quant = kv_bits < 16
        # int8 weights (quantize_weights): halves the OTHER half of
        # decode's HBM traffic — the per-step weight sweep
        self.w8 = w8

    @property
    def sequence_parallel(self) -> bool:
        return self.attn_impl in ("ring", "ulysses")

    @property
    def n_rep(self) -> int:
        return self.n_heads // self.n_kv_heads


def llama3_8b(**kw) -> LlamaConfig:
    return LlamaConfig(**kw)


def params_from_config(cfg: "LlamaConfig", seed: int = 0,
                       checkpoint_dir: str | None = None) -> dict:
    """Init or restore params honoring the config's serving knobs — the
    one place that consumes ``cfg.w8`` and ``LLAMA_CKPT``, so every boot
    path (examples, multi-host workers) serves the same way.

    ``LLAMA_CKPT=<dir>`` (or ``checkpoint_dir``) restores real weights
    instead of random init. Two layouts are auto-detected:

    - a **HuggingFace model directory** (config.json + *.safetensors):
      imported via ml/hf_import (from-scratch safetensors parser,
      projections transposed, layers stacked);
    - an **orbax run**: the latest step, either a bare params tree or a
      training state whose ``"params"`` entry matches.

    Quantization (``w8``) applies AFTER restore — checkpoints store fp
    weights.
    """
    import os as _os

    checkpoint_dir = checkpoint_dir or _os.environ.get("LLAMA_CKPT")
    from ..ml.hf_import import import_hf_llama, is_hf_dir

    if checkpoint_dir and is_hf_dir(checkpoint_dir):
        _, params = import_hf_llama(checkpoint_dir, cfg)
        if cfg.w8:
            params = quantize_weights(params)
        return params
    params = init_params(cfg, jax.random.PRNGKey(seed))
    if checkpoint_dir:
        from ..ml.checkpoint import Checkpointer

        ckpt = Checkpointer(checkpoint_dir)
        try:
            try:
                params = ckpt.restore(like=params)
            except Exception:
                # training states save {"params": ..., "opt_state": ...}
                restored = ckpt.restore()
                if not (isinstance(restored, dict) and "params" in restored):
                    raise
                params = jax.tree.map(
                    lambda leaf, ref: jnp.asarray(leaf, ref.dtype),
                    restored["params"], params)
        finally:
            ckpt.close()
    if cfg.w8:
        params = quantize_weights(params)
    return params


def kv_bits_from_env() -> int | None:
    """``GOFR_ML_KV_BITS`` → 4 | 8 | 16, or None when unset. Malformed
    values fail loudly at construction (the PR-6 drain/replicas pattern)
    instead of silently serving at the wrong precision."""
    import os

    raw = os.environ.get("GOFR_ML_KV_BITS", "").strip()
    if not raw:
        return None
    try:
        bits = int(raw)
    except ValueError:
        raise ValueError(
            f"GOFR_ML_KV_BITS must be 4, 8 or 16, got {raw!r}") from None
    if bits not in (4, 8, 16):
        raise ValueError(f"GOFR_ML_KV_BITS must be 4, 8 or 16, got {bits}")
    return bits


def config_from_env(tiny_vocab_size: int | None = None) -> LlamaConfig:
    """The examples' shared boot path: LLAMA_PRESET=tiny|1b|8b selects the
    config (tiny disables the flash kernel and can adopt a tokenizer's
    vocab so decoded text is always valid), LLAMA_KV_QUANT=1 turns on the
    int8 cache, GOFR_ML_KV_BITS=4|8|16 selects the KV precision directly
    (4 = packed int4 pages, overrides LLAMA_KV_QUANT), LLAMA_W8=1 turns
    on int8 weights (pair with params_from_config, which applies the
    quantization). Centralized so the llama/openai servers can't drift."""
    import os

    preset = os.environ.get("LLAMA_PRESET", "tiny")
    kv_quant = os.environ.get("LLAMA_KV_QUANT") == "1"
    kv_bits = kv_bits_from_env()  # validated loudly; None = unset
    if kv_bits is not None:
        kv_quant = kv_bits < 16
    elif kv_quant:
        kv_bits = 8
    w8 = os.environ.get("LLAMA_W8") == "1"
    ckpt = os.environ.get("LLAMA_CKPT")
    # LLAMA_DTYPE=bf16|f32: activation/weight dtype override. f32 is the
    # bit-identity dtype — bf16 rounding can flip a near-tie argmax
    # between two program SHAPES computing the same math (e.g. a spec
    # verify window vs a plain decode step), which is numeric noise, not
    # a serving bug; tests assert cross-arm token identity under f32
    raw_dtype = os.environ.get("LLAMA_DTYPE", "").strip().lower()
    dtype_kw: dict = {}
    if raw_dtype:
        names = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                 "f32": jnp.float32, "float32": jnp.float32}
        if raw_dtype not in names:
            raise ValueError(
                f"LLAMA_DTYPE must be one of {sorted(names)}, "
                f"got {raw_dtype!r}")
        dtype_kw["dtype"] = names[raw_dtype]
    from ..ml.hf_import import hf_config, is_hf_dir

    if ckpt and is_hf_dir(ckpt):
        # a HF checkpoint defines its own architecture: the preset only
        # contributes serving knobs
        return hf_config(ckpt, kv_quant=kv_quant, kv_bits=kv_bits, w8=w8,
                         **dtype_kw)
    if preset == "tiny":
        kw = {"use_flash": False, "kv_quant": kv_quant, "kv_bits": kv_bits,
              "w8": w8, **dtype_kw}
        if tiny_vocab_size is not None:
            kw["vocab_size"] = tiny_vocab_size
        return tiny_llama(**kw)
    if preset == "1b":
        return LlamaConfig(
            vocab_size=32_128, dim=2048, n_layers=16, n_heads=16,
            n_kv_heads=8, ffn_dim=8192, max_seq_len=2048, kv_quant=kv_quant,
            kv_bits=kv_bits, w8=w8, **dtype_kw,
        )
    if preset == "8b":
        return llama3_8b(kv_quant=kv_quant, kv_bits=kv_bits, w8=w8,
                         **dtype_kw)
    raise ValueError(f"unknown LLAMA_PRESET {preset!r}")


def draft_from_env(target_cfg: "LlamaConfig", target_params=None) -> tuple:
    """(draft_params, draft_cfg) for speculative decoding, from env — or
    (None, None) when no draft is configured.

    ``LLM_DRAFT_CKPT=<hf dir>`` loads a real shared-vocab draft checkpoint
    (e.g. a 1B draft for an 8B target); ``LLM_DRAFT_PRESET=tiny|1b``
    builds a random-weight draft of that shape (demo/testing — a random
    draft keeps outputs lossless, it just accepts ~nothing);
    ``LLM_DRAFT_PRESET=self`` reuses the target weights as the draft —
    the acceptance upper bound for the draft-model machinery (a real
    small checkpoint slots in via LLM_DRAFT_CKPT).
    """
    import os

    ckpt = os.environ.get("LLM_DRAFT_CKPT")
    preset = os.environ.get("LLM_DRAFT_PRESET")
    if not ckpt and not preset:
        return None, None
    from ..ml.hf_import import hf_config, is_hf_dir

    if preset == "self" and not ckpt:
        if target_params is None:
            raise ValueError("LLM_DRAFT_PRESET=self needs target params")
        # the draft path keeps its own fp dense cache, so clone the config
        # with quant/paging knobs off
        dcfg = LlamaConfig(
            vocab_size=target_cfg.vocab_size, dim=target_cfg.dim,
            n_layers=target_cfg.n_layers, n_heads=target_cfg.n_heads,
            n_kv_heads=target_cfg.n_kv_heads, ffn_dim=target_cfg.ffn_dim,
            max_seq_len=target_cfg.max_seq_len,
            rope_theta=target_cfg.rope_theta, norm_eps=target_cfg.norm_eps,
            dtype=target_cfg.dtype, use_flash=target_cfg.use_flash,
            w8=target_cfg.w8, rope_scaling=target_cfg.rope_scaling)
        return target_params, dcfg
    if ckpt:
        if not is_hf_dir(ckpt):
            # fail loudly: silently substituting a random draft would make
            # serving strictly SLOWER (~0% acceptance) with no signal
            raise ValueError(
                f"LLM_DRAFT_CKPT={ckpt!r} is not a HF model directory "
                "(config.json + *.safetensors)")
        dcfg = hf_config(ckpt)
        dparams = params_from_config(dcfg, checkpoint_dir=ckpt)
    else:
        if preset == "1b":
            dcfg = LlamaConfig(
                vocab_size=target_cfg.vocab_size, dim=2048, n_layers=16,
                n_heads=16, n_kv_heads=8, ffn_dim=8192,
                max_seq_len=target_cfg.max_seq_len)
        else:
            dcfg = tiny_llama(use_flash=False,
                              vocab_size=target_cfg.vocab_size)
        dparams = init_params(dcfg, jax.random.PRNGKey(1))
    if dcfg.vocab_size != target_cfg.vocab_size:
        raise ValueError(
            f"draft vocab {dcfg.vocab_size} != target "
            f"{target_cfg.vocab_size}: speculation needs a shared vocab")
    return dparams, dcfg


def tiny_llama(**kw) -> LlamaConfig:
    """Test-scale config: same topology, toy widths (divisible by tp=4)."""
    defaults = dict(
        vocab_size=512, dim=128, n_layers=2, n_heads=8, n_kv_heads=4,
        ffn_dim=256, max_seq_len=128, rope_theta=10_000.0,
    )
    defaults.update(kw)
    return LlamaConfig(**defaults)


# Megatron-style TP over the canonical mesh. Leading axis of every layer
# weight is the stacked n_layers axis (never sharded). The ``/s`` rules
# (first match wins) cover int8-quantized weights' per-out-channel scales:
# column-parallel outputs shard the scale over tp, row-parallel outputs
# are full-width so their scales replicate.
SHARDING_RULES = (
    (r"layers/(wq|wk|wv|w_gate|w_up)/s", P(None, "tp")),
    (r"layers/(wo|w_down)/s", P(None, None)),
    (r"lm_head/s", P("tp")),
    (r"layers/(wq|wk|wv|w_gate|w_up)", P(None, None, "tp")),  # column parallel
    (r"layers/(wo|w_down)", P(None, "tp", None)),             # row parallel
    (r"layers/(attn_norm|mlp_norm)", P(None)),
    (r"embed", P(None, None)),
    (r"lm_head", P(None, "tp")),                              # vocab sharded
    (r"final_norm", P(None)),
)

# FSDP variant: weights additionally sharded over the fsdp axis (ZeRO-3
# style — GSPMD all-gathers each layer's weights just-in-time inside the
# scan and reduce-scatters its grads). Combine with tp for 2D sharding.
# The /s rules keep a quantized (serving-only) tree shardable here too.
SHARDING_RULES_FSDP = (
    (r"layers/(wq|wk|wv|w_gate|w_up)/s", P(None, "tp")),
    (r"layers/(wo|w_down)/s", P(None, "fsdp")),
    (r"lm_head/s", P("tp")),
    (r"layers/(wq|wk|wv|w_gate|w_up)", P(None, "fsdp", "tp")),
    (r"layers/(wo|w_down)", P(None, "tp", "fsdp")),
    (r"layers/(attn_norm|mlp_norm)", P(None)),
    (r"embed", P("fsdp", None)),
    (r"lm_head", P("fsdp", "tp")),
    (r"final_norm", P(None)),
)

# KV cache [L, B, S, KV, D]: batch on dp, kv heads on tp.
CACHE_SPEC = P(None, "dp", None, "tp", None)


def init_params(cfg: LlamaConfig, key) -> dict:
    """bf16 weights, truncated-normal-ish scaled init; stacked layer axis."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    L, D, H, KV, hd, F = (cfg.n_layers, cfg.dim, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim, cfg.ffn_dim)

    def dense(key, *shape, fan_in):
        # draw and cast in one program: eagerly, the f32 draw (3.8 GB for
        # one FFN stack at 8B widths) is resident beside the bf16 result
        return jax.jit(
            lambda k: (jax.random.normal(k, shape, jnp.float32)
                       * (fan_in ** -0.5)).astype(cfg.dtype))(key)

    ks = jax.random.split(k_layers, 7)
    return {
        "embed": dense(k_embed, cfg.vocab_size, D, fan_in=D),
        "layers": {
            "attn_norm": jnp.ones((L, D), jnp.float32),
            "mlp_norm": jnp.ones((L, D), jnp.float32),
            "wq": dense(ks[0], L, D, H * hd, fan_in=D),
            "wk": dense(ks[1], L, D, KV * hd, fan_in=D),
            "wv": dense(ks[2], L, D, KV * hd, fan_in=D),
            "wo": dense(ks[3], L, H * hd, D, fan_in=H * hd),
            "w_gate": dense(ks[4], L, D, F, fan_in=D),
            "w_up": dense(ks[5], L, D, F, fan_in=D),
            "w_down": dense(ks[6], L, F, D, fan_in=F),
        },
        "final_norm": jnp.ones((D,), jnp.float32),
        "lm_head": dense(k_head, D, cfg.vocab_size, fan_in=D),
    }


_QUANT_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_weights(params: dict) -> dict:
    """Serving-time int8 weight quantization (w8a16, LLAMA_W8=1).

    Every layer matmul weight and the lm_head become {"q": int8,
    "s": f32 per-out-channel} (ops.quantize_weight); norms and the embed
    gather stay fp. Decode at large slot counts is weight-bandwidth-bound,
    so halving weight bytes per step is a direct throughput lever —
    composes with the int8 KV cache (kv_quant), which covers the other
    half of decode's HBM traffic. Quantized params are serving-only (not
    trainable; checkpoints should store the fp weights).
    """
    from ..ops import quantize_weight

    out = dict(params)
    layers = dict(params["layers"])
    for name in _QUANT_KEYS:
        q, s = quantize_weight(layers[name])
        layers[name] = {"q": q, "s": s}
    out["layers"] = layers
    q, s = quantize_weight(params["lm_head"])
    out["lm_head"] = {"q": q, "s": s}
    return out


def _mm(x, w):
    """x @ w for plain or int8-quantized ({"q": int8, "s": f32}) weights.

    The per-output-channel scale commutes out of the contraction, so HBM
    streams the int8 tensor and the widening convert fuses into the MXU
    operand read (ops.quantize_weight). Serving-only: quantized params
    are not trainable.
    """
    if isinstance(w, dict):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _swiglu(x, lp):
    g = jax.nn.silu(_mm(x, lp["w_gate"]))
    return _mm(g * _mm(x, lp["w_up"]), lp["w_down"])


# Where the block's activations are pinned when a mesh is in context
# (``constrain`` is a no-op without one): heads on tp, batch on dp,
# sequence on sp. Programs that never ran on a mesh pass neither.
_QK_SPEC = P("dp", None, "tp", None)
_ACT_SPEC = P("dp", "sp", None)


def _block(cfg: LlamaConfig, x, lp, cos, sin, attend, *, qk_spec=None,
           out_spec=None):
    """THE decoder block, for any ``x`` [b, s, D]: norm → q/k/v → rope →
    ``attend`` → ``wo`` → residual → SwiGLU. ``cos`` None leaves the rope
    out (a family whose attention carries no positional term).

    ``attend(q, k, v) -> (o, kept)`` is the only thing a program supplies:
    it writes the roped K and V where its layout keeps them (nowhere, a
    dense cache row, a segment, pages, a shard's pages) and returns the
    attention output [b, s, H, hd] (or flat) plus whatever the layer scan
    must carry on — the updated cache arrays, or this layer's (k, v).
    Returns (x, kept)."""
    b, s, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    pin = lambda a, spec: a if spec is None else constrain(a, spec)

    with jax.named_scope("attention"):
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q = _mm(h, lp["wq"]).reshape(b, s, H, hd)
        k = _mm(h, lp["wk"]).reshape(b, s, KV, hd)
        v = _mm(h, lp["wv"]).reshape(b, s, KV, hd)
        q = pin(q, qk_spec)
        k = pin(k, qk_spec)
        if cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        o, kept = attend(q, k, v)
        x = x + pin(_mm(o.reshape(b, s, H * hd), lp["wo"]), out_spec)
    with jax.named_scope("mlp"):
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        x = x + pin(_swiglu(h, lp), out_spec)
    return x, kept


def _head(params: dict, cfg: LlamaConfig, x, pick):
    """Final norm, then the ``lm_head`` projection of the rows ``pick``
    selects from [b, s, D] (project only what is sampled) -> f32 logits."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return _mm(pick(x), params["lm_head"]).astype(jnp.float32)


def _full_sequence(params: dict, tokens, cfg: LlamaConfig, seq_lens, mesh,
                   pick, *, keep_kv: bool, remat: bool = False):
    """Embed ``tokens`` [B, S], run every block over the whole sequence
    and project ``pick``'s rows: (logits, stacked per-layer (k, v) or
    None). ``forward`` and ``prefill`` are this with and without the K/V
    kept; nothing but ``x`` rides the scan's carry."""
    x = params["embed"][tokens].astype(cfg.dtype)
    x = constrain(x, _ACT_SPEC)
    positions = jnp.arange(tokens.shape[1])[None, :]
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta,
                          scaling=cfg.rope_scaling)

    def attend(q, k, v):
        kf, vf = repeat_kv(k, cfg.n_rep), repeat_kv(v, cfg.n_rep)
        if cfg.sequence_parallel and mesh is not None:
            # long-context: exact sequence-parallel attention over sp — K/V
            # blocks never leave their shard (ring) or reshard once (ulysses)
            from ..parallel.ring import ring_attention
            from ..parallel.ulysses import ulysses_attention

            sp_attn = (ring_attention if cfg.attn_impl == "ring"
                       else ulysses_attention)
            o = sp_attn(q, kf, vf, mesh, kv_len=seq_lens, causal=True)
        elif cfg.use_flash:
            o = flash_attention(q, kf, vf, causal=True, kv_len=seq_lens)
        else:
            o = attention(q, kf, vf, causal=True, kv_len=seq_lens)
        return o, ((k, v) if keep_kv else None)

    def body(x, lp):
        return _block(cfg, x, lp, cos, sin, attend, qk_spec=_QK_SPEC,
                      out_spec=_ACT_SPEC)

    if remat:
        # recompute layer activations in the backward pass: HBM footprint
        # stays O(1) in depth for long-sequence training
        body = jax.checkpoint(body)
    x, kv = jax.lax.scan(body, x, params["layers"])
    return _head(params, cfg, x, pick), kv


def forward(params: dict, tokens: jnp.ndarray, cfg: LlamaConfig,
            *, seq_lens: jnp.ndarray | None = None, mesh=None) -> jnp.ndarray:
    """Full-sequence forward: tokens [B, S] -> f32 logits [B, S, V].

    Used for training and for prefill-without-cache; ``seq_lens`` masks
    padded tail positions out of attention.
    """
    logits, _ = _full_sequence(params, tokens, cfg, seq_lens, mesh,
                               lambda x: x, keep_kv=False, remat=cfg.remat)
    return constrain(logits, _ACT_SPEC)


# -- KV-cache serving path ----------------------------------------------------

def kv_plane_names(cfg: LlamaConfig) -> tuple[str, ...]:
    """Per-vector side planes riding next to the quantized values —
    ``scale`` for symmetric int8, ``scale`` + ``zero`` for asymmetric
    int4. Cache keys are ``k_<plane>`` / ``v_<plane>``, always shaped
    sequence-minor ([..., KV, S] / [..., KV, page_s])."""
    return ("scale", "zero") if cfg.kv_bits == 4 else ("scale",)


def kv_store_width(cfg: LlamaConfig) -> int:
    """Stored bytes-axis width of ONE kv vector: ``head_dim`` int8 codes,
    or ``head_dim / 2`` packed int4 bytes."""
    return cfg.head_dim // 2 if cfg.kv_bits == 4 else cfg.head_dim


def kv_encode(cfg: LlamaConfig, x: jnp.ndarray):
    """Quantize [..., KV, hd] at the config's precision. Returns
    (values [..., KV, kv_store_width], {plane: [..., KV]})."""
    if cfg.kv_bits == 4:
        q, sc, zp = quantize_kv4(x)
        return q, {"scale": sc, "zero": zp}
    q, sc = quantize_kv(x)
    return q, {"scale": sc}


def kv_decode(cfg: LlamaConfig, q: jnp.ndarray, planes: dict,
              dtype=None) -> jnp.ndarray:
    """Dequantize values [..., KV, kv_store_width] with their planes back
    to [..., KV, hd] — the inverse of ``kv_encode``."""
    dtype = dtype or cfg.dtype
    if cfg.kv_bits == 4:
        return dequantize_kv4(q, planes["scale"], planes["zero"], dtype)
    return dequantize_kv(q, planes["scale"], dtype)


def _kv_value_dtype(cfg: LlamaConfig):
    return jnp.uint8 if cfg.kv_bits == 4 else jnp.int8


def init_cache(cfg: LlamaConfig, batch: int, max_seq: int | None = None) -> dict:
    S = max_seq or cfg.max_seq_len
    shape = (cfg.n_layers, batch, S, cfg.n_kv_heads, cfg.head_dim)
    if cfg.kv_quant:
        # quantized values are stored FLAT, [L, B, S, KV*W]: int8's VMEM
        # tile is (32, 128), so a [block_s, KV, D] slab with KV=8 sublanes
        # pads 4x (which made int8 SLOWER than bf16); the flat
        # [block_s, KV*W] slab tiles perfectly (W = head_dim, halved for
        # packed int4). Scale/zero planes are [L, B, KV, S] (seq minor) so
        # their [KV, block_s] DMA slices stay 128-aligned too.
        flat = (cfg.n_layers, batch, S, cfg.n_kv_heads * kv_store_width(cfg))
        scale_shape = (cfg.n_layers, batch, cfg.n_kv_heads, S)
        cache = {
            "k": jnp.zeros(flat, _kv_value_dtype(cfg)),
            "v": jnp.zeros(flat, _kv_value_dtype(cfg)),
        }
        for pl in kv_plane_names(cfg):
            cache[f"k_{pl}"] = jnp.zeros(scale_shape, jnp.bfloat16)
            cache[f"v_{pl}"] = jnp.zeros(scale_shape, jnp.bfloat16)
        cache["len"] = jnp.zeros((batch,), jnp.int32)
        return cache
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def _planes(cache: dict) -> dict:
    """The cache's arrays without its ``len``: what rides the layer scan."""
    return {key: cache[key] for key in cache if key != "len"}


def _scan_blocks(params: dict, cfg: LlamaConfig, x, cos, sin, arrays: dict,
                 attend_at, pick, **pins):
    """The frame every cached program shares: blocks over
    ``params["layers"]``, final norm, ``lm_head`` of ``pick``'s rows.
    Returns (logits, arrays).

    Weights stream through the scan's xs; the FULL cache ``arrays`` ride
    its CARRY beside a layer counter, so XLA aliases them in place. A
    first version returned per-layer caches through scan ys, which
    restacked (= copied) the entire multi-GB cache every token — that
    copy, not attention, was the first decode bottleneck.
    ``attend_at(arrays, layer)`` gives the block's ``attend`` for one
    layer; the ``kept`` it returns is the updated ``arrays``."""
    def body(carry, lp):
        x, arrays, layer = carry
        x, arrays = _block(cfg, x, lp, cos, sin, attend_at(arrays, layer),
                           **pins)
        return (x, arrays, layer + 1), None

    (x, arrays, _), _ = jax.lax.scan(
        body, (x, arrays, jnp.int32(0)), params["layers"])
    return _head(params, cfg, x, pick), arrays


def _store_kv(cfg: LlamaConfig, arrays: dict, layer, at, k, v, *,
              drop: int | None = None, mode=None) -> dict:
    """Write the block's ``k``/``v`` [b, s, KV, hd] into layer ``layer``
    of a cache at ``at = (i, j)``: (slot row, position) of the dense
    cache, (page, offset) of the pool — the two layouts keep the same
    planes (init_cache, init_paged_cache). ``i`` and ``j`` are [b, s], or
    lack the axis ``drop`` of size one (decode's s, one slot's b).
    Quantised values scatter FLAT ([..., KV*W] rows); their scale/zero
    planes are sequence-minor, so each vector's [KV] entries scatter at
    (i, :, j) through full advanced indexing."""
    i, j = at
    cells = lambda a: a if drop is None else a[(slice(None),) * drop + (0,)]
    if not cfg.kv_quant:
        dt = arrays["k"].dtype
        return {
            "k": arrays["k"].at[layer, i, j].set(cells(k).astype(dt),
                                                 mode=mode),
            "v": arrays["v"].at[layer, i, j].set(cells(v).astype(dt),
                                                 mode=mode)}
    kq, k_pl = kv_encode(cfg, cells(k))
    vq, v_pl = kv_encode(cfg, cells(v))
    kv_i = jnp.arange(cfg.n_kv_heads)[(None,) * j.ndim]
    flat = lambda q: q.reshape(*q.shape[:-2], -1)
    arrays = dict(arrays)
    arrays["k"] = arrays["k"].at[layer, i, j].set(flat(kq), mode=mode)
    arrays["v"] = arrays["v"].at[layer, i, j].set(flat(vq), mode=mode)
    for name, planes in (("k", k_pl), ("v", v_pl)):
        for pl, val in planes.items():
            key = f"{name}_{pl}"
            arrays[key] = arrays[key].at[
                layer, i[..., None], kv_i, j[..., None]].set(val, mode=mode)
    return arrays


def _gather_pages(cfg: LlamaConfig, arrays: dict, layer, table):
    """(k, v) [B, P*page_s, KV, hd] of the virtual sequences that
    ``table`` ([B, P], or one row [P] -> B = 1) maps onto layer ``layer``
    of the pool, dequantised to ``cfg.dtype`` where the pages are int8 or
    int4. What every paged reader without a kernel attends over."""
    n = 1 if table.ndim == 1 else table.shape[0]
    KV = cfg.n_kv_heads
    lyr = {key: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
           for key, a in arrays.items()}

    def virt(name):
        vals = jnp.take(lyr[name], table, axis=0)       # [B, P, ps, ...]
        if not cfg.kv_quant:
            return vals.reshape(n, -1, KV, cfg.head_dim)
        planes = {
            pl: jnp.swapaxes(jnp.take(lyr[f"{name}_{pl}"], table, axis=0),
                             -1, -2).reshape(n, -1, KV)  # [B, P, KV, ps] ->
            for pl in kv_plane_names(cfg)}
        return kv_decode(cfg, vals.reshape(n, -1, KV, kv_store_width(cfg)),
                         planes, cfg.dtype)

    return virt("k"), virt("v")


def prefill(params: dict, tokens: jnp.ndarray, seq_lens: jnp.ndarray,
            cfg: LlamaConfig, cache: dict, mesh=None
            ) -> tuple[jnp.ndarray, dict]:
    """Run the prompt [B, S_pad] through the model, filling the cache.

    Returns (last-token logits [B, V], cache). S_pad is a shape bucket;
    ``seq_lens`` gives each row's true prompt length.
    """
    b, s = tokens.shape
    # gather each row's last valid position, then project only that row
    logits, (ks, vs) = _full_sequence(
        params, tokens, cfg, seq_lens, mesh,
        lambda x: x[jnp.arange(b), seq_lens - 1], keep_kv=True)

    S_max = cache["k"].shape[2]
    pad = S_max - s
    if pad < 0:
        raise ValueError(f"prompt bucket {s} exceeds cache length {S_max}")
    widen = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    if cfg.kv_quant:
        # quantized values flatten [L, B, S, KV, W] -> [L, B, S, KV*W];
        # scale/zero planes go [L, B, S, KV] -> [L, B, KV, S] (layouts:
        # see init_cache)
        L, B = ks.shape[0], ks.shape[1]
        widen_q = lambda a: jnp.pad(a.reshape(L, B, s, -1),
                                    ((0, 0), (0, 0), (0, pad), (0, 0)))
        widen_s = lambda a: jnp.pad(a.transpose(0, 1, 3, 2),
                                    ((0, 0), (0, 0), (0, 0), (0, pad)))
        kq, k_pl = kv_encode(cfg, ks)
        vq, v_pl = kv_encode(cfg, vs)
        cache = {"k": widen_q(kq), "v": widen_q(vq),
                 "len": seq_lens.astype(jnp.int32)}
        for pl in kv_plane_names(cfg):
            cache[f"k_{pl}"] = widen_s(k_pl[pl])
            cache[f"v_{pl}"] = widen_s(v_pl[pl])
    else:
        cache = {"k": widen(ks), "v": widen(vs),
                 "len": seq_lens.astype(jnp.int32)}
    return logits, cache


def prefill_into(params: dict, tokens: jnp.ndarray, seq_lens: jnp.ndarray,
                 cfg: LlamaConfig, cache: dict, slot: jnp.ndarray, mesh=None
                 ) -> tuple[jnp.ndarray, dict]:
    """Prefill ONE prompt [1, S_pad] directly into row ``slot`` of a shared
    multi-slot cache. One jitted program per request (donate the cache!):
    the eager pad + scatter of the two-step prefill would copy the whole
    cache through HBM outside XLA's control.
    """
    logits, filled = prefill(params, tokens, seq_lens, cfg,
                             init_cache(cfg, 1, cache["k"].shape[2]),
                             mesh=mesh)
    new_cache = {
        key: jax.lax.dynamic_update_index_in_dim(
            cache[key], filled[key][:, 0], slot, axis=1)
        for key in cache
        if key != "len"
    }
    new_cache["len"] = cache["len"].at[slot].set(seq_lens[0])
    return logits, new_cache


def prefill_segment_into(params: dict, tokens: jnp.ndarray,
                         seg_len: jnp.ndarray, cfg: LlamaConfig,
                         cache: dict, slot: jnp.ndarray, start: jnp.ndarray,
                         new_len: jnp.ndarray, mesh=None
                         ) -> tuple[jnp.ndarray, dict]:
    """CHUNKED prefill: one segment [1, C] of a longer prompt into row
    ``slot`` of the shared cache at positions start..start+C-1, attending
    the slot's already-prefilled rows plus the segment (causal). A long
    prompt becomes several of these interleaved with decode chunks, so a
    2k-token prefill can no longer stall every live stream for its whole
    duration (the TTFT-jitter fix).

    Returns (logits of the segment's LAST VALID token [1, V], cache).
    ``new_len`` lands in cache["len"][slot]: pass the cache CAPACITY for
    non-final segments — interleaved decode chunks then scatter this
    row's garbage writes out of bounds (dropped) instead of corrupting
    prefilled positions — and the true prompt length on the final
    segment. Composes with the int8 cache (kv_quant)."""
    if cfg.kv_bits == 4:
        raise ValueError("int4 KV is a paged-cache precision — use "
                         "page_size > 0 (paged_suffix_prefill)")
    _, c = tokens.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    positions = start + jnp.arange(c)[None, :]            # [1, C]
    x = params["embed"][tokens].astype(cfg.dtype)         # [1, C, D]
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    valid_to = start + seg_len[0]                         # rows < this attend

    def attend_at(arrays, layer):
        # the segment is contiguous in one cache row: slab updates and one
        # row's slice, where decode scatters and reads every row
        s_max = arrays["k"].shape[2]

        def attend(q, k, v):
            if cfg.kv_quant:
                kq, k_sc = quantize_kv(k[0])  # [C, KV, hd] -> sc [C, KV]
                vq, v_sc = quantize_kv(v[0])
                upd_q = lambda a, w: jax.lax.dynamic_update_slice(
                    a, w.reshape(1, 1, c, KV * hd), (layer, slot, start, 0))
                upd_s = lambda a, s_: jax.lax.dynamic_update_slice(
                    a, s_.T[None, None], (layer, slot, jnp.int32(0), start))
                new = {"k": upd_q(arrays["k"], kq),
                       "v": upd_q(arrays["v"], vq),
                       "k_scale": upd_s(arrays["k_scale"], k_sc),
                       "v_scale": upd_s(arrays["v_scale"], v_sc)}
                row = lambda a: jax.lax.dynamic_slice(
                    a, (layer, slot, 0, 0), (1, 1, s_max, KV * hd)
                )[0, 0].reshape(s_max, KV, hd)
                row_s = lambda a: jax.lax.dynamic_slice(
                    a, (layer, slot, 0, 0), (1, 1, KV, s_max))[0, 0]
                deq = lambda name: dequantize_kv(
                    row(new[name]), row_s(new[f"{name}_scale"]).T,
                    cfg.dtype)[None]
                k_row, v_row = deq("k"), deq("v")
            else:
                dt = arrays["k"].dtype
                upd = lambda a, w: jax.lax.dynamic_update_slice(
                    a, w.astype(dt)[:, None], (layer, slot, start, 0, 0))
                new = {"k": upd(arrays["k"], k), "v": upd(arrays["v"], v)}
                row5 = lambda a: jax.lax.dynamic_slice(
                    a, (layer, slot, 0, 0, 0), (1, 1, s_max, KV, hd))[0]
                k_row, v_row = row5(new["k"]), row5(new["v"])
            o = attention(q, repeat_kv(k_row, cfg.n_rep),
                          repeat_kv(v_row, cfg.n_rep), causal=True,
                          q_offset=start, kv_len=valid_to[None])
            return o, new

        return attend

    logits, arrays = _scan_blocks(
        params, cfg, x, cos, sin, _planes(cache), attend_at,
        lambda x: x[0, seg_len[0] - 1][None])
    return logits, {**arrays, "len": cache["len"].at[slot].set(new_len)}


def decode_step(params: dict, tokens: jnp.ndarray, cache: dict,
                cfg: LlamaConfig, mesh=None) -> tuple[jnp.ndarray, dict]:
    """One token per row: tokens [B] -> (logits [B, V], updated cache).

    Rows may sit at different positions (continuous batching); each row
    writes its cache slot at its own ``len`` and attends to len+1 keys —
    the only cache write is the [B, KV, D] scatter of the new token at
    ``[layer, rows, pos]``.
    """
    if cfg.kv_bits == 4:
        raise ValueError("int4 KV is a paged-cache precision — use "
                         "page_size > 0 (paged_decode_step)")
    b = tokens.shape[0]
    pos = cache["len"]  # [B]
    x = params["embed"][tokens][:, None, :].astype(cfg.dtype)
    cos, sin = rope_table(pos[:, None], cfg.head_dim, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    rows = jnp.arange(b)

    def attend_at(arrays, layer):
        # a row that has sat at capacity (its ``len`` is capped at S_max
        # below) must not ask the kernels for S_max + 1 keys: the Pallas
        # decode kernel would fetch a block past the end of the cache
        kv_len = jnp.minimum(pos + 1, arrays["k"].shape[2])

        def attend(q, k, v):
            new = _store_kv(cfg, arrays, layer, (rows, pos), k, v, drop=1)
            scales = ({"k_scale": new["k_scale"], "v_scale": new["v_scale"]}
                      if cfg.kv_quant else {})
            if cfg.sequence_parallel and mesh is not None:
                # S-sharded cache: grouped online-softmax per shard + one
                # pmax/psum combine (parallel/ring.py) — no cache
                # all-gather; each shard dequantises its own int8 slice
                from ..parallel.ring import sp_decode_attention

                o = sp_decode_attention(q, new["k"], new["v"], kv_len, mesh,
                                        layer=layer, **scales)
            else:
                o = cached_decode_attention(q, new["k"], new["v"], kv_len,
                                            layer=layer,
                                            use_kernel=cfg.use_flash,
                                            **scales)
            return o, new

        return attend

    logits, arrays = _scan_blocks(
        params, cfg, x, cos, sin, _planes(cache), attend_at,
        lambda x: x[:, 0], qk_spec=_QK_SPEC, out_spec=_ACT_SPEC)
    # cap len at capacity: rows past the end keep decoding garbage (their
    # cache writes are dropped as out-of-bounds) but never index OOB.
    S_max = cache["k"].shape[2]
    new_len = jnp.minimum(pos + 1, S_max)
    return logits, {**arrays, "len": new_len}


def init_paged_cache(cfg: LlamaConfig, batch: int, n_pages: int,
                     page_s: int) -> dict:
    """Block-paged KV cache: a POOL of pages shared by every slot instead
    of a dense [B, S_max] rectangle per slot.

    Dense caches pin worst-case HBM per slot — a 1024-token budget costs
    the full 1024 rows even for a 40-token chat turn. Here slots map
    virtual positions onto pool pages through a host-owned page table
    ([B, pages_per_slot] int32, passed into each program), so concurrent
    slot count is bounded by ACTUAL tokens, not worst case — the capacity
    lever for long-context serving. Page 0 is reserved as
    scratch: unallocated table entries point at it, over-capacity writes
    land there harmlessly, and kv_len masking keeps reads out.

    kv_quant composes: quantized page values stay FLAT [L, N, ps, KV*W]
    (W = head_dim for int8, head_dim/2 for packed int4) and the per-page
    scale — plus zero, at int4 — planes ride page-shaped [L, N, KV, ps]
    (the same tiling rationale as the dense int8 layout) — the memory
    levers multiply: half (int8) or a quarter (int4) of the value bytes
    per token AND pages shared across slots.
    """
    if cfg.kv_quant:
        flat = (cfg.n_layers, n_pages, page_s,
                cfg.n_kv_heads * kv_store_width(cfg))
        scale_shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_s)
        cache = {
            "k": jnp.zeros(flat, _kv_value_dtype(cfg)),
            "v": jnp.zeros(flat, _kv_value_dtype(cfg)),
        }
        for pl in kv_plane_names(cfg):
            cache[f"k_{pl}"] = jnp.zeros(scale_shape, jnp.bfloat16)
            cache[f"v_{pl}"] = jnp.zeros(scale_shape, jnp.bfloat16)
        cache["len"] = jnp.zeros((batch,), jnp.int32)
        return cache
    shape = (cfg.n_layers, n_pages, page_s, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


def paged_prefill_into(params: dict, tokens: jnp.ndarray,
                       seq_lens: jnp.ndarray, cfg: LlamaConfig, cache: dict,
                       table_row: jnp.ndarray, slot: jnp.ndarray,
                       page_s: int, mesh=None, set_len: bool = True
                       ) -> tuple[jnp.ndarray, dict]:
    """Prefill ONE prompt [1, S_pad] and scatter its kv rows into the
    slot's pages (``table_row`` [S_pad // page_s]). Pages past the prompt
    point at scratch page 0, so whole-page writes never need masking.

    ``mesh`` + a sequence-parallel ``cfg`` (``attn_impl="ring"|"ulysses"``)
    is the long-context SP prefill path: the forward's attention shards
    the prompt over the ``sp`` axis, and — when the pool itself is
    STRIPED across the mesh (generate.py's sp paged layout) — the page
    scatters below write each device's own shard (GSPMD routes each
    page-sized slab to its owner). ``set_len=False`` is the prefix-build
    variant (register_prefix): pages fill, no slot admits."""
    logits, filled = prefill(params, tokens, seq_lens, cfg,
                             init_cache(cfg, 1, tokens.shape[1]),
                             mesh=mesh)
    arrays = {key: cache[key] for key in cache if key != "len"}
    n_pg = tokens.shape[1] // page_s
    for j in range(n_pg):  # static unroll: one page-sized slab per write
        for key in arrays:
            if key.endswith(("_scale", "_zero")):  # planes: [L, B, KV, S]
                slab = filled[key][:, 0, :, j * page_s:(j + 1) * page_s]
            else:                       # values: [L, B, S, ...]
                slab = filled[key][:, 0, j * page_s:(j + 1) * page_s]
            arrays[key] = jax.lax.dynamic_update_index_in_dim(
                arrays[key], slab, table_row[j], axis=1)
    new_len = (cache["len"].at[slot].set(seq_lens[0]) if set_len
               else cache["len"])
    return logits, {**arrays, "len": new_len}


def _page_of(table, positions, page_s: int):
    """(page, offset) of virtual ``positions`` ([S] through one slot's
    row [P]; [B] or [B, W] through ``table`` [B, P]). Positions past
    virtual capacity land in scratch page 0 — the paged analogue of the
    dense path's dropped out-of-bounds scatters — never in a wrapped
    real page."""
    p_max = table.shape[-1]

    def entry():
        rows = () if table.ndim == 1 else (jnp.arange(table.shape[0]).reshape(
            -1, *[1] * (positions.ndim - 1)),)
        return table[(*rows, jnp.minimum(positions // page_s, p_max - 1))]

    page = jnp.where(positions < p_max * page_s, entry(), 0)
    return page, positions % page_s


def paged_suffix_prefill(params: dict, tokens: jnp.ndarray,
                         seq_lens: jnp.ndarray, cfg: LlamaConfig,
                         cache: dict, table_row: jnp.ndarray,
                         start, page_s: int
                         ) -> tuple[jnp.ndarray, dict]:
    """Prefill ONE sequence segment [1, S_pad] at virtual positions
    ``start..start+S_pad-1`` of a paged slot — the engine behind
    shared-prefix serving: the common prefix's kv pages are computed once
    (``start=0``) and every request then prefills only its SUFFIX
    (``start=shared_len``), attending the shared pages through the same
    table. Rows beyond ``seq_lens`` write garbage at positions decode
    will overwrite before any masked read can reach them (the dense
    prefill_into argument). Returns last-valid-token logits [1, V].
    Composes with int8 pages (cfg.kv_quant).
    """
    b, s = tokens.shape
    start = jnp.asarray(start, jnp.int32)
    positions = start + jnp.arange(s)[None, :]            # [1, S_pad]
    at = _page_of(table_row, positions[0], page_s)
    x = params["embed"][tokens].astype(cfg.dtype)
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta,
                          scaling=cfg.rope_scaling)

    def attend_at(arrays, layer):
        def attend(q, k, v):
            new = _store_kv(cfg, arrays, layer, at, k, v, drop=0)
            # this ONE slot's virtual sequence [1, P_max*page_s, KV, hd]
            k_virt, v_virt = _gather_pages(cfg, new, layer, table_row)
            # causal from the segment's absolute offset: suffix token t
            # attends every prefix position plus the window up to itself
            o = attention(q, repeat_kv(k_virt, cfg.n_rep),
                          repeat_kv(v_virt, cfg.n_rep),
                          causal=True, q_offset=start)
            return o, new

        return attend

    logits, arrays = _scan_blocks(
        params, cfg, x, cos, sin, _planes(cache), attend_at,
        lambda x: x[jnp.arange(b), seq_lens - 1])
    return logits, {**arrays, "len": cache["len"]}


def paged_decode_step(params: dict, tokens: jnp.ndarray, cache: dict,
                      table: jnp.ndarray, cfg: LlamaConfig
                      ) -> tuple[jnp.ndarray, dict]:
    """One token per row against the paged pool. ``table`` [B, P_max]
    maps each row's virtual pages (in order, so virtual positions are
    contiguous and kv_len masking is exact). The new token's kv row
    writes at (table[b, pos//page_s], pos % page_s); attention over a
    full-precision pool is ``ops.paged_decode_attention`` (on a TPU a
    kernel that walks the row's live pages), over int8/int4 pages a
    dequantising gather of the row's virtual [P_max * page_s] sequence.
    """
    from ..ops import paged_decode_attention, record_branch

    b = tokens.shape[0]
    page_s = cache["k"].shape[2]
    pos = cache["len"]                           # [B]
    p_max = table.shape[1]
    at = _page_of(table, pos, page_s)
    x = params["embed"][tokens][:, None, :].astype(cfg.dtype)
    cos, sin = rope_table(pos[:, None], cfg.head_dim, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    # an over-capacity row attends its whole table and no further: one
    # past it would send the kernel's page walk off the table's end
    new_len = jnp.minimum(pos + 1, p_max * page_s)
    # a freed slot keeps decoding (its output is dropped) and its ``len``
    # keeps counting, but its row of the table is all scratch page 0: it
    # attends one position of that page, not 2,048 of them
    kv_len = jnp.where(table[:, 0] == 0, 1, new_len)

    def attend_at(arrays, layer):
        def attend(q, k, v):
            new = _store_kv(cfg, arrays, layer, at, k, v, drop=1)
            if cfg.kv_quant:
                # quantised pages have no kernel yet
                record_branch("paged_decode_attention", False, q, new["k"])
                k_virt, v_virt = _gather_pages(cfg, new, layer, table)
                o = attention(q, repeat_kv(k_virt, cfg.n_rep),
                              repeat_kv(v_virt, cfg.n_rep),
                              causal=False, kv_len=kv_len)
            else:
                o = paged_decode_attention(q, new["k"], new["v"], table,
                                           kv_len, layer=layer)
            return o, new

        return attend

    logits, arrays = _scan_blocks(params, cfg, x, cos, sin, _planes(cache),
                                  attend_at, lambda x: x[:, 0])
    return logits, {**arrays, "len": new_len}


def sp_paged_decode_step(params: dict, tokens: jnp.ndarray, cache: dict,
                         table: jnp.ndarray, cfg: LlamaConfig, mesh
                         ) -> tuple[jnp.ndarray, dict]:
    """``paged_decode_step`` against a page pool STRIPED across the
    ``sp`` mesh axis: each device owns ``n_pages/sp`` pool pages (the
    host allocator round-robins a slot's virtual pages across devices),
    so a single request's KV can exceed one chip's HBM.

    One shard_map wraps the whole step. Per shard: the new token's KV
    row writes only on the page's OWNER (non-owners route the scatter
    out of bounds, mode="drop"); attention gathers the shard's LOCAL
    pages into a virtual sequence, masks pages it doesn't own plus
    positions past ``len``, runs the grouped online-softmax, and the
    shards combine EXACTLY with one ``pmax`` + two ``psum``s — the
    ``sp_decode_attention`` combine (parallel/ring.py), page-routed.
    Activations and weights are computed replicated (the psum result is
    identical on every shard, so the layers stay in lockstep); only the
    pool planes are sharded. Composes with int8/int4 pages
    (``cfg.kv_quant``): each shard dequantizes only its own pages.
    ``table`` holds GLOBAL page ids, unchanged from the single-device
    layout — striping is purely the pool's device placement."""
    from ..parallel import shard_map

    b = tokens.shape[0]
    page_s = cache["k"].shape[2]
    p_max = table.shape[1]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    arrays0 = _planes(cache)
    # values are [L, N, ps, KV, hd] at full precision, flat and with
    # their planes [L, N, KV, ps] when quantised: pages on sp either way
    pool_specs = {key: P(None, "sp", *[None] * (a.ndim - 2))
                  for key, a in arrays0.items()}

    def local(params, tokens, arrays, table, pos):
        shard = jax.lax.axis_index("sp")
        p_loc = arrays["k"].shape[1]      # pages THIS device owns
        base = shard * p_loc
        # the write target (global), exactly as paged_decode_step
        page_g, off = _page_of(table, pos, page_s)
        # non-owned writes route out of bounds and drop
        wpage = jnp.where((page_g >= base) & (page_g < base + p_loc),
                          page_g - base, p_loc)
        # local view of each row's table: owned pages + a clipped gather
        # index (masked below, so the duplicate reads never contribute)
        ltab = jnp.clip(table - base, 0, p_loc - 1)
        owned = (table >= base) & (table < base + p_loc)  # [B, P_max]
        vpos = jnp.arange(p_max * page_s).reshape(p_max, page_s)
        valid = (owned[:, :, None]
                 & (vpos[None] < (pos + 1)[:, None, None])
                 ).reshape(b, -1)                         # [B, S_virt]
        x = params["embed"][tokens][:, None, :].astype(cfg.dtype)
        cos, sin = rope_table(pos[:, None], cfg.head_dim, cfg.rope_theta,
                              scaling=cfg.rope_scaling)
        scale = hd ** -0.5

        def attend_at(arrays, layer):
            def attend(q, k, v):
                new = _store_kv(cfg, arrays, layer, (wpage, off), k, v,
                                drop=1, mode="drop")
                k_virt, v_virt = _gather_pages(cfg, new, layer, ltab)
                # grouped online-softmax over LOCAL keys, exact
                # cross-shard combine: one pmax (global row max) + two
                # psums (rescaled numerator / denominator) —
                # _sp_decode_local's math over a page-gathered virtual
                # sequence
                qg = (q[:, 0].reshape(b, KV, cfg.n_rep, hd)
                      .astype(jnp.float32) * scale)
                att = jnp.einsum("bgrd,bsgd->bgrs", qg,
                                 k_virt.astype(jnp.float32))
                att = jnp.where(valid[:, None, None, :], att, -1e30)
                m = jnp.max(att, axis=-1, keepdims=True)
                m_glob = jax.lax.pmax(m, "sp")
                p = jnp.exp(att - m_glob)
                l_loc = jnp.sum(p, axis=-1, keepdims=True)
                acc_loc = jnp.einsum("bgrs,bsgd->bgrd", p,
                                     v_virt.astype(jnp.float32))
                l_glob = jax.lax.psum(l_loc, "sp")
                acc_glob = jax.lax.psum(acc_loc, "sp")
                o = (acc_glob / jnp.maximum(l_glob, 1e-30)).astype(cfg.dtype)
                return o, new

            return attend

        logits, arrays = _scan_blocks(params, cfg, x, cos, sin, arrays,
                                      attend_at, lambda x: x[:, 0])
        new_len = jnp.minimum(pos + 1, p_max * page_s)
        return logits, arrays, new_len

    logits, arrays, new_len = shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), pool_specs, P(), P()),
        out_specs=(P(), pool_specs, P()), check_vma=False,
    )(params, tokens, arrays0, table, cache["len"])
    return logits, {**arrays, "len": new_len}


def paged_decode_window(params: dict, toks: jnp.ndarray, cache: dict,
                        table: jnp.ndarray, cfg: LlamaConfig
                        ) -> tuple[jnp.ndarray, dict]:
    """decode_window (speculative K+1 verify) against the paged pool:
    toks [B, W] at per-row positions ``cache['len']``; kv rows scatter
    through each row's page table, attention gathers the virtual
    sequences back. ``len`` is NOT advanced — the caller advances by
    1 + accepted, and rejected rows are overwritten before any causal
    mask can reach them (the decode_window argument, page-routed).
    Composes with int8 pages (cfg.kv_quant): window rows quantize on
    write, attention dequantizes the gathered virtual sequence."""
    w = toks.shape[1]
    pos0 = cache["len"]                                    # [B]
    positions = pos0[:, None] + jnp.arange(w)[None, :]     # [B, W]
    at = _page_of(table, positions, cache["k"].shape[2])
    x = params["embed"][toks].astype(cfg.dtype)            # [B, W, D]
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta,
                          scaling=cfg.rope_scaling)

    def attend_at(arrays, layer):
        def attend(q, k, v):
            new = _store_kv(cfg, arrays, layer, at, k, v)
            k_virt, v_virt = _gather_pages(cfg, new, layer, table)
            o = attention(q, repeat_kv(k_virt, cfg.n_rep),
                          repeat_kv(v_virt, cfg.n_rep),
                          causal=True, q_offset=pos0)  # per-row offsets
            return o, new

        return attend

    logits, arrays = _scan_blocks(params, cfg, x, cos, sin, _planes(cache),
                                  attend_at, lambda x: x)  # [B, W, V]
    return logits, {**arrays, "len": cache["len"]}


def decode_window(params: dict, toks: jnp.ndarray, cache: dict,
                  cfg: LlamaConfig, mesh=None) -> tuple[jnp.ndarray, dict]:
    """Speculative verify window: W tokens per row, starting at each row's
    own ``cache['len']`` — the batched continuous-batching counterpart of
    ml/speculate.py's single-stream window program.

    toks [B, W] -> (logits [B, W, V], updated cache arrays). Each row's W
    q/k/v rows are scattered at positions len..len+W-1 (out-of-capacity
    writes drop) and queries attend causally over prefix + window. ``len``
    is NOT advanced here: the caller advances by 1 + accepted, so
    "rollback" of rejected drafts is simply not advancing past them —
    later windows overwrite the stale rows before any query can reach
    them. Composes with the int8 cache (cfg.kv_quant): window rows are
    quantized per token per KV head on write, and each layer's cache is
    dequantized for the window attention — the HBM sweep (the decode
    roofline) still reads int8.
    """
    if cfg.kv_bits == 4:
        raise ValueError("int4 KV is a paged-cache precision — use "
                         "page_size > 0 (paged_decode_window)")
    b, w = toks.shape
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    pos0 = cache["len"]                                   # [B]
    positions = pos0[:, None] + jnp.arange(w)[None, :]    # [B, W]
    x = params["embed"][toks].astype(cfg.dtype)           # [B, W, D]
    cos, sin = rope_table(positions, cfg.head_dim, cfg.rope_theta,
                          scaling=cfg.rope_scaling)
    rows = jnp.arange(b)

    def attend_at(arrays, layer):
        def attend(q, k, v):
            new = _store_kv(cfg, arrays, layer, (rows[:, None], positions),
                            k, v, mode="drop")
            lyr = lambda key: jax.lax.dynamic_index_in_dim(
                new[key], layer, 0, keepdims=False)
            if cfg.kv_quant:
                s_max = new["k"].shape[2]
                deq = lambda name: dequantize_kv(
                    lyr(name).reshape(b, s_max, KV, hd),
                    lyr(f"{name}_scale").transpose(0, 2, 1), cfg.dtype)
                k_row, v_row = deq("k"), deq("v")
            else:
                k_row, v_row = lyr("k"), lyr("v")
            # per-row causal offset: query t of row i attends positions
            # <= pos0[i]+t — its prefix plus the window so far; stale
            # cells past the window are unreachable
            o = attention(q, repeat_kv(k_row, cfg.n_rep),
                          repeat_kv(v_row, cfg.n_rep),
                          causal=True, q_offset=pos0)
            return o, new

        return attend

    logits, arrays = _scan_blocks(
        params, cfg, x, cos, sin, _planes(cache), attend_at,
        lambda x: x, qk_spec=_QK_SPEC)                    # [B, W, V]
    return logits, {**arrays, "len": cache["len"]}


def loss_fn(params: dict, tokens: jnp.ndarray, targets: jnp.ndarray,
            mask: jnp.ndarray, cfg: LlamaConfig) -> jnp.ndarray:
    """Masked next-token cross-entropy (f32 logits)."""
    logits = forward(params, tokens, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    maskf = mask.astype(jnp.float32)
    return -(ll * maskf).sum() / jnp.maximum(maskf.sum(), 1.0)


class Llama:
    """Engine-facing wrapper: holds params, exposes ``apply`` for ctx.ml."""

    def __init__(self, cfg: LlamaConfig | None = None, seed: int = 0) -> None:
        self.cfg = cfg or llama3_8b()
        self.params = init_params(self.cfg, jax.random.PRNGKey(seed))
        self.example_inputs = (np.zeros((1, 16), np.int32),)

    def apply(self, params, tokens):
        return forward(params, tokens, self.cfg)

    def sharding_specs(self):
        from ..parallel import specs_from_rules

        return specs_from_rules(self.params, SHARDING_RULES)
