"""Compute-bound benchmarks: prefill MFU, train-step MFU, flash-vs-XLA A/B.

The serving benches (configs #2-#5) are latency/throughput shaped; this one
answers "does the compute path actually use the MXU" with three numbers on
the 1B proxy (the 8B/8-chip per-chip share):

  - prefill MFU   — full-sequence forward, bf16, batch x 2k tokens. The
                    MXU-bound op mix (QKV/MLP matmuls + flash attention);
                    target >= 0.4 of the chip's bf16 peak.
  - train MFU     — one optimizer step (fwd + bwd + AdamW update) with
                    rematerialized layers; flops counted as 6*N*tokens +
                    3x the attention term.
  - flash A/B     — Pallas flash attention vs the XLA reference softmax
                    attention at 2k and 8k sequence, causal, bf16. The
                    kernel's reason to exist is here: at 8k the XLA path
                    materializes the [S, S] logits in HBM, flash streams
                    K/V through VMEM.

Each timed section runs K iterations inside ONE jitted lax.scan with a
data-dependent carry so XLA cannot elide iterations and the host's
dispatch/fetch overhead amortizes across the scan, not per sample.
"""

from __future__ import annotations

import os
import time

import numpy as np

from common import emit

# bf16 peak FLOP/s per chip by device kind (public specs)
_PEAK = {
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v4": 275e12,
    "v6 lite": 918e12,
}


def _peak_flops() -> float:
    """bf16 peak FLOP/s of the attached device. An unlisted device kind is
    an error: an MFU against another chip's roofline means nothing."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for key, val in _PEAK.items():
        if key in kind:
            return val
    raise ValueError(f"no bf16 peak on file for device kind {kind!r}; add "
                     f"it to _PEAK with its source")


def _timed_scan(fn, init, length: int, *consts) -> float:
    """Best-of-3 wall time of one dispatch running ``fn`` x length inside
    lax.scan, divided by length. ``fn(carry, *consts) -> carry`` must be
    data-dependent on its carry. ``consts`` (params, K/V, ...) ride as jit
    ARGUMENTS — closing over big arrays would capture them as module
    constants and bake GBs into the compiled program."""
    import jax

    def scanned(c, *xs):
        return jax.lax.scan(lambda c, _: (fn(c, *xs), None),
                            c, None, length=length)[0]

    # donate the carry and chain each call on the previous output: without
    # aliasing, a (params, opt_state) carry exists twice (in + out) and
    # OOMs the 16 GB HBM on the 1B train step
    f = jax.jit(scanned, donate_argnums=(0,))
    out = f(init, *consts)
    np.asarray(jax.tree.leaves(out)[0].ravel()[:1])  # compile + real sync
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = f(out, *consts)
        np.asarray(jax.tree.leaves(out)[0].ravel()[:1])
        best = min(best, time.perf_counter() - t0)
    return best / length


def _attention_ab(on_tpu: bool) -> dict:
    """Flash (Pallas) vs XLA reference attention, causal bf16 BSHD."""
    import jax.numpy as jnp

    from gofr_tpu.ops import attention

    results = {}
    cases = ((2048, 4), (8192, 1)) if on_tpu else ((256, 1),)
    for seq, batch in cases:
        h, d = 16, 128
        shape = (batch, seq, h, d)
        key_flops = 4 * batch * h * seq * seq * d / 2  # qk + pv, causal half
        # fresh q per timed run: _timed_scan donates its init
        make_q = lambda: jnp.ones(shape, jnp.bfloat16)
        k = jnp.full(shape, 0.5, jnp.bfloat16)
        v = jnp.ones(shape, jnp.bfloat16)

        def xla_step(c, k, v):
            return attention(c, k, v, causal=True).astype(jnp.bfloat16)

        def flash_step(c, k, v):
            if on_tpu:
                from gofr_tpu.ops.flash_attention import flash_attention_tpu

                return flash_attention_tpu(c, k, v, causal=True)
            return attention(c, k, v, causal=True).astype(jnp.bfloat16)

        t_xla = _timed_scan(xla_step, make_q(), 4, k, v)
        t_flash = _timed_scan(flash_step, make_q(), 4, k, v)
        results[f"seq{seq}"] = {
            "batch": batch,
            "xla_ms": round(t_xla * 1e3, 2),
            "flash_ms": round(t_flash * 1e3, 2),
            "speedup": round(t_xla / t_flash, 2),
            "flash_tflops": round(key_flops / t_flash / 1e12, 1),
        }
    return results


def main() -> None:
    os.environ.setdefault("LOG_LEVEL", "ERROR")
    import jax
    import jax.numpy as jnp
    import optax

    from gofr_tpu.ml.train import make_train_step
    from gofr_tpu.models import llama

    on_tpu = jax.default_backend() == "tpu"
    peak = _peak_flops()

    if on_tpu:
        cfg = llama.LlamaConfig(
            vocab_size=32_128, dim=2048, n_layers=16, n_heads=16,
            n_kv_heads=8, ffn_dim=8192, max_seq_len=2048, remat=True,
        )
        pf_batch, pf_seq = 4, 2048
        tr_batch, tr_seq = 2, 2048
    else:
        cfg = llama.tiny_llama(use_flash=False)
        pf_batch, pf_seq = 2, 64
        tr_batch, tr_seq = 2, 64

    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    attn_flops_tok = 2 * cfg.n_layers * cfg.n_heads * cfg.head_dim  # per tok²/seq

    # ---- prefill MFU ----------------------------------------------------
    tokens0 = jnp.ones((pf_batch, pf_seq), jnp.int32)

    def prefill_step(toks, p):
        logits = llama.forward(p, toks, cfg)
        # argmax chains the next iteration on this one's result
        return jnp.clip(jnp.argmax(logits, -1).astype(jnp.int32), 0,
                        cfg.vocab_size - 1)

    t_prefill = _timed_scan(prefill_step, tokens0, 4 if on_tpu else 2, params)
    pf_tokens = pf_batch * pf_seq
    pf_flops = 2 * n_params * pf_tokens + attn_flops_tok * pf_batch * pf_seq**2
    prefill_mfu = pf_flops / t_prefill / peak

    # ---- train-step MFU -------------------------------------------------
    # AdamW with bf16 first moment: the f32 nu + bf16 mu + params + grads
    # fit the 16 GB HBM alongside remat'd activations at 2x2048
    def loss_fn(p, toks, labels):
        logits = llama.forward(p, toks, cfg)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    step = make_train_step(loss_fn, opt)
    opt_state = opt.init(params)
    batch = (jnp.ones((tr_batch, tr_seq), jnp.int32),
             jnp.ones((tr_batch, tr_seq), jnp.int32))

    train_detail: dict = {}
    try:
        def train_once(carry, toks, labels):
            p, s = carry
            p, s, _ = step(p, s, toks, labels)
            return (p, s)

        t_train = _timed_scan(train_once, (params, opt_state), 2, *batch)
        tr_tokens = tr_batch * tr_seq
        tr_flops = (6 * n_params * tr_tokens
                    + 3 * attn_flops_tok * tr_batch * tr_seq**2)
        train_detail = {
            "train_mfu": round(tr_flops / t_train / peak, 4),
            "train_step_ms": round(t_train * 1e3, 1),
            "train_tokens_per_step": tr_tokens,
            "train_batch": [tr_batch, tr_seq],
            "remat": True,
        }
    except Exception as exc:  # OOM etc: record, don't lose the other rows
        train_detail = {"train_mfu": None, "train_error": repr(exc)[:300]}
    finally:
        del opt_state

    # ---- flash vs XLA attention -----------------------------------------
    ab = _attention_ab(on_tpu)

    emit(
        "prefill_mfu_1b_proxy", prefill_mfu, "mfu", None,
        {
            "target_mfu": 0.4,
            "prefill_ok": bool(prefill_mfu >= 0.4),
            "prefill_step_ms": round(t_prefill * 1e3, 1),
            "prefill_batch": [pf_batch, pf_seq],
            "prefill_tflops": round(pf_flops / t_prefill / 1e12, 1),
            "peak_tflops": round(peak / 1e12, 1),
            "params_m": round(n_params / 1e6),
            **train_detail,
            "flash_vs_xla": ab,
            "backend": jax.default_backend(),
            "device": jax.devices()[0].device_kind,
            "config": 6,
        },
    )


if __name__ == "__main__":
    main()
