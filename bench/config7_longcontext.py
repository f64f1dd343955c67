"""Long-context decode: int8 KV cache vs fp at >= 8k context, plus the
paged-pool capacity A/B (r3 verdict #8): at EQUAL cache HBM, the paged
layout serves 2x the concurrent mixed-length slots of the dense one.

kv_quant's reason to exist is long contexts — decode there is dominated by
sweeping the KV cache out of HBM, so halving cache bytes should buy real
step time (r2 VERDICT #4 asked for exactly this delta, at >= 8k, measured
not asserted). 8 slots x 8192 tokens of context on the 1B proxy:
fp cache = 4 GiB, int8 = 2 GiB + scales.

Prefill fills each slot to near-8k via the bucketed prefill path, then the
timed section decodes chunks with every slot live. One JSON line; off-TPU
emits a tiny smoke variant.
"""

from __future__ import annotations

import os
import time

import numpy as np

from common import emit


def _decode_tok_s(kv_quant: bool, *, slots: int, ctx: int, max_seq: int,
                  chunk: int, n_chunks: int, cfg_kw: dict,
                  w8: bool = False) -> dict:
    import jax  # noqa: F401

    from gofr_tpu.ml.generate import Generator
    from gofr_tpu.models import llama

    cfg = llama.LlamaConfig(**cfg_kw, kv_quant=kv_quant, w8=w8)
    params = llama.params_from_config(cfg)
    gen = Generator(params, cfg, batch_slots=slots, max_seq=max_seq,
                    prefill_buckets=(ctx,), chunk=chunk)
    rng = np.random.default_rng(0)
    for _ in range(slots):
        prompt = rng.integers(1, cfg.vocab_size, (ctx,)).astype(np.int32)
        gen.add_request(prompt, max_new_tokens=10**9)
    gen.step()  # compile + warm
    np.asarray(gen.cache["len"])  # a real sync: fetch, not just enqueue

    t0 = time.perf_counter()
    for _ in range(n_chunks):
        gen.step()
    np.asarray(gen.cache["len"])
    elapsed = time.perf_counter() - t0
    steps = chunk * n_chunks
    out = {
        "tok_per_s": round(slots * steps / elapsed, 1),
        "step_ms": round(1e3 * elapsed / steps, 2),
        "cache_gib": round(
            sum(int(np.prod(gen.cache[k].shape)) * gen.cache[k].dtype.itemsize
                for k in gen.cache if k != "len") / 2**30, 2),
    }
    del gen, params  # free HBM before the other variant allocates
    return out


def _mixed_run(*, paged: bool, slots: int, n_pages: int | None,
               page_size: int, prompts, max_new: int, max_seq: int,
               chunk: int, buckets, cfg_kw: dict) -> dict:
    """Serve the SAME mixed-length request set with `slots` concurrency;
    returns aggregate tok/s + the cache HBM actually allocated."""
    import jax  # noqa: F401

    from gofr_tpu.ml.generate import Generator
    from gofr_tpu.models import llama

    cfg = llama.LlamaConfig(**cfg_kw)
    params = llama.params_from_config(cfg)
    gen = Generator(params, cfg, batch_slots=slots, max_seq=max_seq,
                    prefill_buckets=buckets, chunk=chunk,
                    page_size=page_size if paged else 0,
                    n_pages=n_pages if paged else None)
    done: dict[int, int] = {}

    def collect() -> None:
        # settle bookkeeping and bank finished slots BEFORE any admission:
        # add_request's internal drain could otherwise finish a slot whose
        # tokens the slot-reuse then discards (the hazard llm.py guards)
        gen.drain()
        for i, s in enumerate(gen.slots):
            if not s.live and s.tokens:
                done[i] = done.get(i, 0) + len(s.tokens)
                gen.release(i)

    t0 = time.perf_counter()
    pending = list(prompts)
    while pending or gen.n_live:
        collect()
        while pending and gen.free_slot() is not None:
            try:
                slot = gen.add_request(pending[0], max_new_tokens=max_new)
            except RuntimeError:
                break  # pool momentarily dry: decode some slots out first
            pending.pop(0)
            done[slot] = done.get(slot, 0)
        gen.step()
        collect()
    elapsed = time.perf_counter() - t0
    total = sum(done.values())
    cache_gib = sum(
        int(np.prod(gen.cache[k].shape)) * gen.cache[k].dtype.itemsize
        for k in gen.cache if k != "len") / 2**30
    out = {"tok_per_s": round(total / elapsed, 1),
           "slots": slots,
           "cache_gib": round(cache_gib, 2),
           "wall_s": round(elapsed, 2),
           "evictions": gen.evictions}
    del gen, params
    return out


def _sp_probe(mode: str | None, shards: int, *, ctxs, cfg_kw: dict,
              page_size: int, max_seq: int, buckets, min_tokens: int,
              decode_chunks: int, chunk: int) -> dict:
    """One sequence-parallel arm: admit a prompt per context length and
    measure TTFT (admission wall — the prefill SP shards) and TPOT
    (steady decode over the striped pool), plus the greedy tokens for
    the cross-arm identity check. ``mode=None`` is the SP-off baseline
    on the identical workload."""
    from gofr_tpu.ml.generate import Generator
    from gofr_tpu.ml.sp_serving import SPConfig
    from gofr_tpu.models import llama

    cfg = llama.LlamaConfig(**cfg_kw)
    params = llama.params_from_config(cfg)
    sp = (None if mode is None
          else SPConfig(mode, min_tokens=min_tokens, shards=shards))
    gen = Generator(params, cfg, batch_slots=1, max_seq=max_seq,
                    prefill_buckets=buckets, chunk=chunk,
                    page_size=page_size, sp=sp)
    gen.warmup()
    rng = np.random.default_rng(7)
    rows = {}
    for ctx in ctxs:
        prompt = rng.integers(1, cfg_kw["vocab_size"], (ctx,)).astype(
            np.int32)
        t0 = time.perf_counter()
        slot = gen.add_request(prompt,
                               max_new_tokens=decode_chunks * chunk)
        ttft_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        while gen.slots[slot].live:
            gen.step()
        gen.drain()
        decode_s = time.perf_counter() - t1
        toks = list(gen.slots[slot].tokens)
        gen.release(slot)
        rows[str(ctx)] = {
            "ttft_ms": round(1e3 * ttft_s, 2),
            "tpot_ms": round(1e3 * decode_s / max(1, len(toks)), 2),
            "tokens": toks,
        }
    out = {"mode": mode or "off", "shards": shards if mode else 1,
           "contexts": rows,
           "sp_prefills": getattr(gen, "sp_prefills", 0),
           "sp_fallbacks": getattr(gen, "sp_fallbacks", 0)}
    del gen, params
    return out


def _sp_arm(on_tpu: bool) -> dict:
    """BENCH_SP_ARM=1: TTFT/TPOT vs context length with SP off vs
    ring/ulysses at a shard sweep, plus the token-identity verdict —
    bench phase for ROADMAP item 2 (sequence-parallel serving)."""
    import jax

    from gofr_tpu.models.llama import tiny_llama

    if len(jax.devices()) < 2:
        # a custom XLA_FLAGS without the host-device-count trick (or a
        # one-chip box): report the skip instead of crashing the config
        return {"skipped": f"needs >= 2 devices, have "
                           f"{len(jax.devices())}"}

    if on_tpu:
        cfg_kw = dict(vocab_size=32_128, dim=2048, n_layers=16, n_heads=16,
                      n_kv_heads=8, ffn_dim=8192, max_seq_len=16_640)
        ctxs, max_seq, buckets = (4096, 8192, 16384), 16_640, (16_384,)
        page_size, min_tokens, decode_chunks, chunk = 128, 2048, 4, 16
        arms = [("ring", 2), ("ring", 4), ("ulysses", 4)]
    else:
        tiny = tiny_llama(use_flash=False)
        import jax.numpy as jnp

        # f32: the bit-identity dtype (the cross-arm verdict below)
        cfg_kw = dict(vocab_size=tiny.vocab_size, dim=tiny.dim,
                      n_layers=tiny.n_layers, n_heads=tiny.n_heads,
                      n_kv_heads=tiny.n_kv_heads, ffn_dim=tiny.ffn_dim,
                      max_seq_len=128, use_flash=False, dtype=jnp.float32)
        ctxs, max_seq, buckets = (32, 96), 128, (96,)
        page_size, min_tokens, decode_chunks, chunk = 8, 16, 2, 4
        arms = [("ring", 2), ("ring", 4), ("ulysses", 2)]
    common = dict(ctxs=ctxs, cfg_kw=cfg_kw, page_size=page_size,
                  max_seq=max_seq, buckets=buckets, min_tokens=min_tokens,
                  decode_chunks=decode_chunks, chunk=chunk)
    base = _sp_probe(None, 1, **common)
    results = [base]
    identical = True
    for mode, shards in arms:
        probe = _sp_probe(mode, shards, **common)
        results.append(probe)
        for ctx, row in probe["contexts"].items():
            if row["tokens"] != base["contexts"][ctx]["tokens"]:
                identical = False
    # the measured table: tokens served their identity check — strip
    # them so the JSON line stays readable
    for probe in results:
        for row in probe["contexts"].values():
            row.pop("tokens")
    return {"arms": results, "token_identity": identical,
            "contexts": list(ctxs), "page_size": page_size}


def main() -> None:
    os.environ.setdefault("LOG_LEVEL", "ERROR")
    if os.environ.get("BENCH_SP_ARM") == "1":
        # the SP arm shards over >= 2 devices; off-TPU that means the
        # virtual CPU mesh (the tests/conftest.py trick). Must land
        # before the first jax import; an operator's own XLA_FLAGS wins
        # (setdefault) and the arm then skips gracefully below if it
        # still sees one device.
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    import jax

    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        cfg_kw = dict(vocab_size=32_128, dim=2048, n_layers=16, n_heads=16,
                      n_kv_heads=8, ffn_dim=8192, max_seq_len=8448)
        slots, ctx, max_seq, chunk, n_chunks = 8, 8192, 8448, 16, 8
    else:
        from gofr_tpu.models.llama import tiny_llama

        tiny = tiny_llama(use_flash=False)
        cfg_kw = dict(vocab_size=tiny.vocab_size, dim=tiny.dim,
                      n_layers=tiny.n_layers, n_heads=tiny.n_heads,
                      n_kv_heads=tiny.n_kv_heads, ffn_dim=tiny.ffn_dim,
                      max_seq_len=64, use_flash=False)
        slots, ctx, max_seq, chunk, n_chunks = 2, 16, 64, 2, 2

    fp = _decode_tok_s(False, slots=slots, ctx=ctx, max_seq=max_seq,
                       chunk=chunk, n_chunks=n_chunks, cfg_kw=cfg_kw)
    q8 = _decode_tok_s(True, slots=slots, ctx=ctx, max_seq=max_seq,
                       chunk=chunk, n_chunks=n_chunks, cfg_kw=cfg_kw)
    # full-int8 sweep: int8 weights AND int8 cache — decode's entire
    # per-step HBM traffic quantized (w8 halves the weight bytes that
    # dominate at low slot counts; kv8 halves the cache bytes that
    # dominate at long context)
    w8 = _decode_tok_s(True, slots=slots, ctx=ctx, max_seq=max_seq,
                       chunk=chunk, n_chunks=n_chunks, cfg_kw=cfg_kw,
                       w8=True)

    # ---- paged capacity A/B at EQUAL cache HBM ---------------------------
    # mixed-length workload (1-in-4 long): dense pins worst-case rows per
    # slot; the paged pool shares them, so the same HBM carries 2x (fp) /
    # 4x (int8) the concurrent slots (the long-context capacity lever).
    if on_tpu:
        ps, dense_slots, max_new = 128, 4, 64
        ctx_long, ctx_short = 8192, 1024
    else:
        ps, dense_slots, max_new = 8, 2, 4
        ctx_long, ctx_short = 16, 8
    rng = np.random.default_rng(1)
    vocab = cfg_kw["vocab_size"]
    n_req = 4 * dense_slots
    # 1-in-4 long: the mixed ratio where worst-case CONCURRENT pages fit
    # the shared pool at 2x (fp) / 4x (int8) the dense slot count — the
    # dense layout still pins max_seq rows for every one of them
    prompts = [
        rng.integers(1, vocab,
                     (ctx_long if i % 4 == 0 else ctx_short,)
                     ).astype(np.int32)
        for i in range(n_req)
    ]
    common = dict(page_size=ps, prompts=prompts, max_new=max_new,
                  max_seq=max_seq, chunk=chunk,
                  buckets=(ctx_short, ctx_long), cfg_kw=cfg_kw)
    dense_run = _mixed_run(paged=False, slots=dense_slots, n_pages=None,
                           **common)
    equal_hbm_pages = 1 + dense_slots * (-(-max_seq // ps))
    paged_run = _mixed_run(paged=True, slots=2 * dense_slots,
                           n_pages=equal_hbm_pages, **common)
    # both memory levers at once: int8 pages are ~half the bytes, so the
    # SAME byte budget holds ~2x the pages -> 4x the dense slot count
    paged_q_run = _mixed_run(
        paged=True, slots=4 * dense_slots,
        n_pages=1 + 2 * dense_slots * (-(-max_seq // ps)),
        **{**common, "cfg_kw": dict(cfg_kw, kv_quant=True)})

    # ---- sequence-parallel serving arm (BENCH_SP_ARM=1) ------------------
    # TTFT/TPOT vs context length, SP off vs ring/ulysses at a shard
    # sweep, with the greedy token-identity verdict (ROADMAP item 2)
    sp_arm = (_sp_arm(on_tpu)
              if os.environ.get("BENCH_SP_ARM") == "1" else None)

    emit(
        "longcontext_int8_speedup_8k", q8["tok_per_s"] / fp["tok_per_s"],
        "x", None,
        {
            "context": ctx,
            "slots": slots,
            "fp": fp,
            "int8": q8,
            "int8_w8": w8,
            "w8_speedup": round(w8["tok_per_s"] / fp["tok_per_s"], 3),
            # paged A/B: same request set, same cache HBM, 2x slots
            "paged_ab": {
                "dense": dense_run,
                "paged_equal_hbm": paged_run,
                "paged_int8_equal_hbm": paged_q_run,
                "paged_speedup": round(
                    paged_run["tok_per_s"] / dense_run["tok_per_s"], 3),
                "paged_int8_speedup": round(
                    paged_q_run["tok_per_s"] / dense_run["tok_per_s"], 3),
                "page_size": ps,
            },
            **({"sp_arm": sp_arm} if sp_arm is not None else {}),
            "backend": jax.default_backend(),
            "config": 7,
        },
    )


if __name__ == "__main__":
    main()
