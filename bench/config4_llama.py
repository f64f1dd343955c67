"""BASELINE config #4 serving surface: Llama chat, gRPC server-streaming,
continuous batching — aggregate tok/s THROUGH the serving path + TTFT.

Three phases, all in one run so the numbers share the same conditions:

  0. dispatch probe — p50 of an empty jitted round trip (dispatch + D2H):
                     the floor the host's path to the device puts under
                     every wire latency.
  A. TTFT          — 8 concurrent streams, short generations: p50 wire
                     TTFT, server-side TTFT (enqueue -> first token) from
                     the app_llm_ttft_seconds histogram delta, and the
                     decomposition wire = server + dispatch round trip.
  B. throughput    — BENCH_STREAMS (default 64) concurrent gRPC streams,
                     BENCH_MAX_NEW (default 256) new tokens each, slots
                     sized to match: aggregate tok/s over the full window,
                     counted at the CLIENT after gRPC framing — the number
                     the north-star >= 2000 tok/s target is about.
  C. prefill jitter— short-stream TTFT while LONG prompts keep arriving,
                     A/B'd against a reboot with LLM_PREFILL_CHUNK set:
                     segmented prefill bounds the p99 TTFT spike a 2k
                     prefill otherwise injects into every live stream
                     (VERDICT r4 #2).
  D. prefix cache  — shared-system-prompt arm: a reboot with
                     LLM_PAGE_SIZE turns on the framework radix prefix
                     cache; a long common prefix + short user suffixes
                     measures TTFT and tok/s cache-COLD (first sightings,
                     full prefill) vs WARM (auto-promoted, suffix-only
                     prefill), plus the prefill-tokens-saved counter —
                     the north-star millions-of-users-few-system-prompts
                     win, visible in BENCH_*.json.
  E. scheduler     — adaptive token-budget A/B: mixed load (steady decode
                     streams + long prompts arriving) served by the
                     fixed-chunk path (GOFR_ML_TOKEN_BUDGET=0) vs the
                     adaptive scheduler; short-probe TTFT p50/p99,
                     steady-stream tok/s, and a greedy token-identity
                     check between the two boots.
  F. kv offload    — tiered KV cache A/B: rotating system prompts sized
                     to overflow the HBM page pool, offload ON
                     (GOFR_ML_KV_HOST_BUDGET_MB set) vs OFF (=0, today's
                     discard). Warm-hit TTFT p50/p99 per arm, prefill
                     tokens restored vs recomputed (tokens-saved +
                     restore counters), and a greedy token-identity
                     check between the two boots.
  G. resilience    — fault arm (GOFR_ML_FAULT=step:0.05) vs clean arm
                     under the same traffic: every client must end in
                     valid output or a typed gRPC error (no hangs), the
                     watchdog's recovered-restart count, shed/deadline
                     counters, and the clean arm's zero-restart baseline.
  H. stalls        — flight-recorder arm: mixed load with the dispatch
                     recorder ON records the per-phase breakdown of step
                     wall time (queue pop / decide / assemble / launch /
                     d2h issue / device wait / emit / other) + the named
                     top host-side stall from /debug/serving, A/B'd
                     against a GOFR_ML_FLIGHT_RECORDER=0 reboot to price
                     the recorder itself (acceptance <= 2% on steady
                     tok/s). This is the ledger ROADMAP 3c reads to
                     attribute the non-device share of step_ms.
  I. speculation   — spec x KV-precision grid: speculative decoding off
                     vs on (LLM_SPEC_K, adaptive floor armed) at each of
                     GOFR_ML_KV_BITS=16/8/4 over the paged pool. Per
                     cell: steady decode tok/s, realized step_ms and
                     per-phase breakdown, the accept rate + adaptive
                     disable state from /debug/serving, and a greedy
                     token-identity check spec-on vs spec-off at the
                     SAME precision (speculation is lossless; precisions
                     legitimately differ). The raw-speed ROADMAP-3 arm:
                     spec-on tok/s must beat spec-off at the tiny
                     preset, and kv4's page VALUE bytes are exactly half
                     kv8's (total page bytes carry the scale+zero plane
                     overhead; see pool_stats).

LLAMA_PRESET=1b on TPU by default (the 8B/8-chip per-chip share), tiny on CPU.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from common import (boot, configure_free_ports, dispatch_rtt_ms, emit,
                    percentile, run)


async def _metrics_ttft(ports) -> tuple[float, float]:
    """(sum_seconds, count) of the server-side TTFT histogram."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            r = await s.get(f"http://127.0.0.1:{ports['METRICS_PORT']}/metrics")
            text = await r.text()
        tot = cnt = 0.0
        for line in text.splitlines():
            if line.startswith("app_llm_ttft_seconds_sum"):
                tot = float(line.rsplit(" ", 1)[1])
            elif line.startswith("app_llm_ttft_seconds_count"):
                cnt = float(line.rsplit(" ", 1)[1])
        return tot, cnt
    except Exception:
        return 0.0, 0.0


async def _metrics_counter(ports, name: str) -> float:
    """Sum of one counter across label sets (e.g. prefill tokens saved)."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            r = await s.get(f"http://127.0.0.1:{ports['METRICS_PORT']}/metrics")
            text = await r.text()
        return sum(float(line.rsplit(" ", 1)[1])
                   for line in text.splitlines()
                   if line.startswith(name) and not line.startswith("#"))
    except Exception:
        return 0.0


async def _debug_pool(ports, llm: str = "chat") -> dict:
    """The per-LLM pool block of /debug/serving (prefix_prefills,
    kv_spills/kv_restores — the recomputed-vs-restored ledger)."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            r = await s.get(
                f"http://127.0.0.1:{ports['HTTP_PORT']}/debug/serving")
            body = await r.json()
        return body["data"]["llms"][llm]["pool"]
    except Exception:
        return {}


async def _debug_resilience(ports, llm: str = "chat") -> dict:
    """The per-LLM resilience block of /debug/serving (watchdog state,
    restart history, shed/deadline counters, fault config)."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            r = await s.get(
                f"http://127.0.0.1:{ports['HTTP_PORT']}/debug/serving")
            body = await r.json()
        return body["data"]["llms"][llm]["resilience"]
    except Exception:
        return {}


async def _debug_stalls(ports, llm: str = "chat") -> dict:
    """The per-LLM flight-recorder block of /debug/serving (rolling
    per-dispatch phase breakdown + the named top host-side stall)."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            r = await s.get(
                f"http://127.0.0.1:{ports['HTTP_PORT']}/debug/serving")
            body = await r.json()
        return body["data"]["llms"][llm].get("stalls", {})
    except Exception:
        return {}


async def _debug_requests(ports) -> dict:
    """The /debug/requests journey summary (per-mark percentiles +
    finish-reason mix) for the journey bench arm."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            r = await s.get(
                f"http://127.0.0.1:{ports['HTTP_PORT']}/debug/requests")
            body = await r.json()
        return body["data"]
    except Exception:
        return {}


async def _debug_llm(ports, llm: str = "chat") -> dict:
    """The whole per-LLM block of /debug/serving (speculation block,
    pool stats — the phase-I grid reads both)."""
    import aiohttp

    try:
        async with aiohttp.ClientSession() as s:
            r = await s.get(
                f"http://127.0.0.1:{ports['HTTP_PORT']}/debug/serving")
            body = await r.json()
        return body["data"]["llms"][llm]
    except Exception:
        return {}


async def main() -> None:
    import asyncio

    ports = configure_free_ports()
    os.environ.setdefault("LOG_LEVEL", "ERROR")

    import grpc.aio
    import jax

    on_tpu = jax.default_backend() == "tpu"
    streams = int(os.environ.get("BENCH_STREAMS", "64" if on_tpu else "8"))
    max_new = int(os.environ.get("BENCH_MAX_NEW", "256" if on_tpu else "16"))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", "128" if on_tpu else "8"))
    if on_tpu:
        os.environ.setdefault("LLAMA_PRESET", "1b")
        # slots sized to the stream count so phase B decodes every stream
        # in ONE program per chunk (128 slots x 1024 seq is the HBM limit)
        os.environ.setdefault("LLM_SLOTS", str(min(max(streams, 8), 128)))
        os.environ.setdefault("LLM_CHUNK", "16")
    slots = int(os.environ.get("LLM_SLOTS", "0")) or None

    from examples.llama_server.main import main as build_app

    app = build_app()
    await boot(app)

    channel = grpc.aio.insecure_channel(f"127.0.0.1:{ports['GRPC_PORT']}")
    generate = channel.unary_stream(
        "/llm.Chat/Generate",
        request_serializer=lambda o: json.dumps(o).encode(),
        response_deserializer=lambda raw: json.loads(raw) if raw else {},
    )

    rng = np.random.default_rng(0)
    vocab_hi = 200

    def req(n_new: int):
        return {
            "prompt_ids": rng.integers(1, vocab_hi, (prompt_len,)).tolist(),
            "max_new_tokens": n_new,
        }

    def n_toks(msg) -> int:
        # server frames one decode-chunk burst per message ({"tokens": [...]})
        return len(msg.get("tokens", ()))

    # warmup: compile prefill + decode (all admission shapes) before timing
    async for _ in generate(req(4)):
        pass

    # ---- phase 0: empty dispatch + D2H round trip -----------------------
    rtt_ms = dispatch_rtt_ms()

    # ---- phase A: TTFT at moderate load ---------------------------------
    ttft_streams = int(os.environ.get("BENCH_TTFT_STREAMS", "8"))
    sum0, cnt0 = await _metrics_ttft(ports)

    async def ttft_stream(out: list[float]):
        t0 = time.perf_counter()
        async for _ in generate(req(16)):
            out.append(time.perf_counter() - t0)
            break  # TTFT only; cancel the rest of the stream

    wire_ttfts: list[float] = []
    await asyncio.gather(*[ttft_stream(wire_ttfts) for _ in range(ttft_streams)])
    sum1, cnt1 = await _metrics_ttft(ports)
    server_ttft_ms = (round(1e3 * (sum1 - sum0) / (cnt1 - cnt0), 1)
                      if cnt1 > cnt0 else None)
    p50_ttft_ms = percentile(wire_ttfts, 50) * 1e3

    await asyncio.sleep(0.3)  # let cancelled slots reap before phase B

    # ---- phase B: aggregate throughput at high concurrency --------------
    token_counts: list[int] = []
    herd_ttfts: list[float] = []

    async def one_stream():
        t0 = time.perf_counter()
        first = None
        count = 0
        async for msg in generate(req(max_new)):
            got = n_toks(msg)
            if first is None and got:
                first = time.perf_counter() - t0
            count += got
        herd_ttfts.append(first if first is not None else float("nan"))
        token_counts.append(count)

    sum2, cnt2 = await _metrics_ttft(ports)
    t_start = time.perf_counter()
    await asyncio.gather(*[one_stream() for _ in range(streams)])
    elapsed = time.perf_counter() - t_start
    sum3, cnt3 = await _metrics_ttft(ports)

    # ---- phase C: prefill-induced TTFT jitter, chunked-prefill A/B ------
    # BENCH_SKIP_JITTER=1 (bench.py sets it): phase C boots the server a
    # second time, which doesn't fit the headline run's watchdog budget —
    # the capture loop runs config4 standalone with phase C included
    skip_jitter = os.environ.get("BENCH_SKIP_JITTER") == "1"
    long_len = int(os.environ.get("BENCH_LONG_PROMPT",
                                  "768" if on_tpu else "48"))
    seg = int(os.environ.get("LLM_PREFILL_CHUNK_AB",
                             "256" if on_tpu else "16"))

    async def jitter_phase(gen_fn) -> dict:
        """Short-stream TTFTs while long prompts arrive every ~40 ms."""
        stop = asyncio.Event()

        async def long_loop():
            while not stop.is_set():
                body = {"prompt_ids": rng.integers(
                            1, vocab_hi, (long_len,)).tolist(),
                        "max_new_tokens": 8}
                async for _ in gen_fn(body):
                    break  # prefill is the interference; drop the rest
                await asyncio.sleep(0.04)

        interferers = [asyncio.create_task(long_loop()) for _ in range(2)]
        ttfts: list[float] = []
        try:
            for _ in range(int(os.environ.get("BENCH_JITTER_PROBES",
                                              "16" if on_tpu else "6"))):
                t0 = time.perf_counter()
                async for _ in gen_fn(req(8)):
                    ttfts.append(time.perf_counter() - t0)
                    break
                await asyncio.sleep(0.02)
        finally:
            stop.set()
            for t in interferers:
                t.cancel()
            await asyncio.gather(*interferers, return_exceptions=True)
        return {"p50_ms": round(percentile(ttfts, 50) * 1e3, 1),
                "p99_ms": round(percentile(ttfts, 99) * 1e3, 1)}

    jitter_plain = None if skip_jitter else await jitter_phase(generate)
    await channel.close()
    await app.shutdown()

    jitter_chunked = None
    if not skip_jitter:
        # reboot with segmented prefill and repeat the same interference
        os.environ["LLM_PREFILL_CHUNK"] = str(seg)
        try:
            app2 = build_app()
            await boot(app2)
            channel2 = grpc.aio.insecure_channel(
                f"127.0.0.1:{ports['GRPC_PORT']}")
            generate2 = channel2.unary_stream(
                "/llm.Chat/Generate",
                request_serializer=lambda o: json.dumps(o).encode(),
                response_deserializer=lambda raw: (json.loads(raw)
                                                   if raw else {}),
            )
            async for _ in generate2(req(4)):   # warm compiles
                pass
            body = {"prompt_ids": rng.integers(1, vocab_hi,
                                               (long_len,)).tolist(),
                    "max_new_tokens": 4}
            async for _ in generate2(body):     # warm the segment program
                pass
            jitter_chunked = await jitter_phase(generate2)
            await channel2.close()
            await app2.shutdown()
        finally:
            os.environ.pop("LLM_PREFILL_CHUNK", None)

    # ---- phase D: shared-system-prompt prefix cache, cold vs warm -------
    # Reboot with a paged pool: LLM_PAGE_SIZE turns on the framework radix
    # prefix cache (LLMServer). The same long system prefix + short user
    # suffixes: the first sightings prefill the whole prompt (cold), then
    # the cache auto-promotes the shared prefix and every later request
    # prefills only its suffix (warm). Skipped with phase C under the
    # headline watchdog budget (extra server boots).
    prefix_arm = None
    if not (os.environ.get("BENCH_SKIP_PREFIX",
                           "1" if skip_jitter else "0") == "1"):
        pfx_len = int(os.environ.get("BENCH_PREFIX_LEN",
                                     "384" if on_tpu else "24"))
        sfx_len = int(os.environ.get("BENCH_SUFFIX_LEN",
                                     "16" if on_tpu else "4"))
        reps = int(os.environ.get("BENCH_PREFIX_REPS",
                                  "12" if on_tpu else "6"))
        os.environ["LLM_PAGE_SIZE"] = "16" if on_tpu else "8"
        app3 = channel3 = None
        try:
            app3 = build_app()
            await boot(app3)
            channel3 = grpc.aio.insecure_channel(
                f"127.0.0.1:{ports['GRPC_PORT']}")
            generate3 = channel3.unary_stream(
                "/llm.Chat/Generate",
                request_serializer=lambda o: json.dumps(o).encode(),
                response_deserializer=lambda raw: (json.loads(raw)
                                                   if raw else {}),
            )
            async for _ in generate3(req(4)):   # warm compiles
                pass
            shared = rng.integers(1, vocab_hi, (pfx_len,)).tolist()

            async def prefixed_request() -> tuple[float, float, int]:
                body = {"prompt_ids":
                        shared + rng.integers(1, vocab_hi,
                                              (sfx_len,)).tolist(),
                        "max_new_tokens": max(16, max_new // 8)}
                t0 = time.perf_counter()
                first = None
                count = 0
                async for msg in generate3(body):
                    got = n_toks(msg)
                    if first is None and got:
                        first = time.perf_counter() - t0
                    count += got
                return first or 0.0, time.perf_counter() - t0, count

            saved0 = await _metrics_counter(
                ports, "app_ml_prefill_tokens_saved_total")
            # cold: the first two sightings (insert, then promote —
            # promotion itself pays one prefix prefill)
            cold = [await prefixed_request() for _ in range(2)]
            warm = [await prefixed_request() for _ in range(max(reps - 2, 1))]
            saved1 = await _metrics_counter(
                ports, "app_ml_prefill_tokens_saved_total")
            prefix_arm = {
                "prefix_len": pfx_len,
                "suffix_len": sfx_len,
                "requests": len(cold) + len(warm),
                "cold_ttft_ms": round(cold[0][0] * 1e3, 1),
                "warm_p50_ttft_ms": round(
                    percentile([w[0] for w in warm], 50) * 1e3, 1),
                "cold_tok_s": round(
                    sum(c[2] for c in cold) / max(sum(c[1] for c in cold),
                                                  1e-9), 1),
                "warm_tok_s": round(
                    sum(w[2] for w in warm) / max(sum(w[1] for w in warm),
                                                  1e-9), 1),
                "prefill_tokens_saved": int(saved1 - saved0),
            }
        except Exception as exc:  # optional arm: record, don't abort
            prefix_arm = {"error": str(exc)}
        finally:
            # a failed optional arm must not leak the booted server or
            # abort the run before emit() records phases A-C
            os.environ.pop("LLM_PAGE_SIZE", None)
            if channel3 is not None:
                await channel3.close()
            if app3 is not None:
                await app3.shutdown()

    # ---- phase E: adaptive token-budget scheduler, fixed vs adaptive ----
    # Same mixed-load interference as phase C plus STEADY decode streams,
    # so the number pair is (TTFT under prefill pressure, sustained tok/s):
    # the adaptive scheduler must improve the former without giving up the
    # latter. Two boots (fixed via GOFR_ML_TOKEN_BUDGET=0, then adaptive) —
    # skipped under the headline watchdog budget unless BENCH_SCHED_ARM=1
    # (bench/run_all.py sets it).
    sched_arm = None
    if os.environ.get("BENCH_SCHED_ARM",
                      "0" if skip_jitter else "1") == "1":
        steady_new = int(os.environ.get("BENCH_SCHED_STEADY_NEW",
                                        "128" if on_tpu else "24"))
        # several segments per long prompt: the scheduler's batched-segment
        # advantage scales with prefill length, and 3-segment prompts
        # drown in CPU dispatch noise (7 * 16 = 112 stays inside the tiny
        # preset's 128-token max_seq with decode room)
        long_e = int(os.environ.get("BENCH_SCHED_LONG",
                                    str(long_len) if on_tpu
                                    else str(7 * seg)))
        ident_prompt = rng.integers(1, vocab_hi, (prompt_len,)).tolist()

        window_s = float(os.environ.get("BENCH_SCHED_WINDOW_S", "1.6"))
        reps = int(os.environ.get("BENCH_SCHED_REPS", "2"))

        async def sched_window(gen_fn) -> dict:
            """One fixed-length window of mixed load: short-probe TTFT +
            steady-stream tok/s under open-loop long-prompt arrivals (a
            closed loop would let the faster arm generate more
            interference for itself and bias the A/B). The window is
            TIME-bounded so both arms face the same arrival count."""
            stop = asyncio.Event()
            steady_tokens = [0]
            long_done = [0]

            async def steady_loop():
                while not stop.is_set():
                    async for msg in gen_fn(req(steady_new)):
                        steady_tokens[0] += n_toks(msg)
                        if stop.is_set():
                            break

            async def one_long():
                body = {"prompt_ids": rng.integers(
                            1, vocab_hi, (long_e,)).tolist(),
                        "max_new_tokens": 4}
                async for _ in gen_fn(body):
                    break  # the prefill is the interference
                long_done[0] += 1

            async def long_loop():
                pending = []
                while not stop.is_set():
                    pending.append(asyncio.create_task(one_long()))
                    await asyncio.sleep(0.06)
                for t in pending:
                    t.cancel()
                await asyncio.gather(*pending, return_exceptions=True)

            # one of each: with the CPU default of 4 slots, more
            # interferers would make probe TTFT measure SLOT contention
            # (admission queueing) instead of dispatch-iteration latency —
            # the thing the scheduler actually changes
            steady = [asyncio.create_task(steady_loop())]
            longs = [asyncio.create_task(long_loop())]
            ttfts: list[float] = []
            t0 = time.perf_counter()
            try:
                while time.perf_counter() - t0 < window_s:
                    t1 = time.perf_counter()
                    async for _ in gen_fn(req(8)):
                        ttfts.append(time.perf_counter() - t1)
                        break
                    await asyncio.sleep(0.05)
            finally:
                window = time.perf_counter() - t0
                stop.set()
                for t in steady + longs:
                    t.cancel()
                await asyncio.gather(*steady, *longs,
                                     return_exceptions=True)
            return {
                "p50_ttft_ms": round(percentile(ttfts, 50) * 1e3, 1),
                "p99_ttft_ms": round(percentile(ttfts, 99) * 1e3, 1),
                "steady_tok_s": round(steady_tokens[0] / window, 1),
                "long_prompts_served": long_done[0],
                "probes": len(ttfts),
            }

        async def sched_phase(gen_fn) -> dict:
            """Best of ``reps`` windows by steady tok/s — the same
            selection rule for both arms picks each arm's least
            OS-interfered window (this box shares 2 cores between the
            serving thread, the event loop, and XLA; single windows swing
            ~2x run to run)."""
            runs = [await sched_window(gen_fn) for _ in range(reps)]
            return max(runs, key=lambda r: r["steady_tok_s"])

        arms: dict = {}
        ident_tokens: dict = {}
        for mode in ("fixed", "adaptive"):
            os.environ["LLM_PREFILL_CHUNK"] = str(seg)
            if mode == "fixed":
                os.environ["GOFR_ML_TOKEN_BUDGET"] = "0"
            else:
                os.environ.pop("GOFR_ML_TOKEN_BUDGET", None)  # auto
            appE = chE = None
            try:
                appE = build_app()
                await boot(appE)
                chE = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genE = chE.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                async for _ in genE(req(4)):        # warm compiles
                    pass
                warm_long = {"prompt_ids": rng.integers(
                                 1, vocab_hi, (long_e,)).tolist(),
                             "max_new_tokens": 4}
                async for _ in genE(warm_long):     # warm segment program
                    pass
                toks: list = []
                async for msg in genE({"prompt_ids": ident_prompt,
                                       "max_new_tokens": 16}):
                    toks.extend(msg.get("tokens", ()))
                ident_tokens[mode] = toks
                arms[mode] = await sched_phase(genE)
            except Exception as exc:    # optional arm: record, don't abort
                arms[mode] = {"error": str(exc)}
            finally:
                os.environ.pop("GOFR_ML_TOKEN_BUDGET", None)
                os.environ.pop("LLM_PREFILL_CHUNK", None)
                if chE is not None:
                    await chE.close()
                if appE is not None:
                    await appE.shutdown()
        sched_arm = {
            "prefill_chunk": seg,
            "long_prompt_len": long_e,
            "fixed": arms.get("fixed"),
            "adaptive": arms.get("adaptive"),
            # bit-identity of the greedy probe across the two boots — the
            # scheduler only reshapes dispatches, never tokens
            "tokens_identical": (ident_tokens.get("fixed")
                                 == ident_tokens.get("adaptive")
                                 if len(ident_tokens) == 2 else None),
        }

    # ---- phase F: tiered KV cache — host offload A/B --------------------
    # Rotating system prompts deliberately overflow the HBM page pool so
    # every rotation evicts the oldest prefix. Offload ON turns those
    # evictions into host-RAM spills and warm hits into DMA restores;
    # OFF (GOFR_ML_KV_HOST_BUDGET_MB=0) recomputes the prefill each time.
    # Two boots, same prompt set + greedy probe for token identity —
    # skipped under the headline watchdog budget unless BENCH_OFFLOAD_ARM=1
    # (bench/run_all.py sets it).
    offload_arm = None
    if os.environ.get("BENCH_OFFLOAD_ARM",
                      "0" if skip_jitter else "1") == "1":
        page_f = int(os.environ.get("BENCH_OFFLOAD_PAGE",
                                    "16" if on_tpu else "8"))
        # one past a page boundary: a page-ALIGNED prefix registers one
        # token short (prefix_cache._reg_len_for) and would share a page
        # less than the sizing below assumes
        pfx_len_f = int(os.environ.get("BENCH_OFFLOAD_PREFIX_LEN",
                                       "385" if on_tpu else "25"))
        sfx_len_f = int(os.environ.get("BENCH_OFFLOAD_SUFFIX_LEN",
                                       "16" if on_tpu else "4"))
        n_sys = int(os.environ.get("BENCH_OFFLOAD_PROMPTS", "6"))
        new_f = max(16, max_new // 8) if on_tpu else 8
        pages_per = pfx_len_f // page_f
        # pool holds HALF the rotating set (N resident, 2N rotating) plus
        # one live slot's worst case and the scratch page
        slot_pages = -(-(pfx_len_f + sfx_len_f + new_f + 8) // page_f)
        pool_f = (n_sys // 2) * pages_per + slot_pages + 1
        shared_f = [rng.integers(1, vocab_hi, (pfx_len_f,)).tolist()
                    for _ in range(n_sys)]
        ident_sfx = rng.integers(1, vocab_hi, (sfx_len_f,)).tolist()

        async def offload_window(gen_fn) -> dict:
            """One boot's traffic: a cold rotation (every prefix promotes,
            later rotations evict earlier prefixes), then warm rotations
            whose hits either restore (offload on) or re-prefill (off)."""
            async def one(prefix_ids, sfx_ids) -> tuple[float, int]:
                body = {"prompt_ids": prefix_ids + sfx_ids,
                        "max_new_tokens": new_f}
                t0 = time.perf_counter()
                first = None
                count = 0
                async for msg in gen_fn(body):
                    got = n_toks(msg)
                    if first is None and got:
                        first = time.perf_counter() - t0
                    count += got
                return first or 0.0, count

            # cold pass: two sightings each (insert, then promote)
            for p in shared_f:
                await one(p, rng.integers(1, vocab_hi,
                                          (sfx_len_f,)).tolist())
                await one(p, rng.integers(1, vocab_hi,
                                          (sfx_len_f,)).tolist())
            saved0 = await _metrics_counter(
                ports, "app_ml_prefill_tokens_saved_total")
            pool0 = await _debug_pool(ports)
            warm_ttfts: list[float] = []
            rounds = int(os.environ.get("BENCH_OFFLOAD_ROUNDS", "2"))
            for _ in range(rounds):
                for p in shared_f:
                    ttft, _ = await one(p, rng.integers(
                        1, vocab_hi, (sfx_len_f,)).tolist())
                    warm_ttfts.append(ttft)
            saved1 = await _metrics_counter(
                ports, "app_ml_prefill_tokens_saved_total")
            pool1 = await _debug_pool(ports)
            restores_d = (pool1.get("kv_restores", 0)
                          - pool0.get("kv_restores", 0))
            reprefills_d = (pool1.get("prefix_prefills", 0)
                            - pool0.get("prefix_prefills", 0))
            return {
                "warm_p50_ttft_ms": round(
                    percentile(warm_ttfts, 50) * 1e3, 1),
                "warm_p99_ttft_ms": round(
                    percentile(warm_ttfts, 99) * 1e3, 1),
                "warm_requests": len(warm_ttfts),
                # the recomputed-vs-restored ledger over the warm window:
                # a discard-arm re-hit pays a prefix PREFILL
                # (prefix_prefills moves), an offload-arm re-hit pays a
                # DMA (kv_restores moves); both then admit suffix-only
                # (the saved counter moves identically)
                "prefill_tokens_saved": int(saved1 - saved0),
                "prefill_tokens_recomputed": int(reprefills_d * pfx_len_f),
                "prefill_tokens_restored": int(
                    restores_d * pages_per * page_f),
                "restores": int(restores_d),
                "prefix_reprefills": int(reprefills_d),
                "spills": int(pool1.get("kv_spills", 0)
                              - pool0.get("kv_spills", 0)),
            }

        arms_f: dict = {}
        ident_f: dict = {}
        for mode in ("offload", "discard"):
            os.environ["LLM_PAGE_SIZE"] = str(page_f)
            os.environ["LLM_PAGES"] = str(pool_f)
            os.environ["GOFR_ML_KV_HOST_BUDGET_MB"] = (
                os.environ.get("BENCH_OFFLOAD_BUDGET_MB", "256")
                if mode == "offload" else "0")
            appF = chF = None
            try:
                appF = build_app()
                await boot(appF)
                chF = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genF = chF.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                async for _ in genF(req(4)):        # warm compiles
                    pass
                # greedy identity probe: collected per arm, compared below
                toks_f: list = []
                async for msg in genF({"prompt_ids":
                                       shared_f[0] + ident_sfx,
                                       "max_new_tokens": new_f}):
                    toks_f.extend(msg.get("tokens", ()))
                ident_f[mode] = toks_f
                arms_f[mode] = await offload_window(genF)
            except Exception as exc:    # optional arm: record, don't abort
                arms_f[mode] = {"error": str(exc)}
            finally:
                os.environ.pop("GOFR_ML_KV_HOST_BUDGET_MB", None)
                os.environ.pop("LLM_PAGE_SIZE", None)
                os.environ.pop("LLM_PAGES", None)
                if chF is not None:
                    await chF.close()
                if appF is not None:
                    await appF.shutdown()
        offload_arm = {
            "page_size": page_f,
            "n_pages": pool_f,
            "prefix_len": pfx_len_f,
            "rotating_prompts": n_sys,
            "offload": arms_f.get("offload"),
            "discard": arms_f.get("discard"),
            # bit-identity of the greedy probe across the two boots: the
            # tier moves KV bytes, never changes tokens
            "tokens_identical": (ident_f.get("offload")
                                 == ident_f.get("discard")
                                 if len(ident_f) == 2 else None),
        }

    # ---- phase G: resilience — fault arm vs clean arm -------------------
    # Same mixed traffic against two boots: one with GOFR_ML_FAULT arming
    # probabilistic step faults (the generator watchdog recovers between
    # crashes), one clean. The invariant under test: every client ends in
    # valid output or a TYPED gRPC error within the hang budget — never a
    # hang — while the fault arm's restart counter moves and the clean
    # arm's stays zero (the resilience layer priced at nothing when idle).
    # Skipped under the headline watchdog budget unless BENCH_FAULT_ARM=1
    # (bench/run_all.py sets it).
    fault_arm = None
    if os.environ.get("BENCH_FAULT_ARM",
                      "0" if skip_jitter else "1") == "1":
        n_req_g = int(os.environ.get("BENCH_FAULT_REQUESTS",
                                     "48" if on_tpu else "12"))
        new_g = max(8, max_new // 8) if on_tpu else 8
        spec_g = os.environ.get("BENCH_FAULT_SPEC",
                                "step:0.05:RuntimeError")
        hang_s = float(os.environ.get("BENCH_FAULT_HANG_S", "180"))
        typed_codes = {grpc.StatusCode.UNAVAILABLE,
                       grpc.StatusCode.RESOURCE_EXHAUSTED,
                       grpc.StatusCode.DEADLINE_EXCEEDED}

        async def fault_window(gen_fn) -> dict:
            outcome = {"ok": 0, "typed_errors": 0, "other_errors": 0}
            tokens_box = [0]
            t0 = time.perf_counter()

            async def one() -> None:
                body = {"prompt_ids": rng.integers(
                            1, vocab_hi, (prompt_len,)).tolist(),
                        "max_new_tokens": new_g}
                try:
                    got = 0
                    async for msg in gen_fn(body):
                        got += n_toks(msg)
                    outcome["ok"] += 1
                    tokens_box[0] += got
                except grpc.aio.AioRpcError as exc:
                    key = ("typed_errors" if exc.code() in typed_codes
                           else "other_errors")
                    outcome[key] += 1

            tasks = [asyncio.create_task(one()) for _ in range(n_req_g)]
            _, pending = await asyncio.wait(tasks, timeout=hang_s)
            for t in pending:   # a pending task past the budget IS a hang
                t.cancel()
            elapsed_g = time.perf_counter() - t0
            res = await _debug_resilience(ports)
            restarts = (res.get("restarts") or {}).get("total", 0)
            return {
                **outcome,
                "hangs": len(pending),
                "requests": n_req_g,
                "elapsed_s": round(elapsed_g, 2),
                "tok_per_s": round(tokens_box[0] / elapsed_g, 1),
                "generator_restarts": restarts,
                "state": res.get("state"),
                "shed": res.get("shed"),
                "deadline_expired": res.get("deadline_expired"),
                "fault": res.get("fault"),
            }

        arms_g: dict = {}
        for mode in ("clean", "fault"):
            if mode == "fault":
                os.environ["GOFR_ML_FAULT"] = spec_g
                # generous budget: the arm measures recovery, not death
                os.environ["GOFR_ML_MAX_RESTARTS"] = os.environ.get(
                    "BENCH_FAULT_MAX_RESTARTS", "1000")
            appG = chG = None
            try:
                appG = build_app()
                await boot(appG)
                chG = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genG = chG.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                try:
                    async for _ in genG(req(4)):    # warm compiles
                        pass
                except grpc.aio.AioRpcError:
                    # the fault arm may crash the very first dispatch —
                    # that's the feature under test, not a boot failure
                    # (warmup compiled everything server-side regardless)
                    if mode != "fault":
                        raise
                arms_g[mode] = await fault_window(genG)
            except Exception as exc:    # optional arm: record, don't abort
                arms_g[mode] = {"error": str(exc)}
            finally:
                os.environ.pop("GOFR_ML_FAULT", None)
                os.environ.pop("GOFR_ML_MAX_RESTARTS", None)
                if chG is not None:
                    await chG.close()
                if appG is not None:
                    await appG.shutdown()
        clean_g, faulted_g = arms_g.get("clean", {}), arms_g.get("fault", {})
        fault_arm = {
            "fault_spec": spec_g,
            "clean": clean_g,
            "fault": faulted_g,
            # the headline invariant: nobody hangs, in either arm, and
            # the fault arm actually exercised recovery
            "no_hangs": (clean_g.get("hangs") == 0
                         and faulted_g.get("hangs") == 0
                         if "hangs" in clean_g and "hangs" in faulted_g
                         else None),
            "recovered_crashes": faulted_g.get("generator_restarts"),
        }

    # ---- phase H: flight recorder — per-phase stall attribution ---------
    # The same steady-decode + long-prompt mixed load against two boots:
    # recorder ON (default) records WHERE each dispatch's wall time goes
    # (queue pop / decide / assemble / dispatch / device wait / emit /
    # other, from /debug/serving's stalls block) next to the realized
    # step_ms and steady tok/s; recorder OFF (GOFR_ML_FLIGHT_RECORDER=0)
    # reruns the identical window so the recorder's own overhead is a
    # measured number, not a promise (acceptance: <= 2%). This is the
    # breakdown ROADMAP 3c reads to attribute the ~101 ms tiny-preset
    # step time before attacking it.
    # Skipped under the headline watchdog budget unless BENCH_STALL_ARM=1
    # (bench/run_all.py sets it).
    stall_arm = None
    if os.environ.get("BENCH_STALL_ARM",
                      "0" if skip_jitter else "1") == "1":
        window_h = float(os.environ.get("BENCH_STALL_WINDOW_S", "1.6"))
        reps_h = int(os.environ.get("BENCH_STALL_REPS", "2"))
        steady_new_h = int(os.environ.get("BENCH_STALL_STEADY_NEW",
                                          "128" if on_tpu else "24"))
        long_h = int(os.environ.get("BENCH_STALL_LONG",
                                    str(long_len) if on_tpu
                                    else str(5 * seg)))

        async def stall_window(gen_fn) -> dict:
            """One time-bounded mixed-load window: a steady decode stream
            (tok/s — the overhead A/B number) under open-loop long-prompt
            arrivals (so assemble/prefill phases actually exercise)."""
            stop = asyncio.Event()
            steady_tokens = [0]

            async def steady_loop():
                while not stop.is_set():
                    async for msg in gen_fn(req(steady_new_h)):
                        steady_tokens[0] += n_toks(msg)
                        if stop.is_set():
                            break

            async def long_loop():
                pending = []
                while not stop.is_set():
                    body = {"prompt_ids": rng.integers(
                                1, vocab_hi, (long_h,)).tolist(),
                            "max_new_tokens": 4}

                    async def one(b=body):
                        async for _ in gen_fn(b):
                            break

                    pending.append(asyncio.create_task(one()))
                    await asyncio.sleep(0.08)
                for t in pending:
                    t.cancel()
                await asyncio.gather(*pending, return_exceptions=True)

            tasks = [asyncio.create_task(steady_loop()),
                     asyncio.create_task(long_loop())]
            t0 = time.perf_counter()
            try:
                await asyncio.sleep(window_h)
            finally:
                window = time.perf_counter() - t0
                stop.set()
                for t in tasks:
                    t.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
            return {"steady_tok_s": round(steady_tokens[0] / window, 1)}

        arms_h: dict = {}
        # pin BOTH observability knobs explicitly PER ARM (an ambient
        # operator-set GOFR_ML_FLIGHT_RECORDER=0 / GOFR_ML_JOURNEY=0
        # would otherwise turn the A/B into off-vs-off) and restore the
        # operator's values afterwards. Three arms price the layers
        # separately: recorder+journeys on (the shipped default),
        # journeys off (the journey tracer's own cost), everything off
        # (the PR-10-baseline floor the acceptance bound compares to).
        prior_rec_env = os.environ.get("GOFR_ML_FLIGHT_RECORDER")
        prior_jrn_env = os.environ.get("GOFR_ML_JOURNEY")
        for mode, rec_knob, jrn_knob in (("recorder", "1", None),
                                         ("journeys_off", "1", "0"),
                                         ("off", "0", "0")):
            os.environ["GOFR_ML_FLIGHT_RECORDER"] = rec_knob
            if jrn_knob is None:
                os.environ.pop("GOFR_ML_JOURNEY", None)
            else:
                os.environ["GOFR_ML_JOURNEY"] = jrn_knob
            appH = chH = None
            try:
                appH = build_app()
                await boot(appH)
                chH = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genH = chH.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                async for _ in genH(req(4)):        # warm compiles
                    pass
                warm_long_h = {"prompt_ids": rng.integers(
                                   1, vocab_hi, (long_h,)).tolist(),
                               "max_new_tokens": 4}
                async for _ in genH(warm_long_h):   # warm long buckets
                    pass
                # best of reps_h windows, the phase-E selection rule: the
                # overhead A/B compares each arm's least OS-interfered
                # window (single windows swing ~2x on this shared box)
                runs_h = [await stall_window(genH) for _ in range(reps_h)]
                arm = max(runs_h, key=lambda r: r["steady_tok_s"])
                if mode == "recorder":
                    stalls = await _debug_stalls(ports)
                    win = stalls.get("window", {})
                    arm.update({
                        "dispatches": stalls.get("dispatches"),
                        "step_ms": win.get("per_dispatch_ms"),
                        "phases": {name: p.get("share")
                                   for name, p in
                                   win.get("phases", {}).items()},
                        "top_stall": stalls.get("top_stall"),
                        "attributed_share": stalls.get("attributed_share"),
                    })
                    journeys = await _debug_requests(ports)
                    if journeys.get("enabled"):
                        # per-request attribution next to the per-dispatch
                        # one: where the requests' wall actually went
                        arm["journeys"] = {
                            "finished": journeys.get("finished"),
                            "wall": journeys.get("wall"),
                            "marks": {
                                name: p.get("p50_ms")
                                for name, p in
                                journeys.get("marks", {}).items()},
                            "finish_reasons":
                                journeys.get("finish_reasons"),
                        }
                arms_h[mode] = arm
            except Exception as exc:    # optional arm: record, don't abort
                arms_h[mode] = {"error": str(exc)}
            finally:
                if chH is not None:
                    await chH.close()
                if appH is not None:
                    await appH.shutdown()
        if prior_rec_env is None:
            os.environ.pop("GOFR_ML_FLIGHT_RECORDER", None)
        else:
            os.environ["GOFR_ML_FLIGHT_RECORDER"] = prior_rec_env
        if prior_jrn_env is None:
            os.environ.pop("GOFR_ML_JOURNEY", None)
        else:
            os.environ["GOFR_ML_JOURNEY"] = prior_jrn_env
        rec_h, off_h = arms_h.get("recorder", {}), arms_h.get("off", {})
        joff_h = arms_h.get("journeys_off", {})
        overhead = journey_overhead = None
        if rec_h.get("steady_tok_s") and off_h.get("steady_tok_s"):
            overhead = round(
                100.0 * (1 - rec_h["steady_tok_s"] / off_h["steady_tok_s"]),
                2)
        if rec_h.get("steady_tok_s") and joff_h.get("steady_tok_s"):
            # the journey tracer's OWN cost: both-on vs recorder-only
            journey_overhead = round(
                100.0 * (1 - rec_h["steady_tok_s"]
                         / joff_h["steady_tok_s"]), 2)
        stall_arm = {
            "long_prompt_len": long_h,
            "recorder": rec_h,
            "journeys_off": joff_h,
            "recorder_off": off_h,
            # recorder-on vs recorder-off steady decode: the acceptance
            # bound is <= 2% (negative = measurement noise in our favor)
            "recorder_overhead_pct": overhead,
            "journey_overhead_pct": journey_overhead,
        }

    # ---- phase I: speculative serving — spec x KV-precision grid --------
    # For each KV precision (fp16 reference / int8 / packed int4) over
    # the SAME paged pool, boot spec-off and spec-on (LLM_SPEC_K with the
    # adaptive floor armed) and measure steady decode tok/s, realized
    # step_ms + per-phase breakdown, and the accept-rate/disable state.
    # Greedy token identity is asserted spec-on vs spec-off per precision
    # (speculation is lossless by construction; precisions differ).
    # Skipped under the headline watchdog budget unless BENCH_SPEC_ARM=1
    # (bench/run_all.py sets it).
    spec_arm = None
    if os.environ.get("BENCH_SPEC_ARM",
                      "0" if skip_jitter else "1") == "1":
        window_i = float(os.environ.get("BENCH_SPEC_WINDOW_S", "1.6"))
        # best-of-3 windows per cell (the phase-E selection rule): single
        # windows swing ~2x on this shared box and the A/B sign must not
        reps_i = int(os.environ.get("BENCH_SPEC_REPS", "3"))
        steady_new_i = int(os.environ.get("BENCH_SPEC_STEADY_NEW",
                                          "128" if on_tpu else "96"))
        spec_k_i = os.environ.get("BENCH_SPEC_K", "4")
        page_i = "16" if on_tpu else "8"
        kv_grid = [b.strip() for b in os.environ.get(
            "BENCH_SPEC_KV_GRID", "16,8,4").split(",") if b.strip()]
        # draft source for the spec-on arms: "" = prompt lookup (default)
        # or "self" (the draft-model machinery at its acceptance ceiling).
        # The steady workload below is repetition-heavy — prompt-lookup
        # decoding's target workload (extractive/templated generation);
        # fully-random streams are the ADVERSARIAL case, which is what
        # the adaptive per-slot disable handles (tests cover it)
        draft_i = os.environ.get("BENCH_SPEC_DRAFT", "")
        # identity dtype: bf16 rounding can flip near-tie argmaxes
        # BETWEEN program shapes (window vs step) — numeric noise. The
        # tiny/CPU grid runs f32 so the lossless check is exact; on TPU
        # the preset's serving dtype stands
        dtype_i = os.environ.get("BENCH_SPEC_DTYPE",
                                 "" if on_tpu else "float32")
        ident_prompt_i = rng.integers(1, vocab_hi, (prompt_len,)).tolist()
        # repetition-heavy steady prompt: a short motif tiled to 3x the
        # probe prompt length — trailing-n-gram lookup finds real matches
        motif_i = rng.integers(1, vocab_hi, (4,)).tolist()
        steady_prompt_i = (motif_i * (3 * max(prompt_len, 8)))[
            :3 * max(prompt_len, 8)]

        # concurrent steady streams: fill the slot batch so the window
        # measures aggregate decode throughput, not one stream's latency
        streams_i = int(os.environ.get("BENCH_SPEC_STREAMS",
                                       "8" if on_tpu else "4"))

        async def spec_window(gen_fn) -> dict:
            """One time-bounded steady-decode window (pure decode load —
            the number speculation is supposed to move)."""
            stop = asyncio.Event()
            steady_tokens = [0]

            async def steady_loop():
                while not stop.is_set():
                    body = {"prompt_ids": steady_prompt_i,
                            "max_new_tokens": steady_new_i}
                    async for msg in gen_fn(body):
                        steady_tokens[0] += n_toks(msg)
                        if stop.is_set():
                            break

            tasks = [asyncio.create_task(steady_loop())
                     for _ in range(streams_i)]
            t0 = time.perf_counter()
            try:
                await asyncio.sleep(window_i)
            finally:
                window = time.perf_counter() - t0
                stop.set()
                for task in tasks:
                    task.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
            return {"steady_tok_s": round(steady_tokens[0] / window, 1)}

        grid: dict = {}
        for bits in kv_grid:
            cells: dict = {}
            ident_i: dict = {}
            for mode in ("off", "on"):
                os.environ["LLM_PAGE_SIZE"] = page_i  # int4 needs paging;
                # paged everywhere so the grid varies ONE thing per axis
                if dtype_i:
                    os.environ["LLAMA_DTYPE"] = dtype_i
                if bits != "16":
                    os.environ["GOFR_ML_KV_BITS"] = bits
                if mode == "on":
                    os.environ["LLM_SPEC_K"] = spec_k_i
                    if draft_i:
                        os.environ["LLM_DRAFT_PRESET"] = draft_i
                    os.environ["GOFR_ML_SPEC_MIN_ACCEPT"] = os.environ.get(
                        "BENCH_SPEC_MIN_ACCEPT", "0.05")
                appI = chI = None
                try:
                    appI = build_app()
                    await boot(appI)
                    chI = grpc.aio.insecure_channel(
                        f"127.0.0.1:{ports['GRPC_PORT']}")
                    genI = chI.unary_stream(
                        "/llm.Chat/Generate",
                        request_serializer=lambda o: json.dumps(o).encode(),
                        response_deserializer=lambda raw: (json.loads(raw)
                                                           if raw else {}),
                    )
                    async for _ in genI(req(4)):        # warm compiles
                        pass
                    toks_i: list = []
                    async for msg in genI({"prompt_ids": ident_prompt_i,
                                           "max_new_tokens": 16}):
                        toks_i.extend(msg.get("tokens", ()))
                    ident_i[mode] = toks_i
                    # warm the steady shape TWICE: the second sighting
                    # promotes the shared prompt in the radix cache, so
                    # the suffix-prefill program compiles here and not
                    # inside the timed window (int4's compile is the
                    # slowest of the grid)
                    for _ in range(2):
                        async for _ in genI({"prompt_ids": steady_prompt_i,
                                             "max_new_tokens": 8}):
                            pass
                    runs_i = [await spec_window(genI)
                              for _ in range(reps_i)]
                    cell = max(runs_i, key=lambda r: r["steady_tok_s"])
                    entry = await _debug_llm(ports)
                    stalls = entry.get("stalls", {})
                    win = stalls.get("window", {})
                    cell.update({
                        "step_ms": win.get("per_dispatch_ms"),
                        "phases": {name: p.get("share")
                                   for name, p in
                                   win.get("phases", {}).items()},
                        "top_stall": stalls.get("top_stall"),
                    })
                    pool = entry.get("pool", {})
                    cell["page_bytes"] = pool.get("page_bytes")
                    if mode == "on":
                        spec_block = entry.get("speculation", {})
                        cell["accept_rate"] = spec_block.get("accept_rate")
                        cell["spec_windows"] = spec_block.get("windows")
                        cell["disables"] = spec_block.get("disables_total")
                        cell["reprobes"] = spec_block.get("reprobes_total")
                    cells[mode] = cell
                except Exception as exc:  # optional arm: record, don't abort
                    cells[mode] = {"error": str(exc)}
                finally:
                    os.environ.pop("GOFR_ML_KV_BITS", None)
                    os.environ.pop("LLM_SPEC_K", None)
                    os.environ.pop("LLM_DRAFT_PRESET", None)
                    os.environ.pop("GOFR_ML_SPEC_MIN_ACCEPT", None)
                    os.environ.pop("LLM_PAGE_SIZE", None)
                    os.environ.pop("LLAMA_DTYPE", None)
                    if chI is not None:
                        await chI.close()
                    if appI is not None:
                        await appI.shutdown()
            off_i, on_i = cells.get("off", {}), cells.get("on", {})
            speedup = None
            if off_i.get("steady_tok_s") and on_i.get("steady_tok_s"):
                speedup = round(
                    on_i["steady_tok_s"] / off_i["steady_tok_s"], 3)
            identical = (ident_i.get("off") == ident_i.get("on")
                         if len(ident_i) == 2 else None)
            grid[f"kv{bits}"] = {
                "off": off_i,
                "on": on_i,
                # spec-on vs spec-off at the SAME precision must be
                # token-identical — speculation is lossless under greedy
                "tokens_identical": identical,
                "spec_speedup": speedup,
            }
            if identical is False:
                # a lossless-contract violation is a bug report: keep the
                # evidence in the artifact
                grid[f"kv{bits}"]["ident_tokens"] = ident_i
        spec_arm = {
            "spec_k": int(spec_k_i),
            "page_size": int(page_i),
            "draft": draft_i or "lookup",
            "dtype": dtype_i or "preset-default",
            "grid": grid,
        }

    # ---- phase J: disaggregated prefill/decode A/B ----------------------
    # 2-replica pool, mixed load: STEADY short-prompt decode streams (the
    # TPOT side) + an open-loop burst of heavy prompts (the TTFT side).
    # Disagg ON routes the heavy prompts to the prefill-biased replica,
    # ships their prefix KV through the transport, and decodes them
    # suffix-only on the decode replica — prompt bursts stop competing
    # with steady decode for one token budget. Reports burst TTFT
    # p50/p99, steady TPOT p99 + tok/s, the ships/lands ledger from
    # /debug/serving, and greedy token identity across the two boots.
    # Skipped under the headline watchdog budget unless
    # BENCH_DISAGG_ARM=1 (bench/run_all.py sets it).
    disagg_arm = None
    if os.environ.get("BENCH_DISAGG_ARM",
                      "0" if skip_jitter else "1") == "1":
        window_j = float(os.environ.get("BENCH_DISAGG_WINDOW_S", "1.6"))
        reps_j = int(os.environ.get("BENCH_DISAGG_REPS", "2"))
        page_j = os.environ.get("BENCH_DISAGG_PAGE",
                                "16" if on_tpu else "8")
        steady_new_j = int(os.environ.get("BENCH_DISAGG_STEADY_NEW",
                                          "128" if on_tpu else "24"))
        long_j = int(os.environ.get("BENCH_DISAGG_LONG",
                                    str(long_len) if on_tpu else "32"))
        streams_j = int(os.environ.get("BENCH_DISAGG_STREAMS",
                                       "8" if on_tpu else "2"))
        ident_prompt_j = rng.integers(1, vocab_hi, (long_j,)).tolist()

        async def disagg_window(gen_fn) -> dict:
            """One time-bounded mixed-load window: steady decode streams
            measured for tok/s AND per-token cadence (TPOT), while heavy
            prompts arrive open-loop and their first-token latency is
            probed."""
            stop = asyncio.Event()
            steady_tokens = [0]
            tpot_gaps: list[float] = []
            burst_ttfts: list[float] = []
            long_done = [0]

            async def steady_loop():
                while not stop.is_set():
                    last = None
                    async for msg in gen_fn(req(steady_new_j)):
                        now = time.perf_counter()
                        n = n_toks(msg)
                        if last is not None and n:
                            tpot_gaps.append((now - last) / n)
                        last = now
                        steady_tokens[0] += n
                        if stop.is_set():
                            break

            async def one_long():
                body = {"prompt_ids": rng.integers(
                            1, vocab_hi, (long_j,)).tolist(),
                        "max_new_tokens": 8}
                t1 = time.perf_counter()
                async for _ in gen_fn(body):
                    burst_ttfts.append(time.perf_counter() - t1)
                    break
                long_done[0] += 1

            async def long_loop():
                pending = []
                while not stop.is_set():
                    pending.append(asyncio.create_task(one_long()))
                    await asyncio.sleep(0.08)
                for t in pending:
                    t.cancel()
                await asyncio.gather(*pending, return_exceptions=True)

            steady = [asyncio.create_task(steady_loop())
                      for _ in range(streams_j)]
            longs = [asyncio.create_task(long_loop())]
            t0 = time.perf_counter()
            try:
                await asyncio.sleep(window_j)
            finally:
                window = time.perf_counter() - t0
                stop.set()
                for t in steady + longs:
                    t.cancel()
                await asyncio.gather(*steady, *longs,
                                     return_exceptions=True)
            return {
                "burst_p50_ttft_ms": round(
                    percentile(burst_ttfts, 50) * 1e3, 1),
                "burst_p99_ttft_ms": round(
                    percentile(burst_ttfts, 99) * 1e3, 1),
                "steady_tpot_p99_ms": round(
                    percentile(tpot_gaps, 99) * 1e3, 2),
                "steady_tok_s": round(steady_tokens[0] / window, 1),
                "bursts_served": long_done[0],
            }

        armsJ: dict = {}
        ident_j: dict = {}
        for mode in ("off", "on"):
            os.environ["GOFR_ML_REPLICAS"] = "2"
            os.environ["LLM_PAGE_SIZE"] = page_j
            os.environ["LLM_PREFILL_CHUNK"] = str(seg)
            if mode == "on":
                os.environ["GOFR_ML_DISAGG"] = "1"
            appJ = chJ = None
            try:
                appJ = build_app()
                await boot(appJ)
                chJ = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genJ = chJ.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                async for _ in genJ(req(4)):        # warm compiles
                    pass
                warm_long = {"prompt_ids": rng.integers(
                                 1, vocab_hi, (long_j,)).tolist(),
                             "max_new_tokens": 4}
                async for _ in genJ(warm_long):     # warm heavy shapes
                    pass
                toks_j: list = []
                async for msg in genJ({"prompt_ids": ident_prompt_j,
                                       "max_new_tokens": 16}):
                    toks_j.extend(msg.get("tokens", ()))
                ident_j[mode] = toks_j
                runs_j = [await disagg_window(genJ)
                          for _ in range(reps_j)]
                cell = max(runs_j, key=lambda r: r["steady_tok_s"])
                entry = await _debug_llm(ports)
                routing = entry.get("routing", {})
                dis = routing.get("disagg") or {}
                cell["ships"] = dis.get("ships")
                cell["lands"] = dis.get("lands")
                cell["transport_failures"] = dis.get("failures")
                cell["prefill_replicas"] = dis.get("prefill_replicas")
                cell["routed"] = routing.get("routed")
                armsJ[mode] = cell
            except Exception as exc:    # optional arm: record, don't abort
                armsJ[mode] = {"error": str(exc)}
            finally:
                os.environ.pop("GOFR_ML_REPLICAS", None)
                os.environ.pop("GOFR_ML_DISAGG", None)
                os.environ.pop("LLM_PAGE_SIZE", None)
                os.environ.pop("LLM_PREFILL_CHUNK", None)
                if chJ is not None:
                    await chJ.close()
                if appJ is not None:
                    await appJ.shutdown()
        disagg_arm = {
            "replicas": 2,
            "page_size": int(page_j),
            "burst_prompt_len": long_j,
            "off": armsJ.get("off"),
            "on": armsJ.get("on"),
            # greedy probe across the two boots: the transport moves KV,
            # never changes tokens
            "tokens_identical": (ident_j.get("off") == ident_j.get("on")
                                 if len(ident_j) == 2 else None),
        }

    # ---- phase K: elastic fleet A/B -------------------------------------
    # Diurnal ramp over an elastic (1 -> 2 -> 1 autoscaled) vs a static
    # 2-replica fleet, plus a FORCED scale-down of the radix-cache
    # holder: warm-TTFT across the scale event (migrated cache restored
    # on the survivor) vs a cold-start prompt of the same length, the
    # fleet-size trace, the migration ledger (ships == adoptions +
    # failures), and greedy token identity across arms. Skipped under
    # the headline watchdog budget unless BENCH_ELASTIC_ARM=1
    # (bench/run_all.py sets it).
    elastic_arm = None
    if os.environ.get("BENCH_ELASTIC_ARM",
                      "0" if skip_jitter else "1") == "1":
        page_k = os.environ.get("BENCH_ELASTIC_PAGE",
                                "16" if on_tpu else "8")
        hot_len = int(os.environ.get("BENCH_ELASTIC_HOT",
                                     str(long_len) if on_tpu else "96"))
        ramp_s = float(os.environ.get("BENCH_ELASTIC_RAMP_S", "1.2"))
        hot_prompt_k = rng.integers(1, vocab_hi, (hot_len,)).tolist()
        ident_prompt_k = rng.integers(1, vocab_hi, (12,)).tolist()

        async def hot_ttft(gen_fn, prompt) -> float:
            t1 = time.perf_counter()
            async for _ in gen_fn({"prompt_ids": list(prompt),
                                   "max_new_tokens": 4}):
                return time.perf_counter() - t1
            return float("nan")

        armsK: dict = {}
        ident_k: dict = {}
        # both boots share the persistent XLA cache (always on, fixed
        # path): scale-ups replay compiles from disk (the production
        # story), and the TTFT probes time serving work, not first-use
        # compilation
        for mode in ("static", "elastic"):
            os.environ["LLM_PAGE_SIZE"] = page_k
            os.environ["LLM_PREFILL_CHUNK"] = str(seg)
            os.environ["GOFR_ML_KV_HOST_BUDGET_MB"] = "64"
            if mode == "static":
                os.environ["GOFR_ML_REPLICAS"] = "2"
            else:
                os.environ["GOFR_ML_REPLICAS"] = "2"
                os.environ["GOFR_ML_ELASTIC"] = "1"
                os.environ["GOFR_ML_REPLICAS_MAX"] = "3"
                os.environ["GOFR_ML_ELASTIC_INTERVAL_S"] = "0.2"
            appK = chK = None
            try:
                appK = build_app()
                await boot(appK)
                chK = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genK = chK.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                async for _ in genK(req(4)):        # warm compiles
                    pass
                toks_k: list = []
                async for msg in genK({"prompt_ids": ident_prompt_k,
                                       "max_new_tokens": 16}):
                    toks_k.extend(msg.get("tokens", ()))
                ident_k[mode] = toks_k
                pool = appK.container.ml.llm("chat")
                if mode == "elastic" and pool._steer is not None:
                    # CPU-preset cadence: the default hysteresis is
                    # sized for production diurnals (seconds of
                    # sustained pressure), not a 1.2 s bench ramp
                    pool._steer.interval_s = 0.15
                    pool._steer.up_after = 1
                    pool._steer.down_after = 3
                # warm every core's register/spill/migrate/restore
                # machinery (each core owns its jitted gather/scatter):
                # the probes below must time serving work, not XLA
                warm_ids = rng.integers(1, vocab_hi,
                                        (hot_len - 1,)).tolist()

                async def warm_cores() -> None:
                    if not hasattr(pool, "replicas"):
                        return
                    for i in range(len(pool.replicas)):
                        if i in pool._retired:
                            continue
                        core = pool.replicas[i]
                        try:
                            pid = await asyncio.to_thread(
                                core.register_prefix, warm_ids)
                            entry = await asyncio.to_thread(
                                core.export_resident_prefix, warm_ids,
                                pid)
                            if entry:
                                await asyncio.to_thread(
                                    core.import_prefix_kv, entry[0],
                                    entry[1], entry[2])
                                await core.generate(
                                    list(warm_ids) + [5], 2)
                        except Exception:
                            pass

                await warm_cores()
                # hot prompt: cold first use, promoted + registered on
                # the repeats, warm once affinity routes to the holder
                cold_ttft = await hot_ttft(genK, hot_prompt_k)
                for _ in range(3):
                    await hot_ttft(genK, hot_prompt_k)
                warm_ttft = await hot_ttft(genK, hot_prompt_k)
                # diurnal ramp: an open-loop burst (the up-slope), then
                # quiet (the down-slope); the fleet-size trace is polled
                # from /debug/serving's routing.elastic block
                trace: list[int] = []

                async def poll_fleet(stop_ev):
                    while not stop_ev.is_set():
                        entry = await _debug_llm(ports)
                        el = (entry.get("routing") or {}).get(
                            "elastic") or {}
                        if el.get("size"):
                            trace.append(el["size"])
                        await asyncio.sleep(0.1)

                stopK = asyncio.Event()
                poller = asyncio.create_task(poll_fleet(stopK))
                t0 = time.perf_counter()
                burst: list = []

                async def slow_req():
                    t1 = time.perf_counter()
                    first = None
                    async for _ in genK(req(24)):
                        if first is None:
                            first = time.perf_counter() - t1
                    return first if first is not None else float("nan")

                # up-slope: a front-loaded wave plus a trickle keeps the
                # fleet queue pressured for the whole ramp window
                burst.extend(asyncio.create_task(slow_req())
                             for _ in range(24))
                while time.perf_counter() - t0 < ramp_s:
                    burst.append(asyncio.create_task(slow_req()))
                    await asyncio.sleep(0.03)
                ramp_ttfts = [t for t in await asyncio.gather(*burst)
                              if t == t]
                await asyncio.sleep(1.5)            # the quiet slope
                stopK.set()
                await poller
                # forced scale-down of the HOT HOLDER (in-process: the
                # bench owns the app): migration ships the hot subtree
                # to the survivor, and the next hot probe restores
                # instead of re-prefilling
                post_warm = post_cold = None
                led = None
                if hasattr(pool, "remove_replica"):
                    if pool._steer is not None:
                        # park the autoscaler's floor at 2 so it cannot
                        # race the forced probe below (retiring the peer
                        # we just ensured)
                        pool._steer.n_min = 2
                    if pool.fleet_size() < 2:
                        # the autoscaler's quiet slope may have shrunk
                        # the fleet already: restore a peer so the
                        # forced scale-down has a survivor to migrate to
                        await asyncio.to_thread(pool.add_replica)
                    await warm_cores()  # autoscale-built cores too
                    holder = max(
                        (i for i in range(len(pool.replicas))
                         if i not in pool._retired),
                        key=lambda i: (
                            pool.replicas[i].prefix_cache.peek(
                                hot_prompt_k)[1]
                            if pool.replicas[i].prefix_cache else 0))
                    await asyncio.to_thread(pool.remove_replica, holder,
                                            drain_s=30.0)
                    post_warm = await hot_ttft(genK, hot_prompt_k)
                    post_cold = await hot_ttft(genK, rng.integers(
                        1, vocab_hi, (hot_len,)).tolist())
                    led = pool.routing_snapshot()["elastic"]["migrations"]
                armsK[mode] = {
                    "cold_ttft_ms": round(cold_ttft * 1e3, 1),
                    "warm_ttft_ms": round(warm_ttft * 1e3, 1),
                    "ramp_p50_ttft_ms": round(
                        percentile(ramp_ttfts, 50) * 1e3, 1),
                    "ramp_p99_ttft_ms": round(
                        percentile(ramp_ttfts, 99) * 1e3, 1),
                    "ramp_requests": len(ramp_ttfts),
                    "fleet_trace": trace[:64],
                    "post_scale_warm_ttft_ms": (
                        round(post_warm * 1e3, 1)
                        if post_warm is not None else None),
                    "post_scale_cold_ttft_ms": (
                        round(post_cold * 1e3, 1)
                        if post_cold is not None else None),
                    "migrations": led,
                }
            except Exception as exc:    # optional arm: record, don't abort
                armsK[mode] = {"error": str(exc)}
            finally:
                for k in ("GOFR_ML_REPLICAS", "GOFR_ML_ELASTIC",
                          "GOFR_ML_REPLICAS_MAX",
                          "GOFR_ML_ELASTIC_INTERVAL_S",
                          "GOFR_ML_KV_HOST_BUDGET_MB", "LLM_PAGE_SIZE",
                          "LLM_PREFILL_CHUNK"):
                    os.environ.pop(k, None)
                if chK is not None:
                    await chK.close()
                if appK is not None:
                    await appK.shutdown()
        elastic_arm = {
            "page_size": int(page_k),
            "hot_prompt_len": hot_len,
            "static": armsK.get("static"),
            "elastic": armsK.get("elastic"),
            # greedy probe across the two boots: scale events move KV,
            # never change tokens
            "tokens_identical": (
                ident_k.get("static") == ident_k.get("elastic")
                if len(ident_k) == 2 else None),
        }

    # ---- phase L: serving economics — goodput ledger + auto-profiler ----
    # Two boots sharing one traffic shape: a CLEAN run and a GOFR_ML_FAULT
    # chaos run (probabilistic step crashes + watchdog recoveries + a slice
    # of deadline-bound requests + speculation), each reporting the goodput
    # fraction, the wasted-token ledger by reason, and the auto-profiler
    # trigger count — and asserting the ledger BALANCES (delivered +
    # wasted == device tokens). The ledger is process-global, so each arm
    # reads per-model DELTAS around its own window.
    # Skipped under the headline watchdog budget unless BENCH_GOODPUT_ARM=1
    # (bench/run_all.py sets it).
    goodput_arm = None
    if os.environ.get("BENCH_GOODPUT_ARM",
                      "0" if skip_jitter else "1") == "1":
        from gofr_tpu.flight_recorder import event_log as _event_log
        from gofr_tpu.ml.goodput import goodput_ledger as _goodput_ledger

        n_req_l = int(os.environ.get("BENCH_GOODPUT_REQUESTS",
                                     "48" if on_tpu else "16"))
        new_l = max(8, max_new // 8) if on_tpu else 8
        spec_l = os.environ.get("BENCH_GOODPUT_FAULT",
                                "step:0.04:RuntimeError")
        deadline_every = 4  # every 4th request carries a tight TTL
        typed_codes_l = {grpc.StatusCode.UNAVAILABLE,
                         grpc.StatusCode.RESOURCE_EXHAUSTED,
                         grpc.StatusCode.DEADLINE_EXCEEDED}

        def _ledger_chat() -> dict:
            led = _goodput_ledger()
            return led.snapshot_model("chat") if led is not None else {}

        def _ledger_delta(before: dict, after: dict) -> dict:
            wasted = {
                r: after.get("wasted", {}).get(r, 0)
                - before.get("wasted", {}).get(r, 0)
                for r in set(after.get("wasted", {}))
                | set(before.get("wasted", {}))
            }
            wasted = {r: n for r, n in wasted.items() if n}
            delivered = (after.get("delivered", 0)
                         - before.get("delivered", 0))
            total = (after.get("device_tokens", 0)
                     - before.get("device_tokens", 0))
            return {
                "device_tokens": total,
                "delivered": delivered,
                "wasted": wasted,
                "goodput": (round(delivered / total, 4) if total else None),
                # the acceptance invariant, checked on the window's delta
                "balanced": delivered + sum(wasted.values()) == total,
            }

        async def goodput_window(gen_fn) -> dict:
            outcome = {"ok": 0, "typed_errors": 0, "other_errors": 0}
            # client-side delivered count: tokens received by requests
            # that COMPLETED — the independent observation the ledger's
            # delivered side must match (the in-ledger balance holds by
            # construction; this cross-check is the falsifiable one)
            client_delivered = [0]
            before = _ledger_chat()
            ev_cursor = _event_log().cursor

            async def one(i: int) -> None:
                body = {"prompt_ids": rng.integers(
                            1, vocab_hi, (prompt_len,)).tolist(),
                        "max_new_tokens": new_l}
                if i % deadline_every == 0:
                    body["deadline_s"] = 0.15  # some answers WILL miss
                try:
                    got = 0
                    async for msg in gen_fn(body):
                        got += n_toks(msg)
                    outcome["ok"] += 1
                    client_delivered[0] += got
                except grpc.aio.AioRpcError as exc:
                    key = ("typed_errors" if exc.code() in typed_codes_l
                           else "other_errors")
                    outcome[key] += 1

            # half-concurrent waves keep slots contended without hangs
            for lo in range(0, n_req_l, 8):
                await asyncio.gather(*(one(i)
                                       for i in range(lo,
                                                      min(lo + 8,
                                                          n_req_l))))
            after = _ledger_chat()
            profile_events = _event_log().query(
                since=ev_cursor, kind="profile")["events"]
            # the endpoint answers the same ledger the deltas came from
            import aiohttp

            endpoint_ok = False
            try:
                async with aiohttp.ClientSession() as s:
                    r = await s.get(f"http://127.0.0.1:"
                                    f"{ports['HTTP_PORT']}/debug/goodput")
                    endpoint_ok = (r.status == 200
                                   and (await r.json())["data"]["enabled"])
            except Exception:
                pass
            res = await _debug_resilience(ports)
            ledger = _ledger_delta(before, after)
            return {
                **outcome,
                "requests": n_req_l,
                "ledger": ledger,
                "client_delivered": client_delivered[0],
                # the falsifiable invariant: the ledger's delivered side
                # equals what completed clients actually received
                "delivered_matches_client": (
                    ledger["delivered"] == client_delivered[0]),
                "autoprof_captures": len(profile_events),
                "generator_restarts": (res.get("restarts") or {}
                                       ).get("total", 0),
                "endpoint_ok": bool(endpoint_ok),
            }

        arms_l: dict = {}
        for mode in ("clean", "chaos"):
            if mode == "chaos":
                os.environ["GOFR_ML_FAULT"] = spec_l
                os.environ["GOFR_ML_MAX_RESTARTS"] = os.environ.get(
                    "BENCH_GOODPUT_MAX_RESTARTS", "1000")
                # a regression under crash churn should auto-profile
                os.environ.setdefault("GOFR_ML_AUTOPROF_MULT", "1.5")
            os.environ["LLM_SPEC_K"] = os.environ.get(
                "BENCH_GOODPUT_SPEC_K", "2")  # spec_rejected in both arms
            appL = chL = None
            try:
                appL = build_app()
                await boot(appL)
                chL = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genL = chL.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                try:
                    async for _ in genL(req(4)):    # warm compiles
                        pass
                except grpc.aio.AioRpcError:
                    if mode != "chaos":
                        raise  # chaos may crash the first dispatch
                arms_l[mode] = await goodput_window(genL)
            except Exception as exc:    # optional arm: record, don't abort
                arms_l[mode] = {"error": str(exc)}
            finally:
                for k in ("GOFR_ML_FAULT", "GOFR_ML_MAX_RESTARTS",
                          "GOFR_ML_AUTOPROF_MULT", "LLM_SPEC_K"):
                    os.environ.pop(k, None)
                if chL is not None:
                    await chL.close()
                if appL is not None:
                    await appL.shutdown()
        clean_l = arms_l.get("clean", {})
        chaos_l = arms_l.get("chaos", {})
        goodput_arm = {
            "fault_spec": spec_l,
            "clean": clean_l,
            "chaos": chaos_l,
            # the acceptance invariant, both windows: the ledger balances
            # AND its delivered side matches the tokens completed clients
            # actually received (the half that can actually fail)
            "ledger_balanced": (
                (clean_l.get("ledger") or {}).get("balanced") is True
                and (chaos_l.get("ledger") or {}).get("balanced") is True
                and clean_l.get("delivered_matches_client") is True
                and chaos_l.get("delivered_matches_client") is True
                if "ledger" in clean_l and "ledger" in chaos_l else None),
        }

    # ---- phase M: serving time machine — traffic capture & replay -------
    # Capture a mixed-load window (priorities + deadlines) with
    # GOFR_ML_CAPTURE armed and price the capture overhead against a
    # capture-off boot of the SAME window; then replay the bundle at 1x
    # and 4x speed against a fresh capture-off boot, reporting the
    # output-digest identity rate (must be 1.0 greedy), TTFT/TPOT deltas
    # vs the recorded percentiles, and the goodput delta. Skipped under
    # the headline watchdog budget unless BENCH_REPLAY_ARM=1
    # (bench/run_all.py sets it).
    replay_arm = None
    if os.environ.get("BENCH_REPLAY_ARM",
                      "0" if skip_jitter else "1") == "1":
        import aiohttp

        from gofr_tpu.ml.capture import decode_bundle, traffic_capture
        from gofr_tpu.ml.replay import ReplayHarness

        n_req_m = int(os.environ.get("BENCH_REPLAY_REQUESTS",
                                     "32" if on_tpu else "12"))
        new_m = max(8, max_new // 8) if on_tpu else 8
        prio_cycle = ("high", "normal", "normal", "low")

        async def replay_window(gen_fn) -> dict:
            """The mixed-load window both arms run — priorities cycle,
            every request carries a generous deadline (the TTL plumbing
            is exercised, nothing trips, so greedy replay identity can
            hold); returns the tok/s the overhead pct compares."""
            tokens_got = [0]
            t0 = time.perf_counter()

            async def one(i: int) -> None:
                body = {"prompt_ids": rng.integers(
                            1, vocab_hi, (prompt_len,)).tolist(),
                        "max_new_tokens": new_m,
                        "priority": prio_cycle[i % len(prio_cycle)],
                        "deadline_s": 60.0}
                async for msg in gen_fn(body):
                    tokens_got[0] += n_toks(msg)

            for lo in range(0, n_req_m, 8):
                await asyncio.gather(*(one(i)
                                       for i in range(lo,
                                                      min(lo + 8,
                                                          n_req_m))))
            wall = time.perf_counter() - t0
            return {"tokens": tokens_got[0], "wall_s": round(wall, 3),
                    "tok_s": round(tokens_got[0] / wall, 1)}

        arms_m: dict = {}
        bundle_m = None
        raw_len_m = 0
        for mode in ("capture", "off"):
            if mode == "capture":
                os.environ["GOFR_ML_CAPTURE"] = os.environ.get(
                    "BENCH_REPLAY_RING", "512")
            appM = chM = None
            try:
                appM = build_app()
                await boot(appM)
                chM = grpc.aio.insecure_channel(
                    f"127.0.0.1:{ports['GRPC_PORT']}")
                genM = chM.unary_stream(
                    "/llm.Chat/Generate",
                    request_serializer=lambda o: json.dumps(o).encode(),
                    response_deserializer=lambda raw: (json.loads(raw)
                                                       if raw else {}),
                )
                async for _ in genM(req(4)):    # warm compiles
                    pass
                cap = traffic_capture()
                if cap is not None:
                    cap.clear()  # the warmup request is not the window
                arms_m[mode] = await replay_window(genM)
                if mode == "capture":
                    async with aiohttp.ClientSession() as s:
                        r = await s.get(
                            f"http://127.0.0.1:{ports['HTTP_PORT']}"
                            f"/debug/capture")
                        raw = await r.read()
                    raw_len_m = len(raw)
                    bundle_m = decode_bundle(raw)
            except Exception as exc:    # optional arm: record, don't abort
                arms_m[mode] = {"error": str(exc)}
            finally:
                os.environ.pop("GOFR_ML_CAPTURE", None)
                if chM is not None:
                    await chM.close()
                if appM is not None:
                    await appM.shutdown()

        verdicts_m: dict = {}
        if bundle_m is not None and bundle_m.get("requests"):
            appR = None
            try:
                appR = build_app()
                await boot(appR)
                # drive the serving core directly: the harness IS the
                # client, scheduling at the bundle's recorded offsets
                serverR = appR.container.ml.llm("chat")
                await serverR.generate(
                    bundle_m["requests"][0]["tokens"], 4)  # warm compiles
                for speed in (1.0, 4.0):
                    verdicts_m[f"x{speed:g}"] = await ReplayHarness(
                        serverR, bundle_m, speed=speed).run()
            except Exception as exc:
                verdicts_m["error"] = str(exc)
            finally:
                if appR is not None:
                    await appR.shutdown()
        cap_on_m = arms_m.get("capture", {})
        cap_off_m = arms_m.get("off", {})
        overhead_pct = None
        if cap_on_m.get("tok_s") and cap_off_m.get("tok_s"):
            overhead_pct = round(
                100.0 * (cap_off_m["tok_s"] - cap_on_m["tok_s"])
                / cap_off_m["tok_s"], 2)
        rates_m = [v["identity"]["rate"] for v in verdicts_m.values()
                   if isinstance(v, dict) and "identity" in v]
        replay_arm = {
            "requests": n_req_m,
            "captured": len((bundle_m or {}).get("requests", ())),
            "bundle_bytes": raw_len_m,
            "capture_window": cap_on_m,
            "off_window": cap_off_m,
            # the zero-ish cost of recording the window (tok/s delta)
            "capture_overhead_pct": overhead_pct,
            "replay": verdicts_m,
            # the acceptance invariant: greedy same-config replay is
            # bit-identical at EVERY speed
            "identity_ok": (bool(rates_m)
                            and all(r == 1.0 for r in rates_m)),
        }

    # ---- phase N: fused decode windows — single-step vs fused A/B -------
    # The ISSUE-17 acceptance surface: for each variant (plain paged /
    # spec-enabled / int8-KV pages) boot window-off (today's single-step
    # dispatch) and window-on (GOFR_ML_DECODE_WINDOW=K — K device steps
    # per program launch) over the SAME steady mixed load, and report
    # steady tok/s, the flight recorder's LAUNCH phase share (the number
    # the fusion exists to collapse), top_stall, client-side TTFT/TPOT
    # p50/p99, the realized decode_window block, and greedy token
    # identity off-vs-on. f32 on the CPU preset: identity crosses
    # program shapes, where bf16 can flip a near-tie argmax. Skipped
    # under the headline watchdog budget unless BENCH_WINDOW_ARM=1
    # (bench/run_all.py sets it).
    window_arm = None
    if os.environ.get("BENCH_WINDOW_ARM",
                      "0" if skip_jitter else "1") == "1":
        window_n = float(os.environ.get("BENCH_WINDOW_WINDOW_S", "1.6"))
        reps_n = int(os.environ.get("BENCH_WINDOW_REPS", "3"))
        steady_new_n = int(os.environ.get("BENCH_WINDOW_STEADY_NEW",
                                          "128" if on_tpu else "96"))
        win_k_n = os.environ.get("BENCH_WINDOW_K", "8")
        page_n = "16" if on_tpu else "8"
        dtype_n = os.environ.get("BENCH_WINDOW_DTYPE",
                                 "" if on_tpu else "float32")
        streams_n = int(os.environ.get("BENCH_WINDOW_STREAMS",
                                       "8" if on_tpu else "4"))
        ident_prompt_n = rng.integers(1, vocab_hi, (prompt_len,)).tolist()
        # the spec variant wants a repetition-heavy prompt so prompt
        # lookup actually accepts (phase I's motif pattern); the plain
        # variants use it too so every cell runs the SAME workload
        motif_n = rng.integers(1, vocab_hi, (4,)).tolist()
        steady_prompt_n = (motif_n * (3 * max(prompt_len, 8)))[
            :3 * max(prompt_len, 8)]

        async def fused_window_run(gen_fn) -> dict:
            """One time-bounded steady-decode window; collects
            client-side TTFT (first chunk) and TPOT (inter-chunk mean)
            samples next to the aggregate tok/s."""
            stop = asyncio.Event()
            steady_tokens = [0]
            ttfts_n: list = []
            tpots_n: list = []

            async def steady_loop():
                while not stop.is_set():
                    body = {"prompt_ids": steady_prompt_n,
                            "max_new_tokens": steady_new_n}
                    t_req = time.perf_counter()
                    t_first = None
                    n_got = 0
                    async for msg in gen_fn(body):
                        now = time.perf_counter()
                        if t_first is None:
                            t_first = now
                            ttfts_n.append(t_first - t_req)
                        n_got += n_toks(msg)
                        steady_tokens[0] += n_toks(msg)
                        if stop.is_set():
                            break
                    if t_first is not None and n_got > 1:
                        tpots_n.append(
                            (time.perf_counter() - t_first) / (n_got - 1))

            tasks = [asyncio.create_task(steady_loop())
                     for _ in range(streams_n)]
            t0 = time.perf_counter()
            try:
                await asyncio.sleep(window_n)
            finally:
                window = time.perf_counter() - t0
                stop.set()
                for task in tasks:
                    task.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
            out = {"steady_tok_s": round(steady_tokens[0] / window, 1)}
            if ttfts_n:
                out["ttft_p50_ms"] = round(
                    percentile(ttfts_n, 50) * 1e3, 2)
                out["ttft_p99_ms"] = round(
                    percentile(ttfts_n, 99) * 1e3, 2)
            if tpots_n:
                out["tpot_p50_ms"] = round(
                    percentile(tpots_n, 50) * 1e3, 3)
                out["tpot_p99_ms"] = round(
                    percentile(tpots_n, 99) * 1e3, 3)
            return out

        variants_n = [v.strip() for v in os.environ.get(
            "BENCH_WINDOW_VARIANTS", "plain,spec,kv8").split(",")
            if v.strip()]
        grid_n: dict = {}
        for variant in variants_n:
            cells_n: dict = {}
            ident_n: dict = {}
            for mode in ("off", "on"):
                os.environ["LLM_PAGE_SIZE"] = page_n
                if dtype_n:
                    os.environ["LLAMA_DTYPE"] = dtype_n
                if variant == "spec":
                    os.environ["LLM_SPEC_K"] = os.environ.get(
                        "BENCH_WINDOW_SPEC_K", "2")
                elif variant == "kv8":
                    os.environ["GOFR_ML_KV_BITS"] = "8"
                if mode == "on":
                    os.environ["GOFR_ML_DECODE_WINDOW"] = win_k_n
                appN = chN = None
                try:
                    appN = build_app()
                    await boot(appN)
                    chN = grpc.aio.insecure_channel(
                        f"127.0.0.1:{ports['GRPC_PORT']}")
                    genN = chN.unary_stream(
                        "/llm.Chat/Generate",
                        request_serializer=lambda o: json.dumps(o).encode(),
                        response_deserializer=lambda raw: (json.loads(raw)
                                                           if raw else {}),
                    )
                    async for _ in genN(req(4)):        # warm compiles
                        pass
                    toks_n: list = []
                    async for msg in genN({"prompt_ids": ident_prompt_n,
                                           "max_new_tokens": 16}):
                        toks_n.extend(msg.get("tokens", ()))
                    ident_n[mode] = toks_n
                    # warm the steady shape (and promote it in the radix
                    # cache) so ladder compiles stay out of the window
                    for _ in range(2):
                        async for _ in genN({"prompt_ids": steady_prompt_n,
                                             "max_new_tokens": 8}):
                            pass
                    runs_n = [await fused_window_run(genN)
                              for _ in range(reps_n)]
                    cell = max(runs_n, key=lambda r: r["steady_tok_s"])
                    entry = await _debug_llm(ports)
                    stalls = entry.get("stalls", {})
                    win = stalls.get("window", {})
                    phases_n = {name: p.get("share")
                                for name, p in
                                win.get("phases", {}).items()}
                    cell.update({
                        "step_ms": win.get("per_dispatch_ms"),
                        # the headline number of the whole PR: how much
                        # of the dispatch wall is program launch
                        "launch_share": phases_n.get("launch"),
                        "phases": phases_n,
                        "top_stall": stalls.get("top_stall"),
                    })
                    if mode == "on":
                        cell["decode_window"] = entry.get("decode_window")
                        cell["recorder_windows"] = stalls.get(
                            "decode_window")
                    cells_n[mode] = cell
                except Exception as exc:  # optional arm: record, don't abort
                    cells_n[mode] = {"error": str(exc)}
                finally:
                    os.environ.pop("GOFR_ML_DECODE_WINDOW", None)
                    os.environ.pop("GOFR_ML_KV_BITS", None)
                    os.environ.pop("LLM_SPEC_K", None)
                    os.environ.pop("LLM_PAGE_SIZE", None)
                    os.environ.pop("LLAMA_DTYPE", None)
                    if chN is not None:
                        await chN.close()
                    if appN is not None:
                        await appN.shutdown()
            off_n, on_n = cells_n.get("off", {}), cells_n.get("on", {})
            speedup_n = None
            if off_n.get("steady_tok_s") and on_n.get("steady_tok_s"):
                speedup_n = round(
                    on_n["steady_tok_s"] / off_n["steady_tok_s"], 3)
            identical_n = (ident_n.get("off") == ident_n.get("on")
                           if len(ident_n) == 2 else None)
            grid_n[variant] = {
                "off": off_n,
                "on": on_n,
                # the fused window is lossless under greedy — identity
                # is an acceptance gate, not a statistic
                "tokens_identical": identical_n,
                "window_speedup": speedup_n,
                # the flight-recorder acceptance: launch stops being the
                # top stall once K steps share one launch
                "launch_share_delta": (
                    round(off_n["launch_share"] - on_n["launch_share"], 4)
                    if isinstance(off_n.get("launch_share"), float)
                    and isinstance(on_n.get("launch_share"), float)
                    else None),
                "launch_top_stall_off": off_n.get("top_stall"),
                "launch_top_stall_on": on_n.get("top_stall"),
            }
            if identical_n is False:
                grid_n[variant]["ident_tokens"] = ident_n
        window_arm = {
            "window_k": int(win_k_n),
            "page_size": int(page_n),
            "dtype": dtype_n or "preset-default",
            "grid": grid_n,
        }

    # ---- phase O: pipelined serving loop — double-buffered dispatch -----
    # The ISSUE-18 acceptance surface: pipeline off/on × window {1, K} ×
    # spec off/on over the SAME steady mixed load. For each cell report
    # steady tok/s, the flight recorder's host_idle_estimate estimate
    # (launch→settle busy credit vs dispatch wall — the number the
    # double-buffering exists to collapse), overlapped_dispatches,
    # client-side TTFT/TPOT p50/p99, and greedy token identity
    # pipeline-off vs pipeline-on (the fused loop must not change one
    # token). "Window 1" is the single-step dispatch path (knob unset);
    # "window K" arms GOFR_ML_DECODE_WINDOW. f32 on the CPU preset:
    # identity crosses dispatch cadences, where bf16 can flip a near-tie
    # argmax. Skipped under the headline watchdog budget unless
    # BENCH_PIPELINE_ARM=1 (bench/run_all.py sets it).
    pipeline_arm = None
    if os.environ.get("BENCH_PIPELINE_ARM",
                      "0" if skip_jitter else "1") == "1":
        window_o = float(os.environ.get("BENCH_PIPELINE_WINDOW_S", "1.6"))
        reps_o = int(os.environ.get("BENCH_PIPELINE_REPS", "3"))
        steady_new_o = int(os.environ.get("BENCH_PIPELINE_STEADY_NEW",
                                          "128" if on_tpu else "96"))
        win_k_o = os.environ.get("BENCH_PIPELINE_WINDOW_K", "4")
        page_o = "16" if on_tpu else "8"
        dtype_o = os.environ.get("BENCH_PIPELINE_DTYPE",
                                 "" if on_tpu else "float32")
        streams_o = int(os.environ.get("BENCH_PIPELINE_STREAMS",
                                       "8" if on_tpu else "4"))
        ident_prompt_o = rng.integers(1, vocab_hi, (prompt_len,)).tolist()
        # the spec cells want a repetition-heavy prompt so prompt lookup
        # actually accepts (phase I's motif pattern); every cell runs the
        # SAME workload so off/on compare apples to apples
        motif_o = rng.integers(1, vocab_hi, (4,)).tolist()
        steady_prompt_o = (motif_o * (3 * max(prompt_len, 8)))[
            :3 * max(prompt_len, 8)]

        async def pipelined_run(gen_fn) -> dict:
            """One time-bounded steady-decode window; client-side TTFT
            (first chunk) and TPOT (inter-chunk mean) samples next to
            the aggregate tok/s."""
            stop = asyncio.Event()
            steady_tokens = [0]
            ttfts_o: list = []
            tpots_o: list = []

            async def steady_loop():
                while not stop.is_set():
                    body = {"prompt_ids": steady_prompt_o,
                            "max_new_tokens": steady_new_o}
                    t_req = time.perf_counter()
                    t_first = None
                    n_got = 0
                    async for msg in gen_fn(body):
                        now = time.perf_counter()
                        if t_first is None:
                            t_first = now
                            ttfts_o.append(t_first - t_req)
                        n_got += n_toks(msg)
                        steady_tokens[0] += n_toks(msg)
                        if stop.is_set():
                            break
                    if t_first is not None and n_got > 1:
                        tpots_o.append(
                            (time.perf_counter() - t_first) / (n_got - 1))

            tasks = [asyncio.create_task(steady_loop())
                     for _ in range(streams_o)]
            t0 = time.perf_counter()
            try:
                await asyncio.sleep(window_o)
            finally:
                window = time.perf_counter() - t0
                stop.set()
                for task in tasks:
                    task.cancel()
                await asyncio.gather(*tasks, return_exceptions=True)
            out = {"steady_tok_s": round(steady_tokens[0] / window, 1)}
            if ttfts_o:
                out["ttft_p50_ms"] = round(
                    percentile(ttfts_o, 50) * 1e3, 2)
                out["ttft_p99_ms"] = round(
                    percentile(ttfts_o, 99) * 1e3, 2)
            if tpots_o:
                out["tpot_p50_ms"] = round(
                    percentile(tpots_o, 50) * 1e3, 3)
                out["tpot_p99_ms"] = round(
                    percentile(tpots_o, 99) * 1e3, 3)
            return out

        variants_o = [v.strip() for v in os.environ.get(
            "BENCH_PIPELINE_VARIANTS", "plain,spec").split(",")
            if v.strip()]
        grid_o: dict = {}
        for variant in variants_o:
            for wk in ("1", win_k_o):
                cells_o: dict = {}
                ident_o: dict = {}
                for mode in ("off", "on"):
                    os.environ["LLM_PAGE_SIZE"] = page_o
                    if dtype_o:
                        os.environ["LLAMA_DTYPE"] = dtype_o
                    if variant == "spec":
                        os.environ["LLM_SPEC_K"] = os.environ.get(
                            "BENCH_PIPELINE_SPEC_K", "2")
                    if wk != "1":
                        os.environ["GOFR_ML_DECODE_WINDOW"] = wk
                    if mode == "on":
                        os.environ["GOFR_ML_PIPELINE"] = "1"
                    appO = chO = None
                    try:
                        appO = build_app()
                        await boot(appO)
                        chO = grpc.aio.insecure_channel(
                            f"127.0.0.1:{ports['GRPC_PORT']}")
                        genO = chO.unary_stream(
                            "/llm.Chat/Generate",
                            request_serializer=lambda o: (
                                json.dumps(o).encode()),
                            response_deserializer=lambda raw: (
                                json.loads(raw) if raw else {}),
                        )
                        async for _ in genO(req(4)):        # warm compiles
                            pass
                        toks_o: list = []
                        async for msg in genO(
                                {"prompt_ids": ident_prompt_o,
                                 "max_new_tokens": 16}):
                            toks_o.extend(msg.get("tokens", ()))
                        ident_o[mode] = toks_o
                        # warm the steady shape (and promote it in the
                        # radix cache) so compiles stay out of the window
                        for _ in range(2):
                            async for _ in genO(
                                    {"prompt_ids": steady_prompt_o,
                                     "max_new_tokens": 8}):
                                pass
                        runs_o = [await pipelined_run(genO)
                                  for _ in range(reps_o)]
                        cell = max(runs_o, key=lambda r: r["steady_tok_s"])
                        entry = await _debug_llm(ports)
                        stalls = entry.get("stalls", {})
                        # the headline number of the whole PR: how much
                        # of the dispatch wall the device sat idle
                        cell["host_idle_estimate"] = stalls.get(
                            "host_idle_estimate")
                        cell["overlapped_dispatches"] = stalls.get(
                            "overlapped_dispatches")
                        if mode == "on":
                            cell["pipeline"] = entry.get("pipeline")
                        cells_o[mode] = cell
                    except Exception as exc:  # optional arm: record only
                        cells_o[mode] = {"error": str(exc)}
                    finally:
                        os.environ.pop("GOFR_ML_PIPELINE", None)
                        os.environ.pop("GOFR_ML_DECODE_WINDOW", None)
                        os.environ.pop("LLM_SPEC_K", None)
                        os.environ.pop("LLM_PAGE_SIZE", None)
                        os.environ.pop("LLAMA_DTYPE", None)
                        if chO is not None:
                            await chO.close()
                        if appO is not None:
                            await appO.shutdown()
                off_o, on_o = cells_o.get("off", {}), cells_o.get("on", {})
                speedup_o = None
                if off_o.get("steady_tok_s") and on_o.get("steady_tok_s"):
                    speedup_o = round(
                        on_o["steady_tok_s"] / off_o["steady_tok_s"], 3)
                idle_delta_o = None
                if (isinstance(off_o.get("host_idle_estimate"), float)
                        and isinstance(on_o.get("host_idle_estimate"),
                                       float)):
                    # positive = the double-buffered loop kept the
                    # device busier (acceptance wants this at window=K)
                    idle_delta_o = round(off_o["host_idle_estimate"]
                                         - on_o["host_idle_estimate"], 4)
                identical_o = (ident_o.get("off") == ident_o.get("on")
                               if len(ident_o) == 2 else None)
                grid_o[f"{variant}_w{wk}"] = {
                    "off": off_o,
                    "on": on_o,
                    # double-buffering is lossless under greedy —
                    # identity is an acceptance gate, not a statistic
                    "tokens_identical": identical_o,
                    "pipeline_speedup": speedup_o,
                    "idle_share_delta": idle_delta_o,
                }
                if identical_o is False:
                    grid_o[f"{variant}_w{wk}"]["ident_tokens"] = ident_o
        pipeline_arm = {
            "window_k": int(win_k_o),
            "page_size": int(page_o),
            "dtype": dtype_o or "preset-default",
            "grid": grid_o,
        }

    # ---- phase P: self-tuning — replay-driven config search + canary ----
    # Ride the committed bench/ bundle through the offline tuner
    # (ml/tune.py): replay the SAME captured window across a config grid
    # on the tiny reference model, prune identity violators, and report
    # the scoreboard, the winner, and the steady decode tok/s lift vs
    # the default arm. Then boot the winner as a shadow canary on a
    # 1-replica pool, mirror the bundle's prompts through it, and report
    # the promotion verdict plus the canary waste ledger (balanced:
    # every client token delivered, every completed mirror billed as
    # ``canary`` waste). Skipped under the headline watchdog budget
    # unless BENCH_TUNE_ARM=1 (bench/run_all.py sets it).
    tune_arm = None
    if os.environ.get("BENCH_TUNE_ARM",
                      "0" if skip_jitter else "1") == "1":
        from gofr_tpu.flight_recorder import event_log
        from gofr_tpu.ml.goodput import goodput_ledger
        from gofr_tpu.ml.replay import load_bundle
        from gofr_tpu.ml.replica import ReplicaPool
        from gofr_tpu.ml.tune import Tuner, _tiny_builder, default_grid

        tune_arm = {}
        profile_p = None
        bundle_p = None
        try:
            bundle_p = load_bundle(os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "tune_window.bundle"))
            grid_p = default_grid(bundle_p)[:int(os.environ.get(
                "BENCH_TUNE_ARMS", "5"))]
            tuner_p = Tuner(bundle_p, _tiny_builder(), grid_p,
                            speed=float(os.environ.get("BENCH_TUNE_SPEED",
                                                       "1000")))
            result_p = await tuner_p.run()
            winner_p = result_p.get("winner") or {}
            tune_arm.update({
                "bundle_requests": len(bundle_p.get("requests", ())),
                "arms": result_p["arms"],
                "pruned": result_p["pruned"],
                "scoreboard": [
                    {k: r.get(k) for k in ("arm", "score", "steady_tok_s",
                                           "identity", "pruned",
                                           "pruned_reason")}
                    for r in result_p["scoreboard"]],
                "winner": winner_p.get("arm"),
                "winner_knobs": winner_p.get("knobs"),
                "speedup_vs_default": result_p.get("speedup_vs_default"),
                # the acceptance gate: the recommendation is CORRECT
                # (identity 1.0) before it is fast
                "identity_ok": winner_p.get("identity") == 1.0,
            })
            profile_p = tuner_p.profile(result_p)
        except Exception as exc:    # optional arm: record, don't abort
            tune_arm["error"] = str(exc)

        if profile_p is not None and not profile_p.get("knobs"):
            tune_arm["canary"] = "skipped (default arm won: nothing to arm)"
        elif profile_p is not None and bundle_p is not None:
            # canary leg: shadow the winner on a live 1-replica pool and
            # let the mirrored window judge it. Window == request count
            # so the verdict lands exactly when the LAST mirror's pair
            # completes — no canary work is in flight when the billing
            # flips, and the waste count is deterministic.
            os.environ["GOFR_ML_CANARY_SAMPLE"] = "1"
            os.environ["GOFR_ML_CANARY_WINDOW"] = str(
                len(bundle_p["requests"]))
            poolP = None
            try:
                import jax.numpy as jnp

                from gofr_tpu.ml.generate import Generator
                from gofr_tpu.models import llama as llama_mod

                cfg_p = llama_mod.tiny_llama(use_flash=False,
                                             dtype=jnp.float32)
                params_p = llama_mod.init_params(cfg_p,
                                                 jax.random.PRNGKey(0))

                def gen_p():
                    return Generator(params_p, cfg_p, batch_slots=2,
                                     max_seq=64, prefill_buckets=(8, 16),
                                     page_size=8)

                led_p = goodput_ledger()
                base_p = (led_p.snapshot_model("tune-canary")
                          if led_p is not None else None)
                since_p = event_log().cursor
                poolP = ReplicaPool([gen_p()], name="tune-canary",
                                    spawn=lambda idx: gen_p(),
                                    canary={"knobs": profile_p["knobs"]})
                # the candidate pays its own JIT compiles on its first
                # mirror — on CPU that dwarfs the primary's warm latency,
                # so the verdict here is identity + ledger, not SLO
                poolP._canary.slo_slack = float("inf")
                outs_p = []
                for r in bundle_p["requests"]:
                    outs_p.append(await poolP.generate(
                        list(r["tokens"]), int(r["max_new"]),
                        deadline_s=60.0))
                t0p = time.perf_counter()
                while (poolP._canary is not None
                       and time.perf_counter() - t0p < 60.0):
                    await asyncio.sleep(0.05)
                while (poolP._canary_last is None
                       and time.perf_counter() - t0p < 60.0):
                    await asyncio.sleep(0.05)
                snap_p = poolP.routing_snapshot().get("canary")
                after_p = (led_p.snapshot_model("tune-canary")
                           if led_p is not None else None)
                delivered_p = wasted_p = None
                if base_p is not None and after_p is not None:
                    delivered_p = (after_p["delivered"]
                                   - base_p["delivered"])
                    wasted_p = (after_p["wasted"].get("canary", 0)
                                - base_p["wasted"].get("canary", 0))
                client_toks_p = sum(len(o) for o in outs_p)
                tune_arm["canary"] = {
                    "verdict": snap_p,
                    "client_tokens": client_toks_p,
                    "delivered_tokens": delivered_p,
                    "canary_waste_tokens": wasted_p,
                    # balanced: mirrored answers never billed delivered
                    "ledger_balanced": (delivered_p == client_toks_p
                                        if delivered_p is not None
                                        else None),
                    "fleet_size": poolP.fleet_size(),
                    "events": [e["kind"] for e in event_log().query(
                        since_p, model="tune-canary",
                        kind=("canary_promote",
                              "canary_rollback"))["events"]],
                }
            except Exception as exc:    # optional arm: record only
                tune_arm["canary"] = {"error": str(exc)}
            finally:
                os.environ.pop("GOFR_ML_CANARY_SAMPLE", None)
                os.environ.pop("GOFR_ML_CANARY_WINDOW", None)
                if poolP is not None:
                    poolP.close()

    agg_tok_s = sum(token_counts) / elapsed
    emit(
        "llama_served_tok_per_s", agg_tok_s, "tok/s", 2000.0,
        {
            "streams": streams,
            "max_new_tokens": max_new,
            "prompt_len": prompt_len,
            "slots": slots,  # None = server default (env unset, CPU path)
            "elapsed_s": round(elapsed, 2),
            "total_tokens": sum(token_counts),
            # TTFT decomposition (phase A, moderate load):
            #   wire p50 = server work + dispatch/D2H round trip
            "p50_ttft_ms": round(p50_ttft_ms, 1),
            "p99_ttft_ms": round(percentile(wire_ttfts, 99) * 1e3, 1),
            "server_ttft_avg_ms": server_ttft_ms,
            "dispatch_rtt_p50_ms": round(rtt_ms, 1),
            "ttft_minus_dispatch_rtt_ms": round(p50_ttft_ms - rtt_ms, 1),
            "ttft_ok": bool(p50_ttft_ms < 200),
            "ttft_streams": ttft_streams,
            "target_ttft_ms": 200,
            # thundering-herd TTFT (phase B: all streams at t=0, admission
            # waves of admit_cap) — queueing, not per-request serving work
            "herd_p50_ttft_ms": round(percentile(herd_ttfts, 50) * 1e3, 1),
            "herd_server_ttft_avg_ms": (
                round(1e3 * (sum3 - sum2) / (cnt3 - cnt2), 1)
                if cnt3 > cnt2 else None),
            # phase C: short-stream TTFT under long-prompt interference —
            # segmented prefill must bound the p99 spike
            "prefill_jitter": ("skipped (headline budget)" if skip_jitter
                               else {
                "long_prompt_len": long_len,
                "plain": jitter_plain,
                "chunked": {**jitter_chunked, "prefill_chunk": seg},
            }),
            # phase D: shared-system-prompt arm — prefix cache cold vs warm
            "prefix_cache": (prefix_arm if prefix_arm is not None
                             else "skipped (headline budget)"),
            # phase E: adaptive token-budget scheduler, fixed vs adaptive
            # mixed-load TTFT/throughput + token identity
            "scheduler": (sched_arm if sched_arm is not None
                          else "skipped (headline budget)"),
            # phase F: tiered KV cache — warm-hit TTFT with host offload
            # on vs off under rotating pool-overflowing system prompts
            "kv_offload": (offload_arm if offload_arm is not None
                           else "skipped (headline budget)"),
            # phase G: resilience — fault arm vs clean arm: no client
            # hangs, watchdog recoveries counted, clean arm untouched
            "resilience": (fault_arm if fault_arm is not None
                           else "skipped (headline budget)"),
            # phase H: flight recorder — per-phase dispatch breakdown
            # (where the step wall time goes) + recorder on/off overhead
            "stalls": (stall_arm if stall_arm is not None
                       else "skipped (headline budget)"),
            # phase I: speculative serving — spec on/off x kv 16/8/4 grid
            # (steady tok/s, step_ms, phases, accept rate, token identity)
            "speculation": (spec_arm if spec_arm is not None
                            else "skipped (headline budget)"),
            # phase J: disaggregated prefill/decode — 2-replica disagg
            # on/off under prompt-burst + steady-decode mixed load (burst
            # TTFT, steady TPOT p99, ships/lands ledger, token identity)
            "disagg": (disagg_arm if disagg_arm is not None
                       else "skipped (headline budget)"),
            # phase K: elastic fleet — diurnal ramp over autoscaled vs
            # static replicas + a forced holder scale-down (migrated
            # warm TTFT vs cold start, fleet-size trace, migration
            # ledger, token identity)
            "elastic": (elastic_arm if elastic_arm is not None
                        else "skipped (headline budget)"),
            # phase L: serving economics — goodput ledger balance under a
            # clean vs chaos window (wasted-token ledger by reason,
            # goodput fraction, auto-profiler trigger count)
            "goodput": (goodput_arm if goodput_arm is not None
                        else "skipped (headline budget)"),
            # phase M: serving time machine — capture a mixed window,
            # replay it at 1x and 4x (digest identity must be 1.0
            # greedy), capture overhead pct vs capture-off
            "replay": (replay_arm if replay_arm is not None
                       else "skipped (headline budget)"),
            # phase N: fused decode windows — single-step vs fused over
            # plain/spec/int8 variants (steady tok/s, launch share,
            # TTFT/TPOT p50/p99, realized window stats, token identity)
            "decode_window": (window_arm if window_arm is not None
                              else "skipped (headline budget)"),
            # phase O: pipelined serving loop — double-buffered dispatch
            # off/on × window {1,K} × spec off/on (steady tok/s,
            # host_idle_estimate, TTFT/TPOT p50/p99, token identity)
            "pipeline": (pipeline_arm if pipeline_arm is not None
                         else "skipped (headline budget)"),
            # phase P: self-tuning — replay-driven config search over
            # the committed bundle (scoreboard, winner, lift vs default)
            # + the winner shadow-canaried on a live pool (verdict,
            # balanced canary waste ledger)
            "tune": (tune_arm if tune_arm is not None
                     else "skipped (headline budget)"),
            "preset": os.environ.get("LLAMA_PRESET", "tiny"),
            "backend": jax.default_backend(),
            "config": 4,
        },
    )


if __name__ == "__main__":
    run(main())
