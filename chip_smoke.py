#!/usr/bin/env python3
"""Does the LLM serving path still start on the chip?

One process boots the llama example's app (``examples/llama_server``:
HTTP ``/generate``, the gRPC ``llm.Chat/Generate`` stream, ``register_llm``)
at the published Llama-3-8B widths with random bf16 weights from a fixed
seed, answers a few requests over the real sockets, and checks every answer
against a teacher-forced ``llama.forward`` that shares no kernel with the
served path. Depth is cut to fit one v5e chip; width never is.

    python chip_smoke.py             # one chip: dense boot, then paged boot
    python chip_smoke.py --chips 4   # four one-chip replicas behind the pool

It needs the accelerator: anywhere else, or when any phase fails, the last
line says ``"ok": false`` and the exit code is 1. Earlier lines are one JSON
object each: set-up facts and counts, none of them a benchmark metric.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import copy
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

SEED = 0
PUBLISHED_DEPTH = 32
DEPTH = 16  # 9.1 GB of bf16 weights + 2.1 GB of KV on a 16 GB chip
# Logits at these widths are ~N(0, 1) over 128,256 ids, so the top one sits
# ~4.4 above a random id's. A served token whose reference logit is within
# 0.5 of the position's maximum is the argmax up to bf16 noise between two
# implementations; a wrong token misses by several units.
LOGIT_TOL = 0.5


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a phase serves with. ``__main__`` fixes the real ones; a CPU
    test steers the same phase functions with toy ones."""
    batch_slots: int
    max_seq: int
    chunk: int
    page_size: int           # the paged layout (phases ``paged``, ``replicas``)
    prefill_chunk: int
    prompt_lens: tuple[int, int]
    prefix_len: int
    max_new: int
    kernels: str             # branch decode attention must take: "pallas" | "xla"
    tol: float = LOGIT_TOL
    devices: tuple = ()      # phase ``replicas``: one replica per device


REAL = Sizes(batch_slots=32, max_seq=1024, chunk=8, page_size=16,
             prefill_chunk=128, prompt_lens=(100, 400), prefix_len=256,
             max_new=32, kernels="pallas")


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


class _CompileCounters:
    """Process-wide tallies of jax's own compile events (the serving thread
    compiles too, which ``ml.programs.watch_compiles`` — per thread — would
    not see). Inert, all zero, until ``install``, which only ``__main__``
    calls."""

    def __init__(self) -> None:
        self.requests = self.hits = self.misses = 0
        self.compile_s = 0.0

    def install(self) -> None:
        import jax.monitoring as mon

        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name: str, secs: float, **kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def snapshot(self) -> dict:
        return {"programs_built": self.requests,
                "persistent_cache_hits": self.hits,
                "persistent_cache_misses": self.misses,
                "backend_compile_s": round(self.compile_s, 2)}


COMPILES = _CompileCounters()


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _prompts(rng, n: int, sizes: Sizes, vocab: int) -> list[list[int]]:
    """``n`` random prompts whose lengths span ``sizes.prompt_lens`` evenly,
    so the short and the long prefill bucket are both hit."""
    import numpy as np

    lens = np.linspace(*sizes.prompt_lens, n).astype(int)
    return [rng.integers(1, vocab, int(k)).tolist() for k in lens]


def _check_answer(tokens, sizes: Sizes, vocab: int) -> None:
    _check(len(tokens) == sizes.max_new,
           f"{len(tokens)} tokens answered, {sizes.max_new} asked")
    _check(all(isinstance(t, int) and 0 <= t < vocab for t in tokens),
           f"token outside [0, {vocab}): {tokens}")


def make_reference(cfg, tol: float):
    """``check(params, prompt, served) -> shortfall``: one teacher-forced
    ``llama.forward`` (no cache, ``use_flash=False``: no kernel shared with
    the served path) over prompt + served tokens. Each served token's
    reference logit must lie within ``tol`` of that position's maximum,
    which holds across bf16 argmax ties where token equality would not.
    Returns the largest shortfall."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from gofr_tpu.models import llama

    ref_cfg = copy.copy(cfg)
    ref_cfg.use_flash = False

    @jax.jit
    def shortfalls(params, tokens, pos, served):
        logits = llama.forward(params, tokens, ref_cfg)[0]  # [T, V] f32
        rows = logits[pos]
        return rows.max(-1) - rows[jnp.arange(served.shape[0]), served]

    def check(params, prompt, served) -> float:
        ids = list(prompt) + list(served)
        # causal, so right-padding to a shape bucket changes no checked row
        tokens = np.asarray([ids + [0] * (-len(ids) % 128)], np.int32)
        pos = np.arange(len(prompt) - 1, len(ids) - 1)
        worst = float(np.max(np.asarray(shortfalls(
            params, tokens, pos, np.asarray(served, np.int32)))))
        _check(worst <= tol, f"served token {worst:.3f} below the reference "
                             f"maximum (tolerance {tol})")
        return worst

    return check


@contextlib.asynccontextmanager
async def _served(params, cfg, stats: dict, **llm_kwargs):
    """The llama example's app on free ports. On the way out it is shut
    down and every replica's cache is freed, so the next boot finds the
    memory."""
    import jax

    from examples.llama_server.main import build_app
    from gofr_tpu.testutil import get_free_port

    env = {"HTTP_PORT": str(get_free_port()),
           "GRPC_PORT": str(get_free_port()),
           "METRICS_PORT": str(get_free_port()),
           "LOG_LEVEL": os.environ.get("LOG_LEVEL", "ERROR")}
    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        t0 = time.perf_counter()
        # register_llm compiles the whole warm-up ladder in here
        app = build_app(params, cfg, **llm_kwargs)
        stats["warmup_s"] = round(time.perf_counter() - t0, 2)
        llm = app.container.ml.llm("chat")
        cores = list(getattr(llm, "replicas", [llm]))
        try:
            await app.start()
            built = COMPILES.requests
            yield app
            # what the warm-up ladder did not cover: a request waited for
            # each of these to compile (or load from the persistent cache)
            stats["programs_built_while_serving"] = COMPILES.requests - built
        finally:
            await app.shutdown()
            for core in cores:
                for leaf in jax.tree.leaves(core.gen.cache):
                    leaf.delete()
        _check(all(core.closed_cleanly for core in cores),
               "a serving thread outlived shutdown")
    finally:
        for key, val in old.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val


async def _generate(session, base: str, ids, sizes: Sizes, vocab: int):
    async with session.post(base + "/generate", json={
            "prompt_ids": ids, "max_new_tokens": sizes.max_new}) as r:
        body = await r.text()
        _check(r.status == 201, f"POST /generate -> {r.status}: {body[:300]}")
    tokens = json.loads(body)["data"]["tokens"]
    _check_answer(tokens, sizes, vocab)
    return tokens


async def _grpc_generate(port: int, ids, sizes: Sizes, vocab: int):
    import grpc.aio

    tokens, frames = [], 0
    async with grpc.aio.insecure_channel(f"127.0.0.1:{port}") as channel:
        call = channel.unary_stream(
            "/llm.Chat/Generate",
            request_serializer=lambda o: json.dumps(o).encode(),
            response_deserializer=lambda raw: json.loads(raw) if raw else {})
        async for msg in call({"prompt_ids": ids,
                               "max_new_tokens": sizes.max_new}):
            tokens.extend(msg.get("tokens", ()))
            frames += 1
    _check_answer(tokens, sizes, vocab)
    return tokens, frames


async def _get_json(session, url: str) -> dict:
    async with session.get(url) as r:
        body = await r.text()
        _check(r.status == 200, f"GET {url} -> {r.status}: {body[:300]}")
    out = json.loads(body)
    return out.get("data", out)


def _branches(snapshot: dict, expected: dict) -> dict:
    """The rows of ``/debug/serving``'s ``runtime.kernels`` table that this
    phase's programs wrote, checked against what they must say."""
    table = snapshot["runtime"]["kernels"]
    for key, want in expected.items():
        _check(table.get(key) == want,
               f"{key} took {table.get(key)!r}, expected {want!r}")
    return {key: table[key] for key in expected}


def _sds(cfg, *shape):
    import jax

    return jax.ShapeDtypeStruct(shape, cfg.dtype)


# ------------------------------------------------------------------ phases
def _phase(scenario):
    """A phase is ``phase(cfg, params, sizes) -> stats``: the coroutine,
    run to its end on a loop of its own."""
    @functools.wraps(scenario)
    def phase(cfg, params, sizes: Sizes) -> dict:
        return asyncio.run(scenario(cfg, params, sizes))
    return phase


@_phase
async def phase_serve(cfg, params, sizes: Sizes) -> dict:
    """The default boot: dense KV cache. Health, 8 concurrent
    ``POST /generate``, one gRPC stream, ``/debug/serving``, shutdown."""
    import aiohttp
    import numpy as np

    from gofr_tpu import ops

    rng = np.random.default_rng(SEED)
    *prompts, grpc_prompt = _prompts(rng, 9, sizes, cfg.vocab_size)
    stats: dict = {"phase": "serve", "layout": "dense"}
    async with _served(params, cfg, stats, batch_slots=sizes.batch_slots,
                       max_seq=sizes.max_seq, chunk=sizes.chunk) as app:
        gen = app.container.ml.llm("chat").gen
        base = f"http://127.0.0.1:{app.http_port}"
        t0 = time.perf_counter()
        async with aiohttp.ClientSession() as s:
            await _get_json(s, base + "/.well-known/health")
            answers = await asyncio.gather(*[
                _generate(s, base, p, sizes, cfg.vocab_size)
                for p in prompts])
            streamed, frames = await _grpc_generate(
                app.grpc_port, grpc_prompt, sizes, cfg.vocab_size)
            snap = await _get_json(s, base + "/debug/serving")
        stats["drive_s"] = round(time.perf_counter() - t0, 2)
        served = snap["llms"]["chat"]["served"]
        _check(served == len(prompts) + 1,
               f"server counts {served} requests, {len(prompts) + 1} sent")
        B, S, H, KV, D = (sizes.batch_slots, sizes.max_seq, cfg.n_heads,
                          cfg.n_kv_heads, cfg.head_dim)
        expected = {ops.branch_key(
            "decode_attention", _sds(cfg, B, 1, H, D),
            _sds(cfg, cfg.n_layers, B, S, KV, D)): sizes.kernels}
        for bucket in gen.prefill_buckets:
            q = _sds(cfg, 1, bucket, H, D)
            expected[ops.branch_key("flash_attention", q, q)] = (
                sizes.kernels if bucket >= 128 else "xla")
        stats["branches"] = _branches(snap, expected)
        stats["prefill_buckets"] = list(gen.prefill_buckets)
    check = make_reference(cfg, sizes.tol)
    stats["requests"] = {"http": len(answers), "grpc": 1,
                         "grpc_frames": frames, "failed": 0}
    # the shortest prompt, the longest over HTTP, and the streamed one
    stats["max_logit_shortfall"] = round(max(
        check(params, prompts[0], answers[0]),
        check(params, prompts[-1], answers[-1]),
        check(params, grpc_prompt, streamed)), 4)
    return stats


@_phase
async def phase_paged(cfg, params, sizes: Sizes) -> dict:
    """The layout every feature since the page pool needs: ``page_size`` +
    chunked prefill. 4 distinct prompts, then 4 that share a prefix in two
    waves, so suffix prefill and the prefix cache both run."""
    import aiohttp
    import numpy as np

    from gofr_tpu import ops

    rng = np.random.default_rng(SEED + 1)
    prompts = _prompts(rng, 4, sizes, cfg.vocab_size)
    prefix = rng.integers(1, cfg.vocab_size, sizes.prefix_len).tolist()
    shared = [prefix + rng.integers(1, cfg.vocab_size, 8 + i).tolist()
              for i in range(4)]
    stats: dict = {"phase": "paged", "layout": f"page_size={sizes.page_size}"}
    async with _served(params, cfg, stats, batch_slots=sizes.batch_slots,
                       max_seq=sizes.max_seq, chunk=sizes.chunk,
                       page_size=sizes.page_size,
                       prefill_chunk=sizes.prefill_chunk) as app:
        gen = app.container.ml.llm("chat").gen
        base = f"http://127.0.0.1:{app.http_port}"
        t0 = time.perf_counter()
        async with aiohttp.ClientSession() as s:
            await _get_json(s, base + "/.well-known/health")

            def wave(ps):
                return asyncio.gather(*[
                    _generate(s, base, p, sizes, cfg.vocab_size) for p in ps])

            answers = await wave(prompts)
            # the prefix is promoted at its second sighting (wave one) and
            # borrowed at its third and fourth (wave two)
            shared_answers = (await wave(shared[:2])) + (await wave(shared[2:]))
            snap = await _get_json(s, base + "/debug/serving")
        stats["drive_s"] = round(time.perf_counter() - t0, 2)
        entry = snap["llms"]["chat"]
        _check(entry["served"] == 8, f"server counts {entry['served']} of 8")
        cache = entry["prefix_cache"]
        _check(cache["hits"] >= 1 and cache["prefill_tokens_saved"] > 0,
               f"the shared prefix never hit the prefix cache: {cache}")
        _check(gen.prefill_segments_run > 0, "no chunked-prefill segment ran")
        stats["prefix_cache"] = {k: cache[k] for k in
                                 ("hits", "misses", "prefill_tokens_saved")}
        stats["prefill_segments"] = gen.prefill_segments_run
        # full-precision pages at these widths: the kernel that walks the
        # page table on a TPU, the gather of every row's pages elsewhere
        stats["branches"] = _branches(snap, {ops.branch_key(
            "paged_decode_attention",
            _sds(cfg, sizes.batch_slots, 1, cfg.n_heads, cfg.head_dim),
            _sds(cfg, cfg.n_layers, gen.n_pages, sizes.page_size,
                 cfg.n_kv_heads, cfg.head_dim)): sizes.kernels})
    check = make_reference(cfg, sizes.tol)
    stats["requests"] = {"http": 8, "failed": 0}
    stats["max_logit_shortfall"] = round(max(
        check(params, prompts[-1], answers[-1]),
        check(params, shared[-1], shared_answers[-1])), 4)
    return stats


@_phase
async def phase_replicas(cfg, params, sizes: Sizes) -> dict:
    """``register_llm(..., replicas=N)``: one process, one one-chip replica
    per device of ``sizes.devices``, paged layout. 4N concurrent requests
    through the router, one fixed prompt answered by every replica, and
    every replica's params, cache and page table on its own device."""
    import aiohttp
    import jax
    import numpy as np

    devices = list(sizes.devices)
    n = len(devices)
    rng = np.random.default_rng(SEED + 2)
    *prompts, fixed = _prompts(rng, 4 * n + 1, sizes, cfg.vocab_size)
    stats: dict = {"phase": "replicas", "replicas": n,
                   "layout": f"page_size={sizes.page_size}"}
    async with _served(params, cfg, stats, replicas=n, devices=devices,
                       batch_slots=sizes.batch_slots, max_seq=sizes.max_seq,
                       chunk=sizes.chunk, page_size=sizes.page_size,
                       prefill_chunk=sizes.prefill_chunk) as app:
        pool = app.container.ml.llm("chat")
        base = f"http://127.0.0.1:{app.http_port}"
        t0 = time.perf_counter()
        async with aiohttp.ClientSession() as s:
            await _get_json(s, base + "/.well-known/health")
            await asyncio.gather(*[
                _generate(s, base, p, sizes, cfg.vocab_size)
                for p in prompts])
            snap = await _get_json(s, base + "/debug/serving")
        routed = snap["llms"]["chat"]["routing"]["routed"]
        per_replica = {i: sum(routed.get(str(i), {}).values())
                       for i in range(n)}
        _check(all(per_replica.values())
               and sum(per_replica.values()) == len(prompts),
               f"not every replica served: routed {routed}")
        # HTTP cannot address one replica: ask each core in turn
        fixed_answers = []
        for core in pool.replicas:
            tokens = [int(t) for t in await core.generate(fixed,
                                                          sizes.max_new)]
            _check_answer(tokens, sizes, cfg.vocab_size)
            fixed_answers.append(tokens)
        stats["drive_s"] = round(time.perf_counter() - t0, 2)
        _check(all(a == fixed_answers[0] for a in fixed_answers),
               f"replicas disagree on the fixed prompt: {fixed_answers}")
        placement = {}
        for i, (core, dev) in enumerate(zip(pool.replicas, devices,
                                            strict=True)):
            gen = core.gen
            for what, tree in (("params", gen.params), ("cache", gen.cache),
                               ("page_table", gen._table_device())):
                homes = {d for leaf in jax.tree.leaves(tree)
                         for d in leaf.devices()}
                _check(homes == {dev}, f"replica {i}'s {what} is on "
                                       f"{sorted(map(str, homes))}, not {dev}")
            mem = dev.memory_stats()
            if mem:  # the CPU backend reports none
                weights = sum(leaf.nbytes
                              for leaf in jax.tree.leaves(gen.params))
                _check(mem["bytes_in_use"] >= weights,
                       f"{dev} holds {mem['bytes_in_use']} bytes, less than "
                       f"replica {i}'s {weights} bytes of weights")
            placement[str(i)] = {"device": str(dev), "bytes_in_use":
                                 mem["bytes_in_use"] if mem else None}
        stats["routed"] = per_replica
        stats["placement"] = placement
    stats["requests"] = {"http": len(prompts), "in_process": n, "failed": 0}
    stats["max_logit_shortfall"] = round(
        make_reference(cfg, sizes.tol)(params, fixed, fixed_answers[0]), 4)
    return stats


# -------------------------------------------------------------------- main
def _run(chips: int, devices) -> None:
    import jax
    import jaxlib

    from examples.llama_server import main as example
    from gofr_tpu.ml.scheduler import maybe_enable_compilation_cache
    from gofr_tpu.models import llama

    COMPILES.install()
    t_start = time.perf_counter()
    cfg = llama.llama3_8b(n_layers=DEPTH)
    emit(jax=jax.__version__, jaxlib=jaxlib.__version__,
         device_kind=devices[0].device_kind, chips=chips,
         model="llama3-8b widths", vocab=cfg.vocab_size, dim=cfg.dim,
         n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads, ffn_dim=cfg.ffn_dim,
         n_layers=cfg.n_layers,
         reduced={"n_layers": [PUBLISHED_DEPTH, DEPTH]},
         dtype=str(jax.numpy.dtype(cfg.dtype)), seed=SEED,
         compilation_cache_dir=maybe_enable_compilation_cache(),
         tokenizer="native .so" if example.TOKENIZER.native
         else "python fallback")

    t0 = time.perf_counter()
    params = jax.block_until_ready(
        llama.init_params(cfg, jax.random.PRNGKey(SEED)))
    emit(step="init", wall_s=round(time.perf_counter() - t0, 2),
         param_bytes=sum(p.nbytes for p in jax.tree.leaves(params)),
         memory=devices[0].memory_stats())

    if chips == 1:
        plan = [(phase_serve, REAL), (phase_paged, REAL)]
    else:
        plan = [(phase_replicas,
                 dataclasses.replace(REAL, devices=tuple(devices)))]
    for phase, sizes in plan:
        t0 = time.perf_counter()
        stats = phase(cfg, params, sizes)
        mem = devices[0].memory_stats() or {}
        emit(**stats, wall_s=round(time.perf_counter() - t0, 2),
             peak_bytes_in_use=mem.get("peak_bytes_in_use"))
    wall = time.perf_counter() - t_start
    emit(step="summary", wall_s=round(wall, 2), **COMPILES.snapshot(),
         compile_share_of_wall=round(COMPILES.compile_s / wall, 3))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the four-replica path (default 1)")
    chips = parser.parse_args(argv).chips
    device = None
    try:
        import jax

        devices = jax.devices()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices)}
        # before anything is built: no accelerator, no run
        _check(device["platform"] == "tpu",
               f"needs a TPU, jax found {device['platform']!r}")
        _check(device["count"] == chips,
               f"--chips {chips} needs exactly {chips} chip(s), "
               f"jax found {device['count']}")
        _run(chips, devices)
    except Exception as exc:
        traceback.print_exc()
        emit(ok=False, error=f"{type(exc).__name__}: {exc}", device=device)
        return 1
    emit(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
