"""Adaptive token-budget scheduler invariants: chunk-ladder selection,
stall-free prefill/decode interleave, SLO steering, priority admission with
aging, and adaptive-vs-fixed token identity.
"""

import asyncio
import os
import time
import types

import jax
import numpy as np
import pytest

from gofr_tpu.ml.generate import Generator, _chunk_ladder
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.ml.scheduler import (AgingPriorityQueue, SLOController,
                                   TokenBudgetScheduler,
                                   maybe_enable_compilation_cache,
                                   normalize_priority)
from gofr_tpu.models import llama


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_llama(use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# --------------------------------------------------------------- pure policy
def test_chunk_ladder_shapes():
    assert _chunk_ladder(1) == (1,)
    assert _chunk_ladder(2) == (1, 2)
    assert _chunk_ladder(3) == (1, 2, 3)
    assert _chunk_ladder(16) == (1, 2, 4, 8, 16)
    assert _chunk_ladder(24) == (1, 2, 4, 8, 16, 24)


def test_plan_fills_budget_with_smallest_covering_chunk():
    sched = TokenBudgetScheduler(64, (1, 2, 4, 8, 16), prefill_chunk=8)
    # no prefill pending: the whole budget belongs to decode
    assert sched.plan(4, False) == (16, 0)    # 16*4 == 64 fits exactly
    assert sched.plan(8, False) == (8, 0)
    assert sched.plan(64, False) == (1, 0)    # saturated: smallest entry
    assert sched.plan(0, False)[0] == 16      # idle batch: cap at ladder max
    # prefill pending (share 0.5): half the budget reserved -> decode
    # shrinks down the ladder, remainder becomes prefill segments
    size, segs = sched.plan(4, True)
    assert size == 8                          # 8*4 == 32 == decode share
    assert segs == (64 - size * 4) // 8
    # decode-light: most of the budget turns into prefill segments
    size, segs = sched.plan(1, True)
    assert segs >= 4
    # stall-free bound: planned work never exceeds one budget (beyond the
    # two progress floors)
    for n_dec in (0, 1, 2, 4, 8, 16, 64):
        size, segs = sched.plan(n_dec, True)
        assert size >= 1 and segs >= 1
        assert (size * n_dec + segs * 8 <= 64
                or segs == 1 or size == 1)


def test_normalize_priority():
    assert normalize_priority(None) == 1
    assert normalize_priority("high") == 0
    assert normalize_priority("Normal") == 1
    assert normalize_priority("low") == 2
    assert normalize_priority(0) == 0
    with pytest.raises(ValueError):
        normalize_priority("urgent")
    with pytest.raises(ValueError):
        normalize_priority(7)


def _item(priority: int, enqueued_at: float):
    return types.SimpleNamespace(priority=priority, enqueued_at=enqueued_at)


def test_priority_queue_orders_classes_and_ages():
    q = AgingPriorityQueue(aging_s=2.0)
    now = 100.0
    low = _item(2, now)
    normal = _item(1, now)
    high = _item(0, now)
    for item in (low, normal, high):
        q.push(item)
    assert q.pop(now) is high
    assert q.pop(now) is normal
    assert q.pop(now) is low
    assert q.pop(now) is None
    # aging: a low-priority request parked > 2 classes' worth of aging
    # outranks fresh high-priority traffic — starvation-free
    starved = _item(2, now - 5.0)             # eff = 2 - 5/2 = -0.5
    fresh_high = _item(0, now)                # eff = 0
    q.push(starved)
    q.push(fresh_high)
    assert q.pop(now) is starved
    assert q.pop(now) is fresh_high


def test_priority_queue_front_requeue_and_prune():
    q = AgingPriorityQueue(aging_s=2.0)
    now = 10.0
    first = _item(1, now - 1.0)
    second = _item(1, now - 0.5)
    q.push(first)
    q.push(second)
    got = q.pop(now)
    assert got is first
    q.push_front(got)                         # paged admission retry path
    assert q.pop(now) is first                # still at the head of its class
    q.push_front(first)
    cancelled = _item(1, now)
    cancelled.cancelled = True
    first.cancelled = False
    q.push(cancelled)
    removed = q.prune(lambda r: getattr(r, "cancelled", False))
    assert removed == [cancelled]
    assert len(q) == 2                        # first + second kept, in order
    assert q.pop(now) is first


def test_slo_controller_steers_share():
    sched = TokenBudgetScheduler(64, (1, 2, 4, 8), prefill_chunk=8,
                                 prefill_share=0.5)
    ctl = SLOController(sched, ttft_target_s=0.2, tpot_target_s=0.05,
                        interval_s=0.0)
    # TPOT over target: decode is squeezed -> share backs off fast
    ctl.observe_tpot(0.5)
    assert ctl.maybe_update(now=1.0)
    assert sched.prefill_share < 0.5
    # TTFT over target (TPOT healthy): share grows
    sched.set_share(0.3)
    ctl._tpot.clear()
    ctl.observe_tpot(0.01)
    ctl.observe_ttft(1.0)
    ctl.maybe_update(now=2.0)
    assert sched.prefill_share > 0.3
    # both healthy: drift toward neutral, always clamped
    ctl._ttft.clear()
    ctl.observe_ttft(0.01)
    sched.set_share(0.9)
    ctl.maybe_update(now=3.0)
    assert sched.min_share <= sched.prefill_share < 0.9


# ------------------------------------------------------------ generator level
def test_ladder_dispatch_respects_budget(model):
    """With a budget below chunk * live slots, step() walks DOWN the ladder
    to the largest size that fits — and the tokens equal the fixed path."""
    cfg, params = model
    fixed = Generator(params, cfg, batch_slots=2, max_seq=64,
                      prefill_buckets=(8,), chunk=4, token_budget=0)
    prompts = [[3, 1, 4], [2, 7, 1]]
    want = [fixed.generate(p, 6) for p in prompts]

    gen = Generator(params, cfg, batch_slots=2, max_seq=64,
                    prefill_buckets=(8,), chunk=4, token_budget=2)
    assert gen.scheduler is not None
    slots = [gen.add_request(p, 6) for p in prompts]
    while any(gen.slots[i].live for i in slots):
        gen.step()
    gen.drain()
    got = [gen.slots[i].tokens[:6] for i in slots]
    assert got == want
    # two live slots, budget 2 -> every non-mini dispatch picked size 1
    sizes = set(gen.scheduler.dispatches)
    assert sizes <= {1}, gen.scheduler.snapshot()


def test_multiple_prefill_segments_when_decode_light(model):
    """Decode-light dispatches spend the budget remainder on SEVERAL
    prefill segments: a 40-token prompt (5 segments of 8) finishes its
    prefill within one step() while a single short stream decodes —
    the fixed path would need 5 interleaved dispatches."""
    cfg, params = model
    gen = Generator(params, cfg, batch_slots=2, max_seq=128,
                    prefill_buckets=(8, 64), chunk=2, prefill_chunk=8,
                    token_budget=64)
    short = gen.add_request([5, 3, 2], 24)
    gen.step()                      # short's mini-chunk: firsts resolve
    long_prompt = list((np.arange(40) % 200 + 3).astype(int))
    long_slot = gen.add_request(long_prompt, 4)
    assert long_slot in gen._chunked
    segs0 = gen.prefill_segments_run
    gen.step()                      # ONE dispatch: all 5 segments + decode
    assert gen.prefill_segments_run - segs0 >= 5
    assert long_slot not in gen._chunked
    while gen.slots[long_slot].live or gen.slots[short].live:
        gen.step()
    gen.drain()
    # both streams still exact vs the fixed path
    fixed = Generator(params, cfg, batch_slots=1, max_seq=128,
                      prefill_buckets=(8, 64), chunk=2, token_budget=0)
    assert gen.slots[long_slot].tokens[:4] == fixed.generate(long_prompt, 4)
    assert gen.slots[short].tokens[:24] == fixed.generate([5, 3, 2], 24)


def test_adaptive_vs_fixed_outputs_token_identical(model):
    """The acceptance bar: identical seeds + identical admission order ->
    bit-identical tokens, adaptive or fixed, across a mixed short/long
    workload (the budget only reshapes dispatches)."""
    cfg, params = model
    short = [5, 3, 2]
    long_prompt = list((np.arange(40) % 200 + 3).astype(int))

    def run(token_budget):
        gen = Generator(params, cfg, batch_slots=2, max_seq=128,
                        prefill_buckets=(8, 64), chunk=4, prefill_chunk=8,
                        token_budget=token_budget, seed=0)
        s1 = gen.add_request(short, 12)
        gen.step()
        s2 = gen.add_request(long_prompt, 8)
        while gen.slots[s1].live or gen.slots[s2].live:
            gen.step()
        gen.drain()
        return gen.slots[s1].tokens[:12], gen.slots[s2].tokens[:8]

    assert run(0) == run(32)


def test_temperature_single_stream_identical(model):
    """Sampling keys fold the ABSOLUTE step counter, so even stochastic
    sampling is chunking-invariant for a lone stream."""
    from gofr_tpu.ml.generate import Sampler

    cfg, params = model
    kwargs = dict(batch_slots=1, max_seq=64, prefill_buckets=(8,),
                  sampler=Sampler(temperature=0.8, top_k=8), seed=7)
    a = Generator(params, cfg, chunk=4, token_budget=0, **kwargs)
    b = Generator(params, cfg, chunk=4, token_budget=3, **kwargs)
    assert a.generate([3, 1, 4], 10) == b.generate([3, 1, 4], 10)


def test_prefetch_failure_counted_not_fatal(model):
    """The copy_to_host_async guard keeps a counter instead of swallowing
    transport errors invisibly — and decode still lands correct tokens
    through the blocking read."""
    cfg, params = model
    gen = Generator(params, cfg, batch_slots=1, max_seq=64,
                    prefill_buckets=(8,), chunk=2, token_budget=0)
    want = gen.generate([3, 1, 4], 6)
    assert gen.prefetch_errors == 0

    class _NoPrefetch:
        def __init__(self, arr) -> None:
            self._arr = arr

        def copy_to_host_async(self):
            raise RuntimeError("transport lost")

        def __array__(self, *args, **kwargs):
            return np.asarray(self._arr)

    def wrap(fn):
        def inner(*args):
            toks, tok_dev, cache = fn(*args)
            return _NoPrefetch(toks), tok_dev, cache
        return inner

    gen._chunk_fn = wrap(gen._chunk_fn)
    gen._mini_chunk_fn = wrap(gen._mini_chunk_fn)
    assert gen.generate([3, 1, 4], 6) == want
    assert gen.prefetch_errors > 0
    assert gen.pool_stats()["prefetch_errors"] == gen.prefetch_errors


@pytest.mark.parametrize("env_set", [True, False],
                         ids=["env-set", "env-unset"])
def test_compilation_cache_dir(env_set, tmp_path, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax's own handling stands and
    the code sets no directory. Unset: one fixed path in the checkout."""
    from gofr_tpu.ml.scheduler import DEFAULT_COMPILATION_CACHE_DIR

    before = jax.config.jax_compilation_cache_dir
    # stands for what jax read from the variable at import
    jax_own = str(tmp_path / "jax-own")
    jax.config.update("jax_compilation_cache_dir", jax_own)
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", jax_own)
            assert maybe_enable_compilation_cache() == jax_own
            assert jax.config.jax_compilation_cache_dir == jax_own
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert DEFAULT_COMPILATION_CACHE_DIR == os.path.join(
                repo, ".jax_cache")
            assert (maybe_enable_compilation_cache()
                    == DEFAULT_COMPILATION_CACHE_DIR)
            assert (jax.config.jax_compilation_cache_dir
                    == DEFAULT_COMPILATION_CACHE_DIR)
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        from jax.experimental.compilation_cache import compilation_cache

        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


# --------------------------------------------------------------- server level
def test_server_priority_admission_order(model, run):
    """Under slot contention the ready queue admits high before normal
    before low, regardless of arrival order."""
    cfg, params = model

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=1, max_seq=64,
                                     prefill_buckets=(8,), chunk=2))
        order: list[str] = []
        try:
            hog = asyncio.create_task(server.generate([9, 9, 9], 24))
            await asyncio.sleep(0.3)    # hog admitted; queue the rest

            async def one(name, prio):
                await server.generate([5, 3], 3, priority=prio)
                order.append(name)

            jobs = [asyncio.create_task(one("low", "low"))]
            await asyncio.sleep(0.05)   # low definitely enqueued first
            jobs += [asyncio.create_task(one("normal", "normal")),
                     asyncio.create_task(one("high", "high"))]
            await asyncio.wait_for(asyncio.gather(hog, *jobs), 120)
            return order
        finally:
            server.close()

    order = run(scenario())
    assert order == ["high", "normal", "low"]


def test_server_aging_promotes_starved_low(model, run, monkeypatch):
    """With aggressive aging, a parked low-priority request outranks a
    later-arriving high one — no starvation under a hot high class."""
    cfg, params = model
    monkeypatch.setenv("GOFR_ML_PRIORITY_AGING_S", "0.05")

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=1, max_seq=64,
                                     prefill_buckets=(8,), chunk=2))
        order: list[str] = []
        try:
            hog = asyncio.create_task(server.generate([9, 9, 9], 24))
            await asyncio.sleep(0.3)

            async def one(name, prio):
                await server.generate([5, 3], 3, priority=prio)
                order.append(name)

            low = asyncio.create_task(one("low", "low"))
            await asyncio.sleep(0.4)    # low ages ~8 classes' worth
            high = asyncio.create_task(one("high", "high"))
            await asyncio.wait_for(asyncio.gather(hog, low, high), 120)
            return order
        finally:
            server.close()

    assert run(scenario()) == ["low", "high"]


def test_server_rejects_unknown_priority(model, run):
    cfg, params = model

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=1, max_seq=64,
                                     prefill_buckets=(8,)))
        try:
            with pytest.raises(ValueError):
                await server.generate([5, 3], 2, priority="urgent")
            return await server.generate([5, 3], 2, priority="high")
        finally:
            server.close()

    assert len(run(scenario())) == 2


def test_scheduler_snapshot_through_server(model, run):
    """/debug/serving's scheduler block: budget, ladder, realized chunk
    sizes, SLO state, and per-priority queue depths."""
    cfg, params = model

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8,), chunk=4,
                                     token_budget=8))
        try:
            await server.generate([3, 1, 4], 6)
            return server.scheduler_snapshot()
        finally:
            server.close()

    snap = run(scenario())
    assert snap["budget"] == 8
    assert snap["ladder"] == [1, 2, 4]
    assert sum(int(v) for v in snap["dispatches"].values()) > 0
    assert set(snap["waiting"]) == {"high", "normal", "low"}
    assert "slo" in snap and snap["slo"]["updates"] >= 0


def test_stall_free_decode_under_adaptive_interleave(model, run):
    """The headline invariant end-to-end: with the budget scheduler ON, a
    live short stream keeps receiving bursts while a long prompt
    prefills, and both outputs stay exact."""
    cfg, params = model
    long_prompt = list((np.arange(40) % 200 + 3).astype(int))
    short = [5, 3, 2]
    dense = Generator(params, cfg, batch_slots=1, max_seq=128,
                      prefill_buckets=(64,), token_budget=0)
    ref_long = dense.generate(long_prompt, 8)
    ref_short = dense.generate(short, 16)

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=128,
                                     prefill_buckets=(8, 64), chunk=2,
                                     prefill_chunk=8, token_budget=16))
        try:
            short_bursts: list[int] = []
            seq = [0]

            async def short_stream():
                out = []
                async for burst in server.stream_chunks(short, 16):
                    seq[0] += 1
                    short_bursts.append(seq[0])
                    out.extend(burst)
                return out

            async def long_req():
                await asyncio.sleep(0.05)
                seq[0] += 1
                mark = seq[0]
                out = await server.generate(long_prompt, 8)
                return mark, out

            short_out, (mark, long_out) = await asyncio.gather(
                short_stream(), long_req())
            assert short_out == ref_short
            assert long_out == ref_long
            assert any(i > mark for i in short_bursts)
            return True
        finally:
            server.close()

    assert run(scenario())
