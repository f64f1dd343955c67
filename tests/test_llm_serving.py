"""LLM serving layer: async stream/generate over the continuous-batching
Generator, slot queueing, and the HTTP + WS transports end-to-end.
"""

import asyncio

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gofr_tpu.app import App
from gofr_tpu.config import MapConfig
from gofr_tpu.ml.generate import Generator
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.models import llama


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_llama(use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _expected(params, cfg, prompt, n):
    gen = Generator(params, cfg, batch_slots=1, max_seq=64, prefill_buckets=(8,))
    return gen.generate(prompt, n)


def test_generate_and_stream_agree(model, run):
    cfg, params = model
    expect = _expected(params, cfg, [3, 1, 4], 6)

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8,)))
        try:
            full = await server.generate([3, 1, 4], 6)
            streamed = [t async for t in server.stream([3, 1, 4], 6)]
            return full, streamed
        finally:
            server.close()

    full, streamed = run(scenario())
    assert full == expect
    assert streamed == expect


def test_stream_chunks_bursts(model, run):
    """stream_chunks yields one list per decode-chunk burst: the first is
    the TTFT mini-chunk's [first_token], bursts are bounded by the chunk
    size, and the concatenation equals the token-level stream."""
    cfg, params = model
    expect = _expected(params, cfg, [3, 1, 4], 7)

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8,), chunk=3))
        try:
            return [b async for b in server.stream_chunks([3, 1, 4], 7)]
        finally:
            server.close()

    bursts = run(scenario())
    assert all(isinstance(b, list) and b for b in bursts)
    assert len(bursts[0]) == 1                  # mini-chunk first token
    assert max(len(b) for b in bursts) <= 3     # never beyond chunk
    assert [t for b in bursts for t in b] == expect


def test_concurrent_requests_beyond_slots(model, run):
    """6 concurrent requests over 2 slots: all finish, each correct."""
    cfg, params = model
    prompts = [[i + 1, i + 2] for i in range(6)]
    expects = [_expected(params, cfg, p, 4) for p in prompts]

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8,)))
        try:
            return await asyncio.gather(
                *(server.generate(p, 4) for p in prompts)
            )
        finally:
            server.close()

    results = run(scenario())
    assert results == expects


def test_chunked_decode_slot_reuse_no_hang(model, run):
    """Regression (ADVICE r1): with chunk>1, add_request's internal drain()
    can finish another slot mid-admission; admitting into it before the
    server released it overwrote the old request, which then never received
    its _DONE and awaited forever. Staggered max_new makes slots free at
    different chunk boundaries; every request must still complete."""
    cfg, params = model
    prompts = [[i + 1, i + 2, i + 3] for i in range(8)]
    lengths = [2, 7, 3, 9, 4, 6, 5, 8]
    expects = [_expected(params, cfg, p, n) for p, n in zip(prompts, lengths)]

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=3, max_seq=64,
                                     prefill_buckets=(8,), chunk=4))
        try:
            return await asyncio.wait_for(
                asyncio.gather(
                    *(server.generate(p, n) for p, n in zip(prompts, lengths))
                ),
                timeout=120,
            )
        finally:
            server.close()

    results = run(scenario())
    for got, want in zip(results, expects):
        assert got == want


def test_answer_to_a_stream_end_is_seen_at_the_next_settle(model, run):
    """A client that answers the end of its stream at once (here: from
    inside the serving thread, the moment the finish marker is sent) is
    admitted after the next dispatch has settled, never in the pass that
    follows the finish: the queue is read before streams end, so which
    launch such a request catches does not hang on a race between the
    serving thread and the transport's."""
    from gofr_tpu.ml.llm import _Finish, _Request

    cfg, params = model
    expect = _expected(params, cfg, [5, 6], 4)

    async def scenario():
        gen = Generator(params, cfg, batch_slots=2, max_seq=64,
                        prefill_buckets=(8,), chunk=2)
        server = LLMServer(gen)
        loop = asyncio.get_running_loop()
        answer_q: asyncio.Queue = asyncio.Queue()
        seen = {}
        finish, add = server._finish_dead_slots, gen.add_requests

        def finish_then_answer():
            served = server.served
            finish()
            if server.served > served and "sent_at" not in seen:
                seen["sent_at"] = gen.steps
                server._requests.put(_Request([5, 6], 4, answer_q, loop))

        def add_and_note(requests):
            if "sent_at" in seen and "admitted_at" not in seen:
                seen["admitted_at"] = gen.steps
            return add(requests)

        server._finish_dead_slots = finish_then_answer
        gen.add_requests = add_and_note
        try:
            long = asyncio.ensure_future(server.generate([1, 2, 3], 24))
            short = await server.generate([7, 8], 3)
            tokens = []
            while True:
                item = await asyncio.wait_for(answer_q.get(), timeout=60)
                if isinstance(item, _Finish):
                    break
                tokens.extend(item)
            await long
            return short, tokens, seen
        finally:
            server.close()

    short, tokens, seen = run(scenario())
    assert len(short) == 3
    assert tokens == expect
    assert seen["admitted_at"] > seen["sent_at"]


def test_a_dispatch_wakes_the_consumers_loop_once(model, run):
    """What one processed dispatch has for its streams (every stream's
    burst) reaches the consumers' loop in ONE wakeup, and so do the finish
    markers of the streams that ended: a wakeup a stream was a write to
    the loop's wake-up socket a stream, each giving up the interpreter's
    lock with the device waiting for the next launch. Every stream still
    gets its own tokens, in order, and its marker last."""
    cfg, params = model
    prompts = [[i + 1, i + 2] for i in range(4)]
    expects = [_expected(params, cfg, p, 9) for p in prompts]

    async def scenario():
        gen = Generator(params, cfg, batch_slots=4, max_seq=64,
                        prefill_buckets=(8,), chunk=4)
        server = LLMServer(gen, idle_wait_s=1.0, admit_window_s=0.25)
        loop = asyncio.get_running_loop()
        wakeups, wake = [], loop.call_soon_threadsafe

        def counted(fn, *args):
            if fn.__name__ == "_deliver":
                wakeups.append(len(args[0]))
            return wake(fn, *args)

        loop.call_soon_threadsafe = counted
        try:
            got = await asyncio.gather(*(server.generate(p, 9)
                                         for p in prompts))
            return got, wakeups, gen.settled
        finally:
            loop.call_soon_threadsafe = wake
            server.close()

    got, wakeups, settled = run(scenario())
    assert got == expects
    # 4 streams x (first token + 8 more in chunks of 1, 4 and 4) + 4 markers
    assert sum(wakeups) == 4 * 4 + 4
    # a wakeup a settled dispatch and one for the markers, not one a stream
    assert len(wakeups) <= settled + 1
    assert max(wakeups) >= 4


def test_idle_burst_is_collected_until_it_is_over(model, run):
    """Requests that reach an idle server one after another, each within
    the admit window of the last but over a longer span than one window,
    are one admission wave: the burst ends when nothing has come for a
    window (or every free slot has a taker), not a window after its first
    request."""
    cfg, params = model
    prompts = [[i + 1, i + 2] for i in range(4)]

    async def scenario():
        gen = Generator(params, cfg, batch_slots=4, max_seq=64,
                        prefill_buckets=(8,))
        server = LLMServer(gen, idle_wait_s=1.0, admit_window_s=0.25)
        waves, add = [], gen.add_requests

        def add_and_note(requests):
            waves.append(len(requests))
            return add(requests)

        gen.add_requests = add_and_note

        async def later(i, p):
            await asyncio.sleep(0.1 * i)  # 0.3 s in all, over one window
            return await server.generate(p, 3)

        try:
            await asyncio.gather(*(later(i, p) for i, p in enumerate(prompts)))
            return waves
        finally:
            server.close()

    assert run(scenario()) == [4]


def test_bad_prompt_raises_not_hangs(model, run):
    cfg, params = model

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=1, max_seq=64,
                                     prefill_buckets=(8,)))
        try:
            with pytest.raises(ValueError):
                await server.generate([], 4)
            # server still serves after the failure
            return await server.generate([5], 2)
        finally:
            server.close()

    assert len(run(scenario())) == 2


def test_health_reports_slots(model, run):
    cfg, params = model

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=3, max_seq=64,
                                     prefill_buckets=(8,)))
        try:
            await server.generate([1, 2], 2)
            return server.health_check()
        finally:
            server.close()

    h = run(scenario())
    assert h["status"] == "UP"
    assert h["details"]["slots"] == 3
    assert h["details"]["served"] == 1


def test_http_and_ws_transports(model, run):
    """The llama_server example wiring: POST /generate + WS /stream."""
    cfg, params = model
    expect = _expected(params, cfg, [2, 7, 1], 5)

    async def scenario():
        app = App(config=MapConfig({"APP_NAME": "llm-test"}))
        app.register_llm("chat", params, cfg, batch_slots=2, max_seq=64,
                         prefill_buckets=(8,))

        async def generate(ctx):
            body = await ctx.bind()
            toks = await ctx.ml.llm("chat").generate(
                body["prompt_ids"], int(body.get("max_new_tokens", 8)))
            return {"tokens": toks}

        async def stream_ws(ctx):
            body = await ctx.bind()
            async for tok in ctx.ml.llm("chat").stream(
                    body["prompt_ids"], int(body.get("max_new_tokens", 8))):
                await ctx.write_message_to_socket({"token": tok})
            return {"done": True}

        app.post("/generate", generate)
        app.websocket("/stream", stream_ws)

        client = TestClient(TestServer(app._build_http_app()))
        await client.start_server()
        try:
            r = await client.post("/generate", json={
                "prompt_ids": [2, 7, 1], "max_new_tokens": 5})
            assert r.status == 201  # responder rule: POST with data -> 201
            body = await r.json()

            ws = await client.ws_connect("/stream")
            await ws.send_json({"prompt_ids": [2, 7, 1], "max_new_tokens": 5})
            ws_tokens = []
            while len(ws_tokens) < 5:
                frame = await ws.receive_json()
                if "token" in frame:
                    ws_tokens.append(frame["token"])
            await ws.close()
            return body["data"]["tokens"], ws_tokens
        finally:
            await client.close()
            await app.container.close()

    http_tokens, ws_tokens = run(scenario())
    assert http_tokens == expect
    assert ws_tokens == expect


def test_paged_pool_backpressure_requeues(model, run):
    """With a page pool too small for every stream at once, admission hits
    PagePoolExhausted; the server must REQUEUE (transient back-pressure),
    not error the clients — all streams finish correctly."""
    cfg, params = model
    prompts = [[i + 1, i + 2, i + 3] for i in range(6)]
    expects = [_expected(params, cfg, p, 4) for p in prompts]

    async def scenario():
        # 4 slots but pages for ~2 concurrent requests (8 tokens each)
        server = LLMServer(Generator(params, cfg, batch_slots=4, max_seq=32,
                                     prefill_buckets=(8,), chunk=2,
                                     page_size=8, n_pages=3))
        try:
            return await asyncio.gather(
                *(server.generate(p, 4) for p in prompts))
        finally:
            server.close()

    outs = run(scenario())
    assert outs == expects


def test_shared_prefix_through_server(model, run):
    """register_prefix on the live server (runs on the serving thread) +
    prefix= streaming: output equals the full-prompt decode, concurrent
    streams share the prefix pages."""
    cfg, params = model
    prefix = [5, 9, 2, 7, 1, 4, 8, 3]
    suffixes = [[6, 2], [9, 1, 1]]
    expects = [_expected(params, cfg, prefix + sfx, 5) for sfx in suffixes]

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8, 16), chunk=2,
                                     page_size=8))
        try:
            pid = await asyncio.to_thread(server.register_prefix, prefix)
            return await asyncio.gather(
                *(server.generate(sfx, 5, prefix=pid) for sfx in suffixes))
        finally:
            server.close()

    outs = run(scenario())
    assert outs == expects


def test_rotating_prefixes_never_exhaust_pool(model, run):
    """VERDICT r4 #6 'Done' bar: a rotating set of system prompts (each
    registered as a shared prefix, used, then abandoned) must never
    exhaust the page pool — idle prefixes LRU-evict — and the
    PagePoolExhausted back-pressure requeue still fires for concurrent
    bursts afterwards."""
    cfg, params = model
    prefixes = [[i + 1] * 8 for i in range(5)]   # one page each
    suffix = [7, 3]
    # ONE dense generator computes every expectation (compile once)
    dense = Generator(params, cfg, batch_slots=1, max_seq=64,
                      prefill_buckets=(16,))
    expects = [dense.generate(p + suffix, 4) for p in prefixes]
    burst = [[i + 2, i + 5, i + 1] for i in range(4)]
    burst_expect = [dense.generate(p, 4) for p in burst]

    async def scenario():
        # 1 scratch + 4 usable pages: at most ~2 prefixes + a live slot
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=32,
                                     prefill_buckets=(8, 16), chunk=2,
                                     page_size=8, n_pages=5))
        try:
            outs = []
            for pfx in prefixes:  # rotation: register, use once, abandon
                pid = await asyncio.to_thread(server.register_prefix, pfx)
                outs.append(await server.generate(suffix, 4, prefix=pid))
            assert server.gen.prefix_evictions > 0
            # pool still serves a concurrent burst with requeue pressure
            burst_out = await asyncio.gather(
                *(server.generate(p, 4) for p in burst))
            assert burst_out == burst_expect
            return outs
        finally:
            server.close()

    outs = run(scenario())
    assert outs == expects


# ------------------------------------------------------------ chunked prefill
def test_chunked_prefill_lossless_and_nonblocking(model, run):
    """VERDICT r4 #2: with prefill_chunk set, a long prompt prefills in
    segments interleaved with decode — a live short stream KEEPS receiving
    tokens while the long prompt fills in, and both outputs equal their
    whole-prompt-prefill decodes exactly."""
    import numpy as np

    cfg, params = model
    long_prompt = list((np.arange(40) % 200 + 3).astype(int))
    short = [5, 3, 2]
    dense = Generator(params, cfg, batch_slots=1, max_seq=128,
                      prefill_buckets=(64,))
    ref_long = dense.generate(long_prompt, 8)
    ref_short = dense.generate(short, 16)

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=128,
                                     prefill_buckets=(8, 64), chunk=2,
                                     prefill_chunk=8))
        try:
            import asyncio

            short_bursts: list[tuple[int, list[int]]] = []
            seq = [0]

            async def short_stream():
                out = []
                async for burst in server.stream_chunks(short, 16):
                    seq[0] += 1
                    short_bursts.append((seq[0], burst))
                    out.extend(burst)
                return out

            async def long_req():
                # admitted while the short stream decodes: its 5-segment
                # prefill must interleave, not stall
                await asyncio.sleep(0.05)
                seq[0] += 1
                mark = seq[0]
                out = await server.generate(long_prompt, 8)
                return mark, out

            short_out, (mark, long_out) = await asyncio.gather(
                short_stream(), long_req())
            assert short_out == ref_short
            assert long_out == ref_long
            # the short stream received bursts AFTER the long request
            # started — the long prefill did not stall it to completion
            assert any(i > mark for i, _ in short_bursts), short_bursts
            return True
        finally:
            server.close()

    assert run(scenario())


def test_chunked_prefill_cancel_mid_prefill(model, run):
    """A client abandoning a request during its segmented prefill frees
    the slot; later requests serve normally."""
    import asyncio

    import numpy as np

    cfg, params = model
    long_prompt = list((np.arange(60) % 200 + 3).astype(int))

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=1, max_seq=128,
                                     prefill_buckets=(8, 64), chunk=2,
                                     prefill_chunk=8))
        try:
            agen = server.stream_chunks(long_prompt, 8)
            task = asyncio.create_task(agen.__anext__())
            await asyncio.sleep(0.05)   # admission + first segments
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, StopAsyncIteration):
                pass
            await agen.aclose()         # client walks away mid-prefill
            # the slot must come back: a fresh request completes
            out = await asyncio.wait_for(server.generate([5, 3, 2], 4), 60)
            assert len(out) == 4
            return True
        finally:
            server.close()

    assert run(scenario())


def test_chunked_prefill_paged_and_speculative(model, run):
    """Chunked prefill now covers the paged pool and speculation: a long
    prompt segments through the page tables (int8 pages included
    elsewhere), and under spec_k the final segment seeds the device
    history row — all outputs equal the dense whole-prompt decode."""
    import numpy as np

    cfg, params = model
    long_prompt = list((np.arange(40) % 200 + 3).astype(int))
    short = [5, 3, 2]
    dense = Generator(params, cfg, batch_slots=1, max_seq=64,
                      prefill_buckets=(64,))
    ref_long = dense.generate(long_prompt, 8)
    ref_short = dense.generate(short, 8)

    async def scenario():
        import asyncio

        # paged + chunked through the server
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8, 64), chunk=2,
                                     page_size=8, prefill_chunk=16))
        try:
            outs = await asyncio.gather(server.generate(long_prompt, 8),
                                        server.generate(short, 8))
            assert outs == [ref_long, ref_short]
        finally:
            server.close()

        # speculative + chunked through the server
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8, 64), chunk=2,
                                     spec_k=2, prefill_chunk=16))
        try:
            assert await server.generate(long_prompt, 8) == ref_long
            assert server.gen.spec_windows > 0
        finally:
            server.close()
        return True

    assert run(scenario())


def test_pool_gauges_exported(model, run):
    """Operators size n_pages by evictions/free-pages; the serving thread
    exports them as gauges alongside the request metrics."""
    cfg, params = model
    gauges: dict[str, float] = {}

    class _Metrics:
        def set_gauge(self, name, value, **labels):
            gauges[name] = value

        def record_histogram(self, name, value, **labels):
            pass

    async def scenario():
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=32,
                                     prefill_buckets=(8,), chunk=2,
                                     page_size=8, n_pages=4),
                           metrics=_Metrics())
        try:
            await server.generate([5, 3, 2], 4)
        finally:
            server.close()

    run(scenario())
    assert gauges.get("app_llm_evictions") == 0.0
    assert "app_llm_free_pages" in gauges
    assert "app_llm_prefix_evictions" in gauges


def test_chunked_prefill_pool_dry_evicts_honestly(model, run):
    """If the paged pool runs dry MID-segmented-prefill (another stream
    holds the pages), the chunked request finishes as an eviction — the
    client sees finish_reason 'eviction', never a hang or a silent fake
    completion — and the pool recovers."""
    import numpy as np

    cfg, params = model
    long_prompt = list((np.arange(30) % 200 + 3).astype(int))

    async def scenario():
        import asyncio

        # 1 scratch + 6 usable pages: the long request needs 5 (fits
        # alone), the hog pins 3 while decoding -> dry mid-prefill
        server = LLMServer(Generator(params, cfg, batch_slots=2, max_seq=64,
                                     prefill_buckets=(8, 64), chunk=2,
                                     page_size=8, n_pages=7,
                                     prefill_chunk=8))
        try:
            hog_task = asyncio.create_task(
                server.generate([1, 2, 3, 4, 5, 6, 7], 16))
            await asyncio.sleep(0.2)  # hog admitted and decoding
            fin: dict = {}
            out = await asyncio.wait_for(
                server.generate(long_prompt, 8, info=fin), 120)
            hog = await asyncio.wait_for(hog_task, 120)
            assert len(hog) == 16          # the hog was never corrupted
            # the long request either squeezed through (pages freed in
            # time) or was evicted — but NEVER silently truncated as a
            # natural stop
            if len(out) < 8:
                assert fin.get("finish_reason") == "eviction", (out, fin)
            # pool recovers fully for the next request
            out2 = await asyncio.wait_for(server.generate([5, 3], 4), 120)
            assert len(out2) == 4
            return True
        finally:
            server.close()

    assert run(scenario())


def test_serving_soak_all_compositions(model, run):
    """Soak the full composition through the server — paged + int8-free
    spec drafting + chunked prefill + rotating prefixes — and assert the
    steady-state invariants: every stream correct-length, all slots free,
    all pages back in the pool, prefix evictions bounded the cache."""
    import numpy as np

    cfg, params = model

    async def scenario():
        import asyncio

        server = LLMServer(Generator(params, cfg, batch_slots=3, max_seq=64,
                                     prefill_buckets=(8, 64), chunk=2,
                                     page_size=8, n_pages=12, spec_k=2,
                                     prefill_chunk=8))
        try:
            rng = np.random.default_rng(0)
            for wave in range(6):
                pfx = [int(x) for x in rng.integers(1, 200, 8)]
                pid = await asyncio.to_thread(server.register_prefix, pfx)
                jobs = [
                    server.generate([int(x) for x in rng.integers(1, 200, 3)], 5),
                    server.generate(
                        [int(x) for x in rng.integers(1, 200, 20)], 5),
                    server.generate([7, 3], 5, prefix=pid),
                ]
                outs = await asyncio.wait_for(asyncio.gather(*jobs), 180)
                assert [len(o) for o in outs] == [5, 5, 5]
            gen = server.gen
            assert gen.n_live == 0
            held = sum(len(i["pages"])
                       for i in gen._prefixes.values())
            assert gen.free_pages + held == gen.n_pages - 1  # no page leak
            assert gen.evictions == 0
            return True
        finally:
            server.close()

    assert run(scenario())
