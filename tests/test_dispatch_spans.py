"""Dispatch spans: every phase of a serving pass has a start and an end,
the committed record says when it ran and what it launched, all records
live in one process-global log, the serving programs carry stable names,
and a profiler capture holds the ``gofr.serve.*`` annotations.

CPU, tiny model. The spans are perf_counter facts; nothing here is a
speed."""

import asyncio
import glob
import threading

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu import flight_recorder as fr
from gofr_tpu.flight_recorder import DispatchRecorder, dispatch_log, event_log
from gofr_tpu.ml import programs
from gofr_tpu.ml.generate import Generator
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.models import llama


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_llama(use_flash=False)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _gen(model, **kw):
    cfg, params = model
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8,))
    return Generator(params, cfg, **kw)


def _serve(model, name, prompts, max_new=6, **gen_kw):
    """One server's life: every prompt generated concurrently."""
    async def scenario():
        server = LLMServer(_gen(model, **gen_kw), name=name)
        try:
            return server, await asyncio.gather(
                *[server.generate(p, max_new) for p in prompts])
        finally:
            server.close()

    return asyncio.run(scenario())


def _of(*models):
    """The log's records of these models (a pool's cores are ``name/i``)."""
    return [r for r in dispatch_log().records() if r["model"] in models]


def _self_times(spans):
    """Per phase, each span's interval less what its direct children
    cover: what the record's ``phases`` must read."""
    out: dict = {}
    for i, (name, a, b) in enumerate(spans):
        inner = 0.0
        for j, (_, c, d) in enumerate(spans):
            if j != i and a <= c and d <= b and not any(
                    k not in (i, j) and a <= e and f <= b
                    and e <= c and d <= f
                    for k, (_, e, f) in enumerate(spans)):
                inner += d - c
        out[name] = out.get(name, 0.0) + (b - a) - inner
    return out


def test_spans_lie_inside_the_pass_in_order_and_sum_to_its_wall(model):
    _serve(model, "sp-sum", [[3, 1, 4], [1, 5, 9, 2]])
    records = _of("sp-sum")
    assert records
    for r in records:
        assert r["t1"] - r["t0"] == pytest.approx(r["wall_s"], abs=1e-9)
        starts = [a for _, a, _ in r["spans"]]
        assert starts == sorted(starts)
        for name, a, b in r["spans"]:
            assert name in fr.PHASES
            assert r["t0"] - 1e-9 <= a <= b <= r["t1"] + 1e-9
        assert sum(r["phases"].values()) == pytest.approx(r["wall_s"],
                                                          abs=1e-9)
        # a phase's seconds are its spans' SELF times
        want = _self_times(r["spans"])
        for name, s in want.items():
            assert r["phases"][name] == pytest.approx(s, abs=1e-6)
    assert {r["seq"] for r in records} == set(range(1, len(records) + 1))
    assert any(r["kind"] == "mini" and r["steps"] == 1 for r in records)


def test_nested_phase_is_self_time_and_note_is_the_primitive():
    rec = DispatchRecorder(model="sp-nest")
    rec.reset()
    with rec.phase("assemble") as outer:
        with rec.phase("device_wait") as inner:
            sum(range(20000))
        with rec.phase("emit"):
            pass
    rec.note("decide", 0.25)  # synthetic durations still add in
    rec.commit()
    r, = _of("sp-nest")
    assert [s[0] for s in r["spans"]] == ["assemble", "device_wait", "emit"]
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    nested = sum(b - a for n, a, b in r["spans"] if n != "assemble")
    assert r["phases"]["assemble"] == pytest.approx(
        (outer.t1 - outer.t0) - nested, abs=1e-9)
    assert r["phases"]["device_wait"] == pytest.approx(inner.t1 - inner.t0)
    assert r["phases"]["decide"] == 0.25
    assert r["kind"] == "flush" and r["steps"] == 0 and r["rows"] == 0


def test_assemble_around_an_admission_wave_excludes_its_inner_drain(model):
    """The second request is admitted while the first decodes: the wave's
    own ``gen.drain()`` stamps device_wait/emit INSIDE assemble, and the
    record's assemble seconds leave them out (what the deleted
    ``pending_total`` subtraction did by hand)."""
    async def scenario(name):
        server = LLMServer(_gen(model), name=name)
        try:
            # as long an answer as max_seq 64 holds: on a loaded machine a
            # short one ended before the second request was admitted
            first = asyncio.ensure_future(server.generate([3, 1, 4], 56))
            for _ in range(400):  # until the first request is decoding
                if _of(name):
                    break
                await asyncio.sleep(0.005)
            await asyncio.gather(first, server.generate([2, 7, 1], 6))
        finally:
            server.close()

    # whether the second request lands while the first still decodes is
    # a race with the machine's other work (six test workers): a life in
    # which it came too late shows no wave and is run again
    nested = []
    for attempt in range(5):
        name = f"sp-wave-{attempt}"
        asyncio.run(scenario(name))
        for r in _of(name):
            for span, a, b in r["spans"]:
                if span != "assemble":
                    continue
                inside = [(n, c, d) for n, c, d in r["spans"]
                          if n in ("device_wait", "emit")
                          and a <= c and d <= b]
                if inside:
                    nested.append(
                        (r, b - a, sum(d - c for _, c, d in inside)))
        if nested:
            break
    assert nested, "no admission wave drained a chunk in flight"
    for r, interval, inner in nested:
        assert r["phases"]["assemble"] <= interval - inner + 1e-6
        assert sum(r["phases"].values()) == pytest.approx(r["wall_s"],
                                                          abs=1e-9)


def test_rows_steps_kind_count_decoding_rows_not_mid_prefill_ones(model):
    gen = _gen(model, batch_slots=3, prefill_chunk=8, chunk=4,
               token_budget=0)
    rec = DispatchRecorder(model="sp-rows")
    gen.recorder = rec
    gen.add_request([3, 1, 4], 40)
    gen.add_request([1, 5, 9], 40)
    gen.step()          # the mini chunk that carries both first tokens
    rec.commit()
    gen.add_request(list(range(1, 31)), 4)   # 30 tokens: chunked prefill
    assert gen._chunked and gen._n_decodable() == 2
    gen.step()
    rec.commit()
    mini, chunk = _of("sp-rows")
    assert (mini["kind"], mini["steps"], mini["rows"]) == ("mini", 1, 2)
    assert (chunk["kind"], chunk["steps"], chunk["rows"]) == ("chunk", 4, 2)
    assert [s[0] for s in chunk["spans"]].count("launch") == 2  # + segment
    gen.drain()
    rec.commit()        # the tail flush launched nothing
    assert _of("sp-rows")[-1]["kind"] == "flush"


def test_dispatch_log_holds_two_servers_and_outlives_them(model):
    _serve(model, "sp-one", [[3, 1, 4]])
    _serve(model, "sp-two/0", [[2, 7, 1]])   # a pool's replica core
    one, two = _of("sp-one"), _of("sp-two/0")
    assert one and two
    assert [r["seq"] for r in one] == list(range(1, len(one) + 1))
    assert len(_of("sp-one", "sp-two/0")) == len(one) + len(two)


def test_recorders_of_one_name_read_their_own_rolling_records():
    a = DispatchRecorder(model="sp-same", ring=2)
    b = DispatchRecorder(model="sp-same")
    for rec, n in ((a, 3), (b, 5)):
        for _ in range(n):
            rec.note("launch", 0.001)
            rec.commit()
    assert a.snapshot()["window"]["records"] == 2   # its ring of 2
    assert a.snapshot()["dispatches"] == 3
    assert b.snapshot()["window"]["records"] == 5
    assert [r["seq"] for r in a.tail(16)] == [2, 3]
    assert len(_of("sp-same")) == 8


def test_a_busy_recorder_never_pushes_a_quiet_ones_records_out():
    log = fr.DispatchLog(per_recorder=4, recorders=2)
    log.append(1, {"seq": 1})
    for seq in range(1, 10):
        log.append(2, {"seq": seq})
    assert [r["seq"] for r in log.records(1)] == [1]
    assert [r["seq"] for r in log.records(2)] == [6, 7, 8, 9]
    assert [r["seq"] for r in log.records(2, 2)] == [8, 9]
    assert len(log.records()) == 5
    log.append(3, {"seq": 1})       # a third recorder: the oldest rolls off
    assert log.records(1) == [] and len(log.records()) == 5


def test_a_pass_that_launches_twice_counts_both_programs():
    rec = DispatchRecorder(model="sp-twice")
    rec.reset()
    with rec.phase("launch", kind="mini", steps=1, rows=4):
        pass
    with rec.phase("launch", kind="chunk", steps=4, rows=2) as second:
        pass
    assert second.ann is not None
    rec.commit()
    r, = _of("sp-twice")
    assert (r["kind"], r["steps"]) == ("chunk", 5)
    assert r["rows"] * r["steps"] == 4 * 1 + 2 * 4


def test_the_pipeline_launches_one_decode_program_a_pass(model, monkeypatch):
    monkeypatch.setenv("GOFR_ML_PIPELINE", "1")
    server, _ = _serve(model, "sp-pipe", [[3, 1, 4], [1, 5, 9, 2]],
                       max_new=24, chunk=4, token_budget=0)
    assert server.gen.pipeline
    records = _of("sp-pipe")
    assert any(r.get("overlap", 0) >= 2 for r in records)
    for r in records:
        assert r["steps"] in (0, 1, 4) and 0 <= r["rows"] <= 2
        assert isinstance(r["rows"], int)


def test_recorder_off_stamps_nothing(model, monkeypatch):
    monkeypatch.setenv("GOFR_ML_FLIGHT_RECORDER", "0")
    server, outs = _serve(model, "sp-off", [[3, 1, 4]])
    assert server.recorder is None and server.gen.recorder is None
    assert len(outs[0]) == 6
    assert _of("sp-off") == []
    # one shared no-op context, nothing constructed per phase
    assert fr.phase(None, "launch") is fr.phase(None, "emit")
    with fr.phase(None, "launch") as span:
        assert span is None


def _jitted(gen):
    fns = [v for v in vars(gen).values() if hasattr(v, "lower")]
    for ladder in (gen._chunk_fns, gen._plain_fns):
        fns.extend(ladder.values())
    return fns


@pytest.mark.parametrize("kw", [
    dict(),
    dict(page_size=8, n_pages=32),
    dict(prefill_chunk=8),
    dict(page_size=8, n_pages=32, prefill_chunk=8),
], ids=["dense", "paged", "dense-chunked", "paged-chunked"])
def test_every_serving_program_has_a_name_of_its_own(model, kw):
    gen = _gen(model, **kw)
    names = {fn.__name__ for fn in _jitted(gen)}
    assert len(names) >= 3  # dense: chunk_fn, prefill_into, post_prefill
    for name in names:
        assert "lambda" not in name and name != "f", names
    prefill = {n for n in names if "prefill" in n}
    assert not any("chunk_fn" in n for n in prefill)
    assert ("paged_chunk_fn" if kw.get("page_size") else "chunk_fn") in names
    want = ({"paged_prefill", "suffix_prefill", "prefix_prefill"}
            if kw.get("page_size") else set()) | {"prefill_into"}
    if kw.get("prefill_chunk"):
        want.add("paged_segment_prefill" if kw.get("page_size")
                 else "segment_prefill")
    assert want <= prefill, (want, prefill)
    # the name is the compiled module's, so the trace's: jit_<name>
    fn = gen._prefill_into
    assert fn.__name__ == "prefill_into"


def test_model_parts_carry_named_scopes(model):
    cfg, params = model
    cache = llama.init_cache(cfg, 2, 16)
    text = jax.jit(lambda p, t, c: llama.decode_step(p, t, c, cfg)).lower(
        params, jnp.zeros((2,), jnp.int32), cache).as_text(debug_info=True)
    for scope in ("attention", "mlp", "lm_head"):
        assert f"/{scope}/" in text or f"{scope}/" in text, scope


def test_a_capture_holds_launch_with_its_seq_and_device_wait(model, tmp_path):
    from jax.profiler import ProfileData

    gen = _gen(model)
    rec = DispatchRecorder(model="sp-trace")
    gen.recorder = rec
    gen.add_request([3, 1, 4], 12)
    gen.step()
    rec.commit()        # warm: every program has run before the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            gen.step()
            rec.commit()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    found: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("gofr.serve."):
                    found.setdefault(e.name, []).append(dict(e.stats))
    assert len(found["gofr.serve.launch"]) == 3
    # lag one: the first launch of the three settles nothing
    assert len(found["gofr.serve.device_wait"]) == 2
    seqs = [r["seq"] for r in _of("sp-trace")][1:]
    assert [s["seq"] for s in found["gofr.serve.launch"]] == seqs
    assert all(s["kind"] == "chunk" and s["rows"] == 1
               for s in found["gofr.serve.launch"])


def test_a_compile_after_warm_up_is_counted_and_named_in_the_events():
    with programs.watch_compiles():
        pass            # installs the listeners, claims nothing
    late = jax.jit(lambda x: x * 3 + 1)
    ones = jnp.ones((3,))
    before = programs.late_compiles()["compiles"]
    cursor = event_log().cursor
    t = threading.Thread(target=lambda: late(ones), name="sp-late-thread")
    t.start()
    t.join()
    assert programs.late_compiles()["compiles"] == before + 1
    assert programs.ProgramLog().totals()["late_compiles"] == before + 1
    ev, = [e for e in event_log().query(cursor, kind="compile")["events"]
           if e["thread"] == "sp-late-thread"]
    assert ev["cache"] in ("hit", "miss") and ev["seconds"] >= 0
    with programs.watch_compiles() as acc:   # a claimed compile is not late
        jax.jit(lambda x: x * 5 + 2)(ones)
    assert acc["compiles"] + acc["cache_hits"] >= 1
    assert programs.late_compiles()["compiles"] == before + 1


def test_a_late_compile_served_from_a_warm_cache_is_one_event(
        tmp_path, monkeypatch):
    """jax times a load from the persistent cache under the compile's own
    event too: one compile, one count, one event, and it says ``hit``."""
    from jax.experimental.compilation_cache import compilation_cache

    from gofr_tpu.ml.scheduler import maybe_enable_compilation_cache

    before_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    maybe_enable_compilation_cache()
    with programs.watch_compiles():
        pass
    ones = jnp.ones((5,))
    try:
        seen = []
        for _ in range(2):      # the same program from a fresh jit object
            before = programs.late_compiles()["compiles"]
            cursor = event_log().cursor
            jax.jit(lambda x: x * 7 + 3)(ones)
            assert programs.late_compiles()["compiles"] == before + 1
            ev, = event_log().query(cursor, kind="compile")["events"]
            seen.append(ev["cache"])
        assert seen == ["miss", "hit"]
    finally:
        jax.config.update("jax_compilation_cache_dir", before_dir)
        compilation_cache.reset_cache()
