"""Double-buffered dispatch (tier-1): the pipelined serving loop.

The headline contracts under test: ``GOFR_ML_PIPELINE`` unset (or 0)
leaves the lag-one serving loop byte-identical with NO pipeline
machinery constructed (the test_decode_window zero-overhead pattern);
greedy output with two dispatches in flight is bit-identical to the
settled loop — plain chunked, fused windows, speculative windows, the
token-budget scheduler, and int4 KV pages; the knob validates loudly;
tokens a speculatively re-dispatched window computed for a slot that
died before its settle are charged as ``pipeline_overshoot`` (the
ledger balances, and ``window_overshoot`` keeps naming live rows'
raggedness); a crash with two windows in flight fails only the active
slots and recovers with zero dispatches outstanding; the deadline
reaper works mid-overlap; journey decode marks carry the in-flight
depth; and the flight recorder stamps the ``overlap`` dim and
estimates ``host_idle_estimate``.
"""

import asyncio

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.flight_recorder import DispatchRecorder
from gofr_tpu.ml.errors import DeadlineExceeded
from gofr_tpu.ml.generate import Generator, pipeline_from_env
from gofr_tpu.ml.goodput import (WASTE_REASONS, GoodputLedger,
                                 goodput_ledger)
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.models import llama

PROMPTS = ([3, 1, 4, 1], [2, 7, 1, 8])


@pytest.fixture(scope="module")
def model():
    # float32 for the same reason as test_decode_window: the identity
    # claims compare different dispatch cadences, and bf16 rounding can
    # flip a near-tie argmax between them
    cfg = llama.tiny_llama(use_flash=False, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _gen(model, **kw):
    cfg, params = model
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("page_size", 8)
    return Generator(params, cfg, **kw)


def _serve(gen, prompts=PROMPTS, max_new=(10, 7)):
    outs: dict[int, list[int]] = {}

    def cb(slot):
        def f(_s, toks):
            outs.setdefault(slot, []).extend(int(t) for t in toks)
        return f

    for i, (p, n) in enumerate(zip(prompts, max_new, strict=True)):
        gen.add_request(list(p), n, callback=cb(i))
    for _ in range(200):
        if gen.n_live == 0:
            break
        gen.step()
    gen.drain()
    return outs


# ----------------------------------------------------------- env validation
def test_pipeline_knob_validation(monkeypatch):
    monkeypatch.delenv("GOFR_ML_PIPELINE", raising=False)
    assert pipeline_from_env() == 0
    for raw, want in (("0", 0), ("off", 0), ("1", 1), ("on", 1),
                      (" ON ", 1)):
        monkeypatch.setenv("GOFR_ML_PIPELINE", raw)
        assert pipeline_from_env() == want
    for bad in ("2", "banana", "true"):
        monkeypatch.setenv("GOFR_ML_PIPELINE", bad)
        with pytest.raises(ValueError, match="GOFR_ML_PIPELINE"):
            pipeline_from_env()


def test_pipeline_env_pickup(model, monkeypatch):
    monkeypatch.setenv("GOFR_ML_PIPELINE", "1")
    gen = _gen(model)
    assert gen.pipeline == 1
    # an explicit constructor arg beats the env
    assert _gen(model, pipeline=0).pipeline == 0


# ----------------------------------------------------- zero-overhead contract
def test_pipeline_unset_constructs_nothing(model, monkeypatch):
    """Knob unset: no pipeline machinery anywhere (the is-not-None
    contract) and greedy output is byte-identical to an explicit
    pipeline=0 generator."""
    monkeypatch.delenv("GOFR_ML_PIPELINE", raising=False)
    gen = _gen(model, decode_window=4)
    assert gen.pipeline == 0
    assert gen.pipeline_stats() is None
    assert not hasattr(gen, "pipeline_windows")
    assert not hasattr(gen, "pipeline_overshoot")
    out = _serve(gen)
    exp = _serve(_gen(model, decode_window=4, pipeline=0))
    assert out == exp


# --------------------------------------------------------- greedy identity
def test_pipelined_chunk_greedy_identity(model):
    """Plain chunked decode (no windows): double-buffering the chunk
    dispatches changes nothing about the tokens."""
    exp = _serve(_gen(model))
    gen = _gen(model, pipeline=1)
    assert _serve(gen) == exp
    stats = gen.pipeline_stats()
    assert stats["depth"] == 2 and stats["windows_overlapped"] >= 1


def test_pipelined_window_greedy_identity(model):
    exp = _serve(_gen(model, decode_window=0))
    gen = _gen(model, decode_window=4, pipeline=1)
    assert _serve(gen) == exp
    assert gen.pipeline_stats()["windows_overlapped"] >= 1
    assert gen.window_stats()["windows"] >= 1


def test_pipelined_window_identity_with_budget_scheduler(model):
    """TokenBudgetScheduler plans window N+1 from N's planned state:
    the pending-grant subtraction keeps the budget honest at depth 2."""
    exp = _serve(_gen(model, decode_window=0, token_budget=64))
    gen = _gen(model, decode_window=4, token_budget=64, pipeline=1)
    assert _serve(gen) == exp
    assert gen.scheduler.window_mode is True


def test_pipelined_spec_window_identity(model):
    # budgets big enough that one specwin's conservative grant
    # (window * (k+1) positions) doesn't exhaust them — otherwise the
    # planner never has a reason to put a second window in flight
    new = (20, 18)
    exp = _serve(_gen(model, decode_window=0, spec_k=2), max_new=new)
    gen = _gen(model, decode_window=4, spec_k=2, pipeline=1)
    assert _serve(gen, max_new=new) == exp
    assert gen.spec_stats()["windows"] >= 1
    assert gen.pipeline_stats()["windows_overlapped"] >= 1


def test_pipelined_quantized_kv_identity():
    cfg = llama.tiny_llama(use_flash=False, dtype=jnp.float32, kv_bits=4)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    model = (cfg, params)
    exp = _serve(_gen(model, decode_window=4))
    assert _serve(_gen(model, decode_window=4, pipeline=1)) == exp


# ------------------------------------------------------ overshoot economics
def test_pipeline_overshoot_charged_to_goodput(model):
    """A slot reaped host-side with TWO windows in flight: everything
    the device computed for it in the unsettled windows is charged as
    pipeline_overshoot — and window_overshoot stays untouched, because
    no live row had raggedness."""
    assert "pipeline_overshoot" in WASTE_REASONS
    gen = _gen(model, decode_window=4, pipeline=1)
    ledger = GoodputLedger()
    gen.goodput = ledger.handle("pp-over")
    outs: dict[int, list[int]] = {}
    slot = gen.add_request([3, 1, 4, 1], 16,
                           callback=lambda s, t: outs.setdefault(
                               s, []).extend(int(x) for x in t))
    gen.step()  # mini dispatch (first token), drains synchronously
    gen.step()  # window A dispatched, in flight
    gen.step()  # window B dispatched from A's planned state — depth 2
    assert len(gen._inflight) == 2
    gen.slots[slot].live = False  # the serving reaper's cancel
    gen.drain()
    assert gen.pipeline_overshoot > 0
    assert gen.window_overshoot == 0
    wasted = ledger.wasted_totals()
    assert (wasted[("pp-over", "pipeline_overshoot")]
            == gen.pipeline_overshoot)
    snap = ledger.snapshot_model("pp-over")
    assert snap["delivered"] == 0
    assert snap["device_tokens"] == snap["delivered"] + snap["wasted_total"]


# ----------------------------------------------------------- chaos & reaping
def test_crash_with_two_windows_in_flight(model, run):
    """GOFR_ML_FAULT-style poison with the pipe full: the watchdog
    fails only the in-flight slots, queued requests survive on the
    rebuilt generator, the ledger balances, and recovery leaves ZERO
    dispatches outstanding — no hang."""
    box: dict = {"fired": 0}

    def hook(point):
        if (point == "step" and box["fired"] == 0
                and len(box["gen"]._inflight) >= 2):
            box["fired"] += 1
            raise RuntimeError("chaos with two windows in flight")

    server = LLMServer(_gen(model, decode_window=4, pipeline=1),
                       name="pp-chaos", fault=hook, max_restarts=3)
    box["gen"] = server.gen

    async def scenario():
        async def one(p):
            try:
                return await server.generate(p, 8, deadline_s=30.0)
            except Exception:
                return None
        return await asyncio.gather(*(one(p) for p in
                                      ([3, 1, 4], [2, 7, 1, 8],
                                       [5, 9, 2], [6, 2, 6])))

    try:
        outs = run(scenario())
    finally:
        server.close()
    assert box["fired"] == 1
    ok = [o for o in outs if o is not None]
    assert len(ok) >= 2, "queued requests must survive the crash"
    assert len(server.gen._inflight) == 0
    snap = goodput_ledger().snapshot_model("pp-chaos")
    assert snap["wasted"].get("crashed", 0) >= 1
    assert (snap["delivered"] + sum(snap["wasted"].values())
            == snap["device_tokens"])


def test_deadline_reap_mid_overlap(model, run):
    """The reaper cancels a slot while its next window is already in
    flight: the request fails with DeadlineExceeded, the in-flight
    tokens land in the pipeline_overshoot column, and the ledger still
    balances."""
    import time

    server = LLMServer(_gen(model, decode_window=4, pipeline=1),
                       name="pp-dl")
    server.gen.fault = lambda p: (time.sleep(0.05) if p == "step"
                                  else None)

    async def scenario():
        with pytest.raises(DeadlineExceeded):
            await server.generate([3, 1, 4], 50, deadline_s=0.3)

    try:
        run(scenario())
    finally:
        server.close()
    gen = server.gen
    snap = goodput_ledger().snapshot_model("pp-dl")
    assert snap["wasted"].get("deadline_cancelled", 0) >= 1
    assert (snap["wasted"].get("pipeline_overshoot", 0)
            == gen.pipeline_overshoot)
    assert snap["delivered"] == 0
    assert (snap["delivered"] + sum(snap["wasted"].values())
            == snap["device_tokens"])


def test_recover_drops_both_inflight_windows(model):
    gen = _gen(model, decode_window=4, pipeline=1)
    gen.add_request([3, 1, 4, 1], 16, callback=lambda s, t: None)
    gen.step()
    gen.step()
    gen.step()
    assert len(gen._inflight) == 2
    gen.recover()
    assert len(gen._inflight) == 0
    # the rebuilt generator serves a fresh request to completion
    outs = _serve(gen, prompts=([2, 7, 1, 8],), max_new=(6,))
    assert len(outs[0]) == 6


# ------------------------------------------------------------- observability
def test_journey_decode_marks_carry_inflight_depth(model, run):
    from gofr_tpu.ml.journey import journey_log

    server = LLMServer(_gen(model, decode_window=4, pipeline=1),
                       name="pp-journey")

    async def scenario():
        return await server.generate([3, 1, 4, 1], 12)

    try:
        out = run(scenario())
    finally:
        server.close()
    assert len(out) == 12
    rid = journey_log().snapshot()["recent_rids"][-1]
    waterfall = journey_log().get(rid).snapshot()
    depths = [m["inflight"] for m in waterfall["marks"]
              if m["mark"] in ("prefill", "decode")]
    assert depths and all(0 <= d <= 2 for d in depths)
    assert any(d == 2 for d in depths), \
        "steady-state settles must observe the double-buffered depth"


def test_recorder_overlap_dim_and_idle_share(model):
    gen = _gen(model, decode_window=4, pipeline=1)
    rec = DispatchRecorder(model="pp-rec", ring=64)
    gen.recorder = rec
    outs: dict[int, list[int]] = {}
    gen.add_request([3, 1, 4, 1], 12,
                    callback=lambda s, t: outs.setdefault(
                        s, []).extend(int(x) for x in t))
    for _ in range(50):
        if gen.n_live == 0:
            break
        gen.step()
        rec.commit()
    gen.drain()
    rec.commit()
    tail = rec.tail(64)
    assert any(r.get("overlap", 0) >= 2 for r in tail), \
        "double-buffered passes must stamp the overlap dim"
    assert any(r.get("busy_s", 0.0) > 0.0 for r in tail)
    snap = rec.snapshot()
    assert snap["overlapped_dispatches"] >= 1
    idle = snap["host_idle_estimate"]
    assert idle is None or 0.0 <= idle <= 1.0
    # the per-generator stats block surfaces the same estimate
    stats = gen.pipeline_stats()
    assert set(stats) == {"depth", "windows_overlapped",
                          "overshoot_tokens", "host_idle_estimate"}
    assert stats["host_idle_estimate"] == idle


def test_serving_snapshot_pipeline_block(model, run):
    """/debug/serving's per-LLM block: an armed generator reports its
    pipeline stats; an unarmed one has no pipeline key at all."""
    from gofr_tpu.ml import MLDatasource

    async def scenario():
        ml = MLDatasource()
        server = ml.register_llm(
            "pp-chat", None, None,
            generator=_gen(model, decode_window=4, pipeline=1))
        plain = ml.register_llm("pp-plain", None, None,
                                generator=_gen(model))
        try:
            await server.generate([3, 1, 4, 1], 14)
            llms = ml.serving_snapshot()["llms"]
            return llms["pp-chat"], llms["pp-plain"]
        finally:
            server.close()
            plain.close()

    armed, plain = run(scenario())
    assert armed["pipeline"]["depth"] == 2
    assert armed["pipeline"]["windows_overlapped"] >= 1
    assert "pipeline" not in plain
