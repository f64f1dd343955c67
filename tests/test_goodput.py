"""Serving economics: goodput ledger, program/compile telemetry, and the
anomaly-triggered auto-profiler (tier-1, CPU).

The headline contracts under test: the goodput ledger BALANCES BY
CONSTRUCTION (delivered + sum of wasted reasons == device-computed
tokens) across natural finishes, speculation, deadlines, disconnects,
and crashes; ``GOFR_ML_GOODPUT=0`` and ``GOFR_ML_AUTOPROF=0`` leave the
serving hot path untouched (no ledger/profiler objects anywhere,
byte-identical greedy output — the ``GOFR_ML_JOURNEY=0`` pattern); every
warmed jitted program appears in the /debug/programs inventory with its
compile wall and cache provenance; and a forced slowdown trips exactly
ONE auto-profile capture within the cooldown window.
"""

import asyncio
import os
import time

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gofr_tpu.app import App
from gofr_tpu.config import MapConfig
from gofr_tpu.flight_recorder import (AutoProfiler, ProfileVault,
                                      autoprof_enabled, event_log,
                                      profile_vault)
from gofr_tpu.ml.errors import DeadlineExceeded, GeneratorCrashed
from gofr_tpu.ml.generate import Generator
from gofr_tpu.ml.goodput import (WASTE_REASONS, GoodputLedger,
                                 goodput_enabled, goodput_ledger)
from gofr_tpu.ml.kv_offload import HostKVStore, OffloadConfig
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.models import llama


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_llama(use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _gen(model, **kw):
    cfg, params = model
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    return Generator(params, cfg, **kw)


def _balanced(snap: dict) -> bool:
    return (snap["delivered"] + sum(snap["wasted"].values())
            == snap["device_tokens"])


def _ledger_for(name: str) -> dict:
    led = goodput_ledger()
    assert led is not None
    return led.snapshot_model(name)


# ---------------------------------------------------------------- unit level
def test_ledger_unit():
    led = GoodputLedger()
    led.note("m", "delivered", 10)
    led.note("m", "spec_rejected", 3)
    led.note("m/0", "crashed", 2)  # a replica core rolls up under "m"
    led.note("m", "delivered", 0)  # zero-token notes are dropped
    with pytest.raises(ValueError):
        led.note("m", "not_a_reason", 1)
    snap = led.snapshot_model("m")
    assert snap["device_tokens"] == 15
    assert snap["delivered"] == 10
    assert snap["wasted"] == {"spec_rejected": 3, "crashed": 2}
    assert _balanced(snap)
    assert snap["goodput"] == pytest.approx(10 / 15, abs=1e-4)
    # the handle binds a model name for components that don't know theirs
    led.handle("other").note("restore_fallback", 4)
    assert led.snapshot_model("other")["wasted"] == {"restore_fallback": 4}
    fleet = led.snapshot()["fleet"]
    assert fleet["device_tokens"] == 19
    assert _balanced(fleet)
    assert led.wasted_totals()[("m", "spec_rejected")] == 3


def test_knob_defaults(monkeypatch):
    assert goodput_enabled() and autoprof_enabled()
    monkeypatch.setenv("GOFR_ML_GOODPUT", "0")
    monkeypatch.setenv("GOFR_ML_AUTOPROF", "0")
    assert not goodput_enabled() and not autoprof_enabled()
    assert goodput_ledger() is None


# ----------------------------------------------------------- delivered path
def test_delivered_end_to_end(model, run):
    server = LLMServer(_gen(model), name="gp-ok")

    async def scenario():
        out = await server.generate([3, 1, 4, 1], 8)
        assert len(out) == 8

    try:
        run(scenario())
    finally:
        server.close()
    snap = _ledger_for("gp-ok")
    assert snap["delivered"] == 8
    assert snap["wasted"] == {}
    assert snap["goodput"] == 1.0
    assert _balanced(snap)


def test_spec_rejected_balances(model, run):
    """Lookup-mode speculation on a random tiny model rejects most
    drafts: the ledger itemizes them and still balances exactly —
    delivered + spec_rejected == every position the device computed."""
    server = LLMServer(_gen(model, spec_k=2, chunk=2), name="gp-spec")

    async def scenario():
        out = await server.generate([3, 1, 4, 1, 5], 10)
        assert len(out) == 10

    try:
        run(scenario())
    finally:
        server.close()
    snap = _ledger_for("gp-spec")
    assert snap["delivered"] == 10
    assert snap["wasted"].get("spec_rejected", 0) > 0
    assert _balanced(snap)
    # cross-check against the generator's own acceptance accounting:
    # every verify window computed K+1 positions; emitted ones delivered
    gen = server.gen
    computed = gen.spec_windows * (gen.spec_k + 1)
    assert (snap["wasted"]["spec_rejected"]
            == computed - gen.spec_emitted)


# ------------------------------------------------------------- wasted paths
def test_deadline_cancelled_mid_decode(model, run):
    server = LLMServer(_gen(model), name="gp-dl")
    server.gen.fault = lambda p: time.sleep(0.05) if p == "step" else None

    async def scenario():
        with pytest.raises(DeadlineExceeded):
            await server.generate([3, 1, 4], 50, deadline_s=0.3)

    try:
        run(scenario())
    finally:
        server.close()
    snap = _ledger_for("gp-dl")
    assert snap["wasted"].get("deadline_cancelled", 0) >= 1
    assert snap["delivered"] == 0
    assert _balanced(snap)


def test_disconnected_consumer(model, run):
    server = LLMServer(_gen(model), name="gp-bye")

    async def scenario():
        agen = server.stream_chunks([3, 1, 4], 40)
        async for _burst in agen:
            break  # walk away after the first burst
        await agen.aclose()
        # wait for the serving thread to reap the cancelled slot
        for _ in range(400):
            if server.gen.n_live == 0 and not server._active:
                break
            await asyncio.sleep(0.005)

    try:
        run(scenario())
    finally:
        server.close()
    snap = _ledger_for("gp-bye")
    assert snap["wasted"].get("disconnected", 0) >= 1
    assert _balanced(snap)


def test_crashed_slots(model, run):
    server = LLMServer(_gen(model), name="gp-boom", max_restarts=0)
    fired = {"n": 0}

    def hook(point):
        if point == "step":
            fired["n"] += 1
            if fired["n"] > 1:
                raise RuntimeError("injected mid-decode")

    server.gen.fault = hook

    async def scenario():
        with pytest.raises(GeneratorCrashed):
            await server.generate([3, 1, 4], 12)

    try:
        run(scenario())
    finally:
        server.close()
    snap = _ledger_for("gp-boom")
    assert snap["wasted"].get("crashed", 0) >= 1
    assert snap["delivered"] == 0
    assert _balanced(snap)


def test_restore_fallback_classification_points():
    """The host-tier fallback points note the already-paid tokens: an
    over-budget reject in the store, and the admission-race miss in the
    radix cache."""
    import numpy as np

    from gofr_tpu.ml.prefix_cache import RadixPrefixCache

    led = GoodputLedger()
    store = HostKVStore(OffloadConfig(budget_mb=1 / 1024))  # 1 KiB
    store.goodput = led.handle("kv")
    big = {"k": np.zeros((4, 4, 64), np.float32)}
    assert not store.put((1, 2, 3), big, {"len": 12, "tail": [],
                                          "ids_full": [1, 2, 3]})
    assert led.snapshot_model("kv")["wasted"] == {"restore_fallback": 12}

    cache = RadixPrefixCache.__new__(RadixPrefixCache)  # record_miss only
    import threading

    cache._lock = threading.Lock()
    cache.misses = 0
    cache._metrics = None
    cache.goodput = led.handle("px")
    cache.record_miss(lost_tokens=8)
    assert led.snapshot_model("px")["wasted"] == {"restore_fallback": 8}
    assert cache.misses == 1


def test_failover_recompute_in_pool(model, run, monkeypatch):
    """A replica loss re-prefills the rerouted prompt on the survivor:
    the pool classifies those prompt tokens as failover_recompute under
    the POOL name, the dead core's in-flight tokens as crashed under its
    own — and the pool-level rollup still balances."""
    from gofr_tpu.ml.replica import ReplicaPool

    monkeypatch.setenv("GOFR_ML_FAULT", "step:1.0:RuntimeError")
    monkeypatch.setenv("GOFR_ML_FAULT_REPLICA", "0")
    gens = [_gen(model, batch_slots=1), _gen(model, batch_slots=1)]
    pool = ReplicaPool(gens, name="gp-pool", max_restarts=0)

    async def scenario():
        out = await pool.generate([3, 1, 4], 6)
        assert len(out) == 6

    try:
        run(scenario())
    finally:
        pool.close()
    led = goodput_ledger()
    fleet = led.snapshot_model("gp-pool")  # pool + cores rolled up
    assert fleet["wasted"].get("failover_recompute", 0) >= 3
    assert fleet["delivered"] >= 6
    assert _balanced(fleet)


# -------------------------------------------------------- zero overhead
def test_goodput_disabled_leaves_hot_path_untouched(model, run,
                                                    monkeypatch):
    exp = _gen(model).generate([3, 1, 4], 6)
    monkeypatch.setenv("GOFR_ML_GOODPUT", "0")
    server = LLMServer(_gen(model), name="gp-off")

    async def scenario():
        assert server._goodput is None
        assert server.gen.goodput is None
        out = await server.generate([3, 1, 4], 6)
        assert out == exp

    try:
        run(scenario())
    finally:
        server.close()
    from gofr_tpu.ml import goodput as goodput_mod

    # nothing was recorded anywhere, not even on the underlying global
    snap = goodput_mod._LEDGER.snapshot_model("gp-off")
    assert snap["device_tokens"] == 0


def test_autoprof_disabled_leaves_hot_path_untouched(model, run,
                                                     monkeypatch):
    exp = _gen(model).generate([3, 1, 4], 6)
    monkeypatch.setenv("GOFR_ML_AUTOPROF", "0")
    server = LLMServer(_gen(model), name="ap-off")

    async def scenario():
        assert server.autoprof is None
        assert server.recorder is not None
        assert server.recorder.observer is None
        out = await server.generate([3, 1, 4], 6)
        assert out == exp

    try:
        run(scenario())
    finally:
        server.close()


# ------------------------------------------------------- auto-profiler
def _fake_capture(calls):
    def capture(trace_dir, seconds):
        calls.append(seconds)
        with open(os.path.join(trace_dir, "trace.txt"), "w") as f:
            f.write("fake-trace")

    return capture


def _drain_captures(prof, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if prof.captures + prof.failures + prof.skipped_busy > 0:
            return
        time.sleep(0.01)


def test_autoprof_triggers_exactly_once_per_cooldown():
    vault = ProfileVault()
    calls: list = []
    prof = AutoProfiler(model="ap-unit", vault=vault, multiplier=2.0,
                        cooldown_s=60.0, capture_s=0.2, window=4,
                        baseline=16, min_baseline=8,
                        capture_fn=_fake_capture(calls))
    cursor = event_log().cursor
    for _ in range(16):  # fill the baseline with fast steps
        prof.observe(0.001, {"launch": 0.001})
    for _ in range(12):  # sustained 10x regression: 3 slow windows
        prof.observe(0.010, {"launch": 0.010})
    _drain_captures(prof)
    # wait for the capture thread to land the bundle
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and not vault.list():
        time.sleep(0.01)
    assert prof.captures == 1, prof.snapshot()
    assert prof.failures == 0
    bundles = vault.list()
    assert len(bundles) == 1
    assert bundles[0]["model"] == "ap-unit"
    assert bundles[0]["trigger"]["reason"] == "step_ms_p50"
    assert calls == [0.2]
    full = vault.get(bundles[0]["id"])
    assert full["data"]  # the zip bytes exist
    events = event_log().query(since=cursor, kind="profile")["events"]
    assert len(events) == 1 and events[0]["model"] == "ap-unit"
    # cooldown holds: more regressed windows don't re-trigger
    for _ in range(20):
        prof.observe(0.010, {"launch": 0.010})
    assert prof.captures == 1
    snap = prof.snapshot()
    assert snap["cooling_down"] and snap["last_trigger"] is not None


def test_autoprof_phase_share_trigger():
    calls: list = []
    prof = AutoProfiler(model="ap-share", vault=ProfileVault(),
                        multiplier=100.0,  # step p50 can never trip
                        cooldown_s=60.0, capture_s=0.1, window=4,
                        baseline=16, min_baseline=8, share_jump=0.25,
                        capture_fn=_fake_capture(calls))
    for _ in range(16):
        prof.observe(0.002, {"launch": 0.002})
    for _ in range(8):  # same wall, but device_wait-dominant → emit jump
        prof.observe(0.002, {"emit": 0.002})
    _drain_captures(prof)
    assert prof.captures == 1
    assert prof.last_trigger["reason"] == "phase_share"
    assert prof.last_trigger["phase"] == "emit"


def test_autoprof_serving_integration(model, run):
    """The serve-loop wiring: recorder commits feed the profiler, and a
    forced slowdown (fault-injected sleep) trips one capture."""
    server = LLMServer(_gen(model), name="ap-live")
    assert server.autoprof is not None
    calls: list = []
    # re-tune the profiler for test scale; rebind the observer
    prof = AutoProfiler(model="ap-live", vault=ProfileVault(),
                        multiplier=3.0, cooldown_s=300.0, capture_s=0.1,
                        window=4, baseline=16, min_baseline=8,
                        capture_fn=_fake_capture(calls))
    server.autoprof = prof
    server.recorder.observer = prof.observe
    slow = {"on": False}
    server.gen.fault = (lambda p: time.sleep(0.03)
                        if slow["on"] and p == "step" else None)

    async def scenario():
        for _ in range(4):  # baseline traffic
            await server.generate([3, 1, 4], 8)
        slow["on"] = True
        for _ in range(4):  # regressed traffic
            await server.generate([3, 1, 4], 8)

    try:
        run(scenario())
        _drain_captures(prof)
    finally:
        server.close()
    assert prof.dispatches >= 24
    assert prof.captures == 1, prof.snapshot()
    snap = _ledger_for("ap-live")
    assert snap["delivered"] == 64 and _balanced(snap)


# --------------------------------------------------- program inventory
def test_programs_inventory_ladder_and_buckets(model):
    gen = _gen(model, chunk=4)
    gen.warmup()
    rows = {r["name"]: r for r in gen.programs.snapshot()}
    assert "decode/chunk4" in rows and "decode/chunk1" in rows
    assert "prefill/1x64" in rows  # dense: the ladder of max_seq 64
    for row in rows.values():
        assert row["wall_s"] > 0
        assert row["cache"] in ("compiled", "persistent_cache", "cached",
                                "unknown")
    costed = {r["name"]: r for r in gen.programs.snapshot(cost=True)}
    assert costed["decode/chunk4"]["cost"]["flops"] > 0
    totals = gen.programs.totals()
    assert totals["programs"] == len(rows)
    assert totals["compile_s"] > 0
    # a re-warm (recover path) must not duplicate rows
    gen.programs.record("decode/chunk4", wall_s=1.0)
    assert gen.programs.totals()["programs"] == len(rows)
    assert gen.programs.snapshot()[0]["warm_count"] >= 1


def test_programs_spec_ladder_named(model):
    gen = _gen(model, spec_k=2, chunk=2)
    gen.warmup()
    names = {r["name"] for r in gen.programs.snapshot()}
    assert any(n.startswith("spec/window") for n in names)


def test_programs_paged_ops_recorded(model):
    cfg, params = model
    store = HostKVStore(OffloadConfig(budget_mb=8))
    gen = Generator(params, cfg, batch_slots=2, max_seq=64,
                    prefill_buckets=(8, 16), page_size=4, n_pages=16,
                    host_kv=store)
    gen.warmup()
    pid = gen.register_prefix([5, 6, 7, 8, 9])
    assert gen.drop_prefix(pid, spill=True)  # → paged/gather compiles
    gen.restore_prefix(tuple([5, 6, 7, 8, 9]))  # → paged/scatter
    names = {r["name"] for r in gen.programs.snapshot()}
    assert "paged/gather" in names and "paged/scatter" in names


def test_engine_program_row(model):
    import numpy as np

    from gofr_tpu.ml import MLDatasource

    ml = MLDatasource()
    x = np.ones((2, 4), np.float32)
    engine = ml.register("toy", apply_fn=lambda p, a: a * p,
                         params=np.float32(2.0), example_inputs=(x,))
    assert "apply/b2" in engine.programs
    rows = engine.programs.snapshot(cost=True)
    row = next(r for r in rows if r["name"] == "apply/b2")
    assert row["wall_s"] > 0 and row["cache"] != ""
    snap = ml.programs_snapshot(cost=False)
    assert "toy" in snap["models"]
    assert snap["models"]["toy"]["totals"]["programs"] >= 1
    assert "hbm" in snap
    ml.close()


# ------------------------------------------------------ debug endpoints
def test_debug_endpoints(model, run):
    async def scenario():
        app = App(config=MapConfig({"APP_NAME": "gp-app"}))
        ml = app._ensure_ml()
        gen = _gen(model)
        gen.warmup()  # register_llm warms in production: the ladder rows
        server = LLMServer(gen, name="gp-http")
        ml._llms["gp-http"] = server
        http_server = TestServer(app._build_http_app())
        client = TestClient(http_server)
        await client.start_server()
        try:
            await server.generate([3, 1, 4], 6)

            resp = await client.get("/debug/goodput")
            assert resp.status == 200
            data = (await resp.json())["data"]
            assert data["enabled"]
            assert data["models"]["gp-http"]["delivered"] == 6
            assert _balanced(data["fleet"])

            resp = await client.get("/debug/serving")
            body = (await resp.json())["data"]
            entry = body["llms"]["gp-http"]
            assert entry["goodput"]["delivered"] == 6
            assert "autoprof" in entry
            # CPU devices report no memory_stats: the hbm block says so
            # explicitly, with the RSS fallback spelled out
            hbm = body["hbm"]
            assert all(v == "unsupported" for v in hbm["devices"].values())
            assert hbm["fallback"] == "host_rss"
            assert hbm["host_rss_bytes"] > 0

            resp = await client.get("/debug/programs")
            progs = (await resp.json())["data"]
            names = {r["name"]
                     for r in progs["models"]["gp-http"]["entries"]}
            assert any(n.startswith("decode/chunk") for n in names)

            resp = await client.get("/debug/profile/auto")
            assert resp.status == 200
            body = (await resp.json())["data"]
            assert body["enabled"] is True
            assert isinstance(body["captures"], list)
            resp = await client.get("/debug/profile/auto/nope-1")
            assert resp.status == 404

            # a vault entry is downloadable as a zip
            pid = profile_vault().capture(
                model="gp-http", trigger={"reason": "step_ms_p50"},
                data=b"PK\x05\x06" + b"\0" * 18)
            resp = await client.get(f"/debug/profile/auto/{pid}")
            assert resp.status == 200
            assert resp.content_type == "application/zip"
        finally:
            await client.close()
            server.close()

    run(scenario())
