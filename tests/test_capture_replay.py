"""Serving time machine: traffic capture & deterministic replay (tier-1).

The headline contracts under test: ``GOFR_ML_CAPTURE`` unset constructs
NO capture machinery and leaves the hot path byte-identical (the
test_journey zero-overhead pattern); a greedy mixed-load window
(priorities + deadlines + a replica-pool fleet) captured then replayed
on the same config yields a 100% output-digest identity rate and a
balanced goodput-ledger delta; the bundle codec round-trips bit-exactly
(the kv_transport frame style); capture under chaos replays clean with
the recorded failures CLASSIFIED, not reproduced or crashed; crash
bundles embed the capture tail so a saved ``/debug/crash/<id>`` body
feeds ``ml.replay.load_bundle`` directly; and ``/debug/capture`` +
the ``/debug/serving`` top-level ``runtime`` block answer over HTTP.
"""

import asyncio
import json

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from gofr_tpu.app import App
from gofr_tpu.config import MapConfig
from gofr_tpu.ml.capture import (BUNDLE_FORMAT, decode_bundle,
                                 encode_bundle, fingerprint_drift,
                                 runtime_fingerprint, token_digest,
                                 traffic_capture)
from gofr_tpu.ml.generate import Generator
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.ml.replay import ReplayHarness, load_bundle
from gofr_tpu.ml.replica import ReplicaPool
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


def _arm(monkeypatch, ring: int = 64):
    monkeypatch.setenv("GOFR_ML_CAPTURE", str(ring))
    cap = traffic_capture()
    cap.clear()
    return cap


# ---------------------------------------------------------------- unit level
def test_bundle_codec_round_trip():
    bundle = {
        "format": BUNDLE_FORMAT,
        "captured_at": 123.0,
        "runtime": runtime_fingerprint(),
        "fleet": {"chat": {"kind": "pool", "replicas": 2}},
        "counts": {"exported": 2},
        "requests": [
            {"rid": "r1", "model": "chat", "t_offset_s": 0.0,
             "tokens": [3, 1, 4, 1, 5], "max_new": 8, "priority": 0,
             "deadline_s": 0.0, "mode": "chunks", "prefix": False,
             "done": True, "finish_reason": "stop", "n_out": 3,
             "digest": token_digest([9, 2, 6]), "ttft_s": 0.01,
             "tpot_s": 0.002},
            {"rid": "r2", "model": "chat", "t_offset_s": 0.25,
             "tokens": [], "max_new": 4, "priority": 2,
             "deadline_s": 1.5, "mode": "generate", "prefix": True,
             "done": True, "finish_reason": "deadline", "n_out": 0,
             "digest": None, "ttft_s": None, "tpot_s": None},
        ],
    }
    raw = encode_bundle(bundle)
    back = decode_bundle(raw)
    assert back["requests"][0]["tokens"] == [3, 1, 4, 1, 5]
    assert back["requests"][1]["tokens"] == []
    # everything but the payload section survives as the same JSON
    strip = [{k: v for k, v in r.items() if k != "tokens"}
             for r in bundle["requests"]]
    assert [{k: v for k, v in r.items() if k != "tokens"}
            for r in back["requests"]] == strip
    with pytest.raises(ValueError, match="format"):
        decode_bundle(encode_bundle({**bundle, "format": "other/9"}))
    with pytest.raises(ValueError, match="truncated"):
        decode_bundle(raw[:-3])


def test_fingerprint_drift_lines():
    rec = runtime_fingerprint()
    assert fingerprint_drift(rec, runtime_fingerprint()) == []
    other = json.loads(json.dumps(rec))
    other["jax"] = "99.0"
    other["devices"]["count"] = 1024
    other["knobs"]["GOFR_ML_SPEC_K"] = "4"
    # the time machine's own knobs differing is the tool itself, never
    # workload drift
    other["knobs"]["GOFR_ML_CAPTURE"] = "512"
    other["knobs"]["GOFR_ML_REPLAY_SPEED"] = "4"
    drift = fingerprint_drift(rec, other)
    assert any("jax" in line for line in drift)
    assert any("count" in line for line in drift)
    assert any("GOFR_ML_SPEC_K" in line for line in drift)
    assert not any("GOFR_ML_CAPTURE" in line for line in drift)
    assert not any("GOFR_ML_REPLAY_SPEED" in line for line in drift)


def test_capture_knob_validation(monkeypatch):
    from gofr_tpu.ml.capture import capture_enabled

    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    assert not capture_enabled() and traffic_capture() is None
    monkeypatch.setenv("GOFR_ML_CAPTURE", "0")
    assert not capture_enabled()
    monkeypatch.setenv("GOFR_ML_CAPTURE", "banana")
    with pytest.raises(ValueError, match="GOFR_ML_CAPTURE"):
        capture_enabled()
    monkeypatch.setenv("GOFR_ML_CAPTURE", "-2")
    with pytest.raises(ValueError, match="GOFR_ML_CAPTURE"):
        capture_enabled()


def test_replay_speed_validation(monkeypatch):
    from gofr_tpu.ml.replay import replay_speed_from_env

    monkeypatch.delenv("GOFR_ML_REPLAY_SPEED", raising=False)
    assert replay_speed_from_env() == 1.0
    monkeypatch.setenv("GOFR_ML_REPLAY_SPEED", "4")
    assert replay_speed_from_env() == 4.0
    for bad in ("0", "-1", "nan", "inf", "fast"):
        monkeypatch.setenv("GOFR_ML_REPLAY_SPEED", bad)
        with pytest.raises(ValueError, match="GOFR_ML_REPLAY_SPEED"):
            replay_speed_from_env()


def test_capture_ring_bounds_and_offset_normalization(monkeypatch):
    cap = _arm(monkeypatch, ring=16)
    for i in range(40):
        rec = cap.admit(f"cr{i}", model="m", tokens=[1, i], max_new=4,
                        priority=1, deadline_s=0.0, mode="chunks")
        rec.add_tokens([7, 8])
        rec.finish("stop")
    stats = cap.stats()
    assert stats["retained"] == 16 and stats["dropped"] == 24
    out = cap.export()
    assert out["counts"]["exported"] == 16
    # offsets normalize to the window start: replay never sleeps
    # through the uptime that preceded the ring's oldest survivor
    assert out["requests"][0]["t_offset_s"] == 0.0
    assert out["requests"][0]["digest"] == token_digest([7, 8])
    one = cap.export(rid="cr39")
    assert (one["counts"]["exported"] == 1
            and one["requests"][0]["rid"] == "cr39")
    # the requested bound is honored EXACTLY (capture holds prompt
    # tokens in memory — a 4-deep ring means 4, not a silent 16 floor)
    from gofr_tpu.ml.capture import TrafficCapture

    tiny = TrafficCapture(capacity=4)
    for i in range(9):
        tiny.admit(f"t{i}", model="m", tokens=[i], max_new=1,
                   priority=1, deadline_s=0.0, mode="chunks")
    assert tiny.stats()["capacity"] == 4
    assert tiny.stats()["retained"] == 4 and tiny.stats()["dropped"] == 5


def test_rearming_with_new_ring_size_starts_fresh(monkeypatch):
    """Re-pinning GOFR_ML_CAPTURE with a DIFFERENT size (between
    in-process app boots) must honor the new bound and must NOT leak
    the previous window's records into the next bundle."""
    cap = _arm(monkeypatch, ring=24)
    assert cap.stats()["capacity"] == 24
    cap.admit("old1", model="m", tokens=[1], max_new=1, priority=1,
              deadline_s=0.0, mode="chunks").finish("stop")
    monkeypatch.setenv("GOFR_ML_CAPTURE", "48")
    fresh = traffic_capture()
    assert fresh is not cap and fresh.stats()["capacity"] == 48
    assert fresh.export()["requests"] == []
    # same size re-reads keep the same store
    assert traffic_capture() is fresh


# ------------------------------------------------------ zero-overhead contract
def test_capture_unset_constructs_nothing(model, run, monkeypatch):
    """GOFR_ML_CAPTURE unset: no capture machinery anywhere (the
    instrumented sites see None) and greedy output is byte-identical."""
    exp = _gen(model).generate([3, 1, 4], 6)
    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    server = LLMServer(_gen(model), name="cap-off")

    async def scenario():
        assert server._capture is None and server._cap_sampler is None
        out = await server.generate([3, 1, 4], 6)
        assert out == exp

    try:
        run(scenario())
    finally:
        server.close()


# --------------------------------------------------- round-trip fidelity
def test_mixed_pool_window_replays_bit_identical(model, run, monkeypatch):
    """The acceptance contract: a greedy mixed-load window (priorities +
    deadlines + a 2-replica pool fleet) captured then replayed on the
    same config yields a 100% output-digest identity rate and a
    balanced goodput-ledger delta."""
    cap = _arm(monkeypatch)
    prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 9, 2, 6, 5],
               [3, 5, 8, 9], [7, 9, 3], [2, 3, 8, 4, 6]]
    prios = ["high", "normal", "low", "normal", "high", "low"]

    def build():
        return ReplicaPool([_gen(model), _gen(model)], name="cap-pool")

    pool = build()

    async def window(server):
        async def one(i):
            # every request carries a (generous) deadline so the TTL
            # plumbing is exercised without ever tripping
            return await server.generate(p_list[i], 6, priority=prios[i],
                                         deadline_s=30.0)
        p_list = prompts
        return await asyncio.gather(*(one(i) for i in range(len(prompts))))

    try:
        outs = run(window(pool))
    finally:
        pool.close()
    assert all(len(o) == 6 for o in outs)
    bundle = cap.export()
    assert len(bundle["requests"]) == len(prompts)
    assert bundle["fleet"]["cap-pool"]["replicas"] == 2
    # the fleet block names serving FRONTS only: pool cores ("cap-pool/0"
    # …) never own capture records and must not register as fronts
    assert all("/" not in name for name in bundle["fleet"])
    rows = {tuple(r["tokens"]): r for r in bundle["requests"]}
    for p, out in zip(prompts, outs, strict=True):
        row = rows[tuple(p)]
        assert row["finish_reason"] == "length"
        assert row["digest"] == token_digest(out)
        assert row["deadline_s"] == 30.0 and row["mode"] == "generate"
    # the bundle survives its own wire codec
    bundle = decode_bundle(encode_bundle(bundle))

    replica_pool = build()
    try:
        verdict = run(ReplayHarness(replica_pool, bundle,
                                    speed=8.0).run())
    finally:
        replica_pool.close()
    assert verdict["identity"]["compared"] == len(prompts)
    assert verdict["identity"]["rate"] == 1.0
    assert verdict["replay_failed"] == 0 and verdict["skipped"] == 0
    assert verdict["fingerprint_drift"] == []
    gp = verdict["goodput"]
    assert gp["balanced"] and gp["delivered"] == 6 * len(prompts)
    assert verdict["ttft"]["recorded"]["p50_ms"] is not None
    assert verdict["ttft"]["delta_p50_ms"] is not None


def test_window_replay_on_fused_path_is_identical(run, monkeypatch):
    """The ISSUE-17 replay gate: a captured production window replayed
    with GOFR_ML_DECODE_WINDOW armed reports digest identity 1.0 — the
    fused multi-step path reproduces the single-step path's outputs
    bit-for-bit. float32: the comparison crosses program shapes, where
    bf16 rounding can flip a near-tie argmax."""
    import jax.numpy as jnp

    cfg = llama.tiny_llama(use_flash=False, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cap = _arm(monkeypatch)

    def build(**kw):
        return LLMServer(
            Generator(params, cfg, batch_slots=2, max_seq=64,
                      prefill_buckets=(8, 16), page_size=8, **kw),
            name="cap-window")

    server = build(decode_window=0)

    async def window(srv):
        return await asyncio.gather(*(
            srv.generate(p, 6, deadline_s=30.0)
            for p in ([3, 1, 4, 1], [2, 7, 1], [5, 9, 2, 6, 5])))

    try:
        run(window(server))
    finally:
        server.close()
    bundle = cap.export()
    assert len(bundle["requests"]) == 3

    # the replica picks the window up from the ENV, like production
    monkeypatch.setenv("GOFR_ML_DECODE_WINDOW", "4")
    replica = build()
    try:
        assert replica.gen.decode_window == 4
        verdict = run(ReplayHarness(replica, bundle, speed=8.0).run())
        stats = replica.gen.window_stats()
    finally:
        replica.close()
    assert verdict["identity"]["compared"] == 3
    assert verdict["identity"]["rate"] == 1.0
    assert verdict["replay_failed"] == 0 and verdict["skipped"] == 0
    assert stats["windows"] >= 1, "the replay must have run fused windows"


def test_pipelined_replay_on_double_buffered_path_is_identical(
        run, monkeypatch):
    """The ISSUE-18 replay gate: a captured single-step window replayed
    with GOFR_ML_PIPELINE=1 + GOFR_ML_DECODE_WINDOW=4 — two dispatches
    in flight — keeps digest identity 1.0. Budgets are big enough that
    the planner actually double-buffers (a window's conservative grant
    must not exhaust max_new in one dispatch)."""
    import jax.numpy as jnp

    cfg = llama.tiny_llama(use_flash=False, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    cap = _arm(monkeypatch)

    def build(**kw):
        return LLMServer(
            Generator(params, cfg, batch_slots=2, max_seq=64,
                      prefill_buckets=(8, 16), page_size=8, **kw),
            name="cap-pipe")

    server = build(decode_window=0, pipeline=0)

    async def window(srv):
        return await asyncio.gather(*(
            srv.generate(p, 14, deadline_s=30.0)
            for p in ([3, 1, 4, 1], [2, 7, 1], [5, 9, 2, 6, 5])))

    try:
        run(window(server))
    finally:
        server.close()
    bundle = cap.export()
    assert len(bundle["requests"]) == 3

    # the replica arms BOTH knobs from the ENV, like production
    monkeypatch.setenv("GOFR_ML_DECODE_WINDOW", "4")
    monkeypatch.setenv("GOFR_ML_PIPELINE", "1")
    replica = build()
    try:
        assert replica.gen.decode_window == 4
        assert replica.gen.pipeline == 1
        verdict = run(ReplayHarness(replica, bundle, speed=8.0).run())
        stats = replica.gen.pipeline_stats()
    finally:
        replica.close()
    assert verdict["identity"]["compared"] == 3
    assert verdict["identity"]["rate"] == 1.0
    assert verdict["replay_failed"] == 0 and verdict["skipped"] == 0
    assert stats["windows_overlapped"] >= 1, \
        "the replay must have held two dispatches in flight"


def test_journey_carries_output_digest(model, run, monkeypatch):
    """The digest↔rid crosslink: the capture row and the journey share
    the rid, and the journey's request summary names the digest."""
    from gofr_tpu.ml.journey import journey_log

    cap = _arm(monkeypatch)
    server = LLMServer(_gen(model), name="cap-xlink")

    async def scenario():
        return await server.generate([3, 1, 4], 5)

    try:
        out = run(scenario())
    finally:
        server.close()
    row = cap.export()["requests"][-1]
    assert row["digest"] == token_digest(out)
    waterfall = journey_log().get(row["rid"]).snapshot()
    assert waterfall["request"]["output_digest"] == row["digest"]


# ------------------------------------------------------- replay under chaos
def test_chaos_window_replays_clean_with_failures_classified(
        model, run, monkeypatch):
    """Capture with GOFR_ML_FAULT armed, replay clean: the identity
    verdict is still computed (over the requests the capture delivered),
    and the recorded failures are CLASSIFIED — never replay crashes."""
    cap = _arm(monkeypatch)
    fired = {"n": 0}

    def hook(point):
        # deterministic chaos: poison exactly one decode dispatch
        if point == "step" and fired["n"] == 0:
            fired["n"] += 1
            raise RuntimeError("chaos step")

    server = LLMServer(_gen(model), name="cap-chaos", fault=hook,
                       max_restarts=3)

    async def window():
        async def one(p):
            try:
                return await server.generate(p, 6, deadline_s=30.0)
            except Exception:
                return None
        return await asyncio.gather(*(one(p) for p in
                                      ([3, 1, 4], [2, 7, 1, 8],
                                       [5, 9, 2], [6, 2, 6])))

    try:
        outs = run(window())
    finally:
        server.close()
    assert fired["n"] == 1
    ok = [o for o in outs if o is not None]
    assert ok, "some requests must survive the chaos window"
    bundle = cap.export()
    reasons = {r["finish_reason"] for r in bundle["requests"]}
    assert "crashed" in reasons, "the poisoned dispatch must be recorded"

    clean = LLMServer(_gen(model), name="cap-chaos")
    try:
        verdict = run(ReplayHarness(clean, bundle, speed=8.0).run())
    finally:
        clean.close()
    assert verdict["recorded_failed"] >= 1
    assert verdict["identity"]["compared"] == len(ok)
    assert verdict["identity"]["rate"] == 1.0
    assert verdict["replay_failed"] == 0


# ------------------------------------------------------------ crash forensics
def test_crash_bundle_embeds_capture_tail(model, run, monkeypatch,
                                          tmp_path):
    """Capture-on crash bundles carry the newest captured requests under
    state.capture, and a saved bundle body feeds load_bundle directly —
    the offline repro path."""
    from gofr_tpu.flight_recorder import crash_vault

    cap = _arm(monkeypatch)
    fired = {"n": 0}

    def hook(point):
        if point == "step" and fired["n"] == 0:
            fired["n"] += 1
            raise RuntimeError("boom")

    server = LLMServer(_gen(model), name="cap-crash", fault=hook,
                       max_restarts=3)

    async def scenario():
        try:
            await server.generate([3, 1, 4, 1, 5], 8)
        except Exception:
            pass

    try:
        run(scenario())
    finally:
        server.close()
    crashes = [c for c in crash_vault().list()
               if c["id"].startswith("cap-crash")]
    assert crashes
    bundle = crash_vault().get(crashes[-1]["id"])
    tail = bundle["state"]["capture"]
    assert tail["format"] == BUNDLE_FORMAT
    assert any(r["tokens"] == [3, 1, 4, 1, 5] for r in tail["requests"])
    # the saved /debug/crash/<id> body loads as a replayable bundle
    path = tmp_path / "crash.json"
    path.write_text(json.dumps({"data": bundle}))
    loaded = load_bundle(str(path))
    assert loaded["format"] == BUNDLE_FORMAT
    assert loaded["requests"] == tail["requests"]


def test_crash_bundle_has_no_capture_key_when_off(model, run, monkeypatch):
    from gofr_tpu.flight_recorder import crash_vault

    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    fired = {"n": 0}

    def hook(point):
        if point == "step" and fired["n"] == 0:
            fired["n"] += 1
            raise RuntimeError("boom")

    server = LLMServer(_gen(model), name="cap-nocap", fault=hook,
                       max_restarts=3)

    async def scenario():
        try:
            await server.generate([3, 1, 4], 6)
        except Exception:
            pass

    try:
        run(scenario())
    finally:
        server.close()
    crashes = [c for c in crash_vault().list()
               if c["id"].startswith("cap-nocap")]
    assert crashes
    assert "capture" not in crash_vault().get(crashes[-1]["id"])["state"]


# ------------------------------------------------------------- HTTP surface
def test_debug_capture_endpoint_and_runtime_block(model, run, monkeypatch):
    """GET /debug/capture downloads the binary bundle (?rid= narrows,
    unknown rids 404, unarmed answers enabled:false) and /debug/serving
    gains the top-level runtime fingerprint block."""
    cap = _arm(monkeypatch)

    async def scenario():
        app = App(config=MapConfig({"APP_NAME": "cap-app"}))
        ml = app._ensure_ml()
        server = LLMServer(_gen(model), name="cap-http")
        ml._llms["cap-http"] = server
        http_server = TestServer(app._build_http_app())
        client = TestClient(http_server)
        await client.start_server()
        try:
            out = await server.generate([3, 1, 4], 5)

            r = await client.get("/debug/capture")
            assert r.status == 200
            assert r.content_type == "application/octet-stream"
            bundle = decode_bundle(await r.read())
            assert bundle["runtime"]["backend"] == "cpu"
            row = bundle["requests"][-1]
            assert row["digest"] == token_digest(out)

            r = await client.get("/debug/capture",
                                 params={"rid": row["rid"]})
            one = decode_bundle(await r.read())
            assert [x["rid"] for x in one["requests"]] == [row["rid"]]

            r = await client.get("/debug/capture",
                                 params={"rid": "no-such-rid"})
            assert r.status == 404

            # the satellite: /debug/serving answers the SAME runtime
            # fingerprint dict the bundle header snapshots
            r = await client.get("/debug/serving")
            runtime = (await r.json())["data"]["runtime"]
            assert runtime["backend"] == bundle["runtime"]["backend"]
            assert runtime["devices"] == bundle["runtime"]["devices"]
            assert runtime["knobs"].get("GOFR_ML_CAPTURE") == "64"

            # unarmed: a clean JSON no, not an empty binary
            monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
            r = await client.get("/debug/capture")
            body = (await r.json())["data"]
            assert body["enabled"] is False
        finally:
            await client.close()
            server.close()

    run(scenario())
    assert cap.stats()["captured"] >= 1
