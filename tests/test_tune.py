"""Self-tuning serving: replay-driven config search + shadow canary
(tier-1).

The headline contracts under test: the ``Tuner`` prunes every arm whose
replay digest identity is not exactly 1.0 (a seeded identity-violating
arm dies at the gate, not in review), ranks survivors deterministically
and never recommends an arm slower than the default; the emitted tuned
profile round-trips through ``load_profile`` and drift-warns when the
runtime moved; ``GOFR_ML_PROFILE`` unset constructs nothing and the
boot stays byte-identical; a shadow canary mirrors a traffic sample
whose tokens bill to the ``canary`` waste reason (the ledger stays
balanced — mirrored answers never reach a client), promotes into the
fleet on a good verdict, rolls back on degraded SLO medians, and a
canary-core crash is a rollback signal that never touches client
traffic.
"""

import asyncio
import json
import time

import jax
import jax.numpy as jnp
import pytest

from gofr_tpu.app import App
from gofr_tpu.config import MapConfig
from gofr_tpu.flight_recorder import event_log
from gofr_tpu.ml.capture import runtime_fingerprint, traffic_capture
from gofr_tpu.ml.generate import Generator
from gofr_tpu.ml.goodput import goodput_ledger
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.ml.replica import ReplicaPool
from gofr_tpu.ml import tune as tune_mod
from gofr_tpu.ml.tune import (PROFILE_FORMAT, TUNABLE_KNOBS, Tuner,
                              default_grid, load_profile,
                              profile_boot_warnings, profile_from_env,
                              profile_overlay)
from gofr_tpu.models import llama

@pytest.fixture(scope="module")
def model():
    # float32: identity claims cross program shapes (fused windows,
    # pipelining), where bf16 rounding can flip a near-tie argmax
    cfg = llama.tiny_llama(use_flash=False, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def poisoned_model():
    # same config, different weights: the canonical identity violation
    cfg = llama.tiny_llama(use_flash=False, dtype=jnp.float32)
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _gen(model, **kw):
    cfg, params = model
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("page_size", 8)
    return Generator(params, cfg, **kw)


# ---------------------------------------------------------------- unit level
def test_profile_load_validation(tmp_path):
    path = tmp_path / "prof.json"
    with pytest.raises(ValueError, match="cannot read"):
        load_profile(str(tmp_path / "missing.json"))
    path.write_text("{nope")
    with pytest.raises(ValueError, match="not JSON"):
        load_profile(str(path))
    path.write_text(json.dumps({"format": "other/9", "knobs": {}}))
    with pytest.raises(ValueError, match="format"):
        load_profile(str(path))
    path.write_text(json.dumps({"format": PROFILE_FORMAT}))
    with pytest.raises(ValueError, match="knobs"):
        load_profile(str(path))
    # empty knobs is legal: "the stock config won" applies as a no-op
    path.write_text(json.dumps({"format": PROFILE_FORMAT, "knobs": {}}))
    assert load_profile(str(path))["knobs"] == {}
    path.write_text(json.dumps({"format": PROFILE_FORMAT,
                                "knobs": {"GOFR_ML_EVIL": "1"}}))
    # a tuned profile must never become a backdoor for arbitrary env
    with pytest.raises(ValueError, match="unknown knob"):
        load_profile(str(path))
    path.write_text(json.dumps({"format": PROFILE_FORMAT,
                                "knobs": {"GOFR_ML_PIPELINE": [1]}}))
    with pytest.raises(ValueError, match="non-scalar"):
        load_profile(str(path))
    path.write_text(json.dumps({
        "format": PROFILE_FORMAT,
        "knobs": {"GOFR_ML_DECODE_WINDOW": 4, "GOFR_ML_PIPELINE": "1"}}))
    prof = load_profile(str(path))
    # scalar values normalize to the strings the env overlay will set
    assert prof["knobs"] == {"GOFR_ML_DECODE_WINDOW": "4",
                             "GOFR_ML_PIPELINE": "1"}
    assert prof["path"] == str(path)


def test_profile_overlay_sets_and_restores_env(monkeypatch):
    monkeypatch.setenv("GOFR_ML_DECODE_WINDOW", "2")
    monkeypatch.delenv("GOFR_ML_PIPELINE", raising=False)
    import os
    with profile_overlay({"GOFR_ML_DECODE_WINDOW": "8",
                          "GOFR_ML_PIPELINE": "1"}):
        assert os.environ["GOFR_ML_DECODE_WINDOW"] == "8"
        assert os.environ["GOFR_ML_PIPELINE"] == "1"
    assert os.environ["GOFR_ML_DECODE_WINDOW"] == "2"
    assert "GOFR_ML_PIPELINE" not in os.environ
    # the restore survives an exception inside the overlay
    with pytest.raises(RuntimeError):
        with profile_overlay({"GOFR_ML_PIPELINE": "1"}):
            raise RuntimeError("boom")
    assert "GOFR_ML_PIPELINE" not in os.environ


def test_profile_boot_warnings_drift_and_kv_bits():
    prof = {"format": PROFILE_FORMAT, "runtime": runtime_fingerprint(),
            "knobs": {"GOFR_ML_DECODE_WINDOW": "4"}}
    assert profile_boot_warnings(prof) == []
    stale = json.loads(json.dumps(prof))
    stale["runtime"]["jax"] = "99.0"
    # the profile's own knobs differing from the live env is the profile
    # WORKING, never drift
    stale["runtime"]["knobs"]["GOFR_ML_DECODE_WINDOW"] = "4"
    lines = profile_boot_warnings(stale)
    assert any("jax" in line for line in lines)
    assert not any("GOFR_ML_DECODE_WINDOW" in line for line in lines)
    kv = {"format": PROFILE_FORMAT, "runtime": runtime_fingerprint(),
          "knobs": {"GOFR_ML_KV_BITS": "8"}}
    assert any("GOFR_ML_KV_BITS" in line for line in
               profile_boot_warnings(kv))


def test_default_grid_knobs_are_tunable():
    arms = default_grid()
    names = [a["name"] for a in arms]
    assert len(names) == len(set(names)) and "default" in names
    for arm in arms:
        assert set(arm["knobs"]) <= TUNABLE_KNOBS


# ------------------------------------------------- ranking (stubbed replay)
def _fake_verdict(steady, *, rate=1.0, compared=3, failed=0, good=1.0,
                  ttft_p99=50.0, tpot_p99=10.0):
    return {
        "identity": {"rate": rate, "compared": compared},
        "replay_failed": failed,
        "throughput": {"steady_tok_s": steady, "tok_s": steady * 0.9},
        "ttft": {"replayed": {"p99_ms": ttft_p99}},
        "tpot": {"replayed": {"p99_ms": tpot_p99}},
        "goodput": {"goodput": good},
    }


def _stub_harness(monkeypatch, verdicts: dict):
    class _Server:
        def __init__(self, arm):
            self.arm = arm

        def close(self):
            pass

    class _Harness:
        def __init__(self, server, bundle, speed=None, logger=None):
            self.server = server

        async def run(self):
            return verdicts[self.server.arm]

    monkeypatch.setattr(tune_mod, "ReplayHarness", _Harness)
    return lambda arm: _Server(arm["name"])


def test_tuner_scoreboard_ranking_is_deterministic(run, monkeypatch):
    verdicts = {
        "default": _fake_verdict(100.0),
        "turbo": _fake_verdict(150.0),
        "tie-b": _fake_verdict(120.0),
        "tie-a": _fake_verdict(120.0),
        "laggy": _fake_verdict(90.0),
        "poisoned": _fake_verdict(200.0, rate=0.5),
        "flaky": _fake_verdict(180.0, failed=1),
    }
    grid = [{"name": n, "knobs": {}} if n == "default"
            else {"name": n, "knobs": {"GOFR_ML_DECODE_WINDOW": "4"}}
            for n in verdicts]

    def build(arm):
        if arm["name"] == "broken":
            raise RuntimeError("no such config")
        return builder(arm)

    builder = _stub_harness(monkeypatch, verdicts)
    grid.append({"name": "broken", "knobs": {"GOFR_ML_PIPELINE": "1"}})
    boards = []
    for _ in range(2):
        tuner = Tuner({"requests": []}, build, grid,
                      ttft_slo_ms=200.0, tpot_slo_ms=50.0)
        result = run(tuner.run())
        boards.append(result["scoreboard"])
    # bit-identical scoreboards run to run: score desc, name tie-break,
    # pruned arms sorted by name at the bottom
    assert boards[0] == boards[1]
    order = [r["arm"] for r in boards[0]]
    assert order == ["turbo", "tie-a", "tie-b", "default", "laggy",
                     "broken", "flaky", "poisoned"]
    rows = {r["arm"]: r for r in boards[0]}
    assert rows["poisoned"]["pruned_reason"] == "identity"
    assert rows["flaky"]["pruned_reason"] == "replay_failed"
    assert rows["broken"]["pruned_reason"] == "error"
    assert "RuntimeError" in rows["broken"]["error"]
    assert result["winner"]["arm"] == "turbo"
    assert result["speedup_vs_default"] == 1.5


def test_tuner_never_recommends_slower_than_default(run, monkeypatch):
    # "eco" out-SCORES the default (the default's TTFT p99 blows the
    # SLO) but its raw steady tok/s is lower — the winner must fall
    # back: a tuned profile that regresses the boot is worse than none
    verdicts = {
        "default": _fake_verdict(100.0, ttft_p99=400.0),
        "eco": _fake_verdict(80.0),
    }
    build = _stub_harness(monkeypatch, verdicts)
    tuner = Tuner({"requests": []}, build,
                  [{"name": "default", "knobs": {}},
                   {"name": "eco",
                    "knobs": {"GOFR_ML_TOKEN_BUDGET": "auto"}}],
                  ttft_slo_ms=200.0, tpot_slo_ms=50.0)
    result = run(tuner.run())
    assert result["scoreboard"][0]["arm"] == "eco"
    assert result["winner"]["arm"] == "default"
    assert result["speedup_vs_default"] == 1.0


# ------------------------------------------------ real search, real replay
def test_tuner_prunes_poisoned_arm_and_emits_profile(
        model, poisoned_model, run, monkeypatch, tmp_path):
    """The selftest contract on a 3-arm grid: capture a window, search
    {default, window4, poisoned}; the poisoned arm (same config,
    different weights) dies at the identity gate, the winner is
    identity-1.0 and not slower than default, and the emitted profile
    round-trips through load_profile."""
    monkeypatch.setenv("GOFR_ML_CAPTURE", "64")
    cap = traffic_capture()
    cap.clear()
    server = LLMServer(_gen(model), name="tune-cap")

    async def window():
        await asyncio.gather(*(
            server.generate(p, 6, deadline_s=30.0)
            for p in ([3, 1, 4, 1], [2, 7, 1], [5, 9, 2, 6, 5])))

    try:
        run(window())
    finally:
        server.close()
    bundle = cap.export()
    assert len(bundle["requests"]) == 3

    def build(arm):
        src = poisoned_model if arm["name"] == "poisoned" else model
        return LLMServer(_gen(src), name="tune-arm")

    grid = [{"name": "default", "knobs": {}},
            {"name": "window4",
             "knobs": {"GOFR_ML_DECODE_WINDOW": "4"}},
            {"name": "poisoned", "knobs": {}}]
    with pytest.raises(ValueError, match="duplicate arm"):
        Tuner(bundle, build, grid + [{"name": "default", "knobs": {}}])
    tuner = Tuner(bundle, build, grid, speed=1000.0)
    result = run(tuner.run())
    rows = {r["arm"]: r for r in result["scoreboard"]}
    assert rows["poisoned"]["pruned"] is True
    assert rows["poisoned"]["pruned_reason"] == "identity"
    assert rows["poisoned"]["identity"] < 1.0
    winner, default = result["winner"], result["default"]
    assert winner["identity"] == 1.0 and not winner["pruned"]
    assert winner["steady_tok_s"] >= default["steady_tok_s"]
    assert result["speedup_vs_default"] >= 1.0

    profile = tuner.profile(result)
    path = tmp_path / "tuned.json"
    path.write_text(json.dumps(profile))
    loaded = load_profile(str(path))
    assert loaded["knobs"] == winner["knobs"]
    assert loaded["bundle"]["requests"] == 3
    # same process, same runtime: applying the fresh profile warns not
    assert profile_boot_warnings(loaded) == []


# ----------------------------------------------------- boot-time application
def test_profile_unset_constructs_nothing(model, run, monkeypatch):
    """GOFR_ML_PROFILE unset: no profile machinery anywhere and greedy
    output is byte-identical to the plain boot."""
    monkeypatch.delenv("GOFR_ML_PROFILE", raising=False)
    monkeypatch.delenv("GOFR_ML_CANARY", raising=False)
    assert profile_from_env() is None
    exp = _gen(model).generate([3, 1, 4], 6)
    ml = App(config=MapConfig({"APP_NAME": "tune-app"}))._ensure_ml()
    server = ml.register_llm("tune-plain", None, None,
                             generator=_gen(model))
    try:
        assert isinstance(server, LLMServer)
        assert not hasattr(server, "tuned_profile")

        async def scenario():
            return await server.generate([3, 1, 4], 6)

        assert run(scenario()) == exp
    finally:
        server.close()


def test_register_llm_applies_profile_and_restores_env(
        model, run, monkeypatch, tmp_path):
    import os

    cfg, params = model
    monkeypatch.delenv("GOFR_ML_DECODE_WINDOW", raising=False)
    monkeypatch.delenv("GOFR_ML_PROFILE", raising=False)
    stale_runtime = runtime_fingerprint()
    stale_runtime["jax"] = "0.0.1"
    profile = {"format": PROFILE_FORMAT, "created_at": "2026-01-01T00:00:00Z",
               "runtime": stale_runtime,
               "knobs": {"GOFR_ML_DECODE_WINDOW": "4"}}
    path = tmp_path / "tuned.json"
    path.write_text(json.dumps(profile))
    monkeypatch.setenv("GOFR_ML_PROFILE", str(path))
    ml = App(config=MapConfig({"APP_NAME": "tune-app2"}))._ensure_ml()
    server = ml.register_llm("tune-boot", params, cfg, warmup=False,
                             batch_slots=2, max_seq=64,
                             prefill_buckets=(8, 16), page_size=8)
    try:
        # the knob steered construction, then the overlay came off
        assert server.gen.decode_window == 4
        assert "GOFR_ML_DECODE_WINDOW" not in os.environ
        assert server.tuned_profile["path"] == str(path)
        assert server.tuned_profile["knobs"] == {
            "GOFR_ML_DECODE_WINDOW": "4"}
        # the stale fingerprint surfaced as a recorded drift warning
        assert any("jax" in w for w in server.tuned_profile["warnings"])
    finally:
        server.close()
    with pytest.raises(ValueError, match="non-tunable"):
        ml.register_llm("tune-bad", params, cfg, warmup=False,
                        profile={"knobs": {"GOFR_ML_EVIL": "1"}})


# ------------------------------------------------------------ shadow canary
def _canary_pool(model, spawn_model=None, *, knobs=None, **kw):
    src = spawn_model
    return ReplicaPool(
        [_gen(model)], name=kw.pop("name"),
        spawn=lambda idx: _gen(src if src is not None else model),
        canary={"knobs": knobs or {"GOFR_ML_DECODE_WINDOW": "4"}}, **kw)


async def _drive(pool, prompts, n=6):
    outs = []
    for p in prompts:  # sequential: each mirror settles before the next
        outs.append(await pool.generate(p, n, deadline_s=30.0))
    return outs


async def _await_decided(pool, timeout=30.0):
    t0 = time.monotonic()
    while pool._canary is not None:
        assert time.monotonic() - t0 < timeout, "canary never decided"
        await asyncio.sleep(0.05)


def _wait(cond, timeout=15.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, "condition never held"
        time.sleep(0.05)


def test_canary_mirror_bills_canary_waste_then_promotes(
        model, run, monkeypatch):
    """The full happy path: every admitted request is mirrored
    (sample 1/1), mirrored tokens bill to the ``canary`` waste reason
    (clients get exactly the primary's bytes), and a full window of
    identity-true in-SLO pairs promotes the candidate into the fleet
    with a canary_promote event and a scale_up marked canary=True."""
    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    monkeypatch.setenv("GOFR_ML_CANARY_SAMPLE", "1")
    # window == request count: the verdict lands exactly when the LAST
    # mirror's pair completes, so no canary work is in flight when the
    # billing flips to delivered — the waste count is deterministic
    monkeypatch.setenv("GOFR_ML_CANARY_WINDOW", "3")
    prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 9, 2, 6, 5]]
    exp = [_gen(model).generate(p, 6) for p in prompts]
    since = event_log().cursor
    pool = _canary_pool(model, name="cn-pool")
    # the candidate pays its own JIT compiles on its first mirror — on a
    # CPU test box that dwarfs the primary's warm latency, so pin the
    # slack wide open; the SLO verdict has its own test below
    pool._canary.slo_slack = float("inf")
    led = goodput_ledger()
    base = led.snapshot_model("cn-pool")

    async def scenario():
        outs = await _drive(pool, prompts)
        await _await_decided(pool)
        return outs

    try:
        outs = run(scenario())
        assert outs == exp, "canary output must never reach a client"
        _wait(lambda: pool.fleet_size() == 2)
        snap = pool.routing_snapshot()["canary"]
        assert snap["state"] == "promoted" and snap["replica"] == 1
        assert snap["knobs"] == {"GOFR_ML_DECODE_WINDOW": "4"}
        assert snap["mirrored"] == 3
        # the ledger stayed balanced: every client token is delivered,
        # every completed mirror's tokens are ``canary`` waste
        after = led.snapshot_model("cn-pool")
        delivered = after["delivered"] - base["delivered"]
        wasted = (after["wasted"].get("canary", 0)
                  - base["wasted"].get("canary", 0))
        assert delivered == sum(len(o) for o in outs)
        assert wasted == 3 * 6
        evs = event_log().query(since, model="cn-pool",
                                kind="canary_promote")["events"]
        assert len(evs) == 1 and evs[0]["replica"] == 1
        scale = event_log().query(since, model="cn-pool",
                                  kind="scale_up")["events"]
        assert scale and scale[-1]["canary"] is True
        # the promoted core now serves clients: its answers bill
        # delivered, and the fleet keeps identity

        async def after_promo():
            return await pool.generate(prompts[0], 6, deadline_s=30.0)

        assert run(after_promo()) == exp[0]
    finally:
        pool.close()


def test_canary_rolls_back_on_degraded_slo(model, run, monkeypatch):
    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    monkeypatch.setenv("GOFR_ML_CANARY_SAMPLE", "1")
    monkeypatch.setenv("GOFR_ML_CANARY_WINDOW", "2")
    prompts = [[3, 1, 4, 1], [2, 7, 1], [5, 9, 2, 6, 5]]
    exp = [_gen(model).generate(p, 6) for p in prompts]
    since = event_log().cursor
    pool = _canary_pool(model, name="cn-slo")
    # any positive candidate latency now breaches the verdict: the
    # window fills identity-true but the SLO medians disqualify
    pool._canary.slo_slack = 0.0

    async def scenario():
        outs = await _drive(pool, prompts)
        await _await_decided(pool)
        return outs

    try:
        outs = run(scenario())
        assert outs == exp
        _wait(lambda: pool._canary_last is not None)
        assert pool.fleet_size() == 1, "a rolled-back canary never joins"
        snap = pool.routing_snapshot()["canary"]
        assert snap["state"] == "rolled_back"
        assert snap["reason"].startswith("slo:")
        evs = event_log().query(since, model="cn-slo",
                                kind="canary_rollback")["events"]
        assert len(evs) == 1 and evs[0]["reason"].startswith("slo:")
        assert not event_log().query(since, model="cn-slo",
                                     kind="canary_promote")["events"]
    finally:
        pool.close()


def test_canary_identity_mismatch_rolls_back(
        model, poisoned_model, run, monkeypatch):
    """The candidate computes different tokens (poisoned weights): ONE
    digest mismatch disqualifies it immediately — clients keep getting
    the primary's answers throughout."""
    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    monkeypatch.setenv("GOFR_ML_CANARY_SAMPLE", "1")
    monkeypatch.setenv("GOFR_ML_CANARY_WINDOW", "8")
    prompts = [[3, 1, 4, 1], [2, 7, 1]]
    exp = [_gen(model).generate(p, 6) for p in prompts]
    pool = _canary_pool(model, poisoned_model, name="cn-poison")

    async def scenario():
        outs = await _drive(pool, prompts)
        await _await_decided(pool)
        return outs

    try:
        outs = run(scenario())
        assert outs == exp
        _wait(lambda: pool._canary_last is not None)
        assert pool.fleet_size() == 1
        snap = pool.routing_snapshot()["canary"]
        assert snap["state"] == "rolled_back"
        assert snap["reason"] == "identity"
    finally:
        pool.close()


def test_canary_crash_never_touches_client_traffic(model, run, monkeypatch):
    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    monkeypatch.setenv("GOFR_ML_CANARY_SAMPLE", "1")
    monkeypatch.setenv("GOFR_ML_CANARY_WINDOW", "4")
    prompts = [[3, 1, 4, 1], [2, 7, 1]]
    exp = [_gen(model).generate(p, 6) for p in prompts]
    pool = _canary_pool(model, name="cn-crash")

    def boom(*args, **kwargs):
        raise RuntimeError("canary boom")

    # the candidate core dies on its very first mirrored request
    pool._canary.core.stream_chunks = boom

    async def scenario():
        outs = await _drive(pool, prompts)
        await _await_decided(pool)
        return outs

    try:
        outs = run(scenario())
        assert outs == exp, "a canary crash is invisible to clients"
        _wait(lambda: pool._canary_last is not None)
        assert pool.fleet_size() == 1
        snap = pool.routing_snapshot()["canary"]
        assert snap["state"] == "rolled_back"
        assert snap["reason"] == "canary_error:RuntimeError"
    finally:
        pool.close()


def test_canary_boot_validation(model, monkeypatch):
    monkeypatch.delenv("GOFR_ML_CAPTURE", raising=False)
    gen = _gen(model)
    # a canary without a spawn factory cannot build its candidate core
    with pytest.raises(ValueError, match="spawn"):
        ReplicaPool([gen], name="cn-bad",
                    canary={"knobs": {"GOFR_ML_PIPELINE": "1"}})
    with pytest.raises(ValueError, match="knobs"):
        ReplicaPool([gen], name="cn-bad2", spawn=lambda i: _gen(model),
                    canary={"knobs": {}})
    monkeypatch.setenv("GOFR_ML_CANARY_SAMPLE", "banana")
    with pytest.raises(ValueError, match="GOFR_ML_CANARY_SAMPLE"):
        ReplicaPool([gen], name="cn-bad3", spawn=lambda i: _gen(model),
                    canary={"knobs": {"GOFR_ML_PIPELINE": "1"}})
    monkeypatch.delenv("GOFR_ML_CANARY_SAMPLE", raising=False)
    pool = ReplicaPool([gen], name="cn-off")
    try:
        # canary unset constructs nothing: no block in the debug surface
        assert pool._canary is None
        assert pool.routing_snapshot()["canary"] is None
    finally:
        pool.close()
