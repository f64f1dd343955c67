"""Disaggregated prefill/decode: the KV transport over the host tier
(tier-1, CPU).

The headline contract under test: with ``GOFR_ML_DISAGG=1`` on a
2-replica pool, a prompt is prefilled on the prefill-biased replica, its
whole-page KV prefix ships through the transport, and the decode replica
restores it at admission and decodes suffix-only — with greedy output
bit-identical to the single-replica path at kv16, int8, and int4. Every
transport failure (``ship``/``land`` faults, a dead prefill replica, an
over-budget entry) ends in valid output via full-prefill fallback — no
hangs, no cross-slot garbage — and with ``GOFR_ML_DISAGG`` unset the
pool never constructs a transport at all.
"""

import asyncio
import socket

import jax
import numpy as np
import pytest

from gofr_tpu.flight_recorder import event_log
from gofr_tpu.ml import MLDatasource
from gofr_tpu.ml.generate import Generator
from gofr_tpu.ml.kv_offload import HostKVStore, OffloadConfig
from gofr_tpu.ml.kv_transport import KVTransport, decode_entry, encode_entry
from gofr_tpu.ml.llm import LLMServer
from gofr_tpu.ml.multihost import recv_frame, send_bytes, send_frame
from gofr_tpu.ml.replica import ReplicaPool, disagg_from_env
from gofr_tpu.models import llama
from gofr_tpu.testutil.faults import FaultInjector


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_llama(use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _gen(model, **kw):
    cfg, params = model
    kw.setdefault("batch_slots", 1)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("page_size", 4)
    kw.setdefault("chunk", 2)
    return Generator(params, cfg, **kw)


def _expected(model, prompt, n, **kw):
    return _gen(model, **kw).generate(prompt, n)


def _expected_via_pages(model, prompt, n, **kw):
    """One replica serving ``prompt`` the way a decode replica does: the
    whole pages of the prompt built as a prefix, the rest prefilled as a
    suffix that attends those pages."""
    gen = _gen(model, **kw)
    whole = (len(prompt) - 1) // gen.page_size * gen.page_size
    pid = gen.register_prefix(prompt[:whole])
    out: list[int] = []
    gen.add_request(prompt[whole:], n, prefix=pid,
                    callback=lambda _slot, toks: out.extend(toks))
    while gen.n_live:
        gen.step()
    gen.drain()
    return out


def _fail_after(point: str, ok: int):
    left = {"n": ok}

    def hook(p):
        if p == point:
            if left["n"] > 0:
                left["n"] -= 1
            else:
                raise RuntimeError(f"injected at {p}")

    return hook


async def _wait_dead(core, timeout_s: float = 10.0) -> None:
    for _ in range(int(timeout_s / 0.01)):
        if core.health() == "dead":
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"replica never died (health={core.health()})")


# 9 tokens -> 2 whole pages @ page_size 4, non-empty suffix
PROMPT = [5, 9, 2, 7, 1, 4, 8, 3, 6]


# ------------------------------------------------------------- construction
def test_disagg_from_env(monkeypatch):
    monkeypatch.delenv("GOFR_ML_DISAGG", raising=False)
    assert disagg_from_env() is False
    monkeypatch.setenv("GOFR_ML_DISAGG", "0")
    assert disagg_from_env() is False
    monkeypatch.setenv("GOFR_ML_DISAGG", "1")
    assert disagg_from_env() is True
    monkeypatch.setenv("GOFR_ML_DISAGG", "yes")
    with pytest.raises(ValueError, match="GOFR_ML_DISAGG"):
        disagg_from_env()


def test_disagg_off_never_constructs_transport(model, run, monkeypatch):
    """The acceptance guard: GOFR_ML_DISAGG unset keeps the pool on the
    PR-6 code path — no KVTransport instance exists anywhere, and the
    routing snapshot says so."""
    monkeypatch.delenv("GOFR_ML_DISAGG", raising=False)
    pool = ReplicaPool([_gen(model), _gen(model)], name="chat")
    try:
        assert pool._transport is None and pool._roles is None
        assert pool.routing_snapshot()["disagg"] is None
        exp = _expected(model, PROMPT, 6)

        async def scenario():
            assert await pool.generate(PROMPT, 6) == exp

        run(scenario())
    finally:
        pool.close()


def test_disagg_construction_validation(model, monkeypatch):
    """Loud startup errors: disagg needs >= 2 replicas, paged
    generators, and register_llm refuses a single-replica disagg."""
    with pytest.raises(ValueError, match=">= 2 replicas"):
        ReplicaPool([_gen(model)], disagg=True)
    dense = [_gen(model, page_size=0), _gen(model, page_size=0)]
    with pytest.raises(ValueError, match="paged"):
        ReplicaPool(dense, disagg=True)
    for g in dense:
        pass  # dense generators hold no pool state to release
    ml = MLDatasource()
    with pytest.raises(ValueError, match="requires replicas >= 2"):
        ml.register_llm("chat", None, None, generator=_gen(model),
                        disagg=True)
    monkeypatch.setenv("GOFR_ML_DISAGG", "1")
    with pytest.raises(ValueError, match="requires replicas >= 2"):
        ml.register_llm("chat", None, None, generator=_gen(model))


def test_disagg_arms_host_tier_when_offload_off(model, monkeypatch):
    """The transport moves pages THROUGH the host tier: with
    GOFR_ML_KV_HOST_BUDGET_MB unset, disagg construction arms a default
    store on every replica instead of silently never shipping."""
    monkeypatch.delenv("GOFR_ML_KV_HOST_BUDGET_MB", raising=False)
    gens = [_gen(model), _gen(model)]
    assert all(g.host_kv is None for g in gens)
    pool = ReplicaPool(gens, name="chat", disagg=True)
    try:
        assert all(g.host_kv is not None for g in gens)
        # the owning core stamped the tier for event attribution
        assert {g.host_kv.model for g in gens} == {"chat/0", "chat/1"}
    finally:
        pool.close()


# ------------------------------------------------- the acceptance scenario
@pytest.mark.parametrize("precision", ["kv16", "int8", "int4"])
def test_disagg_bit_identity(precision, run):
    """THE acceptance bar: prefill on the prefill replica, ship, restore
    and decode on the decode replica — greedy output bit-identical to
    one replica that decodes from the same pages, at every KV precision:
    the transport changes nothing. Against a whole-prompt prefill it is
    identical at 16 and 8 bits. At 4 bits it is not, and not by a fault:
    a whole-prompt prefill attends its own K and V at full precision and
    quantises them afterwards, a suffix prefill attends the int4 pages,
    and int4 rounding (a seventh of the logits' range at these widths,
    tests/test_llama.py::test_cached_program_matches_forward) flips the
    toy model's argmax — the two paths are held to ``forward``'s logits
    there, not to each other's tokens."""
    kw = {"kv16": {}, "int8": {"kv_quant": True},
          "int4": {"kv_bits": 4}}[precision]
    cfg = llama.tiny_llama(use_flash=False, **kw)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    model = (cfg, params)
    exp = _expected_via_pages(model, PROMPT, 6)
    if precision != "int4":
        assert exp == _expected(model, PROMPT, 6)
    pool = ReplicaPool([_gen(model), _gen(model)], name=f"dg-{precision}",
                       disagg=True)

    async def scenario():
        out = await asyncio.wait_for(pool.generate(PROMPT, 6), 120)
        assert out == exp
        snap = pool.routing_snapshot()["disagg"]
        assert snap["ships"] == 1 and snap["lands"] == 1
        assert snap["failures"] == 0 and snap["bytes_moved"] > 0
        assert snap["roles"] == {"0": "prefill", "1": "decode"}
        # the decode replica RESTORED the shipped pages (no re-prefill of
        # the prefix) and the prefill replica took no decode work
        assert pool.replicas[1].gen.kv_restores == 1
        routed = pool.routing_snapshot()["routed"]
        assert routed["0"] == {"prefill": 1}
        assert routed["1"].get("affinity", 0) == 1

    try:
        run(scenario())
    finally:
        pool.close()


def test_short_prompt_skips_transport(model, run):
    """Prompts below one whole page + suffix have nothing to ship: they
    route straight to a decode replica, no transport traffic."""
    pool = ReplicaPool([_gen(model), _gen(model)], name="chat",
                       disagg=True)
    exp = _expected(model, [3, 1], 4)

    async def scenario():
        assert await pool.generate([3, 1], 4) == exp
        assert pool._transport.ships == 0
        assert pool.routing_snapshot()["routed"]["1"].get(
            "least_loaded", 0) >= 1

    try:
        run(scenario())
    finally:
        pool.close()


# ------------------------------------------------------- failure semantics
@pytest.mark.parametrize("point", ["ship", "land"])
def test_transport_fault_full_prefill_fallback(model, run, point):
    """An armed ship/land fault kills the handoff mid-flight: the
    request still completes bit-identically via a full prefill on the
    decode replica — the transport may lose pages, never requests."""
    exp = _expected(model, PROMPT, 6)
    pool = ReplicaPool([_gen(model), _gen(model)], name=f"f-{point}",
                       disagg=True, fault=FaultInjector.parse(f"{point}:1"))

    async def scenario():
        out = await asyncio.wait_for(pool.generate(PROMPT, 6), 120)
        assert out == exp
        t = pool._transport
        assert t.failures >= 1
        if point == "ship":
            assert t.ships == 0          # pages never left the source
        else:
            assert t.ships == 1 and t.lands == 0
        # nothing restored: the decode replica paid the full prefill
        assert all(c.gen.kv_restores == 0 for c in pool.replicas)

    try:
        run(scenario())
    finally:
        pool.close()


def test_dead_prefill_replica_full_prefill_fallback(model, run):
    """A dead prefill replica is not an outage: the prefill stage is
    skipped outright (no parking behind a corpse) and prompts
    full-prefill on the decode replica — valid, bit-identical output,
    fleet health degraded, no hangs."""
    exp = _expected(model, PROMPT, 6)
    pool = ReplicaPool([_gen(model), _gen(model)], name="chat",
                       disagg=True, max_restarts=0)

    async def scenario():
        pool.replicas[0].gen.fault = _fail_after("step", 0)
        with pytest.raises(Exception):
            await pool.replicas[0].generate([1, 2], 2)
        await _wait_dead(pool.replicas[0])
        out = await asyncio.wait_for(pool.generate(PROMPT, 6), 120)
        assert out == exp
        assert pool._transport.ships == 0   # stage skipped, not failed
        assert pool.health() == "degraded"

    try:
        run(scenario())
    finally:
        pool.close()


def test_mid_flight_prefill_crash_falls_back(model, run):
    """The prefill replica crashing UNDER the export (spill fault) loses
    the shipped pages mid-flight; the in-flight prompt still completes
    via full prefill on the survivor."""
    exp = _expected(model, PROMPT, 6)
    pool = ReplicaPool([_gen(model), _gen(model)], name="chat",
                       disagg=True)

    async def scenario():
        pool.replicas[0].gen.fault = _fail_after("spill", 0)
        out = await asyncio.wait_for(pool.generate(PROMPT, 6), 120)
        assert out == exp
        assert pool._transport.ships == 0
        assert pool._transport.failures >= 1
        # the aborted export's idle registration must not leak pool
        # pages forever: it stays reclaimable (refs == 0)
        gen0 = pool.replicas[0].gen
        assert all(i["refs"] == 0 for i in gen0._prefixes.values())

    try:
        run(scenario())
    finally:
        pool.close()


def test_oversize_entry_falls_back(model, run):
    """An entry larger than the decode replica's host budget cannot
    land: ship fails, the request full-prefills."""
    exp = _expected(model, PROMPT, 6)
    gens = [_gen(model, host_kv=HostKVStore(OffloadConfig(budget_mb=64))),
            _gen(model, host_kv=HostKVStore(
                OffloadConfig(budget_mb=1e-6)))]  # ~1 byte: nothing lands
    pool = ReplicaPool(gens, name="chat", disagg=True)

    async def scenario():
        out = await asyncio.wait_for(pool.generate(PROMPT, 6), 120)
        assert out == exp
        assert pool._transport.lands == 0
        assert pool._transport.failures >= 1

    try:
        run(scenario())
    finally:
        pool.close()


# --------------------------------------------------------- observability
def test_transport_metrics_and_events(model, run):
    counts = {}

    class _Metrics:
        def add_counter(self, name, delta, **labels):
            counts[name] = counts.get(name, 0) + delta

        def set_gauge(self, name, value, **labels):
            pass

        def record_histogram(self, name, value, **labels):
            pass

    cursor = event_log().cursor
    pool = ReplicaPool([_gen(model), _gen(model)], name="ev-chat",
                       disagg=True, metrics=_Metrics())

    async def scenario():
        await pool.generate(PROMPT, 6)
        assert counts.get("app_ml_kv_transport_ships_total") == 1
        assert counts.get("app_ml_kv_transport_lands_total") == 1
        assert counts.get("app_ml_kv_transport_bytes", 0) > 0
        kinds = [e["kind"] for e in event_log().query(
            since=cursor, model="ev-chat")["events"]]
        assert "kv_ship" in kinds and "kv_land" in kinds
        # ship rides the fleet log BEFORE land (the handoff's order)
        assert kinds.index("kv_ship") < kinds.index("kv_land")

    try:
        run(scenario())
    finally:
        pool.close()


def test_ship_land_stamped_in_dispatch_phases(model, run):
    """The flight recorder's per-dispatch ring carries the transport
    phases: the prefill core's records show ``ship`` time, the decode
    core's show ``land`` — and records still sum to their wall."""
    pool = ReplicaPool([_gen(model), _gen(model)], name="chat",
                       disagg=True)

    async def scenario():
        await pool.generate(PROMPT, 6)
        ship_snap = pool.replicas[0].recorder.snapshot()
        land_snap = pool.replicas[1].recorder.snapshot()
        assert ship_snap["totals_s"].get("ship", 0) > 0
        assert land_snap["totals_s"].get("land", 0) > 0

    try:
        run(scenario())
    finally:
        pool.close()


# ------------------------------------------------------ cross-host seam
def test_wire_codec_roundtrip_bit_exact():
    arrays = {
        "k": np.arange(24, dtype=np.int8).reshape(2, 3, 4),
        "v_scale": np.linspace(0, 1, 6, dtype=np.float32).reshape(2, 3),
    }
    meta = {"len": 8, "tail": [7], "ids_full": list(range(9)),
            "pinned": False}
    raw = encode_entry((1, 2, 3), arrays, meta)
    key, back, meta2 = decode_entry(raw)
    assert key == (1, 2, 3) and meta2 == meta
    for name, arr in arrays.items():
        assert back[name].dtype == arr.dtype
        assert np.array_equal(back[name], arr)


def test_cross_host_ship_over_binary_frame(model, run):
    """The cross-host seam end-to-end: export on one server, encode,
    ride a multihost binary frame over a real socket (interleaved with
    JSON frames), land on the other server — the landed pages restore
    and decode bit-identically."""
    exp = _expected(model, PROMPT, 6)
    src = LLMServer(_gen(model, host_kv=HostKVStore(
        OffloadConfig(budget_mb=64))), name="src")
    dst = LLMServer(_gen(model, host_kv=HostKVStore(
        OffloadConfig(budget_mb=64))), name="dst")
    t = KVTransport(name="xhost")
    a, b = socket.socketpair()
    try:
        raw = t.ship_bytes(src, PROMPT)
        assert raw is not None and t.ships == 1
        send_frame(a, {"op": "kv", "tokens": len(PROMPT)})
        send_bytes(a, raw)
        send_frame(a, {"op": "done"})
        assert recv_frame(b) == {"op": "kv", "tokens": len(PROMPT)}
        got = recv_frame(b)
        assert isinstance(got, bytes) and got == raw
        assert recv_frame(b) == {"op": "done"}
        assert t.land_bytes(dst, got) == tuple(PROMPT)
        assert t.lands == 1

        async def scenario():
            out = await dst.generate(PROMPT, 6)
            assert out == exp
            assert dst.gen.kv_restores == 1  # decoded from shipped pages

        run(scenario())
    finally:
        a.close()
        b.close()
        src.close()
        dst.close()


def test_cross_host_ship_single_trace_id(model, run):
    """THE trace-propagation acceptance: a cross-host ship carries its
    W3C traceparent INSIDE the binary entry header, so the sender's
    ``ml.kv_ship`` span and the receiver's ``ml.kv_land`` span (opened
    by a DIFFERENT tracer, as on a different host) share one trace id —
    with land parented under ship — and the landed meta never leaks the
    reserved header key into the host store."""
    from gofr_tpu.testutil import RecordingTracer

    src = LLMServer(_gen(model, host_kv=HostKVStore(
        OffloadConfig(budget_mb=64))), name="tr-src")
    dst = LLMServer(_gen(model, host_kv=HostKVStore(
        OffloadConfig(budget_mb=64))), name="tr-dst")
    sender_tr, receiver_tr = RecordingTracer(), RecordingTracer()
    sender = KVTransport(name="tr-a", tracer=sender_tr)
    receiver = KVTransport(name="tr-b", tracer=receiver_tr)
    a, b = socket.socketpair()
    try:
        cursor = event_log().cursor
        with sender_tr.start_span("request") as root:
            raw = sender.ship_bytes(src, PROMPT, rid="r-xhost")
        assert raw is not None
        send_bytes(a, raw)
        got = recv_frame(b)
        assert receiver.land_bytes(dst, got, rid="r-xhost") == tuple(PROMPT)
        ship = sender_tr.by_name("ml.kv_ship")[0]
        land = receiver_tr.by_name("ml.kv_land")[0]
        # ONE trace across the socket: the land span continues the
        # sender's trace and hangs under the ship span
        assert ship.trace_id == land.trace_id == root.trace_id
        assert land.parent_span_id == ship.span_id
        assert land.attributes["ml.rid"] == "r-xhost"
        # the fleet events carry rid + trace on both ends
        evs = {e["kind"]: e for e in event_log().query(
            since=cursor, kind=("kv_ship", "kv_land"))["events"]}
        assert evs["kv_ship"]["rid"] == evs["kv_land"]["rid"] == "r-xhost"
        assert evs["kv_ship"]["trace"] == root.trace_id
        assert evs["kv_land"]["trace"] == root.trace_id
        # the reserved traceparent key is wire-only — never store meta
        entry = dst.gen.host_kv._entries[tuple(PROMPT)]
        assert "_traceparent" not in entry.meta
    finally:
        a.close()
        b.close()
        src.close()
        dst.close()


def test_land_bytes_corrupt_frame_counts_failure(model):
    """A truncated/garbage binary frame never crashes the receiver: it
    counts as a transport failure and returns None (the full-prefill
    fallback contract, like every other lost handoff)."""
    dst = LLMServer(_gen(model, host_kv=HostKVStore(
        OffloadConfig(budget_mb=64))), name="dst-corrupt")
    t = KVTransport(name="xhost")
    try:
        good = encode_entry((1, 2), {"k": np.zeros((4,), np.int8)},
                            {"len": 0, "tail": [], "ids_full": [1, 2]})
        for bad in (b"", b"\x00\x00\x00\xffgarbage", good[:-3]):
            assert t.land_bytes(dst, bad) is None
        assert t.failures == 3 and t.lands == 0
    finally:
        dst.close()


# -------------------------------------- chunked-ladder prefix registration
def test_segmented_register_prefix_long_prefix(model):
    """register_prefix beyond the largest prefill bucket: with chunked
    prefill armed the prefix KV builds in bucket-sized segments, and
    prefixed decode matches the full-prompt path bit-for-bit."""
    long_pfx = list(np.random.RandomState(0).randint(1, 400, size=24))
    ref = _expected(model, long_pfx + [7, 7], 5, prefill_chunk=8,
                    n_pages=32)
    gen = _gen(model, prefill_chunk=8, n_pages=32)
    pid = gen.register_prefix(long_pfx)
    slot = gen.add_request([7, 7], 5, prefix=pid)
    while gen.slots[slot].live:
        gen.step()
    gen.drain()
    assert gen.slots[slot].tokens[:5] == ref
    gen.release(slot)
    # without chunked prefill the old loud error stands, naming the knob
    with pytest.raises(ValueError, match="prefill_chunk"):
        _gen(model).register_prefix(long_pfx)


# ------------------------------------------ shard-reassembly buffer bound
def test_pending_shard_sets_bounded_with_eviction():
    """The shard-reassembly buffer is BOUNDED: flooding incomplete
    partial sets (a sender that dies mid-ship, repeatedly) evicts the
    stalest set at the cap and counts it in ``sp_shards_dropped`` —
    memory stays bounded, nothing crashes, and a complete set arriving
    AFTER the flood still reassembles and lands."""
    from gofr_tpu.ml.kv_transport import encode_entry_shards

    landed = {}

    class Dst:
        def import_prefix_kv(self, key, arrays, meta, timeout_s):
            landed["key"] = key
            landed["arrays"] = arrays
            return True

    def shard0(key_base):
        arrays = {"k": np.full((2, 4, 8, 4), key_base, np.float32)}
        meta = {"len": 16, "tail": [], "ids_full": list(range(key_base,
                                                              key_base + 4))}
        return encode_entry_shards(tuple(range(key_base, key_base + 4)),
                                   arrays, meta, 2)

    cap = 3
    t = KVTransport(name="flood", pending_cap=cap)
    # flood: 10 distinct sets, each sending only shard 0 of 2 — none can
    # ever complete, so without the cap the dict would grow unbounded
    for i in range(10):
        assert t.land_bytes(Dst(), shard0(100 * (i + 1))[0]) is None
        assert len(t._pending_shards) <= cap
    snap = t.snapshot()
    assert snap["sp_shards_pending"] == cap
    assert snap["sp_shards_dropped"] == 10 - cap
    assert t.lands == 0 and not landed

    # a COMPLETE set arriving after the flood still lands whole: the cap
    # bounds memory, it does not wedge the transport
    frames = shard0(9000)
    assert t.land_bytes(Dst(), frames[0]) is None  # evicts one more stale set
    assert t.land_bytes(Dst(), frames[1]) == tuple(range(9000, 9004))
    assert landed["key"] == tuple(range(9000, 9004))
    snap = t.snapshot()
    assert snap["sp_shards_pending"] == cap - 1  # completed set removed
    assert snap["sp_shards_dropped"] == 10 - cap + 1

    # the cap is a loud constructor contract, not a silent clamp
    with pytest.raises(ValueError):
        KVTransport(name="bad", pending_cap=0)
