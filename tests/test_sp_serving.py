"""Sequence-parallel SERVING (ml/sp_serving.py, ROADMAP item 2).

The seed's ring/Ulysses kernels become a serving capability: GOFR_ML_SP
arms a per-generator plan that prefills long prompts sequence-parallel
across the device mesh and — in paged mode — stripes the KV page pool
across the devices, with sp_paged_decode_step gathering cross-device.
The contracts under test:

- **Off means off**: GOFR_ML_SP unset constructs NO SP machinery; the
  single-device serving path is byte-identical to before.
- **Greedy token identity**: SP-on output == SP-off output at fp32 on
  the CPU mesh — dense and striped-paged, ring and Ulysses, int8 pages,
  the register_prefix (disagg ship) path, and both fault fallbacks.
- **Loud validation**: every nonsense knob combination rejects at
  construction with the knob's name, never mid-dispatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.ml.generate import Generator
from gofr_tpu.ml.sp_serving import SPConfig, resolve, sp_mode_from_env
from gofr_tpu.models import llama
from gofr_tpu.testutil.faults import FaultInjector


def _cfg(**kw):
    return llama.tiny_llama(use_flash=False, dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(1, 13, dtype=np.int32) % cfg.vocab_size
    return cfg, params, prompt


def _build(params, cfg, **kw):
    return Generator(params, cfg, batch_slots=2, max_seq=64,
                     prefill_buckets=(16,), chunk=4, **kw)


def _sp(mode="ring", min_tokens=8, shards=2):
    return SPConfig(mode, min_tokens=min_tokens, shards=shards)


@pytest.fixture(scope="module")
def dense_want(setup):
    """Plain single-device dense baseline, computed once."""
    cfg, params, prompt = setup
    return _build(params, cfg).generate(prompt, max_new_tokens=16)


@pytest.fixture(scope="module")
def paged_want(setup):
    """Plain single-device paged baseline, computed once."""
    cfg, params, prompt = setup
    return _build(params, cfg, page_size=8).generate(prompt,
                                                     max_new_tokens=16)


# ---------------------------------------------------------- knob validation

def test_env_mode_validation(monkeypatch):
    monkeypatch.setenv("GOFR_ML_SP", "rign")
    with pytest.raises(ValueError, match="GOFR_ML_SP"):
        sp_mode_from_env()
    for off in ("", "0", "off"):
        monkeypatch.setenv("GOFR_ML_SP", off)
        assert sp_mode_from_env() is None
    monkeypatch.setenv("GOFR_ML_SP", "ULYSSES")
    assert sp_mode_from_env() == "ulysses"


def test_env_knob_validation(monkeypatch):
    monkeypatch.setenv("GOFR_ML_SP_MIN_TOKENS", "zero")
    with pytest.raises(ValueError, match="GOFR_ML_SP_MIN_TOKENS"):
        SPConfig("ring")
    monkeypatch.setenv("GOFR_ML_SP_MIN_TOKENS", "0")
    with pytest.raises(ValueError, match="GOFR_ML_SP_MIN_TOKENS"):
        SPConfig("ring")
    monkeypatch.delenv("GOFR_ML_SP_MIN_TOKENS")
    monkeypatch.setenv("GOFR_ML_SP_SHARDS", "1")
    with pytest.raises(ValueError, match="shards"):
        SPConfig("ring")
    monkeypatch.delenv("GOFR_ML_SP_SHARDS")


def test_resolve_rejects_nonsense(setup):
    cfg, params, _ = setup
    common = dict(cfg=cfg, mesh=None, prefill_buckets=(16,), max_seq=64,
                  page_size=0, spec_k=0, shard_cache=False)
    # more shards than devices
    with pytest.raises(ValueError, match="GOFR_ML_SP_SHARDS"):
        resolve(SPConfig("ring", 8, 16), **common)
    # ulysses head divisibility (tiny_llama has 8 heads)
    with pytest.raises(ValueError, match="head count"):
        resolve(SPConfig("ulysses", 8, 3), **{**common, "max_seq": 66,
                                              "prefill_buckets": (15,)})
    # bucket divisibility for SP-eligible buckets
    with pytest.raises(ValueError, match="multiple of the sp shard"):
        resolve(SPConfig("ring", 8, 3), **{**common,
                                           "prefill_buckets": (16,)})
    # min_tokens past every bucket: the SP path would be unreachable
    with pytest.raises(ValueError, match="GOFR_ML_SP_MIN_TOKENS"):
        resolve(SPConfig("ring", 1024, 2), **common)
    # dense cache needs max_seq to shard evenly
    with pytest.raises(ValueError, match="max_seq"):
        resolve(SPConfig("ring", 8, 2), **{**common, "max_seq": 63,
                                           "prefill_buckets": (16,)})
    # speculation conflict
    with pytest.raises(ValueError, match="GOFR_ML_SPEC_K"):
        resolve(SPConfig("ring", 8, 2), **{**common, "spec_k": 3})


def test_generator_rejects_spec_plus_sp(setup):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="GOFR_ML_SPEC_K"):
        _build(params, cfg, sp=_sp(), spec_k=2)


# ------------------------------------------------- off = byte-identical off

def test_unset_env_builds_no_sp_machinery(setup):
    cfg, params, _ = setup
    gen = _build(params, cfg)
    assert gen._sp is None
    assert gen.sp_stats() is None
    assert not hasattr(gen, "_sp_prefill_into")
    # the dense layout's own one-row ladder, not the buckets given
    assert gen.prefill_buckets == (64,)
    assert gen._prefill_into.__name__ == "prefill_into"
    # sp=False wins over an armed env (explicit opt-out)
    import os
    os.environ["GOFR_ML_SP"] = "ring"
    try:
        gen2 = _build(params, cfg, sp=False)
        assert gen2._sp is None
    finally:
        del os.environ["GOFR_ML_SP"]


# ------------------------------------------------------ greedy token identity

def test_dense_sp_token_identity_and_dual_path(setup, dense_want):
    cfg, params, prompt = setup

    for mode in ("ring", "ulysses"):
        gen = _build(params, cfg, sp=_sp(mode))
        got = gen.generate(prompt, max_new_tokens=16)
        assert got == dense_want
        assert gen.sp_prefills == 1 and gen.sp_fallbacks == 0
        # the dense cache rides the sp mesh, sequence axis sharded
        assert tuple(gen.cache["k"].sharding.spec)[2] == "sp"

    # under the threshold: the single-device program, no SP counters
    short = _build(params, cfg, sp=_sp(min_tokens=13))
    assert short.generate(prompt, max_new_tokens=16) == dense_want
    assert short.sp_prefills == 0


def test_striped_pages_token_identity(setup, paged_want):
    cfg, params, prompt = setup
    gen = _build(params, cfg, page_size=8, sp=_sp())
    got = gen.generate(prompt, max_new_tokens=16)
    assert got == paged_want
    assert gen.sp_prefills == 1
    # the POOL is striped: page axis sharded over sp, page count rounded
    # up to a multiple of the shard count
    assert tuple(gen.cache["k"].sharding.spec)[1] == "sp"
    assert gen.n_pages % 2 == 0
    stats = gen.sp_stats()
    assert stats["striped_pages"] and stats["mode"] == "ring"


def test_striped_allocator_round_robins_devices(setup):
    cfg, params, prompt = setup
    gen = _build(params, cfg, page_size=8, sp=_sp())
    slot = gen.add_request(prompt, max_new_tokens=4)
    pages = gen._slot_pages[slot]
    assert len(pages) >= 2
    p_loc = gen.n_pages // 2
    owners = {pg // p_loc for pg in pages}
    assert owners == {0, 1}  # consecutive virtual pages on both shards


def test_striped_int8_pages_token_identity(setup):
    _, params, prompt = setup
    cfg8 = _cfg(kv_quant=True)
    want = _build(params, cfg8, page_size=8).generate(prompt,
                                                      max_new_tokens=16)
    gen = _build(params, cfg8, page_size=8, sp=_sp())
    got = gen.generate(prompt, max_new_tokens=16)
    assert got == want
    # quantized planes stripe too (page axis = 1 on the 4-dim layout)
    assert tuple(gen.cache["k_scale"].sharding.spec)[1] == "sp"


@pytest.mark.slow
def test_striped_int4_pages_token_identity(setup):
    _, params, prompt = setup
    cfg4 = _cfg(kv_bits=4)
    want = _build(params, cfg4, page_size=8).generate(prompt,
                                                      max_new_tokens=16)
    gen = _build(params, cfg4, page_size=8, sp=_sp("ulysses"))
    assert gen.generate(prompt, max_new_tokens=16) == want


# ------------------------------------------------------------ fault fallback

@pytest.mark.parametrize("point", ["sp_prefill", "sp_gather"])
def test_sp_fault_falls_back_bit_identically(setup, paged_want, point):
    cfg, params, prompt = setup
    gen = _build(params, cfg, page_size=8, sp=_sp())
    gen.fault = FaultInjector.parse(f"{point}:1")
    got = gen.generate(prompt, max_new_tokens=16)
    assert got == paged_want
    assert gen.sp_fallbacks == 1 and gen.sp_prefills == 0
    # the fallback admitted on the plain path: no sp journey stamp
    assert all(s.sp_shards == 0 for s in gen.slots)


# ------------------------------------- register_prefix (the disagg ship leg)

def test_register_prefix_sp_build_matches_plain(setup):
    cfg, params, prompt = setup
    prefix = np.arange(1, 17, dtype=np.int32) % cfg.vocab_size  # 2 pages
    suffix = np.array([3, 1, 4], np.int32)

    def run(gen):
        pid = gen.register_prefix(prefix)
        slot = gen.add_request(suffix, max_new_tokens=10, prefix=pid)
        while gen.slots[slot].live:
            gen.step()
        gen.drain()
        return gen.slots[slot].tokens[:10]

    want = run(_build(params, cfg, page_size=8))
    gen = _build(params, cfg, page_size=8, sp=_sp())
    got = run(gen)
    assert got == want
    assert gen.sp_prefills == 1  # the prefix built sequence-parallel


# ----------------------------------------------- scheduler / journey / debug

def test_scheduler_charged_at_tokens_over_shards(setup):
    cfg, params, prompt = setup
    gen = _build(params, cfg, page_size=8, sp=_sp(), token_budget=64)
    gen.add_request(prompt, max_new_tokens=4)
    sched = gen.scheduler
    assert sched.sp_charges == 1
    # 12 tokens over 2 shards -> ceil = 6 of restore-ledger debt
    assert sched.restore_debt == 6
    assert sched.snapshot()["sp_charges"] == 1


def test_slot_carries_shard_count_and_sp_stats(setup):
    cfg, params, prompt = setup
    gen = _build(params, cfg, sp=_sp())
    slot = gen.add_request(prompt, max_new_tokens=4)
    assert gen.slots[slot].sp_shards == 2
    stats = gen.sp_stats()
    assert stats == {"mode": "ring", "shards": 2, "min_tokens": 8,
                     "striped_pages": False, "prefills": 1,
                     "fallbacks": 0, "tokens": 12}


def test_sp_warmup_compiles_eligible_buckets(setup, paged_want):
    cfg, params, prompt = setup
    gen = _build(params, cfg, page_size=8, sp=_sp())
    gen.warmup()
    assert "sp_prefill/b16" in gen.programs
    # warmup leaves the generator serving-identical
    assert gen.generate(prompt, max_new_tokens=16) == paged_want


# ----------------------------------------------------- per-shard wire frames

def test_kv_transport_shard_frames_round_trip():
    from gofr_tpu.ml.kv_transport import (decode_entry, encode_entry_shards)

    rng = np.random.default_rng(0)
    key = tuple(range(12))
    arrays = {"k": rng.normal(size=(2, 5, 8, 4)).astype(np.float32),
              "v": rng.normal(size=(2, 5, 8, 4)).astype(np.float32)}
    meta = {"len": 40, "tail": [], "ids_full": list(key), "pinned": False}
    frames = encode_entry_shards(key, arrays, meta, 2)
    assert len(frames) == 2
    # each frame is a page-contiguous slice stamped with [idx, n]
    k0, a0, m0 = decode_entry(frames[0])
    k1, a1, m1 = decode_entry(frames[1])
    assert k0 == key and m0["_sp_shard"] == [0, 2]
    assert m1["_sp_shard"] == [1, 2]
    rejoined = np.concatenate([a0["k"], a1["k"]], axis=1)
    np.testing.assert_array_equal(rejoined, arrays["k"])
    # degenerate cases collapse to one plain frame
    assert len(encode_entry_shards(key, arrays, meta, 1)) == 1
    assert len(encode_entry_shards(key, arrays, meta, 9)) == 1


def test_kv_transport_land_bytes_reassembles_shards():
    from gofr_tpu.ml.kv_transport import KVTransport, encode_entry_shards

    rng = np.random.default_rng(1)
    key = tuple(range(8))
    arrays = {"k": rng.normal(size=(2, 4, 8, 4)).astype(np.float32)}
    meta = {"len": 32, "tail": [], "ids_full": list(key), "pinned": False}
    frames = encode_entry_shards(key, arrays, meta, 2)

    landed = {}

    class Dst:
        def import_prefix_kv(self, key, arrays, meta, timeout_s):
            landed["key"] = key
            landed["arrays"] = arrays
            landed["meta"] = meta
            return True

    t = KVTransport(name="llm")
    # first shard parks; nothing lands yet
    assert t.land_bytes(Dst(), frames[0]) is None
    assert t.snapshot()["sp_shards_pending"] == 1
    assert t.land_bytes(Dst(), frames[1]) == key
    assert t.snapshot()["sp_shards_pending"] == 0
    assert t.snapshot()["sp_shard_frames"] == 2
    np.testing.assert_array_equal(landed["arrays"]["k"], arrays["k"])
    assert "_sp_shard" not in landed["meta"]


# ------------------------------------- disagg composition (the ship path)

def test_disagg_sp_prefill_worker_bit_identity(setup, run):
    """PR 9 composition: a prefill-biased replica with an SP plan is a
    SEQUENCE-PARALLEL prefill worker — the prefix KV builds sharded
    across its mesh (register_prefix's SP path), ships through the
    transport, and the decode replica restores and decodes suffix-only.
    Greedy output stays bit-identical to a plain single-replica server."""
    import asyncio

    from gofr_tpu.ml.replica import ReplicaPool

    cfg, params, _ = setup
    prompt = [5, 9, 2, 7, 1, 4, 8, 3, 6]  # 2 whole pages @ page_size 4

    def gen(**kw):
        return Generator(params, cfg, batch_slots=1, max_seq=64,
                         prefill_buckets=(8, 16), page_size=4, chunk=2,
                         **kw)

    want = gen().generate(prompt, 6)
    prefill_worker = gen(sp=_sp(min_tokens=8, shards=2))
    pool = ReplicaPool([prefill_worker, gen()], name="sp-dg", disagg=True)

    async def scenario():
        out = await asyncio.wait_for(pool.generate(prompt, 6), 120)
        assert out == want
        snap = pool.routing_snapshot()["disagg"]
        assert snap["ships"] == 1 and snap["lands"] == 1
        assert snap["failures"] == 0
        # the prefix KV really built sequence-parallel on the worker
        assert prefill_worker.sp_prefills == 1
        # and the decode replica restored the shipped pages
        assert pool.replicas[1].gen.kv_restores == 1

    try:
        run(scenario())
    finally:
        pool.close()
