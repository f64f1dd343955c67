"""Dense admission: one prompt a prefill program, on a ladder of lengths
fixed by ``max_seq`` (CPU, toy widths).

The ladder itself, the smallest-entry rule, waves against one-at-a-time
admission under greedy sampling, the all-or-nothing unwind, no compile
after ``warmup()``, the warm-up inventory, and the two pad counters held
against the ``launch`` metadata of the dispatch records.
"""

import itertools

import jax
import numpy as np
import pytest

from gofr_tpu.flight_recorder import DispatchRecorder, dispatch_log
from gofr_tpu.ml.generate import Generator, _prefill_ladder
from gofr_tpu.models import llama

LADDERS = {
    512: (128, 256, 512),
    2048: (128, 256, 512, 768, 1024, 2048),
    4096: (128, 256, 512, 768, 1024, 2048, 4096),
}


@pytest.fixture(scope="module")
def model():
    cfg = llama.tiny_llama(use_flash=False)
    return cfg, llama.init_params(cfg, jax.random.PRNGKey(0))


def _gen(model, **kw):
    cfg, params = model
    kw.setdefault("batch_slots", 12)
    kw.setdefault("max_seq", 512)
    kw.setdefault("chunk", 2)
    return Generator(params, cfg, **kw)


@pytest.fixture(scope="module")
def gen(model):
    """One dense generator for the cases that only admit and decode: its
    three prefill programs compile once for the module."""
    return _gen(model)


def _prompts(n: int, seed: int = 0) -> list:
    """``n`` prompts of mixed lengths, every ladder length of 512 among
    the first three."""
    rng = np.random.default_rng(seed)
    lens = itertools.cycle((5, 130, 300, 17, 128, 257, 64, 200, 3, 129, 256))
    return [rng.integers(1, 500, n_tok).tolist()
            for n_tok in itertools.islice(lens, n)]


def _run_to_end(gen) -> None:
    while gen.n_live:
        gen.step()
    gen.drain()


# ------------------------------------------------------------------ the ladder
@pytest.mark.parametrize("max_seq", sorted(LADDERS))
def test_the_ladder_follows_from_max_seq(max_seq):
    assert _prefill_ladder(max_seq) == LADDERS[max_seq]


@pytest.mark.parametrize("cap,want", [
    (64, (64,)), (128, (128,)), (300, (128, 256, 300)),
    (1536, (128, 256, 512, 768, 1024, 1536)),
    (3000, (128, 256, 512, 768, 1024, 2048, 3000)),
])
def test_the_ladder_ends_at_a_cap_that_is_no_power_of_two(cap, want):
    assert _prefill_ladder(cap) == want


@pytest.mark.parametrize("max_seq", sorted(LADDERS))
def test_every_length_maps_to_the_smallest_entry_that_holds_it(max_seq):
    ladder = _prefill_ladder(max_seq)
    assert list(ladder) == sorted(set(ladder)) and ladder[-1] == max_seq
    # every entry keeps ops.flash_attention on its kernel branch
    assert all(n == 128 or n % 256 == 0 for n in ladder)
    picked = [next(s for s in ladder if n <= s)
              for n in range(1, max_seq + 1)]
    assert all(s >= n for n, s in enumerate(picked, 1))
    below = {s: max([0] + [e for e in ladder if e < s]) for s in ladder}
    assert all(below[s] < n for n, s in enumerate(picked, 1))
    assert set(picked) == set(ladder)


@pytest.mark.parametrize("kw,want", [
    (dict(max_seq=2048), LADDERS[2048]),
    (dict(max_seq=2048, prefill_buckets=(8, 16)), LADDERS[2048]),
    (dict(max_seq=2048, prefill_chunk=256), (128, 256)),
    (dict(max_seq=2048, prefill_chunk=512), (128, 256, 512)),
    (dict(max_seq=2048, prefill_chunk=64), (64,)),
    (dict(max_seq=64), (64,)),
], ids=["2048", "buckets-ignored", "chunk256", "chunk512", "chunk64", "64"])
def test_the_dense_generator_holds_the_ladder(model, kw, want):
    assert _gen(model, batch_slots=2, **kw).prefill_buckets == want


def test_paged_and_draft_keep_what_they_need(model):
    cfg, params = model
    paged = _gen(model, batch_slots=2, max_seq=2048, page_size=16,
                 prefill_buckets=(128, 512, 2048))
    assert paged.prefill_buckets == (128, 512, 2048)
    # a draft model ingests a segmented prompt's history whole
    draft = _gen(model, batch_slots=2, max_seq=512, prefill_chunk=128,
                 spec_k=2, draft_params=params, draft_cfg=cfg)
    assert draft.prefill_buckets == LADDERS[512]


@pytest.mark.parametrize("n,seq", [(1, 128), (128, 128), (129, 256),
                                   (256, 256), (257, 512), (511, 512)])
def test_admission_takes_the_smallest_program(gen, n, seq):
    rec = DispatchRecorder(model=f"ladder-pick-{n}")
    gen.recorder = rec
    try:
        gen.add_request(list(range(1, n + 1)), 2)
        gen.step()
        rec.commit()
        _run_to_end(gen)
    finally:
        gen.recorder = None
    launches = [p for r in dispatch_log().records()
                if r["model"] == rec.model for p in r.get("prefills", [])]
    assert launches == [{"kind": "prefill", "rows": 1, "seq": seq,
                         "real_tokens": n}]


# ---------------------------------------------------- waves against singles
@pytest.mark.parametrize("n_wave", [1, 2, 5, 8, 11])
def test_a_wave_serves_what_its_prompts_serve_alone(gen, n_wave):
    prompts = _prompts(n_wave, seed=n_wave)
    alone = []
    for p in prompts:  # admitted one at a time, each to its end
        alone.append(gen.generate(p, 6))
    got: dict = {}
    slots = gen.add_requests([
        (p, 6, lambda i, toks: got.setdefault(i, []).extend(toks))
        for p in prompts])
    # the caller's order: the k-th prompt sits in the k-th free slot
    assert slots == list(range(n_wave))
    assert [gen.slots[s].prompt_len for s in slots] == [len(p)
                                                        for p in prompts]
    _run_to_end(gen)
    assert [got[s] for s in slots] == alone


def test_a_program_that_raises_mid_wave_leaves_no_slot_live(gen):
    prompts = _prompts(5, seed=99)
    real, calls = gen._prefill_into, []

    def third_raises(*args):
        calls.append(args[1].shape)
        if len(calls) == 3:
            raise RuntimeError("boom")
        return real(*args)

    gen._prefill_into = third_raises
    try:
        with pytest.raises(RuntimeError, match="boom"):
            gen.add_requests([(p, 4, None) for p in prompts])
    finally:
        gen._prefill_into = real
    assert len(calls) == 3
    assert gen.n_live == 0 and not gen._pending_first
    # and the generator still serves: the same wave, whole
    got: dict = {}
    slots = gen.add_requests([
        (p, 4, lambda i, toks: got.setdefault(i, []).extend(toks))
        for p in prompts])
    _run_to_end(gen)
    assert [got[s] for s in slots] == [gen.generate(p, 4) for p in prompts]


# ------------------------------------------------------------------- warm-up
class _Compiles:
    """jax's own compile events, process-wide, as ``benchmark/harness.py``'s
    ``CompileCounter`` reads them."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests = 0
        mon.register_event_listener(self._event)

    def _event(self, name: str, **kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


@pytest.mark.parametrize("kw", [dict(), dict(prefill_chunk=128)],
                         ids=["dense", "dense-chunked"])
def test_after_warmup_no_admissible_prompt_compiles(model, compiles, kw):
    gen = _gen(model, batch_slots=2, **kw)
    gen.warmup()
    before = compiles.requests
    assert before > 0  # the counter counts: warm-up compiled
    for n in (1, 127, 128, 129, 256, 257, 300, 511):
        gen.add_request(list(range(1, n + 1)), 3)
        _run_to_end(gen)
    gen.add_requests([(list(range(1, n + 1)), 3, None) for n in (5, 200)])
    _run_to_end(gen)
    assert compiles.requests == before
    # the check of the check: a shape nobody warmed does count
    jax.jit(lambda x: x * 3 + 1)(np.zeros((7, 3), np.float32))
    assert compiles.requests == before + 1


@pytest.mark.parametrize("kw,decode", [
    (dict(chunk=4), {"decode/chunk4", "decode/chunk1"}),
    (dict(chunk=1), {"decode/chunk1"}),
], ids=["chunk4", "chunk1"])
def test_the_dense_warmup_inventory_is_the_ladder_and_decode(model, kw,
                                                             decode):
    gen = _gen(model, batch_slots=2, token_budget=0, **kw)
    gen.warmup()
    names = {r["name"] for r in gen.programs.snapshot()}
    assert names == decode | {f"prefill/1x{n}" for n in LADDERS[512]}


# ------------------------------------------------------------------ counters
def test_the_pad_counters_are_the_launch_records_sums(model):
    gen = _gen(model)
    rec = DispatchRecorder(model="ladder-counters")
    gen.recorder = rec
    assert (gen.prefill_tokens_real, gen.prefill_tokens_padded) == (0, 0)
    gen.warmup()  # warm-up sends no prompt: nothing counted
    assert (gen.prefill_tokens_real, gen.prefill_tokens_padded) == (0, 0)
    waves = [_prompts(5, seed=1), _prompts(1, seed=2), _prompts(11, seed=3)]
    for wave in waves:
        gen.add_requests([(p, 3, None) for p in wave])
        while gen.n_live:
            gen.step()
            rec.commit()
        gen.drain()
    launches = [p for r in dispatch_log().records()
                if r["model"] == "ladder-counters"
                for p in r.get("prefills", [])]
    assert len(launches) == 17
    assert all(p["kind"] == "prefill" and p["rows"] == 1 for p in launches)
    real = sum(len(p) for wave in waves for p in wave)
    assert gen.prefill_tokens_real == real == sum(
        p["real_tokens"] for p in launches)
    assert gen.prefill_tokens_padded == sum(
        p["rows"] * p["seq"] for p in launches)
    assert gen.prefill_tokens_padded > gen.prefill_tokens_real
    pool = gen.pool_stats()
    assert pool["prefill_tokens_real"] == real
    assert pool["prefill_tokens_padded"] == gen.prefill_tokens_padded


def test_the_paged_layout_counts_nothing(model):
    gen = _gen(model, batch_slots=2, max_seq=64, page_size=8,
               prefill_buckets=(8, 16))
    gen.generate([5, 3, 2, 6], 4)
    pool = gen.pool_stats()
    assert (pool["prefill_tokens_real"], pool["prefill_tokens_padded"]) == (
        0, 0)
