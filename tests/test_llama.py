"""Llama flagship model: math consistency, sharding, training, generation.

The multi-chip analogue of the reference's hermetic pkg tests (SURVEY §4):
every distributed path runs on the 8-device CPU mesh from conftest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu import parallel as par
from gofr_tpu.ml.generate import Generator, Sampler, greedy, sample_logits
from gofr_tpu.ml.train import Trainer
from gofr_tpu.models import llama
from gofr_tpu.parallel import P


@pytest.fixture(scope="module")
def setup():
    cfg = llama.tiny_llama(use_flash=False)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_shapes_and_dtype(setup):
    cfg, params = setup
    toks = jnp.zeros((2, 8), jnp.int32)
    logits = llama.forward(params, toks, cfg)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_prefill_matches_forward(setup):
    cfg, params = setup
    toks = np.array([[1, 2, 3, 4, 5, 0, 0, 0], [7, 8, 9, 10, 11, 12, 13, 2]],
                    np.int32)
    lens = jnp.array([5, 8], jnp.int32)
    logits = llama.forward(params, jnp.asarray(toks), cfg)
    cache = llama.init_cache(cfg, 2, 32)
    pl_logits, cache = llama.prefill(params, jnp.asarray(toks), lens, cfg, cache)
    # last valid token of each row must agree with the no-cache forward
    np.testing.assert_allclose(np.asarray(logits[0, 4]), np.asarray(pl_logits[0]),
                               atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(np.asarray(logits[1, 7]), np.asarray(pl_logits[1]),
                               atol=3e-2, rtol=3e-2)
    np.testing.assert_array_equal(np.asarray(cache["len"]), [5, 8])


# One sequence, every cached program: each must give ``forward``'s logits
# at the positions it computes. Row 1 of two slots carries the sequence;
# row 0 stays empty (a freed slot: its table row is scratch page 0).
_SEQ = np.array([[3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]], np.int32)
_PAGE = 4
# largest logit difference over the reference's largest logit: bf16
# rounding alone at 16 bits, plus the K/V rounding of symmetric int8 and
# asymmetric int4 per vector (largest readings at the tiny config on the
# CPU: under 0.0001, 0.017, 0.143)
_KV_TOL = {16: 0.02, 8: 0.05, 4: 0.25}


def _dense_prefilled(cfg, params):
    cache = llama.init_cache(cfg, 2, 32)
    return llama.prefill_into(
        params, jnp.asarray(_SEQ[:, :4]), jnp.array([4], jnp.int32), cfg,
        cache, jnp.int32(1))


def _paged_prefilled(cfg, params):
    cache = llama.init_paged_cache(cfg, 2, 9, _PAGE)
    table = jnp.asarray([[0] * 8, list(range(1, 9))], jnp.int32)
    logits, cache = llama.paged_prefill_into(
        params, jnp.asarray(_SEQ[:, :4]), jnp.array([4], jnp.int32), cfg,
        cache, table[1, :1], jnp.int32(1), _PAGE)
    return logits, cache, table


def _run_decode_step(cfg, params):
    logits, cache = _dense_prefilled(cfg, params)
    out = {3: logits[0]}
    for t in range(4, 12):
        logits, cache = llama.decode_step(
            params, jnp.asarray([0, _SEQ[0, t]], jnp.int32), cache, cfg)
        out[t] = logits[1]
    return out


def _run_prefill_segment_into(cfg, params):
    cache = llama.init_cache(cfg, 2, 32)
    out = {}
    for start, n in ((0, 4), (4, 4), (8, 3)):  # the last segment is ragged
        seg = np.zeros((1, 4), np.int32)
        seg[0, :n] = _SEQ[0, start:start + n]
        logits, cache = llama.prefill_segment_into(
            params, jnp.asarray(seg), jnp.array([n], jnp.int32), cfg, cache,
            jnp.int32(1), jnp.int32(start), jnp.int32(start + n))
        out[start + n - 1] = logits[0]
    return out


def _run_paged_decode_step(cfg, params):
    logits, cache, table = _paged_prefilled(cfg, params)
    out = {3: logits[0]}
    for t in range(4, 12):
        logits, cache = llama.paged_decode_step(
            params, jnp.asarray([0, _SEQ[0, t]], jnp.int32), cache, table, cfg)
        out[t] = logits[1]
    return out


def _run_paged_suffix_prefill(cfg, params):
    cache = llama.init_paged_cache(cfg, 2, 9, _PAGE)
    row = jnp.arange(1, 9, dtype=jnp.int32)
    first, cache = llama.paged_suffix_prefill(
        params, jnp.asarray(_SEQ[:, :4]), jnp.array([4], jnp.int32), cfg,
        cache, row, 0, _PAGE)
    sfx = np.zeros((1, 8), np.int32)
    sfx[0, :7] = _SEQ[0, 4:11]
    second, cache = llama.paged_suffix_prefill(
        params, jnp.asarray(sfx), jnp.array([7], jnp.int32), cfg, cache, row,
        4, _PAGE)
    return {3: first[0], 10: second[0]}


def _windows(step, cache):
    """Two verify windows of four tokens; the caller advances ``len``."""
    out = {}
    for start in (4, 8):
        toks = np.zeros((2, 4), np.int32)
        toks[1] = _SEQ[0, start:start + 4]
        logits, cache = step(jnp.asarray(toks), cache)
        out.update({start + i: logits[1, i] for i in range(4)})
        cache = {**cache, "len": cache["len"].at[1].add(4)}
    return out


def _run_decode_window(cfg, params):
    logits, cache = _dense_prefilled(cfg, params)
    return {3: logits[0], **_windows(
        lambda toks, c: llama.decode_window(params, toks, c, cfg), cache)}


def _run_paged_decode_window(cfg, params):
    logits, cache, table = _paged_prefilled(cfg, params)
    return {3: logits[0], **_windows(
        lambda toks, c: llama.paged_decode_window(params, toks, c, table, cfg),
        cache)}


_CACHED_PROGRAMS = {
    "decode_step": (_run_decode_step, (16, 8)),
    "prefill_segment_into": (_run_prefill_segment_into, (16, 8)),
    "paged_decode_step": (_run_paged_decode_step, (16, 8, 4)),
    "paged_suffix_prefill": (_run_paged_suffix_prefill, (16, 8, 4)),
    "decode_window": (_run_decode_window, (16, 8)),
    "paged_decode_window": (_run_paged_decode_window, (16, 8, 4)),
}


@pytest.mark.parametrize(
    "program,kv_bits",
    [(name, bits) for name, (_, precisions) in _CACHED_PROGRAMS.items()
     for bits in precisions])
def test_cached_program_matches_forward(setup, program, kv_bits):
    """Every cached program is the one block with another ``attend``: its
    logits are ``forward``'s at the same positions, to the KV precision."""
    _, params = setup
    cfg = llama.tiny_llama(use_flash=False, kv_bits=kv_bits)
    full = np.asarray(llama.forward(params, jnp.asarray(_SEQ), cfg)[0])
    got = _CACHED_PROGRAMS[program][0](cfg, params)
    assert got, program
    for pos, logits in got.items():
        err = np.max(np.abs(np.asarray(logits) - full[pos]))
        assert err / np.max(np.abs(full[pos])) < _KV_TOL[kv_bits], (pos, err)


def test_ragged_decode_rows_at_different_positions(setup):
    """Continuous batching: rows decode at unequal lengths in one step."""
    cfg, params = setup
    toks = np.array([[1, 2, 0, 0], [5, 6, 7, 8]], np.int32)
    lens = jnp.array([2, 4], jnp.int32)
    cache = llama.init_cache(cfg, 2, 16)
    _, cache = llama.prefill(params, jnp.asarray(toks), lens, cfg, cache)
    logits, cache = llama.decode_step(params, jnp.array([9, 9], jnp.int32), cache, cfg)
    np.testing.assert_array_equal(np.asarray(cache["len"]), [3, 5])
    # row 0 must equal the single-row reference: [1, 2, 9]
    ref = llama.forward(params, jnp.array([[1, 2, 9]], jnp.int32), cfg)
    np.testing.assert_allclose(np.asarray(ref[0, 2]), np.asarray(logits[0]),
                               atol=3e-2, rtol=3e-2)


def test_sharded_forward_matches_single_device(setup):
    cfg, params = setup
    mesh = par.make_mesh(par.MeshConfig(dp=2, tp=4))
    specs = par.specs_from_rules(params, llama.SHARDING_RULES)
    sharded = par.shard_params(params, specs, mesh)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 16)),
                       jnp.int32)
    expect = llama.forward(params, toks, cfg)
    with mesh:
        got = jax.jit(lambda p, t: llama.forward(p, t, cfg))(
            sharded, par.shard_like(toks, P("dp", None), mesh)
        )
    # bf16 psum reduction order differs across shardings: absolute-only tol
    np.testing.assert_allclose(np.asarray(expect), np.asarray(got), atol=8e-2)


def test_trainer_loss_decreases(setup):
    cfg, _ = setup
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    mesh = par.make_mesh(par.MeshConfig(dp=2, tp=4))
    specs = par.specs_from_rules(params, llama.SHARDING_RULES)
    trainer = Trainer(
        lambda p, t, y, m: llama.loss_fn(p, t, y, m, cfg),
        params, mesh=mesh, param_specs=specs,
        batch_spec=P("dp"), learning_rate=1e-2,
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)
    mask = np.ones_like(toks)
    mask[:, -1] = 0
    losses = [trainer.step(toks, tgts, mask) for _ in range(5)]
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_sampler_greedy_and_temperature():
    logits = jnp.asarray([[0.0, 5.0, 1.0], [9.0, 0.0, 0.0]])
    out = sample_logits(logits, jax.random.PRNGKey(0), greedy())
    np.testing.assert_array_equal(np.asarray(out), [1, 0])
    # top_k=1 at any temperature collapses to greedy
    out = sample_logits(logits, jax.random.PRNGKey(0), Sampler(temperature=1.0, top_k=1))
    np.testing.assert_array_equal(np.asarray(out), [1, 0])


def test_generator_matches_teacher_forced_greedy(setup):
    """Continuous-batching generator == naive forward-argmax loop."""
    cfg, params = setup
    prompt = [3, 1, 4, 1, 5]
    gen = Generator(params, cfg, batch_slots=2, max_seq=32,
                    prefill_buckets=(8,))
    got = gen.generate(prompt, max_new_tokens=6)

    # naive reference: argmax over full forward each step
    seq = list(prompt)
    expect = []
    for _ in range(6):
        logits = llama.forward(params, jnp.asarray([seq], jnp.int32), cfg)
        t = int(jnp.argmax(logits[0, len(seq) - 1]))
        expect.append(t)
        seq.append(t)
    assert got == expect


def test_generator_interleaved_requests(setup):
    """A request joining mid-decode must not corrupt the resident one."""
    cfg, params = setup
    solo = Generator(params, cfg, batch_slots=2, max_seq=32, prefill_buckets=(8,))
    expect_a = solo.generate([3, 1, 4], max_new_tokens=8)
    expect_b = solo.generate([2, 7], max_new_tokens=4)

    gen = Generator(params, cfg, batch_slots=2, max_seq=32, prefill_buckets=(8,))
    streamed: dict[int, list[int]] = {}
    sa = gen.add_request([3, 1, 4], 8, callback=lambda i, toks: streamed.setdefault(i, []).extend(toks))
    gen.step(); gen.step()
    sb = gen.add_request([2, 7], 4, callback=lambda i, toks: streamed.setdefault(i, []).extend(toks))
    while gen.n_live:
        gen.step()
    assert streamed[sa] == expect_a
    assert streamed[sb] == expect_b


def test_generator_slot_reuse_and_exhaustion(setup):
    cfg, params = setup
    gen = Generator(params, cfg, batch_slots=1, max_seq=32, prefill_buckets=(8,))
    gen.add_request([1, 2], 64)  # occupies the only slot
    with pytest.raises(RuntimeError):
        gen.add_request([3], 1)
    while gen.n_live:
        gen.step()
    assert gen.free_slot() == 0  # reusable after completion


def test_fsdp_training_matches_unsharded(setup):
    """ZeRO-3-style fsdp+tp sharding must not change the training math."""
    import optax

    from gofr_tpu.ml.train import make_train_step

    cfg, _ = setup
    params = llama.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, (8, 16)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1).astype(np.int32)
    mask = np.ones_like(toks)

    opt = optax.sgd(1e-2)
    step = make_train_step(lambda p, t, y, m: llama.loss_fn(p, t, y, m, cfg), opt)
    _, _, loss_ref = jax.jit(step)(params, opt.init(params), toks, tgts, mask)

    mesh = par.make_mesh(par.MeshConfig(dp=2, fsdp=2, tp=2))
    specs = par.specs_from_rules(params, llama.SHARDING_RULES_FSDP)
    sharded = par.shard_params(params, specs, mesh)
    with mesh:
        _, _, loss_sh = jax.jit(step)(
            sharded, opt.init(sharded),
            *(par.shard_like(jnp.asarray(a), P("dp"), mesh)
              for a in (toks, tgts, mask)),
        )
    assert float(loss_sh) == pytest.approx(float(loss_ref), rel=2e-2)


# --------------------------------------------------------------- paged KV
def test_paged_generator_matches_dense(setup):
    """page_size>0 swaps the dense [B, S_max] cache for a shared page pool
    + page tables; greedy output must equal the dense Generator's exactly
    (f32), across multiple concurrent slots and slot reuse."""
    from gofr_tpu.ml.generate import Generator

    cfg, params = setup
    prompts = [[3, 1, 4, 1, 5], [2, 7], [9, 9, 2, 6]]

    dense = Generator(params, cfg, batch_slots=2, max_seq=32,
                      prefill_buckets=(8,), chunk=2)
    expects = [dense.generate(p, max_new_tokens=7) for p in prompts]

    paged = Generator(params, cfg, batch_slots=2, max_seq=32,
                      prefill_buckets=(8,), chunk=2, page_size=8)
    outs = [paged.generate(p, max_new_tokens=7) for p in prompts]
    assert outs == expects
    # all pages returned after release
    assert paged.free_pages == paged.n_pages - 1


def test_paged_capacity_beyond_dense_equivalent(setup):
    """The capacity lever: with a pool HALF the dense worst case, all
    slots still serve short requests concurrently — the dense layout
    would need 2x the HBM for the same slot count."""
    from gofr_tpu.ml.generate import Generator

    cfg, params = setup
    slots, max_seq, ps = 4, 32, 8
    dense_pages = slots * (max_seq // ps)
    gen = Generator(params, cfg, batch_slots=slots, max_seq=max_seq,
                    prefill_buckets=(8,), chunk=2, page_size=ps,
                    n_pages=1 + dense_pages // 2)

    solo = Generator(params, cfg, batch_slots=1, max_seq=max_seq,
                     prefill_buckets=(8,))
    prompts = [[i + 1, i + 2, i + 3] for i in range(slots)]
    expects = [solo.generate(p, max_new_tokens=5) for p in prompts]

    streamed: dict[int, list[int]] = {}
    got_slots = [gen.add_request(
        p, 5, callback=lambda i, toks: streamed.setdefault(i, []).extend(toks))
        for p in prompts]  # 4 concurrent slots on a half-size pool
    while gen.n_live:
        gen.step()
    gen.drain()
    for slot, expect in zip(got_slots, expects):
        assert streamed[slot] == expect
    assert gen.evictions == 0


def test_paged_pool_exhaustion_truncates_not_corrupts(setup):
    """A dry pool truncates the growing slot (finishes early, counted in
    ``evictions``) instead of corrupting neighbors; admission with no
    pages raises instead of silently degrading."""
    from gofr_tpu.ml.generate import Generator

    cfg, params = setup
    # tiny pool: 3 real pages of 8 = 24 tokens total capacity
    gen = Generator(params, cfg, batch_slots=2, max_seq=32,
                    prefill_buckets=(8,), chunk=2, page_size=8, n_pages=4)
    a = gen.add_request([3, 1, 4], 24)  # wants 3+24 tokens = all 4 pages
    while gen.n_live:
        gen.step()
    gen.drain()
    toks = gen.slots[a].tokens
    assert gen.evictions >= 1          # ran out before 24 new tokens
    assert 1 <= len(toks) < 24
    gen.release(a)
    assert gen.free_pages == 3         # pages recycled

    # pool free again: a fresh request must work and match dense output
    dense = Generator(params, cfg, batch_slots=1, max_seq=32,
                      prefill_buckets=(8,))
    assert gen.generate([2, 7], 5) == dense.generate([2, 7], 5)


def test_shared_prefix_matches_full_prompt(setup):
    """register_prefix + suffix admission must reproduce the full-prompt
    decode exactly: the suffix attends the shared pages with the right
    rope offsets, and two slots BORROW the same physical pages."""
    from gofr_tpu.ml.generate import Generator

    cfg, params = setup
    prefix = [5, 9, 2, 7, 1, 4, 8, 3]          # one full page of 8
    suffixes = [[6, 2], [9, 9, 1]]

    dense = Generator(params, cfg, batch_slots=1, max_seq=32,
                      prefill_buckets=(16,))
    expects = [dense.generate(prefix + sfx, max_new_tokens=6)
               for sfx in suffixes]

    gen = Generator(params, cfg, batch_slots=2, max_seq=32,
                    prefill_buckets=(8, 16), chunk=2, page_size=8)
    pid = gen.register_prefix(prefix)
    streamed: dict[int, list[int]] = {}
    slots = [gen.add_request(
        sfx, 6, prefix=pid,
        callback=lambda i, toks: streamed.setdefault(i, []).extend(toks))
        for sfx in suffixes]
    # both slots' tables start with the SAME physical page (borrowed)
    assert gen._table[slots[0], 0] == gen._table[slots[1], 0] != 0
    while gen.n_live:
        gen.step()
    gen.drain()
    for slot, expect in zip(slots, expects):
        assert streamed[slot] == expect
    for slot in slots:
        gen.release(slot)
    # borrowed pages stayed with the prefix; own pages returned
    assert gen._prefixes[pid]["refs"] == 0
    gen.drop_prefix(pid)
    assert gen.free_pages == gen.n_pages - 1


def test_shared_prefix_partial_page_tail(setup):
    """A prefix that is not page-aligned shares only its whole pages; the
    tail tokens re-prefill with each suffix — output still exact."""
    from gofr_tpu.ml.generate import Generator

    cfg, params = setup
    prefix = [5, 9, 2, 7, 1, 4, 8, 3, 6, 6]    # 8 shared + tail [6, 6]
    suffix = [2, 2]

    dense = Generator(params, cfg, batch_slots=1, max_seq=32,
                      prefill_buckets=(16,))
    expect = dense.generate(prefix + suffix, max_new_tokens=6)

    gen = Generator(params, cfg, batch_slots=2, max_seq=32,
                    prefill_buckets=(8, 16), chunk=2, page_size=8)
    pid = gen.register_prefix(prefix)
    assert gen._prefixes[pid]["len"] == 8
    assert gen._prefixes[pid]["tail"] == [6, 6]
    streamed: dict[int, list[int]] = {}
    slot = gen.add_request(
        suffix, 6, prefix=pid,
        callback=lambda i, toks: streamed.setdefault(i, []).extend(toks))
    while gen.n_live:
        gen.step()
    gen.drain()
    assert streamed[slot] == expect


def test_prefix_lru_eviction_rotating_prompts(setup):
    """A rotating set of registered prefixes must never exhaust the pool:
    idle (refs == 0) prefixes are LRU-evicted to make room (VERDICT r4
    #6), in-use prefixes are never touched, and admitting on an evicted
    id raises the typed PrefixEvicted."""
    from gofr_tpu.ml.generate import Generator, PrefixEvicted

    cfg, params = setup
    # 1 scratch + 4 usable pages; every one-page prefix is 8 tokens
    gen = Generator(params, cfg, batch_slots=2, max_seq=32,
                    prefill_buckets=(8, 16), chunk=2, page_size=8,
                    n_pages=5)
    first = gen.register_prefix([1, 2, 3, 4, 5, 6, 7, 8])
    # a live borrower pins `first`
    slot = gen.add_request([9, 9], 2, prefix=first)
    # rotate through more prefixes than the pool could ever hold at once
    pids = [gen.register_prefix([i + 1] * 8) for i in range(6)]
    assert gen.prefix_evictions > 0
    assert gen.has_prefix(first)          # refs > 0: never evicted
    assert gen.has_prefix(pids[-1])       # newest survives
    assert not gen.has_prefix(pids[0])    # oldest idle went first
    with pytest.raises(PrefixEvicted):
        gen.add_request([7], 2, prefix=pids[0])
    while gen.n_live:
        gen.step()
    gen.drain()
    gen.release(slot)
    # once the borrower is gone the pinned prefix becomes evictable too
    assert gen._prefixes[first]["refs"] == 0
    for _ in range(4):
        gen.register_prefix([3] * 8)
    assert not gen.has_prefix(first)


def test_grow_pages_reclaims_idle_prefix_before_truncating(setup):
    """Under pool pressure mid-decode, an idle prefix's pages are
    reclaimed BEFORE a live stream is truncated: the stream finishes its
    full budget and only the prefix dies."""
    from gofr_tpu.ml.generate import Generator

    cfg, params = setup
    gen = Generator(params, cfg, batch_slots=1, max_seq=32,
                    prefill_buckets=(8,), chunk=2, page_size=8, n_pages=5)
    pid = gen.register_prefix([1, 2, 3, 4, 5, 6, 7, 8])  # 1 idle page
    got: list[int] = []
    slot = gen.add_request([5, 3, 2, 6, 1, 9, 4, 7], 20,
                           callback=lambda i, toks: got.extend(toks))
    while gen.n_live:
        gen.step()
    gen.drain()
    assert len(got) == 20                  # full budget, no truncation
    assert not gen.slots[slot].evicted
    assert gen.evictions == 0
    assert not gen.has_prefix(pid)         # the idle prefix paid instead
    assert gen.prefix_evictions == 1


def test_shared_prefix_int8_pages_matches_dense_quant():
    """Prefix sharing now composes with int8 pages: suffix admission over
    a quantized shared prefix reproduces the int8 dense decode exactly."""
    from gofr_tpu.ml.generate import Generator

    cfg = llama.tiny_llama(use_flash=False, kv_quant=True)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    prefix = [5, 9, 2, 7, 1, 4, 8, 3]
    suffixes = [[6, 2], [9, 9, 1]]
    dense = Generator(params, cfg, batch_slots=1, max_seq=32,
                      prefill_buckets=(16,))
    expects = [dense.generate(prefix + sfx, 6) for sfx in suffixes]

    gen = Generator(params, cfg, batch_slots=2, max_seq=32,
                    prefill_buckets=(8, 16), chunk=2, page_size=8)
    pid = gen.register_prefix(prefix)
    got: dict[int, list[int]] = {}
    slots = [gen.add_request(
        sfx, 6, prefix=pid,
        callback=lambda i, toks: got.setdefault(i, []).extend(toks))
        for sfx in suffixes]
    while gen.n_live:
        gen.step()
    gen.drain()
    assert [got[s] for s in slots] == expects
    for s in slots:
        gen.release(s)
    gen.drop_prefix(pid)
    assert gen.free_pages == gen.n_pages - 1
