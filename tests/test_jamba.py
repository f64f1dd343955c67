"""Jamba at toy widths on the CPU: the served path (``prefill_into`` then
``decode_step`` through the dense slot layout) against the float32 plain
reference's full forward, on logits; the chunked selective scan against
the token-by-token recurrence; the layer pattern; the position-free
attention and its flat one-KV-head cache; what ``register_llm`` refuses
for the family; and staggered requests through ``LLMServer``.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_jamba as reference
from gofr_tpu import ops
from gofr_tpu.ml import MLDatasource
from gofr_tpu.ml.generate import Generator
from gofr_tpu.models import jamba, llama, qwen3_next

# a configuration file's keys at toy widths that keep the shape of the
# thing: a period of 3 with its attention layer off the period's start
# (layers 1 and 4 of 6), four query heads on ONE KV head, and R, N, K small
# but all different
SIZES = dict(
    vocab_size=128, hidden_size=32, intermediate_size=48,
    num_hidden_layers=6, num_attention_heads=4, num_key_value_heads=1,
    head_dim=8, attn_layer_period=3, attn_layer_offset=1, mamba_d_state=5,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=6, rms_norm_eps=1e-6)
MAX_SEQ = 256
# float32 program against float32 reference: they differ by the order of
# sums (cached against whole; the scan's arithmetic is the recurrence's
# own). Logits are of order 3; the same reference on bfloat16-rounded
# weights is 100 times further off (``test_tolerance_refuses_bfloat16``).
TOL = 1e-4
# the bfloat16 program (weights, activations, keys and values in bfloat16;
# the state and everything from ``dt`` on in float32) against the float32
# reference on the same bfloat16 weights: what is left is the rounding of
# activations between layers: 0.22 at most and 0.02 in the mean over 31
# positions x 128 logits of order 3 here, so twice that is the bound
TOL_BF16 = 0.45


def _cfg(sizes=SIZES, **kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("use_flash", False)
    return jamba.JambaConfig(max_position_embeddings=MAX_SEQ, **sizes, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, jamba.init_params(cfg, jax.random.PRNGKey(0))


def _programs(cfg):
    return (jax.jit(lambda p, t, l, c, s: jamba.prefill_into(
                p, t, l, cfg, c, s)),
            jax.jit(lambda p, t, c: jamba.decode_step(p, t, c, cfg)))


@pytest.fixture(scope="module")
def programs(model):
    return _programs(model[0])


def _bucket(n):
    return next(b for b in (16, 128, 256) if b >= n)


def _serve(programs, params, cache, ids, n, slot, steps):
    """Prefill ``ids[:n]`` into ``slot`` and decode ``steps`` tokens
    teacher-forced from ``ids``; the logits at positions n-1 .. n-1+steps.
    The other rows decode token 0 alongside."""
    prefill, decode = programs
    toks = np.zeros((1, _bucket(n)), np.int32)
    toks[0, :n] = ids[:n]
    logits, cache = prefill(params, toks, np.array([n], np.int32), cache,
                            np.int32(slot))
    out = [np.asarray(logits)[0]]
    for t in range(n, n + steps):
        tok = np.zeros((cache["len"].shape[0],), np.int32)
        tok[slot] = ids[t]
        logits, cache = decode(params, tok, cache)
        out.append(np.asarray(logits)[slot])
    return np.stack(out), cache


def _reference(params, ids, n, steps, sizes=SIZES):
    return reference.logits_at(params, sizes, ids[:n + steps],
                               np.arange(n - 1, n + steps), pad_to=64,
                               max_positions=64)


@pytest.mark.parametrize("n", [1, 2, 5, 16, 17, 100, 128, 129, 200])
def test_prefill_then_cached_decode_match_reference(model, programs, n):
    """Logits at every position, the prompt's last and 20 decoded: a
    prompt shorter than the window, one that fills a ladder program
    exactly, one past it, and lengths that are no multiple of the scan's
    chunk."""
    cfg, params = model
    ids = np.random.default_rng(n).integers(1, 128, n + 20).tolist()
    got, _ = _serve(programs, params, jamba.init_cache(cfg, 3, MAX_SEQ),
                    ids, n, 1, 20)
    np.testing.assert_allclose(got, _reference(params, ids, n, 20), atol=TOL)


def test_tolerance_refuses_bfloat16(model):
    cfg, params = model
    ids = np.random.default_rng(3).integers(1, 128, 60).tolist()
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    gap = np.abs(_reference(rounded, ids, 40, 20)
                 - _reference(params, ids, 40, 20)).max()
    assert gap > 50 * TOL


def test_bfloat16_program_stays_near_the_float32_reference():
    """The precision the benchmark serves in: weights, activations and
    keys and values in bfloat16, the state in float32."""
    cfg = _cfg(dtype=jnp.bfloat16)
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))
    assert params["mamba"]["w_in"].dtype == jnp.bfloat16
    cache = jamba.init_cache(cfg, 2, MAX_SEQ)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].dtype == cache["k"].dtype == jnp.bfloat16
    ids = np.random.default_rng(4).integers(1, 128, 80).tolist()
    got, _ = _serve(_programs(cfg), params, cache, ids, 50, 0, 30)
    want = _reference(params, ids, 50, 30)
    assert np.abs(got - want).max() < TOL_BF16
    assert np.abs(got - want).mean() < TOL_BF16 / 10


def test_reused_slot_holds_no_trace_of_its_last_request(model, programs):
    """A slot that served a longer request, then a short one, serves the
    short one as a fresh slot does: the prefill starts the state and the
    window from zero."""
    cfg, params = model
    rng = np.random.default_rng(7)
    long_ids = rng.integers(1, 128, 190).tolist()
    short_ids = rng.integers(1, 128, 30).tolist()
    _, used = _serve(programs, params, jamba.init_cache(cfg, 2, MAX_SEQ),
                     long_ids, 170, 1, 20)
    again, _ = _serve(programs, params, used, short_ids, 10, 1, 20)
    fresh, _ = _serve(programs, params, jamba.init_cache(cfg, 2, MAX_SEQ),
                      short_ids, 10, 1, 20)
    np.testing.assert_array_equal(again, fresh)


def test_live_rows_are_unmoved_by_what_idle_rows_hold(model, programs):
    cfg, params = model
    ids = np.random.default_rng(8).integers(1, 128, 60).tolist()
    clean = jamba.init_cache(cfg, 3, MAX_SEQ)
    dirty = {key: (jnp.full_like(a, 3) if key != "len" else
                   jnp.array([MAX_SEQ, 0, 77], jnp.int32))
             for key, a in clean.items()}
    a, _ = _serve(programs, params, clean, ids, 40, 1, 20)
    b, _ = _serve(programs, params, dirty, ids, 40, 1, 20)
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n", [3, 13, 16, 40])
def test_padding_leaves_state_and_window_alone(model, programs, n):
    """The same ``n`` tokens in a program of their own length and padded
    into the ladder's next: state and window come out as the last real
    token left them (``dt = 0`` past it: decay 1, input 0)."""
    cfg, params = model
    prefill, _ = programs
    ids = np.random.default_rng(n).integers(1, 128, n)
    caches = []
    for width in (n, 128):
        toks = np.full((1, width), 99, np.int32)  # the padding is not zero
        toks[0, :n] = ids
        _, cache = prefill(params, toks, np.array([n], np.int32),
                           jamba.init_cache(cfg, 2, MAX_SEQ), np.int32(1))
        caches.append(cache)
    exact, padded = caches
    # (the two programs' matmuls sum in another order: 2e-6)
    np.testing.assert_allclose(padded["state"], exact["state"], atol=1e-5)
    np.testing.assert_allclose(padded["conv"], exact["conv"], atol=1e-5)
    assert np.asarray(exact["state"][:, 1]).any()
    assert not np.asarray(exact["state"][:, 0]).any()
    assert int(padded["len"][1]) == n


@pytest.mark.parametrize("tokens,real,chunk", [
    (16, 16, 16), (48, 48, 16), (50, 37, 16), (50, 50, 8), (7, 7, 16),
    (33, 20, 4)])
def test_chunked_scan_equals_recurrence(tokens, real, chunk):
    """The chunked scan against the recurrence token by token (the
    reference's, and the program's own one-token update): whole chunks, a
    length that is no multiple of the chunk, a sequence shorter than one,
    and ragged real lengths (``dt = 0`` on the padding)."""
    Di, N = 24, 5
    ks = jax.random.split(jax.random.PRNGKey(tokens + chunk), 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (tokens, Di)) - 2.0)
    dt = jnp.where((jnp.arange(tokens) < real)[:, None], dt, 0.0)
    x = jax.random.normal(ks[1], (tokens, Di))
    B = jax.random.normal(ks[2], (tokens, N))
    C = jax.random.normal(ks[3], (tokens, N))
    A = -jnp.exp(jax.random.normal(ks[4], (N, Di)))
    y, S = jamba.selective_scan_chunked(dt, x, B, C, A, chunk=chunk)
    want_y, want_S = reference.selective_scan(dt, x, B, C, A)
    np.testing.assert_allclose(y, want_y, atol=2e-5)
    np.testing.assert_allclose(S, want_S, atol=2e-5)
    if real < tokens:  # the state stopped at the last real token
        _, S_real = reference.selective_scan(dt[:real], x[:real], B[:real],
                                             C[:real], A)
        np.testing.assert_allclose(S, S_real, atol=2e-5)
    S1, ys = jnp.zeros((N, Di)), []
    for t in range(tokens):
        S1, y1 = jamba.selective_scan_step(S1, dt[t], x[t], B[t], C[t], A)
        ys.append(y1)
    np.testing.assert_allclose(jnp.stack(ys), want_y, atol=2e-5)


@pytest.mark.parametrize("layers,period,offset,attn", [
    (28, 14, 7, (7, 21)),          # AI21-Jamba2-3B
    (32, 8, 4, (4, 12, 20, 28)),   # Jamba-v0.1's pattern
    (6, 3, 1, (1, 4)),
])
def test_layer_pattern_follows_period_and_offset(layers, period, offset,
                                                 attn):
    cfg = jamba.JambaConfig(
        **{**SIZES, "num_hidden_layers": layers, "attn_layer_period": period,
           "attn_layer_offset": offset})
    assert cfg.attn_layers == attn
    assert sorted(cfg.attn_layers + cfg.mamba_layers) == list(range(layers))
    assert reference.layer_kinds(
        {"num_hidden_layers": layers, "attn_layer_period": period,
         "attn_layer_offset": offset}) == [i in attn for i in range(layers)]
    shapes = jax.eval_shape(
        lambda: jamba.init_params(cfg, jax.random.PRNGKey(0)))
    assert shapes["attn"]["wq"].shape[0] == len(attn)
    assert shapes["mamba"]["w_in"].shape[0] == layers - len(attn)
    cache = jax.eval_shape(lambda: jamba.init_cache(cfg, 2, 64))
    assert cache["k"].shape == (len(attn), 2, 64, 8)    # flat, one KV head
    assert cache["state"].shape == (layers - len(attn), 2, 5, 64)
    assert cache["conv"].shape == (layers - len(attn), 3, 2, 64)


def test_a_stack_of_one_kind_is_refused():
    with pytest.raises(ValueError, match="one kind"):
        jamba.JambaConfig(**{**SIZES, "attn_layer_offset": 7,
                             "attn_layer_period": 14})
    with pytest.raises(ValueError, match="routed"):
        jamba.JambaConfig(**SIZES, num_experts=16)


def test_attention_is_position_free_but_causal(monkeypatch):
    """No rotary table is built for the family, and an attention layer
    alone is causal and blind to order: in a stack with one attention
    layer and the Mamba mixers silenced, the last token's logits do not
    change when the tokens before it swap places, and no token sees a
    later one."""
    sizes = {**SIZES, "num_hidden_layers": 3}
    cfg = _cfg(sizes)
    params = jamba.init_params(cfg, jax.random.PRNGKey(0))

    def no_table(*a, **kw):
        raise AssertionError("the family has no positional term")

    monkeypatch.setattr(llama, "rope_table", no_table)
    monkeypatch.setattr(llama, "apply_rope", no_table)
    monkeypatch.setattr(ops, "rope_table", no_table)
    quiet = {**params, "mamba": {**params["mamba"], "w_out": jnp.zeros_like(
        params["mamba"]["w_out"])}}
    prefill, decode = _programs(cfg)

    def last_logits(ids):
        toks = np.zeros((1, 16), np.int32)
        toks[0, :len(ids)] = ids
        logits, cache = prefill(quiet, toks, np.array([len(ids)], np.int32),
                                jamba.init_cache(cfg, 1, 64), np.int32(0))
        after, _ = decode(quiet, np.array([5], np.int32), cache)
        return np.asarray(logits)[0], np.asarray(after)[0]

    a, a_next = last_logits([3, 9, 27, 81, 11])
    b, b_next = last_logits([27, 3, 81, 9, 11])     # the first four shuffled
    np.testing.assert_allclose(a, b, atol=1e-5)
    np.testing.assert_allclose(a_next, b_next, atol=1e-5)
    c, _ = last_logits([3, 9, 27, 81, 11, 64])      # a later token changes it
    assert np.abs(c - a).max() > 1e-3
    # with the mixers on, order matters: the Mamba layers carry it
    full_a = _reference(params, [3, 9, 27, 81, 11], 5, 0, sizes)
    full_b = _reference(params, [27, 3, 81, 9, 11], 5, 0, sizes)
    assert np.abs(full_a - full_b).max() > 1e-3


@pytest.mark.parametrize("lens", [[1, 17, 500], [128, 129, 127],
                                  [2048, 2049, 3]])
def test_flat_decode_kernel_matches_the_grouped_einsum(lens):
    """The one-matrix body on a cache stored flat, at the published head
    shape (20 query heads on one KV head of 128), interpreted: short rows,
    a chunk's edges, a row at capacity handed one more than it holds."""
    from gofr_tpu.ops.decode_attention import (
        gqa_decode_attention_tpu,
        row_tiling,
    )

    S, H, D = 2048, 20, 128
    assert row_tiling(S, 1, D, 2) is None          # [S, 1, D] pads on the chip
    assert row_tiling(S, 1, D, 2, flat=True) == (128, 2048)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    b = len(lens)
    q = jax.random.normal(keys[0], (b, 1, H, D), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (2, b, S, D), jnp.bfloat16)
            for kk in keys[1:])
    attended = jnp.asarray(np.minimum(lens, S), jnp.int32)
    want = ops.gqa_decode_attention(q, k[1][:, :, None], v[1][:, :, None],
                                    kv_len=attended)
    got = gqa_decode_attention_tpu(q, k, v, jnp.asarray(lens, jnp.int32),
                                   layer=1, flat_kv_heads=1, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("on_tpu,branch", [(True, "pallas"), (False, "xla")])
def test_decode_branch_is_recorded_for_the_published_head_shape(
        monkeypatch, on_tpu, branch):
    """20 query heads on 1 KV head over a flat cache of 2,048 positions:
    ``pallas`` where there is a TPU, ``xla`` elsewhere, under the key the
    benchmark's ``decode_branch`` looks for."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: on_tpu)
    q = jax.ShapeDtypeStruct((4, 1, 20, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 4, 2048, 128), jnp.bfloat16)
    fn = lambda q, k, v, n: ops.cached_decode_attention(  # noqa: E731
        q, k, v, n, layer=1, flat_kv_heads=1)
    try:
        jax.eval_shape(fn, q, kv, kv, jax.ShapeDtypeStruct((4,), jnp.int32))
    except Exception:
        assert on_tpu  # the kernel does not trace for the CPU; the record stands
    key = ops.branch_key("decode_attention", q, kv)
    assert key.startswith("decode_attention[4x1x20x128,")
    assert ops.kernel_branches()[key] == branch


def _gen(model, **kw):
    cfg, params = model
    return Generator(params, cfg, batch_slots=2, max_seq=MAX_SEQ, chunk=2,
                     **kw)


def test_generator_serves_it_and_counts_the_state_sweep(model):
    """Through ``Generator`` (warmed, greedy): a slot reused after a
    longer request decodes as a fresh generator does, every served token
    is the reference's best within the tolerance, and ``pool_stats()``
    reads the two kinds of state and the sweep's counters."""
    cfg, params = model
    rng = np.random.default_rng(2)
    long_p = rng.integers(1, 128, 150).tolist()
    short_p = rng.integers(1, 128, 9).tolist()
    gen = _gen(model)
    gen.warmup()
    before = gen.pool_stats()
    gen.generate(long_p, 12)
    served = gen.generate(short_p, 12)
    assert served == _gen(model).generate(short_p, 12)
    want = reference.logits_at(params, SIZES, short_p + served,
                               np.arange(8, 20), pad_to=64, max_positions=16)
    gaps = want.max(-1) - want[np.arange(12), served]
    assert gaps.max() <= TOL
    stats = gen.pool_stats()
    cache = jamba.init_cache(cfg, 2, MAX_SEQ)
    assert stats["recurrent_state_bytes"] == (cache["state"].nbytes
                                              + cache["conv"].nbytes)
    assert stats["kv_cache_bytes"] == cache["k"].nbytes + cache["v"].nbytes
    swept = stats["state_rows_swept"] - before["state_rows_swept"]
    live = stats["state_rows_live"] - before["state_rows_live"]
    steps = stats["decode_steps"] - before["decode_steps"]
    # both slots' rows every step; one request at a time held one of them
    assert swept == 2 * steps and 0 < live <= swept // 2
    assert live >= 2 * 11  # 11 tokens after the first, twice
    assert "expert_pairs_routed" not in stats


def test_pool_stats_hang_on_the_state_not_on_the_experts():
    """A Llama cache reports neither; Qwen3-Next's, which has a state and
    routed experts, reports both and the routing counters."""
    cfg = llama.tiny_llama(use_flash=False)
    gen = Generator(llama.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                    batch_slots=2, max_seq=64)
    assert "recurrent_state_bytes" not in gen.pool_stats()
    assert "state_rows_swept" not in gen.pool_stats()
    assert jamba.UNSUPPORTED is qwen3_next.UNSUPPORTED


@pytest.mark.parametrize("kwargs,word", [
    ({"page_size": 16}, "page_size"),
    ({"page_size": 16, "prefix_cache": True}, "prefix cache"),
    ({"page_size": 16, "host_kv": object()}, "kv_offload"),
    ({"page_size": 16, "replicas": 2, "disagg": True}, "kv_transport"),
    ({"sp": "ring"}, "sequence-parallel"),
    ({"spec_k": 4}, "speculation"),
    ({"prefill_chunk": 64}, "segments"),
    ({"shard_cache": True}, "shard_cache"),
    ({"kv_bits": 8}, "int8"),
])
def test_register_llm_refuses_what_the_family_lacks(model, kwargs, word):
    """Every layout the family is not served in is refused where it is
    registered, with what it would take (the table both state families
    use); nothing falls back."""
    cfg, params = model
    kwargs = dict(kwargs)
    if "kv_bits" in kwargs:
        cfg = _cfg(kv_bits=kwargs.pop("kv_bits"))
    with pytest.raises(ValueError, match=word) as err:
        MLDatasource().register_llm("chat", params, cfg, batch_slots=2,
                                    max_seq=MAX_SEQ, warmup=False, **kwargs)
    assert "JambaConfig" in str(err.value)


def test_llm_server_staggered_requests_get_the_tokens_they_get_alone(model):
    """``register_llm`` with the configuration and nothing else; through
    ``LLMServer`` under greedy sampling, requests admitted while others
    decode (more requests than slots, different lengths) each get the
    tokens they get alone on a fresh generator."""
    cfg, params = model
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 128, n).tolist() for n in (5, 40, 17, 130, 3)]
    budgets = [9, 5, 12, 4, 10]
    alone = [_gen(model).generate(p, n)
             for p, n in zip(prompts, budgets, strict=True)]
    server = MLDatasource().register_llm("chat", params, cfg, batch_slots=2,
                                         max_seq=MAX_SEQ, chunk=2)

    async def drive():
        async def one(i):
            await asyncio.sleep(0.05 * i)
            return await server.generate(prompts[i], budgets[i])
        return await asyncio.gather(*[one(i) for i in range(len(prompts))])

    try:
        assert asyncio.run(drive()) == alone
    finally:
        server.close()
