"""Qwen3-Next at toy widths on the CPU: the served path (``prefill_into``
then ``decode_step`` through the dense slot layout) against the float32
plain reference's full forward, on logits; the chunk-parallel DeltaNet
against the token-by-token recurrence; one chip's share of the experts
against the uncut layer; what ``register_llm`` refuses for the family; the
value the decode kernels are handed as ``kv_len``; and the rows of the flat
cache where keys and values lie.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_qwen3_next as reference
from gofr_tpu import ops
from gofr_tpu.ml import MLDatasource
from gofr_tpu.ml.generate import Generator, _prefill_ladder
from gofr_tpu.models import llama, moe
from gofr_tpu.models import qwen3_next as qn
from gofr_tpu.ops.gated_delta import gated_delta_step

# a configuration file's keys at toy widths: ``num_experts`` held of
# ``router_width``; two periods of three DeltaNet layers and one attention
SIZES = dict(
    vocab_size=128, hidden_size=64, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=16, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, num_experts=4, router_width=16,
    num_experts_per_tok=4, full_attention_interval=4,
    partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6)
MAX_SEQ = 256
# float32 program against float32 reference: they differ by the order of
# sums (chunked against token by token, cached against whole). Logits are
# of order 3; the same reference on bfloat16-rounded weights is 100 times
# further off (``test_tolerance_refuses_bfloat16``).
TOL = 2e-4


def _cfg(sizes=SIZES, **kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("use_flash", False)
    plain = {k: v for k, v in sizes.items()
             if k not in ("num_experts", "router_width")}
    return qn.Qwen3NextConfig(
        num_experts=sizes["router_width"], held=(0, sizes["num_experts"]),
        max_position_embeddings=MAX_SEQ, **plain, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, qn.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def programs(model):
    cfg, _ = model
    return (jax.jit(lambda p, t, l, c, s: qn.prefill_into(p, t, l, cfg, c, s)),
            jax.jit(lambda p, t, c: qn.decode_step(p, t, c, cfg)))


def _serve(programs, params, cache, ids, n, slot, steps):
    """Prefill ``ids[:n]`` into ``slot`` in the ladder's bucket, then feed
    the next ``steps`` tokens through the cache (the other rows decode
    garbage beside it). Logits at positions ``n - 1 .. n - 1 + steps``."""
    prefill, step = programs
    bucket = next(b for b in _prefill_ladder(MAX_SEQ) if n <= b)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = ids[:n]
    logits, cache = prefill(params, toks, np.array([n], np.int32), cache,
                            np.int32(slot))
    got = [np.asarray(logits[0])]
    for j in range(steps):
        tok = np.full((cache["len"].shape[0],), 7, np.int32)
        tok[slot] = ids[n + j]
        logits, cache = step(params, tok, cache)
        got.append(np.asarray(logits[slot]))
    return np.stack(got), cache


def _reference(params, ids, n, steps, sizes=SIZES):
    return reference.logits_at(params, sizes, ids[:n + steps],
                               np.arange(n - 1, n + steps), pad_to=64,
                               max_positions=8)


# on the ladder (128, 256), just off it, and shorter than the conv window
@pytest.mark.parametrize("n", [2, 5, 100, 128, 129, 200])
def test_prefill_then_cached_decode_match_reference(model, programs, n):
    cfg, params = model
    ids = np.random.default_rng(n).integers(1, 128, n + 6).tolist()
    got, _ = _serve(programs, params, qn.init_cache(cfg, 3, MAX_SEQ), ids,
                    n, slot=1, steps=6)
    want = _reference(params, ids, n, 6)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_tolerance_refuses_bfloat16(model):
    """The reference on weights rounded to bfloat16 misses the tolerance
    by far: it is tight enough to tell the precisions apart."""
    _, params = model
    ids = np.random.default_rng(3).integers(1, 128, 70).tolist()
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    off = np.abs(_reference(rounded, ids, 64, 6)
                 - _reference(params, ids, 64, 6)).max()
    assert off > 20 * TOL


def test_reused_slot_holds_no_trace_of_its_last_request(model, programs):
    """A long request, then a short one in the same slot: the short one's
    logits are those of a fresh cache (recurrent state, convolution window
    and keys are all rewritten by its prefill), and the reference's."""
    cfg, params = model
    rng = np.random.default_rng(11)
    long_ids = rng.integers(1, 128, 206).tolist()
    short_ids = rng.integers(1, 128, 26).tolist()
    _, used = _serve(programs, params, qn.init_cache(cfg, 2, MAX_SEQ),
                     long_ids, 200, slot=0, steps=6)
    again, _ = _serve(programs, params, used, short_ids, 20, slot=0, steps=6)
    fresh, _ = _serve(programs, params, qn.init_cache(cfg, 2, MAX_SEQ),
                      short_ids, 20, slot=0, steps=6)
    np.testing.assert_array_equal(again, fresh)
    np.testing.assert_allclose(again, _reference(params, short_ids, 20, 6),
                               atol=TOL, rtol=0)


def test_padding_leaves_the_recurrent_state_alone(model, programs):
    """The same 100-token prompt in the 128 and in the 256 program: state,
    window and logits agree (padding has ``beta = 0, g = 0`` and stays out
    of the window)."""
    cfg, params = model
    prefill, _ = programs
    ids = np.random.default_rng(5).integers(1, 128, 100)
    out = []
    for bucket in (128, 256):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :100] = ids
        logits, cache = prefill(params, toks, np.array([100], np.int32),
                                qn.init_cache(cfg, 1, MAX_SEQ), np.int32(0))
        out.append((logits, cache["state"], cache["conv"]))
    for a, b in zip(*out, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   rtol=0)


@pytest.mark.parametrize("tokens,real", [(64, 64), (192, 192), (256, 130)])
def test_chunked_delta_rule_equals_recurrence(tokens, real):
    """The chunk-parallel form against the reference's token-by-token
    rule: outputs and last state, with strong and weak decay and a padded
    tail (``beta = 0, g = 0``)."""
    ks = jax.random.split(jax.random.PRNGKey(tokens), 5)
    H, dk, dv = 3, 16, 8
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (tokens, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (tokens, H, dk)))
    v = jax.random.normal(ks[2], (tokens, H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (tokens, H), minval=-7.0,
                                    maxval=2.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (tokens, H)))
    pad = (jnp.arange(tokens) >= real)[:, None]
    g, beta = jnp.where(pad, 0.0, g), jnp.where(pad, 0.0, beta)
    o, S = jax.jit(qn.gated_delta_chunked)(q, k, v, g, beta)
    o_ref, S_ref = reference.delta_rule(q[:real], k[:real], v[:real],
                                        g[:real], beta[:real])
    np.testing.assert_allclose(np.asarray(o[:real]), np.asarray(o_ref),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(S), np.asarray(S_ref), atol=2e-5,
                               rtol=0)
    # and one recurrent step of the program's own from that state
    S1, o1 = gated_delta_step(S_ref, q[0], k[0], v[0], g[0], beta[0])
    S = S_ref * jnp.exp(g[0])[:, None, None]
    d = beta[0][:, None] * (v[0] - jnp.einsum("hkv,hk->hv", S, k[0]))
    S = S + k[0][:, :, None] * d[:, None, :]
    np.testing.assert_allclose(np.asarray(S1), np.asarray(S), atol=1e-6)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(
        jnp.einsum("hkv,hk->hv", S, q[0])), atol=1e-6)


def _expert_layer(seed=0, n=24, d=16, f=8, e=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (n, d))
    router = jax.random.normal(ks[1], (d, e))
    w_gu = jax.random.normal(ks[2], (e, d, 2 * f)) * d ** -0.5
    w_d = jax.random.normal(ks[3], (e, f, d)) * f ** -0.5
    return x, router, w_gu, w_d


def test_four_shares_sum_to_the_uncut_layer():
    """Each of four chips computes its four experts' terms; with the
    shared expert counted once they add up to the whole layer, which is a
    plain loop over every token's top-k experts."""
    x, router, w_gu, w_d = _expert_layer()
    top_k, f = 4, w_d.shape[1]
    w, idx = moe.route_top_k(x, router, top_k)
    whole, stats = moe.dropless_experts(x, w, idx, w_gu, w_d, (0, 16))
    assert [int(s) for s in stats] == [24 * top_k, 24 * top_k,
                                       len(np.unique(np.asarray(idx)))]
    shares = sum(
        moe.dropless_experts(x, w, idx, w_gu[first:first + 4],
                             w_d[first:first + 4], (first, 4))[0]
        for first in (0, 4, 8, 12))
    np.testing.assert_allclose(np.asarray(shares), np.asarray(whole),
                               atol=1e-5)
    probs = np.asarray(jax.nn.softmax(x @ router, axis=-1), np.float64)
    plain = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        top = np.argsort(-probs[t])[:top_k]
        assert set(top) == set(np.asarray(idx[t]).tolist())
        for e in top:
            h = np.asarray(x[t], np.float64) @ np.asarray(w_gu[e], np.float64)
            act = h[:f] / (1 + np.exp(-h[:f])) * h[f:]
            plain[t] += (probs[t, e] / probs[t, top].sum()
                         * (act @ np.asarray(w_d[e], np.float64)))
    np.testing.assert_allclose(np.asarray(whole), plain, atol=1e-4)


def test_share_counters_and_stacked_layers():
    """The counts are those of a seeded batch (pairs on held experts,
    padding left out), and a layer's block of a stack of layers gives what
    the layer's own weights give."""
    x, router, w_gu, w_d = _expert_layer(seed=1)
    w, idx = moe.route_top_k(x, router, 4)
    valid = jnp.arange(24) < 20
    y, (routed, held, touched) = moe.dropless_experts(
        x, w, idx, w_gu[4:8], w_d[4:8], (4, 4), valid=valid)
    on_held = (np.asarray(idx[:20]) >= 4) & (np.asarray(idx[:20]) < 8)
    assert int(routed) == 80 and int(held) == on_held.sum()
    assert int(touched) == len(np.unique(np.asarray(idx[:20])[on_held]))
    assert not np.asarray(y[20:]).any()
    stack_gu = jnp.concatenate([w_gu[8:12], w_gu[4:8], w_gu[0:4]])
    stack_d = jnp.concatenate([w_d[8:12], w_d[4:8], w_d[0:4]])
    y2, _ = jax.jit(lambda layer: moe.dropless_experts(
        x, w, idx, stack_gu, stack_d, (4, 4), valid=valid, layer=layer))(1)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y), atol=1e-6)


def _gen(model, **kw):
    cfg, params = model
    return Generator(params, cfg, batch_slots=2, max_seq=MAX_SEQ, chunk=2,
                     **kw)


def test_generator_serves_it_and_counts_the_routing(model):
    """Through ``Generator`` (warmed, greedy): a slot reused after a longer
    request decodes as a fresh generator does, every served token is the
    reference's best within the tolerance, and ``pool_stats()`` reads the
    routing counters and the two kinds of state."""
    cfg, params = model
    rng = np.random.default_rng(2)
    long_p = rng.integers(1, 128, 150).tolist()
    short_p = rng.integers(1, 128, 9).tolist()
    gen = _gen(model)
    gen.warmup()
    assert gen.prefill_buckets == (128, 256)
    before = gen.pool_stats()
    gen.generate(long_p, 12)
    served = gen.generate(short_p, 12)
    assert served == _gen(model).generate(short_p, 12)
    want = reference.logits_at(params, SIZES, short_p + served,
                               np.arange(8, 20), pad_to=64, max_positions=16)
    gaps = want.max(-1) - want[np.arange(12), served]
    assert gaps.max() <= TOL
    stats = gen.pool_stats()
    routed = stats["expert_pairs_routed"] - before["expert_pairs_routed"]
    held = stats["expert_pairs_held"] - before["expert_pairs_held"]
    k, layers = cfg.num_experts_per_tok, cfg.num_hidden_layers
    # the prompts' real tokens, and both rows of every decode step
    decoded = gen.steps * 2
    assert routed >= (150 + 9) * k * layers
    assert routed <= (150 + 9 + decoded) * k * layers
    assert 0.1 < held / routed < 0.45          # 4 of 16 experts held
    assert 0 < stats["experts_touched"] <= stats["expert_pairs_held"]
    cache = qn.init_cache(cfg, 2, MAX_SEQ)
    assert stats["recurrent_state_bytes"] == (cache["state"].nbytes
                                              + cache["conv"].nbytes)
    assert stats["kv_cache_bytes"] == cache["k"].nbytes + cache["v"].nbytes


def test_all_experts_held_counts_every_pair():
    sizes = {**SIZES, "num_hidden_layers": 4, "num_experts": 16}
    cfg = _cfg(sizes)
    params = qn.init_params(cfg, jax.random.PRNGKey(1))
    toks = np.zeros((1, 128), np.int32)
    toks[0, :50] = np.arange(1, 51)
    _, cache = jax.jit(lambda p, t, l, c: qn.prefill_into(
        p, t, l, cfg, c, 0))(params, toks, np.array([50], np.int32),
                             qn.init_cache(cfg, 1, 128))
    counts = np.asarray(cache["moe_counts"])[:, 0]
    assert counts[0] == counts[1] == 50 * 4 * 4 and 0 < counts[2] <= 64


@pytest.mark.parametrize("kwargs,word", [
    ({"page_size": 16}, "page_size"),
    ({"page_size": 16, "prefix_cache": True}, "prefix cache"),
    ({"page_size": 16, "host_kv": object()}, "kv_offload"),
    ({"page_size": 16, "replicas": 2, "disagg": True}, "kv_transport"),
    ({"page_size": 16, "decode_window": 4}, "page_size"),
    ({"sp": "ring"}, "sequence-parallel"),
    ({"spec_k": 4}, "speculation"),
    ({"prefill_chunk": 64}, "segments"),
    ({"shard_cache": True}, "shard_cache"),
    ({"kv_bits": 8}, "int8"),
    ({"kv_bits": 4}, "int8"),
])
def test_register_llm_refuses_what_the_family_lacks(model, kwargs, word):
    """Every layout the family is not served in yet is refused where it is
    registered, with what it would take; nothing falls back."""
    cfg, params = model
    kwargs = dict(kwargs)
    if "kv_bits" in kwargs:
        cfg = _cfg(kv_bits=kwargs.pop("kv_bits"))
    with pytest.raises(ValueError, match=word):
        MLDatasource().register_llm("chat", params, cfg, batch_slots=2,
                                    max_seq=MAX_SEQ, warmup=False, **kwargs)


def test_register_llm_serves_the_family(model):
    """``register_llm`` with the new configuration and nothing else: the
    dense layout, answered through ``LLMServer`` as any model's."""
    import asyncio

    cfg, params = model
    server = MLDatasource().register_llm("chat", params, cfg, batch_slots=2,
                                         max_seq=MAX_SEQ, chunk=2)
    try:
        prompt = list(range(1, 12))
        tokens = asyncio.run(server.generate(prompt, 6))
        assert tokens == _gen(model).generate(prompt, 6)
    finally:
        server.close()


@pytest.mark.parametrize("family", ["llama", "qwen3_next"])
def test_decode_hands_the_kernels_a_length_inside_the_cache(family, model,
                                                            monkeypatch):
    """A row that sat at capacity has ``len == S_max``; the decode kernels
    must be told ``S_max`` keys, not ``S_max + 1`` (the Pallas kernel would
    fetch a block past the cache's end)."""
    S_max, seen = 32, []
    module = llama if family == "llama" else qn
    inner = module.cached_decode_attention

    def spy(q, k_cache, v_cache, kv_len, **kw):
        jax.debug.callback(lambda v: seen.append(np.asarray(v)), kv_len)
        return inner(q, k_cache, v_cache, kv_len, **kw)

    monkeypatch.setattr(module, "cached_decode_attention", spy)
    if family == "llama":
        cfg = llama.tiny_llama(use_flash=False)
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
    else:
        cfg, params = model
    cache = module.init_cache(cfg, 3, S_max)
    cache["len"] = jnp.array([S_max, S_max - 1, 4], jnp.int32)
    _, new = jax.jit(lambda p, t, c: module.decode_step(p, t, c, cfg))(
        params, np.zeros((3,), np.int32), cache)
    jax.effects_barrier()
    assert seen  # once a layer that attends
    for handed in seen:
        np.testing.assert_array_equal(handed, [S_max, S_max, 5])
    np.testing.assert_array_equal(np.asarray(new["len"]), [S_max, S_max, 5])


def test_keys_and_values_lie_flat(model, monkeypatch):
    """Token ``t``'s KV head ``g`` lies at row ``t * KV + g`` of its
    period's plane: where ``prefill_into`` puts a prompt's keys and values,
    and where ``decode_step`` writes each row's next token (the rows below
    it unmoved); the step hands the decode attention that flat cache."""
    cfg, params = model
    KV, hd = cfg.num_key_value_heads, cfg.head_dim
    seen = []
    inner = qn._attn_inputs

    def spy(*args):
        q, gate, k, v = inner(*args)
        jax.debug.callback(
            lambda k, v: seen.append((np.asarray(k), np.asarray(v))), k, v,
            ordered=True)
        return q, gate, k, v

    monkeypatch.setattr(qn, "_attn_inputs", spy)
    n, slot = 10, 1
    toks = np.zeros((1, 128), np.int32)
    toks[0, :n] = np.random.default_rng(4).integers(1, 128, n)
    cache = qn.init_cache(cfg, 3, MAX_SEQ)
    assert cache["k"].shape == (cfg.n_periods, 3, MAX_SEQ * KV, hd)
    _, cache = jax.jit(lambda p, t, l, c, s: qn.prefill_into(
        p, t, l, cfg, c, s))(params, toks, np.array([n], np.int32), cache,
                             np.int32(slot))
    jax.effects_barrier()
    assert len(seen) == cfg.n_periods
    for p, (k, v) in enumerate(seen):       # [1, T, KV, hd] a period
        for name, want in (("k", k), ("v", v)):
            np.testing.assert_array_equal(
                np.asarray(cache[name][p, slot, :n * KV]),
                want[0, :n].reshape(n * KV, hd))
    # past the prompt's bucket of 128, nothing
    assert not np.asarray(cache["k"][:, slot, 128 * KV:]).any()

    seen.clear()
    _, new = jax.jit(lambda p, t, c: qn.decode_step(p, t, c, cfg))(
        params, np.full((3,), 7, np.int32), cache)
    jax.effects_barrier()
    assert len(seen) == cfg.n_periods
    pos = np.asarray(cache["len"])              # [0, n, 0]
    for p, (k, v) in enumerate(seen):       # [3, 1, KV, hd] a period
        for name, want in (("k", k), ("v", v)):
            for row in range(3):
                at = pos[row] * KV + np.arange(KV)
                np.testing.assert_array_equal(
                    np.asarray(new[name][p, row, at]), want[row, 0])
            np.testing.assert_array_equal(
                np.asarray(new[name][p, slot, :n * KV]),
                np.asarray(cache[name][p, slot, :n * KV]))
    H = cfg.num_attention_heads
    key = ops.branch_key("decode_attention",
                         jax.ShapeDtypeStruct((3, 1, H, hd), jnp.float32),
                         new["k"])
    assert ops.kernel_branches()[key] == "xla"     # off the chip


def test_a_prompt_and_a_step_record_the_grouped_products_branch(model,
                                                                programs):
    """Both expert products of a prefill and of a decode step go through
    ``ops.grouped_matmul`` over the whole stack of every layer's held
    experts, and the dispatch record says which branch they took: off the
    chip ``jax.lax.ragged_dot``."""
    cfg, params = model
    ids = np.random.default_rng(3).integers(1, 128, 12).tolist()
    _serve(programs, params, qn.init_cache(cfg, 3, MAX_SEQ), ids, 10,
           slot=0, steps=1)
    k, (_, held) = cfg.num_experts_per_tok, cfg.held
    stack, d, f = 8 * held, cfg.hidden_size, cfg.moe_intermediate_size
    took = ops.kernel_branches()
    for rows in (3 * k, 128 * k):  # a decode step's pairs, a prompt's
        for xs, w in (((rows, d), (stack, d, 2 * f)),
                      ((rows, f), (stack, f, d))):
            key = ops.branch_key(
                "grouped_matmul", jax.ShapeDtypeStruct(xs, jnp.float32),
                jax.ShapeDtypeStruct(w, jnp.float32))
            assert took[key] == "xla", (key, sorted(took))
