"""The DeepSeek-V3 block (GigaChat3.1's architecture) at toy widths on the
CPU: the served path (``prefill_into`` then ``decode_step`` through the
latent cache of the dense slot layout) against the float32 plain
reference's full forward, on logits; the group-limited router against a
plain loop; one chip's share of the experts against the uncut layer;
YaRN against the published formula; what ``register_llm`` refuses for the
family; and where the latent rows lie.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_deepseek_v3 as reference
from gofr_tpu import ops
from gofr_tpu.ml import MLDatasource
from gofr_tpu.ml.generate import Generator, _prefill_ladder
from gofr_tpu.models import deepseek_v3 as dv
from gofr_tpu.models import moe

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 64,
        "rope_type": "yarn"}
# a configuration file's keys at toy widths: ``n_routed_experts`` held of
# ``router_width``; one leading dense layer and two expert layers; YaRN on
# a short original context, so the prompts here cross its ramp
SIZES = dict(
    vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=3, num_attention_heads=4,
    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=24, n_routed_experts=4,
    router_width=16, n_shared_experts=1, num_experts_per_tok=4, n_group=4,
    topk_group=2, routed_scaling_factor=2.5, first_k_dense_replace=1,
    rope_theta=1e5, rope_scaling=YARN, rms_norm_eps=1e-6)
MAX_SEQ = 256
# float32 program against float32 reference: they differ by the order of
# sums (absorbed against expanded, cached against whole). Logits are of
# order 3; the same reference on bfloat16-rounded weights is 100 times
# further off (``test_tolerance_refuses_bfloat16``).
TOL = 1e-4


def _cfg(sizes=SIZES, **kw):
    kw.setdefault("dtype", jnp.float32)
    kw.setdefault("use_flash", False)
    plain = {k: v for k, v in sizes.items()
             if k not in ("n_routed_experts", "router_width")}
    return dv.DeepseekV3Config(
        n_routed_experts=sizes["router_width"],
        held=(0, sizes["n_routed_experts"]),
        max_position_embeddings=MAX_SEQ, **plain, **kw)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, dv.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def programs(model):
    cfg, _ = model
    return (jax.jit(lambda p, t, l, c, s: dv.prefill_into(p, t, l, cfg, c, s)),
            jax.jit(lambda p, t, c: dv.decode_step(p, t, c, cfg)))


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


# on the ladder (128, 256), just off it, and past YaRN's original context
@pytest.mark.parametrize("n", [2, 5, 100, 128, 129, 240])
def test_prefill_then_cached_decode_match_reference(model, programs, n):
    cfg, params = model
    ids = np.random.default_rng(n).integers(1, 128, n + 6).tolist()
    got, _ = _serve(programs, params, dv.init_cache(cfg, 3, MAX_SEQ), ids,
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
    logits are those of a fresh cache, and the reference's."""
    cfg, params = model
    rng = np.random.default_rng(11)
    long_ids = rng.integers(1, 128, 206).tolist()
    short_ids = rng.integers(1, 128, 26).tolist()
    _, used = _serve(programs, params, dv.init_cache(cfg, 2, MAX_SEQ),
                     long_ids, 200, slot=0, steps=6)
    again, _ = _serve(programs, params, used, short_ids, 20, slot=0, steps=6)
    fresh, _ = _serve(programs, params, dv.init_cache(cfg, 2, MAX_SEQ),
                      short_ids, 20, slot=0, steps=6)
    np.testing.assert_allclose(again, fresh, atol=1e-6, rtol=0)
    np.testing.assert_allclose(again, _reference(params, short_ids, 20, 6),
                               atol=TOL, rtol=0)


def test_latent_rows_lie_where_the_kernel_reads_them(model, programs):
    """One row a token and layer, ``[c_kv | k_pe]`` and zeros to whole
    lanes, at the token's position in its slot: the prompt's rows from the
    prefill (its bucket's padding too, past the length, where nothing
    reads), the next token's from the decode step; the idle slots decode
    garbage at their position 0 and nowhere else."""
    cfg, params = model
    ids = np.random.default_rng(5).integers(1, 128, 40).tolist()
    _, cache = _serve(programs, params, dv.init_cache(cfg, 3, MAX_SEQ), ids,
                      30, slot=2, steps=1)
    latent = np.asarray(cache["latent"])
    assert latent.shape == (3, 3, MAX_SEQ, 128)   # 32 + 8 values at 128
    assert int(cache["len"][2]) == 31
    assert not latent[:, 2, :31, cfg.latent_width:].any()
    assert np.abs(latent[:, 2, :31, :cfg.latent_width]).sum(-1).min() > 0
    assert not latent[:, 2, 128:].any()          # past the prompt's bucket
    assert not latent[:, :2, 1:].any()
    # c_kv is RMS-normed (its norm weight near 1): mean square near 1
    ms = (latent[:, 2, :31, :cfg.kv_lora_rank] ** 2).mean(-1)
    assert 0.5 < ms.min() and ms.max() < 2.0


def test_decode_at_capacity_keeps_its_length_inside_the_cache(
        model, monkeypatch):
    """A row whose length reached ``S_max`` decodes garbage without
    writing outside its slot; the kernel is handed at most ``S_max``."""
    cfg, params = model
    cache = dv.init_cache(cfg, 2, 16)
    cache = {**cache, "len": jnp.asarray([16, 3], jnp.int32)}
    seen = []
    real = ops.latent_decode_attention

    def spy(q, c, kv_len, **kw):
        seen.append(kv_len)
        return real(q, c, kv_len, **kw)

    monkeypatch.setattr(dv, "latent_decode_attention", spy)
    logits, out = dv.decode_step(params, jnp.asarray([5, 6]), cache, cfg)
    assert np.isfinite(np.asarray(logits)).all()
    assert not np.asarray(out["latent"])[:, 0].any()   # nothing written
    np.testing.assert_array_equal(np.asarray(out["len"]), [16, 4])
    assert all(np.asarray(k).max() <= 16 for k in seen)


# ------------------------------------------------------------------ router
def _plain_route(x, router, bias, top_k, n_group, topk_group, scale):
    """noaux_tc as a loop over tokens, groups and experts, float64."""
    x, router = np.asarray(x, np.float64), np.asarray(router, np.float64)
    bias = np.asarray(bias, np.float64)
    s = 1.0 / (1.0 + np.exp(-(x @ router)))
    b = s + bias
    n, e = b.shape
    size = e // n_group
    out_w, out_i = [], []
    for t in range(n):
        scores = []
        for g in range(n_group):
            members = sorted(b[t, g * size:(g + 1) * size], reverse=True)
            scores.append(members[0] + members[1])
        groups = sorted(range(n_group), key=lambda g: -scores[g])[:topk_group]
        choice = [b[t, i] if i // size in groups else 0.0 for i in range(e)]
        chosen = sorted(range(e), key=lambda i: -choice[i])[:top_k]
        w = np.array([s[t, i] for i in chosen])
        out_w.append(dict(zip(chosen, w / w.sum() * scale)))
        out_i.append(set(chosen))
    return out_w, out_i


def test_group_limited_routing_equals_a_plain_loop():
    """``moe.route_group_limited`` at the published shape (256 experts in
    8 groups, 4 kept, top 8, scale 2.5) equals the loop, and the bias
    moves some token's choice: its biased top 8 are not its unbiased top
    8, and the weights stay the unbiased scores."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    D, E = 32, 256
    x = jax.random.normal(ks[0], (24, D))
    router = jax.random.normal(ks[1], (D, E)) * D ** -0.5
    bias = 0.05 * jax.random.normal(ks[2], (E,))
    w, idx = moe.route_group_limited(x, router, bias, 8, 8, 4, 2.5)
    assert w.shape == idx.shape == (24, 8) and w.dtype == jnp.float32
    want_w, want_i = _plain_route(x, router, bias, 8, 8, 4, 2.5)
    moved = 0
    for t in range(24):
        got = dict(zip(np.asarray(idx[t]).tolist(),
                       np.asarray(w[t]).tolist()))
        assert set(got) == want_i[t]
        for e, v in want_w[t].items():
            assert abs(got[e] - v) < 1e-5
        assert abs(sum(got.values()) - 2.5) < 1e-5
        unbiased, _ = _plain_route(x[t:t + 1], router, np.zeros(E), 8, 8, 4,
                                   2.5)
        moved += set(unbiased[0]) != want_i[t]
    assert moved > 0


def _expert_layer(seed=0, n=24, D=16, F=8, E=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (n, D))
    router = jax.random.normal(ks[1], (D, E)) * D ** -0.5
    bias = 0.05 * jax.random.normal(ks[2], (E,))
    w_gu = jax.random.normal(ks[3], (E, D, 2 * F)) * D ** -0.5
    w_d = jax.random.normal(ks[4], (E, F, D)) * F ** -0.5
    s_gu = jax.random.normal(ks[5], (D, 2 * F)) * D ** -0.5
    return x, router, bias, w_gu, w_d, s_gu, w_d[0] * 0.5


def test_sixteen_shares_sum_to_the_uncut_layer():
    """Sixteen chips each hold one of 16 experts and compute its terms;
    with the shared expert counted once they add up to the whole layer,
    which is a plain loop over every token's chosen experts."""
    x, router, bias, w_gu, w_d, s_gu, s_d = _expert_layer()
    f = w_d.shape[1]
    w, idx = moe.route_group_limited(x, router, bias, 4, 4, 2, 2.5)
    shared = moe.shared_expert(x, s_gu, s_d)
    whole = moe.dropless_experts(x, w, idx, w_gu, w_d, (0, 16))[0] + shared
    shares = shared + sum(
        moe.dropless_experts(x, w, idx, w_gu[e:e + 1], w_d[e:e + 1],
                             (e, 1))[0] for e in range(16))
    np.testing.assert_allclose(np.asarray(shares), np.asarray(whole),
                               atol=1e-5)

    def swiglu(v, gu, d):
        h = np.asarray(v, np.float64) @ np.asarray(gu, np.float64)
        return (h[:f] / (1 + np.exp(-h[:f])) * h[f:]) @ np.asarray(
            d, np.float64)

    want_w, _ = _plain_route(x, router, bias, 4, 4, 2, 2.5)
    plain = np.stack([swiglu(x[t], s_gu, s_d) + sum(
        v * swiglu(x[t], w_gu[e], w_d[e]) for e, v in want_w[t].items())
        for t in range(x.shape[0])])
    np.testing.assert_allclose(np.asarray(whole), plain, atol=1e-4)


# -------------------------------------------------------------------- YaRN
def test_yarn_frequencies_and_scale_follow_the_published_formula():
    """``ops.scale_rope_freqs`` (yarn) and ``ops.yarn_mscale`` against a
    line-by-line transcription of transformers' ``_compute_yarn_parameters``
    and ``yarn_get_mscale`` at GigaChat3.1's rotary settings; unknown
    types still raise and llama3 is untouched."""
    dim, base = 64, 100_000.0
    sc = {**YARN, "original_max_position_embeddings": 4096}
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]

    def find_correction_dim(num_rotations, dim, base, max_pos):
        return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    low = math.floor(find_correction_dim(32, dim, base, orig))
    high = math.ceil(find_correction_dim(1, dim, base, orig))
    low, high = max(low, 0), min(high, dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2) / dim)
    extra = 1 - ramp
    want = (1 / (factor * pos_freqs)) * (1 - extra) + (1 / pos_freqs) * extra
    freqs = 1.0 / (base ** (jnp.arange(0, dim // 2, dtype=jnp.float32)
                            / (dim // 2)))
    got = np.asarray(ops.scale_rope_freqs(freqs, sc, base))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert (low, high) == (8, 19)
    np.testing.assert_allclose(got[:low], 1 / pos_freqs[:low], rtol=1e-5)
    np.testing.assert_allclose(got[high:], 1 / (factor * pos_freqs[high:]),
                               rtol=1e-5)
    m = 0.1 * 1 * math.log(factor) + 1.0
    assert ops.yarn_mscale(64, 1) == pytest.approx(m)
    assert ops.yarn_mscale(1.0, 1) == 1.0
    cfg = _cfg({**SIZES, "rope_scaling": sc})
    assert cfg.softmax_scale == pytest.approx(24 ** -0.5 * m * m)
    assert cfg.rope_factor == 1.0
    # the benchmark's reference computes its own, and agrees
    np.testing.assert_allclose(reference.yarn_inv_freq(dim, base, sc), want,
                               rtol=1e-12)
    with pytest.raises(ValueError, match="unsupported rope_scaling"):
        ops.scale_rope_freqs(freqs, {"rope_type": "dynamic", "factor": 2.0},
                             base)
    llama3 = {"rope_type": "llama3", "factor": 8.0, "low_freq_factor": 1.0,
              "high_freq_factor": 4.0,
              "original_max_position_embeddings": 8192}
    np.testing.assert_array_equal(ops.scale_rope_freqs(freqs, llama3),
                                  ops.scale_rope_freqs(freqs, llama3, base))


# ---------------------------------------------------------- serving stack
def _gen(model, **kw):
    cfg, params = model
    return Generator(params, cfg, batch_slots=2, max_seq=MAX_SEQ, chunk=2,
                     **kw)


def test_generator_serves_it_and_counts_the_routing(model):
    """Through ``Generator`` (warmed, greedy): a slot reused after a longer
    request decodes as a fresh generator does, every served token is the
    reference's best within the tolerance, and ``pool_stats()`` reads the
    routing counters and the latent cache's bytes."""
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
    routed = stats["expert_pairs_routed"] - before["expert_pairs_routed"]
    held = stats["expert_pairs_held"] - before["expert_pairs_held"]
    k, layers = cfg.num_experts_per_tok, cfg.n_expert_layers
    decoded = gen.steps * 2
    assert routed >= (150 + 9) * k * layers
    assert routed <= (150 + 9 + decoded) * k * layers
    assert 0.05 < held / routed < 0.6          # 4 of 16 experts held
    assert 0 < stats["experts_touched"] <= stats["expert_pairs_held"]
    latent = dv.init_cache(cfg, 2, MAX_SEQ)["latent"].nbytes
    assert stats["latent_cache_bytes"] == stats["kv_cache_bytes"] == latent
    branches = ops.kernel_branches()
    assert any(key.startswith("latent_decode_attention[")
               for key in branches)


@pytest.mark.parametrize("kwargs,what", [
    ({"page_size": 16}, "page_size"),
    ({"sp": "ring"}, "sp"),
    ({"spec_k": 4}, "spec_k"),
    ({"kv_bits": 8}, "kv_bits"),
    ({"prefill_chunk": 64}, "prefill_chunk"),
    ({"shard_cache": True}, "mesh"),
])
def test_register_llm_refuses_what_the_family_lacks(model, kwargs, what):
    """Every layout the family is not served in yet is refused where it is
    registered, with its reason from ``UNSUPPORTED``; nothing falls back."""
    cfg, params = model
    kwargs = dict(kwargs)
    if "kv_bits" in kwargs:
        cfg = _cfg(kv_bits=kwargs.pop("kv_bits"))
    with pytest.raises(ValueError) as err:
        MLDatasource().register_llm("chat", params, cfg, batch_slots=2,
                                    max_seq=MAX_SEQ, warmup=False, **kwargs)
    assert dv.UNSUPPORTED[what] in str(err.value)
    assert f"not served with {what}" in str(err.value)


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


@pytest.mark.parametrize("bad,word", [
    ({"topk_method": "greedy"}, "noaux_tc"),
    ({"first_k_dense_replace": 3}, "no expert layer"),
    ({"v_head_dim": 16}, "d_v"),
])
def test_configuration_refuses_what_is_not_written(bad, word):
    with pytest.raises(ValueError, match=word):
        _cfg({**SIZES, **bad})
