"""The serving path's kernels compiled for a TPU v5e that is described, not
attached (on-chip-measurement guide, section 2): Llama-3-8B head shapes
(H=32, KV=8, D=128), 32 slots, a 1024-token cache. Nothing runs — these
catch what interpret mode cannot: a slice off the tiling, too much VMEM, a
program that does not fit the chip. Skipped where the TPU compiler is not
installed.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from gofr_tpu import ops
from gofr_tpu.models import llama, qwen3_next

# The kernel modules are imported inside the tests: importing the submodule
# ``ops.decode_attention`` rebinds the package attribute of that name from
# the function to the module, which must not happen while pytest collects.

L, B, S, H, KV, D = 16, 32, 1024, 32, 8, 128


@pytest.fixture(scope="module")
def chip():
    """A ``SingleDeviceSharding`` on one chip of a described v5e:2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs land in /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:
        pytest.skip(f"no TPU compiler to describe a v5e to: {exc}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: the next one would warn
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _shape(chip, dtype, *shape):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _on_chip(chip, tree):
    """The shapes of ``tree``'s arrays, placed on the described chip."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree)


def _cache_shapes(chip, layout):
    if layout == "bf16-stacked":
        kv = _shape(chip, jnp.bfloat16, L, B, S, KV, D)
        return kv, None
    kv = _shape(chip, jnp.int8, L, B, S, KV * D)
    return kv, _shape(chip, jnp.bfloat16, L, B, KV, S)


@pytest.mark.parametrize("layout", ["bf16-stacked", "int8-flat"])
def test_decode_kernel_compiles(chip, layout):
    from gofr_tpu.ops.decode_attention import gqa_decode_attention_tpu

    kv, scale = _cache_shapes(chip, layout)

    def step(q, k, v, kv_len, layer, *scales):
        k_scale, v_scale = scales or (None, None)
        return gqa_decode_attention_tpu(q, k, v, kv_len, layer=layer,
                                        k_scale=k_scale, v_scale=v_scale)

    args = [_shape(chip, jnp.bfloat16, B, 1, H, D), kv, kv,
            _shape(chip, jnp.int32, B), _shape(chip, jnp.int32)]
    if scale is not None:
        args += [scale, scale]
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,s_max,heads,kv_heads,head_dim", [
    (32, 2048, 32, 8, 128),    # mistral7b-batch
    (128, 4096, 16, 2, 256),   # qwen3next-longanswer's attention layers
    (8, 2048, 32, 32, 128),    # DeepSeek's widths on the dense layout
], ids=["mistral-32x2048", "qwen3next-128x4096", "deepseek-8x2048"])
def test_dense_kernel_compiles_at_cell_widths(chip, rows, s_max, heads,
                                              kv_heads, head_dim):
    """The full-precision dense kernel at the cells' own widths, the cache
    stacked and the layer traced: it compiles, its block fits VMEM (the
    compiler refuses one that does not), and NO copy of the cache is made
    on the way in. The one-matrix body sees ``[S, KV, D]`` as
    ``[S * KV, D]``, which is the same bytes only where the KV heads are
    whole sublane tiles: at 2 KV heads XLA answered that reshape with a
    relayout of both planes (2 GB of temporaries at the hybrid cell's
    size), which is why such a cache keeps the per-head body."""
    from gofr_tpu.ops.decode_attention import (
        gqa_decode_attention_tpu,
        row_tiling,
    )

    tiling = row_tiling(s_max, kv_heads, head_dim, 2)
    assert (tiling is not None) == (kv_heads % 8 == 0)
    if tiling is not None:
        assert 4 * tiling[1] * head_dim * 2 <= 4 * 2**20
    kv = _shape(chip, jnp.bfloat16, 2, rows, s_max, kv_heads, head_dim)
    compiled = jax.jit(
        lambda q, k, v, kv_len, layer: gqa_decode_attention_tpu(
            q, k, v, kv_len, layer=layer)
    ).lower(_shape(chip, jnp.bfloat16, rows, 1, heads, head_dim), kv, kv,
            _shape(chip, jnp.int32, rows), _shape(chip, jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "gqa_decode_attention_tpu" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2**20


def test_flash_kernel_compiles(chip):
    from gofr_tpu.ops.flash_attention import flash_attention_tpu

    qkv = _shape(chip, jnp.bfloat16, 1, S, H, D)
    compiled = jax.jit(
        lambda q, k, v: flash_attention_tpu(q, k, v, causal=True)
    ).lower(qkv, qkv, qkv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_compiles_with_kernel(chip, monkeypatch):
    """One whole ``llama.decode_step`` at 8B widths (depth 2): the program
    the server dispatches per token carries the Pallas kernel and fits."""
    # under JAX_PLATFORMS=cpu the dispatcher would take its XLA branch: the
    # test steers it, the program has no option for it
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = llama.llama3_8b(n_layers=2)

    params = _on_chip(chip, jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _on_chip(chip, jax.eval_shape(lambda: llama.init_cache(cfg, B, S)))
    compiled = jax.jit(
        lambda p, t, c: llama.decode_step(p, t, c, cfg)
    ).lower(params, _shape(chip, jnp.int32, B), cache).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 16 * 2**30)


def _hybrid(chip):
    """Qwen3-Next at the published widths, two periods, 16 held experts a
    layer: the configuration, and its parameters' and cache's shapes."""
    cfg = qwen3_next.Qwen3NextConfig(vocab_size=4096, num_hidden_layers=8,
                                     held=(0, 16))
    params = _on_chip(chip, jax.eval_shape(
        lambda: qwen3_next.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _on_chip(chip, jax.eval_shape(
        lambda: qwen3_next.init_cache(cfg, B, S)))
    return cfg, params, cache


def test_hybrid_decode_step_compiles_with_kernel_and_one_loop(chip,
                                                              monkeypatch):
    """``qwen3_next.decode_step`` at the published widths (two periods, 16
    held experts a layer): the attention layers take the Pallas kernel at
    head size 256 with a group of 8; the step is ONE loop (what the
    benchmark counts decode steps by); and no layer's expert weights are
    copied out of their stacks (a dynamic slice does not fuse into the
    grouped product's kernel, nor a static slice of a period's slab into a
    matmul: the temporaries were a period's weights wide, 3.8 GB at the
    benchmark's size, until the tree was laid out against it)."""
    import re

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg, params, cache = _hybrid(chip)
    compiled = jax.jit(
        lambda p, t, c: qwen3_next.decode_step(p, t, c, cfg),
        donate_argnums=(2,),
    ).lower(params, _shape(chip, jnp.int32, B), cache).compile()
    text = compiled.as_text()
    assert "gqa_decode_attention_tpu" in text
    assert "grouped_matmul_tpu" in text and "ragged-dot" not in text
    assert len(re.findall(r" while\(", text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_hybrid_prefill_compiles_with_the_grouped_kernel(chip, monkeypatch):
    """``qwen3_next.prefill_into`` for a 512-token prompt at the published
    widths (two periods, 16 held experts a layer): the expert products are
    the Pallas grouped matmul, walking the stack of every layer's experts
    where it lies (no ``ragged-dot``, and the temporaries, 74 MiB of
    activations, are smaller than one layer's slice of that stack)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg, params, cache = _hybrid(chip)
    compiled = jax.jit(
        lambda p, t, n, c, s: qwen3_next.prefill_into(p, t, n, cfg, c, s),
        donate_argnums=(3,),
    ).lower(params, _shape(chip, jnp.int32, 1, 512),
            _shape(chip, jnp.int32, 1), cache, _shape(chip, jnp.int32)).compile()
    text = compiled.as_text()
    assert "grouped_matmul_tpu" in text and "ragged-dot" not in text
    layer_slice = 16 * 3 * cfg.hidden_size * cfg.moe_intermediate_size * 2
    assert compiled.memory_analysis().temp_size_in_bytes < layer_slice


@pytest.mark.parametrize("rows,kv_heads,vocab,ffn", [
    (8, 32, 102400, 11008),   # deepseek7b-sessions: 8 slots, MHA
    (32, 8, 32768, 14336),    # mistral7b-x4-sessions' replica: 32 slots, GQA
], ids=["deepseek-8rows", "mistral-32rows"])
def test_paged_decode_step_compiles_with_kernel_and_no_pool_copy(
        chip, monkeypatch, rows, kv_heads, vocab, ffn):
    """``llama.paged_decode_step`` (the body of ``jit_paged_chunk_fn``) at
    the paged cells' own sizes, depth 16, a pool of ``rows`` x 128 pages
    of 16: the kernel that walks the page table is in the program and fits
    VMEM (the compiler refuses one that does not); the step is ONE loop,
    the scan over layers (what ``decode_step_ms`` counts steps by: a page
    walk written as an HLO loop would make every layer a step); and the
    temporaries hold no copy of a layer's slab of the pool (134 MB a plane
    at either size: a slice that feeds a custom call is copied, so the
    kernel takes the stacked pool and the layer's index)."""
    import re

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = llama.LlamaConfig(vocab_size=vocab, dim=4096, n_layers=16,
                            n_heads=32, n_kv_heads=kv_heads, ffn_dim=ffn,
                            max_seq_len=2048)
    page_s, p_max = 16, 128

    params = _on_chip(chip, jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _on_chip(chip, jax.eval_shape(
        lambda: llama.init_paged_cache(cfg, rows, rows * p_max + 1, page_s)))
    slab = (rows * p_max + 1) * page_s * kv_heads * cfg.head_dim * 2
    compiled = jax.jit(
        lambda p, t, c, tb: llama.paged_decode_step(p, t, c, tb, cfg),
        donate_argnums=(2,),
    ).lower(params, _shape(chip, jnp.int32, rows), cache,
            _shape(chip, jnp.int32, rows, p_max)).compile()
    text = compiled.as_text()
    assert "paged_decode_attention_tpu" in text
    assert len(re.findall(r" while\(", text)) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < slab // 2


def _jamba_shapes(chip, rows, s_max):
    from gofr_tpu.models import jamba

    cfg = jamba.JambaConfig()   # AI21-Jamba2-3B as published, 28 layers
    params = _on_chip(chip, jax.eval_shape(
        lambda: jamba.init_params(cfg, jax.random.PRNGKey(0))))
    cache = _on_chip(chip, jax.eval_shape(
        lambda: jamba.init_cache(cfg, rows, s_max)))
    return jamba, cfg, params, cache


@pytest.mark.parametrize("rows,args_bytes", [(128, (7.5e9, 7.6e9)),
                                             (256, (8.9e9, 9.1e9))])
def test_jamba_decode_step_compiles_with_one_loop_and_no_state_copy(
        chip, monkeypatch, rows, args_bytes):
    """``jamba.decode_step`` at the published widths, uncut, at the
    registered cell's 128 rows of 2,048 and at the 256 ISSUE 34 asked for
    first: the attention layers take the Pallas kernel on the flat
    one-KV-head cache (20 query heads padded to 24 rows) and the Mamba
    layers the selective-state kernel on the whole state stack; the step
    is ONE loop, the scan over 28 layers whose body branches on the
    layer's kind (what the benchmark counts decode steps by); the whole
    fits one chip (6.06 GB of weights beside the slot state: the flat
    cache and the state's layout cost their own bytes); and neither the
    kernel nor a branch copies the state or the windows (a layer's slice
    handed to the kernel, or the stack handed through a branch untouched,
    would be: 2.4 GB at each attention layer of a 256-row step until that
    branch wrote one element back)."""
    import re

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jamba, cfg, params, cache = _jamba_shapes(chip, rows, 2048)
    compiled = jax.jit(
        lambda p, t, c: jamba.decode_step(p, t, c, cfg),
        donate_argnums=(2,),
    ).lower(params, _shape(chip, jnp.int32, rows), cache).compile()
    text = compiled.as_text()
    assert "gqa_decode_attention_tpu" in text
    assert "ssm_update_tpu" in text
    assert len(re.findall(r" while\(", text)) == 1
    memory = compiled.memory_analysis()
    assert args_bytes[0] < memory.argument_size_in_bytes < args_bytes[1]
    # the logits and the step's activations; a copied state is 1.1-2.2 GB
    assert memory.temp_size_in_bytes < 128 * 2**20
    assert not re.findall(rf"f32\[26,{rows},16,5120\]\S* copy\(", text)
    assert not re.findall(rf"bf16\[26,3,{rows},5120\]\S* copy\(", text)


@pytest.mark.parametrize("tokens", [128, 2048])
def test_jamba_prefill_compiles(chip, monkeypatch, tokens):
    """The one-row prefill programs at the ladder's ends: the chunked
    selective scan's intermediates stay a chunk wide (a 2,048-token prompt
    would hold 671 MB a layer as ``[T, d_inner, N]``)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jamba, cfg, params, cache = _jamba_shapes(chip, 256, 2048)
    compiled = jax.jit(
        lambda p, t, n, c, s: jamba.prefill_into(p, t, n, cfg, c, s),
        donate_argnums=(3,),
    ).lower(params, _shape(chip, jnp.int32, 1, tokens),
            _shape(chip, jnp.int32, 1), cache,
            _shape(chip, jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 640 * 2**20
    if tokens >= 128:
        assert "tpu_custom_call" in compiled.as_text()   # flash attention
