#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide, run by hand:

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_real.py

compiles, for a v5e that is described and not attached, the step programs
of every cell of ``BENCHMARK.json`` at its real sizes (depth 16) and the
benchmark's reference, and prints what each needs.
Nothing runs; a compile that passes is not a chip run.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO_ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import reference
    from gofr_tpu import ops
    from gofr_tpu.models import llama

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    ops._on_tpu = lambda: True  # the dispatcher asks the platform; steer it here

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def sds(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def report(name, compiled):
        mem = compiled.memory_analysis()
        print(json.dumps({
            "program": name,
            "argument_gb": mem.argument_size_in_bytes / 1e9,
            "temp_gb": mem.temp_size_in_bytes / 1e9,
            "output_gb": mem.output_size_in_bytes / 1e9,
            "alias_gb": mem.alias_size_in_bytes / 1e9,
            "kernel": "tpu_custom_call" in compiled.as_text()}), flush=True)

    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for name, serve_name in ((w["config"], w["name"])
                             for w in bench["workloads"]):
        with open(os.path.join(REPO_ROOT, files[name])) as f:
            sizes = json.load(f)
        with open(os.path.join(REPO_ROOT, "benchmark", "serve",
                               serve_name + ".json")) as f:
            kw = json.load(f)["register_llm"]
        cfg = llama.LlamaConfig(
            vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
            n_layers=sizes["num_hidden_layers"],
            n_heads=sizes["num_attention_heads"],
            n_kv_heads=sizes["num_key_value_heads"],
            ffn_dim=sizes["intermediate_size"],
            rope_theta=sizes["rope_theta"], norm_eps=sizes["rms_norm_eps"])
        params = on_chip(jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.PRNGKey(0))))
        B, S = kw["batch_slots"], kw["max_seq"]
        if kw.get("page_size"):
            ps = kw["page_size"]
            p_max = S // ps
            cache = on_chip(jax.eval_shape(
                lambda: llama.init_paged_cache(cfg, B, 1 + B * p_max, ps)))
            report(f"{name} paged_decode_step B={B}", jax.jit(
                lambda p, t, c, tab: llama.paged_decode_step(p, t, c, tab, cfg)
            ).lower(params, sds(jnp.int32, B), cache,
                    sds(jnp.int32, B, p_max)).compile())
        else:
            cache = on_chip(jax.eval_shape(
                lambda: llama.init_cache(cfg, B, S)))
            report(f"{name} decode_step B={B}", jax.jit(
                lambda p, t, c: llama.decode_step(p, t, c, cfg)
            ).lower(params, sds(jnp.int32, B), cache).compile())
            report(f"{name} prefill_into 1x2048", jax.jit(
                lambda p, t, l, c: llama.prefill_into(p, t, l, cfg, c, 0)
            ).lower(params, sds(jnp.int32, 1, 2048), sds(jnp.int32, 1),
                    cache).compile())
        fn = reference._program(
            tuple((k, sizes[k]) for k in reference._SHAPE_KEYS), 2048, 256,
            False)
        report(f"{name} reference T=2048", fn.lower(
            params, sds(jnp.int32, 2048), sds(jnp.int32, 256)).compile())
    return 0


if __name__ == "__main__":
    sys.exit(main())
