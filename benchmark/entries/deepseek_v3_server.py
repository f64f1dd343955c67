"""The system under test for the DeepSeek-V3 configuration (GigaChat3.1):
the llama example's own app (``build_app`` -> ``register_llm("chat",
...)``) given a ``DeepseekV3Config``, behind its real gRPC socket. Built
on ``llama_server`` as ``jamba_server`` is, with ``qwen3_next_server``'s
two host measures (the interpreter's switch interval, the heap that exists
before the app starts frozen out of the collector's reach; both argued
there): this entry's cell holds 64 streams and a plan of 2,048 prompts of
some 2,000 tokens in the load generator's process.
"""

from __future__ import annotations

import os
import sys

from benchmark import harness

_here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_base = harness.load_module(_here, os.path.join("entries", "llama_server"))
_hybrid = harness.load_module(
    _here, os.path.join("entries", "qwen3_next_server"))

# the program's counters and gauges the per-layer metrics read, under
# ``moe``
MOE_KEYS = ("expert_pairs_routed", "expert_pairs_held", "experts_touched",
            "latent_cache_bytes", "kv_cache_bytes")

CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
               "moe_intermediate_size", "num_hidden_layers",
               "num_attention_heads", "q_lora_rank", "kv_lora_rank",
               "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
               "n_shared_experts", "num_experts_per_tok", "n_group",
               "topk_group", "routed_scaling_factor", "norm_topk_prob",
               "topk_method", "scoring_func", "first_k_dense_replace",
               "rope_theta", "rope_scaling", "rms_norm_eps",
               "max_position_embeddings")


def config_of(sizes: dict, dtype=None):
    """The program's configuration from a configuration file: the router
    keeps ``router_width`` outputs, the first ``n_routed_experts`` are
    held."""
    import jax.numpy as jnp

    from gofr_tpu.models.deepseek_v3 import DeepseekV3Config

    for key, fixed in (("attention_bias", False),
                       ("tie_word_embeddings", False),
                       ("hidden_act", "silu"),
                       ("num_nextn_predict_layers", 0)):
        if sizes.get(key, fixed) != fixed:
            raise ValueError(f"the program is written for {key} = {fixed}")
    return DeepseekV3Config(
        n_routed_experts=sizes["router_width"],
        held=(0, sizes["n_routed_experts"]),
        dtype=jnp.dtype(dtype or sizes["torch_dtype"]),
        **{key: sizes[key] for key in CONFIG_KEYS})


class System(_hybrid.System):
    """``qwen3_next_server.System``'s ``start`` and ``shutdown`` (the
    frozen heap) around this family's configuration and counters."""

    def __init__(self, params, sizes: dict, serve: dict, devices) -> None:
        http, grpc, metrics = _base._free_ports(3)
        os.environ.update({"HTTP_PORT": str(http), "GRPC_PORT": str(grpc),
                           "METRICS_PORT": str(metrics),
                           "LOG_LEVEL": os.environ.get("LOG_LEVEL", "ERROR")})
        os.environ.update(_base.PROGRAM_ENV)
        sys.setswitchinterval(_hybrid.SWITCH_INTERVAL_S)

        from examples.llama_server.main import build_app

        kwargs = dict(serve["register_llm"])
        self.batch_slots = int(kwargs["batch_slots"])
        self.app = build_app(params, config_of(sizes), **kwargs)
        self.pool = None
        self.cores = [self.app.container.ml.llm("chat")]

    def counters(self) -> dict:
        out = _base.System.counters(self)
        stats = [core.gen.pool_stats() for core in self.cores]
        out["moe"] = {key: sum(s[key] for s in stats) for key in MOE_KEYS}
        return out


def build(params, sizes: dict, serve: dict, devices) -> System:
    if int(serve["chips"]) != 1:
        raise ValueError("the DeepSeek-V3 entry serves one chip's share")
    return System(params, sizes, serve, devices)
