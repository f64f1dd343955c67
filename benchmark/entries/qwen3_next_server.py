"""The system under test for the Qwen3-Next configuration: the llama
example's own app (``build_app`` -> ``register_llm("chat", ...)``) given a
``Qwen3NextConfig``, behind its real gRPC socket. Everything but the
configuration class and the routing counters is ``llama_server``'s.
"""

from __future__ import annotations

import gc
import os
import sys

from benchmark import harness

_base = harness.load_module(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    os.path.join("entries", "llama_server"))

# The interpreter's thread switch interval for every cell behind this
# entry (5 ms by default). The load generator's event loop shares this
# process, and with it the interpreter's lock, with the serving thread: at
# 128 streams it parses and stamps some 250 frames a dispatch, and the
# serving thread, which has to launch the next program the moment one
# drains, waited up to 5 ms for the lock each time it asked. A deployment's
# clients are other processes. PERF.md, section 6 (PR 30).
SWITCH_INTERVAL_S = 0.0005

# the program's counters the per-layer metrics read, under ``moe``
MOE_KEYS = ("expert_pairs_routed", "expert_pairs_held", "experts_touched",
            "recurrent_state_bytes", "kv_cache_bytes")


def config_of(sizes: dict, dtype=None):
    """The program's configuration from a configuration file: the router
    keeps ``router_width`` outputs, the first ``num_experts`` are held."""
    import jax.numpy as jnp

    from gofr_tpu.models.qwen3_next import Qwen3NextConfig

    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "linear_num_key_heads", "linear_num_value_heads",
            "linear_key_head_dim", "linear_value_head_dim",
            "linear_conv_kernel_dim", "moe_intermediate_size",
            "shared_expert_intermediate_size", "num_experts_per_tok",
            "full_attention_interval", "partial_rotary_factor", "rope_theta",
            "rms_norm_eps", "max_position_embeddings")
    return Qwen3NextConfig(
        num_experts=sizes["router_width"], held=(0, sizes["num_experts"]),
        dtype=jnp.dtype(dtype or sizes["torch_dtype"]),
        **{key: sizes[key] for key in keys})


class System(_base.System):
    def __init__(self, params, sizes: dict, serve: dict, devices) -> None:
        http, grpc, metrics = _base._free_ports(3)
        os.environ.update({"HTTP_PORT": str(http), "GRPC_PORT": str(grpc),
                           "METRICS_PORT": str(metrics),
                           "LOG_LEVEL": os.environ.get("LOG_LEVEL", "ERROR")})
        os.environ.update(_base.PROGRAM_ENV)
        sys.setswitchinterval(SWITCH_INTERVAL_S)

        from examples.llama_server.main import build_app

        kwargs = dict(serve["register_llm"])
        self.batch_slots = int(kwargs["batch_slots"])
        self.app = build_app(params, config_of(sizes), **kwargs)
        self.pool = None
        self.cores = [self.app.container.ml.llm("chat")]

    async def start(self) -> None:
        """The plan the load generator sends from lives in this process:
        at a cycle of 128 some 16,000 prompts, ten million token ids in
        lists, which every full collection walks with the interpreter's
        lock held: 170-200 ms each by the window's second half, a whole
        decode dispatch (my chip run, PR 30, call 5; 8-15 ms with the heap
        frozen). A deployment's clients are other processes, so what
        exists before the app starts is put out of the collector's
        reach."""
        gc.collect()
        gc.freeze()
        await super().start()

    async def shutdown(self) -> None:
        await super().shutdown()
        gc.unfreeze()

    def counters(self) -> dict:
        out = super().counters()
        stats = [core.gen.pool_stats() for core in self.cores]
        out["moe"] = {key: sum(s[key] for s in stats) for key in MOE_KEYS}
        return out


def build(params, sizes: dict, serve: dict, devices) -> System:
    if int(serve["chips"]) != 1:
        raise ValueError("the Qwen3-Next entry serves one chip's share")
    return System(params, sizes, serve, devices)
