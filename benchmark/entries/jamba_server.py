"""The system under test for the Jamba configuration: the llama example's
own app (``build_app`` -> ``register_llm("chat", ...)``) given a
``JambaConfig``, behind its real gRPC socket. Built on ``llama_server`` as
``qwen3_next_server`` is, whose two host measures (the interpreter's
switch interval, the heap that exists before the app starts frozen out of
the collector's reach; both argued there) it takes over as they are: this
entry's cell holds 128 streams and a plan of 4,096 prompts in the load
generator's process, as that entry's does.
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
# ``state``
STATE_KEYS = ("state_rows_swept", "state_rows_live",
              "recurrent_state_bytes", "kv_cache_bytes")

CONFIG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
               "num_hidden_layers", "num_attention_heads",
               "num_key_value_heads", "head_dim", "attn_layer_period",
               "attn_layer_offset", "mamba_d_state", "mamba_d_conv",
               "mamba_expand", "mamba_dt_rank", "num_experts",
               "rms_norm_eps", "max_position_embeddings")


def config_of(sizes: dict, dtype=None):
    """The program's configuration from a configuration file."""
    import jax.numpy as jnp

    from gofr_tpu.models.jamba import JambaConfig

    for key, fixed in (("mamba_conv_bias", True), ("mamba_proj_bias", False),
                       ("tie_word_embeddings", True)):
        if sizes.get(key, fixed) is not fixed:
            raise ValueError(f"the program is written for {key} = {fixed}")
    return JambaConfig(dtype=jnp.dtype(dtype or sizes["torch_dtype"]),
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
        out["state"] = {key: sum(s[key] for s in stats) for key in STATE_KEYS}
        return out


def build(params, sizes: dict, serve: dict, devices) -> System:
    if int(serve["chips"]) != 1:
        raise ValueError("the Jamba entry serves one chip")
    return System(params, sizes, serve, devices)
