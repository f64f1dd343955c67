"""The system under test: the llama example's app, in-process, with
``register_llm("chat", ...)`` behind its real gRPC socket. Everything the
benchmark touches of the program is in this file: how it is built from a
configuration and a serve file, and where its spans and counters are read.
(``_served`` and the port set-up are copied from ``chip_smoke.py`` and
``bench/common.py``.)
"""

from __future__ import annotations

import os
import socket


# Two settings of the program that every cell behind this entry runs with.
# GOFR_ML_JOURNEY: the ring of finished request journeys (512 by default)
# holds a whole window's requests; the traced run reads them all after the
# window. GOFR_ML_AUTOPROF (on by default): the program's anomaly-triggered
# profiler is off in traced and untraced runs alike, so that both time the
# same program. jax runs one profile at a time, so its capture would make
# the traced run's own start_trace fail; and it fires when the step time
# doubles against the last 64 dispatches, which in a process that is 30 s
# old is where load begins: a one-second capture at a point that moves from
# run to run, once in a window, where a server that has run for hours
# captures once per anomaly and cooldown (120 s). PERF.md, section 4.
PROGRAM_ENV = {"GOFR_ML_JOURNEY": "8192", "GOFR_ML_AUTOPROF": "0"}


def _free_ports(n: int) -> list:
    """``n`` different free ports below the range the kernel hands to
    outgoing connections: one of those (a client's own, or one in
    TIME_WAIT) can take a port between this look and the app's bind. Each
    is tried on both families, as the app's servers bind ``[::]`` and
    ``0.0.0.0``, and all are held until the last is found, so no two of
    the app's servers get the same."""
    import random

    held, ports = [], []
    try:
        for _ in range(400):
            if len(ports) == n:
                return ports
            port = random.randint(20000, 32000)
            socks = []
            try:
                for family, host in ((socket.AF_INET6, "::"),
                                     (socket.AF_INET, "0.0.0.0")):
                    s = socket.socket(family)
                    socks.append(s)
                    if family == socket.AF_INET6:
                        s.setsockopt(socket.IPPROTO_IPV6,
                                     socket.IPV6_V6ONLY, 1)
                    s.bind((host, port))
            except OSError:
                for s in socks:
                    s.close()
                continue
            held.extend(socks)
            ports.append(port)
    finally:
        for s in held:
            s.close()
    raise OSError(f"found {len(ports)} of {n} free ports between 20000 "
                  f"and 32000")


class System:
    def __init__(self, params, sizes: dict, serve: dict, devices) -> None:
        import jax.numpy as jnp

        http, grpc, metrics = _free_ports(3)
        os.environ.update({"HTTP_PORT": str(http), "GRPC_PORT": str(grpc),
                           "METRICS_PORT": str(metrics),
                           "LOG_LEVEL": os.environ.get("LOG_LEVEL", "ERROR")})
        os.environ.update(PROGRAM_ENV)

        from examples.llama_server.main import build_app
        from gofr_tpu.models import llama

        cfg = llama.LlamaConfig(
            vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
            n_layers=sizes["num_hidden_layers"],
            n_heads=sizes["num_attention_heads"],
            n_kv_heads=sizes["num_key_value_heads"],
            ffn_dim=sizes["intermediate_size"],
            max_seq_len=sizes["max_position_embeddings"],
            rope_theta=sizes["rope_theta"], norm_eps=sizes["rms_norm_eps"],
            dtype=jnp.dtype(sizes["torch_dtype"]))
        if cfg.head_dim != sizes["head_dim"]:
            raise ValueError(f"the program derives head_dim {cfg.head_dim}, "
                             f"the configuration states {sizes['head_dim']}")
        kwargs = dict(serve["register_llm"])
        chips = int(serve["chips"])
        if chips > 1:
            kwargs.update(replicas=chips, devices=list(devices)[:chips])
        self.batch_slots = int(kwargs["batch_slots"])
        self.app = build_app(params, cfg, **kwargs)
        llm = self.app.container.ml.llm("chat")
        self.pool = llm if hasattr(llm, "replicas") else None
        self.cores = list(getattr(llm, "replicas", [llm]))

    @property
    def grpc_port(self) -> int:
        return self.app.grpc_port

    async def start(self) -> None:
        await self.app.start()

    async def shutdown(self) -> None:
        """Stops the app and frees every replica's cache, so that what
        runs next on the chip finds the memory."""
        import jax

        await self.app.shutdown()
        for core in self.cores:
            for leaf in jax.tree.leaves(core.gen.cache):
                leaf.delete()
        if not all(core.closed_cleanly for core in self.cores):
            raise RuntimeError("a serving thread outlived shutdown")

    # ---------------------------------------------------------- read-outs
    def counters(self) -> dict:
        """Monotonic counts, read before and after a window."""
        from gofr_tpu import ops

        prefix = {"hits": 0, "misses": 0, "prefill_tokens_saved": 0}
        for core in self.cores:
            if core.prefix_cache is not None:
                snap = core.prefix_cache.snapshot()
                for key in prefix:
                    prefix[key] += snap[key]
        out = {
            "prefix": prefix,
            "served": sum(core.served for core in self.cores),
            "prefill_segments": sum(
                getattr(core.gen, "prefill_segments_run", 0)
                for core in self.cores),
            "kernel_branches": ops.kernel_branches(),
        }
        if self.pool is not None:
            out["routed"] = self.pool.routing_snapshot()["routed"]
        return out

    def journeys(self) -> list:
        """Every retained request journey: ``t0`` (perf_counter at
        enqueue) and its marks. Request ids are ``r1, r2, ...``."""
        from gofr_tpu.ml.journey import journey_log

        log = journey_log()
        out, misses, i = [], 0, 0
        if log is None:
            return out
        while misses < 4096:
            i += 1
            j = log.get(f"r{i}")
            if j is None:
                misses += 1
                continue
            misses = 0
            snap = j.snapshot()
            snap["t0"] = j.t0
            out.append(snap)
        return out


def build(params, sizes: dict, serve: dict, devices) -> System:
    return System(params, sizes, serve, devices)
