"""Shared harness for the five-config BASELINE suite (BASELINE.md table).

Each config module boots its example app in-process on free ports (real TCP
sockets — the analogue of the reference's boot-and-curl integration tests,
examples/http-server/main_test.go:25-66), drives it with a concurrent load
generator, and prints ONE JSON line in the same shape as bench.py.
"""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time
from typing import Any, Awaitable, Callable

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gofr_tpu.testutil import get_free_port  # noqa: E402


def configure_free_ports() -> dict[str, int]:
    """Point HTTP/gRPC/metrics at free ports via env before app construction."""
    ports = {
        "HTTP_PORT": get_free_port(),
        "GRPC_PORT": get_free_port(),
        "METRICS_PORT": get_free_port(),
    }
    for key, val in ports.items():
        os.environ[key] = str(val)
    return ports


async def boot(app) -> None:
    await app.start()


def percentile(samples: list[float], pct: float) -> float:
    if not samples:
        return float("nan")
    qs = statistics.quantiles(samples, n=100, method="inclusive")
    idx = min(98, max(0, int(pct) - 1))
    return qs[idx] if len(samples) > 1 else samples[0]


async def closed_loop(
    n_workers: int,
    duration_s: float,
    once: Callable[[], Awaitable[Any]],
    warmup_s: float = 0.5,
) -> tuple[list[float], int]:
    """Closed-loop load: n workers each issuing `once()` back-to-back for
    duration_s after a warmup. Returns (latencies_s, completed_count)."""
    latencies: list[float] = []
    stop = time.perf_counter() + warmup_s + duration_s
    measure_from = time.perf_counter() + warmup_s

    async def worker() -> int:
        done = 0
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            await once()
            t1 = time.perf_counter()
            if t0 >= measure_from:
                latencies.append(t1 - t0)
                done += 1
        return done

    counts = await asyncio.gather(*[worker() for _ in range(n_workers)])
    return latencies, sum(counts)


def emit(metric: str, value: float, unit: str, target: float | None,
         detail: dict) -> None:
    line = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / target, 3) if target else None,
        "detail": detail,
    }
    print(json.dumps(line), flush=True)


def run(main_coro: Awaitable[None]) -> None:
    asyncio.run(main_coro)


def dispatch_rtt_ms(samples: int = 12) -> float:
    """p50 of an empty dispatch + device->host fetch round trip: the floor
    the host's path to the device puts under every wire latency. Shared by
    the config benches so each run records its own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: x + 1)
    x = jnp.zeros((8,), jnp.float32)
    np.asarray(f(x))  # compile outside the timed window
    times = []
    for _ in range(samples):
        t0 = time.perf_counter()
        np.asarray(f(x))
        times.append(time.perf_counter() - t0)
    return percentile(times, 50) * 1e3
