"""TPU model execution engine.

The green-field core of the framework (BASELINE.json north star): execute
JAX-compiled models behind GoFr-style handlers. The reference has no ML
functionality; the closest structural analogue is a datasource driver —
connect/health/metrics/logging (reference container/datasources.go provider
protocol) — which is exactly how the engine presents itself to the container.

Design (TPU-first):
- the model is a pure ``apply(params, *inputs)`` function, jitted once per
  input-shape bucket; weights live on device permanently (HBM-resident).
- a single dedicated executor thread owns device dispatch, so the asyncio
  event loop never blocks on compilation or synchronous transfers; results
  come back through futures.
- shape bucketing: inputs pad up to the nearest registered bucket to bound
  the number of XLA compilations (dynamic shapes would silently retrace).
- per-step metrics: ``app_tpu_step_seconds`` histogram + HBM gauges read
  from device memory stats.
"""

from __future__ import annotations

import asyncio
import concurrent.futures as cf
import queue
import threading
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..tracing import current_context
from .programs import ProgramLog, abstractify, watch_compiles
from .scheduler import maybe_enable_compilation_cache

__all__ = ["Engine", "EngineConfig"]


def _next_bucket(n: int, buckets: Sequence[int] | None) -> int:
    if not buckets:
        return n
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class EngineConfig:
    def __init__(
        self,
        batch_buckets: Sequence[int] = (1, 2, 4, 8, 16, 32, 64),
        donate_inputs: bool = False,
        warmup: bool = True,
    ) -> None:
        self.batch_buckets = tuple(sorted(batch_buckets))
        self.donate_inputs = donate_inputs
        self.warmup = warmup


class Engine:
    """Owns one model: params on device, jitted apply, executor thread."""

    def __init__(
        self,
        name: str,
        apply_fn: Callable[..., Any],
        params: Any,
        *,
        config: EngineConfig | None = None,
        logger=None,
        metrics=None,
        tracer=None,
        example_inputs: tuple | None = None,
        out_sharding=None,
        backend: str = "jit",
        plugin_path: str | None = None,
    ) -> None:
        self.name = name
        self.config = config or EngineConfig()
        self._logger = logger
        self._metrics = metrics
        self._tracer = tracer
        self.backend = backend
        # persistent XLA compilation cache — restarts load the shape-bucket
        # executables from disk instead of recompiling them (the same
        # cache Generator.warmup turns on)
        maybe_enable_compilation_cache()
        self.compiled_buckets: set[int] = set()  # batch dims seen on device
        # program & compile telemetry (ml/programs.py): one row per
        # compiled batch bucket — the /debug/programs inventory
        self.programs = ProgramLog()
        if backend == "pjrt":
            # native PJRT C-API path: jax traces, our binding executes
            from .pjrt_backend import PjrtExecutor

            self._pjrt = PjrtExecutor(apply_fn, params,
                                      plugin_path=plugin_path,
                                      programs=self.programs)
            self._run = self._pjrt
            self._params = params
        elif backend == "jit":
            self._pjrt = None
            self._params = jax.device_put(params)
            if self.config.donate_inputs:
                # donate the input buffers so XLA reuses the bucketed batch
                # allocation for outputs instead of allocating fresh HBM per
                # step (_execute transfers host inputs into fresh device
                # arrays and copies caller-owned jax.Arrays, so the donated
                # buffer is never one the caller still holds).
                # donate_argnums needs concrete positions and apply_fn is
                # (params, *xs): keep one jitted wrapper per input arity.
                jitted: dict[int, Any] = {}

                def run(*xs):
                    fn = jitted.get(len(xs))
                    if fn is None:
                        fn = jitted[len(xs)] = jax.jit(
                            apply_fn,
                            donate_argnums=tuple(range(1, len(xs) + 1)))
                    return fn(self._params, *xs)

                self._run = run
            else:
                self._apply = jax.jit(apply_fn)
                self._run = lambda *xs: self._apply(self._params, *xs)
        else:
            raise ValueError(f"unknown engine backend {backend!r}")
        self._work: queue.Queue = queue.Queue()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"gofr-ml-{name}"
        )
        self.steps = 0
        self.device = jax.devices()[0]
        self._example_inputs = example_inputs
        self._thread.start()
        if example_inputs is not None and self.config.warmup:
            self.predict_sync(*example_inputs)  # compile before first request

    # -- executor thread ------------------------------------------------------
    def _loop(self) -> None:
        while True:
            item = self._work.get()
            if item is None:
                return
            fut, args, parent_ctx = item
            if fut.set_running_or_notify_cancel():
                try:
                    fut.set_result(self._execute(args, parent_ctx))
                except BaseException as exc:  # noqa: BLE001 - relayed via future
                    fut.set_exception(exc)

    def _execute(self, inputs: tuple, parent_ctx=None) -> Any:
        span = None
        if self._tracer is not None:
            # parent ctx was captured on the caller's thread at enqueue time
            # (contextvars don't follow the executor hop); activate=False so
            # the span can't leak into this thread's next work item.
            span = self._tracer.start_span(
                "ml.device_step", parent=parent_ctx, activate=False,
                attributes={"ml.model": self.name, "ml.backend": self.backend},
            )
        start = time.perf_counter()
        arrays: list | None = None
        try:
            if self._pjrt is not None:
                # the native binding does its own host->device transfer; a
                # jnp.asarray here would bounce each input through jax's device
                arrays = [np.asarray(x) for x in inputs]
            elif self.config.donate_inputs:
                # donation consumes the buffer: host inputs transfer into a
                # fresh (safely donatable) device array anyway, but a caller
                # passing a jax.Array would see it DELETED — copy those
                arrays = [x.copy() if isinstance(x, jax.Array)
                          else jnp.asarray(x) for x in inputs]
            else:
                arrays = [jnp.asarray(x) for x in inputs]
            # a batch bucket not yet seen on device means this execute
            # pays a compile (jit retrace or native _compile_for): watch
            # it so the inventory row carries true compile seconds and
            # persistent-cache provenance
            batch = (int(arrays[0].shape[0])
                     if arrays and getattr(arrays[0], "ndim", 0) > 0
                     else None)
            acc = None
            if batch is not None and batch not in self.compiled_buckets:
                with watch_compiles() as acc:
                    out = self._run(*arrays)
                    # blocks until done — the compile completes inside
                    # the watch window
                    out = jax.tree.map(lambda a: np.asarray(a), out)
            else:
                out = self._run(*arrays)
                out = jax.tree.map(lambda a: np.asarray(a), out)  # blocks
        except BaseException as exc:
            if span is not None:
                span.record_exception(exc)
            raise
        finally:
            if span is not None:
                if arrays and getattr(arrays[0], "ndim", 0) > 0:
                    span.set_attribute("ml.batch", int(arrays[0].shape[0]))
                span.end()
        # successful steps only: a failed execute must not count as served
        # work or skew the step-latency histogram with its error path
        dur = time.perf_counter() - start
        if arrays and getattr(arrays[0], "ndim", 0) > 0:
            b = int(arrays[0].shape[0])
            # the native path records its own pjrt/… rows from
            # _compile_for — a second apply/bN row here would double-count
            # every compile second in the shared log
            if (b not in self.compiled_buckets and acc is not None
                    and self._pjrt is None):
                kwargs: dict = {}
                if not self.config.donate_inputs:
                    # the plain jit path can re-lower for cost analysis;
                    # the donate wrapper cannot (per-arity closures)
                    kwargs = {"fn": self._apply,
                              "abstract": abstractify(
                                  (self._params, *arrays))}
                self.programs.record(
                    f"apply/b{b}", wall_s=dur, acc=acc,
                    shapes={"inputs": [list(np.shape(a)) for a in arrays]},
                    **kwargs)
            self.compiled_buckets.add(b)
        self.steps += 1
        if self._metrics is not None:
            try:
                self._metrics.record_histogram(
                    "app_tpu_step_seconds", dur, model=self.name)
            except Exception:
                pass
        if self._logger is not None:
            self._logger.debug(
                {"ml_step": self.name, "duration_us": int(dur * 1e6)}
            )
        return out

    # -- API -------------------------------------------------------------------
    def predict_sync(self, *inputs: Any, trace_parent=None) -> Any:
        fut: cf.Future = cf.Future()
        self._work.put((fut, inputs, trace_parent or current_context()))
        return fut.result()

    async def predict(self, *inputs: Any, trace_parent=None) -> Any:
        fut: cf.Future = cf.Future()
        self._work.put((fut, inputs, trace_parent or current_context()))
        return await asyncio.wrap_future(fut)

    def queue_depth(self) -> int:
        """Work items awaiting the executor thread (sampled as
        ``app_ml_queue_depth{component="engine"}``)."""
        return self._work.qsize()

    def bucket_for(self, n: int) -> int:
        return _next_bucket(n, self.config.batch_buckets)

    def warmup_buckets(self) -> None:
        """Compile every batch-shape bucket up front by tiling the example
        row, so no XLA compile ever lands on a live request (each distinct
        batch bucket is a separate jit trace; paying them at startup is the
        TPU-first trade — serving latency must never include a compile)."""
        if self._example_inputs is None or not self.config.warmup:
            return
        examples = [np.asarray(x) for x in self._example_inputs]
        if examples[0].ndim == 0:
            return  # no batch axis to tile along: nothing to pre-compile
        example_b = examples[0].shape[0]
        for b in self.config.batch_buckets:
            if b == example_b:
                continue  # the constructor's warmup already compiled this one
            # scalars (0-d side inputs) pass through untiled
            tiled = [
                x if x.ndim == 0 else np.repeat(x[:1], b, axis=0)
                for x in examples
            ]
            self.predict_sync(*tiled)

    def memory_stats(self) -> dict | None:
        try:
            return self.device.memory_stats()
        except Exception:
            return None

    def close(self) -> None:
        self._work.put(None)
        if self._pjrt is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                # worker still mid-execution (slow compile / stalled device):
                # destroying the native client now would be a use-after-free
                # in the worker; leak the client instead of crashing.
                return
            self._pjrt.close()
