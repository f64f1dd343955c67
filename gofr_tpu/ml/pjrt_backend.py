"""Native-PJRT execution backend for the ML engine.

``Engine(backend="pjrt")`` routes device execution through the
framework's own PJRT C-API binding (gofr_tpu/native/pjrt_shim.cpp)
instead of ``jax.jit``'s runtime: jax is used for *tracing only* —
``jax.jit(...).lower(...)`` on the CPU backend produces StableHLO, which
the native binding compiles and executes directly against the plugin
(libtpu.so). This is the BASELINE.json north-star
native component made load-bearing rather than decorative.

Semantics match the jit path:
- params are uploaded to HBM once at construction (HBM-resident weights);
- one executable per input-shape signature (the engine's shape-bucket
  policy bounds how many signatures occur);
- outputs come back as host numpy arrays in the function's pytree.

Known limits (documented, not silent): bf16 outputs surface as uint16
views (numpy has no bfloat16), and the executor is single-device — the
multi-chip path stays on jit/GSPMD where it belongs.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import numpy as np

__all__ = ["PjrtExecutor"]


class PjrtExecutor:
    """Callable that executes ``apply_fn(params, *inputs)`` via the native
    PJRT binding, with params resident on device."""

    def __init__(
        self,
        apply_fn: Callable[..., Any],
        params: Any,
        *,
        plugin_path: str | None = None,
        client_options: dict | None = None,
        programs: Any = None,
    ) -> None:
        import jax

        from gofr_tpu.native import pjrt

        path = plugin_path or pjrt.default_plugin_path()
        if path is None:
            raise pjrt.PjrtError("no PJRT plugin available on this host")
        self._jax = jax
        self._pjrt = pjrt
        self._plugin = pjrt.PjrtPlugin(path)
        self._client = self._plugin.create_client(client_options or {})
        self._apply = apply_fn
        leaves, self._params_tree = jax.tree.flatten(
            jax.tree.map(np.asarray, params))
        self._param_bufs = [self._client.to_device(x) for x in leaves]
        # tracing only needs shapes — keeping the full host copy would pin
        # a second multi-GB weight image in RAM for the engine's lifetime
        self._params_abstract = jax.tree.unflatten(
            self._params_tree,
            [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in leaves])
        del leaves
        self._cache: dict[tuple, tuple] = {}
        # compile telemetry: wall seconds and entry count per native
        # compile — the numbers /debug/programs shows for the native
        # path (the Engine shares its ProgramLog via ``programs=``)
        self.stats = {"compiles": 0, "compile_s": 0.0, "entries": 0}
        self._programs = programs

    @property
    def platform_name(self) -> str:
        return self._client.platform_name

    def _compile_for(self, np_inputs: list[np.ndarray]):
        jax = self._jax

        def fn(params, *xs):
            return self._apply(params, *xs)

        t0 = time.perf_counter()
        # keep_unused: the executable's argument list must stay aligned
        # with the flattened (params, *inputs) leaves we feed it
        lowered = jax.jit(fn, backend="cpu", keep_unused=True).lower(
            self._params_abstract, *np_inputs)
        hlo = str(lowered.compiler_ir("stablehlo"))
        out_shape = jax.eval_shape(fn, self._params_abstract, *np_inputs)
        _, out_tree = jax.tree.flatten(out_shape)
        exe = self._client.compile(hlo)
        wall = time.perf_counter() - t0
        # compile wall + entry count: the native path's share of the
        # program inventory (trace + StableHLO lowering + plugin compile)
        self.stats["compiles"] += 1
        self.stats["compile_s"] += wall
        self.stats["entries"] = len(self._cache) + 1
        if self._programs is not None:
            shapes = [list(a.shape) for a in np_inputs]
            self._programs.record(
                f"pjrt/{'x'.join(str(s) for s in (shapes[0] if shapes else ()))}"
                f"#{self.stats['compiles']}",
                wall_s=wall, kind="pjrt_native",
                shapes={"inputs": shapes})
        return exe, out_tree

    def __call__(self, *inputs: Any) -> Any:
        np_inputs = [np.asarray(x) for x in inputs]
        sig = tuple((a.shape, str(a.dtype)) for a in np_inputs)
        entry = self._cache.get(sig)
        if entry is None:
            entry = self._compile_for(np_inputs)
            self._cache[sig] = entry
        exe, out_tree = entry
        in_bufs = [self._client.to_device(a) for a in np_inputs]
        try:
            out_bufs = exe.execute_buffers(self._param_bufs + in_bufs)
        finally:
            for b in in_bufs:
                b.destroy()
        try:
            host = [b.to_numpy() for b in out_bufs]
        finally:
            for b in out_bufs:
                b.destroy()
        return self._jax.tree.unflatten(out_tree, host)

    def close(self) -> None:
        for b in self._param_bufs:
            b.destroy()
        self._param_bufs = []
        for exe, _ in self._cache.values():
            exe.destroy()
        self._cache.clear()
        self._client.close()
