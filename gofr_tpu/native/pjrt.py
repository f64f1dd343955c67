"""ctypes driver for the native PJRT C-API binding (pjrt_shim.cpp).

The reference framework is pure Go (no native runtime); this module is
the TPU build's mandated native component: it loads any PJRT plugin —
``libtpu.so`` (the attached TPU) or the in-tree fake plugin used by CI —
and exposes a
small object model over the shim's flat C ABI:

    plugin = PjrtPlugin(so_path)
    client = plugin.create_client({"session_id": "...", ...})
    exe    = client.compile(stablehlo_text)         # "mlir" format
    outs   = exe.execute(np_a, np_b)                # list[np.ndarray]

Compilation takes StableHLO (text or bytecode) straight from
``jax.jit(f).lower(*args).compiler_ir("stablehlo")``, and the compile
options default to a serialized single-device CompileOptionsProto from
jaxlib — the same proto the C API expects.

The shim itself is compiled on first use by gofr_tpu.native's
build_and_load with the public ``xla/pjrt/c/pjrt_c_api.h`` header found
in the installed tensorflow (or jaxlib) package; no PJRT code is
vendored.
"""

from __future__ import annotations

import ctypes
import glob
import os
import sys

import numpy as np

from . import build_and_load

# PJRT_Buffer_Type values (xla/pjrt/c/pjrt_c_api.h, stable append-only enum)
_PJRT_TYPES: dict[str, int] = {
    "bool": 1, "int8": 2, "int16": 3, "int32": 4, "int64": 5,
    "uint8": 6, "uint16": 7, "uint32": 8, "uint64": 9,
    "float16": 10, "float32": 11, "float64": 12, "bfloat16": 13,
    "complex64": 14, "complex128": 15,
}
_PJRT_TYPES_INV = {v: k for k, v in _PJRT_TYPES.items()}

_ERRCAP = 4096


def find_pjrt_header_dir() -> str | None:
    """Locate the directory containing xla/pjrt/c/pjrt_c_api.h in installed
    packages (tensorflow ships it; future jaxlibs may too)."""
    import importlib.util

    for pkg in ("tensorflow", "jaxlib"):
        spec = importlib.util.find_spec(pkg)
        if spec is None or not spec.submodule_search_locations:
            continue
        root = spec.submodule_search_locations[0]
        for cand in (os.path.join(root, "include"), root):
            if os.path.exists(os.path.join(cand, "xla/pjrt/c/pjrt_c_api.h")):
                return cand
    for cand in glob.glob(os.path.join(sys.prefix, "**/xla/pjrt/c/pjrt_c_api.h"),
                          recursive=True):
        return cand[: -len("xla/pjrt/c/pjrt_c_api.h")].rstrip("/")
    return None


def _load_shim():
    inc = find_pjrt_header_dir()
    if inc is None:
        return None
    lib = build_and_load("pjrt_shim.cpp", "libgofr_pjrt", ("-I" + inc,))
    if lib is None:
        return None
    lib.gofr_pjrt_load.restype = ctypes.c_void_p
    lib.gofr_pjrt_load.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                   ctypes.c_size_t]
    lib.gofr_pjrt_api_version.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.gofr_pjrt_client_create.restype = ctypes.c_void_p
    lib.gofr_pjrt_client_create.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int), ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t]
    lib.gofr_pjrt_client_destroy.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.gofr_pjrt_device_count.restype = ctypes.c_longlong
    lib.gofr_pjrt_device_count.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                           ctypes.c_char_p, ctypes.c_size_t]
    lib.gofr_pjrt_platform_name.restype = ctypes.c_longlong
    lib.gofr_pjrt_platform_name.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t]
    lib.gofr_pjrt_compile.restype = ctypes.c_void_p
    lib.gofr_pjrt_compile.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t]
    lib.gofr_pjrt_executable_destroy.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_void_p]
    lib.gofr_pjrt_num_outputs.restype = ctypes.c_longlong
    lib.gofr_pjrt_num_outputs.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_char_p, ctypes.c_size_t]
    lib.gofr_pjrt_buffer_from_host.restype = ctypes.c_void_p
    lib.gofr_pjrt_buffer_from_host.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_int64), ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t]
    lib.gofr_pjrt_buffer_destroy.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.gofr_pjrt_buffer_ndims.restype = ctypes.c_longlong
    lib.gofr_pjrt_buffer_ndims.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t]
    lib.gofr_pjrt_buffer_dtype.restype = ctypes.c_int
    lib.gofr_pjrt_buffer_dtype.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.gofr_pjrt_buffer_to_host.restype = ctypes.c_longlong
    lib.gofr_pjrt_buffer_to_host.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
        ctypes.c_size_t, ctypes.c_char_p, ctypes.c_size_t]
    lib.gofr_pjrt_execute.restype = ctypes.c_longlong
    lib.gofr_pjrt_execute.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_size_t, ctypes.POINTER(ctypes.c_void_p), ctypes.c_size_t,
        ctypes.c_longlong, ctypes.c_char_p, ctypes.c_size_t]
    return lib


class PjrtError(RuntimeError):
    """An error surfaced from the plugin through the C API."""


class PjrtPlugin:
    """A loaded PJRT plugin (.so) with a negotiated API table."""

    def __init__(self, so_path: str):
        self._lib = _load_shim()
        if self._lib is None:
            raise PjrtError(
                "native PJRT shim unavailable (no g++ toolchain or no "
                "pjrt_c_api.h header in installed packages)")
        err = ctypes.create_string_buffer(_ERRCAP)
        self._api = self._lib.gofr_pjrt_load(so_path.encode(), err, _ERRCAP)
        if not self._api:
            raise PjrtError(f"load {so_path}: {err.value.decode()}")
        self.so_path = so_path

    @property
    def api_version(self) -> tuple[int, int]:
        major, minor = ctypes.c_int(), ctypes.c_int()
        self._lib.gofr_pjrt_api_version(self._api, ctypes.byref(major),
                                        ctypes.byref(minor))
        return major.value, minor.value

    def create_client(self, options: dict[str, str | int | bool] | None = None
                      ) -> "PjrtClient":
        options = options or {}
        n = len(options)
        keys = (ctypes.c_char_p * n)()
        svals = (ctypes.c_char_p * n)()
        ivals = (ctypes.c_int64 * n)()
        kinds = (ctypes.c_int * n)()
        for i, (k, v) in enumerate(options.items()):
            keys[i] = k.encode()
            if isinstance(v, bool):
                kinds[i], ivals[i], svals[i] = 2, int(v), b""
            elif isinstance(v, int):
                kinds[i], ivals[i], svals[i] = 1, v, b""
            else:
                kinds[i], svals[i] = 0, str(v).encode()
        err = ctypes.create_string_buffer(_ERRCAP)
        client = self._lib.gofr_pjrt_client_create(
            self._api, keys, svals, ivals, kinds, n, err, _ERRCAP)
        if not client:
            raise PjrtError(f"client create: {err.value.decode()}")
        return PjrtClient(self, client)


class PjrtClient:
    def __init__(self, plugin: PjrtPlugin, handle):
        self._plugin = plugin
        self._lib = plugin._lib
        self._api = plugin._api
        self._handle = handle

    def close(self) -> None:
        if self._handle:
            self._lib.gofr_pjrt_client_destroy(self._api, self._handle)
            self._handle = None

    @property
    def device_count(self) -> int:
        err = ctypes.create_string_buffer(_ERRCAP)
        n = self._lib.gofr_pjrt_device_count(self._api, self._handle, err,
                                             _ERRCAP)
        if n < 0:
            raise PjrtError(err.value.decode())
        return int(n)

    @property
    def platform_name(self) -> str:
        out = ctypes.create_string_buffer(256)
        err = ctypes.create_string_buffer(_ERRCAP)
        n = self._lib.gofr_pjrt_platform_name(self._api, self._handle, out,
                                              256, err, _ERRCAP)
        if n < 0:
            raise PjrtError(err.value.decode())
        return out.value.decode()

    def compile(self, code: str | bytes, *, fmt: str = "mlir",
                compile_options: bytes | None = None) -> "PjrtExecutable":
        """Compile StableHLO/MLIR (fmt="mlir") or HloModuleProto (fmt="hlo").

        ``compile_options`` is a serialized CompileOptionsProto; defaults
        to jaxlib's single-replica/single-partition options.
        """
        if compile_options is None:
            compile_options = default_compile_options()
        blob = code.encode() if isinstance(code, str) else code
        err = ctypes.create_string_buffer(_ERRCAP)
        exe = self._lib.gofr_pjrt_compile(
            self._api, self._handle, blob, len(blob), fmt.encode(),
            compile_options, len(compile_options), err, _ERRCAP)
        if not exe:
            raise PjrtError(f"compile: {err.value.decode()}")
        return PjrtExecutable(self, exe)

    def to_device(self, arr: np.ndarray) -> "PjrtBuffer":
        arr = np.ascontiguousarray(arr)
        dtype_name = arr.dtype.name
        if dtype_name not in _PJRT_TYPES:
            raise PjrtError(f"unsupported dtype {arr.dtype}")
        dims = (ctypes.c_int64 * arr.ndim)(*arr.shape)
        err = ctypes.create_string_buffer(_ERRCAP)
        buf = self._lib.gofr_pjrt_buffer_from_host(
            self._api, self._handle, arr.ctypes.data_as(ctypes.c_void_p),
            _PJRT_TYPES[dtype_name], dims, arr.ndim, err, _ERRCAP)
        if not buf:
            raise PjrtError(f"to_device: {err.value.decode()}")
        return PjrtBuffer(self, buf)


class PjrtBuffer:
    def __init__(self, client: PjrtClient, handle):
        self._client = client
        self._lib = client._lib
        self._api = client._api
        self._handle = handle

    def destroy(self) -> None:
        if self._handle:
            self._lib.gofr_pjrt_buffer_destroy(self._api, self._handle)
            self._handle = None

    def to_numpy(self) -> np.ndarray:
        err = ctypes.create_string_buffer(_ERRCAP)
        dims = (ctypes.c_int64 * 16)()
        ndims = self._lib.gofr_pjrt_buffer_ndims(self._api, self._handle,
                                                 dims, 16, err, _ERRCAP)
        if ndims < 0:
            raise PjrtError(f"dims: {err.value.decode()}")
        code = self._lib.gofr_pjrt_buffer_dtype(self._api, self._handle)
        if code not in _PJRT_TYPES_INV:
            raise PjrtError(f"unknown PJRT dtype code {code}")
        np_dtype = _PJRT_TYPES_INV[code]
        if np_dtype == "bfloat16":  # numpy has no bf16; view as uint16
            np_dtype = "uint16"
        nbytes = self._lib.gofr_pjrt_buffer_to_host(
            self._api, self._handle, ndims, None, 0, err, _ERRCAP)
        if nbytes < 0:
            raise PjrtError(f"to_host size: {err.value.decode()}")
        out = np.empty(nbytes, np.uint8)
        got = self._lib.gofr_pjrt_buffer_to_host(
            self._api, self._handle, ndims,
            out.ctypes.data_as(ctypes.c_void_p), nbytes, err, _ERRCAP)
        if got < 0:
            raise PjrtError(f"to_host: {err.value.decode()}")
        shape = tuple(dims[i] for i in range(min(ndims, 16)))
        return out.view(np_dtype).reshape(shape)


class PjrtExecutable:
    def __init__(self, client: PjrtClient, handle):
        self._client = client
        self._lib = client._lib
        self._api = client._api
        self._handle = handle
        self._num_outputs: int | None = None

    def destroy(self) -> None:
        if self._handle:
            self._lib.gofr_pjrt_executable_destroy(self._api, self._handle)
            self._handle = None

    @property
    def num_outputs(self) -> int:
        if self._num_outputs is None:
            err = ctypes.create_string_buffer(_ERRCAP)
            n = self._lib.gofr_pjrt_num_outputs(self._api, self._handle, err,
                                                _ERRCAP)
            if n < 0:
                raise PjrtError(err.value.decode())
            self._num_outputs = int(n)
        return self._num_outputs

    def execute_buffers(self, buffers: list[PjrtBuffer]) -> list[PjrtBuffer]:
        n_in = len(buffers)
        in_arr = (ctypes.c_void_p * max(n_in, 1))(
            *[b._handle for b in buffers])
        out_arr = (ctypes.c_void_p * 256)()
        err = ctypes.create_string_buffer(_ERRCAP)
        # cached output count skips a GetExecutable/NumOutputs round trip
        # inside the shim on every call (hot serving path)
        nout_hint = self.num_outputs
        n_out = self._lib.gofr_pjrt_execute(
            self._api, self._handle, in_arr, n_in, out_arr, 256,
            nout_hint, err, _ERRCAP)
        if n_out < 0:
            raise PjrtError(f"execute: {err.value.decode()}")
        return [PjrtBuffer(self._client, out_arr[i]) for i in range(n_out)]

    def execute(self, *arrays: np.ndarray) -> list[np.ndarray]:
        """Host arrays in, host arrays out; device buffers are transient."""
        bufs = [self._client.to_device(a) for a in arrays]
        try:
            outs = self.execute_buffers(bufs)
        finally:
            for b in bufs:
                b.destroy()
        try:
            return [o.to_numpy() for o in outs]
        finally:
            for o in outs:
                o.destroy()


def default_compile_options(num_replicas: int = 1,
                            num_partitions: int = 1) -> bytes:
    """Serialized CompileOptionsProto via jaxlib (the same proto the C API
    documents for PJRT_Client_Compile_Args.compile_options)."""
    from jaxlib import xla_client as xc

    opts = xc.CompileOptions()
    opts.num_replicas = num_replicas
    opts.num_partitions = num_partitions
    return opts.SerializeAsString()


def fake_plugin_path() -> str | None:
    """Build (if needed) and return the in-tree fake plugin used by CI."""
    inc = find_pjrt_header_dir()
    if inc is None:
        return None
    lib = build_and_load("pjrt_fake_plugin.cpp", "libgofr_pjrt_fake",
                         ("-I" + inc,))
    if lib is None:
        return None
    return lib._name


def default_plugin_path() -> str | None:
    """The best real-hardware plugin available on this machine."""
    cand = os.environ.get("GOFR_PJRT_PLUGIN")
    if cand and os.path.exists(cand):
        return cand
    try:
        import libtpu

        return os.path.join(os.path.dirname(libtpu.__file__), "libtpu.so")
    except ImportError:
        return None
