"""Native PJRT C-API binding tests.

Hermetic: the shim (pjrt_shim.cpp) is exercised against the in-tree fake
plugin (pjrt_fake_plugin.cpp), which speaks the genuine PJRT C API over
host memory — same fake-speaking-the-real-protocol discipline as the
Kafka/NATS broker tests. The real-chip path (libtpu.so) is covered by
``python -m gofr_tpu.native.pjrt_selftest``, run here only when
GOFR_PJRT_REAL=1 because it claims the machine's TPU.
"""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from gofr_tpu.native import pjrt


@pytest.fixture(scope="module")
def plugin():
    path = pjrt.fake_plugin_path()
    if path is None:
        pytest.skip("no C++ toolchain or pjrt_c_api.h header")
    return pjrt.PjrtPlugin(path)


@pytest.fixture()
def client(plugin):
    c = plugin.create_client({})
    yield c
    c.close()


def test_api_version_negotiated(plugin):
    major, minor = plugin.api_version
    assert major == 0 and minor > 0


def test_client_platform_and_devices(client):
    assert client.platform_name == "gofr_fake"
    assert client.device_count == 1


def test_named_value_options_cross_the_boundary(plugin):
    c = plugin.create_client({"addr": "tcp://x:1", "rank": 7, "spmd": True})
    try:
        lib = ctypes.CDLL(plugin.so_path)
        lib.GofrFake_OptionLog.restype = ctypes.c_char_p
        lib.GofrFake_OptionLog.argtypes = [ctypes.c_void_p]
        log = lib.GofrFake_OptionLog(c._handle).decode()
        assert "addr=tcp://x:1;" in log
        assert "rank=7;" in log
        assert "spmd=true;" in log
    finally:
        c.close()


def test_compile_error_surfaces_message(client):
    with pytest.raises(pjrt.PjrtError, match="empty program"):
        client.compile("", compile_options=b"x")


def test_echo_roundtrip_preserves_dtype_shape_and_bytes(client):
    exe = client.compile("module gofr_fake_echo3", compile_options=b"x")
    assert exe.num_outputs == 3
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    b = np.array([[1, 2], [3, 4]], dtype=np.int64)
    c = np.array([255, 0, 7], dtype=np.uint8)
    outs = exe.execute(a, b, c)
    assert len(outs) == 3
    for orig, got in zip((a, b, c), outs):
        assert got.dtype == orig.dtype and got.shape == orig.shape
        np.testing.assert_array_equal(got, orig)
    exe.destroy()


def test_add_mode_computes_through_the_binding(client):
    exe = client.compile("func gofr_fake_add_f32", compile_options=b"x")
    x = np.linspace(-2, 2, 8, dtype=np.float32).reshape(2, 4)
    y = np.full((2, 4), 0.5, np.float32)
    (out,) = exe.execute(x, y)
    np.testing.assert_allclose(out, x + y)
    exe.destroy()


def test_execute_arity_error(client):
    exe = client.compile("gofr_fake_add_f32", compile_options=b"x")
    with pytest.raises(pjrt.PjrtError, match="2 args"):
        exe.execute(np.ones(3, np.float32))
    exe.destroy()


def test_device_buffer_object_lifecycle(client):
    buf = client.to_device(np.eye(3, dtype=np.float32))
    arr = buf.to_numpy()
    np.testing.assert_array_equal(arr, np.eye(3, dtype=np.float32))
    buf.destroy()
    buf.destroy()  # idempotent


def test_default_compile_options_is_valid_proto_bytes():
    blob = pjrt.default_compile_options()
    assert isinstance(blob, bytes) and len(blob) > 10


def test_stablehlo_text_lowers_from_jax():
    """The artifact handed to compile() is real StableHLO from jax."""
    import jax

    def f(x):
        return x * 2.0

    hlo = str(jax.jit(f, backend="cpu").lower(np.ones((2, 2), np.float32))
              .compiler_ir("stablehlo"))
    assert "stablehlo" in hlo and "func" in hlo


@pytest.mark.skipif(os.environ.get("GOFR_PJRT_REAL") != "1",
                    reason="claims the machine's TPU session; opt-in")
def test_selftest_on_real_plugin():
    proc = subprocess.run(
        [sys.executable, "-m", "gofr_tpu.native.pjrt_selftest"],
        capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    line = proc.stdout.strip().splitlines()[-1]
    result = json.loads(line)
    assert result["ok"], result
