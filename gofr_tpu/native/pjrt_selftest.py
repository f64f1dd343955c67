"""Real-hardware selftest for the native PJRT binding.

Run standalone: ``python -m gofr_tpu.native.pjrt_selftest``

Lowers a small jax function to StableHLO on the CPU backend (no chip
claim), then drives the plugin named by ``default_plugin_path()`` —
normally the machine's real TPU plugin — through the native shim:
client create, compile, host->device, execute, device->host, and checks
the result against the CPU reference. Prints one JSON line.

Kept out of the default pytest run because it claims the machine's TPU
session; tests/test_pjrt.py covers the shim hermetically with the fake
plugin and runs this selftest only when GOFR_PJRT_REAL=1.
"""

from __future__ import annotations

import json
import os
import sys


def lower_reference() -> tuple[str, list, list]:
    """StableHLO text + inputs + expected outputs, computed on CPU."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    def f(x, y):
        return jnp.tanh(x @ y) + 1.0, (x * 2.0).sum(axis=1)

    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 16)).astype(np.float32)
    y = rng.normal(size=(16, 4)).astype(np.float32)
    lowered = jax.jit(f, backend="cpu").lower(x, y)
    hlo = lowered.compiler_ir("stablehlo")
    expected = [np.asarray(v) for v in jax.jit(f, backend="cpu")(x, y)]
    return str(hlo), [x, y], expected


def mnist_engine_parity() -> dict:
    """Engine(backend='pjrt') vs Engine(backend='jit') on the MNIST MLP —
    the same model behind config #2's POST /predict."""
    import numpy as np

    from gofr_tpu.ml.engine import Engine
    from gofr_tpu.models.mlp import mnist_mlp

    model = mnist_mlp(hidden=128)
    x = np.random.default_rng(1).normal(size=(8, 784)).astype(np.float32)
    native = Engine("mnist-native", model.apply, model.params,
                    backend="pjrt", example_inputs=(x,))
    jit = Engine("mnist-jit", model.apply, model.params,
                 example_inputs=(x,))
    try:
        got = np.asarray(native.predict_sync(x))
        want = np.asarray(jit.predict_sync(x))
        err = float(np.abs(got - want).max())
        return {"mnist_parity_ok": bool(np.allclose(got, want, atol=2e-2,
                                                    rtol=2e-2)),
                "mnist_max_abs_err": err,
                "engine_platform": native._pjrt.platform_name}
    finally:
        native.close()
        jit.close()


def main() -> int:
    import numpy as np

    from gofr_tpu.native import pjrt

    so = pjrt.default_plugin_path()
    if so is None:
        print(json.dumps({"ok": False, "reason": "no PJRT plugin on host"}))
        return 1
    hlo, inputs, expected = lower_reference()

    plugin = pjrt.PjrtPlugin(so)
    client = plugin.create_client({})
    try:
        exe = client.compile(hlo)
        outs = exe.execute(*inputs)
        ok = len(outs) == len(expected) and all(
            np.allclose(o, e, atol=2e-2, rtol=2e-2)
            for o, e in zip(outs, expected, strict=True)
        )
        result = {
            "ok": bool(ok),
            "plugin": so,
            "platform": client.platform_name,
            "api_version": list(plugin.api_version),
            "devices": client.device_count,
            "num_outputs": exe.num_outputs,
            "max_abs_err": max(
                float(np.abs(np.asarray(o, np.float32) - e).max())
                for o, e in zip(outs, expected, strict=True)
            ) if len(outs) == len(expected) else None,
        }
        exe.destroy()
    finally:
        client.close()

    # second client lifecycle: the engine-level parity check
    try:
        result.update(mnist_engine_parity())
        result["ok"] = bool(result["ok"] and result["mnist_parity_ok"])
    except Exception as exc:  # noqa: BLE001 - selftest reports, not raises
        result["ok"] = False
        result["mnist_parity_error"] = f"{type(exc).__name__}: {exc}"
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
