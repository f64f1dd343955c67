"""BASELINE config #3: BERT embeddings over gRPC unary, effective batch 32.

32 concurrent unary Embed calls coalesce in the DynamicBatcher into device
batches; reports aggregate embeddings/s and p50 per-call latency, plus a
measured (not prose) decomposition: the empty dispatch + D2H round trip
and the direct device path — one jitted batch-32 forward timed on-device,
giving the throughput the device would serve with the wire removed. BERT_PRESET=base
selects bert-base dims (default on TPU, tiny on CPU).
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from common import (boot, closed_loop, configure_free_ports, emit,
                    percentile, run, dispatch_rtt_ms)


def _direct_device_path(preset: str, batch: int, max_len: int) -> dict:
    """Time the same jitted batch-32 BERT forward the server dispatches,
    chained on-device so only one D2H sync ends the timed window — the
    serving ceiling with the wire removed."""
    import jax

    from gofr_tpu.models import bert

    cfg = bert.tiny_bert() if preset == "tiny" else bert.bert_base()
    model = bert.Bert(cfg)
    toks = np.random.default_rng(0).integers(
        1, 1000, (batch, max_len)).astype(np.int32)
    lens = np.full((batch,), 64, np.int32)

    fwd = jax.jit(lambda p, t, l: model.apply(p, t, l))
    out = fwd(model.params, toks, lens)
    np.asarray(out)  # compile + sync
    reps = 8
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fwd(model.params, toks, lens)
    np.asarray(out)
    step_s = (time.perf_counter() - t0) / reps
    return {
        "device_step_ms": round(step_s * 1e3, 2),
        "direct_path_req_per_s": round(batch / step_s, 1),
    }


async def main() -> None:
    ports = configure_free_ports()
    os.environ.setdefault("LOG_LEVEL", "ERROR")

    import grpc.aio
    import jax

    if "BERT_PRESET" not in os.environ and jax.default_backend() == "tpu":
        os.environ["BERT_PRESET"] = "base"

    from examples.bert_server.main import main as build_app

    app = build_app()
    await boot(app)
    workers = int(os.environ.get("BENCH_WORKERS", "32"))
    duration = float(os.environ.get("BENCH_DURATION_S", "4"))

    rng = np.random.default_rng(0)
    reqs = [
        {"token_ids": rng.integers(1, 1000, (64,)).tolist()}
        for _ in range(workers)
    ]

    channel = grpc.aio.insecure_channel(f"127.0.0.1:{ports['GRPC_PORT']}")
    embed = channel.unary_unary(
        "/ml.Embeddings/Embed",
        request_serializer=lambda o: json.dumps(o).encode(),
        response_deserializer=lambda raw: json.loads(raw) if raw else {},
    )
    await embed(reqs[0])  # compile warmup

    i = 0

    async def once():
        nonlocal i
        i += 1
        resp = await embed(reqs[i % workers])
        assert "embedding" in resp

    lats, n = await closed_loop(workers, duration, once, warmup_s=1.0)
    await channel.close()
    await app.shutdown()

    preset = os.environ.get("BERT_PRESET", "tiny")
    rtt_ms = dispatch_rtt_ms()
    direct = _direct_device_path(preset, batch=32, max_len=64)

    emit(
        "bert_grpc_embeddings_per_s", n / duration, "req/s", None,
        {
            "p50_ms": round(percentile(lats, 50) * 1e3, 2),
            "p99_ms": round(percentile(lats, 99) * 1e3, 2),
            "workers": workers,
            "preset": preset,
            # wire p50 = batcher wait + device step + dispatch round
            # trip; the direct rows are measured in this same run
            "dispatch_rtt_p50_ms": round(rtt_ms, 1),
            **direct,
            "backend": jax.default_backend(),
            "config": 3,
        },
    )


if __name__ == "__main__":
    run(main())
