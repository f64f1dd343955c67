"""Helpers of the CPU rehearsal: a run of the harness at toy size with
the look for a chip steered round from here (never through an option or
an environment variable of ``run.py``)."""

from __future__ import annotations

import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO_ROOT = os.path.dirname(BENCH_DIR)
TOY = os.path.join(HERE, "toy")

CPU_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def toy_bench() -> dict:
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        return json.load(f)


def cpu_devices(chips: int, peaks: dict):
    import jax

    return jax.devices()[:chips], CPU_PEAK


def run_toy(monkeypatch, workload: str, *, seed: int = 11, seconds: float = 4.0,
            traced: bool = False, control: bool = False,
            slice_s: float = 1.0) -> dict:
    from benchmark import harness

    monkeypatch.setattr(harness, "find_devices", cpu_devices)
    monkeypatch.setattr(harness, "TRACE_SLICE_S", slice_s)
    monkeypatch.setattr(harness, "DRAIN_S", 30.0)
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda system: 1)
    # a CPU trace has no device plane: the host's XLA executor threads
    # stand in, one made-up plane per chip of the cell
    from benchmark import trace_reduce

    real_load = trace_reduce.load
    chips = next(w["chips"] for w in toy_bench()["workloads"]
                 if w["name"] == workload)

    def load_with_stand_in(path):
        planes = real_load(path)
        host = next(p for p in planes if p["name"] == "/host:CPU")
        ops = [e for line in host["lines"] if line["name"].startswith("tf_")
               for e in line["events"]]
        mods = [(e[0][len("PjitFunction("):-1], e[1], e[2])
                for line in host["lines"] for e in line["events"]
                if e[0].startswith("PjitFunction(")]
        loops = [("while.1", a, d) for _, a, d in mods]
        for i in range(chips):
            planes.append({"name": f"/device:TPU:{i}", "lines": [
                {"name": "XLA Ops", "events": ops + loops},
                {"name": "XLA Modules", "events": mods}]})
        return planes

    monkeypatch.setattr(trace_reduce, "load", load_with_stand_in)
    return harness.run_cell(
        repo_root=REPO_ROOT, bench_dir=BENCH_DIR, bench=toy_bench(),
        workload=workload, seed=seed, seconds=seconds, traced=traced,
        t_process=time.perf_counter(), control=control, data_dir=TOY)
