"""The three metrics read from inside the program (``prefill_device_share``,
``decode_rows_per_step``, ``dispatch_host_ms``): each reader on a hand-made
``obs``, and the toy cells run traced with the three entries appended in
memory (the toy ``BENCHMARK.json`` stays as it is)."""

import json
import os

import pytest

from benchmark import contract, harness
from benchmark.tests import rehearse

NEW = ("prefill_device_share", "decode_rows_per_step", "dispatch_host_ms")
CELLS = ["toy-chat", "toy-sessions", "toy-batch", "toy-x4-sessions"]


def _reader(name):
    return harness.load_reader(rehearse.BENCH_DIR, name)


def test_prefill_device_share_is_the_prefill_programs_mean_over_chips():
    meta, read = _reader("prefill_device_share")
    row = lambda s: {"seconds": s, "runs": 1, "inner_loops": 0}  # noqa: E731
    obs = {"trace": {"window_s": 4.0, "devices": [
        {"programs": {"jit_suffix_prefill(1)": row(0.9),
                      "jit_post_prefill(2)": row(0.1),
                      "jit_paged_chunk_fn(3)": row(2.5)}},
        {"programs": {"jit_prefill_many(4)": row(1.0),
                      "jit_chunk_fn(5)": row(2.0)}}]}}
    assert read(obs, meta["params"]) == pytest.approx(100 * 2.0 / 2 / 4.0)
    obs["trace"]["devices"] = [{"programs": {"jit_chunk_fn(5)": row(2.0)}}]
    assert read(obs, meta["params"]) is None


def _records(t0):
    """Two replicas' dispatches in a window that no real record shares."""
    from gofr_tpu.flight_recorder import dispatch_log

    def rec(model, at, steps, rows, **phases):
        dispatch_log().append(0, {
            "model": model, "t0": t0 + at, "t1": t0 + at + 0.1, "kind": "chunk",
            "steps": steps, "rows": rows, "phases": phases, "spans": []})

    rec("toy/0", 0.1, 8, 4, launch=0.002, device_wait=0.1, other=0.001)
    rec("toy/0", 0.2, 1, 2, launch=0.004, assemble=0.010, queue_pop=0.5)
    rec("toy/0", 0.3, 0, 0, device_wait=0.05, emit=0.001)       # a flush
    rec("toy/1", 0.4, 8, 1, launch=0.003, emit=0.002)
    rec("toy/1", 9.0, 8, 4, launch=9.0)                 # after the window
    return {"t0": t0, "seconds": 5.0}


def test_decode_rows_per_step_weighs_rows_by_steps_and_sums_replicas():
    meta, read = _reader("decode_rows_per_step")
    obs = _records(7.0e8)
    assert read(obs, meta.get("params", {})) == pytest.approx(
        (4 * 8 + 2 * 1) / 9 + 1.0)
    assert read({"t0": 6.0e8, "seconds": 5.0}, {}) is None


def test_dispatch_host_ms_is_the_median_pass_without_the_two_waits():
    meta, read = _reader("dispatch_host_ms")
    obs = _records(8.0e8)
    # per record: 3, 14, 1 and 5 ms of host work
    assert read(obs, meta["params"]) == pytest.approx((3.0 + 5.0) / 2)
    assert read({"t0": 6.0e8, "seconds": 5.0}, meta["params"]) is None


def test_every_new_reader_has_its_files_and_the_listed_ones_validate():
    with open(os.path.join(rehearse.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        meta, _ = _reader(name)
        if name in listed:
            for key in ("layer", "unit", "source", "moves"):
                assert listed[name][key] == meta[key], (name, key)


@pytest.mark.parametrize("workload", CELLS)
def test_toy_cell_reports_the_three_metrics(monkeypatch, workload):
    bench = rehearse.toy_bench()
    for name in NEW:
        meta, _ = _reader(name)
        bench["per_layer"].append({
            "name": name, "unit": meta["unit"], "source": meta["source"],
            "better": "higher" if name == "decode_rows_per_step" else "lower",
            "layer": meta["layer"], "moves": meta["moves"],
            "workloads": CELLS})
    monkeypatch.setattr(rehearse, "toy_bench", lambda: bench)
    line = rehearse.run_toy(monkeypatch, workload, traced=True)
    contract.validate(line, bench, workload, traced=True)
    got = line["metrics"]
    assert set(NEW) <= set(got)
    cell = contract.cell(bench, workload)
    with open(os.path.join(rehearse.TOY, "serve", f"{workload}.json")) as f:
        slots = json.load(f)["register_llm"]["batch_slots"]
    assert 0 < got["decode_rows_per_step"]["value"] <= slots * cell["chips"]
    assert 0 < got["prefill_device_share"]["value"] <= 100
    assert got["dispatch_host_ms"]["value"] > 0
