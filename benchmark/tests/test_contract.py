"""``contract.validate`` refuses what the driver refuses."""

import copy
import json
import os

import pytest

from benchmark import contract

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELL = BENCH["workloads"][0]["name"]


def good_line(traced: bool) -> dict:
    metrics = {m["name"]: 12.5 for m in contract.metrics_of(BENCH, CELL, traced)}
    units = {m["name"]: m["unit"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 12 * 10**9}
    breakdown = None
    if traced:
        device.update(busy_s=3.1, window_s=4.0)
        breakdown = {"device_ops": [["fusion.1", 1.5]],
                     "idle_gaps": [["host: unattributed", 0.01]]}
    return contract.build_line(correct=True, attempted=100, failed=0,
                               metrics=metrics, units=units, device=device,
                               breakdown=breakdown,
                               compared={"gap_max": [0.01, 0.2]})


@pytest.mark.parametrize("traced", [False, True])
def test_a_good_line_passes(traced):
    contract.validate(good_line(traced), BENCH, CELL, traced)
    assert list(good_line(traced))[-1] == "compared"


def _broken(traced, edit):
    line = copy.deepcopy(good_line(traced))
    edit(line)
    return line


CASES = {
    "missing metric": (False, lambda l: l["metrics"].pop("setup_s")),
    "missing traced metric": (True, lambda l: l["metrics"].pop("device_idle_share")),
    "nan": (False, lambda l: l["metrics"]["tokens_per_s"].update(value=float("nan"))),
    "infinity": (False, lambda l: l["metrics"]["tokens_per_s"].update(value=float("inf"))),
    "null": (False, lambda l: l["metrics"]["tokens_per_s"].update(value=None)),
    "wrong unit": (False, lambda l: l["metrics"]["tokens_per_s"].update(unit="tok/s")),
    "metric of another run kind": (False, lambda l: l["metrics"].update(
        device_idle_share={"value": 1.0, "unit": "%"})),
    "busy zero": (True, lambda l: l["device"].update(busy_s=0.0)),
    "busy over window": (True, lambda l: l["device"].update(busy_s=4.2)),
    "busy missing": (True, lambda l: l["device"].pop("busy_s")),
    "window missing": (True, lambda l: l["device"].pop("window_s")),
    "no memory peak": (False, lambda l: l["device"].update(memory_peak_bytes=0)),
    "wrong chip count": (False, lambda l: l["device"].update(count=4)),
    "no device": (False, lambda l: l.pop("device")),
    "failed over attempted": (False, lambda l: l.update(failed=101)),
    "correct not a boolean": (False, lambda l: l.update(correct="yes")),
    "share of a peak over 105": (True, lambda l: l["metrics"]["serve_mfu"].update(value=130.0)),
    "mfu zero": (True, lambda l: l["metrics"]["serve_mfu"].update(value=0.0)),
    "breakdown too long": (True, lambda l: l["breakdown"].update(
        device_ops=[["x", 1.0]] * 11)),
    "breakdown row null": (True, lambda l: l["breakdown"].update(
        idle_gaps=[["host", None]])),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_broken_line_is_refused(case):
    traced, edit = CASES[case]
    with pytest.raises(contract.ContractError):
        contract.validate(_broken(traced, edit), BENCH, CELL, traced)


def test_benchmark_json_names_files_that_exist():
    for cfg in BENCH["configs"]:
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
    for w in BENCH["workloads"]:
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "serve", w["name"] + ".json"))
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    for m in BENCH["per_layer"]:
        for ext in (".json", ".py"):
            assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ext))


def test_benchmark_json_keeps_the_contracts_limits():
    """What the driver refuses before a single run: names, lengths, the
    keys of each entry, which cells a metric lists."""
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    assert sorted(BENCH) == sorted(["command", "paths", "run_seconds", "configs",
                                    "workloads", "end_to_end", "per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"] for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs
    for c in BENCH["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
    for w in BENCH["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4) and name.match(w["traffic"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.1 and "workloads" not in e2e["setup_s"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
        moved = e2e[m["moves"]]
        # each listed cell reports the end-to-end metric this one moves
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    for entry in (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"]):
        assert name.match(entry["name"]), entry["name"]
        assert set(entry.get("workloads", [])) <= cells
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200, (entry["name"], key)
                assert "\n" not in entry[key] and "\t" not in entry[key]
        if "unit" in entry:
            assert unit.match(entry["unit"]) and entry["better"] in ("lower", "higher")
    for w in cells:
        assert [m for m in contract.metrics_of(BENCH, w, False)
                if m["name"] != "setup_s"]
        assert contract.metrics_of(BENCH, w, True)
