"""The Jamba cell end to end on the CPU at a toy size (rehearsal 1 of the
on-chip-measurement guide): its entry, weights, reference, counters and
per-layer readers through the harness, the printed line held to the
contract, and the int8 control refused by the cell's limits."""

import json
import os

import pytest

from benchmark import contract
from benchmark.tests import rehearse

TOY = os.path.join(rehearse.HERE, "toy_jamba")


@pytest.fixture
def toy(monkeypatch):
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        bench = json.load(f)
    monkeypatch.setattr(rehearse, "TOY", TOY)
    monkeypatch.setattr(rehearse, "toy_bench", lambda: bench)
    return bench


def test_untraced_run_validates_and_refuses_the_control(monkeypatch, toy):
    line = rehearse.run_toy(monkeypatch, "toy-reasoning", control=True)
    control = line.pop("control")
    contract.validate(line, toy, "toy-reasoning", traced=False)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert control["correct"] is False, control["compared"]


def test_traced_run_reports_every_listed_metric(monkeypatch, toy):
    line = rehearse.run_toy(monkeypatch, "toy-reasoning", traced=True)
    contract.validate(line, toy, "toy-reasoning", traced=True)
    assert line["correct"], line["compared"]
    m = {name: v["value"] for name, v in line["metrics"].items()}
    assert m["ssm_serve_mfu"] > 0
    assert m["ssm_decode_step_roofline"] > 0
    assert 25.0 <= m["state_live_share"] <= 100.0   # 4 clients on 4 slots
    assert m["decode_step_ms"] > 0


def test_the_real_cell_is_what_the_issue_names():
    """The registered files say letter for letter what the cell is: one
    chip, ISSUE 34's one fallback (128 clients on 128 slots, taken because
    the 256-stream cell spread by 1.1-1.2 % on the host's account; PERF.md
    section 6) and everything else as the issue names it: slots of 2,048,
    chunk 16, the Pallas branch demanded, the five shared metrics and the
    three new ones listed."""
    with open(os.path.join(rehearse.REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = contract.cell(bench, "jamba2-3b-reasoning")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ai21-jamba2-3b", "reasoning", 1)
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == []
    with open(os.path.join(rehearse.BENCH_DIR, "serve",
                           "jamba2-3b-reasoning.json")) as f:
        serve = json.load(f)
    assert serve["register_llm"] == {"batch_slots": 128, "max_seq": 2048,
                                     "chunk": 16}
    assert serve["decode_branch"] == {"op": "decode_attention",
                                      "must_be": "pallas"}
    assert serve["warmup"] == {"prompt_tokens": [100, 200, 400], "burst": 128}
    with open(os.path.join(rehearse.BENCH_DIR, "traffic",
                           "reasoning.json")) as f:
        mix = json.load(f)
    assert mix["arrivals"] == {"kind": "closed", "clients": 128, "cycle": 32}
    assert (mix["schedule_seed"], mix["preroll_s"], mix["grid"]) == (
        2034, 3.0, 64)
    assert mix["prompt_tokens"] == {"dist": "lognormal", "median": 128,
                                    "sigma": 0.7, "min": 32, "max": 512}
    assert mix["answer_tokens"] == {"dist": "lognormal", "median": 768,
                                    "sigma": 0.4, "min": 256, "max": 1536}
    traced = {m["name"] for m in contract.metrics_of(
        bench, "jamba2-3b-reasoning", True)}
    assert traced == {"slot_occupancy", "decode_step_ms", "device_idle_share",
                      "compiles_in_window", "prefill_device_share",
                      "ssm_serve_mfu", "ssm_decode_step_roofline",
                      "state_live_share"}
    untraced = {m["name"] for m in contract.metrics_of(
        bench, "jamba2-3b-reasoning", False)}
    assert untraced == {"tpot_p95_ms", "tokens_per_s", "setup_s"}
