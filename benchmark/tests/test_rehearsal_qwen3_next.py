"""The Qwen3-Next cell end to end on the CPU at a toy size (rehearsal 1 of
the on-chip-measurement guide): its entry, weights, reference, counters and
per-layer readers through the harness, the printed line held to the
contract, and the int8 control refused by the cell's limits."""

import json
import os

import pytest

from benchmark import contract
from benchmark.tests import rehearse

TOY = os.path.join(rehearse.HERE, "toy_qwen3_next")


@pytest.fixture
def toy(monkeypatch):
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        bench = json.load(f)
    monkeypatch.setattr(rehearse, "TOY", TOY)
    monkeypatch.setattr(rehearse, "toy_bench", lambda: bench)
    return bench


def test_untraced_run_validates_and_refuses_the_control(monkeypatch, toy):
    line = rehearse.run_toy(monkeypatch, "toy-longanswer", control=True)
    control = line.pop("control")
    contract.validate(line, toy, "toy-longanswer", traced=False)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert control["correct"] is False, control["compared"]


def test_traced_run_reports_every_listed_metric(monkeypatch, toy):
    line = rehearse.run_toy(monkeypatch, "toy-longanswer", traced=True)
    contract.validate(line, toy, "toy-longanswer", traced=True)
    assert line["correct"], line["compared"]
    m = {name: v["value"] for name, v in line["metrics"].items()}
    assert 10.0 < m["expert_held_share"] < 45.0     # 4 of 16 experts held
    assert m["expert_tokens_per_touched"] >= 1.0
    assert m["hybrid_serve_mfu"] > 0
