"""Rehearsal 1 and 2 of the on-chip-measurement guide: every cell end to
end on the CPU at a toy size, the four-replica cell on four virtual
devices, and the line each prints held to the contract."""

import pytest

from benchmark import contract
from benchmark.tests import rehearse


@pytest.mark.parametrize("workload", ["toy-chat", "toy-sessions", "toy-batch",
                                      "toy-x4-sessions"])
def test_untraced_run_validates(monkeypatch, workload):
    line = rehearse.run_toy(monkeypatch, workload)
    contract.validate(line, rehearse.toy_bench(), workload, traced=False)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("workload", ["toy-chat", "toy-sessions", "toy-batch",
                                      "toy-x4-sessions"])
def test_traced_run_validates(monkeypatch, workload):
    line = rehearse.run_toy(monkeypatch, workload, traced=True)
    contract.validate(line, rehearse.toy_bench(), workload, traced=True)
    assert line["correct"], line["compared"]
    dev = line["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]


def test_the_reference_is_the_one_the_configuration_names(monkeypatch,
                                                          tmp_path):
    """No module of the harness is wired to one architecture: the weights
    and the reference are found by the names in the configuration's file."""
    import json
    import os

    bench = rehearse.toy_bench()
    with open(os.path.join(rehearse.REPO_ROOT,
                           bench["configs"][0]["file"])) as f:
        sizes = json.load(f)
    sizes["modules"]["reference"] = "tests/toy/reference_off"
    path = tmp_path / "toy-off.json"
    path.write_text(json.dumps(sizes))
    bench["configs"][0]["file"] = str(path)
    monkeypatch.setattr(rehearse, "toy_bench", lambda: bench)
    line = rehearse.run_toy(monkeypatch, "toy-batch")
    assert line["correct"] is False
    assert line["compared"]["gap_max"][0] == 1.0
