"""The DeepSeek-V3 cell end to end on the CPU at a toy size, before any
chip run: its entry, weights, reference, counters and per-layer readers
through the harness, the printed line held to the contract, and the int8
control refused by the cell's limits."""

import json
import os

import pytest

from benchmark import contract
from benchmark.tests import rehearse

TOY = os.path.join(rehearse.HERE, "toy_deepseek_v3")


@pytest.fixture
def toy(monkeypatch):
    with open(os.path.join(TOY, "BENCHMARK.json")) as f:
        bench = json.load(f)
    monkeypatch.setattr(rehearse, "TOY", TOY)
    monkeypatch.setattr(rehearse, "toy_bench", lambda: bench)
    return bench


def test_untraced_run_validates_and_refuses_the_control(monkeypatch, toy):
    line = rehearse.run_toy(monkeypatch, "toy-longdecode", control=True)
    control = line.pop("control")
    contract.validate(line, toy, "toy-longdecode", traced=False)
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert control["correct"] is False, control["compared"]


def test_traced_run_reports_every_listed_metric(monkeypatch, toy):
    """On the CPU the latent decode runs the plain computation in the
    kernel's place, so no operation bears the kernel's name: the decode
    programs' device time stands in for it under that name, and the
    roofline's reader does its arithmetic on that."""
    from benchmark import trace_reduce

    real_reduce = trace_reduce.reduce

    def with_kernel_stand_in(planes, **kw):
        out = real_reduce(planes, **kw)
        for dev in out["devices"]:
            dev["op_total_s"]["latent_decode_attention_tpu.0"] = sum(
                row["seconds"] for name, row in dev["programs"].items()
                if "chunk_fn" in name)
        return out

    monkeypatch.setattr(trace_reduce, "reduce", with_kernel_stand_in)
    line = rehearse.run_toy(monkeypatch, "toy-longdecode", traced=True)
    contract.validate(line, toy, "toy-longdecode", traced=True)
    assert line["correct"], line["compared"]
    m = {name: v["value"] for name, v in line["metrics"].items()}
    assert 10.0 < m["expert_held_share"] < 45.0     # 4 of 16 experts held
    assert m["mla_serve_mfu"] > 0
    assert m["latent_decode_roofline"] > 0
