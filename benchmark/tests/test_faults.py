"""The comparison that decides ``correct`` has been shown to fail: with
the timed path broken underneath (a token altered where it is produced),
and with the reference computed in int8 in the program's place (the
control). The look for a chip is skipped; the rest of a run is driven."""

import pytest

from benchmark.tests import rehearse


@pytest.mark.parametrize("workload", ["toy-batch", "toy-x4-sessions"])
def test_an_altered_token_reads_incorrect(monkeypatch, workload):
    from gofr_tpu.ml import llm

    real_emit = llm.LLMServer._emit

    def emit_altered(self, req, tokens):
        tokens = list(tokens)
        if len(tokens) > 1:  # every burst of a decode chunk: its last token
            tokens[-1] = (tokens[-1] + 1) % self.gen.cfg.vocab_size
        return real_emit(self, req, tokens)

    monkeypatch.setattr(llm.LLMServer, "_emit", emit_altered)
    line = rehearse.run_toy(monkeypatch, workload)
    assert line["correct"] is False
    value, limit = line["compared"]["gap_max"]
    assert value > limit


def test_an_unanswered_request_reads_incorrect(monkeypatch):
    from benchmark import load

    real_send = load.Client.send

    async def send_dropping(self, rec, deadline=None):
        if rec.request.session == 3:   # one request's answer never comes
            rec.sent = rec.end = rec.due
            rec.error = "dropped by the test"
            return rec
        return await real_send(self, rec, deadline)

    monkeypatch.setattr(load.Client, "send", send_dropping)
    line = rehearse.run_toy(monkeypatch, "toy-chat")
    assert line["correct"] is False and line["failed"] >= 1
    assert line["compared"]["unanswered"][0] >= 1


@pytest.mark.parametrize("workload", ["toy-chat", "toy-sessions"])
def test_the_int8_control_reads_incorrect(monkeypatch, workload):
    line = rehearse.run_toy(monkeypatch, workload, control=True)
    assert line["correct"] is True          # the program itself is sound
    # the control went through the same verdict against the same limits
    assert line["control"]["correct"] is False
    ctrl, prog = line["control"]["compared"], line["compared"]
    assert {k: v[1] for k, v in ctrl.items()} == {k: v[1] for k, v in prog.items()}
    assert ctrl["gap_max"][0] > ctrl["gap_max"][1]


def test_control_py_fails_where_a_control_passes(monkeypatch, capsys):
    """``control.py`` exits non-zero when the control comes out correct."""
    from benchmark import control, harness

    def run_cell(**kw):
        return {"attempted": 1, "failed": 0, "correct": True, "compared": {},
                "control": {"correct": kw["seed"] != 2, "compared": {}}}

    monkeypatch.setattr(harness, "run_cell", run_cell)
    assert control.main(["--workload", "w", "--seeds", "1,2",
                         "--seconds", "1"]) == 5
    assert control.main(["--workload", "w", "--seeds", "2",
                         "--seconds", "1"]) == 0
    capsys.readouterr()
