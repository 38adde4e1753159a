"""The comparison that decides ``correct``, driven without a chip at sizes a
test run holds: the rest of a run as ``run.py`` drives it, with the limits
of the configuration files.  A sound run passes; the control (the
reference one precision lower in the program's place) and a planted fault
(an answer or a token altered where it is produced) fail."""

import json
import time

import numpy as np
import pytest

from conftest import BENCH
from yardstick import harness

BIG_SEED = 2 ** 33 + 12345          # seeds may pass 32 bits


def _json(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def _driver(name):
    return harness.load_module(BENCH / "drivers" / f"{name}.py")


def _ctx(seconds=0.3):
    return harness.Context("test", BIG_SEED, seconds, False, BENCH.parent,
                           time.perf_counter())


def _failed(out):
    return {c.name for c in out.checks if not c.ok}


@pytest.fixture
def rounds():
    cfg = _json("configs", "spacdc-fig3-ffn")
    cfg["shape"] = {"a_rows": 24 * 64, "d": 2048, "b_cols": 128}
    return cfg, _json("traffic", "round-plain")


def _serving(traffic):
    cfg = _json("configs", "phi3-mini-4l")
    cfg["model"].update(hidden_size=256, intermediate_size=512,
                        num_attention_heads=4, num_key_value_heads=4,
                        num_hidden_layers=2, vocab_size=1024)
    cfg["cluster"]["serve"]["max_slots"] = 4
    tr = _json("traffic", traffic)
    tr.update(requests_per_pass=6, prompt=[4, 16], gen=[4, 12],
              check_requests=6)
    return cfg, tr


@pytest.fixture(params=["chat-backlog-c8", "chat-backlog-c8-uncoded"])
def serving(request):
    return _serving(request.param)


def test_round_run_is_correct(rounds):
    out = _driver("round").run(*rounds, _ctx())
    assert not _failed(out) and out.attempted > 0


def test_round_control_fails_its_limit(rounds):
    cfg, tr = rounds
    prog, ctrl = _driver("round").control_readings(
        cfg, tr, 7, 0.2)["round_rel_gap"]
    assert prog <= cfg["limits"]["round_rel_gap"] < ctrl


def test_round_altered_answer_fails(rounds, monkeypatch):
    from repro.api import Session
    matmul = Session.matmul

    def altered(self, a, b, round_idx=None):
        out, stats = matmul(self, a, b, round_idx)
        out = np.array(out)
        out[3, 5] += 1e-3 * np.abs(out).max()
        return out, stats

    monkeypatch.setattr(Session, "matmul", altered)
    assert _failed(_driver("round").run(*rounds, _ctx())) == {"round_rel_gap"}


def test_serve_run_is_correct(serving):
    out = _driver("serve").run(*serving, _ctx(1.0))
    assert not _failed(out) and out.failed == 0 and out.attempted > 0


def test_serve_control_fails_its_limit():
    cfg, tr = _serving("chat-backlog-c8")
    prog, ctrl = _driver("serve").control_readings(
        cfg, tr, 7, 0.5)["served_gap_mean"]
    assert prog <= cfg["limits"]["served_gap_mean"] < ctrl


def test_serve_altered_token_fails(serving, monkeypatch):
    from repro.runtime.serve_loop import ContinuousBatcher
    step, run = ContinuousBatcher._run_step, ContinuousBatcher.run
    passes, steps = [], []

    def counted_run(self, *args, **kw):
        res = run(self, *args, **kw)
        passes.append(1)
        return res

    def altered(self, *args):
        out = step(self, *args)
        if len(passes) == 1:            # the window's one pass
            steps.append(1)
            if len(steps) == 12:        # a step in which slots generate
                nxt = (np.asarray(out[0]) + 1) % self.model.cfg.vocab_size
                out = (nxt,) + out[1:]
        return out

    monkeypatch.setattr(ContinuousBatcher, "run", counted_run)
    monkeypatch.setattr(ContinuousBatcher, "_run_step", altered)
    out = _driver("serve").run(*serving, _ctx(0.01))
    assert len(passes) == 2 and len(steps) >= 12
    assert _failed(out) == {"served_gap_mean"}
