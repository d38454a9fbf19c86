"""``correct`` has to come out false when the timed path is wrong: for the
control (the reference itself one precision step down) and for an answer
altered where it is produced.  These run the runners at a tiny size on the
CPU; the control's readings at the cells' own sizes are taken on the chip
with ``bench/tools/readings.py``."""
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

CELLS = {
    "krr-msd.fit": ("krr", tiny.KRR_CONFIG, tiny.KRR_TRAFFIC, "predict_error"),
    "stablelm-3b.decode": ("lm_serve", tiny.LM_CONFIG, tiny.LM_TRAFFIC,
                           "served_logit_gap"),
}
# (control, number it has to fail) for each cell.  The bfloat16-attention
# serving control is read, but fails nothing: bfloat16 weights set the gap.
CONTROLS = {
    "krr-msd.fit": [("high", "fitted_excess"), ("high", "predict_excess"),
                    ("bf16", "solve_residual"), ("bf16", "predict_error")],
    "stablelm-3b.decode": [("fp8", "served_logit_gap")],
}


class Counted(harness.Window):
    """A backlog window that closes after ``n`` items, whatever the host's
    speed, so that the items, and the sample checked, are the same on any
    host."""

    def __init__(self, n: int):
        super().__init__(n, {"kind": "backlog"})

    def now(self) -> float:
        return float(len(self.items))


def _limits(traffic):
    c = traffic["check"]
    return c["limits"] if "limits" in c else {"served_logit_gap": c["limit"]}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails_where_the_program_passes(name):
    runner, config, traffic, _ = CELLS[name]
    r = harness.load_module(harness.BENCH / "runners" / f"{runner}.py")
    state = r.setup(tiny.cell(name, config, traffic))
    win = Counted(3)
    r.measure(state, win)
    got = r.readings(state, win, control=True)
    limits = _limits(traffic)
    assert all(got[k] <= v for k, v in limits.items()), got
    for ctrl, number in CONTROLS[name]:
        assert limits[number] < got[f"control.{ctrl}.{number}"], (ctrl, got)
    read = {k.split(".")[1] for k in got if k.startswith("control.")}
    assert read == set(config["controls"]), got


def _alter_prediction(monkeypatch):
    from repro.core.krr import SketchedKRR

    predict = SketchedKRR.predict
    monkeypatch.setattr(SketchedKRR, "predict",
                        lambda self, X, **kw: predict(self, X, **kw).at[0].add(1.0))


def _alter_token(monkeypatch):
    from repro.serve.engine import Engine

    generate = Engine.generate

    def altered(self, prompts, n_new, **kw):
        toks, cache = generate(self, prompts, n_new, **kw)
        toks = np.array(toks)
        toks[0, -1] = (toks[0, -1] + 1) % self.cfg.vocab_size
        return toks, cache

    monkeypatch.setattr(Engine, "generate", altered)


@pytest.mark.parametrize("name,alter", [("krr-msd.fit", _alter_prediction),
                                        ("stablelm-3b.decode", _alter_token)])
def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch, name, alter):
    _, config, traffic, number = CELLS[name]
    alter(monkeypatch)
    r = tiny.run(tiny.cell(name, config, traffic))
    assert r["correct"] is False
    assert r["checks"][number]["value"] > r["checks"][number]["limit"]
