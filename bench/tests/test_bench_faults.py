"""The comparison that decides `correct` fails its control and each fault
a one-chip cell can have: a run driven through the harness, the accelerator
check skipped, with the program's timed path broken underneath (CPU, tiny
sizes).  Cells on one chip have no exchange between chips to leave out."""
import jax.numpy as jnp
import pytest

import benchtest_util as bt
from repro.core.lower import CompiledProgram

run = bt.run


def _unchanged(orig):
    """A step that returns its state unchanged."""
    def f(self, inputs):
        return {k: inputs[k] for k in self.program.outputs}
    return f


def _half_batch(orig):
    """Half of every bag left out; the sums over rows scaled back up."""
    def f(self, inputs):
        halved = {k: tuple(c[:c.shape[0] // 2] for c in v)
                  if isinstance(v, tuple) else v for k, v in inputs.items()}
        out = dict(orig(self, halved))
        out["C"] = out["C"] * 2.0
        return out
    return f


def _altered(orig):
    """One answer altered where it is produced."""
    def f(self, inputs):
        out = dict(orig(self, inputs))
        k = self.program.outputs[-1] if "P" not in out else "P"
        v = jnp.asarray(out[k])
        out[k] = v.at[0].set(v[0] * 1.01 + 1.0)
        return out
    return f


@pytest.mark.parametrize("cell", bt.CELLS)
def test_sound_run_is_correct(cell):
    res = run.measure(bt.tiny_cell(cell), 77, 0.2, False,
                      require_device=False)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _altered])
@pytest.mark.parametrize("cell", bt.CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    monkeypatch.setattr(CompiledProgram, "run", fault(CompiledProgram.run))
    res = run.measure(bt.tiny_cell(cell), 78, 0.2, False,
                      require_device=False)
    assert res["attempted"] >= 1 and res["correct"] is False


@pytest.mark.parametrize("cell", bt.CELLS)
def test_control_fails_the_limits(cell):
    """The reference in bfloat16, in the program's place, makes the run
    incorrect: it reads above at least one limit."""
    res = run.measure(bt.tiny_cell(cell), 79, 0.2, False,
                      require_device=False, entry="control")
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["correct"] is False, res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_control_script_reports_each_seed(monkeypatch, capsys):
    """bench/control.py drives the same run per seed and exits 0 only when
    every seed reads incorrect."""
    import json
    import control
    tiny = bt.tiny_cell("tpch-sf10.q1-groupby")
    monkeypatch.setattr(control.run, "load_cell", lambda name: tiny)
    real = run.measure
    monkeypatch.setattr(control.run, "measure", lambda *a, **k: real(
        *a, **dict(k, require_device=False)))
    assert control.main(["--workload", tiny.name, "--seeds", "5,6",
                         "--seconds", "0.2"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [5, 6]
    assert all(x["correct"] is False for x in lines)
