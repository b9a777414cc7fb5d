"""The control (the reference in float32) is not correct at a size a test
run holds, on three seeds, in every cell."""

import pytest

from benchmark import control, run

SIZES = {"gdelt": {"rows": 1_000_000}, "tdrive": {"taxis": 400, "points": 400_000}}


@pytest.fixture
def test_size(monkeypatch):
    orig = run.load_cell

    def sized(name):
        bench, cell, cfg, mix = orig(name)
        return bench, cell, {**cfg, "rehearse": SIZES[cfg["name"]]}, mix

    monkeypatch.setattr(control, "load_cell", sized)


@pytest.mark.parametrize("seed", [1, 2**31 + 11, 977])
@pytest.mark.parametrize("workload", ["gdelt.select", "tdrive.select", "gdelt.count"])
def test_control_fails(test_size, workload, seed):
    out = control.control(workload, seed, 40, rehearse=True)
    assert out["correct"] is False, out
