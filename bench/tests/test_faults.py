"""Whole one-chip runs on the CPU: correct when sound, and not correct
with each fault of a training cell planted in the timed path."""
import pytest

from bench.tests import _tiny

CELLS = ["flight-exact", "flight-svi-stream", "usps-gplvm-exact"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _tiny.run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
def test_fault_is_caught(monkeypatch, cell, fault):
    _tiny.plant(monkeypatch, fault)
    out = _tiny.run(cell)
    assert not out["correct"], (fault, out["checks"])
