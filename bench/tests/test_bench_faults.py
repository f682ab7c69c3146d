"""A whole benchmark run at a test's size on the CPU (the look for a chip
skipped), sound and with each fault a training cell can have planted in
the timed path: ``correct`` must come out true, then false for each."""
import _benchpath  # noqa: F401
import pytest

from _tinycell import CELLS, tiny_cell
from benchlib import faults, harness


def _run(cell):
    _, checks, _ = harness.run_cell(cell, 2**31 + 12345, 0.2, False, 0.0,
                                    gather="xla", compile_cache=False)
    return harness.verdict(checks, cell.limits)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name, tmp_path, monkeypatch):
    ok, shown = _run(tiny_cell(name, tmp_path, monkeypatch))
    assert ok, shown


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, tmp_path, monkeypatch):
    cell = tiny_cell(name, tmp_path, monkeypatch)
    with faults.FAULTS[fault]():
        ok, shown = _run(cell)
    assert not ok, shown
