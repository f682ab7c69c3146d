"""The control of each cell: the reference computed in bfloat16, put in
the program's place, must fail the cell's limits, while the program
passes them on the same seeds (at a test's size, on the CPU)."""
import _benchpath  # noqa: F401
import pytest

from _tinycell import CELLS, tiny_cell
from benchlib import harness

import calibrate


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_and_program_passes(name, tmp_path, monkeypatch):
    cell = tiny_cell(name, tmp_path, monkeypatch)
    out = calibrate.calibrate(cell, [2**31 + 3, 5, 2**32 + 7], 3, 0,
                              gather="xla", compile_cache=False)
    for row in out["program"]:
        ok, shown = harness.verdict(row, cell.limits)
        assert ok, shown
    for row in out["control"]:
        ok, shown = harness.verdict(row, cell.limits)
        assert not ok, shown
