"""Each fault of ``harness.faults`` that a cell can have (its limits file
lists them), planted under the timed path of a whole run of the cell at a
small size on the CPU: ``correct`` comes out false."""

import sys

import pytest

sys.path.insert(0, __import__("os").path.dirname(__file__))
from helpers import CELLS, load_run, small_run  # noqa: E402


def _faults(cell):
    run = load_run()
    return run.resolve(run.load_manifest(), cell)["faults"]


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS for f in _faults(c)])
def test_fault_is_not_correct(cell, fault):
    from harness import faults

    with faults.planted(fault):
        res, _ = small_run(cell)
    assert not res["correct"], res["checks"]
