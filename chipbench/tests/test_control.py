"""The check's control at a size a test run can hold: the tiny control
cell of ``conftest.py``, three seeds, one call each, through the
benchmark's own run and comparison. The program passes the tiny limits;
the program with its own INT8 path on (``control.py --int8-seeds``, as
on the chip at each cell's own size, PERF.md) fails them."""
import pytest

from conftest import DEVICE

from chipbench import control


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_program_passes_and_the_control_fails(control_cell, seed):
    # a window of a microsecond holds exactly one call
    sound = control.reading(control_cell, seed, 1e-6, DEVICE)
    low = control.reading(control_cell, seed, 1e-6, DEVICE, int8=True)
    assert sound["correct"], sound["checked"]
    assert not low["correct"], low["checked"]
