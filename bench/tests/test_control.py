"""The control, the plain reference put in the program's place at the
next precision below the configuration's (float32 at Precision.HIGH:
three bf16 passes), fails each cell's comparison: at least one number
reads above its limit.  The chip readings at the cells' own size are in
PERF.md; this keeps the check at a size a test run holds."""
import numpy as np
import pytest

from conftest import TINY, cell_names
from yardstick import ref
from yardstick.cells import Cell


def test_high_split_is_three_bf16_passes():
    x = np.float32(1.0) / np.arange(3, 4000, dtype=np.float32)
    got = ref.high_split(x)
    err = np.abs(got - x) / x
    assert err.max() <= 2.0 ** -17 and err.max() > 2.0 ** -20


@pytest.mark.parametrize("name", cell_names())
def test_control_fails(name):
    cell = Cell(name)
    drv = cell.driver().Driver(cell, 24681357913, 1.0, dict(TINY))
    drv.setup()
    drv.window()
    drv.release()
    prog = drv.numbers()
    ctrl = drv.numbers(control=True)
    drv.close()
    assert all(prog[k] <= v for k, v in cell.limits.items()), prog
    assert any(ctrl[k] > v for k, v in cell.limits.items()), ctrl
