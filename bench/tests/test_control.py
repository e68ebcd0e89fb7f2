"""The control comes out not correct.

The control is the plain reference put in the program's place one
precision lower: per-round partial sums kept in a float32 running state
(``Reference.control_view``). Its exact views drift from the float64
truth as the rounds add up, past each cell's ``exact_gap`` limit. On
the chip it is read at the cells' own sizes by ``bench/control.py``;
here it runs at 8M rows, the smallest size at which it already fails.
"""

import pytest

from bench import control

ROWS = 8_000_000


@pytest.mark.parametrize("workload", ["flights-151m.suite-solo",
                                      "flights-606m.wholetable-solo"])
def test_control_fails_the_cell_limits(x64, small_cell, workload):
    cell = small_cell(workload, ROWS)
    out = control.control_numbers(cell, 2**31 + 21, cell.limits)
    assert not out["correct"]
    assert out["exact_gap"] > cell.limits["exact_gap"]
