"""The check's control at smoke size: the tokens the reference computed in
fp8 puts first, judged in the program's place by the run's own comparison
and the cell's own check file, must come out not correct where the
program's come out correct.

The chip runs set the cells' own limits from the same two readings at full
size (``control.py``)."""
import pytest

import run
from smoke_cell import CELLS, smoke_cell, smoke_limits


@pytest.mark.parametrize("workload", sorted(CELLS))
@pytest.mark.parametrize("seed", [1, 3])
def test_control_fails_where_the_program_passes(workload, seed):
    res = run.run(workload, seed, 2.0, False, require_chip=False,
                  cell=smoke_cell(*CELLS[workload]),
                  limits=smoke_limits(workload), control=True)
    assert res["program"]["correct"], res["program"]["checks"]
    assert not res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
