"""The control comes out not correct: the reference computed one
precision step below the configuration (``Precision.HIGH`` contractions,
bfloat16 elsewhere) in the program's place fails the cell's limits,
while the program passes them, on the same window at a small size."""
import pytest

from perfbench import calibrate
from perfbench.tests import helpers


@pytest.mark.parametrize("workload", ["paper3_steady", "fleet64_bursty",
                                      "paper3_grid_4chip"])
def test_control_fails_where_the_program_passes(workload):
    cell = helpers.cell(workload, seconds=0.6)
    limits = cell.limits()
    rows = {r["who"]: r for r in calibrate.readings(cell, [777], {777})}
    numbers = [k for k in limits if k in rows["program"]]
    assert numbers
    assert all(rows["program"][k] <= limits[k] for k in numbers), rows
    assert any(rows["control"][k] > limits[k] for k in numbers), rows
