"""The control fails the limits that the program passes: at a tiny size
on the CPU, the reference computed a precision below the configuration
(bfloat16 for float32 at the default precision) in the program's place
reads above at least one limit of the cell."""
from __future__ import annotations

import time

import pytest

from chipbench_cells import CELLS, FIXTURE_CELLS, harness, tiny_cell


@pytest.mark.parametrize("name", CELLS + list(FIXTURE_CELLS))
def test_control_fails_and_program_passes(name, tmp_path):
    cell = tiny_cell(name, tmp_path)
    cell.config["matmul_precision"] = "default"
    _, prog, rounds, theta0 = harness.measure(cell, 12345, 0.0, False,
                                              time.perf_counter())
    ref = harness.reference_readings(cell, theta0, rounds)
    program = harness.compare(prog, ref)
    control = harness.compare(harness.reference_readings(
        cell, theta0, rounds, **harness.control_kwargs(cell)), ref)
    over = lambda got: [k for k in cell.limits if got[k] > cell.limits[k]]
    assert over(program) == []
    assert over(control), control
