"""`correct` must come out false for the control and for every fault a
cell can have, at a smoke size on the CPU, with the cell's own limits
(``smoke_run.smoke_limits``); and true for the program as it is
(test_bench_harness.py)."""
from __future__ import annotations

import pytest

import smoke_run
import harness

ONE_CHIP = ["stablelm_3b-4l.train-1chip"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_control_is_not_correct(name):
    """The float32 reference computed with float8 matmul operands, put in
    the program's place, fails at least one number."""
    cell = harness.smoke_cell(harness.load_cell(name))
    ref = harness.reference_readings(cell, smoke_run.SEED)
    control = harness.reference_readings(cell, smoke_run.SEED, precision="fp8")
    ok, checks = harness.judge(harness.compare(control, ref), smoke_run.smoke_limits(cell))
    assert not ok, checks


@pytest.mark.parametrize("name", ONE_CHIP)
@pytest.mark.parametrize("fault", ["unchanged", "half"])
def test_fault_is_not_correct(name, fault):
    r = smoke_run.run(name, fault=fault)
    assert not r["correct"], r["checks"]
