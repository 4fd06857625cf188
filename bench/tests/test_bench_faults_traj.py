"""The comparison that decides ``correct`` fails what it must, on the
CPU at a tiny size: the control (the reference in the engine's place,
pair arithmetic in bfloat16) and each fault planted under the timed
path (``faults.py``) drive set-up, window and check and read as not
correct."""

import time

import pytest

from bench import control, harness
from bench.tests import faults

CELLS = ["lammps_inlj.traj"]


def _measure(spec, bench, cell):
    return harness.measure(spec, cell, seed=2 ** 32 + 3, seconds=0.2,
                           trace=False, t_start=time.perf_counter(),
                           bench=bench, interpret=True, log=lambda s: None)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(tiny_bench, cell):
    spec, bench = tiny_bench
    rows = control.run(spec, cell, [5], 0.2, bench=bench, interpret=True,
                       log=lambda s: None)
    assert not rows[0]["correct"], rows[0]["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out", "answer_altered"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(tiny_bench, cell, fault):
    spec, bench = tiny_bench
    with faults.planted("trajectory", fault):
        r = _measure(spec, bench, cell)
    assert not r["correct"], r["checks"]
