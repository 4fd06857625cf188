"""The comparison that decides ``correct`` in the bonded cell fails what it
must, on the CPU at a tiny size: the bonded control (the reference in the
engine's place, pair and bond arithmetic in bfloat16) and each fault
planted under the timed path drive set-up, window and check and read as
not correct; and the scene's chains are what the configuration says."""

import time

import numpy as np
import pytest

from bench import control_bonded, harness
from bench.tests import faults

CELL = "kg_melt.force_bonded"


def test_the_tiny_copy_cuts_the_melt_to_a_thousand_beads(tiny_bench):
    """``bench/conftest.py`` sizes reach ``tiny_bench``: no bench test
    builds the million-bead melt."""
    spec, bench = tiny_bench
    cfg = harness.config("kg_melt", bench)
    assert harness.scene(cfg["scene"], bench).count(cfg) == 1000
    traffic = harness.traffic(harness.cell(spec, CELL)["traffic"], bench)
    assert traffic["keep_one_in"] == 2


def test_control_is_not_correct(tiny_bench):
    spec, bench = tiny_bench
    rows = control_bonded.run(spec, CELL, [5], 0.2, bench=bench,
                              interpret=True, log=lambda s: None)
    assert not rows[0]["correct"], rows[0]["checks"]


@pytest.mark.parametrize("fault", ["half_left_out", "answer_altered"])
def test_fault_is_not_correct(tiny_bench, fault):
    spec, bench = tiny_bench
    with faults.planted("execute", fault):
        r = harness.measure(spec, CELL, seed=2 ** 32 + 3, seconds=0.2,
                            trace=False, t_start=time.perf_counter(),
                            bench=bench, interpret=True, log=lambda s: None)
    assert not r["correct"], r["checks"]


def test_chains_are_lattice_neighbours_that_cross_the_box(tiny_bench):
    _, bench = tiny_bench
    cfg = harness.config("kg_melt", bench)
    scene = harness.scene(cfg["scene"], bench)
    pos, bonds = scene.make(cfg, harness._key(2 ** 33 + 5), 2)
    pos, bonds = np.asarray(pos), np.asarray(bonds)
    n, length = scene.count(cfg), cfg["chain_length"]
    assert pos.shape == (2, n, 3) and bonds.shape == (n // length * 99, 2)
    assert (bonds[:, 1] == bonds[:, 0] + 1).all()
    assert not (bonds[:, 1] % length == 0).any()      # no bond across chains
    box = scene.box(cfg)[0]
    assert n / box ** 3 == pytest.approx(cfg["density"], rel=1e-9)
    assert (pos >= 0).all() and (pos < box).all()
    d = pos[:, bonds[:, 0]] - pos[:, bonds[:, 1]]
    crossing = np.abs(d).max(-1) > box / 2
    d -= box * np.round(d / box)
    r = np.sqrt((d * d).sum(-1))
    a = scene.spacing(cfg)
    assert r.max() < a + 0.2 * np.sqrt(3) < cfg["bond"]["r0"]
    assert r.min() > a - 0.2
    assert crossing.any()                       # bonds through the boundary
    assert not np.array_equal(pos[0], pos[1])   # states differ
