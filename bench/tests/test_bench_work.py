"""The yardstick on the CPU at tiny sizes: the pair count and the two
reference evaluations against a brute-force sum in numpy, and the in.lj
generator's density, momentum and temperature."""

import numpy as np
import pytest

from bench import harness, reference


def _brute(pair, box, pos):
    """Forces, potentials and ordered pair count in float64 numpy."""
    box = np.asarray(box)
    d = pos[:, None, :] - pos[None, :, :]
    d -= box * np.round(d / box)
    r2 = (d * d).sum(-1)
    near = (r2 < pair["cutoff"] ** 2) & (r2 > 0)
    r2s = np.where(near, r2, 1.0) + pair["softening"]
    a6 = (pair["sigma"] ** 2 / r2s) ** 3
    c = np.where(near, 24 * pair["epsilon"] * (2 * a6 * a6 - a6) / r2s, 0)
    u = np.where(near, 4 * pair["epsilon"] * (a6 * a6 - a6), 0)
    return (c[..., None] * d).sum(1), u.sum(1), int(near.sum())


@pytest.fixture(scope="module")
def scene():
    cfg = dict(harness.config("lammps_inlj"), unit_cells=5)
    sc = harness.scene(cfg["scene"])
    pos, vel = sc.make(cfg, harness._key(3), 1)
    return cfg, sc, np.asarray(pos[0]), np.asarray(vel[0])


TERMS = harness.pair("lennard_jones").terms


def test_pairs_and_references_match_brute_force(scene):
    cfg, sc, pos, _ = scene
    pair, box = cfg["pair"], sc.box(cfg)
    f0, u0, n0 = _brute(pair, box, pos.astype(np.float64))
    f, u, n = reference.grid_forces(pair, TERMS, box, pos)
    assert int(n) == n0 > 0
    scale = np.abs(f0).max()
    assert np.abs(np.asarray(f) - f0).max() / scale < 1e-5
    assert np.abs(np.asarray(u) - u0).max() / np.abs(u0).max() < 1e-5
    rows = np.arange(0, pos.shape[0], 5)[:96]
    fa, ua = reference.all_pairs(pair, TERMS, box, pos, rows)
    assert np.abs(np.asarray(fa) - f0[rows]).max() / scale < 1e-5
    assert np.abs(np.asarray(ua) - u0[rows]).max() / np.abs(u0).max() < 1e-5


def test_bfloat16_pair_arithmetic_is_far_from_float32(scene):
    cfg, sc, pos, _ = scene
    pair, box = cfg["pair"], sc.box(cfg)
    f32 = np.asarray(reference.grid_forces(pair, TERMS, box, pos)[0])
    b16 = np.asarray(reference.grid_forces(pair, TERMS, box, pos,
                                           pair_dtype="bfloat16")[0])
    assert np.abs(b16 - f32).max() / np.abs(f32).max() > 1e-3


def test_velocity_verlet_conserves_energy(scene):
    cfg, sc, pos, vel = scene
    pair, box = cfg["pair"], sc.box(cfg)
    _, _, _, u0 = reference.velocity_verlet(pair, TERMS, box, pos, vel,
                                            dt=0.005, steps=0)
    x, v, _, u = reference.velocity_verlet(pair, TERMS, box, pos, vel,
                                           dt=0.005, steps=20)
    e0 = 0.5 * (vel ** 2).sum() + 0.5 * float(np.sum(u0))
    e1 = 0.5 * float((np.asarray(v) ** 2).sum()) + 0.5 * float(np.sum(u))
    # the unshifted cutoff makes the energy jump as pairs cross it, so
    # the bound is loose; a broken kick or drift misses it by far
    assert abs(e1 - e0) / abs(e0) < 1e-2
    assert np.abs(np.asarray(x) - pos).max() > 1e-3


def test_inlj_generator_density_momentum_temperature(scene):
    cfg, sc, pos, vel = scene
    n = sc.count(cfg)
    assert pos.shape == (n, 3) == (4 * 5 ** 3, 3)
    assert n / np.prod(sc.box(cfg)) == pytest.approx(0.8442, rel=1e-9)
    assert np.abs(vel.mean(axis=0)).max() < 1e-6
    assert sc.temperature(vel) == pytest.approx(1.44, rel=1e-5)
    assert pos.min() >= 0 and pos.max() < sc.box(cfg)[0]


def test_paper_generator_two_per_cell():
    cfg = dict(harness.config("paper_ppc2"), cells=5)
    sc = harness.scene(cfg["scene"])
    pos = np.asarray(sc.make(cfg, harness._key(9), 1)[0][0])
    cell = np.floor(pos).astype(int)
    lin = (cell[:, 2] * 5 + cell[:, 1]) * 5 + cell[:, 0]
    assert (np.bincount(lin, minlength=125) == 2).all()
