"""Bonded terms on ``plan.execute`` (``core.bonded``) against the plain
reference (``bench/reference_bonded.py``: the all-pairs pair term with each
row's bonded partners masked out, plus FENE + WCA over the bond list), on
the benchmark's chain scene at 1,000 beads on the CPU, the Pallas kernel
in interpret mode.

Tolerance: ``max|dF| / max|F_ref|`` and likewise for the potentials under
``TOL`` = 2e-5. Both sides are float32 and differ in summation order and
in how a displacement is formed (the engine's ghost copies against the
reference's minimum images); a displacement's rounding moves the WCA
force as r^-13, and the gaps read 8.0e-6 (forces) and 1.8e-6
(potentials) here. Without the exclusion they read 0.70 and 0.17.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference_bonded
from repro import obs
from repro.core import BondedTerm, Domain, ParticleState, api, bonded, plan

TOL = 2e-5
BOND = {"k": 30.0, "r0": 1.5, "epsilon": 1.0, "sigma": 1.0}
PAIRS = {
    # in.chain's own pair term: the bond's WCA and the excluded pair term
    # are the same function, so the exclusion hides behind it
    "wca": {"kind": "wca", "epsilon": 1.0, "sigma": 1.0, "cutoff": 1.12246,
            "softening": 0.0},
    # full LJ at 2.5: the exclusion takes the attraction out as well, so
    # leaving it out changes the answer
    "lj25": {"kind": "lennard_jones", "epsilon": 1.0, "sigma": 1.0,
             "cutoff": 2.5, "softening": 1e-6},
}
# the benchmark's scene at 1,000 beads: 10 chains of 100 on a 10^3 lattice
CFG = {"sites_per_axis": 10, "atoms": 1000, "density": 0.85,
       "chain_length": 100, "jitter": 0.1}
SCENE = harness.scene("chain_lattice")


def _domain(box: float, cutoff: float) -> Domain:
    n = int(box // cutoff)
    return Domain(box=(box,) * 3, ncells=(n,) * 3, cutoff=cutoff,
                  periodic=True)


def _plan(pair: dict, box: float, backend: str, pos):
    return plan(_domain(box, pair["cutoff"]),
                harness.pair(pair["kind"]).engine(pair), positions=pos,
                strategy="xpencil", backend=backend, interpret=True,
                bonded=BondedTerm(**BOND))


def _reference(pair: dict, pos, bonds, box):
    rows = np.arange(pos.shape[0])
    return reference_bonded.all_pairs(
        pair, harness.pair(pair["kind"]).terms, BOND, (box,) * 3,
        jnp.asarray(pos), reference_bonded.partners(bonds, pos.shape[0]),
        rows, chunk=8)


def _gap(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture(scope="module")
def melt():
    pos, bonds = SCENE.make(CFG, jax.random.PRNGKey(7), 1)
    pos, bonds, box = np.asarray(pos[0]), np.asarray(bonds), SCENE.box(CFG)[0]
    d = pos[bonds[:, 0]] - pos[bonds[:, 1]]
    assert (np.abs(d) > box / 2).any()     # some bonds cross the boundary
    return pos, bonds, box


@pytest.mark.parametrize("padded", [False, True], ids=["all", "padded"])
@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_bonded_execute_matches_the_plain_reference(melt, pair, backend,
                                                    padded):
    pos, bonds, box = melt
    pair = PAIRS[pair]
    f_ref, u_ref = _reference(pair, pos, bonds, box)
    state = ParticleState(jnp.asarray(pos), bonds=jnp.asarray(bonds))
    n = pos.shape[0]
    if padded:
        # padding rows anywhere in the box, bonded to real rows and to
        # each other: every bond that touches one is ignored
        extra = np.random.default_rng(1).uniform(0, box, (24, 3))
        more = np.array([[0, n], [n + 1, 5], [n + 2, n + 3]], np.int32)
        state = ParticleState(
            jnp.asarray(np.concatenate([pos, extra]).astype(np.float32)),
            valid=jnp.arange(n + 24) < n,
            bonds=jnp.asarray(np.concatenate([bonds, more])))
    f, u = _plan(pair, box, backend, pos).execute(state)
    assert _gap(f[:n], f_ref) < TOL
    assert _gap(u[:n], u_ref) < TOL


@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_leaving_out_the_exclusion_misses_the_reference(melt, pair,
                                                        monkeypatch):
    """The same check as above fails by far when the bonded pass subtracts
    nothing for the pair term between bonded ends (a planted fault: no
    exclusion), so it can see a missing one."""
    pos, bonds, box = melt
    pair = PAIRS[pair]
    f_ref, u_ref = _reference(pair, pos, bonds, box)
    state = ParticleState(jnp.asarray(pos), bonds=jnp.asarray(bonds))

    def nothing(kernel, dx, dy, dz, mask, cutoff2):
        return tuple(jnp.zeros_like(dx) for _ in range(4))

    monkeypatch.setattr(bonded, "pair_contribution", nothing)
    api.clear_executor_cache()
    try:
        f, u = _plan(pair, box, "reference", pos).execute(state)
    finally:
        api.clear_executor_cache()      # no program traced with the fault
    assert _gap(f, f_ref) > 1e-2 and _gap(u, u_ref) > 1e-2


def test_a_state_without_bonds_runs_the_pair_path_alone(melt):
    pos, _, box = melt
    pair = PAIRS["wca"]
    state = ParticleState(jnp.asarray(pos))
    bonded = _plan(pair, box, "pallas", pos)
    plain = dataclasses.replace(bonded, bonded=None)
    for a, b in zip(bonded.execute(state), plain.execute(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def program(p):
        return api._executor(p, ()).lower(state).as_text()
    assert program(bonded) == program(plain)


def test_batched_bonded_states_match_one_by_one(melt):
    pos, bonds, box = melt
    p = _plan(PAIRS["lj25"], box, "reference", pos)
    both = np.stack([pos, np.mod(pos + 0.3, box).astype(np.float32)])
    f, u = p.execute_batch(ParticleState(
        jnp.asarray(both), bonds=jnp.asarray(np.stack([bonds, bonds]))))
    for s in range(2):
        f1, u1 = p.execute(ParticleState(jnp.asarray(both[s]),
                                         bonds=jnp.asarray(bonds)))
        np.testing.assert_allclose(np.asarray(f[s]), np.asarray(f1),
                                   rtol=1e-6, atol=1e-4)
        np.testing.assert_allclose(np.asarray(u[s]), np.asarray(u1),
                                   rtol=1e-6, atol=1e-4)


def test_a_bond_stretched_past_r0_is_a_fault_not_a_nan(melt):
    pos, bonds, box = melt
    pos = pos.copy()
    j = CFG["chain_length"] - 1          # the last bond of a chain
    i = j - 1
    pos[j] = np.mod(pos[i] + np.array([1.6, 0.0, 0.0]), box)
    state = ParticleState(jnp.asarray(pos), bonds=jnp.asarray(bonds))
    p = _plan(PAIRS["wca"], box, "reference", pos)
    before = obs.registry.total(api.BOND_FAULT_TOTAL)
    assert p.bond_faults(state) == 1
    f, u = p.execute(state)
    assert np.isfinite(np.asarray(f)).all()
    assert np.isfinite(np.asarray(u)).all()
    _, report = p.execute_checked(state)
    assert report.bond_faults == 1 and report.status == "ok"
    assert obs.registry.total(api.BOND_FAULT_TOTAL) == before + 2


def test_a_bond_past_the_log_floor_is_clamped_as_lammps_does(melt):
    """LAMMPS bond_fene clamps 1 - r^2/R0^2 to 0.1 below 0.1 (r > ~0.949
    R0) and warns: the engine clamps there too and counts a fault, so the
    force stops growing before r reaches R0."""
    term = BondedTerm(**BOND)
    r = np.array([1.40, 1.45, 1.49], np.float32)
    coeff, energy, fault = (np.asarray(a) for a in bonded.fene_terms(
        term, jnp.asarray(r * r)))
    arg = 1.0 - r * r / BOND["r0"] ** 2
    assert arg[0] > bonded.FAULT_LOG_ARG > arg[1] > arg[2]
    assert fault.tolist() == [False, True, True]
    floor = -BOND["k"] / bonded.FAULT_LOG_ARG
    np.testing.assert_allclose(coeff, [-BOND["k"] / arg[0], floor, floor],
                               rtol=1e-6)
    assert energy[1] == energy[2]
    pos, bonds, box = melt
    pos = pos.copy()
    j = CFG["chain_length"] - 1          # the last bond of a chain
    pos[j] = np.mod(pos[j - 1] + np.array([0.0, 1.45, 0.0]), box)
    p = _plan(PAIRS["wca"], box, "reference", pos)
    assert p.bond_faults(ParticleState(jnp.asarray(pos),
                                       bonds=jnp.asarray(bonds))) == 1


@pytest.mark.parametrize("bad", ["index_past_n", "negative_index",
                                 "three_columns", "float_ids",
                                 "duplicate_pair", "reversed_pair"])
def test_a_malformed_bond_list_is_refused(melt, bad):
    """A gather or scatter would clamp or drop a bad index in silence, and
    a pair listed twice would be excluded twice: an index outside [0, N)
    or a repeated pair, in either order, is refused by the host check
    (``bond_faults``, which ``execute_checked`` runs), a list of the wrong
    shape or dtype when the pass is traced."""
    pos, bonds, box = melt
    bonds = bonds.copy()
    if bad == "index_past_n":
        bonds[3, 1] = pos.shape[0]
    elif bad == "negative_index":
        bonds[0, 0] = -1
    elif bad == "three_columns":
        bonds = np.concatenate([bonds, bonds[:, :1]], axis=1)
    elif bad == "float_ids":
        bonds = bonds.astype(np.float32)
    elif bad == "duplicate_pair":
        bonds = np.concatenate([bonds, bonds[7:8]])
    else:
        bonds = np.concatenate([bonds, bonds[7:8, ::-1]])
    state = ParticleState(jnp.asarray(pos), bonds=jnp.asarray(bonds))
    p = _plan(PAIRS["wca"], box, "reference", pos)
    call = p.execute if bad in ("three_columns", "float_ids") \
        else p.bond_faults
    with pytest.raises(ValueError, match="bond"):
        call(state)


def test_bonded_pass_is_scoped_traced_and_counted(melt):
    pos, bonds, box = melt
    p = _plan(PAIRS["wca"], box, "pallas", pos)
    state = ParticleState(jnp.asarray(pos), bonds=jnp.asarray(bonds))
    api.clear_executor_cache()
    with obs.tracing():
        obs.clear()
        text = p.compile(state).as_text()
        names = [s["name"] for s in obs.spans()]
    assert "bonded.pass" in names
    assert re.search(r'op_name="jit\(impl\)/bonded/', text)
    before = obs.registry.total(api.BONDS_TOTAL)
    jax.block_until_ready(p.execute(state))
    assert obs.registry.total(api.BONDS_TOTAL) == before + len(bonds)


def test_a_state_with_bonds_needs_a_bonded_term(melt):
    pos, bonds, box = melt
    p = dataclasses.replace(_plan(PAIRS["wca"], box, "reference", pos),
                            bonded=None)
    with pytest.raises(ValueError, match="bonded term"):
        p.execute(ParticleState(jnp.asarray(pos), bonds=jnp.asarray(bonds)))


def test_trajectory_refuses_a_state_with_bonds(melt):
    pos, bonds, box = melt
    p = _plan(PAIRS["wca"], box, "reference", pos)
    with pytest.raises(ValueError, match="bonds"):
        p.trajectory(ParticleState(jnp.asarray(pos),
                                   bonds=jnp.asarray(bonds)), 2, 0.005)


def test_multi_shard_halo_plan_refuses_a_state_with_bonds(melt):
    pos, bonds, box = melt
    p = _plan(PAIRS["wca"], box, "reference", pos)
    halo = dataclasses.replace(p, backend="halo", halo_inner="reference",
                               n_shards=3, shard_cap=pos.shape[0])
    with pytest.raises(ValueError, match="bonds"):
        halo.execute(ParticleState(jnp.asarray(pos),
                                   bonds=jnp.asarray(bonds)))


def _pass_args(pos, box, pair="lj25"):
    pair = PAIRS[pair]
    return (_domain(box, pair["cutoff"]), harness.pair(pair["kind"]).engine(
        pair), BondedTerm(**BOND))


def _scalar_pass(domain, kernel, term, positions, bonds, valid, forces, pot):
    """The bonded pass as it was before it moved rows: three 1-D gathers
    per bond end and four 1-D scatter-adds onto both ends. The oracle of
    the row pass."""
    i, j = bonds[:, 0], bonds[:, 1]
    d = []
    for a in range(3):
        x = positions[:, a]
        da = x[i] - x[j]
        if domain.periodic_axes[a]:
            da = da - domain.box[a] * jnp.round(da / domain.box[a])
        d.append(da)
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
    live = i != j
    if valid is not None:
        live = live & valid[i] & valid[j]
    coeff, energy, _ = bonded.fene_terms(term, r2)
    coeff = jnp.where(live, coeff, 0.0)
    energy = jnp.where(live, energy, 0.0)
    *fp, up = bonded.pair_contribution(kernel, *d, live,
                                       float(domain.cutoff) ** 2)
    f = [coeff * da - pa for da, pa in zip(d, fp)]
    energy = energy - up
    ends = jnp.concatenate([i, j])
    n = positions.shape[0]

    def to_ends(first, second):
        return jnp.zeros((n,), first.dtype).at[ends].add(
            jnp.concatenate([first, second]))

    df = jnp.stack([to_ends(fa, -fa) for fa in f], axis=-1)
    return forces + df, pot + to_ends(energy, energy)


def _branched():
    """A star of degree 6 across the periodic boundary and a ring of 8,
    bonds ~0.97 long, jittered: beads with 6 and with 2 bonds."""
    rng = np.random.default_rng(3)
    box = 12.0
    centre = np.array([0.2, 6.0, 6.0])
    leaves = centre + 0.97 * np.concatenate([np.eye(3), -np.eye(3)])
    angle = 2 * np.pi * np.arange(8) / 8
    radius = 0.97 / (2 * np.sin(np.pi / 8))
    ring = np.array([6.0, 6.0, 6.0]) + radius * np.stack(
        [np.cos(angle), np.sin(angle), np.zeros(8)], axis=-1)
    pos = np.concatenate([centre[None], leaves, ring])
    pos = np.mod(pos + rng.uniform(-0.05, 0.05, pos.shape), box)
    star = [[0, k] for k in range(1, 7)]
    loop = [[7 + k, 7 + (k + 1) % 8] for k in range(8)]
    return (pos.astype(np.float32), np.array(star + loop, np.int32), box)


def _n_ops(jaxpr, name, shape):
    """Equations of primitive ``name`` whose first output (gather) or
    updates operand (scatter-add) has ``shape``, sub-jaxprs included."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            aval = (eqn.outvars[0] if name == "gather"
                    else eqn.invars[2]).aval
            count += aval.shape == shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _n_ops(sub, name, shape)
    return count


def test_bonded_pass_moves_rows_not_elements(melt):
    """The pass gathers one (B, 3) row per bond end, and no 1-D gather by
    the bond list is left when no ``valid`` mask is given. The adds stay
    four 1-D scatter-adds of (2B,) onto both ends (fx, fy, fz, energy):
    on a TPU v5e one (2B, 4) row scatter-add is slower than the four."""
    pos, bonds, box = melt
    b = bonds.shape[0]
    args = _pass_args(pos, box)
    n = pos.shape[0]
    jaxpr = jax.make_jaxpr(lambda p, bd, f, u: bonded.add_bonded(
        *args, p, bd, None, f, u))(jnp.asarray(pos), jnp.asarray(bonds),
                                   jnp.zeros((n, 3)), jnp.zeros((n,)))
    jaxpr = jaxpr.jaxpr
    assert _n_ops(jaxpr, "gather", (b, 3)) == 2
    assert _n_ops(jaxpr, "gather", (b,)) == 0
    assert _n_ops(jaxpr, "scatter-add", (2 * b,)) == 4


@pytest.mark.parametrize("case", ["chains", "branched", "masked", "vmap"])
def test_row_pass_matches_the_scalar_oracle(melt, case):
    """The row gathers against the scalar ones they replaced: a row's
    elements are the same values the 1-D gathers picked, and the adds are
    the same four scatter-adds, so the pass matches bit for bit, on chains
    and on a branched list (a bead with six bonds), with or without a
    ``valid`` mask, batched or not."""
    if case == "branched":
        pos, bonds, box = _branched()
    else:
        pos, bonds, box = melt
    args = _pass_args(pos, box)
    n = pos.shape[0]
    rng = np.random.default_rng(11)
    forces = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    pot = jnp.asarray(rng.normal(size=(n,)).astype(np.float32))
    valid = None
    if case == "masked":
        valid = jnp.asarray(rng.uniform(size=n) > 0.1)

    def run(fn, p, u):
        return fn(*args, p, jnp.asarray(bonds), valid, forces, u)

    p = jnp.asarray(pos)
    if case == "vmap":
        p = jnp.stack([p, jnp.mod(p + 0.3, box)])
        pot = jnp.stack([pot, -pot])
        got = jax.vmap(lambda q, u: run(bonded.add_bonded, q, u))(p, pot)
        want = jax.vmap(lambda q, u: run(_scalar_pass, q, u))(p, pot)
    else:
        got, want = run(bonded.add_bonded, p, pot), run(_scalar_pass, p, pot)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
