"""Bonded terms on ``plan.execute``: FENE bonds and the 1-2 exclusion.

A bead-spring polymer (the Kremer-Grest melt of LAMMPS ``bench/in.chain``)
adds to the cutoff pair term a bond list, ``ParticleState.bonds``: a
traced ``(B, 2)`` int32 array of particle indices, each pair listed once.
Each bond carries LAMMPS ``bond_style fene``::

    E(r) = -1/2 K R0^2 ln(1 - r^2 / R0^2)
           + 4 eps [(sigma/r)^12 - (sigma/r)^6] + eps     (r < 2^(1/6) sigma)

and the pair term between the two ends is left out (LAMMPS
``special_bonds fene``: weight 0 on 1-2 pairs, so bonded neighbours do not
also feel the pair term). 1-3 and 1-4 pairs keep weight 1.

The exclusion is a correction in this pass, not a mask in the pair
kernel: for each bond, the pair term the kernel counted between its ends
(``core.interactions.pair_contribution``, the kernel's own arithmetic and
cutoff test) is subtracted. So the kernel and the binning run the
programs they run without bonds.

The pass gathers the positions by rows: one (B, 3) row gather per bond
end, since on a TPU v5e one index costs about as much whether it moves
one value or three (two row gathers of 990,000 bonds take 11.5 ms, the
six 1-D gathers they replace 43.3 ms). The adds onto both ends stay four
1-D scatter-adds: one (2B, 4) row scatter-add of (fx, fy, fz, energy)
took 90.8 ms there against 69.6 ms for the four.

Conventions are the pair term's: the force on ``i`` from ``j`` is
``coeff * (r_i - r_j)`` with minimum-image displacements, and each end's
potential channel gets the bond's whole energy, so ``0.5 *
potential.sum()`` stays the total energy (each end's share is half).
Bonds with an end where ``valid`` is False, and bonds of a particle to
itself, are ignored.

A bond stretched so far that ``1 - r^2/R0^2 < FAULT_LOG_ARG`` (from
r ~ 0.949 R0; at ``r >= R0`` FENE has no energy at all) is a fault, as
LAMMPS warns of it: :func:`bond_faults` counts them
(``InteractionPlan.bond_faults``, ``execute_checked``'s report), and,
like LAMMPS, the pass evaluates its force and energy at that floor, so
the outputs stay finite instead of growing without bound or turning NaN.
:func:`bond_faults` also refuses a list that names one pair twice, in
either order, since each copy would subtract the exclusion again.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .domain import Domain
from .interactions import PairKernel, pair_contribution

Array = jnp.ndarray

# floor of 1 - r^2/R0^2 (LAMMPS bond_fene clamps the log argument to 0.1
# and warns below it; here a bond below it is counted as a fault)
FAULT_LOG_ARG = 0.1


@dataclasses.dataclass(frozen=True)
class BondedTerm:
    """FENE + WCA bonds; the pair term between bonded ends is left out.
    Hashable, part of the plan. Defaults: LAMMPS ``bench/in.chain``
    (``bond_coeff 1 30.0 1.5 1.0 1.0``, ``special_bonds fene``)."""

    k: float = 30.0
    r0: float = 1.5
    epsilon: float = 1.0
    sigma: float = 1.0

    def __post_init__(self):
        if self.r0 <= 0 or self.sigma <= 0 or self.k < 0:
            raise ValueError(f"FENE needs r0 > 0, sigma > 0 and k >= 0, got "
                             f"{self}")


def fene_terms(term: BondedTerm, r2: Array) -> Tuple[Array, Array, Array]:
    """-> (coeff, energy, fault) of bonds at squared length ``r2``: the
    force on the first end is ``coeff * (r_i - r_j)``; ``fault`` marks
    ``1 - r^2/R0^2 < FAULT_LOG_ARG``, where the argument is clamped."""
    arg = 1.0 - r2 / (term.r0 * term.r0)
    fault = arg < FAULT_LOG_ARG
    arg = jnp.where(fault, FAULT_LOG_ARG, arg)
    coeff = -term.k / arg
    energy = -0.5 * term.k * term.r0 * term.r0 * jnp.log(arg)
    rc2 = 2.0 ** (1.0 / 3.0) * term.sigma * term.sigma
    wca = r2 < rc2
    r2s = jnp.where(wca, r2, rc2)
    a6 = (term.sigma * term.sigma / r2s) ** 3
    coeff = coeff + jnp.where(
        wca, 24.0 * term.epsilon * (2.0 * a6 * a6 - a6) / r2s, 0.0)
    energy = energy + jnp.where(
        wca, 4.0 * term.epsilon * (a6 * a6 - a6) + term.epsilon, 0.0)
    return coeff, energy, fault


def _bond_vectors(domain: Domain, positions: Array, bonds: Array):
    """Minimum-image ``r_i - r_j`` of every bond, one array per axis, from
    one (B, 3) row gather per bond end."""
    d = positions[bonds[:, 0]] - positions[bonds[:, 1]]
    box, per = domain.box, domain.periodic_axes
    out = []
    for a in range(3):
        da = d[:, a]
        if per[a]:
            da = da - box[a] * jnp.round(da / box[a])
        out.append(da)
    return out


def _live(bonds: Array, valid) -> Array:
    i, j = bonds[:, 0], bonds[:, 1]
    live = i != j
    if valid is not None:
        live = live & valid[i] & valid[j]
    return live


def add_bonded(domain: Domain, kernel: PairKernel, term: BondedTerm,
               positions: Array, bonds: Array, valid, forces: Array,
               pot: Array) -> Tuple[Array, Array]:
    """The pair path's ``(forces, pot)`` plus the bonds' FENE + WCA minus
    the pair term between bonded ends. Traced inside the plan's program,
    under the ``bonded`` device scope."""
    if bonds.ndim != 2 or bonds.shape[1] != 2 or not jnp.issubdtype(
            bonds.dtype, jnp.integer):
        raise ValueError(f"bonds must be a (B, 2) integer array, got "
                         f"{bonds.dtype}{list(bonds.shape)}")
    with jax.named_scope("bonded"):
        d = _bond_vectors(domain, positions, bonds)
        r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
        live = _live(bonds, valid)
        coeff, energy, _ = fene_terms(term, r2)
        coeff = jnp.where(live, coeff, 0.0)
        energy = jnp.where(live, energy, 0.0)
        *fp, up = pair_contribution(kernel, *d, live,
                                    float(domain.cutoff) ** 2)
        f = [coeff * da - pa for da, pa in zip(d, fp)]
        energy = energy - up
        ends = jnp.concatenate([bonds[:, 0], bonds[:, 1]])
        n = positions.shape[0]

        def to_ends(first, second):
            return jnp.zeros((n,), first.dtype).at[ends].add(
                jnp.concatenate([first, second]))

        df = jnp.stack([to_ends(fa, -fa) for fa in f], axis=-1)
        return forces + df, pot + to_ends(energy, energy)


@functools.partial(jax.jit, static_argnames=("domain", "term"))
def _count_faults(positions, bonds, valid, *, domain, term):
    d = _bond_vectors(domain, positions, bonds)
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
    _, _, fault = fene_terms(term, r2)
    outside = (bonds < 0) | (bonds >= positions.shape[0])
    # one pair named twice, in either order (bonds of a particle to itself
    # are ignored by the pass, so they may repeat)
    i, j = bonds[:, 0], bonds[:, 1]
    lo, hi = jax.lax.sort((jnp.minimum(i, j), jnp.maximum(i, j)), num_keys=2)
    repeated = (lo[1:] == lo[:-1]) & (hi[1:] == hi[:-1]) & (lo[1:] != hi[1:])
    return (jnp.sum(fault & _live(bonds, valid)), jnp.sum(outside),
            jnp.sum(repeated))


def bond_faults(domain: Domain, term: BondedTerm, positions: Array,
                bonds: Array, valid=None) -> int:
    """Live bonds stretched past the log floor (``FAULT_LOG_ARG``; host
    sync). Raises for a bond index outside ``[0, N)``, which the traced
    pass would clamp or drop without a word, and for a pair listed twice,
    which the pass would exclude twice."""
    faults, outside, repeated = _count_faults(positions, bonds, valid,
                                              domain=domain, term=term)
    if int(outside):
        raise ValueError(f"{int(outside)} bond indices lie outside [0, "
                         f"{positions.shape[0]})")
    if int(repeated):
        raise ValueError(f"{int(repeated)} bonds repeat a pair already in "
                         f"the bond list (in either order)")
    return int(faults)
