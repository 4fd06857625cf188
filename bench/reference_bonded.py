"""The plain reference of a bead-spring melt (LAMMPS ``bench/in.chain``):
the cutoff pair term summed over every other particle with each row's
bonded partners masked out, plus FENE + WCA over the bond list.

Like ``reference.py`` it imports nothing of ``repro`` and takes nothing
the engine made: no kernels, binning or cells. The exclusion is a mask of
the partners a row's bonds name (LAMMPS ``special_bonds fene``: weight 0
on 1-2 pairs), not a subtraction, so it does not share the engine's
method. Conventions are ``reference.py``'s: the force on i from j is
``coeff * (r_i - r_j)`` by minimum images, and each particle's potential
sums every term it takes part in, pair or bond, so the total energy is
half the sum.

``bond`` is a dict with ``k``, ``r0``, ``epsilon``, ``sigma`` (LAMMPS
``bond_coeff`` of ``bond_style fene``). A bond at ``r >= R0`` has no
energy and gives NaN here, and LAMMPS clamps one past ~0.949 R0: a state
with one is not a melt.

- ``partners``: each row's bonded partners, a table built on the host;
- ``all_pairs``: forces and potentials of seeded target rows;
- ``bond_rows``: the bonds' part of rows, or the bonds minus the pair term
  between bonded ends, for the control, which adds it to ``reference.
  grid_forces`` (``control_bonded.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference import F32, _terms, min_image


def partners(bonds, n: int) -> np.ndarray:
    """(n, most bonds of one particle) int32: row i lists the particles
    bonded to i, padded with -1."""
    b = np.asarray(bonds, np.int64)
    ends = np.concatenate([b[:, 0], b[:, 1]])
    other = np.concatenate([b[:, 1], b[:, 0]])
    order = np.argsort(ends, kind="stable")
    ends, other = ends[order], other[order]
    counts = np.bincount(ends, minlength=n)
    first = np.cumsum(counts) - counts
    rank = np.arange(ends.size) - first[ends]
    table = np.full((n, max(1, int(counts.max(initial=0)))), -1, np.int32)
    table[ends, rank] = other
    return table


def fene(bond: dict, r2):
    """(coeff, energy) of FENE + WCA at squared length ``r2``, in r2's
    dtype: -1/2 K R0^2 ln(1 - r^2/R0^2), plus 4 eps [(s/r)^12 - (s/r)^6]
    + eps below r = 2^(1/6) s."""
    dt = r2.dtype
    k = jnp.asarray(bond["k"], dt)
    r02 = jnp.asarray(bond["r0"] ** 2, dt)
    eps = jnp.asarray(bond["epsilon"], dt)
    s2 = jnp.asarray(bond["sigma"] ** 2, dt)
    arg = 1 - r2 / r02
    coeff = -k / arg
    energy = jnp.asarray(-0.5, dt) * k * r02 * jnp.log(arg)
    wca = r2 < jnp.asarray(2.0 ** (1.0 / 3.0), dt) * s2
    a6 = (s2 / r2) ** 3
    coeff = coeff + jnp.where(
        wca, jnp.asarray(24.0, dt) * eps * (2 * a6 * a6 - a6) / r2, 0)
    energy = energy + jnp.where(
        wca, jnp.asarray(4.0, dt) * eps * (a6 * a6 - a6) + eps, 0)
    return coeff, energy


def _bond_sums(pair, terms, bond, tp, pp, live, box, minus_pair, pair_dtype):
    """Bond terms of rows ``tp`` (R, 3) with partners ``pp`` (R, D, 3)."""
    d = [min_image(tp[:, None, a] - pp[..., a], box[a]).astype(pair_dtype)
         for a in range(3)]
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
    r2s = jnp.where(live, r2, jnp.ones_like(r2))
    coeff, energy = fene(bond, r2s)
    zero = jnp.zeros_like(r2)
    coeff, energy = jnp.where(live, coeff, zero), jnp.where(live, energy, zero)
    f = jnp.stack([(coeff * x).sum(1) for x in d], axis=-1).astype(F32)
    u = energy.sum(1).astype(F32)
    if minus_pair:
        fp, up, _ = _terms(pair, terms, d, live, pair_dtype, axis=1)
        f, u = f - fp, u - up
    return f, u


@functools.partial(jax.jit, static_argnames=(
    "pair_items", "bond_items", "terms", "minus_pair", "pair_dtype"))
def _bond_rows(positions, table, rows, box, *, pair_items, bond_items,
               terms, minus_pair, pair_dtype):
    p = table[rows]
    return _bond_sums(dict(pair_items), terms, dict(bond_items),
                      positions[rows], positions[jnp.maximum(p, 0)], p >= 0,
                      box, minus_pair, pair_dtype)


def bond_rows(pair: dict, terms, bond: dict, box, positions, table, rows, *,
              minus_pair: bool = False, pair_dtype=F32):
    """Forces (R, 3) and potentials (R,) of the bonds of ``rows``; with
    ``minus_pair``, less the pair term between the bonded ends."""
    with jax.default_matmul_precision("highest"):
        return _bond_rows(positions, jnp.asarray(table),
                          jnp.asarray(rows, jnp.int32),
                          jnp.asarray(box, F32),
                          pair_items=tuple(sorted(pair.items())),
                          bond_items=tuple(sorted(bond.items())),
                          terms=terms, minus_pair=minus_pair,
                          pair_dtype=jnp.dtype(pair_dtype))


@functools.partial(jax.jit, static_argnames=(
    "pair_items", "bond_items", "terms", "chunk"))
def _all_pairs(positions, table, targets, box, *, pair_items, bond_items,
               terms, chunk):
    pair, bond = dict(pair_items), dict(bond_items)
    n = positions.shape[0]
    src = [positions[:, a] for a in range(3)]
    ids = jnp.arange(n)

    def rows(t):                      # (chunk,) target indices
        tp, p = positions[t], table[t]
        d = [min_image(tp[:, a:a + 1] - src[a][None, :], box[a])
             for a in range(3)]
        other = ids[None, :] != t[:, None]
        bonded = (ids[None, None, :] == p[:, :, None]).any(axis=1)
        f, u, _ = _terms(pair, terms, d, other & ~bonded, F32, axis=1)
        fb, ub = _bond_sums(pair, terms, bond, tp,
                            positions[jnp.maximum(p, 0)], p >= 0, box,
                            False, F32)
        return f + fb, u + ub

    f, u = jax.lax.map(rows, targets.reshape(-1, chunk))
    return f.reshape(-1, 3), u.reshape(-1)


def all_pairs(pair: dict, terms, bond: dict, box, positions, table,
              targets, *, chunk: int = 16):
    """Forces (T, 3) and potentials (T,) of the ``targets`` rows: the pair
    term over every other particle but the bonded partners, plus the rows'
    bonds. ``table`` is ``partners(bonds,
    N)``; ``len(targets)`` is a multiple of ``chunk``."""
    with jax.default_matmul_precision("highest"):
        return _all_pairs(positions, jnp.asarray(table),
                          jnp.asarray(targets, jnp.int32),
                          jnp.asarray(box, F32),
                          pair_items=tuple(sorted(pair.items())),
                          bond_items=tuple(sorted(bond.items())),
                          terms=terms, chunk=chunk)
