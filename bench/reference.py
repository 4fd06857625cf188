"""The plain reference: cutoff pair forces and velocity Verlet in
straightforward ``jax.numpy``, independent of the engine under test.

It imports nothing of ``repro`` and takes nothing the engine made. A pair
term is a dict of its parameters (``bench/configs/*.json`` ``"pair"``,
with at least ``cutoff``) and its formula ``terms(pair, r2) -> (coeff,
potential)`` from ``bench/pairs/<kind>.py``. The force on i from j is
``coeff(r2) * (r_i - r_j)`` and each particle's potential is the sum of
``U(r_ij)`` over its partners, so every pair is counted on both sides.
A pair counts where ``0 < r2 < cutoff^2``; displacements take periodic
minimum images (every box side is over twice the cutoff).

Two evaluations:
- ``all_pairs``: every source for a set of target rows (no binning at all);
- ``grid_forces``: every particle, over the 27 neighbour cells of a cell
  grid this module builds itself; used where every particle is needed
  (the velocity-Verlet reference, the control).

``pair_dtype`` is the precision of the pair arithmetic: displacements are
formed in float32, then r^2, the pair term and its sums run in
``pair_dtype``. float32 is the reference; bfloat16 is the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _terms(pair: dict, terms, d, mask, pair_dtype, axis):
    """Masked pair sums over ``axis`` of displacements ``d`` (3 arrays)."""
    d = [x.astype(pair_dtype) for x in d]
    r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
    near = mask & (r2 < jnp.asarray(pair["cutoff"] ** 2, pair_dtype)) \
        & (r2 > 0)
    r2s = jnp.where(near, r2, jnp.ones_like(r2))
    coeff, pot = terms(pair, r2s)
    zero = jnp.zeros_like(r2)
    s = jnp.where(near, coeff, zero)
    u = jnp.where(near, pot, zero)
    f = jnp.stack([(s * x).sum(axis) for x in d], axis=-1)
    return f.astype(F32), u.sum(axis).astype(F32), near.sum(axis)


def min_image(d, box):
    return d - box * jnp.round(d / box)


@functools.partial(jax.jit, static_argnames=("pair_items", "terms", "chunk",
                                             "pair_dtype"))
def _all_pairs(positions, targets, box, *, pair_items, terms, chunk,
               pair_dtype):
    pair = dict(pair_items)
    n = positions.shape[0]
    src = [positions[:, a] for a in range(3)]
    ids = jnp.arange(n)

    def rows(t):                      # (chunk,) target indices
        tp = positions[t]
        d = [min_image(tp[:, a:a + 1] - src[a][None, :], box[a])
             for a in range(3)]
        mask = ids[None, :] != t[:, None]
        f, u, _ = _terms(pair, terms, d, mask, pair_dtype, axis=1)
        return f, u

    f, u = jax.lax.map(rows, targets.reshape(-1, chunk))
    return f.reshape(-1, 3), u.reshape(-1)


def all_pairs(pair: dict, terms, box, positions, targets, *,
              chunk: int = 16, pair_dtype=F32):
    """Forces (T, 3) and potentials (T,) of the ``targets`` rows, summed
    over every other particle. ``len(targets)`` is a multiple of
    ``chunk``."""
    return _all_pairs(positions, jnp.asarray(targets, jnp.int32),
                      jnp.asarray(box, F32),
                      pair_items=tuple(sorted(pair.items())), terms=terms,
                      chunk=chunk, pair_dtype=jnp.dtype(pair_dtype))


# --------------------------------------------------------------------------
# every particle, over a cell grid of its own
# --------------------------------------------------------------------------

def grid_cells(box, cutoff: float) -> tuple:
    """Cells per axis: as many as fit with a width of at least the
    cutoff, and at least 3 so that no neighbour cell is counted twice."""
    cells = tuple(int(np.floor(b / cutoff + 1e-9)) for b in box)
    if min(cells) < 3:
        raise ValueError(f"box {box} holds fewer than 3 cells of width "
                         f">= {cutoff} per axis")
    return cells


@functools.partial(jax.jit, static_argnames=("cells",))
def _cell_of(positions, box, *, cells):
    n = jnp.asarray(cells, jnp.int32)
    c = jnp.floor(positions / (box / n)).astype(jnp.int32) % n
    return (c[:, 2] * n[1] + c[:, 1]) * n[0] + c[:, 0]


def slots_needed(positions, box, cells) -> int:
    """The most particles in one cell, rounded up to 8 (host sync)."""
    lin = _cell_of(positions, jnp.asarray(box, F32), cells=tuple(cells))
    most = int(jnp.bincount(lin, length=int(np.prod(cells))).max())
    return -(-most // 8) * 8


@functools.partial(jax.jit, static_argnames=("pair_items", "terms", "cells",
                                             "slots", "pair_dtype"))
def _grid(positions, box, *, pair_items, terms, cells, slots, pair_dtype):
    pair = dict(pair_items)
    nx, ny, nz = cells
    n = positions.shape[0]
    lin = _cell_of(positions, box, cells=cells)
    order = jnp.argsort(lin)
    counts = jnp.bincount(lin, length=nx * ny * nz)
    start = jnp.cumsum(counts) - counts
    slot = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32) - start[lin[order]])
    idx = jnp.full((nx * ny * nz, slots), -1, jnp.int32).at[lin, slot].set(
        jnp.arange(n, dtype=jnp.int32))
    idx = idx.reshape(nz, ny, nx, slots)
    pos = jnp.where(idx[..., None] >= 0, positions[jnp.maximum(idx, 0)], 0.0)

    def plane(z):                     # forces of the cells of z-plane z
        tp, ti = pos[z], idx[z]       # (ny, nx, slots, 3), (ny, nx, slots)
        f = jnp.zeros((ny, nx, slots, 3), F32)
        u = jnp.zeros((ny, nx, slots), F32)
        cnt = jnp.zeros((ny, nx, slots), jnp.int32)
        for dz in (-1, 0, 1):
            sp_z, si_z = pos[(z + dz) % nz], idx[(z + dz) % nz]
            for dy in (-1, 0, 1):
                for dx in (-1, 0, 1):
                    sp = jnp.roll(sp_z, (-dy, -dx), axis=(0, 1))
                    si = jnp.roll(si_z, (-dy, -dx), axis=(0, 1))
                    d = [min_image(tp[..., :, None, a] - sp[..., None, :, a],
                                    box[a]) for a in range(3)]
                    mask = ((ti[..., :, None] >= 0) & (si[..., None, :] >= 0)
                            & (ti[..., :, None] != si[..., None, :]))
                    df, du, dc = _terms(pair, terms, d, mask, pair_dtype,
                                         axis=-1)
                    f, u, cnt = f + df, u + du, cnt + dc
        return f, u, cnt

    f, u, cnt = jax.lax.map(plane, jnp.arange(nz))
    f = f.reshape(-1, slots, 3)[lin, slot]
    u = u.reshape(-1, slots)[lin, slot]
    return f, u, cnt.sum(dtype=jnp.int32)


def grid_forces(pair: dict, terms, box, positions, *, pair_dtype=F32):
    """Forces (N, 3), potentials (N,) and the number of ordered pairs
    within the cutoff, for every particle."""
    cells = grid_cells(box, pair["cutoff"])
    slots = slots_needed(positions, box, cells)
    return _grid(positions, jnp.asarray(box, F32),
                 pair_items=tuple(sorted(pair.items())), terms=terms,
                 cells=cells, slots=slots, pair_dtype=jnp.dtype(pair_dtype))


@functools.partial(jax.jit, static_argnames=("half",))
def _drift(positions, velocities, forces, box, dt, half):
    v = velocities + half * forces
    return jnp.mod(positions + dt * v, box), v


def velocity_verlet(pair: dict, terms, box, positions, velocities, *,
                    dt: float, steps: int, mass: float = 1.0, pair_dtype=F32):
    """``steps`` velocity-Verlet steps, with the start forces computed
    here -> (positions, velocities, forces, potentials) after the last."""
    boxa = jnp.asarray(box, F32)
    f, u, _ = grid_forces(pair, terms, box, positions,
                          pair_dtype=pair_dtype)
    half = 0.5 * dt / mass
    for _ in range(steps):
        positions, v = _drift(positions, velocities, f, boxa, dt, half)
        f, u, _ = grid_forces(pair, terms, box, positions,
                          pair_dtype=pair_dtype)
        velocities = v + half * f
    return positions, velocities, f, u
