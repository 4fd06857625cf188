"""An fcc lattice as LAMMPS ``lattice fcc <density>`` + ``create_atoms``
builds it, with velocities as ``velocity all create <T> <seed>`` makes
them (uniform components, zero net momentum, scaled to T over 3N - 3
degrees of freedom, unit mass).

Configuration keys: ``unit_cells`` (per axis), ``density`` (reduced),
``temperature`` (reduced), ``displacement`` (each atom moved by a uniform
amount in [-d, d] per axis, in sigma).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BASIS = ((0.0, 0.0, 0.0), (0.5, 0.5, 0.0), (0.5, 0.0, 0.5), (0.0, 0.5, 0.5))


def lattice_constant(cfg: dict) -> float:
    return (len(BASIS) / cfg["density"]) ** (1.0 / 3.0)


def box(cfg: dict) -> tuple:
    side = cfg["unit_cells"] * lattice_constant(cfg)
    return (side,) * 3


def count(cfg: dict) -> int:
    return len(BASIS) * cfg["unit_cells"] ** 3


@functools.partial(jax.jit, static_argnames=("n", "a", "disp", "temp",
                                             "states"))
def _make(key, *, n, a, disp, temp, states):
    cell = jnp.stack(jnp.meshgrid(*(jnp.arange(n, dtype=jnp.float32),) * 3,
                                  indexing="ij"), axis=-1).reshape(-1, 1, 3)
    base = ((cell + jnp.asarray(BASIS, jnp.float32)[None]) * a).reshape(-1, 3)
    side = jnp.float32(n * a)
    dof = 3 * base.shape[0] - 3

    def one(k):
        kp, kv = jax.random.split(k)
        pos = base + jax.random.uniform(kp, base.shape, jnp.float32,
                                        -disp, disp)
        v = jax.random.uniform(kv, base.shape, jnp.float32, -0.5, 0.5)
        v = v - v.mean(axis=0)
        v = v * jnp.sqrt(dof * temp / jnp.sum(v * v))
        return jnp.mod(pos, side), v

    return jax.vmap(one)(jax.random.split(key, states))


def make(cfg: dict, key, states: int):
    """-> positions and velocities, each (states, N, 3) float32, made on
    the device in one call."""
    return _make(key, n=int(cfg["unit_cells"]), a=lattice_constant(cfg),
                 disp=float(cfg["displacement"]),
                 temp=float(cfg["temperature"]), states=int(states))


def temperature(velocities, mass: float = 1.0) -> float:
    """Kinetic temperature over 3N - 3 degrees of freedom, as LAMMPS
    reports it."""
    v = np.asarray(velocities, np.float64)
    return float(mass * np.sum(v * v) / (3 * v.shape[0] - 3))
