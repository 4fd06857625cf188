"""A Kremer-Grest melt started on a lattice, in place of LAMMPS's
equilibrated ``data.chain``: linear chains of ``chain_length`` beads on a
simple cubic lattice of ``sites_per_axis`` sites per axis at reduced
``density`` (spacing ``density^(-1/3)``).

The lattice is cut into blocks of ``BLOCK`` sites (5 x 10 x 2 = 100), and
each block is one chain, its sites visited boustrophedon (x rows back and
forth, the rows of a plane back and forth, plane after plane), so every
bond joins lattice neighbours. Bead ids run chain after chain, bonds join
consecutive beads of a chain. Each bead moves by a uniform amount in
[-jitter, jitter] per axis and the whole lattice by a uniform offset in
[0, box) per axis before it is wrapped into the box, so chains cross the
periodic boundary and each seed gives its own state.

Configuration keys: ``sites_per_axis``, ``atoms`` (its cube, stated),
``density``, ``chain_length``, ``jitter``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

BLOCK = (5, 10, 2)            # sites of one chain along x, y, z


def spacing(cfg: dict) -> float:
    return (1.0 / cfg["density"]) ** (1.0 / 3.0)


def box(cfg: dict) -> tuple:
    return (cfg["sites_per_axis"] * spacing(cfg),) * 3


def count(cfg: dict) -> int:
    return cfg["sites_per_axis"] ** 3


def _check(cfg: dict) -> None:
    bx, by, bz = BLOCK
    n = cfg["sites_per_axis"]
    if cfg["chain_length"] != bx * by * bz or n % bx or n % by or n % bz:
        raise ValueError(
            f"chains of {cfg['chain_length']} on {n}^3 sites: the scene "
            f"tiles {bx}x{by}x{bz}-site blocks, one chain each")
    if cfg["atoms"] != n ** 3:
        raise ValueError(f"atoms {cfg['atoms']} but {n}^3 lattice sites")


def _sites(n: int):
    """(n^3, 3) int32 lattice sites in bead order."""
    bx, by, bz = BLOCK
    length = bx * by * bz
    i = jnp.arange(n ** 3, dtype=jnp.int32)
    chain, k = i // length, i % length
    plane, rest = k // (bx * by), k % (bx * by)
    row, col = rest // bx, rest % bx
    y = jnp.where(plane % 2 == 0, row, by - 1 - row)
    x = jnp.where((plane * by + row) % 2 == 0, col, bx - 1 - col)
    cx, cy = n // bx, n // by
    origin = jnp.stack([chain % cx * bx, chain // cx % cy * by,
                        chain // (cx * cy) * bz], axis=-1)
    return origin + jnp.stack([x, y, plane], axis=-1)


@functools.partial(jax.jit, static_argnames=("n", "a", "jitter", "states"))
def _make(key, *, n, a, jitter, states):
    base = (_sites(n).astype(jnp.float32) + 0.5) * a
    side = jnp.float32(n * a)

    def one(k):
        kj, ko = jax.random.split(k)
        shift = jax.random.uniform(ko, (1, 3), jnp.float32, 0.0, side)
        jit = jax.random.uniform(kj, base.shape, jnp.float32, -jitter,
                                 jitter)
        # every coordinate lies in (0, 2 * side): one subtraction, exact
        return jnp.mod(base + jit + shift, side)

    return jax.vmap(one)(jax.random.split(key, states))


def bonds(cfg: dict):
    """(chains x (chain_length - 1), 2) int32: consecutive beads of each
    chain, on the device."""
    _check(cfg)
    length = cfg["chain_length"]
    chains = count(cfg) // length
    first = (jnp.arange(chains, dtype=jnp.int32)[:, None] * length
             + jnp.arange(length - 1, dtype=jnp.int32)[None, :]).reshape(-1)
    return jnp.stack([first, first + 1], axis=-1)


def make(cfg: dict, key, states: int):
    """-> positions (states, N, 3) float32 and the bond list, made on the
    device."""
    _check(cfg)
    pos = _make(key, n=int(cfg["sites_per_axis"]), a=spacing(cfg),
                jitter=float(cfg["jitter"]), states=int(states))
    return pos, bonds(cfg)
