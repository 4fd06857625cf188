"""The source paper's uniform few-particles-per-cell scene: a periodic
cube of ``cells`` cells of width ``cell_width`` per axis, ``per_cell``
particles per cell placed along X at ``(k + 0.5) / per_cell`` of the
cell and moved by a uniform amount in [-jitter, jitter] per axis, so no
two cores overlap. Velocities are normal with ``velocity_scale``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def box(cfg: dict) -> tuple:
    return (cfg["cells"] * cfg["cell_width"],) * 3


def count(cfg: dict) -> int:
    return cfg["cells"] ** 3 * cfg["per_cell"]


@functools.partial(jax.jit, static_argnames=("cells", "per", "width",
                                             "jitter", "vscale", "states"))
def _make(key, *, cells, per, width, jitter, vscale, states):
    n = cells ** 3 * per
    i = jnp.arange(n, dtype=jnp.int32)
    cell, k = i // per, i % per
    cx, cy, cz = cell % cells, (cell // cells) % cells, cell // (cells * cells)
    base = jnp.stack([cx + (k + 0.5) / per, cy + 0.5, cz + 0.5],
                     axis=-1).astype(jnp.float32) * width

    def one(key_):
        kj, kv = jax.random.split(key_)
        pos = base + jax.random.uniform(kj, (n, 3), jnp.float32,
                                        -jitter, jitter)
        return pos, vscale * jax.random.normal(kv, (n, 3), jnp.float32)

    return jax.vmap(one)(jax.random.split(key, states))


def make(cfg: dict, key, states: int):
    """-> positions and velocities, each (states, N, 3) float32, made on
    the device in one call."""
    return _make(key, cells=int(cfg["cells"]), per=int(cfg["per_cell"]),
                 width=float(cfg["cell_width"]), jitter=float(cfg["jitter"]),
                 vscale=float(cfg["velocity_scale"]), states=int(states))
