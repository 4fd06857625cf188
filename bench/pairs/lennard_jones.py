"""Lennard-Jones 12-6, not shifted, with ``softening`` added to r^2 before
the powers. Parameters (``bench/configs/*.json`` ``"pair"``): ``epsilon``,
``sigma``, ``cutoff``, ``softening``.

A pair term file gives the engine's pair kernel (``engine``) and the
plain reference's formula (``terms``); the harness finds it by the
``kind`` a configuration names.
"""


def engine(pair: dict):
    """The engine's pair kernel for these parameters."""
    from repro.core import make_lennard_jones
    return make_lennard_jones(sigma=pair["sigma"], eps=pair["epsilon"],
                              softening=pair["softening"])


def terms(pair: dict, r2):
    """(coeff, potential) of the pair at squared distance ``r2`` (r2 > 0),
    in r2's dtype: the force on i from j is ``coeff * (r_i - r_j)``."""
    import jax.numpy as jnp
    dt = r2.dtype
    eps = jnp.asarray(pair["epsilon"], dt)
    s2 = jnp.asarray(pair["sigma"] ** 2, dt)
    r2 = r2 + jnp.asarray(pair["softening"], dt)
    inv = s2 / r2
    a6 = inv * inv * inv
    a12 = a6 * a6
    return (jnp.asarray(24.0, dt) * eps * (2 * a12 - a6) / r2,
            jnp.asarray(4.0, dt) * eps * (a12 - a6))
