"""Lennard-Jones 12-6 cut at ``cutoff`` and shifted to 0 there (LAMMPS
``pair_style lj/cut`` with ``pair_modify shift yes``), with ``softening``
added to r^2 before the powers. At a cutoff of 2^(1/6) sigma this is the
WCA repulsion of a Kremer-Grest melt. Parameters (``bench/configs/*.json``
``"pair"``): ``epsilon``, ``sigma``, ``cutoff``, ``softening``.
"""

import pathlib

from bench import harness

_lj = harness.module(pathlib.Path(__file__).resolve().parent
                     / "lennard_jones.py")


def engine(pair: dict):
    """The engine's pair kernel for these parameters."""
    from repro.core import make_lennard_jones
    return make_lennard_jones(sigma=pair["sigma"], eps=pair["epsilon"],
                              softening=pair["softening"],
                              shift_at=pair["cutoff"])


def terms(pair: dict, r2):
    """(coeff, potential) of the pair at squared distance ``r2`` (r2 > 0),
    in r2's dtype: the force on i from j is ``coeff * (r_i - r_j)``."""
    import jax.numpy as jnp
    coeff, pot = _lj.terms(pair, r2)
    _, at_cutoff = _lj.terms(pair, jnp.asarray(pair["cutoff"] ** 2, r2.dtype))
    return coeff, pot - at_cutoff
