"""Pairwise interaction kernels.

Central-force form shared by every scheduling strategy and the Pallas kernels:

    F_ij = coeff(r2) * (r_i - r_j)        (force on target i from source j)
    U_i  = sum_j potential(r2)            (per-particle potential channel)

``coeff``/``potential`` receive a *masked-safe* r2 (strategies replace the r2
of excluded pairs by 1.0 before calling, then zero the contribution), so
kernels never have to defend against r2 == 0 or inf.

The three benchmark kernels reproduce the paper's Figure 8 sweep:
  * ``low_flop``   ~5 FLOP/interaction  (paper's fake kernel: position sums)
  * ``lennard_jones`` 21 FLOP/interaction, arithmetic intensity ~0.4 FLOP/byte
  * ``high_flop``  ~168 FLOP/interaction (LJ + 150 extra FLOP)

``flops`` is bookkeeping metadata used by the benchmarks and the roofline
model (the paper's own counting convention: distance + kernel, sqrt = 1 FLOP).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class PairKernel:
    """A cutoff pair interaction. Hashable; safe to close over under jit.

    Hash/eq are *value-based* on ``(name, flops, static_params)`` rather than
    identity, so two ``make_lennard_jones()`` calls produce equal kernels and
    hit the same jit trace / ``_cached_plan`` entry instead of retracing.
    Factories must fold every behaviour-affecting argument into
    ``static_params`` — equal tuples promise equal ``coeff``/``potential``.
    """

    name: str
    coeff: Callable[[Array], Array]
    potential: Callable[[Array], Array]
    flops: int  # per-interaction FLOP count, paper's convention
    static_params: Tuple = ()  # factory args that define coeff/potential

    def __hash__(self):
        return hash((self.name, self.flops, self.static_params))

    def __eq__(self, other):
        if not isinstance(other, PairKernel):
            return NotImplemented
        return (self.name, self.flops, self.static_params) == \
            (other.name, other.flops, other.static_params)


def _lj_terms(r2: Array, sigma2: float, eps: float):
    inv = sigma2 / r2
    a6 = inv * inv * inv
    a12 = a6 * a6
    return a6, a12


def make_lennard_jones(sigma: float = 0.2, eps: float = 1.0,
                       softening: float = 1e-6,
                       shift_at: Optional[float] = None) -> PairKernel:
    """Lennard-Jones 12-6 with the paper's softening against random overlaps.

    ``shift_at``: subtract the potential's value at that distance (LAMMPS
    ``pair_modify shift yes`` with ``shift_at`` = the cutoff), so the
    potential reaches 0 at the cutoff; the force is unchanged. Cut and
    shifted at 2^(1/6) sigma this is the WCA repulsion. Off by default.
    """
    sigma2 = sigma * sigma

    def coeff(r2):
        r2 = r2 + softening
        a6, a12 = _lj_terms(r2, sigma2, eps)
        return 24.0 * eps * (2.0 * a12 - a6) / r2

    def potential(r2):
        r2 = r2 + softening
        a6, a12 = _lj_terms(r2, sigma2, eps)
        return 4.0 * eps * (a12 - a6)

    if shift_at is None:
        return PairKernel("lennard_jones", coeff, potential, flops=21,
                          static_params=(sigma, eps, softening))
    offset = float(potential(shift_at * shift_at))

    def shifted(r2):
        return potential(r2) - offset

    return PairKernel("lennard_jones", coeff, shifted, flops=22,
                      static_params=(sigma, eps, softening, shift_at))


def make_low_flop() -> PairKernel:
    """~5 FLOP: the paper's memory-bound probe (sums, no divisions)."""

    def coeff(r2):
        return r2 * 0.5

    def potential(r2):
        return r2 + 1.0

    return PairKernel("low_flop", coeff, potential, flops=5)


def make_high_flop(extra_terms: int = 25, sigma: float = 0.2,
                   eps: float = 1.0, softening: float = 1e-6) -> PairKernel:
    """LJ + ``6 * extra_terms`` FLOP of r2-dependent polynomial work
    (25 terms -> +150 FLOP -> 168 total, matching the paper's Figure 8)."""
    lj = make_lennard_jones(sigma, eps, softening)

    def extra(r2):
        acc = r2
        for k in range(extra_terms):  # 6 FLOP per term, not foldable: uses r2
            acc = acc * 0.9999 + r2 * (1e-3 * (k + 1)) + 1e-7
            acc = acc * 1.0001
        return acc * 1e-30  # keep magnitude negligible, dependency real

    def coeff(r2):
        return lj.coeff(r2) + extra(r2)

    def potential(r2):
        return lj.potential(r2) + extra(r2)

    return PairKernel("high_flop", coeff, potential,
                      flops=21 + 6 * extra_terms,
                      static_params=(extra_terms, sigma, eps, softening))


def make_gravity(g: float = 1.0, softening: float = 1e-4) -> PairKernel:
    """Softened attractive 1/r2 (Nyland et al.'s n-body kernel, §8)."""

    def coeff(r2):
        d = r2 + softening
        return -g * jax.lax.rsqrt(d) / d

    def potential(r2):
        return -g * jax.lax.rsqrt(r2 + softening)

    return PairKernel("gravity", coeff, potential, flops=14,
                      static_params=(g, softening))


def make_sph_density(h: float) -> PairKernel:
    """Cubic-spline SPH density accumulation (potential channel = sum of W).

    W(q) = s * (1 - 3/2 q^2 + 3/4 q^3)   for 0 <= q < 1
         = s/4 * (2 - q)^3               for 1 <= q < 2,   q = r / (h/2)

    using smoothing length h/2 so the support radius equals the cell cutoff h
    (the paper's 30-40 neighbor SPH regime).
    """
    hh = h / 2.0
    s = 1.0 / (jnp.pi * hh ** 3)

    def potential(r2):
        q = jnp.sqrt(r2) / hh
        w1 = 1.0 - 1.5 * q * q + 0.75 * q ** 3
        w2 = 0.25 * (2.0 - q) ** 3
        w = jnp.where(q < 1.0, w1, jnp.where(q < 2.0, w2, 0.0))
        return s * w

    def coeff(r2):
        # grad W / r (central-force coefficient) for the pressure pipeline.
        q = jnp.sqrt(jnp.maximum(r2, 1e-12)) / hh
        g1 = -3.0 * q + 2.25 * q * q
        g2 = -0.75 * (2.0 - q) ** 2
        g = jnp.where(q < 1.0, g1, jnp.where(q < 2.0, g2, 0.0))
        r = jnp.maximum(jnp.sqrt(r2), 1e-12)
        return s * g / (hh * r)

    return PairKernel("sph_density", coeff, potential, flops=18,
                      static_params=(h,))


KERNELS: Dict[str, Callable[[], PairKernel]] = {
    "lennard_jones": make_lennard_jones,
    "low_flop": make_low_flop,
    "high_flop": make_high_flop,
    "gravity": make_gravity,
}


def pair_contribution(kernel: PairKernel, dx: Array, dy: Array, dz: Array,
                      mask: Array, cutoff2: float):
    """Masked force coefficient + potential for a batch of candidate pairs.

    Returns (fx, fy, fz, pot); excluded pairs contribute exactly 0 with no
    NaN/Inf leakage (masked-safe r2 substitution).
    """
    r2 = dx * dx + dy * dy + dz * dz
    m = mask & (r2 < cutoff2) & (r2 > 0.0)
    r2_safe = jnp.where(m, r2, 1.0)
    w = m.astype(dx.dtype)
    s = kernel.coeff(r2_safe) * w
    pot = kernel.potential(r2_safe) * w
    return s * dx, s * dy, s * dz, pot
