"""Compatibility engine API over the plan/execute layer (``core.api``).

New code should use the plan/execute API directly:

    p = plan(domain, make_lennard_jones(), positions=pos,
             strategy="auto", backend="pallas")
    forces, potential = p.execute(ParticleState(pos))

``CellListEngine`` and ``compute_interactions`` below are thin shims kept so
pre-existing call sites keep working unchanged; each one owns exactly one
:class:`~repro.core.api.InteractionPlan` and delegates to it. ``m_c`` and
the strategy/backend are static; everything else is traced.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import bounds
from .api import InteractionPlan, ParticleState, plan as make_plan
from .binning import CellBins, bin_particles, cell_counts
from .domain import Domain
from .interactions import PairKernel, make_lennard_jones

Array = jnp.ndarray


def suggest_m_c(domain: Domain, positions,
                slack: float = bounds.SLACK["m_c"],
                align: int = bounds.ALIGN) -> int:
    """One-off (outside jit) M_C choice: the fullest cell with slack (so the
    bound survives a few integration steps before a re-bin needs a larger
    one), rounded *up* to a sublane multiple — always, since
    ``kernels/xpencil.py`` takes sublane-aligned slices as an invariant."""
    return bounds.size(int(jnp.max(cell_counts(domain, positions))), slack,
                       align)


class CellListEngine:
    """Cutoff pair-interaction engine over a uniform cell grid (shim)."""

    def __init__(self, domain: Domain, kernel: Optional[PairKernel] = None,
                 m_c: int = 8, strategy: str = "xpencil",
                 batch_size: int = 64, jit: bool = True,
                 backend: str = "reference"):
        self.plan = make_plan(domain, kernel or make_lennard_jones(),
                              m_c=m_c, strategy=strategy, backend=backend,
                              batch_size=batch_size)
        self._jit = jit

    # -- plan attributes, mirrored for old call sites ------------------------

    @property
    def domain(self) -> Domain:
        return self.plan.domain

    @property
    def kernel(self) -> PairKernel:
        return self.plan.kernel

    @property
    def m_c(self) -> int:
        return self.plan.m_c

    @property
    def strategy(self) -> str:
        return self.plan.strategy

    @property
    def batch_size(self) -> int:
        return self.plan.batch_size

    # -- pipeline ------------------------------------------------------------

    def bin(self, positions: Array, fields: Dict[str, Array] | None = None
            ) -> CellBins:
        return bin_particles(self.domain, positions, fields, m_c=self.m_c)

    def compute(self, positions: Array) -> Tuple[Array, Array]:
        """-> (forces (N, 3), per-particle potential (N,)).

        Total potential energy = 0.5 * potential.sum() (each pair counted
        twice, the paper's convention)."""
        state = ParticleState(positions)
        if not self._jit:
            with jax.disable_jit():
                return self.plan.execute(state)
        return self.plan.execute(state)

    def check_m_c(self, positions: Array) -> bool:
        """True if the current M_C bound still holds for these positions."""
        return not self.plan.check_overflow(ParticleState(positions))


def _interior_to_padded(domain: Domain, plane: Array, m_c: int) -> Array:
    """Deprecated alias; see ``binning.interior_to_padded``."""
    from .binning import interior_to_padded
    return interior_to_padded(domain, plane, m_c)


@functools.lru_cache(maxsize=None)
def _cached_plan(domain: Domain, kernel: PairKernel, m_c: int,
                 strategy: str, batch_size: int) -> InteractionPlan:
    return make_plan(domain, kernel, m_c=m_c, strategy=strategy,
                     batch_size=batch_size)


def compute_interactions(domain: Domain, positions: Array,
                         kernel: Optional[PairKernel] = None,
                         m_c: Optional[int] = None,
                         strategy: str = "xpencil",
                         batch_size: int = 64) -> Tuple[Array, Array]:
    """Functional one-shot API (plans cached by static config)."""
    kernel = kernel or make_lennard_jones()
    if m_c is None:
        m_c = suggest_m_c(domain, positions)
    p = _cached_plan(domain, kernel, m_c, strategy, batch_size)
    return p.execute(ParticleState(positions))
