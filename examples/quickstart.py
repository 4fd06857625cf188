"""Quickstart: cutoff pair interactions through the plan/execute API.

    PYTHONPATH=src python examples/quickstart.py

Builds the paper's benchmark scene (uniform particles, LJ kernel, cell width
= cutoff), plans every schedule x backend combination — including the two
proposed in the paper (All-in-SM, X-pencil) as Pallas TPU kernels (interpret
mode on CPU) — and cross-checks all of them against the O(N^2) oracle
through the same ``plan(...).execute(state)`` front door.
"""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (Domain, ParticleState, backend_matrix,
                        make_lennard_jones, plan, supports_layout)


def main():
    domain = Domain.cubic(division=6, cutoff=1.0)
    key = jax.random.PRNGKey(0)
    positions = domain.sample_uniform(key, 2_000)
    kernel = make_lennard_jones(sigma=0.2)
    state = ParticleState(positions)

    # one-off static planning: measures M_C, and "auto" picks the schedule
    # with the least modelled HBM traffic per interaction
    auto = plan(domain, kernel, positions=positions, strategy="auto")
    print(f"grid {domain.ncells}, N={positions.shape[0]}, M_C={auto.m_c}, "
          f'auto -> "{auto.strategy}"')

    oracle = plan(domain, kernel, m_c=auto.m_c, strategy="naive_n2")
    f_ref, pot_ref = oracle.execute(state)
    e_ref = 0.5 * float(jnp.sum(pot_ref))
    fscale = float(jnp.max(jnp.abs(f_ref)))
    print(f"naive_n2 oracle          : E = {e_ref:+.4e}")

    for backend, strategies in sorted(backend_matrix().items()):
        for strategy in strategies:
            # some pairs exist only under a non-dense layout (the pallas
            # cell_dense runner is the sfc cluster kernel)
            layout = ("dense" if supports_layout(backend, strategy, "dense")
                      else "sfc")
            p = plan(domain, kernel, m_c=auto.m_c, strategy=strategy,
                     backend=backend, layout=layout, positions=positions)
            forces, pot = p.execute(state)
            err = float(jnp.max(jnp.abs(forces - f_ref))) / fscale
            tag = strategy if layout == "dense" else f"{strategy}/{layout}"
            print(f"{backend:9s} {tag:14s}: "
                  f"E = {0.5 * float(jnp.sum(pot)):+.4e} rel|dF| = {err:.2e}")
            np.testing.assert_allclose(np.asarray(forces) / fscale,
                                       np.asarray(f_ref) / fscale,
                                       rtol=3e-4, atol=3e-4)

    # the M_C safety net: many executes, replan only when a cell overflows
    (forces, _), p2 = auto.execute_or_replan(state)
    assert p2 is auto, "uniform scene should not need a replan"
    print("all schedules x backends agree; overflow check passed.")


if __name__ == "__main__":
    main()
