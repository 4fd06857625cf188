"""The static bounds every entry point settles on, pinned value by value.

Each case builds a plan through a public entry point — ``plan()`` with
omitted bounds, ``replan()`` after a breach of one bound, the
trajectory's ``trajectory_plan`` and its segment loop — on a seeded scene,
and compares every static bound of the result (``m_c``, the allin
``box``, ``row_cap``, ``pair_cap``, ``max_active``, ``shard_cap``) with
the exact values recorded for it. The replan contract (ARCHITECTURE.md,
"Static bounds & the replan contract") decides them; a change that moves
any of them shows here.

Halo plans are built and replanned in process: measuring and growing
their per-shard bounds runs on the host and needs no second device.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.core import Domain, ParticleState, make_lennard_jones, plan
from repro.core.interactions import PairKernel
from repro.traj import trajectory_plan

KERN = make_lennard_jones()


def _bounds(p):
    return {"ncells": p.domain.ncells, "m_c": p.m_c, "box": p.box,
            "row_cap": p.row_cap, "pair_cap": p.pair_cap,
            "max_active": p.max_active, "shard_cap": p.shard_cap}


def _uniform(division=4, n=300, seed=0, periodic=True):
    dom = Domain.cubic(division, cutoff=1.0, periodic=periodic)
    return dom, dom.sample_uniform(jax.random.PRNGKey(seed), n)


def _blob(division=8, n=200, seed=3):
    """A Gaussian blob in one corner of an open box plus four strays:
    a third of the pencils occupied, one crowded cell."""
    dom = Domain.cubic(division, cutoff=1.0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    blob = 2.0 + 0.5 * jax.random.normal(k1, (n - 4, 3), jnp.float32)
    blob = jnp.clip(blob, 0.05, division - 0.05)
    return dom, jnp.concatenate([blob, dom.sample_uniform(k2, 4)])


def _crowded_cell(division=12, n_cell=700, n_spread=4, seed=5):
    """One cell holding hundreds of particles (an m_c large enough to
    shrink the allin sub-box) plus a few strays over an open box."""
    dom = Domain.cubic(division, cutoff=1.0)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    cell = 1.1 + 0.8 * jax.random.uniform(k1, (n_cell, 3), jnp.float32)
    return dom, jnp.concatenate([cell, dom.sample_uniform(k2, n_spread)])


def _clustered(division=4, n=400, seed=1):
    """A quarter of the particles squeezed into one corner cell."""
    dom, pos = _uniform(division, n, seed)
    return dom, jnp.concatenate([pos[:n // 4] * 0.04 + 0.3, pos[n // 4:]])


# --------------------------------------------------------------------------
# plan() with omitted bounds
# --------------------------------------------------------------------------

def _plan_case(scene, **kw):
    dom, pos = scene()
    return plan(dom, KERN, positions=pos, **kw)


PLAN_CASES = {
    "dense": lambda: _plan_case(_uniform, strategy="xpencil"),
    "dense_m_c_slack": lambda: _plan_case(_blob, strategy="xpencil",
                                          m_c_slack=1.0),
    "packed": lambda: _plan_case(_uniform, strategy="xpencil",
                                 layout="packed"),
    "sfc": lambda: _plan_case(_uniform, strategy="cell_dense",
                              layout="sfc"),
    "compact_xpencil": lambda: _plan_case(_blob, strategy="xpencil",
                                          compact=True),
    "compact_allin": lambda: _plan_case(_crowded_cell, strategy="allin",
                                        compact=True),
    "halo": lambda: _plan_case(_uniform, strategy="xpencil",
                               backend="halo", n_shards=2),
    "halo_compact": lambda: _plan_case(_blob, strategy="xpencil",
                                       backend="halo", n_shards=2,
                                       compact=True),
    "halo_sfc": lambda: _plan_case(_uniform, strategy="cell_dense",
                                   layout="sfc", backend="halo",
                                   n_shards=2),
}

# --------------------------------------------------------------------------
# replan() after a breach of one bound
# --------------------------------------------------------------------------


def _replan_case(scene, tight, state_scene=None, valid_rows=None, **kw):
    """Plan on ``scene``'s positions, tighten the bounds in ``tight`` so
    that one is breached, and replan on the same (or ``state_scene``'s)
    positions; ``valid_rows`` marks the rows past it as padding."""
    dom, pos = scene()
    p = plan(dom, KERN, positions=pos, **kw)
    if state_scene is not None:
        _, pos = state_scene()
    valid = None
    if valid_rows is not None:
        valid = jnp.arange(pos.shape[0]) < valid_rows
    state = ParticleState(pos, valid=valid)
    p = dataclasses.replace(p, **tight)
    assert p.check_overflow(state)                  # premise: a breach
    return p.replan(state)


REPLAN_CASES = {
    "m_c": lambda: _replan_case(_uniform, {}, state_scene=_clustered,
                                strategy="xpencil"),
    "m_c_padding_excluded": lambda: _replan_case(
        _uniform, {}, state_scene=_clustered, valid_rows=50,
        strategy="xpencil"),
    "m_c_allin": lambda: _replan_case(_uniform, {}, state_scene=_clustered,
                                      strategy="allin"),
    "row_cap": lambda: _replan_case(_uniform, {"row_cap": 8},
                                    strategy="xpencil", layout="packed"),
    "pair_cap": lambda: _replan_case(_uniform, {"pair_cap": 8},
                                     strategy="cell_dense", layout="sfc"),
    "max_active": lambda: _replan_case(_blob, {"max_active": 2},
                                       strategy="xpencil", compact=True),
    "max_active_allin": lambda: _replan_case(_crowded_cell,
                                             {"max_active": 1},
                                             strategy="allin", compact=True),
    # the sub-box of m_c 8 is coarser than the grown m_c's: the grown
    # plan's max_active is measured on the new tiling
    "m_c_allin_compact": lambda: _replan_case(_crowded_cell,
                                              {"m_c": 8, "box": None},
                                              strategy="allin",
                                              compact=True),
    "halo_m_c": lambda: _replan_case(_uniform, {}, state_scene=_clustered,
                                     strategy="xpencil", backend="halo",
                                     n_shards=2),
    "halo_shard_cap": lambda: _replan_case(_uniform, {"shard_cap": 8},
                                           strategy="xpencil",
                                           backend="halo", n_shards=2),
    "halo_max_active": lambda: _replan_case(_blob, {"max_active": 2},
                                            strategy="xpencil",
                                            backend="halo", n_shards=2,
                                            compact=True),
    "halo_pair_cap": lambda: _replan_case(_uniform, {"pair_cap": 8},
                                          strategy="cell_dense",
                                          layout="sfc", backend="halo",
                                          n_shards=2),
}

# --------------------------------------------------------------------------
# the trajectory: its skin plan, and growth in the segment loop
# --------------------------------------------------------------------------


def _traj_scene():
    dom = Domain.cubic(6, cutoff=1.0, periodic=True)
    return dom, dom.sample_uniform(jax.random.PRNGKey(0), 200)


def _traj_plan_case(with_positions=True, **kw):
    dom, pos = _traj_scene()
    p = plan(dom, make_lennard_jones(sigma=0.3, eps=1e-4), positions=pos,
             **kw)
    return trajectory_plan(p, 0.25, pos if with_positions else None)


TRAJ_PLAN_CASES = {
    "dense": lambda: _traj_plan_case(strategy="xpencil"),
    "dense_unmeasured": lambda: _traj_plan_case(False, strategy="xpencil"),
    "packed": lambda: _traj_plan_case(strategy="xpencil", layout="packed"),
    "compact": lambda: _traj_plan_case(strategy="xpencil", compact=True),
    "compact_allin": lambda: _traj_plan_case(strategy="allin",
                                             compact=True),
}

# no forces: the converging particles move ballistically
FREE = PairKernel("free", lambda r2: 0.0 * r2, lambda r2: 0.0 * r2, flops=1)


def _converging(n_spread=150, n_in=48, seed=7):
    """Particles spread over a periodic box, ``n_in`` of them flying
    toward one point they pass within 0.3 of at t = 0.4: a cell that is
    sparse at the start and crowded mid-run."""
    dom = Domain.cubic(6, cutoff=1.0, periodic=True)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    spread = dom.sample_uniform(k1, n_spread)
    u = jax.random.normal(k2, (n_in, 3), jnp.float32)
    u = u / jnp.linalg.norm(u, axis=1, keepdims=True)
    centre = jnp.full((3,), 2.25, jnp.float32)
    start = (centre + 2.0 * u) % 6.0
    aim = centre + 0.3 * jax.random.uniform(k3, (n_in, 3), jnp.float32,
                                            -1.0, 1.0)
    vel_in = (aim - (centre + 2.0 * u)) / 0.4
    pos = jnp.concatenate([spread, start])
    vel = jnp.concatenate([jnp.zeros_like(spread), vel_in])
    return dom, pos, vel


def _traj_run_case(**kw):
    dom, pos, vel = _converging()
    p = plan(dom, FREE, positions=pos, **kw)
    res = p.trajectory(ParticleState(pos), 64, 0.01, velocities=vel,
                       skin=0.25, segment_len=16)
    assert res.status == "ok" and res.steps == 64
    assert res.replans >= 1                  # premise: grown mid-run
    return res.plan


TRAJ_RUN_CASES = {
    "dense": lambda: _traj_run_case(strategy="xpencil"),
    "packed": lambda: _traj_run_case(strategy="xpencil", layout="packed"),
    "compact": lambda: _traj_run_case(strategy="xpencil", compact=True),
}

CASES = {**{f"plan-{k}": v for k, v in PLAN_CASES.items()},
         **{f"replan-{k}": v for k, v in REPLAN_CASES.items()},
         **{f"traj_plan-{k}": v for k, v in TRAJ_PLAN_CASES.items()},
         **{f"traj_run-{k}": v for k, v in TRAJ_RUN_CASES.items()}}

# The values each case settles on.
EXPECTED = {
    "plan-compact_allin": dict(
        ncells=(12, 12, 12), m_c=1056, box=(6, 6, 4), row_cap=None,
        pair_cap=None, max_active=8, shard_cap=None),
    "plan-compact_xpencil": dict(
        ncells=(8, 8, 8), m_c=48, box=None, row_cap=None, pair_cap=None,
        max_active=24, shard_cap=None),
    "plan-dense": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "plan-dense_m_c_slack": dict(
        ncells=(8, 8, 8), m_c=32, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "plan-halo": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=200),
    "plan-halo_compact": dict(
        ncells=(8, 8, 8), m_c=48, box=None, row_cap=None, pair_cap=None,
        max_active=24, shard_cap=264),
    "plan-halo_sfc": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=None, pair_cap=272,
        max_active=None, shard_cap=200),
    "plan-packed": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=48, pair_cap=None,
        max_active=None, shard_cap=None),
    "plan-sfc": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=None, pair_cap=432,
        max_active=None, shard_cap=None),
    "replan-halo_m_c": dict(
        ncells=(4, 4, 4), m_c=168, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=328),
    "replan-halo_max_active": dict(
        ncells=(8, 8, 8), m_c=48, box=None, row_cap=None, pair_cap=None,
        max_active=24, shard_cap=264),
    "replan-halo_pair_cap": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=None, pair_cap=272,
        max_active=None, shard_cap=200),
    "replan-halo_shard_cap": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=200),
    "replan-m_c": dict(
        ncells=(4, 4, 4), m_c=168, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "replan-m_c_allin": dict(
        ncells=(4, 4, 4), m_c=168, box=(2, 2, 2), row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "replan-m_c_allin_compact": dict(
        ncells=(12, 12, 12), m_c=1056, box=(6, 6, 4), row_cap=None,
        pair_cap=None, max_active=8, shard_cap=None),
    "replan-m_c_padding_excluded": dict(
        ncells=(4, 4, 4), m_c=80, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "replan-max_active": dict(
        ncells=(8, 8, 8), m_c=48, box=None, row_cap=None, pair_cap=None,
        max_active=24, shard_cap=None),
    "replan-max_active_allin": dict(
        ncells=(12, 12, 12), m_c=1056, box=(6, 6, 4), row_cap=None,
        pair_cap=None, max_active=8, shard_cap=None),
    "replan-pair_cap": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=None, pair_cap=432,
        max_active=None, shard_cap=None),
    "replan-row_cap": dict(
        ncells=(4, 4, 4), m_c=16, box=None, row_cap=48, pair_cap=None,
        max_active=None, shard_cap=None),
    "traj_plan-compact": dict(
        ncells=(4, 4, 4), m_c=32, box=None, row_cap=None, pair_cap=None,
        max_active=16, shard_cap=None),
    "traj_plan-compact_allin": dict(
        ncells=(4, 4, 4), m_c=32, box=(2, 2, 2), row_cap=None, pair_cap=None,
        max_active=8, shard_cap=None),
    "traj_plan-dense": dict(
        ncells=(4, 4, 4), m_c=32, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "traj_plan-dense_unmeasured": dict(
        ncells=(4, 4, 4), m_c=32, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "traj_plan-packed": dict(
        ncells=(4, 4, 4), m_c=32, box=None, row_cap=88, pair_cap=None,
        max_active=None, shard_cap=None),
    "traj_run-compact": dict(
        ncells=(4, 4, 4), m_c=80, box=None, row_cap=None, pair_cap=None,
        max_active=16, shard_cap=None),
    "traj_run-dense": dict(
        ncells=(4, 4, 4), m_c=80, box=None, row_cap=None, pair_cap=None,
        max_active=None, shard_cap=None),
    "traj_run-packed": dict(
        ncells=(4, 4, 4), m_c=80, box=None, row_cap=88, pair_cap=None,
        max_active=None, shard_cap=None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_static_bounds_are_pinned(case):
    assert _bounds(CASES[case]()) == EXPECTED[case]
