"""Fault-tolerant fused trajectory engine (``repro.traj``).

The acceptance contracts of the trajectory tentpole:

* **parity** — with ``skin=0`` the fused scan is bit-identical to a
  per-step ``plan.execute`` loop (the fig_traj pre-timing gate);
* **skin reuse** — with a positive skin, rebins are rare (``<< n_steps``)
  and the physics stays within float tolerance of the baseline;
* **resume** — an interrupted checkpointed run, resumed, lands on a final
  state bit-identical to the uninterrupted run (dense AND packed);
* **resilience** — injected NaN rolls back to the last checkpointed
  anchor and recovers finite; transient errors retry; stragglers finish;
  a crashed checkpoint write never corrupts the directory.
"""

import dataclasses
import os
import pathlib
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, recompile_count, reset_health
from repro.core.api import ParticleState
from repro.core.domain import Domain, effective_skin, skin_domain
from repro.core.interactions import make_lennard_jones
from repro.ckpt import checkpoint as ckpt
from repro.physics.integrators import MDState, init_state, run as integ_run
from repro.serve import TrajectoryRequest, TrajectoryService
from repro.testing import chaos
from repro.traj import (classify_breach, init_monitors, reference_step,
                        run_trajectory, trajectory_plan)
from repro.traj import monitors as M

DT = 1e-3


@pytest.fixture(autouse=True)
def _fresh_health():
    reset_health()
    yield
    reset_health()


@pytest.fixture(scope="module")
def setup():
    dom = Domain.cubic(6, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 200)
    vel = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (200, 3),
                                  jnp.float32)
    kern = make_lennard_jones(sigma=0.3, eps=1e-4)
    p = api.plan(dom, kern, positions=pos)
    return dom, pos, vel, kern, p


def _baseline(p, md0, n_steps, integrator="velocity_verlet"):
    step = jax.jit(reference_step(p, integrator=integrator))
    md = md0
    for _ in range(n_steps):
        md = step(md, DT)
    return md


def _bitwise(a: MDState, b: MDState):
    for f in ("positions", "velocities", "forces", "potential"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


# ---------------------------------------------------------------------------
# parity + skin contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("integrator", ["velocity_verlet", "leapfrog"])
def test_skin0_bitwise_parity(setup, integrator):
    """skin=0 forces a rebin every step; the fused scan must then match
    the eager per-step plan.execute loop bit for bit."""
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    res = run_trajectory(p, md0, 24, DT, integrator=integrator, skin=0.0,
                         segment_len=8)
    assert res.status == "ok"
    assert res.rebins == 24            # every step re-binned
    assert res.steps == 24
    _bitwise(res.state, _baseline(p, md0, 24, integrator))


def test_skin_reuse_few_rebins(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    res = run_trajectory(p, md0, 200, DT, skin=0.25, segment_len=16)
    assert res.status == "ok"
    assert res.rebins < 200 // 10      # rebins << n_steps
    assert res.eff_skin > 0
    md = _baseline(p, md0, 200)
    np.testing.assert_allclose(res.state.positions, md.positions,
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(res.state.velocities, md.velocities,
                               atol=1e-4, rtol=1e-4)
    assert len(res.traces["total"]) == 200


def test_trajectory_plan_coarsens(setup):
    dom, pos, vel, kern, p = setup
    tp = trajectory_plan(p, 0.25, pos)
    assert all(a <= b for a, b in zip(tp.domain.ncells, dom.ncells))
    assert tp.domain.cutoff == dom.cutoff
    assert effective_skin(tp.domain) >= 0.25 - 1e-6
    assert tp.m_c >= p.m_c             # coarser cells hold more particles
    assert skin_domain(dom, 0.0) is dom


def test_langevin_gamma0_matches_verlet(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    ra = run_trajectory(p, md0, 20, DT, integrator="langevin", gamma=0.0,
                        skin=0.0, segment_len=8)
    rb = run_trajectory(p, md0, 20, DT, skin=0.0, segment_len=8)
    np.testing.assert_allclose(ra.state.positions, rb.state.positions,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# the plans a trajectory takes, and how their bounds grow
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("entry", ["trajectory", "trajectory_plan",
                                   "traj_plan"])
def test_trajectory_refuses_an_sfc_plan(setup, entry):
    """The segment monitors keep no pair-list length, so a pair_cap
    outgrown mid-run would go unseen: every way into the trajectory
    refuses ``layout="sfc"`` by name instead of running it."""
    dom, pos, vel, kern, p = setup
    sfc = api.plan(dom, kern, positions=pos, strategy="cell_dense",
                   layout="sfc")
    with pytest.raises(ValueError, match='layout="sfc"'):
        if entry == "trajectory":
            sfc.trajectory(ParticleState(pos), 8, DT)
        elif entry == "trajectory_plan":
            trajectory_plan(sfc, 0.25, pos)
        else:
            run_trajectory(p, pos, 8, DT, traj_plan=sfc)


def test_trajectory_plan_sizes_without_counting_a_replan(setup):
    """Measuring the skin plan's bounds from positions is planning, not a
    replan of the base plan: the bounds cover the positions and the
    replan counter does not move."""
    dom, pos, vel, kern, p = setup
    blob = (0.45 * jax.random.normal(jax.random.PRNGKey(2), (200, 3),
                                     jnp.float32) + 2.25) % 6.0
    before = api.replan_count()
    sparse = trajectory_plan(p, 0.25, pos)
    tp = trajectory_plan(p, 0.25, blob)
    assert tp.m_c > sparse.m_c
    assert not tp.check_overflow(ParticleState(blob))
    assert api.replan_count() == before


def test_segment_growth_sizes_max_active_on_the_plans_tiling():
    """Between segments the monitors report maxima only. When m_c and
    max_active of a compacted allin plan grow together, max_active is
    sized on the plan's own tiling: 12 sub-boxes of 6x6x4 on a 12^3 grid
    cover the 11 seen active (the m_c=1 tiling has 8)."""
    from repro.core import bounds
    dom = Domain.cubic(12, cutoff=1.0)
    p = api.plan(dom, make_lennard_jones(), m_c=1056, strategy="allin",
                 compact=True, max_active=8)
    assert p.box == (6, 6, 4) and api.n_units(dom, "allin") == 8
    grown = bounds.grow(p, {"m_c": 1100, "max_active": 11})
    assert grown.m_c > 1100 and grown.max_active == 12


# ---------------------------------------------------------------------------
# checkpoint / resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan_kw", [
    {},                                               # dense allin/auto
    {"strategy": "xpencil", "layout": "packed"},      # packed CSR rows
], ids=["dense", "packed"])
def test_resume_bit_identical(setup, tmp_path, plan_kw):
    """Interrupt at step 32 of 64, resume from the checkpoint: the final
    state must be bit-identical to the uninterrupted run."""
    dom, pos, vel, kern, _ = setup
    p = api.plan(dom, kern, positions=pos, **plan_kw)
    md0 = init_state(p, pos, vel)
    kw = dict(skin=0.25, segment_len=8, checkpoint_every=16, seed=7)

    full = run_trajectory(p, md0, 64, DT, **kw)       # uninterrupted
    assert full.status == "ok"

    d = tmp_path / "ck"
    part = run_trajectory(p, md0, 32, DT, checkpoint_dir=d, **kw)
    assert part.status == "ok" and part.checkpoints >= 1
    assert ckpt.latest_step(d) == 32

    res = run_trajectory(p, md0, 64, DT, checkpoint_dir=d, resume=True,
                         **kw)
    assert res.resumed_from == 32
    assert res.steps == 64
    _bitwise(res.state, full.state)
    # resumed traces cover only the replayed half
    assert len(res.traces["total"]) == 32


def test_resume_refuses_mismatched_config(setup, tmp_path):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    d = tmp_path / "ck"
    run_trajectory(p, md0, 16, DT, skin=0.25, segment_len=8,
                   checkpoint_dir=d, checkpoint_every=8)
    with pytest.raises(ValueError, match="refusing to resume"):
        run_trajectory(p, md0, 32, DT, skin=0.25, segment_len=8,
                       checkpoint_dir=d, integrator="leapfrog")


# ---------------------------------------------------------------------------
# fault injection: rollback, retry, straggler, checkpoint crash
# ---------------------------------------------------------------------------

def test_injected_nan_rolls_back_and_recovers(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    clean = run_trajectory(p, md0, 32, DT, skin=0.25, segment_len=8)
    with chaos.inject(chaos.FaultSpec("traj.step", "nonfinite", p=1.0,
                                      after=1, max_fires=1), seed=3):
        res = run_trajectory(p, md0, 32, DT, skin=0.25, segment_len=8)
    assert res.status == "ok"
    assert res.rollbacks >= 1
    assert res.forced_rebins >= 1      # recovery rebins counted apart
    assert any(f.startswith("breach:nonfinite") for f in res.faults)
    assert res.steps == 32
    assert bool(jnp.all(jnp.isfinite(res.state.positions)))
    assert bool(jnp.all(jnp.isfinite(res.state.velocities)))
    np.testing.assert_allclose(res.state.positions, clean.state.positions,
                               atol=1e-5, rtol=1e-5)


def test_transient_error_retries_bitwise(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    clean = run_trajectory(p, md0, 24, DT, skin=0.25, segment_len=8)
    with chaos.inject(chaos.FaultSpec("traj.step", "error", p=1.0,
                                      after=1, max_fires=2), seed=5):
        res = run_trajectory(p, md0, 24, DT, skin=0.25, segment_len=8)
    assert res.status in ("ok", "degraded")
    assert res.retries == 2
    assert res.steps == 24
    # a retried segment replays identical arithmetic
    _bitwise(res.state, clean.state)


def test_straggler_delay_completes(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    naps = []
    with chaos.inject(chaos.FaultSpec("traj.step", "delay", p=1.0,
                                      max_fires=2, param=0.5), seed=1):
        res = run_trajectory(p, md0, 16, DT, skin=0.25, segment_len=8,
                             sleep=naps.append)
    assert res.status == "ok" and res.steps == 16
    assert naps == [0.5, 0.5]          # delays observed, run unharmed


def test_checkpoint_crash_never_kills_run(setup, tmp_path):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    d = tmp_path / "ck"
    with chaos.inject(chaos.FaultSpec("traj.checkpoint", "error", p=1.0,
                                      max_fires=1), seed=2):
        res = run_trajectory(p, md0, 32, DT, skin=0.25, segment_len=8,
                             checkpoint_dir=d, checkpoint_every=8)
    assert res.status == "ok" and res.steps == 32
    assert any(f.startswith("checkpoint:") for f in res.faults)
    # later checkpoints still landed
    assert res.checkpoints >= 1
    assert ckpt.latest_step(d) == 32


def test_forced_overflow_recorded(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    with chaos.inject(chaos.FaultSpec("traj.rebin", "overflow", p=1.0,
                                      max_fires=1), seed=4):
        res = run_trajectory(p, md0, 16, DT, skin=0.25, segment_len=8)
    assert res.status == "ok" and res.steps == 16
    assert "overflow:injected" in res.faults


def test_initial_overflow_replans(setup):
    """A skin plan measured on sparse positions must grow its bounds when
    handed a clustered initial state (the grow-only replan contract)."""
    from repro.core.interactions import make_low_flop
    dom, pos, vel, kern, p = setup
    # bounded kernel: overlapping blob particles must not blow up the
    # dynamics (this test is about bounds, not LJ stiffness)
    base = api.plan(dom, make_low_flop(), positions=pos)
    sparse = trajectory_plan(base, 0.25, pos)
    # center the blob mid-cell of the coarsened grid so one cell takes
    # the bulk of it (a boundary-centered blob splits eight ways)
    blob = (0.45 * jax.random.normal(jax.random.PRNGKey(2), (200, 3),
                                     jnp.float32) + 2.25) % 6.0
    assert sparse.check_overflow(ParticleState(blob))   # premise
    res = run_trajectory(base, blob, 8, 1e-6, segment_len=8, skin=0.25,
                         traj_plan=sparse)
    assert res.status == "ok"
    assert res.replans >= 1
    assert res.plan.m_c > sparse.m_c


def test_energy_budget_breach_fails_to_anchor(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    res = run_trajectory(p, md0, 16, DT, skin=0.25, segment_len=8,
                         energy_budget=0.0, max_rollbacks=1)
    assert res.status == "failed"
    assert res.steps < 16
    assert any(f.startswith("breach:energy") for f in res.faults)
    # the reported state is the last committed healthy anchor
    assert bool(jnp.all(jnp.isfinite(res.state.positions)))


# ---------------------------------------------------------------------------
# monitors
# ---------------------------------------------------------------------------

def test_monitor_energy_convention_matches_e0():
    """The drift monitor must use the same halved-PE (pair-counted-twice)
    convention as the ``e0`` seed: identical state in, zero drift out.
    Regression: update() once re-summed the raw per-particle potential
    un-halved, so any nonzero-PE run breached a finite energy budget."""
    pot = jnp.array([2.0, 4.0], jnp.float32)           # pair-counted twice
    vel = jnp.ones((2, 3), jnp.float32)
    ke = 0.5 * jnp.sum(vel ** 2)
    pe = 0.5 * jnp.sum(pot)
    assert float(pe) != 0.0                             # premise
    mon = init_monitors(ke + pe)
    mon2 = M.update(mon, positions=jnp.zeros((2, 3)), velocities=vel,
                    forces=jnp.zeros((2, 3)), potential=pot, valid=None,
                    kinetic=ke, potential_energy=pe,
                    step_disp=jnp.float32(0.0), eff_skin=0.5,
                    cell_max=jnp.int32(1), row_max=jnp.int32(0),
                    units=jnp.int32(0))
    assert float(mon2.max_drift) == 0.0


def test_energy_budget_healthy_run_not_breached(setup):
    """A healthy run with nonzero PE and a generous finite budget must
    complete without spurious energy breaches or rollbacks."""
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    assert float(jnp.sum(md0.potential)) != 0.0         # premise
    res = run_trajectory(p, md0, 32, DT, skin=0.25, segment_len=8,
                         energy_budget=1e-2)
    assert res.status == "ok"
    assert res.rollbacks == 0
    assert not any(f.startswith("breach:energy") for f in res.faults)


def test_classify_breach_ordering():
    prev = jax.device_get(init_monitors(jnp.float32(1.0)))
    cur = dataclasses.replace(prev, nonfinite_steps=np.int32(1),
                              skin_steps=np.int32(1),
                              max_drift=np.float32(9.0))
    assert classify_breach(prev, cur, energy_budget=0.1) == "nonfinite"
    cur2 = dataclasses.replace(cur, nonfinite_steps=np.int32(0))
    assert classify_breach(prev, cur2, energy_budget=0.1) == "skin"
    cur3 = dataclasses.replace(cur2, skin_steps=np.int32(0))
    assert classify_breach(prev, cur3, energy_budget=0.1) == "energy"
    assert classify_breach(prev, cur3, energy_budget=None) is None
    assert classify_breach(prev, prev, energy_budget=0.1) is None


# ---------------------------------------------------------------------------
# ckpt.save atomicity audit
# ---------------------------------------------------------------------------

def test_ckpt_crash_before_commit_preserves_old(tmp_path):
    """A crash inside save (before the atomic rename) must leave the
    previous checkpoint of the same step intact and restorable."""
    d = tmp_path / "ck"
    tree = {"x": jnp.arange(8.0)}
    ckpt.save(d, 5, tree, extra={"gen": 1})
    with chaos.inject(chaos.FaultSpec("ckpt.save", "error", p=1.0),
                      seed=0):
        with pytest.raises(chaos.TransientBackendError):
            ckpt.save(d, 5, {"x": jnp.arange(8.0) * 2}, extra={"gen": 2})
    assert ckpt.latest_step(d) == 5
    restored, extra = ckpt.restore(d, tree)
    np.testing.assert_array_equal(restored["x"], np.arange(8.0))
    assert extra == {"gen": 1}
    # no temp litter survives the failed save's cleanup
    assert not [f for f in os.listdir(d) if f.startswith(".tmp_")]


def test_ckpt_sweep_repairs_dead_writers(tmp_path):
    """Hard-kill debris: a dead writer's .tmp dir is deleted and its
    .old_<pid>_<step> move-aside is renamed back when the new step never
    committed."""
    d = tmp_path / "ck"
    ckpt.save(d, 3, {"x": jnp.zeros(4)})
    dead = 2 ** 22 + 12345             # no such pid
    # emulate a kill after the move-aside, before the commit rename
    os.replace(d / "step_00000003", d / f".old_{dead}_00000003")
    (d / f".tmp_{dead}_junk").mkdir()
    assert ckpt.latest_step(d) is None
    handled = ckpt.sweep_stale(d)
    assert handled == 2
    assert ckpt.latest_step(d) == 3    # old checkpoint restored
    assert not (d / f".tmp_{dead}_junk").exists()
    # live writers' temp dirs are left alone
    mine = d / f".tmp_{os.getpid()}_busy"
    mine.mkdir()
    assert ckpt.sweep_stale(d) == 0
    assert mine.exists()


def test_ckpt_resave_over_stale_old_dir(tmp_path):
    """A leftover .old_<pid>_<step> dir (partial cleanup / pid reuse) must
    not make a later save of the same step fail with ENOTEMPTY on the
    move-aside rename."""
    d = tmp_path / "ck"
    ckpt.save(d, 7, {"x": jnp.zeros(4)})
    stale = d / f".old_{os.getpid()}_00000007"   # own pid: sweep skips it
    stale.mkdir()
    (stale / "junk.npy").write_bytes(b"x")
    ckpt.save(d, 7, {"x": jnp.ones(4)})
    restored, _ = ckpt.restore(d, {"x": jnp.zeros(4)})
    np.testing.assert_array_equal(restored["x"], np.ones(4))
    assert not stale.exists()


def test_pid_alive_eperm_means_alive(monkeypatch):
    """EPERM from kill(pid, 0) means the process exists (another user's):
    sweep_stale must not treat a live foreign writer as dead."""
    from repro.ckpt.checkpoint import _pid_alive

    def eperm(pid, sig):
        raise PermissionError

    def esrch(pid, sig):
        raise ProcessLookupError

    monkeypatch.setattr(os, "kill", eperm)
    assert _pid_alive(12345) is True
    monkeypatch.setattr(os, "kill", esrch)
    assert _pid_alive(12345) is False


def test_ckpt_kill_mid_save_subprocess(tmp_path):
    """Actual SIGKILL mid-save: whatever instant the writer dies at,
    latest_step/restore only ever see intact checkpoints."""
    import subprocess
    import sys
    d = tmp_path / "ck"
    code = (
        "import sys, numpy as np, jax.numpy as jnp, os\n"
        "sys.path.insert(0, %r)\n"
        "from repro.ckpt import checkpoint as ckpt\n"
        "tree = {'x': jnp.arange(200000.0)}\n"
        "ckpt.save(%r, 1, tree)\n"
        "print('committed', flush=True)\n"
        "for i in range(2, 50):\n"
        "    ckpt.save(%r, i, tree)\n"
    ) % (str(pathlib.Path("src").resolve()), str(d), str(d))
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE)
    proc.stdout.readline()             # first checkpoint committed
    proc.kill()
    proc.wait()
    last = ckpt.latest_step(d)
    assert last is not None and last >= 1
    restored, _ = ckpt.restore(d, {"x": jnp.arange(200000.0)})
    assert restored["x"].shape == (200000,)
    ckpt.sweep_stale(d)                # and the debris sweeps clean
    assert not [f for f in os.listdir(d) if f.startswith(".tmp_")]


# ---------------------------------------------------------------------------
# integrators.run port
# ---------------------------------------------------------------------------

def test_integrators_run_routes_through_trajectory(setup):
    dom, pos, vel, kern, p = setup
    md0 = init_state(p, pos, vel)
    state, traces = integ_run(p, md0, 24, DT, skin=0.0, segment_len=8)
    assert traces["total"].shape == (24,)
    _bitwise(state, _baseline(p, md0, 24))


def test_integrators_run_legacy_rejects_traj_opts(setup):
    from repro.core.engine import CellListEngine
    dom, pos, vel, kern, p = setup
    eng = CellListEngine(dom, kern, m_c=8)
    md0 = init_state(eng, pos, vel)
    with pytest.raises(ValueError, match="legacy per-step scan"):
        integ_run(eng, md0, 4, DT, skin=0.25)
    # integrators the legacy scan does not implement must raise, not
    # silently fall back to leapfrog
    with pytest.raises(ValueError, match="legacy per-step scan"):
        integ_run(eng, md0, 4, DT, integrator="langevin")
    with pytest.raises(ValueError, match="legacy per-step scan"):
        integ_run(eng, md0, 4, DT, integrator="nope")
    state, traces = integ_run(eng, md0, 4, DT)   # legacy path still runs
    assert traces["total"].shape == (4,)


# ---------------------------------------------------------------------------
# serving front door
# ---------------------------------------------------------------------------

def test_trajectory_service_warm_class_and_padding(setup):
    dom, pos, vel, kern, p = setup
    svc = TrajectoryService(skin=0.25)
    req = TrajectoryRequest("job-a", dom, kern,
                            ParticleState(pos[:150]), 16, DT,
                            velocities=vel[:150],
                            opts={"segment_len": 8})
    ra = svc.submit(req)
    assert ra.status == "ok" and ra.n == 150
    assert ra.state.positions.shape == (150, 3)

    # same shape class (150 and 180 both pad to 256): zero recompiles
    before = recompile_count()
    rb = svc.submit(TrajectoryRequest(
        "job-b", dom, kern, ParticleState(pos[:180]), 16, DT,
        velocities=vel[:180], opts={"segment_len": 8}))
    assert rb.status == "ok"
    assert recompile_count() == before
    assert svc.jobs_served == 2

    # padded execution matches the unpadded engine (masked pad rows bin
    # to nothing; real rows see identical pair sets)
    base150 = api.plan(dom, kern, positions=pos[:150])
    direct = run_trajectory(base150, ParticleState(pos[:150]), 16, DT,
                            velocities=vel[:150], skin=0.25,
                            segment_len=8)
    np.testing.assert_allclose(ra.state.positions, direct.state.positions,
                               atol=1e-6, rtol=1e-6)


def test_trajectory_service_resume(setup, tmp_path):
    dom, pos, vel, kern, p = setup
    svc = TrajectoryService(skin=0.25, checkpoint_root=tmp_path / "jobs")
    req = TrajectoryRequest("job-r", dom, kern, ParticleState(pos), 32,
                            DT, velocities=vel,
                            opts={"segment_len": 8,
                                  "checkpoint_every": 16})
    first = svc.submit(req)
    assert first.status == "ok" and first.result.checkpoints >= 1
    again = svc.submit(req)            # resubmission resumes, no rerun
    assert again.result.resumed_from == 32
    assert again.result.steps == 32
    _bitwise(again.state, first.state)
