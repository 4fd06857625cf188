"""Fault-tolerant trajectory engine: fused multi-step simulation.

The tentpole of ROADMAP item 1. A per-step ``plan.execute`` loop pays a
full binning (and pack) pass plus a Python dispatch every timestep; this
engine fuses bin -> force -> integrate under one jitted ``lax.scan`` and
amortizes the binning with a Verlet-skin contract:

* the trajectory runs on a *skin-padded* grid (``domain.skin_domain``:
  cell width >= cutoff + skin, same cutoff — pair masks are unchanged, so
  results stay pair-complete for the true cutoff),
* bins are built once and their slot assignment reused; each step only
  *refreshes* slot contents in place (``binning.refresh_bins``),
* a traced predicate (``binning.max_displacement`` against the measured
  ``skin / 2``) re-bins inside the scan (``lax.cond``) only when drift
  has eaten the margin.

``skin = 0`` is the always-rebin limit: the grid is the plan's own and a
rebin fires whenever anything moved, which makes the fused path
*bit-identical* to the per-step ``plan.execute`` loop (``reference_step``
shares the integrator arithmetic) — the parity gate
``benchmarks/fig_traj.py`` runs before timing anything.

Robustness (the reason this lives in one subsystem): the scan runs in
host-bounded *segments* cut on a fixed absolute grid. Each segment
carries the invariant monitors of ``traj.monitors`` in the scan carry;
at the segment boundary the host

1. classifies breaches (non-finite state, skin thrash, energy drift past
   budget — ``monitors.classify_breach``) and **rolls back** to the last
   committed anchor with a forced rebin, stepping the plan's degradation
   ladder via the PR 7 circuit breaker (``api.plan_health``) on repeated
   failure;
2. applies the grow-only static-bound replan contract when a rebin
   overflowed ``m_c`` / ``row_cap`` / ``max_active`` (a scan cannot
   change static shapes, so overflow is *recorded* by the monitors and
   the bounds are grown between segments, then the segment replayed from
   the anchor — the overflowed segment's results are never committed);
3. checkpoints the whole scan carry ``(MDState, bins, ref, rng,
   monitors)`` through ``repro.ckpt`` (atomic step-dir publish), so a
   killed run resumes **bit-identically**: the carry is checkpointed
   whole and the segment grid is absolute, so a resumed process replays
   exactly the jitted segments the uninterrupted one would have run.

Chaos fault points (``repro.testing.chaos``): ``traj.step`` (error /
delay before a segment, nonfinite on its committed positions),
``traj.checkpoint`` (error — a failed save must never kill the run),
``traj.rebin`` (overflow — forces the replan path), plus ``ckpt.save``
inside the checkpoint writer itself.

Restrictions: trajectories need a cell schedule whose force inputs are
bins (``cell_dense`` / ``xpencil`` / ``allin``) on a single shard, in the
dense or packed layout — ``par_part`` reads raw positions (stale bins
would silently drop its interactions), ``naive_n2`` bypasses binning,
multi-shard halo plans re-partition per call, and the monitors keep no
sfc pair-list length to grow ``pair_cap`` by; all four raise up front.
"""

from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import Any, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..core import api, bounds
from ..core.api import InteractionPlan, ParticleState
from ..obs import metrics as _obs_metrics
from ..obs.trace import event as _obs_event, trace as _obs_trace
from ..core.binning import (bin_particles, cell_counts, image_positions,
                            max_displacement, padded_row_counts,
                            pencil_counts, refresh_bins, subbox_counts)
from ..core.domain import Domain, effective_skin, skin_domain
from ..physics.integrators import MDState
from ..testing import chaos
from ..ckpt import checkpoint as _ckpt
from . import monitors as M

# skin-contract + fault-recovery rebins, registry family next to the
# dispatch/recompile/replan counters of core.api
REBIN_TOTAL = "repro_rebin_total"

Array = jnp.ndarray

# Schedules whose backends consume bins (dense or packed) — the only ones
# whose force evaluation can reuse a stale-but-covering bin structure.
TRAJ_STRATEGIES = ("cell_dense", "xpencil", "allin")

INTEGRATORS = ("velocity_verlet", "leapfrog", "langevin")

# Default skin: a quarter cutoff. Small enough that m_c on the coarsened
# grid stays modest in the paper's few-particles-per-cell regime, large
# enough that a cold LJ/SPH system drifts for tens of steps before a rebin.
DEFAULT_SKIN_FRACTION = 0.25


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrajCarry:
    """Everything the fused scan needs between steps — and therefore
    everything a checkpoint must capture for bit-identical resume."""

    md: MDState               # positions/velocities/forces/potential/step
    bins: Any                 # CellBins on the skin grid (slot-reuse anchor)
    ref: Array                # (N, 3) positions the bins were built at
    rng: Array                # jax PRNG key (langevin noise stream)
    rebins: Array             # () int32 in-scan rebin events so far
    mon: M.MonitorState


@dataclasses.dataclass
class TrajectoryResult:
    """What a trajectory run produced and what it took to produce it."""

    state: MDState                     # final committed MD state
    traces: Dict[str, np.ndarray]      # per-step energies (since resume)
    plan: InteractionPlan              # traj plan with any grown bounds
    status: str = "ok"                 # ok | degraded | failed
    steps: int = 0                     # committed steps
    rebins: int = 0                    # in-scan (skin-contract) rebins
    forced_rebins: int = 0             # host-forced rebins (rollback/replan)
    replans: int = 0                   # bound-growth events
    rollbacks: int = 0                 # breach-triggered rollbacks
    retries: int = 0                   # segment re-executions after faults
    checkpoints: int = 0               # committed checkpoint dirs
    resumed_from: Optional[int] = None  # checkpoint step resumed from
    faults: List[str] = dataclasses.field(default_factory=list)
    ladder_level: int = 0              # rung that produced the final state
    eff_skin: float = 0.0              # measured skin margin of the grid
    # the compiled segment programs this call ran (jax.stages.Compiled;
    # .as_text() holds a tpu_custom_call per Pallas kernel on a TPU)
    executables: List[Any] = dataclasses.field(default_factory=list)


# --------------------------------------------------------------------------
# plan derivation: the skin-padded twin + observed-bound growth
# --------------------------------------------------------------------------


def _check_supported(p: InteractionPlan) -> None:
    if p.strategy not in TRAJ_STRATEGIES:
        raise ValueError(
            f"plan.trajectory needs a cell schedule {TRAJ_STRATEGIES}, got "
            f"{p.strategy!r}: par_part reads raw positions (stale bins "
            "would silently drop its interactions) and naive_n2 bypasses "
            "binning, so neither can reuse a Verlet-skin bin structure")
    if p._multi_shard:
        raise ValueError(
            "plan.trajectory does not run on multi-shard halo plans yet: "
            "the per-call Z-slab re-partition is exactly the cost the "
            "skin contract amortizes away (single-shard halo plans fall "
            "back to their inner backend and work fine)")
    if p.layout == "sfc":
        raise ValueError(
            'plan.trajectory does not run layout="sfc" plans yet: the '
            "segment monitors keep no pair-list length, so a pair_cap "
            "outgrown mid-run would go unseen; use the dense or packed "
            "layout")


def trajectory_plan(base: InteractionPlan, skin: float,
                    positions: Optional[Array] = None,
                    valid: Optional[Array] = None) -> InteractionPlan:
    """The skin-padded twin of ``base``: same kernel / backend / layout on
    the coarsened ``skin_domain`` grid, with static bounds re-measured for
    it (coarser cells hold more particles, so ``m_c`` / ``row_cap`` /
    ``max_active`` must be re-derived, not inherited). Without positions
    to measure against, bounds are scaled by the cell-volume ratio; with
    positions, the replan contract takes over."""
    _check_supported(base)
    dom = skin_domain(base.domain, skin)
    if dom == base.domain:
        return base
    grown = dataclasses.replace(
        base, domain=dom, box=None,
        m_c=_volume_scaled(base.m_c, base.domain, dom),
        row_cap=(None if base.row_cap is None
                 else _volume_scaled(base.row_cap, base.domain, dom)),
        max_active=(None if base.max_active is None
                    else min(base.max_active,
                             api.n_units(dom, base.strategy))))
    if positions is not None:
        counts = cell_counts(dom, positions, valid)
        grown = bounds.grow(grown, bounds.measure(grown, counts), counts)
    return grown


def _volume_scaled(bound: int, old: Domain, new: Domain) -> int:
    ratio = (float(np.prod(np.asarray(new.cell_width)))
             / max(float(np.prod(np.asarray(old.cell_width))), 1e-30))
    return bounds.align_up(max(1, int(np.ceil(bound * max(ratio, 1.0)))))


# --------------------------------------------------------------------------
# traced pieces: integrators, fused segment (forces: api._pair_forces on
# the bins the scan carries, where plan.execute re-bins every call)
# --------------------------------------------------------------------------


def _wrap(domain: Domain, positions: Array) -> Array:
    if not domain.any_periodic:
        return positions
    box = jnp.asarray(domain.box, dtype=positions.dtype)
    per = jnp.asarray(domain.periodic_axes)
    return jnp.where(per, jnp.mod(positions, box), positions)


def _bound_probes(p: InteractionPlan, bins) -> Tuple[Array, Array, Array]:
    """Traced maxima the static bounds must cover (monitor inputs)."""
    cell_max = jnp.max(bins.counts)
    row_max = (jnp.max(padded_row_counts(p.domain, bins.counts))
               if p.layout == "packed" else jnp.int32(0))
    if p.compact:
        uc = (subbox_counts(p.domain, bins.counts, p.box)
              if p.strategy == "allin"
              else pencil_counts(p.domain, bins.counts))
        units = jnp.sum(uc > 0).astype(jnp.int32)
    else:
        units = jnp.int32(0)
    return cell_max, row_max, units


def _masked_energies(vel: Array, pot: Array, valid: Optional[Array],
                     mass: float) -> Tuple[Array, Array]:
    if valid is None:
        ke = 0.5 * mass * jnp.sum(vel ** 2)
        pe = 0.5 * jnp.sum(pot)              # pair-counted-twice convention
    else:
        ke = 0.5 * mass * jnp.sum(jnp.where(valid[:, None], vel, 0.0) ** 2)
        pe = 0.5 * jnp.sum(jnp.where(valid, pot, 0.0))
    return ke, pe


def _nofma(x: Array) -> Array:
    """Pin a product so XLA cannot contract it into an FMA with the
    following add. The fused scan body and the per-step baseline compile
    in different surrounding programs; without this, the compiler fuses
    ``v + c*f`` differently in each (observed: 1-ulp velocity drift on
    CPU), breaking the skin=0 bit-parity contract."""
    return jax.lax.optimization_barrier(x)


def _integ_drift(integrator: str, dom: Domain, mass: float, md: MDState,
                 rng: Array, dt: Array, gamma: Array, kT: Array
                 ) -> Tuple[Array, Array, Array]:
    """First half of a step: new positions + staged velocity + rng."""
    half, inv_m = 0.5 / mass, 1.0 / mass
    if integrator == "velocity_verlet":
        v_half = md.velocities + _nofma((half * dt) * md.forces)
        pos = _wrap(dom, md.positions + _nofma(dt * v_half))
        return pos, v_half, rng
    if integrator == "leapfrog":
        vel = md.velocities + _nofma((dt * inv_m) * md.forces)
        pos = _wrap(dom, md.positions + _nofma(dt * vel))
        return pos, vel, rng
    # langevin (BAOAB): B(dt/2) A(dt/2) O(dt) A(dt/2); trailing B(dt/2)
    # happens in _integ_kick. gamma=0 reduces to velocity-Verlet drift.
    v1 = md.velocities + _nofma((half * dt) * md.forces)
    x1 = md.positions + _nofma((0.5 * dt) * v1)
    rng, sub = jax.random.split(rng)
    c1 = jnp.exp(-gamma * dt)
    c2 = jnp.sqrt(jnp.maximum(kT * inv_m, 0.0)
                  * jnp.maximum(1.0 - c1 * c1, 0.0))
    noise = jax.random.normal(sub, md.velocities.shape, md.velocities.dtype)
    v2 = c1 * v1 + _nofma(c2 * noise)
    pos = _wrap(dom, x1 + _nofma((0.5 * dt) * v2))
    return pos, v2, rng


def _integ_kick(integrator: str, mass: float, v_staged: Array,
                forces: Array, dt: Array) -> Array:
    if integrator == "leapfrog":
        return v_staged
    return v_staged + _nofma(((0.5 / mass) * dt) * forces)


@functools.lru_cache(maxsize=64)
def _segment_exec(p: InteractionPlan, integrator: str, seg_len: int,
                  eff_skin: float, mass: float,
                  field_names: Tuple[str, ...], has_valid: bool):
    """The jitted fused segment:
    ``run(carry, dt, gamma, kT, fields, valid) -> (carry, traces)`` over
    ``seg_len`` steps. Cached per static configuration, so a long run —
    and a warm serving class — compiles each segment shape exactly once."""
    del field_names, has_valid      # cache-key components only
    dom = p.domain

    def make_body(dt, gamma, kT, fields, valid):
        def body(carry: TrajCarry, _):
            md = carry.md
            with jax.named_scope("integrate"):
                pos, v_staged, rng = _integ_drift(integrator, dom, mass, md,
                                                  carry.rng, dt, gamma, kT)

            disp = max_displacement(dom, pos, carry.ref, valid)
            step_disp = max_displacement(dom, pos, md.positions, valid)
            need_rebin = disp > eff_skin * 0.5

            def do_rebin(_):
                return bin_particles(dom, pos, fields, m_c=p.m_c,
                                     valid=valid), pos

            def do_refresh(_):
                img = image_positions(dom, pos, carry.ref)
                return refresh_bins(dom, carry.bins, img, fields,
                                    valid), carry.ref

            bins, ref = jax.lax.cond(need_rebin, do_rebin, do_refresh, None)
            # positions as the (possibly stale) bins see them: the image
            # nearest the binned reference — exactly ``pos`` after a rebin
            img = image_positions(dom, pos, ref)
            forces, pot = api._pair_forces(
                p, bins, ParticleState(img, fields, valid))
            with jax.named_scope("integrate"):
                vel = _integ_kick(integrator, mass, v_staged, forces, dt)

            md2 = MDState(pos, vel, forces, pot, md.step + 1)
            ke, pe = _masked_energies(vel, pot, valid, mass)
            cell_max, row_max, units = _bound_probes(p, bins)
            mon = M.update(carry.mon, positions=pos, velocities=vel,
                           forces=forces, potential=pot, valid=valid,
                           kinetic=ke, potential_energy=pe,
                           step_disp=step_disp,
                           eff_skin=eff_skin, cell_max=cell_max,
                           row_max=row_max, units=units)
            rebinned = need_rebin.astype(jnp.int32)
            out = TrajCarry(md=md2, bins=bins, ref=ref, rng=rng,
                            rebins=carry.rebins + rebinned, mon=mon)
            return out, {"kinetic": ke, "potential": pe, "total": ke + pe,
                         "rebinned": rebinned}
        return body

    @jax.jit
    def run(carry: TrajCarry, dt: Array, gamma: Array, kT: Array,
            fields: Dict[str, Array], valid: Optional[Array]):
        api._count_recompile(p)         # runs at trace time only
        body = make_body(dt, gamma, kT, fields, valid)
        return jax.lax.scan(body, carry, None, length=seg_len)

    return run


@functools.lru_cache(maxsize=64)
def _init_exec(p: InteractionPlan, mass: float,
               field_names: Tuple[str, ...], has_valid: bool,
               has_forces: bool):
    """Jitted cold start: bin, evaluate (or adopt) forces, seed the
    monitors. An MDState input's committed forces are adopted, not
    recomputed — recomputing in a different program can shift them by an
    ulp, which would break the skin=0 parity contract against a baseline
    loop started from the same MDState."""
    del field_names, has_valid

    @jax.jit
    def init(positions, velocities, step0, fields, valid, rng,
             forces0, pot0):
        api._count_recompile(p)
        bins = bin_particles(p.domain, positions, fields, m_c=p.m_c,
                             valid=valid)
        if has_forces:
            forces, pot = forces0, pot0
        else:
            forces, pot = api._pair_forces(
                p, bins, ParticleState(positions, fields, valid))
        md = MDState(positions, velocities, forces, pot, step0)
        ke, pe = _masked_energies(velocities, pot, valid, mass)
        return TrajCarry(md=md, bins=bins, ref=positions, rng=rng,
                         rebins=jnp.int32(0), mon=M.init_monitors(ke + pe))

    return init


@functools.lru_cache(maxsize=64)
def _rebin_exec(p: InteractionPlan, field_names: Tuple[str, ...],
                has_valid: bool):
    """Jitted forced rebin: fresh bins + reference at the carried
    positions; the committed MD state and monitors are untouched. Used on
    rollback (perturb the FP path away from a breach) and after a bound
    replan (the grown ``m_c`` changes the bins' static shapes).

    Does NOT touch ``carry.rebins`` — that counter means skin-contract
    rebins inside the scan; fault-recovery rebins are counted host-side
    in ``TrajectoryResult.forced_rebins``."""
    del field_names, has_valid

    @jax.jit
    def rebin(carry: TrajCarry, fields, valid):
        api._count_recompile(p)
        bins = bin_particles(p.domain, carry.md.positions, fields,
                             m_c=p.m_c, valid=valid)
        return TrajCarry(md=carry.md, bins=bins, ref=carry.md.positions,
                         rng=carry.rng, rebins=carry.rebins,
                         mon=carry.mon)

    return rebin


def reference_step(p: InteractionPlan, integrator: str = "velocity_verlet",
                   mass: float = 1.0):
    """One per-step ``plan.execute`` baseline step, arithmetic-identical
    to the fused scan body — the other side of the fig_traj parity gate
    (with ``skin=0`` the fused path must match it bit for bit)."""
    def step(md: MDState, dt) -> MDState:
        dt = jnp.asarray(dt, md.positions.dtype)
        zero = jnp.zeros((), md.positions.dtype)
        pos, v_staged, _ = _integ_drift(integrator, p.domain, mass, md,
                                        jnp.zeros((2,), jnp.uint32),
                                        dt, zero, zero)
        forces, pot = p.execute(ParticleState(pos))
        vel = _integ_kick(integrator, mass, v_staged, forces, dt)
        return MDState(pos, vel, forces, pot, md.step + 1)
    return step


# --------------------------------------------------------------------------
# the host loop: segments, breaches, rollback, replan, checkpoint, resume
# --------------------------------------------------------------------------


def _normalize_state(state, velocities, plan) -> Tuple[
        Array, Array, Dict[str, Array], Optional[Array], int,
        Optional[Array], Optional[Array]]:
    """Accept MDState / ParticleState / raw (N, 3) positions. An MDState
    also contributes its committed (forces, potential), which the cold
    start adopts instead of recomputing (parity contract)."""
    if isinstance(state, MDState):
        return (state.positions, state.velocities, {}, None,
                int(state.step), state.forces, state.potential)
    if isinstance(state, ParticleState):
        if state.bonds is not None:
            raise ValueError(
                "plan.trajectory does not carry bonds yet: the state's "
                "bond list would be dropped from the fused scan; call "
                "plan.execute once per step of your own integrator")
        pos = state.positions
        vel = (velocities if velocities is not None
               else jnp.zeros_like(pos))
        return pos, vel, dict(state.fields), state.valid, 0, None, None
    pos = jnp.asarray(state)
    vel = velocities if velocities is not None else jnp.zeros_like(pos)
    return pos, vel, {}, None, 0, None, None


def run_trajectory(base: InteractionPlan, state, n_steps: int, dt: float, *,
                   integrator: str = "velocity_verlet",
                   skin: Optional[float] = None,
                   mass: float = 1.0, gamma: float = 0.1, kT: float = 0.0,
                   velocities: Optional[Array] = None, seed: int = 0,
                   checkpoint_dir: Optional[Union[str, pathlib.Path]] = None,
                   checkpoint_every: Optional[int] = None,
                   resume: bool = True,
                   segment_len: int = 32,
                   energy_budget: Optional[float] = None,
                   max_rollbacks: int = 4, max_replans: int = 4,
                   max_retries: Optional[int] = None,
                   traj_plan: Optional[InteractionPlan] = None,
                   sleep=None) -> TrajectoryResult:
    """Run ``n_steps`` of fused, guarded simulation. See the module
    docstring for the contract; ``InteractionPlan.trajectory`` is the
    front door. Never raises for runtime faults — like
    ``execute_checked``, failures degrade/roll back and the worst case is
    ``status="failed"`` with the last committed state."""
    if integrator not in INTEGRATORS:
        raise ValueError(f"unknown integrator {integrator!r}; have "
                         f"{INTEGRATORS}")
    if n_steps < 0:
        raise ValueError(f"n_steps must be >= 0, got {n_steps}")
    _check_supported(base)

    positions, vels, fields, valid, step0, forces0, pot0 = _normalize_state(
        state, velocities, base)
    field_names = tuple(sorted(fields))
    has_valid = valid is not None
    has_forces = forces0 is not None
    if not has_forces:  # placeholders; the jitted init ignores them
        forces0 = jnp.zeros_like(positions)
        pot0 = jnp.zeros((positions.shape[0],), positions.dtype)

    # -- the skin plan ------------------------------------------------------
    if traj_plan is not None:
        _check_supported(traj_plan)
        p = traj_plan
    else:
        if skin is None:
            skin = DEFAULT_SKIN_FRACTION * base.domain.cutoff
        p = trajectory_plan(base, skin, positions, valid)
    eff_skin = 0.0 if (skin == 0 and traj_plan is None) else \
        effective_skin(p.domain)
    # initial bounds must cover the initial positions
    st0 = ParticleState(positions, fields, valid)
    replans = 0
    while p.check_overflow(st0) and replans < max_replans:
        p = p.replan(st0)
        replans += 1

    dtype = positions.dtype
    dt_arr = jnp.asarray(dt, dtype)
    gamma_arr = jnp.asarray(gamma, dtype)
    kT_arr = jnp.asarray(kT, dtype)
    rng0 = jax.random.PRNGKey(seed)

    seg = max(1, int(segment_len))
    ck_every = None
    if checkpoint_dir is not None:
        ck_every = bounds.align_up(checkpoint_every or 4 * seg, seg)
        checkpoint_dir = pathlib.Path(checkpoint_dir)

    result = TrajectoryResult(state=None, traces={}, plan=p,
                              replans=replans, eff_skin=float(eff_skin))

    # -- resume or cold start ----------------------------------------------
    steps_done = 0
    carry = None
    if checkpoint_dir is not None and resume:
        last = _ckpt.latest_step(checkpoint_dir)
        if last is not None:
            extra = _ckpt.read_extra(checkpoint_dir, last)
            if (tuple(extra.get("ncells", ())) != p.domain.ncells
                    or extra.get("integrator") != integrator):
                raise ValueError(
                    f"checkpoint {checkpoint_dir}/step_{last:08d} was "
                    f"written by a different trajectory configuration "
                    f"({extra.get('ncells')}, {extra.get('integrator')}); "
                    "refusing to resume onto it")
            # bounds may have been grown before the checkpoint: the
            # template must match the saved static shapes
            p = dataclasses.replace(
                p, m_c=int(extra["m_c"]), box=None,
                row_cap=(int(extra["row_cap"]) if extra.get("row_cap")
                         else p.row_cap),
                max_active=(int(extra["max_active"])
                            if extra.get("max_active") else p.max_active))
            template = _init_exec(p, mass, field_names, has_valid,
                                  has_forces)(
                positions, vels, jnp.int32(step0), fields, valid, rng0,
                forces0, pot0)
            with _obs_trace("traj.checkpoint.load", step=last,
                            dir=str(checkpoint_dir)):
                carry, _ = _ckpt.restore(checkpoint_dir, template,
                                         step=last)
            steps_done = int(extra["steps_done"])
            result.resumed_from = last
            result.plan = p

    if carry is None:
        carry = _init_exec(p, mass, field_names, has_valid, has_forces)(
            positions, vels, jnp.int32(step0), fields, valid, rng0,
            forces0, pot0)
    # registry baseline: carry.rebins is cumulative across resumes, the
    # process counter must only count rebins this call performs
    rebins0 = int(carry.rebins)

    if n_steps == 0 or steps_done >= n_steps:
        result.state = carry.md
        result.steps = steps_done
        result.rebins = int(carry.rebins)
        result.traces = {k: np.zeros((0,), np.float32)
                         for k in ("kinetic", "potential", "total")}
        return result

    # -- the guarded segment loop ------------------------------------------
    rungs = api.degradation_ladder(p)
    health = api.plan_health(p)
    level = min(health.level, len(rungs) - 1)
    if max_retries is None:
        max_retries = api._FAILURE_THRESHOLD * len(rungs)

    segments: List[Dict[str, np.ndarray]] = []
    anchor = (carry, steps_done, 0)          # (carry, steps_done, n_segments)
    attempts = rollbacks = 0
    mon_prev = jax.device_get(carry.mon)
    failed = False

    def rebin_at(q, c):
        result.forced_rebins += 1
        with _obs_trace("traj.rebin", kind="forced", m_c=q.m_c,
                        strategy=q.strategy):
            return _rebin_exec(q, field_names, has_valid)(c, fields, valid)

    def grown_rungs(q):
        return api.degradation_ladder(q), api.plan_health(q)

    while steps_done < n_steps:
        boundary = (steps_done // seg + 1) * seg
        this_len = min(boundary, n_steps) - steps_done
        rung = rungs[min(level, len(rungs) - 1)]
        exec_fn = _segment_exec(rung, integrator, this_len,
                                float(eff_skin), mass, field_names,
                                has_valid)
        # compile errors raise here, outside the fault handler: a kernel
        # the platform refuses is a defect, not a fault to degrade around
        compiled = api.compile_ahead(exec_fn, carry, dt_arr, gamma_arr,
                                     kT_arr, fields, valid)
        if all(c is not compiled for c in result.executables):
            result.executables.append(compiled)
        st = chaos.state()
        fires_before = (st.fire_count("traj.step", "nonfinite")
                        if st is not None else 0)
        try:
            if sleep is None:
                chaos.maybe_delay("traj.step")
            else:
                chaos.maybe_delay("traj.step", sleep=sleep)
            chaos.maybe_raise("traj.step")
            with _obs_trace("traj.segment", steps=this_len,
                            start=steps_done, backend=rung.backend,
                            strategy=rung.strategy, level=level):
                carry2, ys = exec_fn(carry, dt_arr, gamma_arr, kT_arr,
                                     fields, valid)
            # host-boundary corruption point (the scan itself is traced
            # and must never be poisoned at trace time)
            pos2 = chaos.corrupt("traj.step", carry2.md.positions)
            injected_nan = (st is not None and st.fire_count(
                "traj.step", "nonfinite") > fires_before)
            if injected_nan:
                carry2 = dataclasses.replace(
                    carry2, md=dataclasses.replace(carry2.md,
                                                   positions=pos2))
            mon_cur = jax.device_get(carry2.mon)
        except (chaos.TransientBackendError, RuntimeError, ValueError) as e:
            result.faults.append(f"{type(e).__name__}: {e}")
            attempts += 1
            result.retries += 1
            if health.note_failure(len(rungs)):
                level = health.level
            if attempts > max_retries:
                failed = True
                break
            continue

        # ---- overflow? grow bounds, roll back, replay --------------------
        forced = chaos.forced_overflow("traj.rebin")
        grown = bounds.grow(p, {"m_c": int(mon_cur.max_cell_count),
                                "row_cap": int(mon_cur.max_row_count),
                                "max_active": int(mon_cur.max_active_units)})
        if grown != p or forced:
            if grown == p:
                # injected verdict with nothing to grow: record, move on
                result.faults.append("overflow:injected")
            elif result.replans >= max_replans:
                result.faults.append("overflow:replan-budget-exhausted")
                failed = True
                break
            else:
                result.replans += 1
                api._count_replan(p)
                _obs_event("traj.replan", m_c=grown.m_c, m_c_was=p.m_c,
                           row_cap=grown.row_cap,
                           max_active=grown.max_active)
                p = grown
                result.plan = p
                rungs, health = grown_rungs(p)
                level = min(health.level, len(rungs) - 1)
                # anchor bins were built under the old m_c: rebuild them
                # (and the executors) under the grown bounds
                carry, steps_done, nseg = anchor
                carry = rebin_at(rungs[min(level, len(rungs) - 1)], carry)
                del segments[nseg:]
                anchor = (carry, steps_done, nseg)
                mon_prev = jax.device_get(carry.mon)
                continue

        # ---- invariant breach? roll back + forced rebin ------------------
        breach = ("nonfinite" if injected_nan else
                  M.classify_breach(mon_prev, mon_cur, energy_budget))
        if breach is not None:
            result.faults.append(f"breach:{breach}@{steps_done}")
            rollbacks += 1
            result.rollbacks = rollbacks
            _obs_event("traj.rollback", breach=breach, step=steps_done,
                       anchor_step=anchor[1])
            if health.note_failure(len(rungs)):
                level = health.level
            if rollbacks > max_rollbacks:
                failed = True
                break
            carry, steps_done, nseg = anchor
            carry = rebin_at(rungs[min(level, len(rungs) - 1)], carry)
            del segments[nseg:]
            anchor = (carry, steps_done, nseg)
            mon_prev = jax.device_get(carry.mon)
            continue

        # ---- commit ------------------------------------------------------
        health.note_success()
        attempts = 0
        carry = carry2
        mon_prev = mon_cur
        steps_done += this_len
        segments.append(jax.device_get(ys))

        at_ck = ck_every is not None and steps_done % ck_every == 0
        if at_ck or steps_done >= n_steps or ck_every is None:
            if at_ck and checkpoint_dir is not None:
                try:
                    chaos.maybe_raise("traj.checkpoint")
                    with _obs_trace("traj.checkpoint.save",
                                    step=steps_done,
                                    dir=str(checkpoint_dir)):
                        _ckpt.save(checkpoint_dir, steps_done, carry,
                                   extra={"steps_done": steps_done,
                                          "ncells": list(p.domain.ncells),
                                          "integrator": integrator,
                                          "m_c": p.m_c,
                                          "row_cap": p.row_cap,
                                          "max_active": p.max_active,
                                          "segment_len": seg})
                    result.checkpoints += 1
                except (chaos.TransientBackendError, OSError) as e:
                    # a failed checkpoint must never kill the run; the
                    # in-memory anchor still advances
                    result.faults.append(f"checkpoint:{type(e).__name__}")
            anchor = (carry, steps_done, len(segments))

    # -- finalize ----------------------------------------------------------
    if failed:
        # the anchor is the last committed healthy state
        carry, steps_done, nseg = anchor
        del segments[nseg:]
        result.status = "failed"
    else:
        result.status = "ok" if level == 0 else "degraded"
    result.state = carry.md
    result.steps = steps_done
    result.rebins = int(carry.rebins)
    result.ladder_level = level
    _obs_metrics.registry.counter(
        REBIN_TOTAL, backend=p.backend, strategy=p.strategy,
        layout=p.layout).inc(max(0, result.rebins - rebins0)
                             + result.forced_rebins)
    if segments:
        result.traces = {k: np.concatenate([s[k] for s in segments])
                         for k in ("kinetic", "potential", "total")}
    else:
        result.traces = {k: np.zeros((0,), np.float32)
                         for k in ("kinetic", "potential", "total")}
    return result
