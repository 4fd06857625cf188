"""Plan/execute interaction API — the front door to every schedule + backend.

The paper's subject is choosing among interchangeable schedules (Par-Part,
Par-Cell, X-pencil, All-in-SM) for the same cutoff interaction. This module
separates that choice (static, made once) from the traced computation (made
every step):

    state = ParticleState(positions)                        # traced pytree
    p = plan(domain, kernel, positions=positions,           # static choices
             strategy="auto", backend="pallas")
    forces, potential = p.execute(state)                    # jitted hot path
    (forces, potential), p = p.execute_or_replan(state)     # + M_C safety net

Three layers:

  ``ParticleState``    the universal traced input: positions plus optional
                       per-particle fields (velocity, mass, ...).
  ``InteractionPlan``  all static choices — domain, kernel, ``m_c``,
                       strategy, backend, batch/grid sizing — hashable, so
                       one jit trace per distinct plan. ``strategy="auto"``
                       is driven by the ``core.traffic`` cost model.
  backend registry     one normalized signature
                       ``(plan, bins, state) -> (forces (N,3), pot (N,))``
                       under which the pure-JAX references
                       (``core.strategies``) and the Pallas kernels
                       (``repro.kernels``) register per strategy name, so
                       ``backend="pallas"`` routes ``xpencil``/``allin``
                       through the same front door as their oracles.

``CellListEngine`` / ``compute_interactions`` in ``core.engine`` are thin
compatibility shims over this module.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import weakref
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import strategies as S
from . import traffic
from .binning import (CellBins, PackedRows, SfcClusters, bin_particles,
                      build_sfc_clusters, cell_counts, dense_to_particles,
                      full_pencil_occupancy, pack_rows, packed_to_particles,
                      padded_row_counts, pencil_counts, pencil_occupancy,
                      sfc_n_clusters, sfc_pair_count, sfc_to_particles,
                      subbox_counts, subbox_occupancy)
from .domain import Domain, slab_domain
from .interactions import PairKernel, make_lennard_jones
# obs imports only its own trace/metrics modules eagerly (no core imports),
# so the dependency is acyclic: core.api -> obs.{trace,metrics}
from ..obs import metrics as _obs_metrics
from ..obs.trace import (active as _obs_active, event as _obs_event,
                         trace as _obs_trace)

Array = jnp.ndarray

STRATEGY_NAMES = ("par_part", "cell_dense", "xpencil", "allin")


# --------------------------------------------------------------------------
# traced input
# --------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ParticleState:
    """The universal traced input: positions + optional per-particle fields.

    ``fields`` maps names ("vx", "mass", ...) to (N,) arrays that are binned
    alongside x/y/z so schedules can read them per slot. The dict's *keys*
    are static (part of the trace); the values are traced.

    ``valid`` is an optional (N,) bool mask marking padding rows (False):
    those rows are excluded from binning, interact with nothing, and every
    bound probe ignores them. This is how the serving tier
    (``repro.serve``) pads heterogeneous request sizes up to one shape
    class without perturbing a single real interaction — executing a
    padded, masked state is bit-identical (for the real rows) to executing
    the unpadded state.
    """

    positions: Array                                   # (N, 3)
    fields: Dict[str, Array] = dataclasses.field(default_factory=dict)
    valid: Optional[Array] = None                      # (N,) bool, None=all

    @property
    def n(self) -> int:
        return self.positions.shape[0]


# --------------------------------------------------------------------------
# backend registry
# --------------------------------------------------------------------------

# (backend, strategy, layout) -> fn(plan, bins, state) -> (forces, pot).
# ``layout`` is the execution layout the implementation reads: "dense"
# implementations receive a CellBins, "packed" ones a binning.PackedRows,
# "sfc" ones a binning.SfcClusters (compressed cluster-pair list).
_BACKENDS: Dict[Tuple[str, str, str], Callable] = {}

LAYOUT_NAMES = ("dense", "packed", "sfc")

# (backend, strategy, layout) triples whose implementation honours
# ``plan.compact`` (occupancy-compacted iteration). By register_backend.
_COMPACT_OK: set = set()

# (backend, strategy, layout) -> budget(plan, domain) for implementations
# that stage data in the chip's on-chip memories. By register_backend.
_BUDGETS: Dict[Tuple[str, str, str], Callable] = {}


def register_backend(backend: str, strategy: str, compact: bool = False,
                     layout: str = "dense",
                     budget: Optional[Callable] = None):
    """Register an implementation under ``(backend, strategy, layout)``.

    The implementation receives the (static) plan, the binned layout
    (:class:`~repro.core.binning.CellBins` for ``layout="dense"``,
    :class:`~repro.core.binning.PackedRows` for ``layout="packed"``), and
    the traced state, and must return per-particle ``(forces, pot)`` — the
    one normalized signature both the reference schedules and the Pallas
    kernels conform to. ``compact=True`` declares that the implementation
    also honours ``plan.compact`` (occupancy-compacted iteration).
    ``budget(plan, domain)`` is given by an implementation that stages
    data on-chip: it returns ``(kernel name, VMEM bytes per grid step,
    SMEM bytes)`` for the plan on ``domain`` and raises ``ValueError``,
    naming the memory, when the plan cannot fit (:func:`kernel_budget`).
    """
    if layout not in LAYOUT_NAMES:
        raise ValueError(f"unknown layout {layout!r}; have {LAYOUT_NAMES}")

    def deco(fn: Callable) -> Callable:
        _BACKENDS[(backend, strategy, layout)] = fn
        if compact:
            _COMPACT_OK.add((backend, strategy, layout))
        if budget is not None:
            _BUDGETS[(backend, strategy, layout)] = budget
        return fn
    return deco


def kernel_budget(p: "InteractionPlan") -> Optional[Tuple[str, int, int]]:
    """``(kernel name, VMEM bytes per grid step, SMEM bytes)`` of the
    implementation ``p`` dispatches to, on the domain one shard runs on,
    from the budget it registered; None when it stages nothing on-chip.
    Raises ``ValueError`` naming the VMEM or SMEM limit when the plan
    cannot fit the chip; ``plan()`` calls it, so such a plan is refused
    before it is built."""
    inner = p.halo_inner if p.backend == "halo" else p.backend
    budget = _BUDGETS.get((inner, p.strategy, p.layout))
    if budget is None:
        return None
    dom = p.domain
    if p.backend == "halo" and (p.n_shards or 1) > 1:
        dom = slab_domain(dom, p.n_shards)
    return budget(p, dom)


def supports_compact(backend: str, strategy: str,
                     layout: str = "dense") -> bool:
    """True if ``(backend, strategy, layout)`` implements the compacted
    path."""
    if backend == "pallas":
        import repro.kernels  # noqa: F401  (trigger registration)
    return (backend, strategy, layout) in _COMPACT_OK


def supports_layout(backend: str, strategy: str, layout: str) -> bool:
    """True if ``(backend, strategy)`` implements the given execution
    layout (``"dense"`` / ``"packed"``)."""
    if backend == "pallas":
        import repro.kernels  # noqa: F401  (trigger registration)
    return (backend, strategy, layout) in _BACKENDS


def get_backend(backend: str, strategy: str,
                layout: str = "dense") -> Callable:
    if backend == "pallas":
        # Pallas implementations self-register on import; make sure the
        # module ran before declaring the combination missing.
        import repro.kernels  # noqa: F401
    fn = _BACKENDS.get((backend, strategy, layout))
    if fn is None:
        import repro.kernels  # noqa: F401  (list *all* backends in the error)
        fn = _BACKENDS.get((backend, strategy, layout))
    if fn is None:
        have = sorted(set(b for b, _, _ in _BACKENDS))
        raise ValueError(
            f"no backend {backend!r} for strategy {strategy!r} with layout "
            f"{layout!r}; registered backends: {have}, triples: "
            f"{sorted(_BACKENDS)}")
    return fn


def backend_matrix() -> Dict[str, Tuple[str, ...]]:
    """backend name -> strategies it implements (docs / README helper)."""
    import repro.kernels  # noqa: F401  (trigger pallas registration)
    out: Dict[str, list] = {}
    for b, s, layout in sorted(_BACKENDS):
        if s not in out.setdefault(b, []):
            out[b].append(s)
    return {b: tuple(s) for b, s in out.items()}


# --------------------------------------------------------------------------
# the plan
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class InteractionPlan:
    """All static choices for a cutoff interaction, made once.

    Hashable: two equal plans share one jit trace. Everything traced lives
    in ``ParticleState``; everything here is compile-time constant.
    """

    domain: Domain
    kernel: PairKernel
    m_c: int
    strategy: str = "xpencil"
    backend: str = "reference"
    batch_size: int = 64
    box: Optional[Tuple[int, int, int]] = None   # allin sub-box (bx, by, bz)
    interpret: Optional[bool] = None             # pallas: None = auto
    compact: bool = False                        # occupancy-compacted path
    max_active: Optional[int] = None             # static active-unit bound
    layout: str = "dense"                 # layout: dense | packed | sfc
    row_cap: Optional[int] = None                # static packed-row bound
    pair_cap: Optional[int] = None               # static sfc pair-list bound
    # -- distributed halo execution (backend="halo"; repro.dist.engine) ----
    halo_inner: str = "reference"                # per-shard backend
    n_shards: Optional[int] = None               # Z-slabs on the mesh axis
    shard_axis: str = "halo"                     # mesh axis name
    shard_cap: Optional[int] = None              # static per-shard capacity
    mesh: Optional[object] = None                # jax Mesh; None = default

    def __post_init__(self):
        if self.strategy not in ("naive_n2", *STRATEGY_NAMES):
            raise ValueError(
                f"unknown strategy {self.strategy!r}; have "
                f"{sorted(STRATEGY_NAMES)} + ['naive_n2']")
        if self.backend == "halo":
            if self.strategy not in ("cell_dense", "xpencil", "allin"):
                raise ValueError(
                    f"backend='halo' needs a cell schedule, got "
                    f"{self.strategy!r} (the Z-slab decomposition has no "
                    "meaning for particle-parallel or O(N^2) sweeps)")
            if self.halo_inner == "halo":
                raise ValueError("halo_inner must be a concrete per-shard "
                                 "backend ('reference'/'pallas'), not "
                                 "'halo' itself")
            if not self.n_shards or self.n_shards < 1:
                raise ValueError(
                    "backend='halo' needs n_shards >= 1 "
                    "(plan(..., backend='halo') derives one from the "
                    "visible devices)")
            if self.domain.nz % self.n_shards:
                raise ValueError(
                    f"nz={self.domain.nz} not divisible by "
                    f"n_shards={self.n_shards}")
            if self.n_shards > 1 and (not self.shard_cap
                                      or self.shard_cap < 1):
                raise ValueError(
                    "a multi-shard halo plan needs a positive static "
                    "shard_cap (plan(..., positions=...) measures one)")
            if self.compact and self.strategy == "allin":
                raise ValueError(
                    "backend='halo' supports compact=True for the pencil "
                    "schedules (xpencil/cell_dense) only — the All-in-SM "
                    "sub-box occupancy is not defined per slab")
        if self.strategy == "allin" and self.box is None:
            # directly-constructed plans get the VMEM-budget sub-box too —
            # the pallas backend needs a concrete tiling at trace time.
            # Halo plans tile the *slab* each shard actually runs on.
            bdom = self.domain
            if self.backend == "halo" and self.n_shards:
                bdom = slab_domain(self.domain, self.n_shards)
            object.__setattr__(self, "box", _allin_box(bdom, self.m_c))
        if self.compact:
            if self.strategy not in ("cell_dense", "xpencil", "allin"):
                raise ValueError(
                    f"compact=True is not defined for {self.strategy!r} "
                    "(only the cell schedules have empty work units to skip)")
            if not self.max_active or self.max_active < 1:
                raise ValueError(
                    "compact=True needs a positive static max_active bound "
                    "(plan(..., positions=...) measures one)")
        if self.layout not in LAYOUT_NAMES:
            raise ValueError(
                f"unknown layout {self.layout!r}; have {LAYOUT_NAMES}")
        if self.layout == "packed":
            if self.strategy not in S.PACKED_STRATEGIES:
                raise ValueError(
                    f'layout="packed" is not defined for '
                    f"{self.strategy!r}; packed strategies: "
                    f"{sorted(S.PACKED_STRATEGIES)}")
            if not self.row_cap or self.row_cap < 1:
                raise ValueError(
                    'layout="packed" needs a positive static row_cap bound '
                    "(plan(..., positions=...) measures one)")
        if self.layout == "sfc":
            if self.strategy not in S.SFC_STRATEGIES:
                raise ValueError(
                    f'layout="sfc" is not defined for '
                    f"{self.strategy!r}; sfc strategies: "
                    f"{sorted(S.SFC_STRATEGIES)}")
            if not self.pair_cap or self.pair_cap < 1:
                raise ValueError(
                    'layout="sfc" needs a positive static pair_cap bound '
                    "(plan(..., positions=...) measures one)")

    # -- hot path ----------------------------------------------------------

    def execute(self, state: ParticleState) -> Tuple[Array, Array]:
        """-> (forces (N, 3), per-particle potential (N,)). Jitted; one
        trace per (plan, state structure). Total potential energy is
        ``0.5 * potential.sum()`` (each pair counted twice, the paper's
        convention)."""
        _count_dispatch(self)
        if not _obs_active():            # zero-overhead disabled path
            return _executor(self, tuple(sorted(state.fields)))(state)
        with _obs_trace("plan.execute", backend=self.backend,
                        strategy=self.strategy, layout=self.layout,
                        n=int(state.positions.shape[0])):
            return _executor(self, tuple(sorted(state.fields)))(state)

    def execute_batch(self, states: ParticleState) -> Tuple[Array, Array]:
        """Batched hot path: one jitted vmapped call over stacked states.

        ``states`` holds B independent systems stacked on a leading axis —
        positions ``(B, N, 3)``, each field ``(B, N)`` — all sharing this
        plan's domain and ``m_c``. Binning and interaction run under one
        ``vmap`` inside a single jit trace, so B small systems (the paper's
        few-particles-per-cell regime) cost one dispatch instead of B.
        Returns ``(forces (B, N, 3), potential (B, N))``, bit-identical to
        executing each system separately."""
        _count_dispatch(self)
        if not _obs_active():            # zero-overhead disabled path
            return _batch_executor(self, tuple(sorted(states.fields)))(states)
        with _obs_trace("plan.execute_batch", backend=self.backend,
                        strategy=self.strategy, layout=self.layout,
                        batch=int(states.positions.shape[0])):
            return _batch_executor(self, tuple(sorted(states.fields)))(states)

    def __call__(self, state: ParticleState) -> Tuple[Array, Array]:
        return self.execute(state)

    def compile(self, state: ParticleState):
        """Lower and compile the executor :meth:`execute` runs for
        ``state``'s structure and shapes; returns the
        ``jax.stages.Compiled`` (``.as_text()`` shows the program, with a
        ``tpu_custom_call`` per Pallas kernel on a TPU). A lowering or
        compile error raises here. ``execute`` reuses the executable."""
        return compile_ahead(_executor(self, tuple(sorted(state.fields))),
                             state)

    def compile_batch(self, states: ParticleState):
        """:meth:`compile` for the :meth:`execute_batch` executor."""
        return compile_ahead(
            _batch_executor(self, tuple(sorted(states.fields))), states)

    # -- M_C safety net ----------------------------------------------------

    def check_overflow(self, state: ParticleState) -> bool:
        """True if some static bound of this plan no longer covers these
        positions — results computed anyway would silently drop
        interactions. Which bounds exist, what each one covers and how an
        overflowed one grows is the replan contract: see :meth:`replan`
        (the canonical statement) and ARCHITECTURE.md. For halo plans the
        per-shard flags are reduced (max) across shards, keeping the
        safety contract global; everything derives from one binning
        pass. Padding rows (``state.valid`` False) are excluded — a padded
        request must never trigger a replan its real particles don't
        need."""
        return self.overflow_class(state) is not None

    def overflow_class(self, state: ParticleState) -> Optional[str]:
        """Which static bound these positions breach — ``"m_c"``,
        ``"row_cap"``, ``"shard_cap"``, ``"max_active"``, ``"injected"``
        (a chaos-forced verdict, ``repro.testing.chaos``) — or None when
        every bound holds. Same contract, one binning pass, and padding
        exclusion as :meth:`check_overflow` (which is a thin wrapper)."""
        with _obs_trace("plan.overflow_check", strategy=self.strategy,
                        layout=self.layout) as sp:
            oc = self._overflow_class(state)
            sp.set(result=oc or "ok")
        return oc

    def _overflow_class(self, state: ParticleState) -> Optional[str]:
        from ..testing import chaos
        if chaos.forced_overflow("core.binning"):
            return "injected"
        counts = _cell_counts(self.domain, state.positions, state.valid)
        if int(jnp.max(counts)) > self.m_c:
            return "m_c"
        if self.layout == "packed":
            if int(jnp.max(padded_row_counts(self.domain, counts))
                   ) > self.row_cap:
                return "row_cap"
        if self.layout == "sfc" and not self._multi_shard:
            # multi-shard sfc plans check pair_cap per shard (slab-local
            # cluster orders) inside halo_overflow_class below
            if sfc_pair_count(self.domain, counts=counts) > self.pair_cap:
                return "pair_cap"
        if self._multi_shard:
            from ..dist.engine import halo_overflow_class
            return halo_overflow_class(self, counts)
        if self.compact:
            n_act = active_unit_count(self.domain, state.positions,
                                      self.strategy, box=self.box,
                                      counts=counts)
            if n_act > self.max_active:
                return "max_active"
        return None

    @property
    def _multi_shard(self) -> bool:
        return self.backend == "halo" and (self.n_shards or 1) > 1

    def replan(self, state: ParticleState, slack: float = 1.5,
               align: int = 8) -> "InteractionPlan":
        """A new plan whose static bounds cover ``state``.

        **The replan contract** (canonical statement — ``check_overflow``,
        ``execute_or_replan``, the ``plan()`` bound arguments and the halo
        engine all defer here; prose version in ARCHITECTURE.md):

        Every static bound follows one pattern — *measure with slack,
        round up to ``align``, detect overflow, grow only what
        overflowed*. The bounds, each paired with its measuring probe:

        * ``m_c`` — max particles per cell (``suggest_m_c``),
        * ``max_active`` — active work units of a compacted plan
          (``suggest_max_active``),
        * ``row_cap`` — particles per packed pencil row of a
          ``layout="packed"`` plan (``suggest_row_cap``),
        * ``pair_cap`` — compressed cluster-pair list length of a
          ``layout="sfc"`` plan (``suggest_pair_cap``),
        * ``shard_cap`` — per-shard particle load of a multi-shard halo
          plan (``dist.halo.suggest_shard_cap``; halo plans also apply
          per-shard reductions to ``max_active``).

        Exceeding a bound makes results *silently drop* interactions, so
        bounds are never trusted blindly: ``check_overflow`` detects an
        exceeded bound from one binning pass, and this method grows
        **only the bound that actually overflowed** — re-measured with
        slack and forced strictly past the old value — so e.g. a pencil
        count outgrowing ``max_active`` does not churn ``m_c`` (and with
        it the whole slot layout) for nothing. Derived statics follow
        their inputs: the allin sub-box is recomputed whenever ``m_c``
        changes, and a compacted allin re-measures ``max_active`` against
        the new tiling. ``row_cap`` depends only on the positions, so it
        never moves when ``m_c`` does. Padding rows (``state.valid``
        False) are excluded from every measure, exactly as in
        ``check_overflow``."""
        counts = _cell_counts(self.domain, state.positions, state.valid)
        m_c = self.m_c
        mx_cell = int(jnp.max(counts))
        if mx_cell > self.m_c:
            # suggest_m_c's slack-and-align contract, applied to the
            # mask-aware counts of this one binning pass
            measured = -(-max(1, int(mx_cell * slack + 0.999)) // align
                         ) * align
            grow = -(-(self.m_c + 1) // align) * align  # aligned, > m_c
            m_c = max(measured, grow)
        box = self.box if m_c == self.m_c else None
        row_cap = self.row_cap
        if self.layout == "packed":
            mx_row = int(jnp.max(padded_row_counts(self.domain, counts)))
            if mx_row > row_cap:
                grow = -(-(row_cap + 1) // align) * align
                row_cap = max(suggest_row_cap(self.domain, state.positions,
                                              align=align, counts=counts),
                              grow)
        pair_cap = self.pair_cap
        if self.layout == "sfc":
            if self._multi_shard:
                # the bound is per shard: each slab has its own cluster
                # order, so the busiest shard's pair list sets the cap
                from ..dist.engine import shard_sfc_pairs
                n_pairs = int(max(shard_sfc_pairs(self.domain, counts,
                                                  self.n_shards)))
                suggested = -(-max(1, int(n_pairs * 1.25 + 0.999))
                              // align) * align
            else:
                n_pairs = sfc_pair_count(self.domain, counts=counts)
                suggested = suggest_pair_cap(self.domain, align=align,
                                             counts=counts)
            if n_pairs > pair_cap:
                grow = -(-(pair_cap + 1) // align) * align
                pair_cap = max(suggested, grow, n_pairs)
        max_active = self.max_active
        shard_cap = self.shard_cap
        if self._multi_shard:
            # shard-level bounds: per-shard load vs shard_cap, per-shard
            # active pencils vs max_active — grown only when exceeded
            from ..dist.engine import halo_grown_bounds
            shard_cap, max_active = halo_grown_bounds(self, state,
                                                      align=align)
        elif self.compact:
            if self.strategy == "allin" and box is None:
                # fix the new tiling first: the active-sub-box bound must
                # be measured against the grid that will actually run
                box = _allin_box(self.domain, m_c)
            n_act = active_unit_count(self.domain, state.positions,
                                      self.strategy, box=box, counts=counts)
            if n_act > max_active or box != self.box:
                suggested = suggest_max_active(self.domain, state.positions,
                                               self.strategy, box=box,
                                               align=align, counts=counts)
                max_active = max(suggested, n_act)
        grown = dataclasses.replace(self, m_c=m_c, box=box,
                                    max_active=max_active,
                                    shard_cap=shard_cap, row_cap=row_cap,
                                    pair_cap=pair_cap)
        if grown != self:                # no-op replans are not replans
            _count_replan(self)
            _obs_event("plan.replan", strategy=self.strategy,
                       layout=self.layout, m_c=grown.m_c,
                       m_c_was=self.m_c, row_cap=grown.row_cap,
                       pair_cap=grown.pair_cap,
                       max_active=grown.max_active,
                       shard_cap=grown.shard_cap)
        return grown

    def execute_or_replan(self, state: ParticleState
                          ) -> Tuple[Tuple[Array, Array], "InteractionPlan"]:
        """Overflow-safe execute: detects an exceeded static bound (outside
        jit — replanning changes statics) and re-executes under replanned
        bounds (see :meth:`replan` for the contract). Returns
        ``((forces, potential), plan)`` where ``plan`` is ``self`` when
        every bound held."""
        p: InteractionPlan = self
        while p.check_overflow(state):
            p = p.replan(state)
        return p.execute(state), p

    def execute_checked(self, state: ParticleState, *,
                        max_replans: int = 4,
                        max_retries: Optional[int] = None,
                        sleep=None
                        ) -> Tuple[Tuple[Array, Array], "ExecutionReport"]:
        """Guarded execute: absorbs every runtime fault, always
        terminates, and tells you what happened. A lowering or compile
        error is not a fault: it raises (:func:`compile_ahead`). Returns
        ``((forces, potential), report)`` where the
        :class:`ExecutionReport` carries the overflow class, the
        non-finite output count (one fused ``jnp.isfinite`` reduction),
        the out-of-domain particle count, and the degradation-ladder /
        circuit-breaker trajectory; ``report.plan`` is the plan to keep
        using (replans and elastic shard shrinks applied). See
        :func:`degradation_ladder` and ARCHITECTURE.md "Resilience"."""
        return _execute_checked(self, state, max_replans=max_replans,
                                max_retries=max_retries, sleep=sleep)

    # -- fused multi-step simulation (repro.traj) --------------------------

    def trajectory(self, state, n_steps: int, dt: float, *,
                   integrator: str = "velocity_verlet",
                   skin: Optional[float] = None, **opts):
        """Run ``n_steps`` of fused bin -> force -> integrate simulation
        under one jitted ``lax.scan`` per segment, with Verlet-skin
        neighbor reuse, invariant monitors, checkpoint/rollback and
        deterministic resume. Returns a
        :class:`repro.traj.TrajectoryResult`.

        ``state`` is an ``MDState``, a ``ParticleState`` (+ optional
        ``velocities=``) or a raw ``(N, 3)`` positions array. ``skin`` is
        the Verlet margin (default: a quarter cutoff; ``0`` = re-bin
        every step, bit-identical to a per-step :meth:`execute` loop).
        Forwarded options (``checkpoint_dir``, ``checkpoint_every``,
        ``segment_len``, ``energy_budget``, ``mass``, ``gamma``/``kT``
        for the langevin integrator, ...): see
        :func:`repro.traj.engine.run_trajectory` — the engine and the
        canonical contract live there. Requires a cell schedule
        (``cell_dense`` / ``xpencil`` / ``allin``) on a single shard."""
        from ..traj.engine import run_trajectory
        return run_trajectory(self, state, n_steps, dt,
                              integrator=integrator, skin=skin, **opts)

    # -- distributed execution ---------------------------------------------

    def distribute(self, mesh=None, *, n_shards: Optional[int] = None,
                   shard_axis: Optional[str] = None,
                   positions: Optional[Array] = None,
                   shard_cap: Optional[int] = None,
                   halo_inner: Optional[str] = None) -> "InteractionPlan":
        """A halo twin of this plan: same schedule and static bounds, run
        on a device mesh (``repro.dist.engine``).

        Args:
          mesh: a ``jax.sharding.Mesh`` holding the shard axis; by default
            the engine builds a 1-D mesh over the local devices.
          n_shards: Z-slabs (must divide ``nz``); defaults to the mesh's
            shard-axis size, else the largest ``nz`` divisor that fits the
            visible devices.
          shard_axis: mesh axis name to shard along (default ``"halo"``,
            or the mesh's first axis when a mesh is given).
          positions: representative positions to measure the static
            ``shard_cap`` (and, for compacted plans, the per-shard
            ``max_active``) from; required unless ``shard_cap`` is given.
          shard_cap: explicit static per-shard particle capacity.
          halo_inner: per-shard backend; defaults to this plan's backend.
        """
        from ..dist import engine as dist_engine
        axis = shard_axis or (mesh.axis_names[0] if mesh is not None
                              else self.shard_axis)
        if mesh is not None and axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}, no {axis!r} shard axis")
        if n_shards is None:
            if mesh is not None:
                n_shards = int(mesh.shape[axis])
            else:
                n_shards = dist_engine.default_n_shards(self.domain)
        inner = halo_inner or (self.halo_inner if self.backend == "halo"
                               else self.backend)
        max_active = self.max_active
        if n_shards > 1:
            if shard_cap is None:
                if positions is None:
                    raise ValueError(
                        "distribute() needs either shard_cap or positions "
                        "(to measure the per-shard capacity)")
                from ..dist.halo import suggest_shard_cap
                shard_cap = suggest_shard_cap(self.domain, positions,
                                              n_shards)
            if self.compact and positions is not None:
                from ..dist.halo import suggest_shard_max_active
                max_active = suggest_shard_max_active(self.domain,
                                                      positions, n_shards)
        box = None if self.strategy == "allin" else self.box
        return dataclasses.replace(
            self, backend="halo", halo_inner=inner, n_shards=n_shards,
            shard_axis=axis, shard_cap=shard_cap, mesh=mesh, box=box,
            max_active=max_active)

    # -- introspection -----------------------------------------------------

    def bin(self, state: ParticleState) -> CellBins:
        return bin_particles(self.domain, state.positions, state.fields,
                             m_c=self.m_c)

    def traffic_report(self, avg_ppc: float) -> "traffic.TrafficReport":
        return traffic.model(self.domain, self.m_c, avg_ppc)[self.strategy]


def plan(domain: Domain, kernel: Optional[PairKernel] = None, *,
         positions: Optional[Array] = None, m_c: Optional[int] = None,
         strategy: str = "auto", backend: str = "reference",
         batch_size: int = 64, box: Optional[Tuple[int, int, int]] = None,
         interpret: Optional[bool] = None,
         compact: bool = False, max_active: Optional[int] = None,
         layout: str = "dense", row_cap: Optional[int] = None,
         pair_cap: Optional[int] = None,
         m_c_slack: float = 1.5,
         halo_inner: str = "reference", n_shards: Optional[int] = None,
         shard_axis: str = "halo", shard_cap: Optional[int] = None,
         mesh=None) -> InteractionPlan:
    """Build an :class:`InteractionPlan` (static planning, done once).

    Every static bound taken or measured here (``m_c``, ``max_active``,
    ``row_cap``, ``shard_cap``) obeys one safety contract — measured with
    slack, overflow detectable, grown individually by
    ``execute_or_replan`` — stated once on :meth:`InteractionPlan.replan`.

    Args:
      domain: the cell grid.
      kernel: pair kernel (default Lennard-Jones).
      positions: representative positions; required when ``m_c`` is None
        (measured bound) or ``strategy="auto"`` (fill ratio for the cost
        model).
      m_c: static max-particles-per-cell bound; measured from ``positions``
        with slack + sublane alignment when omitted.
      strategy: one of ``par_part | cell_dense | xpencil | allin |
        naive_n2``; ``"auto"`` to pick the minimum modelled HBM traffic
        per interaction (``core.traffic``); or ``"autotune"`` to *measure*
        candidate schedules on ``positions`` and return the empirically
        fastest (``core.autotune``; winners persist in an on-disk cache).
      backend: ``"reference"`` (pure-JAX schedules), ``"pallas"`` (TPU
        kernels; interpret mode off-TPU), or ``"halo"`` (distributed
        Z-slab execution on a device mesh — ``repro.dist.engine``; the
        per-shard schedule runs on ``halo_inner``). With
        ``strategy="autotune"``, ``"all"`` defers to the tuner's platform
        default set (reference everywhere, plus native Pallas on TPU).
      box: All-in-SM sub-box override; sized from the VMEM budget otherwise.
      interpret: force Pallas interpret mode (None = auto by platform).
      compact: occupancy-compacted execution — iterate only work units
        (pencils / sub-boxes) that actually hold particles. Big win on
        clustered or inhomogeneous distributions; a no-op-sized overhead on
        uniform ones. ``strategy="autotune"`` explores compact candidates
        on its own and ignores this flag (and ``max_active``).
      max_active: static bound on active work units for ``compact=True``;
        measured from ``positions`` (with slack) when omitted.
      layout: slot layout the schedule reads — ``"dense"`` (every cell
        owns ``m_c`` slots), ``"packed"`` (CSR pencil rows: particles
        stored contiguously per row under ``row_cap``, bytes proportional
        to the particles instead of the padding — the few-particles-per-
        cell fix; ``xpencil`` only), or ``"sfc"`` (space-filling-curve
        cell clusters driven by a compressed cluster-pair neighbor list
        under ``pair_cap`` — the schedule itself shrinks to the occupied
        stencil pairs; ``cell_dense`` only). Composes with ``compact``
        and with ``backend="halo"``. Bit-identical to dense.
        ``strategy="autotune"`` explores packed/sfc candidates on its own
        and ignores this flag (and ``row_cap``/``pair_cap``), exactly
        like ``compact``.
      row_cap: static particles-per-packed-row bound for
        ``layout="packed"``; measured from ``positions`` (with slack)
        when omitted.
      pair_cap: static compressed-pair-list bound for ``layout="sfc"``;
        measured from ``positions`` (with slack) when omitted.
      halo_inner: per-shard backend for ``backend="halo"``
        (``"reference"``/``"pallas"``).
      n_shards: Z-slab count for ``backend="halo"`` (must divide ``nz``);
        defaults to the largest divisor of ``nz`` that fits the visible
        devices (1 on a single device — the bit-identical fallback).
      shard_axis / mesh: mesh axis name and an optional explicit
        ``jax.sharding.Mesh``; by default the engine builds a 1-D mesh
        over the local devices.
      shard_cap: static per-shard particle capacity for ``backend="halo"``;
        measured from ``positions`` (with slack) when omitted.
    """
    kernel = kernel or make_lennard_jones()
    if strategy == "autotune":
        from . import autotune
        if positions is None:
            raise ValueError('strategy="autotune" needs positions (the '
                             "tuner times real executions)")
        if backend == "halo":
            # the tuner owns the shard-count axis: fall back to the
            # platform default backends and let halo twins join the sweep
            backend = "all"
        backends = None if backend == "all" else (backend,)
        # the caller's batch_size/box join the sweep as candidates rather
        # than pinning it — the stopwatch gets the final word
        batch_sizes = tuple(dict.fromkeys(
            (batch_size, *autotune.DEFAULT_BATCH_SIZES)))
        return autotune.tune(domain, kernel, positions, m_c=m_c,
                             backends=backends, batch_sizes=batch_sizes,
                             box=box, m_c_slack=m_c_slack,
                             interpret=interpret).plan
    if m_c is None:
        if positions is None:
            raise ValueError("plan() needs either m_c or positions "
                             "(to measure the M_C bound)")
        from .engine import suggest_m_c
        m_c = suggest_m_c(domain, positions, slack=m_c_slack)
    if strategy == "auto":
        if positions is None:
            raise ValueError('strategy="auto" needs positions (the cost '
                             "model is parameterized by the fill ratio)")
        # compact=True narrows the choice to the cell schedules that have a
        # compacted path — otherwise whether auto+compact works would
        # depend on which strategy the cost model happens to pick. The halo
        # decomposition only exists for cell schedules (compacted halo:
        # pencil schedules only). layout="packed" narrows further to the
        # packed-capable schedules.
        among = (("cell_dense", "xpencil", "allin") if compact else None)
        if backend == "halo":
            among = (("cell_dense", "xpencil") if compact
                     else ("cell_dense", "xpencil", "allin"))
        if layout == "packed":
            among = tuple(S.PACKED_STRATEGIES)
        if layout == "sfc":
            among = tuple(S.SFC_STRATEGIES)
        strategy = choose_strategy(domain, m_c,
                                   positions.shape[0] / domain.n_cells,
                                   among=among)
    inner_backend = halo_inner if backend == "halo" else backend
    if backend == "halo":
        from ..dist import engine as dist_engine
        from ..dist.halo import suggest_shard_cap
        if mesh is not None and shard_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh has axes {mesh.axis_names}, no {shard_axis!r} "
                "shard axis — pass shard_axis=<one of them> (or use "
                "plan.distribute(mesh), which defaults to the mesh's "
                "first axis)")
        if n_shards is None:
            if mesh is not None:
                n_shards = int(mesh.shape[shard_axis])
            else:
                n_shards = dist_engine.default_n_shards(domain)
        if n_shards > 1 and shard_cap is None:
            if positions is None:
                raise ValueError("backend='halo' needs either shard_cap or "
                                 "positions (to measure the per-shard "
                                 "capacity)")
            shard_cap = suggest_shard_cap(domain, positions, n_shards)
    if layout == "packed":
        if not supports_layout(inner_backend, strategy, "packed"):
            raise ValueError(
                f"backend {inner_backend!r} has no packed path for "
                f"strategy {strategy!r}; packed-capable pairs: "
                f"{sorted(k[:2] for k in _BACKENDS if k[2] == 'packed')}")
        if row_cap is None:
            if positions is None:
                raise ValueError('layout="packed" needs either row_cap or '
                                 "positions (to measure the packed-row "
                                 "bound)")
            row_cap = suggest_row_cap(domain, positions)
    if layout == "sfc":
        if not supports_layout(inner_backend, strategy, "sfc"):
            raise ValueError(
                f"backend {inner_backend!r} has no sfc path for "
                f"strategy {strategy!r}; sfc-capable pairs: "
                f"{sorted(k[:2] for k in _BACKENDS if k[2] == 'sfc')}")
        if pair_cap is None:
            if positions is None:
                raise ValueError('layout="sfc" needs either pair_cap or '
                                 "positions (to measure the pair-list "
                                 "bound)")
            if backend == "halo" and n_shards > 1:
                # per-shard bound: each slab has its own cluster order,
                # so the busiest shard's measured pair list sets the cap
                from ..dist.engine import shard_sfc_pairs
                counts_ = _cell_counts(domain, positions)
                n_pairs = int(max(shard_sfc_pairs(domain, counts_,
                                                  n_shards)))
                pair_cap = -(-max(1, int(n_pairs * 1.25 + 0.999)) // 8) * 8
            else:
                pair_cap = suggest_pair_cap(domain, positions)
    if compact:
        if not supports_compact(inner_backend, strategy, layout):
            raise ValueError(
                f"backend {inner_backend!r} has no compacted path for "
                f"strategy {strategy!r} (layout {layout!r}); "
                f"compact-capable triples: {sorted(_COMPACT_OK)}")
        if max_active is None:
            if positions is None:
                raise ValueError("compact=True needs either max_active or "
                                 "positions (to measure the active-unit "
                                 "bound)")
            if backend == "halo" and n_shards > 1:
                # one static bound shared by all shards: the busiest
                # shard's active pencils, not the global count
                from ..dist.halo import suggest_shard_max_active
                max_active = suggest_shard_max_active(domain, positions,
                                                      n_shards)
            else:
                mbox = box
                if strategy == "allin" and mbox is None:
                    mbox = _allin_box(domain, m_c)
                max_active = suggest_max_active(domain, positions, strategy,
                                                box=mbox)
    p = InteractionPlan(domain=domain, kernel=kernel, m_c=m_c,
                        strategy=strategy, backend=backend,
                        batch_size=batch_size, box=box, interpret=interpret,
                        compact=compact, max_active=max_active,
                        layout=layout, row_cap=row_cap, pair_cap=pair_cap,
                        halo_inner=halo_inner, n_shards=n_shards,
                        shard_axis=shard_axis, shard_cap=shard_cap,
                        mesh=mesh)
    if strategy != "naive_n2":
        # fail at plan time, not execute time (halo validates the
        # per-shard backend the slab schedule will actually dispatch to)
        get_backend(inner_backend, strategy, layout)
        # a kernel whose staging cannot fit VMEM/SMEM is refused here,
        # never left to fail (or be degraded around) at execute time
        kernel_budget(p)
    return p


def choose_strategy(domain: Domain, m_c: int, avg_ppc: float,
                    among: Optional[Tuple[str, ...]] = None) -> str:
    """``strategy="auto"``: minimize modelled HBM bytes per interaction.

    The paper's Fig. 7 argument as a decision rule — the schedule that moves
    the fewest global-memory bytes per interaction wins in the memory-bound
    regime the paper targets. Ties break toward the paper's X-pencil.
    ``among`` restricts the choice (e.g. to the compact-capable schedules).
    """
    reports = traffic.model(domain, m_c, max(avg_ppc, 1e-3))
    order = {"xpencil": 0, "allin": 1, "cell_dense": 2, "par_part": 3}
    pool = [r for r in reports.values() if among is None or r.strategy in among]
    return min(pool,
               key=lambda r: (r.hbm_bytes_per_interaction,
                              order[r.strategy])).strategy


def _allin_box(domain: Domain, m_c: int) -> Tuple[int, int, int]:
    """VMEM-budget sub-box, shrunk to divisors of the grid (static)."""
    return S.shrink_to_divisors(domain, S.subbox_dims(domain, m_c))


_cell_counts = cell_counts          # binning owns the single binning pass


def _max_cell_count(domain: Domain, positions: Array) -> Array:
    return jnp.max(_cell_counts(domain, positions))


def active_unit_count(domain: Domain, positions: Array,
                      strategy: str = "xpencil",
                      box: Optional[Tuple[int, int, int]] = None,
                      counts: Optional[Array] = None) -> int:
    """Number of active work units — (z, y) pencils (``xpencil`` /
    ``cell_dense``) or sub-boxes (``allin``, for the given tiling) — that
    hold at least one particle. One-off (outside jit) occupancy probe;
    pass precomputed per-cell ``counts`` to skip the binning pass."""
    if counts is None:
        counts = _cell_counts(domain, positions)
    if strategy == "allin":
        if box is None:
            box = _allin_box(domain, 1)
        box = S.shrink_to_divisors(domain, box)
        uc = subbox_counts(domain, counts, box)
    else:
        uc = pencil_counts(domain, counts)
    return int(jnp.sum(uc > 0))


def n_units(domain: Domain, strategy: str = "xpencil",
            box: Optional[Tuple[int, int, int]] = None) -> int:
    """Total work units of a schedule (denominator of the fill fraction)."""
    if strategy == "allin":
        if box is None:
            box = _allin_box(domain, 1)
        bx, by, bz = S.shrink_to_divisors(domain, box)
        return (domain.nx // bx) * (domain.ny // by) * (domain.nz // bz)
    return domain.nz * domain.ny


def suggest_max_active(domain: Domain, positions: Array,
                       strategy: str = "xpencil",
                       box: Optional[Tuple[int, int, int]] = None,
                       slack: float = 1.25, align: int = 8,
                       counts: Optional[Array] = None) -> int:
    """One-off static ``max_active`` bound: measured active units with
    slack, rounded up to ``align``, clipped to the total unit count (a full
    bound degrades gracefully to dense coverage). The compacted-path
    counterpart of ``suggest_m_c``. Pass precomputed per-cell ``counts``
    to skip the binning pass (or to exclude masked padding rows)."""
    n_act = active_unit_count(domain, positions, strategy, box=box,
                              counts=counts)
    total = n_units(domain, strategy, box=box)
    bound = max(1, int(n_act * slack + 0.999))
    bound = -(-bound // align) * align
    return min(bound, total)


def suggest_row_cap(domain: Domain, positions: Array, slack: float = 1.25,
                    align: int = 8, counts: Optional[Array] = None) -> int:
    """One-off static ``row_cap`` bound for ``layout="packed"``: the
    fullest *padded* pencil row (interior particles plus periodic X-ghost
    copies — ``binning.padded_row_counts``) with slack, rounded up to
    ``align`` (sublane contract). The packed-layout counterpart of
    ``suggest_m_c``; obeys the replan contract
    (:meth:`InteractionPlan.replan`). Pass precomputed per-cell ``counts``
    to skip the binning pass."""
    if counts is None:
        counts = _cell_counts(domain, positions)
    mx = int(jnp.max(padded_row_counts(domain, counts)))
    cap = max(1, int(mx * slack + 0.999))
    return -(-cap // align) * align


def suggest_pair_cap(domain: Domain, positions: Optional[Array] = None,
                     slack: float = 1.25, align: int = 8,
                     counts: Optional[Array] = None) -> int:
    """One-off static ``pair_cap`` bound for ``layout="sfc"``: the measured
    compressed cluster-pair list length (``binning.sfc_pair_count``) with
    slack, rounded up to ``align``, clipped to the all-pairs total
    ``n_clusters * 27`` (the bound degrades gracefully to the dense
    stencil). The SFC-layout counterpart of ``suggest_row_cap``; obeys the
    replan contract (:meth:`InteractionPlan.replan`). Pass precomputed
    per-cell ``counts`` to skip the binning pass."""
    n_pairs = sfc_pair_count(domain, positions, counts=counts)
    cap = max(1, int(n_pairs * slack + 0.999))
    cap = -(-cap // align) * align
    return max(min(cap, sfc_n_clusters(domain) * 27), n_pairs)


# --------------------------------------------------------------------------
# execution (jitted per plan)
# --------------------------------------------------------------------------

# Dispatch accounting: incremented once per execute/execute_batch call (i.e.
# per jitted dispatch, not per traced system). Lets tests and benchmarks
# assert that the batched path really amortizes dispatch — B systems through
# ``execute_batch`` move this by 1, a Python loop moves it by B.
#
# Recompile accounting: incremented every time an executor *body* is traced
# (the Python body of a jitted function runs at trace time only, so a
# counter bump inside it counts traces, not calls). The serving tier's
# steady-state guarantee — "a warm engine never recompiles" — is asserted
# against this counter instead of scraping JAX internals.
#
# Both live in the process metrics registry (``repro.obs``), labeled by
# (backend, strategy, layout) when the caller has a plan in hand; the
# functions below are the historical unlabeled views (registry-wide sums),
# so every pre-existing assertion keeps its semantics while
# ``obs.render_prom()`` exposes the labeled families.
DISPATCH_TOTAL = "repro_dispatch_total"
RECOMPILE_TOTAL = "repro_recompile_total"
REPLAN_TOTAL = "repro_replan_total"

# live Counter instances keyed by (name, backend, strategy, layout) — a
# registry ``reset()`` zeroes them in place, so the cache never goes stale
_metric_cache: Dict[tuple, _obs_metrics.Counter] = {}


def _plan_counter(name: str,
                  p: Optional["InteractionPlan"]) -> _obs_metrics.Counter:
    key = (name,) if p is None else (name, p.backend, p.strategy, p.layout)
    c = _metric_cache.get(key)
    if c is None:
        labels = ({} if p is None else
                  {"backend": p.backend, "strategy": p.strategy,
                   "layout": p.layout})
        c = _metric_cache[key] = _obs_metrics.registry.counter(name, **labels)
    return c


def dispatch_count() -> int:
    return int(_obs_metrics.registry.total(DISPATCH_TOTAL))


def recompile_count() -> int:
    """Executor traces so far (see the accounting note above): moves only
    when a jitted executor body is (re-)traced — a new plan, a new state
    structure/shape, or an LRU-evicted executor being rebuilt."""
    return int(_obs_metrics.registry.total(RECOMPILE_TOTAL))


def replan_count() -> int:
    """Replans so far: ``plan.replan`` calls that actually grew a bound."""
    return int(_obs_metrics.registry.total(REPLAN_TOTAL))


def reset_counters() -> None:
    """Zero every steady-state counter in the metrics registry — dispatch,
    recompile, replan, *and* cross-module counters like the autotuner's
    ``timing_run_count`` — in one call (test/benchmark bookkeeping; the
    executor caches themselves are untouched). Historically this cleared
    only dispatch/recompile and silently left ``autotune.timing_run_count``
    running; routing everything through ``obs.registry.reset()`` closes
    that footgun."""
    _obs_metrics.registry.reset()


def _count_dispatch(p: Optional["InteractionPlan"] = None) -> None:
    _plan_counter(DISPATCH_TOTAL, p).inc()


def _count_recompile(p: Optional["InteractionPlan"] = None) -> None:
    _plan_counter(RECOMPILE_TOTAL, p).inc()


def _count_replan(p: Optional["InteractionPlan"] = None) -> None:
    _plan_counter(REPLAN_TOTAL, p).inc()


def _impl(p: InteractionPlan) -> Callable:
    """The traced executor body shared by the single and batched paths."""

    if p._multi_shard:
        # distributed halo execution: partition -> shard_map(bin + ghost
        # exchange + local schedule) -> scatter-back (repro.dist.engine)
        from ..dist.engine import halo_impl
        inner = halo_impl(p)

        def halo_counted(state: ParticleState) -> Tuple[Array, Array]:
            _count_recompile(p)          # runs at trace time only
            return inner(state)
        return halo_counted

    # a single-shard halo plan runs the inner backend directly — no mesh,
    # no exchange: the bit-identical single-device fallback
    backend = p.halo_inner if p.backend == "halo" else p.backend

    def impl(state: ParticleState) -> Tuple[Array, Array]:
        _count_recompile(p)              # runs at trace time only
        if p.strategy == "naive_n2":
            if state.valid is not None:
                raise ValueError(
                    "naive_n2 bypasses binning and cannot mask padded "
                    "(valid=) rows; use a cell schedule")
            with jax.named_scope("pair"):
                fx, fy, fz, pot = S.naive_n2(p.domain, state.positions,
                                             p.kernel)
                return jnp.stack([fx, fy, fz], axis=-1), pot
        bins = bin_particles(p.domain, state.positions, state.fields,
                             m_c=p.m_c, valid=state.valid)
        if p.layout == "packed":
            packed = pack_rows(p.domain, bins, row_cap=p.row_cap)
            return get_backend(backend, p.strategy, "packed")(p, packed,
                                                              state)
        if p.layout == "sfc":
            sfc = build_sfc_clusters(p.domain, bins, pair_cap=p.pair_cap)
            return get_backend(backend, p.strategy, "sfc")(p, sfc, state)
        return get_backend(backend, p.strategy)(p, bins, state)

    return impl


_CacheInfo = collections.namedtuple(
    "CacheInfo", ["hits", "misses", "maxsize", "currsize"])


class _LRU:
    """A ``functools.lru_cache`` stand-in whose capacity can be resized.

    Same observable surface as the stdlib decorator (``cache_info()`` /
    ``cache_clear()``), plus :meth:`resize` so tests can shrink the cache
    and exercise eviction + re-admission without building 100+ plans. Kept
    bounded (not unbounded) because the autotuner times throwaway
    candidate plans by the dozen, and an unbounded cache would pin every
    one of their traces (and compiled executables) for the process
    lifetime.
    """

    def __init__(self, maxsize: int, build: Callable):
        self._build = build
        self._maxsize = maxsize
        self._data: "collections.OrderedDict" = collections.OrderedDict()
        self._hits = 0
        self._misses = 0

    def __call__(self, *key):
        if key in self._data:
            self._hits += 1
            self._data.move_to_end(key)
            return self._data[key]
        self._misses += 1
        value = self._build(*key)
        self._data[key] = value
        self._evict()
        return value

    def _evict(self) -> None:
        while len(self._data) > self._maxsize:
            self._data.popitem(last=False)

    def resize(self, maxsize: int) -> None:
        """Change the capacity; excess (least-recent) entries are evicted
        immediately. Evicting a live executor only costs a retrace on its
        next use — never correctness (tests/test_serve.py proves it)."""
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self._maxsize = maxsize
        self._evict()

    def cache_info(self) -> "_CacheInfo":
        return _CacheInfo(self._hits, self._misses, self._maxsize,
                          len(self._data))

    def cache_clear(self) -> None:
        self._data.clear()
        self._hits = 0
        self._misses = 0


def _build_executor(p: InteractionPlan,
                    field_names: Tuple[str, ...]) -> Callable:
    """One jitted executor per (plan, state structure)."""
    return jax.jit(_impl(p))


def _build_batch_executor(p: InteractionPlan,
                          field_names: Tuple[str, ...]) -> Callable:
    """One jitted executor per (plan, state structure) for stacked states."""
    impl = _impl(p)
    if p._multi_shard:
        # vmap cannot batch through shard_map's collectives; lax.map keeps
        # the contract that matters — B systems, one jitted dispatch,
        # bit-identical to the per-state loop
        return jax.jit(lambda states: jax.lax.map(impl, states))
    return jax.jit(jax.vmap(impl))


_executor = _LRU(128, _build_executor)
_batch_executor = _LRU(32, _build_batch_executor)


# jitted fn -> {argument signature: Compiled}; entries go with the jitted
# function, so the caches that hold the functions bound this one too
_COMPILED: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def compile_ahead(fn: Callable, *args):
    """Lower and compile the jitted ``fn`` for ``args``' shapes before it
    runs; returns the ``jax.stages.Compiled`` executable.

    The guarded loops (``execute_checked``, trajectory segments, serving
    dispatch) call this before each run and let its errors through their
    fault handlers. A lowering or compile error is a defect of the
    program on this platform — a kernel Mosaic refuses, a program that
    does not fit the device — not a runtime fault, so it raises to the
    caller instead of being absorbed by the degradation ladder. Memoized
    per (fn, argument structure and shapes) for as long as ``fn`` lives;
    jit shares the executable, so the call that follows does not compile
    again."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    key = (tree, tuple((getattr(x, "shape", ()),
                        str(getattr(x, "dtype", type(x)))) for x in leaves))
    programs = _COMPILED.setdefault(fn, {})
    if key not in programs:
        programs[key] = fn.lower(*args).compile()
    return programs[key]


def clear_executor_cache() -> None:
    """Drop every cached executor trace (single and batched)."""
    _executor.cache_clear()
    _batch_executor.cache_clear()


def set_executor_cache_size(single: Optional[int] = None,
                            batch: Optional[int] = None) -> None:
    """Resize the executor LRUs (excess entries evicted immediately).

    Serving deployments with many live shape classes can raise the bounds;
    tests shrink them to force eviction. Eviction is a latency event, never
    a correctness one — a rebuilt executor retraces the same plan."""
    if single is not None:
        _executor.resize(single)
    if batch is not None:
        _batch_executor.resize(batch)


def executor_cache_info() -> Dict[str, "_CacheInfo"]:
    """Observability hook: ``{"single": CacheInfo, "batch": CacheInfo}``
    (hits / misses / maxsize / currsize, stdlib ``lru_cache`` schema)."""
    return {"single": _executor.cache_info(),
            "batch": _batch_executor.cache_info()}


# --------------------------------------------------------------------------
# guarded execution: ExecutionReport, degradation ladder, circuit breaker
# --------------------------------------------------------------------------

# The resilience layer's core contract: ``plan.execute_checked`` never
# hangs and absorbs every runtime fault. Failures (transient backend
# errors, non-finite outputs, injected chaos — repro.testing.chaos) are
# absorbed by a per-plan circuit breaker with hysteresis:
# _FAILURE_THRESHOLD consecutive failures step one rung DOWN the ladder
# (pallas -> reference backend, then packed -> compact -> dense layout);
# _RECOVERY_THRESHOLD consecutive clean executions step one rung back UP.
# Every rung is bit-identical to the healthy path by construction (the
# repo-wide parity guarantee), so degradation costs latency, never
# answers — tests/test_chaos.py parity-checks it.

_FAILURE_THRESHOLD = 3     # consecutive failures to trip one rung down
_RECOVERY_THRESHOLD = 8    # consecutive clean calls to climb one rung up


@dataclasses.dataclass
class PlanHealth:
    """Per-plan circuit-breaker state (see the note above). ``level``
    indexes into :func:`degradation_ladder`; 0 = healthy."""

    level: int = 0
    consec_failures: int = 0
    consec_clean: int = 0
    trips: int = 0             # lifetime rung-down transitions
    recoveries: int = 0        # lifetime rung-up transitions

    def note_failure(self, n_rungs: int) -> bool:
        """Record one failed execution; True if the breaker tripped a
        rung down (hysteresis: the failure streak resets on the trip)."""
        self.consec_clean = 0
        self.consec_failures += 1
        if (self.consec_failures >= _FAILURE_THRESHOLD
                and self.level < n_rungs - 1):
            self.level += 1
            self.trips += 1
            self.consec_failures = 0
            return True
        return False

    def note_success(self) -> bool:
        """Record one clean execution; True if the breaker recovered a
        rung up (after _RECOVERY_THRESHOLD consecutive clean calls)."""
        self.consec_failures = 0
        self.consec_clean += 1
        if self.level > 0 and self.consec_clean >= _RECOVERY_THRESHOLD:
            self.level -= 1
            self.recoveries += 1
            self.consec_clean = 0
            return True
        return False


@dataclasses.dataclass
class ExecutionReport:
    """What one :meth:`InteractionPlan.execute_checked` call observed.

    ``status`` is ``"ok"`` (healthy rung, clean), ``"degraded"`` (results
    from a lower ladder rung — still bit-identical) or ``"failed"``
    (every rung exhausted; forces/potential are zeros). ``plan`` is the
    plan to keep using — replans and elastic shard shrinks applied."""

    status: str = "ok"
    plan: Optional[InteractionPlan] = None
    overflow: Optional[str] = None     # bound class that overflowed
    replans: int = 0                   # bound-growth events this call
    retries: int = 0                   # extra execution attempts
    nonfinite: int = 0                 # non-finite output elements seen
    out_of_domain: int = 0             # valid particles outside the box
    faults: List[str] = dataclasses.field(default_factory=list)
    ladder_level: int = 0              # rung that produced the result
    backend: str = ""                  # backend of that rung
    layout: str = ""                   # layout of that rung
    breaker_trips: int = 0             # rung-down transitions this call
    recovered: bool = False            # rung-up transition this call
    shard_shrinks: int = 0             # elastic mesh shrinks this call


def _health_key(p: InteractionPlan) -> Tuple:
    """Breaker identity: the plan minus its grown/derived bounds, so a
    replan (grown m_c/row_cap/...) or an elastic shard shrink keeps the
    same breaker state instead of resetting to healthy."""
    return (p.domain, p.kernel, p.strategy, p.backend, p.halo_inner,
            p.layout, p.compact, p.batch_size, p.interpret)


_health: Dict[Tuple, PlanHealth] = {}


def plan_health(p: InteractionPlan) -> PlanHealth:
    """The live circuit-breaker state for a plan (created healthy on
    first access). Observability + test hook."""
    return _health.setdefault(_health_key(p), PlanHealth())


def reset_health() -> None:
    """Forget every plan's breaker state (test bookkeeping)."""
    _health.clear()


def degradation_ladder(p: InteractionPlan) -> Tuple[InteractionPlan, ...]:
    """The rungs ``execute_checked`` steps down under repeated failure:
    the plan itself, then backend pallas -> reference, then layout
    packed/sfc -> compact -> dense. Every rung computes bit-identical
    results — only cost and code path change. Rung 0 is always ``p``;
    plans already on the reference/dense path have a one-rung ladder."""
    rungs = [p]
    q = p
    inner = q.halo_inner if q.backend == "halo" else q.backend
    if inner == "pallas":
        if q.backend == "halo":
            q = dataclasses.replace(q, halo_inner="reference")
        else:
            q = dataclasses.replace(q, backend="reference")
        rungs.append(q)
    if q.layout in ("packed", "sfc"):
        q = dataclasses.replace(q, layout="dense")
        rungs.append(q)
    if q.compact:
        q = dataclasses.replace(q, compact=False)
        rungs.append(q)
    return tuple(rungs)


def fallback_plan(p: InteractionPlan) -> InteractionPlan:
    """The most-degraded rung (reference backend, dense layout) — the
    serving tier quarantines a broken shape class onto this plan."""
    return degradation_ladder(p)[-1]


@functools.partial(jax.jit, static_argnames=("box",))
def _output_check(forces: Array, pot: Array, positions: Array,
                  valid: Optional[Array], box: Tuple[float, float, float]):
    """One fused reduction over the outputs: (non-finite force/potential
    elements, valid particles outside the domain box). Padding rows are
    excluded from both counts."""
    if valid is None:
        fmask = jnp.ones(forces.shape[:-1], bool)
    else:
        fmask = valid
    bad = (jnp.sum(jnp.where(fmask[..., None], ~jnp.isfinite(forces), False))
           + jnp.sum(jnp.where(fmask, ~jnp.isfinite(pot), False)))
    lim = jnp.asarray(box, positions.dtype)
    ood = jnp.any((positions < 0.0) | (positions > lim), axis=-1)
    ood = jnp.sum(jnp.where(fmask, ood, False))
    return bad, ood


class _NonFiniteOutput(RuntimeError):
    """Internal: an execution produced non-finite forces/potential."""

    def __init__(self, count: int):
        super().__init__(f"{count} non-finite output element(s)")
        self.count = int(count)


def _execute_checked(base: InteractionPlan, state: ParticleState, *,
                     max_replans: int = 4,
                     max_retries: Optional[int] = None,
                     sleep=None
                     ) -> Tuple[Tuple[Array, Array], "ExecutionReport"]:
    """The guarded-dispatch engine behind ``plan.execute_checked``."""
    with _obs_trace("plan.execute_checked", backend=base.backend,
                    strategy=base.strategy, layout=base.layout) as sp:
        out, report = _execute_checked_impl(base, state,
                                            max_replans=max_replans,
                                            max_retries=max_retries,
                                            sleep=sleep)
        sp.set(status=report.status, overflow=report.overflow or "none",
               replans=report.replans, retries=report.retries,
               ladder_level=report.ladder_level)
        return out, report


def _execute_checked_impl(base: InteractionPlan, state: ParticleState, *,
                          max_replans: int = 4,
                          max_retries: Optional[int] = None,
                          sleep=None
                          ) -> Tuple[Tuple[Array, Array], "ExecutionReport"]:
    from ..testing import chaos

    report = ExecutionReport(plan=base)
    p = base

    # 1. bounded replan loop — an injected overflow verdict with nothing
    # to grow must not storm (replan returns an equal plan; stop).
    for _ in range(max_replans):
        oc = p.overflow_class(state)
        if oc is None:
            break
        report.overflow = report.overflow or oc
        grown = p.replan(state)
        report.replans += 1
        if grown == p:
            break
        p = grown
    report.plan = p

    rungs = degradation_ladder(p)
    health = plan_health(p)
    level = min(health.level, len(rungs) - 1)
    if max_retries is None:
        max_retries = _FAILURE_THRESHOLD * len(rungs)
    attempts = 0

    forces = pot = None
    while True:
        rung = rungs[level]
        compiling = False
        try:
            if sleep is None:
                chaos.maybe_delay("core.dispatch")
            else:
                chaos.maybe_delay("core.dispatch", sleep=sleep)
            if rung._multi_shard:
                chaos.maybe_raise("dist.exchange")
            chaos.maybe_raise("core.dispatch")
            compiling = True
            rung.compile(state)
            compiling = False
            f, u = rung.execute(state)
            f = chaos.corrupt("core.dispatch", f)
            bad, ood = _output_check(f, u, state.positions, state.valid,
                                     p.domain.box)
            report.out_of_domain = int(ood)
            if int(bad):
                report.nonfinite += int(bad)
                raise _NonFiniteOutput(int(bad))
            forces, pot = f, u
        except chaos.ShardLost as e:
            report.faults.append(f"shard_loss:{e}")
            if rung._multi_shard:
                # elastic shrink: rebuild at the surviving shard count and
                # re-execute — the existing replan contract re-measures
                # the per-shard bounds (dist.engine.elastic_shrink)
                from ..dist.engine import elastic_shrink
                p = elastic_shrink(p, state)
                report.plan = p
                report.shard_shrinks += 1
                _obs_event("plan.shard_shrink", n_shards=p.n_shards or 1,
                           fault=str(e))
                rungs = degradation_ladder(p)
                health = plan_health(p)      # same key: shrink-stable
                level = min(level, len(rungs) - 1)
            elif health.note_failure(len(rungs)):
                report.breaker_trips += 1
                level = health.level
                _obs_event("plan.degrade", level=level,
                           backend=rungs[level].backend,
                           layout=rungs[level].layout, fault=str(e))
        except (chaos.TransientBackendError, _NonFiniteOutput,
                RuntimeError, ValueError) as e:
            if compiling:
                # a program the compiler refuses is a defect, not a
                # runtime fault: raise it, never degrade around it
                raise
            report.faults.append(f"{type(e).__name__}: {e}")
            if health.note_failure(len(rungs)):
                report.breaker_trips += 1
                level = health.level
                _obs_event("plan.degrade", level=level,
                           backend=rungs[level].backend,
                           layout=rungs[level].layout,
                           fault=type(e).__name__)
        else:
            break                              # clean execution
        attempts += 1
        report.retries = attempts
        if attempts > max_retries:
            report.status = "failed"
            report.ladder_level = level
            report.backend = rung.backend
            report.layout = rung.layout
            zeros = jnp.zeros_like(state.positions)
            return (zeros, jnp.zeros(state.positions.shape[:-1],
                                     state.positions.dtype)), report

    report.recovered = health.note_success()
    if report.recovered:
        _obs_event("plan.recover", level=level,
                   backend=rungs[level].backend)
    report.ladder_level = level
    report.backend = rungs[level].backend
    report.layout = rungs[level].layout
    report.status = "ok" if level == 0 else "degraded"
    return (forces, pot), report


# --------------------------------------------------------------------------
# reference backend: the pure-JAX schedules of core.strategies
# --------------------------------------------------------------------------

@register_backend("reference", "par_part")
def _ref_par_part(p: InteractionPlan, bins: CellBins, state: ParticleState):
    with jax.named_scope("pair"):       # per particle: no scatter-back
        fx, fy, fz, pot = S.par_part(p.domain, bins, state.positions,
                                     p.kernel, p.batch_size)
        return jnp.stack([fx, fy, fz], axis=-1), pot


def _ref_dense(name):
    """Reference cell-schedule backend: dense sweep, or the occupancy-
    compacted variant when the plan asks for it (``plan.compact``)."""
    dense_fn = S.STRATEGIES[name]
    sparse_fn = S.SPARSE_STRATEGIES[name]

    def schedule(p: InteractionPlan, bins: CellBins):
        if p.compact:
            if name == "allin":
                box = S.shrink_to_divisors(p.domain, p.box)
                occ = subbox_occupancy(p.domain, bins.counts, box,
                                       p.max_active)
                return sparse_fn(p.domain, bins, p.kernel, occ, box,
                                 batch_size=p.batch_size)
            occ = pencil_occupancy(p.domain, bins.counts, p.max_active)
            return sparse_fn(p.domain, bins, p.kernel, occ,
                             batch_size=p.batch_size)
        kwargs = {"batch_size": p.batch_size}
        if name == "allin":
            kwargs["box"] = p.box
        return dense_fn(p.domain, bins, p.kernel, **kwargs)

    def impl(p: InteractionPlan, bins: CellBins, state: ParticleState):
        with jax.named_scope("pair"):
            out = schedule(p, bins)
        return dense_to_particles(p.domain, bins, *out)
    return impl


register_backend("reference", "cell_dense", compact=True)(
    _ref_dense("cell_dense"))
register_backend("reference", "xpencil", compact=True)(_ref_dense("xpencil"))
register_backend("reference", "allin", compact=True)(_ref_dense("allin"))


@register_backend("reference", "xpencil", compact=True, layout="packed")
def _ref_xpencil_packed(p: InteractionPlan, packed: PackedRows,
                        state: ParticleState):
    """Packed-row reference backend: CSR rows, active-list iteration when
    the plan is compacted, identity active list otherwise."""
    with jax.named_scope("pair"):
        occ = (pencil_occupancy(p.domain, packed.counts, p.max_active)
               if p.compact else full_pencil_occupancy(p.domain))
        out = S.xpencil_packed(p.domain, packed, p.kernel, occ,
                               batch_size=p.batch_size)
    return packed_to_particles(p.domain, packed, *out)


@register_backend("reference", "cell_dense", compact=True, layout="sfc")
def _ref_cell_sfc(p: InteractionPlan, sfc: SfcClusters,
                  state: ParticleState):
    """SFC cluster reference backend. ``compact=True`` is accepted as a
    no-op: the compressed pair list *is* the occupancy compaction (empty
    neighborhoods never enter ``codes``), so the compacted plan runs the
    same schedule and stays bit-identical by construction."""
    with jax.named_scope("pair"):
        out = S.cell_sfc(p.domain, sfc, p.kernel, batch_size=p.batch_size)
    return sfc_to_particles(p.domain, sfc, *out)
