"""Distributed halo execution engine: ``plan(..., backend="halo")``.

The paper's schedules are single-device; this module makes any cell-schedule
:class:`~repro.core.api.InteractionPlan` run on a JAX device mesh via domain
decomposition — the standard scale-out for cutoff interactions. One jitted
executor per plan does, end to end:

  1. **partition** — a traceable Z-slab gather groups particles by shard
     under the plan's static ``shard_cap`` (``dist.halo.partition_by_shard``),
  2. **per-shard binning** — under ``shard_map``, each shard bins its own
     rows into the slab's padded planes (sentinel rows masked out) and
     offsets slot ids by ``shard * cap`` so the self-pair exclusion stays
     exact across shard boundaries,
  3. **ghost exchange** — the two boundary Z-planes of every binned plane
     (coordinates, extra fields, slot ids) cross to the neighbouring shards
     via ``ppermute`` (``dist.halo.exchange_halo``); periodic Z wraps around
     the shard ring with the minimum-image shift, open Z boundaries get
     empty planes. ``layout="packed"`` plans pack the slab *first*
     (``binning.pack_rows``) and exchange the packed planes — each
     boundary plane crosses as ``row_cap`` slots plus its row-local
     prefix-sum offsets instead of ``(nx+2)*m_c`` dense slots,
  4. **local schedule** — the plan's strategy runs on the local slab through
     the same backend registry as single-device execution (reference or
     Pallas, dense or occupancy-compacted), so every schedule the registry
     knows is immediately distributed,
  5. **scatter-back** — per-shard results return to global particle order.

Overflow stays a *global* contract: ``InteractionPlan.check_overflow``
reduces the per-shard load and per-shard active-pencil counts across shards
(max) against the plan's static bounds, so ``execute_or_replan`` grows
exactly the bound that overflowed — ``m_c``, ``shard_cap``, or the
compacted ``max_active`` — never silently dropping work.

A single-shard halo plan degrades to the inner backend bit-identically (no
mesh, no exchange) — the single-device fallback the README documents.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..core.binning import (EMPTY_POS, bin_particles, build_sfc_clusters,
                            cell_counts, pack_rows, sfc_pair_count,
                            shard_pencil_active, shard_slab_counts)
from ..core.domain import Domain, slab_domain
from ..obs import metrics as _obs_metrics
from ..obs.trace import event as _obs_event, trace as _obs_trace
from . import halo as H

# ppermute ghost-plane exchanges *staged* per executor trace (the halo
# body is shard_mapped and traced once per compile, so — like
# ``core.api.recompile_count`` — this moves at trace time, not per step)
GHOST_EXCHANGE_TOTAL = "repro_ghost_exchange_total"

Array = jnp.ndarray

DEFAULT_SHARD_AXIS = "halo"


# --------------------------------------------------------------------------
# mesh resolution
# --------------------------------------------------------------------------

def default_n_shards(domain: Domain,
                     device_count: Optional[int] = None) -> int:
    """Largest divisor of ``nz`` that fits the available devices (>= 1)."""
    if device_count is None:
        device_count = jax.device_count()
    for n in range(min(device_count, domain.nz), 0, -1):
        if domain.nz % n == 0:
            return n
    return 1


def default_mesh(n_shards: int, axis: str = DEFAULT_SHARD_AXIS) -> Mesh:
    """A 1-D mesh over the first ``n_shards`` local devices."""
    devs = jax.devices()
    if len(devs) < n_shards:
        raise ValueError(
            f"halo plan wants {n_shards} shards but only {len(devs)} "
            "device(s) are visible (emulate with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.asarray(devs[:n_shards]), (axis,))


def resolve_mesh(plan) -> Mesh:
    """The mesh a halo plan executes on: the plan's own, or a default 1-D
    mesh over the first ``n_shards`` local devices."""
    if plan.mesh is not None:
        if plan.shard_axis not in plan.mesh.axis_names:
            raise ValueError(
                f"plan.mesh has axes {plan.mesh.axis_names}, no "
                f"{plan.shard_axis!r} shard axis")
        if int(plan.mesh.shape[plan.shard_axis]) != plan.n_shards:
            raise ValueError(
                f"plan.mesh axis {plan.shard_axis!r} has size "
                f"{plan.mesh.shape[plan.shard_axis]}, plan expects "
                f"{plan.n_shards} shards")
        return plan.mesh
    return default_mesh(plan.n_shards, plan.shard_axis)


# --------------------------------------------------------------------------
# the sharded executor body
# --------------------------------------------------------------------------

def halo_impl(plan):
    """-> traced ``fn(state) -> (forces (N, 3), potential (N,))``.

    Built once per plan (under the plan executor's jit cache). ``plan``
    must be a halo plan with ``n_shards >= 2``; the single-shard fallback
    is handled by the plan layer (it routes straight to the inner backend).
    """
    from ..core.api import ParticleState, get_backend

    dom = plan.domain
    n_shards = plan.n_shards
    axis = plan.shard_axis
    cap = plan.shard_cap
    px, py, pz = dom.periodic_axes
    nz_loc = dom.nz // n_shards
    lz_loc = dom.box[2] / n_shards
    local_dom = slab_domain(dom, n_shards)

    # the per-shard plan: same schedule, same static bounds, slab domain,
    # the inner backend — dispatched through the normal registry so dense,
    # compacted, reference and Pallas shards all share one code path
    inner = dataclasses.replace(plan, domain=local_dom,
                                backend=plan.halo_inner, n_shards=None,
                                shard_cap=None, mesh=None)
    inner_fn = get_backend(inner.backend, inner.strategy, plan.layout)

    def body(pos_blk: Array, fields_blk: Dict[str, Array]):
        idx = jax.lax.axis_index(axis)
        valid = pos_blk[:, 0] < H.VALID_MAX
        z_shift = jnp.asarray([0.0, 0.0, 1.0], pos_blk.dtype) * (
            idx.astype(pos_blk.dtype) * lz_loc)
        local_pos = pos_blk - z_shift
        bins = bin_particles(local_dom, local_pos, fields_blk,
                             m_c=plan.m_c, valid=valid)

        # globally unique slot ids: shard offset keeps the self-pair
        # exclusion exact when a pair straddles a shard boundary
        sid = bins.slot_id
        sid = jnp.where(sid >= 0, sid + idx * cap, sid)

        exchange = lambda plane, fill, coord_shift=0.0: H.exchange_halo(
            plane, axis=axis, n_shards=n_shards, nz_loc=nz_loc,
            shard_index=idx, periodic_z=pz, fill=fill,
            coord_shift=coord_shift)

        def exchange_planes(planes):
            # staging span: the body runs at trace time only, so this
            # records one span per compile, not per step
            with _obs_trace("dist.ghost_exchange", phase="trace",
                            n_shards=n_shards, layout=plan.layout,
                            planes=len(planes)):
                _obs_metrics.registry.counter(
                    GHOST_EXCHANGE_TOTAL,
                    n_shards=n_shards).inc(len(planes))
                out = {}
                for name, plane in planes.items():
                    if name == "z":
                        out[name] = exchange(plane, EMPTY_POS, lz_loc)
                    elif name in ("x", "y"):
                        out[name] = exchange(plane, EMPTY_POS)
                    else:                      # extra per-particle field
                        out[name] = exchange(plane, 0.0)
                return out

        safe_pos = jnp.where(valid[:, None], local_pos, 0.0)
        local_state = ParticleState(safe_pos, fields_blk)

        if plan.layout == "packed":
            # pack the local slab first, then exchange the *packed* ghost
            # planes: each boundary plane crosses as row_cap packed slots
            # plus its (nx+3) prefix-sum offsets — bytes proportional to
            # the boundary particles, not to m_c. No offset rebasing is
            # needed on arrival: cell offsets are row-local (a packed row
            # is self-describing), slot ids already carry the sender's
            # shard offset, and only the z coordinates are rebased into
            # this shard's frame (the usual minimum-image shift).
            packed = pack_rows(local_dom,
                               dataclasses.replace(bins, slot_id=sid),
                               row_cap=plan.row_cap)
            packed = dataclasses.replace(
                packed,
                planes=exchange_planes(packed.planes),
                slot_id=exchange(packed.slot_id, -1),
                slot_cell=exchange(packed.slot_cell, 1),
                cell_offsets=exchange(packed.cell_offsets, 0),
                row_counts=exchange(packed.row_counts[..., None],
                                    0)[..., 0])
            f, pot = inner_fn(inner, packed, local_state)
        elif plan.layout == "sfc":
            # exchange the dense binned planes first — the SFC pair-list
            # bitmask is occupancy-driven (built from slot_id), so ghost
            # planes arriving as dense slots feed the compressed pair
            # list with no extra bookkeeping; each shard then builds its
            # own slab-local cluster order under the plan's static
            # pair_cap (a per-shard bound, checked per shard by
            # halo_overflow_class)
            bins = dataclasses.replace(bins,
                                       planes=exchange_planes(bins.planes),
                                       slot_id=exchange(sid, -1))
            sfc = build_sfc_clusters(local_dom, bins,
                                     pair_cap=plan.pair_cap)
            f, pot = inner_fn(inner, sfc, local_state)
        else:
            bins = dataclasses.replace(bins,
                                       planes=exchange_planes(bins.planes),
                                       slot_id=exchange(sid, -1))
            f, pot = inner_fn(inner, bins, local_state)
        return (jnp.where(valid[:, None], f, 0.0),
                jnp.where(valid, pot, 0.0))

    def impl(state) -> Tuple[Array, Array]:
        if state.bonds is not None:
            raise ValueError(
                "a multi-shard halo plan does not carry bonds yet: the "
                "state's bond list would be dropped at the Z-slab "
                "partition; execute bonded states on one shard")
        mesh = resolve_mesh(plan)
        # like the body, impl itself is traced once per compile: these
        # are staging spans (phase="trace"), not per-dispatch timings
        n = state.positions.shape[0]
        with _obs_trace("dist.partition", phase="trace",
                        n_shards=n_shards, shard_cap=cap, n=n):
            gather_idx, pos_part, fields_part = H.partition_by_shard(
                dom, state.positions, state.fields, n_shards, cap)
        in_specs = (P(axis), {k: P(axis) for k in fields_part})
        sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                out_specs=(P(axis), P(axis)),
                                check_vma=False)
        with _obs_trace("dist.shard_dispatch", phase="trace",
                        n_shards=n_shards, strategy=plan.strategy,
                        layout=plan.layout):
            f_part, pot_part = sharded(pos_part, fields_part)
        # the scatter-back reads every shard's rows: replicate them first
        # (explicit-axis meshes, jax.make_mesh's default, require it), so
        # every device ends up holding the whole (N, 3) / (N,) output
        f_part, pot_part = jax.sharding.reshard(
            (f_part, pot_part), NamedSharding(mesh, P()))
        forces = H.scatter_from_shards(gather_idx, n, f_part)
        pot = H.scatter_from_shards(gather_idx, n, pot_part)
        return forces, pot

    return impl


# --------------------------------------------------------------------------
# the overflow contract, reduced across shards
# --------------------------------------------------------------------------

def halo_overflow(plan, counts: Array) -> bool:
    """Shard-level overflow: True when any shard's particle load exceeds
    ``shard_cap``, or (compacted plans) any shard's active-pencil count
    exceeds ``max_active``. ``counts`` are the global per-cell counts the
    caller already computed for the ``m_c`` check — the shard reductions
    (max across shards) derive from them, so the whole safety check stays
    one binning pass."""
    return halo_overflow_class(plan, counts) is not None


def halo_overflow_class(plan, counts: Array) -> Optional[str]:
    """Which shard-level bound overflowed — ``"shard_cap"`` /
    ``"max_active"`` / ``"pair_cap"`` — or None (:func:`halo_overflow`
    with the bound named, feeding ``InteractionPlan.overflow_class``)."""
    loads = shard_slab_counts(plan.domain, counts, plan.n_shards)
    if int(jnp.max(loads)) > plan.shard_cap:
        return "shard_cap"
    if plan.layout == "sfc":
        if max(shard_sfc_pairs(plan.domain, counts,
                               plan.n_shards)) > plan.pair_cap:
            return "pair_cap"
    if plan.compact:
        act = shard_pencil_active(plan.domain, counts, plan.n_shards)
        if int(jnp.max(act)) > plan.max_active:
            return "max_active"
    return None


def shard_sfc_pairs(domain: Domain, counts: Array, n_shards: int) -> list:
    """Per-shard compressed pair-list lengths of an SFC halo plan.

    Each shard builds its pair list over its *slab* domain's cluster
    order, with the Z ghost planes holding the neighbouring shard's
    boundary occupancy (periodic wrap across the ring, empty on open Z
    boundaries) — exactly the occupancy the exchanged planes carry at
    run time, so this probe bounds every shard's traced ``n_pairs``
    the way ``sfc_pair_count`` bounds the single-device one."""
    nx, ny, nz = domain.ncells
    nz_loc = nz // n_shards
    grid = np.asarray(counts).reshape(nz, ny, nx)
    local_dom = slab_domain(domain, n_shards)
    pz = domain.periodic_axes[2]
    empty = np.zeros((ny, nx), grid.dtype)
    out = []
    for s in range(n_shards):
        lo, hi = s * nz_loc - 1, (s + 1) * nz_loc
        below = grid[lo % nz] if (pz or lo >= 0) else empty
        above = grid[hi % nz] if (pz or hi < nz) else empty
        out.append(sfc_pair_count(
            local_dom, counts=grid[s * nz_loc:(s + 1) * nz_loc],
            ghost_z=(below, above)))
    return out


# --------------------------------------------------------------------------
# elastic shrink: survive a lost shard
# --------------------------------------------------------------------------

# Re-exported here because shard loss is a *distributed* failure mode even
# though the exception class lives with the injection registry: callers
# catching a lost shard should not need to know about repro.testing.
from ..testing.chaos import ShardLost  # noqa: E402  (re-export)


def surviving_shard_count(domain: Domain, n_shards: int,
                          lost: int = 1) -> int:
    """The shard count to rebuild at after ``lost`` shards die: the
    largest divisor of ``nz`` at most ``n_shards - lost`` (>= 1, so a
    mesh can always shrink to the bit-identical single-device
    fallback)."""
    target = max(1, int(n_shards) - int(lost))
    for n in range(target, 0, -1):
        if domain.nz % n == 0:
            return n
    return 1


def elastic_shrink(plan, state=None, lost: int = 1):
    """A twin of ``plan`` rebuilt at the surviving shard count.

    The shard-loss half of the resilience contract
    (``InteractionPlan.execute_checked`` calls this when a
    :class:`ShardLost` surfaces): the Z-slab decomposition is re-cut at
    :func:`surviving_shard_count` shards, the mesh is dropped (re-resolved
    over the surviving devices at next dispatch), and the per-shard static
    bounds are re-measured under the ordinary replan contract —
    ``suggest_shard_cap`` / ``suggest_shard_max_active`` when
    representative ``state`` positions are given, a conservative
    load-ratio scaling of the old bounds otherwise. Shrinking to one
    shard degrades to the inner backend bit-identically."""
    if not plan.n_shards or plan.n_shards <= 1:
        return plan
    ns = surviving_shard_count(plan.domain, plan.n_shards, lost)
    if ns <= 1:
        return dataclasses.replace(plan, n_shards=1, shard_cap=None,
                                   mesh=None, box=None)
    pos = state.positions if state is not None else None
    if pos is not None:
        shard_cap = H.suggest_shard_cap(plan.domain, pos, ns)
    else:
        # fewer shards -> each slab holds at least old_load * old/new
        ratio = plan.n_shards / ns
        shard_cap = -(-int(plan.shard_cap * ratio + 0.999) // 8) * 8
    max_active = plan.max_active
    if plan.compact:
        if pos is not None:
            max_active = H.suggest_shard_max_active(plan.domain, pos, ns)
        else:
            max_active = min(-(-int(max_active * ratio + 0.999) // 8) * 8,
                             plan.domain.nz * plan.domain.ny)
    return dataclasses.replace(plan, n_shards=ns, shard_cap=shard_cap,
                               max_active=max_active, mesh=None, box=None)


def halo_grown_bounds(plan, state, align: int = 8
                      ) -> Tuple[int, Optional[int]]:
    """-> ``(shard_cap, max_active)`` covering ``state``, growing only the
    bound(s) that actually overflowed (the replan contract)."""
    pos = state.positions
    counts = cell_counts(plan.domain, pos)           # one binning pass
    shard_cap = plan.shard_cap
    loads = H.shard_loads(plan.domain, pos, plan.n_shards, counts=counts)
    if int(jnp.max(loads)) > shard_cap:
        grow = -(-(shard_cap + 1) // align) * align      # aligned, > cap
        shard_cap = max(
            H.suggest_shard_cap(plan.domain, pos, plan.n_shards,
                                align=align), grow)
    max_active = plan.max_active
    if plan.compact:
        n_act = int(jnp.max(shard_pencil_active(plan.domain, counts,
                                                plan.n_shards)))
        if n_act > max_active:
            max_active = max(
                H.suggest_shard_max_active(plan.domain, pos, plan.n_shards,
                                           align=align, counts=counts),
                n_act)
    return shard_cap, max_active
