"""Binning pipeline: the paper's Section 2 preprocessing, TPU-shaped.

Pipeline (paper order, atomic-free):
  1. per-particle cell index (parallel),
  2. per-cell counts      -> ``jax.ops.segment_sum`` (replaces atomics),
  3. cell start offsets   -> the paper's prefix sum (``core.prefix``),
  4. out-of-place reorder -> one sort by (cell id, particle id) that carries
     x/y/z and every field along (no gather by its permutation); cell
     coordinates are decoded from the sorted id, rank-in-cell from the
     offsets,
  5. **dense cell-slot layout**: every cell owns exactly ``m_c`` contiguous
     slots in SoA planes of shape ``(nz+2, ny+2, (nx+2)*m_c)``.

Step 5 is the TPU adaptation (DESIGN.md §2): X stays the fastest axis (the
paper's linearization), so an X-pencil of cells is one contiguous row and the
3-cell interaction window of a cell is one contiguous ``3*m_c`` slice — the
structural equivalent of what the paper builds in shared memory with its
local-offset prefix sums. The one-cell ghost ring (always empty for open
boundaries, wrapped copies for periodic domains) removes all border branching.

``m_c`` is the paper's M_C — the max particles per cell — and must be a
static (trace-time) bound. Overflowing particles are dropped by the scatter
(``mode='drop'``); ``CellBins.counts`` lets callers detect that and re-bin
with a larger bound (the engine does exactly what the paper does: track the
max while computing the prefix sum).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import device_scope
from .domain import Domain
from .prefix import exclusive_prefix_sum

Array = jnp.ndarray

# Sentinel coordinate for empty slots: far outside any box, finite so that
# (sentinel - real) stays finite and (sentinel - sentinel) == 0; both cases
# are masked out by slot ids anyway (DESIGN: TPUs want masks, not NaN traps).
EMPTY_POS = 1.0e8

# Slot-id offset carried by periodic ghost *copies*: a particle must still
# interact with its own periodic image, so ghost slots mirror the interior
# ids bumped by this constant — never equal to any real id, so the
# self-pair exclusion (id equality) keeps excluding only the true self
# pair. Shared with the distributed halo layer, whose cross-shard ghost
# planes use per-shard id offsets for the same reason.
GHOST_ID_BUMP = 1_000_000_000


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CellBins:
    """Dense cell-slot state. All planes share shape (nz+2, ny+2, (nx+2)*m_c)."""

    planes: Dict[str, Array]      # SoA field planes ("x","y","z",...)
    slot_id: Array                # int32 particle index per slot, -1 if empty
    counts: Array                 # (n_cells,) particles per cell
    offsets: Array                # (n_cells,) exclusive prefix (paper Fig. 1)
    particle_slot: Array          # (N,) flat slot index of each particle
    m_c: int = dataclasses.field(metadata=dict(static=True))

    @property
    def max_count(self) -> Array:
        return jnp.max(self.counts)


def padded_shape(domain: Domain, m_c: int) -> Tuple[int, int, int]:
    nx, ny, nz = domain.ncells
    return (nz + 2, ny + 2, (nx + 2) * m_c)


def cell_counts(domain: Domain, positions: Array,
                valid: Array | None = None) -> Array:
    """(n_cells,) particles per cell — the one binning pass every static
    bound probe (``m_c``, shard loads, occupancy) derives from. ``valid``
    masks out padding rows (the serving tier pads requests to a shape
    class; padded rows must not inflate any bound probe)."""
    weights = (jnp.ones((positions.shape[0],), jnp.int32) if valid is None
               else valid.astype(jnp.int32))
    return jax.ops.segment_sum(
        weights, domain.cell_ids(positions), num_segments=domain.n_cells)


def bin_particles(domain: Domain, positions: Array,
                  fields: Dict[str, Array] | None = None, *,
                  m_c: int, valid: Array | None = None) -> CellBins:
    """Bin particles into the dense slot layout.

    Args:
      positions: (N, 3) float array.
      fields: optional extra per-particle scalars to bin alongside x/y/z.
      m_c: static max-particles-per-cell bound (paper's M_C).
      valid: optional (N,) bool mask; False rows (e.g. the sentinel padding a
        halo shard carries) are excluded from counts and never land in a slot.

    The sort by (cell id, particle id) carries x/y/z and every field as
    payload, so the sorted columns come out of the sort and no column is
    gathered by its permutation; each row's cell coordinates are decoded
    from its sorted cell id. Slot order is that of a stable argsort by
    cell id.

    Device scopes: ``bin/sort`` (cell ids, counts, prefix, the payload
    sort, rank), ``bin/scatter`` (the slot planes, ``slot_id``,
    ``particle_slot``), then ``ghost`` for a periodic domain.
    """
    n = positions.shape[0]
    nx, ny, nz = domain.ncells
    n_cells = domain.n_cells

    with jax.named_scope("bin/sort"):
        cids = domain.cell_ids(positions)           # (N,)
        columns = [positions[:, 0], positions[:, 1], positions[:, 2],
                   *(fields or {}).values()]

        if valid is None:
            weights = jnp.ones((n,), jnp.int32)
            sort_key = cids
        else:
            # invalid rows carry weight 0 in cell 0 and sort past every
            # real cell
            weights = valid.astype(jnp.int32)
            cids = jnp.where(valid, cids, 0)
            sort_key = jnp.where(valid, cids, n_cells)

        counts = jax.ops.segment_sum(weights, cids, num_segments=n_cells)
        offsets = exclusive_prefix_sum(counts)      # (n_cells,)

        # Rank of each particle within its cell via one sort (the paper's
        # atomic slot-grab, determinized). The columns ride along: on a
        # v5e a gather by the sort's permutation costs ~16x a sorted column.
        sorted_key, order, *sorted_cols = jax.lax.sort(
            (sort_key, jnp.arange(n, dtype=jnp.int32), *columns),
            num_keys=2)
        key = jnp.clip(sorted_key, 0, n_cells - 1)
        rank = jnp.arange(n, dtype=jnp.int32) - offsets[key]

    with jax.named_scope("bin/scatter"):
        # Flat index into the padded planes, with the cell coordinates
        # decoded from the sorted key; ranks >= m_c fall off the end of the
        # cell's slot range — push them fully out of bounds so 'drop'
        # removes them.
        ix, iy, iz = key % nx, (key // nx) % ny, key // (nx * ny)
        row_len = (nx + 2) * m_c
        slot_col = (ix + 1) * m_c + rank
        flat = ((iz + 1) * (ny + 2) + (iy + 1)) * row_len + slot_col
        total = (nz + 2) * (ny + 2) * row_len
        keep = (rank < m_c) & (sorted_key < n_cells)
        flat = jnp.where(keep, flat, total)         # out of range -> dropped

        shape = padded_shape(domain, m_c)

        def scatter(values: Array, fill: float) -> Array:
            plane = jnp.full((total,), fill, dtype=values.dtype)
            plane = plane.at[flat].set(values, mode="drop")
            return plane.reshape(shape)

        planes = {name: scatter(col, EMPTY_POS)
                  for name, col in zip("xyz", sorted_cols)}
        for name, col in zip(fields or {}, sorted_cols[3:]):
            planes[name] = scatter(col, 0.0)

        slot_flat = jnp.full((total,), -1, dtype=jnp.int32)
        slot_flat = slot_flat.at[flat].set(order, mode="drop")
        slot_id = slot_flat.reshape(shape)

        particle_slot = jnp.zeros((n,), dtype=jnp.int32).at[order].set(
            flat.astype(jnp.int32), mode="drop")

    bins = CellBins(planes=planes, slot_id=slot_id, counts=counts,
                    offsets=offsets, particle_slot=particle_slot, m_c=m_c)
    if domain.any_periodic:
        bins = _fill_periodic_ghosts(domain, bins)
    return bins


@device_scope("ghost")
def _fill_periodic_ghosts(domain: Domain, bins: CellBins) -> CellBins:
    """Copy wrapped interior slabs into the ghost ring (minimum image),
    per periodic axis. Callers keep it outside their ``bin`` /
    ``bin_refresh`` scope: a sibling, not a child."""
    nx, ny, nz = domain.ncells
    m_c = bins.m_c
    lx, ly, lz = domain.box
    px, py, pz = domain.periodic_axes

    def wrap(plane: Array, field: str) -> Array:
        if px:
            dx = lx if field == "x" else 0.0
            left_src = plane[:, :, nx * m_c:(nx + 1) * m_c]
            right_src = plane[:, :, m_c:2 * m_c]
            plane = plane.at[:, :, 0:m_c].set(left_src - dx)
            plane = plane.at[:, :, (nx + 1) * m_c:].set(right_src + dx)
        if py:
            dy = ly if field == "y" else 0.0
            plane = plane.at[:, 0, :].set(plane[:, ny, :] - dy)
            plane = plane.at[:, ny + 1, :].set(plane[:, 1, :] + dy)
        if pz:
            dz = lz if field == "z" else 0.0
            plane = plane.at[0, :, :].set(plane[nz, :, :] - dz)
            plane = plane.at[nz + 1, :, :].set(plane[1, :, :] + dz)
        return plane

    planes = {k: wrap(v, k) for k, v in bins.planes.items()}

    # Ghost slots mirror the interior particle ids so self-interaction
    # masking (slot_id equality) keeps excluding only the true self-pair; a
    # particle must still interact with its own periodic *image*, so ghost
    # copies carry offset ids (id + 1e9).
    sid = bins.slot_id

    def bump(s):
        return jnp.where((s >= 0) & (s < GHOST_ID_BUMP), s + GHOST_ID_BUMP, s)

    s = sid
    if px:
        big = bump(s)
        s = s.at[:, :, 0:m_c].set(big[:, :, nx * m_c:(nx + 1) * m_c])
        s = s.at[:, :, (nx + 1) * m_c:].set(big[:, :, m_c:2 * m_c])
    if py:
        big = bump(s)
        s = s.at[:, 0, :].set(big[:, ny, :])
        s = s.at[:, ny + 1, :].set(big[:, 1, :])
    if pz:
        big = bump(s)
        s = s.at[0, :, :].set(big[nz, :, :])
        s = s.at[nz + 1, :, :].set(big[1, :, :])

    return dataclasses.replace(bins, planes=planes, slot_id=s)


def gather_to_particles(bins: CellBins, plane: Array) -> Array:
    """Read a per-slot plane back to per-particle order (inverse of scatter)."""
    return plane.reshape(-1)[bins.particle_slot]


# --------------------------------------------------------------------------
# Verlet-skin trajectory support: displacement tracking + in-place refresh
# --------------------------------------------------------------------------
#
# The trajectory engine (repro.traj) bins once on a skin-padded grid
# (domain.skin_domain: cell width >= cutoff + skin) and then *reuses* the
# slot assignment across timesteps, refreshing slot contents in place each
# step. The reuse contract: as long as no particle has drifted more than
# skin/2 from the position it was binned at, the 27-cell neighborhood still
# covers every pair within the true cutoff, so forces are pair-complete.
# ``max_displacement`` is the traced predicate; ``refresh_bins`` is the
# cheap per-step scatter that replaces a full ``bin_particles`` pass on the
# steps where the predicate says the bins are still valid.


def max_displacement(domain: Domain, positions: Array, ref: Array,
                     valid: Array | None = None) -> Array:
    """Scalar max over particles of |positions - ref| (minimum image).

    The Verlet-skin rebin predicate: the trajectory engine re-bins when
    this crosses ``effective_skin / 2``. Padding rows (``valid`` False)
    contribute zero — they never move and never interact.
    """
    delta = domain.minimum_image(positions - ref)
    mag = jnp.sqrt(jnp.sum(delta * delta, axis=-1))
    if valid is not None:
        mag = jnp.where(valid, mag, 0.0)
    return jnp.max(mag, initial=0.0)


def image_positions(domain: Domain, positions: Array, ref: Array) -> Array:
    """Positions shifted to the periodic image nearest ``ref``.

    Stale bins store each particle near where it was binned; a particle
    that wrapped across a periodic face since then must be *presented* to
    its old neighborhood unwrapped, or pair distances against stale-cell
    neighbors would jump by a box length. The shift is an exact multiple
    of the box, so for particles that did not wrap it is exactly zero and
    the returned positions are bit-identical to the input.
    """
    if not domain.any_periodic:
        return positions
    box = jnp.asarray(domain.box, dtype=positions.dtype)
    per = jnp.asarray(domain.periodic_axes)
    delta = positions - ref
    shift = jnp.where(per, box * jnp.round(delta / box), 0.0)
    return positions - shift


def refresh_bins(domain: Domain, bins: CellBins, positions: Array,
                 fields: Dict[str, Array] | None = None,
                 valid: Array | None = None) -> CellBins:
    """Scatter current particle values into the *existing* slot layout.

    The Verlet-skin fast path: slot assignment (``particle_slot``,
    ``slot_id``, ``counts``, ``offsets``) is reused from the last full
    ``bin_particles`` pass; only the SoA value planes are rewritten, then
    the periodic ghost ring is refilled from the refreshed interior.
    ``positions`` must already be imaged next to the binned reference
    (:func:`image_positions`) so wrapped particles land in their old slots
    with consistent coordinates.

    Particles the original binning dropped (cell overflow past ``m_c``)
    carry ``particle_slot == 0``; their scatter lands in a ghost-corner
    slot that the ghost refill immediately rewrites (periodic) or that is
    masked by ``slot_id == -1`` (open boundaries) — harmless either way,
    and an overflowed binning is flagged for replan before results are
    trusted. Padding rows (``valid`` False) are routed out of range and
    dropped. Device scope ``bin_refresh``, then ``ghost``.
    """
    total = bins.slot_id.size
    planes = {}
    with jax.named_scope("bin_refresh"):
        idx = bins.particle_slot
        if valid is not None:
            idx = jnp.where(valid, idx, total)
        for name, plane in bins.planes.items():
            if name == "x":
                vals = positions[:, 0]
            elif name == "y":
                vals = positions[:, 1]
            elif name == "z":
                vals = positions[:, 2]
            else:
                vals = (fields or {})[name]
            flat = plane.reshape(-1).at[idx].set(
                vals.astype(plane.dtype), mode="drop")
            planes[name] = flat.reshape(plane.shape)

    out = dataclasses.replace(bins, planes=planes)
    if domain.any_periodic:
        out = _fill_periodic_ghosts(domain, out)
    return out


# --------------------------------------------------------------------------
# occupancy: the sparsity summary behind the compacted schedules
# --------------------------------------------------------------------------
#
# The dense slot layout charges every strategy for the *global* worst case:
# all (z, y) pencils (or sub-boxes) are visited, each padded to m_c slots.
# On inhomogeneous distributions most of those work units are empty. The
# occupancy summary is the trace-time-safe sparsity map: per-unit particle
# counts plus a compacted list of the active unit indices, under a static
# ``max_active`` bound that mirrors the m_c replan contract (overflow is
# detectable, never silent — a too-small bound drops work units, so the
# plan layer re-plans with a larger bound instead of computing wrong
# forces).


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Occupancy:
    """Compacted active-work-unit summary (pencils or sub-boxes).

    ``active`` holds the linearized indices of the units with at least one
    particle, padded to the static bound ``max_active`` with index 0 (always
    a valid unit to *read*; padded entries are dropped on the write side via
    :meth:`scatter_indices`). ``n_active`` is the true count — when it
    exceeds ``max_active`` the summary has overflowed and results computed
    from it would silently miss units, exactly like a cell overflowing m_c.
    """

    unit_counts: Array            # (n_units,) int32 particles per work unit
    active: Array                 # (max_active,) int32 unit ids, 0-padded
    n_active: Array               # () int32 true number of active units
    max_active: int = dataclasses.field(metadata=dict(static=True))
    n_units: int = dataclasses.field(metadata=dict(static=True))

    @property
    def overflowed(self) -> Array:
        """True when active units were dropped from ``active`` (replan)."""
        return self.n_active > self.max_active

    def scatter_indices(self) -> Array:
        """(max_active,) write-side unit ids: padding slots are pushed out
        of range so a ``mode='drop'`` scatter discards them."""
        slot = jnp.arange(self.max_active, dtype=jnp.int32)
        return jnp.where(slot < self.n_active, self.active,
                         jnp.int32(self.n_units))

    @property
    def fill_fraction(self) -> Array:
        return self.n_active / max(self.n_units, 1)


def _compact_active(unit_counts: Array, max_active: int,
                    n_units: int) -> Occupancy:
    active = jnp.nonzero(unit_counts > 0, size=max_active,
                         fill_value=0)[0].astype(jnp.int32)
    n_active = jnp.sum(unit_counts > 0).astype(jnp.int32)
    return Occupancy(unit_counts=unit_counts, active=active,
                     n_active=n_active, max_active=max_active,
                     n_units=n_units)


def full_pencil_occupancy(domain: Domain) -> Occupancy:
    """The identity occupancy: every (z, y) pencil active, in order.

    Lets the packed (and any compacted-shaped) runners iterate *all* rows
    through the same chunked active-list machinery when a plan is not
    compacted — ``active`` is just ``arange(nz * ny)`` with no padding.
    """
    n = domain.nz * domain.ny
    return Occupancy(unit_counts=jnp.ones((n,), jnp.int32),
                     active=jnp.arange(n, dtype=jnp.int32),
                     n_active=jnp.asarray(n, jnp.int32),
                     max_active=n, n_units=n)


def counts_grid(domain: Domain, counts: Array) -> Array:
    """(n_cells,) linear cell counts -> (nz, ny, nx) grid (X fastest)."""
    return counts.reshape(domain.nz, domain.ny, domain.nx)


def pencil_counts(domain: Domain, counts: Array) -> Array:
    """(n_cells,) cell counts -> (nz*ny,) particles per (z, y) X-pencil.
    Unit id = z * ny + y — the pencil-schedule linearization. The single
    source of truth for pencil unit ids (occupancy summaries and the plan
    layer's overflow probes both derive from it)."""
    return counts_grid(domain, counts).sum(axis=-1).reshape(-1)


def subbox_counts(domain: Domain, counts: Array,
                  box: Tuple[int, int, int]) -> Array:
    """(n_cells,) cell counts -> (gz*gy*gx,) particles per sub-box of the
    All-in-SM tiling. ``box`` = (bx, by, bz) must divide the grid. Unit
    id = iz*(gy*gx) + iy*gx + ix, matching the allin block linearization."""
    nx, ny, nz = domain.ncells
    bx, by, bz = box
    gx, gy, gz = nx // bx, ny // by, nz // bz
    grid = counts_grid(domain, counts)
    return grid.reshape(gz, bz, gy, by, gx, bx).sum(axis=(1, 3, 5)).reshape(-1)


def shard_slab_counts(domain: Domain, counts: Array, n_shards: int) -> Array:
    """(n_cells,) cell counts -> (n_shards,) particles per Z-slab shard.

    The reduction behind the distributed engine's ``shard_cap`` overflow
    contract: a shard whose load exceeds the static capacity would silently
    drop particles, exactly like a cell overflowing ``m_c``.
    """
    if domain.nz % n_shards:
        raise ValueError(
            f"nz={domain.nz} not divisible by n_shards={n_shards}")
    per_plane = counts_grid(domain, counts).sum(axis=(1, 2))     # (nz,)
    return per_plane.reshape(n_shards, domain.nz // n_shards).sum(axis=1)


def shard_pencil_active(domain: Domain, counts: Array,
                        n_shards: int) -> Array:
    """(n_cells,) cell counts -> (n_shards,) active (z, y) pencils per
    Z-slab shard — the per-shard occupancy the distributed compacted path's
    ``max_active`` bound must cover (the bound is one static number shared
    by every shard, so it is checked against the *busiest* shard)."""
    if domain.nz % n_shards:
        raise ValueError(
            f"nz={domain.nz} not divisible by n_shards={n_shards}")
    pc = pencil_counts(domain, counts).reshape(domain.nz, domain.ny)
    active = (pc > 0).astype(jnp.int32)
    return active.reshape(n_shards, domain.nz // n_shards,
                          domain.ny).sum(axis=(1, 2))


def pencil_occupancy(domain: Domain, counts: Array,
                     max_active: int) -> Occupancy:
    """Active (z, y) X-pencils (see :func:`pencil_counts` for unit ids).
    Traceable: works on ``CellBins.counts`` inside jit."""
    return _compact_active(pencil_counts(domain, counts), max_active,
                           domain.nz * domain.ny)


def subbox_occupancy(domain: Domain, counts: Array,
                     box: Tuple[int, int, int], max_active: int) -> Occupancy:
    """Active sub-boxes (see :func:`subbox_counts` for unit ids)."""
    nx, ny, nz = domain.ncells
    bx, by, bz = box
    n_boxes = (nx // bx) * (ny // by) * (nz // bz)
    return _compact_active(subbox_counts(domain, counts, box), max_active,
                           n_boxes)


def gather_pencil_rows(plane: Array, active_zy: Array, ny: int,
                       dz: int = 0, dy: int = 0) -> Array:
    """Compacted pencil-row gather: one padded row per active pencil.

    ``active_zy`` holds interior pencil ids ``z * ny + y``; the returned
    array is ``(len(active_zy), (nx+2)*m_c)`` — row ``a`` is the padded
    ``(z + dz + 1, y + dy + 1)`` row of ``plane``. This is the sparse
    counterpart of the dense schedules' per-pencil ``dynamic_slice``: one
    vectorized gather instead of a loop over all nz*ny pencils.
    """
    z = active_zy // ny + 1 + dz
    y = active_zy % ny + 1 + dy
    return plane[z, y, :]


# --------------------------------------------------------------------------
# packed-row layout: CSR-style slot compaction per pencil row
# --------------------------------------------------------------------------
#
# The occupancy path (above) removes empty work *units*; inside an active
# cell the dense layout still pays for all m_c slots. In the paper's
# "few particles per cell" regime (ppc 1-4, m_c sublane-aligned to 8) that
# is 2-8x more bytes than the particles warrant. The packed layout is the
# CSR answer: each padded (z, y) pencil row stores its particles
# *contiguously* (cell order preserved), with per-cell start offsets from
# the paper's prefix-sum kernel, under a static ``row_cap`` bound that
# follows the same overflow/replan contract as ``m_c``/``max_active``
# (see ARCHITECTURE.md "Static bounds & the replan contract").


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PackedRows:
    """CSR cell layout: per-pencil packed rows + prefix-sum cell offsets.

    Every padded (z, y) pencil row — interior rows and the ghost ring —
    owns ``row_cap`` slots; the row's particles (including its X-ghost
    copies) sit contiguously at the front in cell-then-rank order, exactly
    the order the dense row stores them in minus the empty slots. The
    per-row exclusive prefix sum ``cell_offsets`` (built with the paper's
    §6 scan, ``core.prefix``) says where each padded cell's particles
    start, so the dense layout's contiguous 3-cell X-window becomes an
    (offset, length) pair: ``[cell_offsets[c-1], cell_offsets[c+2])``.

    Like every static bound, ``row_cap`` overflowing means particles were
    *dropped* by the pack — detectable (``overflowed`` /
    ``InteractionPlan.check_overflow``), never silently wrong.
    """

    planes: Dict[str, Array]      # (nz+2, ny+2, row_cap) packed SoA fields
    slot_id: Array                # (nz+2, ny+2, row_cap) int32, -1 padding
    slot_cell: Array              # (nz+2, ny+2, row_cap) int32 padded cell
    cell_offsets: Array           # (nz+2, ny+2, nx+3) int32 exclusive prefix
    row_counts: Array             # (nz+2, ny+2) int32 particles per row
    counts: Array                 # (n_cells,) pass-through from CellBins
    particle_slot: Array          # (N,) interior flat packed slot per particle
    row_cap: int = dataclasses.field(metadata=dict(static=True))
    m_c: int = dataclasses.field(metadata=dict(static=True))

    @property
    def overflowed(self) -> Array:
        """True when some row held more than ``row_cap`` particles (replan)."""
        return jnp.max(self.row_counts) > self.row_cap


def padded_row_counts(domain: Domain, counts: Array) -> Array:
    """(n_cells,) cell counts -> (nz, ny) particles per *padded* pencil row.

    A padded row holds the pencil's interior particles plus, under a
    periodic X axis, the ghost copies of its first and last cell (a
    1-cell-thick periodic X axis counts its single cell three times). The
    host-side probe behind ``suggest_row_cap`` and the packed
    ``check_overflow``: ghost Y/Z rows are wrapped copies of interior rows,
    so the interior maximum covers every padded row of the layout.
    """
    grid = counts_grid(domain, counts)
    per_row = grid.sum(axis=-1)
    if domain.periodic_axes[0]:
        per_row = per_row + grid[..., 0] + grid[..., -1]
    return per_row


@device_scope("bin/pack")
def pack_rows(domain: Domain, bins: CellBins, row_cap: int) -> PackedRows:
    """Compact a dense :class:`CellBins` into the packed-row (CSR) layout.

    Traceable (runs inside the jitted executor). Per padded row: per-cell
    counts come from the occupied slots, the paper's prefix sum turns them
    into start offsets, and every occupied dense slot ``(cell c, rank r)``
    scatters to packed position ``cell_offsets[c] + r`` — a stable
    compaction, so packed order is dense order minus the sentinels and the
    dense 3-cell window survives as an (offset, length) range. Rows whose
    count exceeds ``row_cap`` drop their tail (``mode='drop'``), flagged by
    :attr:`PackedRows.overflowed` for the replan contract.
    """
    nx, ny, nz = domain.ncells
    m_c = bins.m_c
    nzp, nyp = nz + 2, ny + 2
    shape4 = (nzp, nyp, nx + 2, m_c)

    occupied = (bins.slot_id.reshape(shape4) >= 0)
    cell_counts_p = occupied.sum(axis=-1).astype(jnp.int32)  # (nzp,nyp,nx+2)
    offsets = exclusive_prefix_sum(cell_counts_p)            # paper §6 scan
    row_counts = cell_counts_p.sum(axis=-1)                  # (nzp, nyp)
    cell_offsets = jnp.concatenate(
        [offsets, row_counts[..., None]], axis=-1)           # (nzp,nyp,nx+3)

    # destination of dense slot (c, r): cell start + rank; unoccupied slots
    # and rows past row_cap are pushed out of range so 'drop' discards them
    rank = jnp.arange(m_c, dtype=jnp.int32)
    dest = offsets[..., None] + rank                         # (nzp,nyp,nx+2,m_c)
    dest = jnp.where(occupied & (dest < row_cap), dest, row_cap)
    row_base = (jnp.arange(nzp, dtype=jnp.int32)[:, None] * nyp
                + jnp.arange(nyp, dtype=jnp.int32)[None, :])
    flat = (row_base[..., None, None] * (row_cap + 1) + dest).reshape(-1)
    total = nzp * nyp * (row_cap + 1)

    def pack(plane: Array, fill) -> Array:
        out = jnp.full((total,), fill, dtype=plane.dtype)
        out = out.at[flat].set(plane.reshape(-1), mode="drop")
        return out.reshape(nzp, nyp, row_cap + 1)[..., :row_cap]

    planes = {}
    for name, plane in bins.planes.items():
        fill = EMPTY_POS if name in ("x", "y", "z") else 0.0
        planes[name] = pack(plane, jnp.asarray(fill, plane.dtype))
    slot_id = pack(bins.slot_id, jnp.int32(-1))

    # padded cell index of every packed slot; padding slots read cell 1 (a
    # valid interior cell) so window arithmetic stays in bounds — their
    # results are masked by slot_id == -1 and never unpacked
    cell_idx = jnp.broadcast_to(
        jnp.arange(nx + 2, dtype=jnp.int32)[None, None, :, None], shape4)
    slot_cell = pack(cell_idx.reshape(bins.slot_id.shape), jnp.int32(1))

    # per-particle packed slot (interior rows only): dense flat slot ->
    # (z, y, c, r) -> interior flat (z*ny + y) * row_cap + offset + rank
    row_len = (nx + 2) * m_c
    ds = bins.particle_slot
    zp = ds // ((nyp) * row_len)
    rem = ds % ((nyp) * row_len)
    yp = rem // row_len
    col = rem % row_len
    c = col // m_c
    r = col % m_c
    pos_in_row = offsets[zp, yp, c] + r
    pos_in_row = jnp.minimum(pos_in_row, row_cap)       # overflow-safe read
    particle_slot = (((zp - 1) * ny + (yp - 1)) * (row_cap + 1)
                     + pos_in_row).astype(jnp.int32)

    return PackedRows(planes=planes, slot_id=slot_id, slot_cell=slot_cell,
                      cell_offsets=cell_offsets, row_counts=row_counts,
                      counts=bins.counts, particle_slot=particle_slot,
                      row_cap=row_cap, m_c=m_c)


def unpack_scatter(domain: Domain, packed: PackedRows,
                   rows: Array) -> Array:
    """Packed per-slot values back to particle order (packed counterpart of
    :func:`gather_to_particles` / :func:`dense_to_particles`).

    ``rows`` holds one value per *interior* packed slot —
    ``(nz * ny, row_cap)`` (or any reshape of it) in pencil-id order
    ``z * ny + y``. Out-of-cap particles (an overflowed pack — caught by
    ``check_overflow`` before results are trusted) read a zero pad slot.
    """
    nz, ny = domain.nz, domain.ny
    per_row = rows.reshape(nz * ny, packed.row_cap)
    padded = jnp.concatenate(
        [per_row, jnp.zeros((nz * ny, 1), per_row.dtype)], axis=-1)
    return padded.reshape(-1)[packed.particle_slot]


@device_scope("scatter_back")
def packed_to_particles(domain: Domain, packed: PackedRows, fx: Array,
                        fy: Array, fz: Array, pot: Array
                        ) -> Tuple[Array, Array]:
    """Normalize packed ``(nz * ny, row_cap)`` schedule outputs to
    per-particle ``(forces (N, 3), potential (N,))`` — the same output
    contract as :func:`dense_to_particles`."""
    out = [unpack_scatter(domain, packed, p) for p in (fx, fy, fz, pot)]
    return jnp.stack(out[:3], axis=-1), out[3]


def interior(domain: Domain, plane: Array, m_c: int) -> Array:
    """View of the non-ghost region, reshaped to (nz, ny, nx, m_c)."""
    nx, ny, nz = domain.ncells
    core = plane[1:nz + 1, 1:ny + 1, m_c:(nx + 1) * m_c]
    return core.reshape(nz, ny, nx, m_c)


def interior_to_padded(domain: Domain, plane: Array, m_c: int) -> Array:
    """(nz, ny, nx, m_c) interior tensor -> padded plane (ghosts zero).

    Inverse of ``interior`` up to the ghost ring; the step every dense
    schedule output goes through before ``gather_to_particles``.
    """
    nx, ny, nz = domain.ncells
    padded = jnp.zeros((nz + 2, ny + 2, (nx + 2) * m_c), dtype=plane.dtype)
    return padded.at[1:nz + 1, 1:ny + 1, m_c:(nx + 1) * m_c].set(
        plane.reshape(nz, ny, nx * m_c))


@device_scope("scatter_back")
def dense_to_particles(domain: Domain, bins: CellBins, fx: Array, fy: Array,
                       fz: Array, pot: Array) -> Tuple[Array, Array]:
    """Normalize dense (nz, ny, nx, m_c) schedule outputs to per-particle
    (forces (N, 3), potential (N,)) — the backend-registry output contract."""
    out = []
    for plane in (fx, fy, fz, pot):
        shaped = plane.reshape(domain.nz, domain.ny, domain.nx, bins.m_c)
        out.append(gather_to_particles(
            bins, interior_to_padded(domain, shaped, bins.m_c)))
    return jnp.stack(out[:3], axis=-1), out[3]


# --------------------------------------------------------------------------
# SFC cluster layout: curve-ordered cell clusters + compressed pair list
# --------------------------------------------------------------------------
#
# The packed layout (above) compresses *storage*; the SFC layout compresses
# the *schedule*. Cells are ordered along a space-filling curve (Morton or
# Hilbert — the CSCS follow-up's locality trick) and grouped into fixed-size
# clusters of ``csize`` consecutive cells; the per-step work list is then a
# *compressed cluster-pair neighbor list*: a (cluster, stencil-offset)
# bitmask over the 27-cell stencil, delta/sort-encoded into a flat array of
# ``cluster * 32 + k`` codes under a static ``pair_cap`` bound. Empty
# neighborhoods never even appear in the list — the data-dependent
# counterpart of the occupancy path's active-unit list, one level finer.
#
# Bit-identity with the dense Par-Cell schedule is by construction: each
# kept (cluster, k) pair evaluates the *same* per-cell m_c x m_c masked
# reduction ``cell_dense`` evaluates for stencil slot k, accumulated in the
# same ascending-k order (codes are sorted, and k is the low bits), so the
# float sums associate identically. Dropping a pair is only possible via
# ``pair_cap`` overflow, which is detected (``SfcClusters.overflowed``) and
# grown by the standard replan contract — never silent.

DEFAULT_CSIZE = 4
DEFAULT_CURVE = "morton"
SFC_CURVES = ("morton", "hilbert")


def morton_encode(ix, iy, iz, bits: int) -> np.ndarray:
    """Interleave 3 coordinate arrays into Morton (Z-order) codes (host)."""
    ix = np.asarray(ix, np.int64)
    iy = np.asarray(iy, np.int64)
    iz = np.asarray(iz, np.int64)
    code = np.zeros(np.broadcast(ix, iy, iz).shape, np.int64)
    for b in range(bits):
        code |= ((ix >> b) & 1) << (3 * b)
        code |= ((iy >> b) & 1) << (3 * b + 1)
        code |= ((iz >> b) & 1) << (3 * b + 2)
    return code


def morton_decode(codes, bits: int) -> Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]:
    """Inverse of :func:`morton_encode` (host)."""
    codes = np.asarray(codes, np.int64)
    ix = np.zeros(codes.shape, np.int64)
    iy = np.zeros(codes.shape, np.int64)
    iz = np.zeros(codes.shape, np.int64)
    for b in range(bits):
        ix |= ((codes >> (3 * b)) & 1) << b
        iy |= ((codes >> (3 * b + 1)) & 1) << b
        iz |= ((codes >> (3 * b + 2)) & 1) << b
    return ix, iy, iz


def _hilbert_axes_to_transpose(ix, iy, iz, bits: int):
    """Skilling's AxesToTranspose, vectorized over numpy arrays."""
    X = [np.array(ix, np.int64), np.array(iy, np.int64),
         np.array(iz, np.int64)]
    M = 1 << (bits - 1)
    Q = M
    while Q > 1:                       # inverse undo
        P = Q - 1
        for i in range(3):
            cond = (X[i] & Q) != 0
            t = (X[0] ^ X[i]) & P
            x0 = np.where(cond, X[0] ^ P, X[0] ^ t)
            X[i] = np.where(cond, X[i], X[i] ^ t)
            X[0] = x0
        Q >>= 1
    for i in range(1, 3):              # Gray encode
        X[i] = X[i] ^ X[i - 1]
    t = np.zeros_like(X[0])
    Q = M
    while Q > 1:
        t = np.where((X[2] & Q) != 0, t ^ (Q - 1), t)
        Q >>= 1
    return [x ^ t for x in X]


def _hilbert_transpose_to_axes(X, bits: int):
    """Skilling's TransposeToAxes (inverse of the above), vectorized."""
    X = [np.array(x, np.int64) for x in X]
    N = 2 << (bits - 1)
    t = X[2] >> 1                      # Gray decode by H ^ (H/2)
    for i in range(2, 0, -1):
        X[i] = X[i] ^ X[i - 1]
    X[0] = X[0] ^ t
    Q = 2
    while Q != N:                      # undo excess work
        P = Q - 1
        for i in range(2, -1, -1):
            cond = (X[i] & Q) != 0
            t = (X[0] ^ X[i]) & P
            x0 = np.where(cond, X[0] ^ P, X[0] ^ t)
            X[i] = np.where(cond, X[i], X[i] ^ t)
            X[0] = x0
        Q <<= 1
    return X


def hilbert_encode(ix, iy, iz, bits: int) -> np.ndarray:
    """Hilbert-curve codes for 3-D coordinates (host, Skilling 2004)."""
    X = _hilbert_axes_to_transpose(ix, iy, iz, bits)
    code = np.zeros_like(X[0])
    for b in range(bits - 1, -1, -1):  # X[0] most significant per bit-plane
        for i in range(3):
            code = (code << 1) | ((X[i] >> b) & 1)
    return code


def hilbert_decode(codes, bits: int) -> Tuple[np.ndarray, np.ndarray,
                                              np.ndarray]:
    """Inverse of :func:`hilbert_encode` (host)."""
    codes = np.asarray(codes, np.int64)
    X = [np.zeros(codes.shape, np.int64) for _ in range(3)]
    for b in range(bits):
        for i in range(3):
            shift = 3 * b + (2 - i)
            X[i] |= ((codes >> shift) & 1) << b
    ix, iy, iz = _hilbert_transpose_to_axes(X, bits)
    return ix, iy, iz


def _curve_bits(nx: int, ny: int, nz: int) -> int:
    return max(int(max(nx, ny, nz) - 1).bit_length(), 1)


@dataclasses.dataclass(frozen=True)
class SfcTables:
    """Static (host, geometry-only) cluster tables of an SFC layout.

    ``order`` lists the cell ids along the curve; cluster ``a`` owns cells
    ``order[a*csize:(a+1)*csize]`` (the last cluster is padded with the
    sentinel cell -1). ``tgt_pcell``/``src_pcell`` hold *padded-grid* flat
    cell indices — ``src_pcell[a, k, j]`` is cell j of cluster a shifted by
    stencil offset k (``domain.neighbor_offsets()`` order, k = 13 is self);
    sentinel cells map to ``n_pcells`` (one past the padded grid), where
    occupancy/slot gathers read an appended always-empty block.
    """

    order: np.ndarray           # (n_cells,) cell ids in curve order
    cell_cluster: np.ndarray    # (n_cells,) cluster id per cell
    cell_pos: np.ndarray        # (n_cells,) position of cell in its cluster
    cluster_cells: np.ndarray   # (n_clusters, csize) cell ids, -1 pad
    tgt_pcell: np.ndarray       # (n_clusters, csize) padded flat cell
    src_pcell: np.ndarray       # (n_clusters, 27, csize) padded flat cell
    n_clusters: int
    n_pcells: int


@functools.lru_cache(maxsize=None)
def sfc_cluster_tables(domain: Domain, csize: int = DEFAULT_CSIZE,
                       curve: str = DEFAULT_CURVE) -> SfcTables:
    """Build the static SFC cluster tables (cached per geometry)."""
    if curve not in SFC_CURVES:
        raise ValueError(f"unknown curve {curve!r}; have {SFC_CURVES}")
    if csize < 1:
        raise ValueError(f"csize must be >= 1, got {csize}")
    nx, ny, nz = domain.ncells
    n_cells = domain.n_cells
    cid = np.arange(n_cells, dtype=np.int64)
    ix, iy, iz = cid % nx, (cid // nx) % ny, cid // (nx * ny)
    bits = _curve_bits(nx, ny, nz)
    enc = morton_encode if curve == "morton" else hilbert_encode
    codes = enc(ix, iy, iz, bits)
    order = np.argsort(codes, kind="stable").astype(np.int32)

    n_clusters = -(-n_cells // csize)
    pos = np.arange(n_cells, dtype=np.int64)
    cell_cluster = np.empty(n_cells, np.int32)
    cell_pos = np.empty(n_cells, np.int32)
    cell_cluster[order] = (pos // csize).astype(np.int32)
    cell_pos[order] = (pos % csize).astype(np.int32)
    cluster_cells = np.full((n_clusters * csize,), -1, np.int32)
    cluster_cells[:n_cells] = order
    cluster_cells = cluster_cells.reshape(n_clusters, csize)

    n_pcells = (nz + 2) * (ny + 2) * (nx + 2)
    pad = cluster_cells < 0
    safe = np.where(pad, 0, cluster_cells).astype(np.int64)
    cx, cy, cz = safe % nx, (safe // nx) % ny, safe // (nx * ny)

    def pcell(jx, jy, jz):
        return ((jz + 1) * (ny + 2) + (jy + 1)) * (nx + 2) + (jx + 1)

    tgt_pcell = np.where(pad, n_pcells, pcell(cx, cy, cz)).astype(np.int32)
    offs = domain.neighbor_offsets()                      # (27, 3) (dx,dy,dz)
    src_pcell = np.empty((n_clusters, 27, csize), np.int64)
    for k, (dx, dy, dz) in enumerate(offs):
        src_pcell[:, k, :] = pcell(cx + dx, cy + dy, cz + dz)
    src_pcell = np.where(pad[:, None, :], n_pcells,
                         src_pcell).astype(np.int32)
    return SfcTables(order=order, cell_cluster=cell_cluster,
                     cell_pos=cell_pos, cluster_cells=cluster_cells,
                     tgt_pcell=tgt_pcell, src_pcell=src_pcell,
                     n_clusters=n_clusters, n_pcells=n_pcells)


def sfc_n_clusters(domain: Domain, csize: int = DEFAULT_CSIZE) -> int:
    return -(-domain.n_cells // csize)


@functools.lru_cache(maxsize=None)
def sfc_slot_tables(domain: Domain, m_c: int, csize: int = DEFAULT_CSIZE,
                    curve: str = DEFAULT_CURVE
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Flat *slot* base offsets of the cluster tables for a given ``m_c``:
    ``(tgt_base (n_clusters, csize), src_base (n_clusters, 27, csize))``,
    each ``pcell * m_c`` — directly indexing the flattened padded planes
    (sentinel cells land at ``n_pcells * m_c``, the appended sentinel
    block)."""
    t = sfc_cluster_tables(domain, csize, curve)
    tgt = (t.tgt_pcell.astype(np.int64) * m_c).astype(np.int32)
    src = (t.src_pcell.astype(np.int64) * m_c).astype(np.int32)
    return tgt, src


def encode_pair_masks(masks: np.ndarray, pair_cap: int) -> np.ndarray:
    """(n_clusters, 27) bool stencil bitmask -> sorted compressed codes.

    Each kept pair becomes ``cluster * 32 + k`` (5 bits for the stencil
    slot); codes are sorted ascending — cluster-major, k-minor, the exact
    accumulation order of the dense Par-Cell sweep — padded to ``pair_cap``
    with the sentinel ``n_clusters * 32`` and truncated on overflow (host
    twin of the traced encoder inside :func:`build_sfc_clusters`)."""
    masks = np.asarray(masks, bool)
    n_clusters = masks.shape[0]
    a, k = np.nonzero(masks)
    codes = np.sort(a.astype(np.int64) * 32 + k)
    out = np.full((pair_cap,), n_clusters * 32, np.int32)
    m = min(pair_cap, codes.size)
    out[:m] = codes[:m]
    return out


def decode_pair_codes(codes: np.ndarray, n_clusters: int) -> np.ndarray:
    """Sorted compressed codes -> (n_clusters, 27) bool bitmask (inverse
    of :func:`encode_pair_masks` whenever no pair was truncated)."""
    codes = np.asarray(codes, np.int64)
    masks = np.zeros((n_clusters, 27), bool)
    valid = (codes >= 0) & (codes < n_clusters * 32)
    masks[codes[valid] >> 5, codes[valid] & 31] = True
    return masks


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SfcClusters:
    """SFC cluster layout state: dense bins + the compressed pair list.

    ``codes`` is the sorted compressed cluster-pair list (see
    :func:`encode_pair_masks`) under the static ``pair_cap`` bound;
    ``n_pairs`` is the true pair count — exceeding ``pair_cap`` means
    pairs were truncated (:attr:`overflowed`, replan grows ``pair_cap``).
    The slot data itself stays the dense ``CellBins`` planes: the pair
    list compresses the *schedule* (which cluster-tile interactions run),
    so a cluster with no occupied stencil neighborhood costs nothing.
    """

    bins: CellBins                # dense slot planes the tiles are read from
    codes: Array                  # (pair_cap,) int32 sorted pair codes
    n_pairs: Array                # () int32 true (untruncated) pair count
    cluster_counts: Array         # (n_clusters,) int32 particles per cluster
    pair_cap: int = dataclasses.field(metadata=dict(static=True))
    csize: int = dataclasses.field(metadata=dict(static=True))
    curve: str = dataclasses.field(metadata=dict(static=True))

    @property
    def overflowed(self) -> Array:
        """True when pairs were truncated from ``codes`` (replan)."""
        return self.n_pairs > self.pair_cap


@device_scope("bin/sfc")
def build_sfc_clusters(domain: Domain, bins: CellBins, pair_cap: int,
                       csize: int = DEFAULT_CSIZE,
                       curve: str = DEFAULT_CURVE) -> SfcClusters:
    """Build the compressed cluster-pair list from binned occupancy.

    Traceable (runs inside the jitted executor). The bitmask is driven by
    *padded-cell slot occupancy* (``slot_id >= 0``), not interior counts —
    so periodic ghost copies, open (always-empty) ghosts and the halo
    engine's exchanged ghost planes are all handled by the same rule: a
    (cluster, k) pair is kept iff the cluster holds a particle and the
    k-shifted cells hold one (wherever it came from).
    """
    t = sfc_cluster_tables(domain, csize, curve)
    nx, ny, nz = domain.ncells
    m_c = bins.m_c
    occ = (bins.slot_id.reshape(nz + 2, ny + 2, nx + 2, m_c)
           >= 0).sum(-1).reshape(-1)
    occ_ext = jnp.concatenate([occ, jnp.zeros((1,), occ.dtype)])
    cluster_counts = occ_ext[jnp.asarray(t.tgt_pcell)].sum(-1)
    src_counts = occ_ext[jnp.asarray(t.src_pcell)].sum(-1)
    bits = (cluster_counts[:, None] > 0) & (src_counts > 0)
    n_pairs = jnp.sum(bits).astype(jnp.int32)
    a = jnp.arange(t.n_clusters, dtype=jnp.int32)[:, None]
    k = jnp.arange(27, dtype=jnp.int32)[None, :]
    sentinel = jnp.int32(t.n_clusters * 32)
    codes = jnp.sort(jnp.where(bits, a * 32 + k, sentinel).reshape(-1))
    if pair_cap > codes.size:
        codes = jnp.concatenate(
            [codes, jnp.full((pair_cap - codes.size,), sentinel, jnp.int32)])
    else:
        codes = codes[:pair_cap]
    return SfcClusters(bins=bins, codes=codes, n_pairs=n_pairs,
                       cluster_counts=cluster_counts.astype(jnp.int32),
                       pair_cap=pair_cap, csize=csize, curve=curve)


def sfc_pair_count(domain: Domain, positions: Array | None = None, *,
                   counts: Array | None = None, csize: int = DEFAULT_CSIZE,
                   curve: str = DEFAULT_CURVE,
                   ghost_z: Tuple[Array, Array] | None = None) -> int:
    """Host-side pair-list length probe (the ``pair_cap`` counterpart of
    ``padded_row_counts``): padded-cell occupancy rebuilt from interior
    cell counts (periodic ghosts copied in the same x->y->z order the
    binning ghost fill uses, so corners compose identically), then the
    same bitmask rule as :func:`build_sfc_clusters`. Counts-based, so it
    upper-bounds the traced ``n_pairs`` (slot occupancy is counts clipped
    to ``m_c``) — equal whenever no cell overflows ``m_c``.

    ``ghost_z``: optional ``(below, above)`` interior cell counts, each
    ``(ny, nx)``, that override the Z ghost planes — the halo engine's
    per-shard probe, where the Z ghosts arrive from neighbouring shards
    instead of this domain's own periodic wrap. Their X/Y ghost columns
    get the same periodic copies the exchanged planes carry."""
    if counts is None:
        if positions is None:
            raise ValueError("sfc_pair_count needs positions or counts")
        counts = cell_counts(domain, positions)
    nx, ny, nz = domain.ncells
    grid = np.asarray(counts).reshape(nz, ny, nx)
    occ = np.zeros((nz + 2, ny + 2, nx + 2), np.int64)
    occ[1:nz + 1, 1:ny + 1, 1:nx + 1] = grid
    px, py, pz = domain.periodic_axes
    if ghost_z is not None:
        below, above = ghost_z
        occ[0, 1:ny + 1, 1:nx + 1] = np.asarray(below).reshape(ny, nx)
        occ[nz + 1, 1:ny + 1, 1:nx + 1] = np.asarray(above).reshape(ny, nx)
    if px:
        occ[:, :, 0] = occ[:, :, nx]
        occ[:, :, nx + 1] = occ[:, :, 1]
    if py:
        occ[:, 0, :] = occ[:, ny, :]
        occ[:, ny + 1, :] = occ[:, 1, :]
    if pz and ghost_z is None:
        occ[0] = occ[nz]
        occ[nz + 1] = occ[1]
    t = sfc_cluster_tables(domain, csize, curve)
    occ_ext = np.concatenate([occ.reshape(-1), np.zeros((1,), np.int64)])
    cc = occ_ext[t.tgt_pcell].sum(-1)
    sc = occ_ext[t.src_pcell].sum(-1)
    return int(((cc[:, None] > 0) & (sc > 0)).sum())


@device_scope("scatter_back")
def sfc_to_particles(domain: Domain, sfc: SfcClusters, fx: Array, fy: Array,
                     fz: Array, pot: Array) -> Tuple[Array, Array]:
    """Normalize SFC cluster-tile outputs ``(n_clusters, csize * m_c)`` to
    per-particle ``(forces (N, 3), potential (N,))`` — the backend-registry
    output contract (SFC counterpart of ``packed_to_particles``)."""
    bins = sfc.bins
    nx, ny, nz = domain.ncells
    m_c, csize = bins.m_c, sfc.csize
    t = sfc_cluster_tables(domain, csize, sfc.curve)

    # dense flat slot -> (z, y, cell x, rank) -> cluster-tile flat slot
    row_len = (nx + 2) * m_c
    ds = bins.particle_slot
    zp = ds // ((ny + 2) * row_len)
    rem = ds % ((ny + 2) * row_len)
    yp = rem // row_len
    col = rem % row_len
    cx = col // m_c - 1
    r = col % m_c
    iz, iy = zp - 1, yp - 1
    # dropped particles (slot 0 -> ghost corner) fall outside the interior
    valid = ((iz >= 0) & (iz < nz) & (iy >= 0) & (iy < ny)
             & (cx >= 0) & (cx < nx))
    cid = jnp.where(valid, (iz * ny + iy) * nx + cx, 0)
    cc = jnp.asarray(t.cell_cluster)[cid]
    cp = jnp.asarray(t.cell_pos)[cid]
    n_slots = t.n_clusters * csize * m_c
    flat = jnp.where(valid, cc * (csize * m_c) + cp * m_c + r, n_slots)

    out = []
    for plane in (fx, fy, fz, pot):
        ext = jnp.concatenate([plane.reshape(-1),
                               jnp.zeros((1,), plane.dtype)])
        out.append(ext[flat])
    return jnp.stack(out[:3], axis=-1), out[3]
