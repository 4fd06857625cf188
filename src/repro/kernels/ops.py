"""Public jit'd entry points for the Pallas kernels.

Backend dispatch: ``interpret=None`` (default) runs the kernel body natively
on TPU and in interpret mode everywhere else — so the same call sites work in
CPU tests/dry-runs and on real hardware. The model/engine layers default to
the pure-JAX paths and opt into these kernels via ``implementation="pallas"``.

Device scopes: each ``*_interactions`` entry runs its occupancy, lane
staging (``to_lanes`` / ``from_lanes``) and ``pallas_call`` under ``pair``,
and the way back to particle order under ``scatter_back``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from ..core.binning import (EMPTY_POS, CellBins, PackedRows, SfcClusters,
                            dense_to_particles, full_pencil_occupancy,
                            packed_to_particles, pencil_occupancy,
                            sfc_cluster_tables,
                            sfc_to_particles)
from ..core.domain import Domain
from ..core.interactions import PairKernel
from ..obs.trace import device_scope
from ._platform import resolve_interpret as _interpret
from .allin import allin_forces
from .prefix_sum import prefix_sum as _prefix_sum
from .sfc import cell_sfc_forces
from .window_attn import window_attention as _window_attention
from .xpencil import (xpencil_forces, xpencil_packed_forces,
                      xpencil_sparse_forces)

Array = jnp.ndarray


def xpencil_interactions(domain: Domain, bins: CellBins, kernel: PairKernel,
                         interpret: Optional[bool] = None
                         ) -> Tuple[Array, Array]:
    """X-pencil kernel -> per-particle (forces (N,3), potential (N,))."""
    with jax.named_scope("pair"):
        fx, fy, fz, pot = xpencil_forces(
            bins.planes, bins.slot_id, nx=domain.nx, m_c=bins.m_c,
            kernel=kernel, cutoff2=float(domain.cutoff) ** 2,
            interpret=_interpret(interpret))
    return _to_particles(domain, bins, fx, fy, fz, pot)


def xpencil_sparse_interactions(domain: Domain, bins: CellBins,
                                kernel: PairKernel, max_active: int,
                                interpret: Optional[bool] = None
                                ) -> Tuple[Array, Array]:
    """Compacted X-pencil kernel -> per-particle (forces, potential).

    Builds the pencil occupancy summary from the bin counts (traceable),
    runs the scalar-prefetch kernel over the ``max_active``-bounded active
    list, and scatters the compact rows back into dense planes. If more
    than ``max_active`` pencils are active the extra ones are *dropped* —
    callers detect that via ``InteractionPlan.check_overflow`` and replan,
    exactly like an overflowing ``m_c``.
    """
    nx, ny, nz = domain.ncells
    with jax.named_scope("pair"):
        occ = pencil_occupancy(domain, bins.counts, max_active)
        compact = xpencil_sparse_forces(
            bins.planes, bins.slot_id, occ.active, nx=nx, ny=ny,
            m_c=bins.m_c, kernel=kernel, cutoff2=float(domain.cutoff) ** 2,
            interpret=_interpret(interpret))

    with jax.named_scope("scatter_back"):
        idx = occ.scatter_indices()

        def scatter(rows: Array) -> Array:  # (max_active, nx*m_c) -> dense
            dense = jnp.zeros((nz * ny, nx * bins.m_c), rows.dtype)
            return dense.at[idx].set(rows, mode="drop").reshape(
                nz, ny, nx * bins.m_c)

        fx, fy, fz, pot = (scatter(r) for r in compact)
    return _to_particles(domain, bins, fx, fy, fz, pot)


def xpencil_packed_interactions(domain: Domain, packed: PackedRows,
                                kernel: PairKernel,
                                max_active: Optional[int] = None,
                                interpret: Optional[bool] = None
                                ) -> Tuple[Array, Array]:
    """Packed-row X-pencil kernel -> per-particle (forces, potential).

    Iterates every pencil row when ``max_active`` is None, or the
    occupancy-compacted active list bounded by ``max_active`` otherwise
    (the packed and compacted axes compose). Compact kernel rows scatter
    back into packed ``(nz * ny, row_cap)`` planes, then unpack to
    particle order; overflow of either bound is the caller's replan
    contract (``InteractionPlan.check_overflow``).
    """
    nx, ny, nz = domain.ncells
    with jax.named_scope("pair"):
        occ = (full_pencil_occupancy(domain) if max_active is None
               else pencil_occupancy(domain, packed.counts, max_active))
        compact = xpencil_packed_forces(
            packed.planes, packed.slot_id, packed.slot_cell,
            packed.cell_offsets, occ.active, nx=nx, ny=ny, m_c=packed.m_c,
            row_cap=packed.row_cap, kernel=kernel,
            cutoff2=float(domain.cutoff) ** 2,
            interpret=_interpret(interpret))

    with jax.named_scope("scatter_back"):
        idx = occ.scatter_indices()

        def scatter(rows: Array) -> Array:  # (n_rows, row_cap) -> packed
            dense = jnp.zeros((nz * ny, packed.row_cap), rows.dtype)
            return dense.at[idx].set(rows, mode="drop")

        fx, fy, fz, pot = (scatter(r) for r in compact)
    return packed_to_particles(domain, packed, fx, fy, fz, pot)


def cell_sfc_interactions(domain: Domain, sfc: SfcClusters,
                          kernel: PairKernel,
                          interpret: Optional[bool] = None
                          ) -> Tuple[Array, Array]:
    """SFC cluster-pair kernel -> per-particle (forces (N,3), potential (N,)).

    Gathers the cluster target tiles (plus one all-sentinel ghost row the
    pair-list padding decodes to), stages the padded planes as cell rows
    with one appended sentinel cell, and runs the compressed-pair-list
    Pallas kernel (``kernels.sfc``). Clusters with no kept pair are never
    visited by the grid, so their output rows are explicitly zeroed from
    the kept mask before scattering back to particle order — identical to
    the reference runner, whose fully-masked stencil terms accumulate
    exact (+0.0) zeros.
    """
    return sfc_to_particles(domain, sfc,
                            *_sfc_pair(domain, sfc, kernel, interpret))


@device_scope("pair")
def _sfc_pair(domain: Domain, sfc: SfcClusters, kernel: PairKernel,
              interpret: Optional[bool]) -> Tuple[Array, ...]:
    bins = sfc.bins
    m_c, csize = bins.m_c, sfc.csize
    tables = sfc_cluster_tables(domain, csize, sfc.curve)
    n_clusters = tables.n_clusters
    n_pcells = bins.slot_id.size // m_c

    def cell_rows(plane: Array, fill) -> Array:   # + one sentinel cell
        return jnp.concatenate([plane.reshape(n_pcells, m_c),
                                jnp.full((1, m_c), fill, plane.dtype)])

    rows = {"x": cell_rows(bins.planes["x"], EMPTY_POS),
            "y": cell_rows(bins.planes["y"], EMPTY_POS),
            "z": cell_rows(bins.planes["z"], EMPTY_POS),
            "id": cell_rows(bins.slot_id, -1)}

    def tile(r: Array) -> Array:    # (n_clusters + 1, m_c, csize) + ghost
        t = jnp.concatenate([jnp.asarray(tables.tgt_pcell),
                             jnp.full((1, csize), n_pcells, jnp.int32)])
        return jnp.swapaxes(r[t], 1, 2)

    tiles = {f: tile(r) for f, r in rows.items()}
    src_cell = np.concatenate(
        [np.asarray(tables.src_pcell).reshape(-1),
         np.full((27 * csize,), n_pcells, np.int32)]).astype(np.int32)

    codes = sfc.codes.astype(jnp.int32)
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         ((codes[1:] >> 5) != (codes[:-1] >> 5)).astype(jnp.int32)])

    fx, fy, fz, pot = cell_sfc_forces(
        tiles, rows, codes, first, jnp.asarray(src_cell), csize=csize,
        m_c=m_c, kernel=kernel, cutoff2=float(domain.cutoff) ** 2,
        interpret=_interpret(interpret))

    kept = jnp.zeros((n_clusters + 1,), jnp.int32).at[codes >> 5].add(1)
    has = (kept[:n_clusters] > 0)[:, None]
    return tuple(
        jnp.where(has, jnp.swapaxes(o[:n_clusters], 1, 2).reshape(
            n_clusters, csize * m_c), 0.0)
        for o in (fx, fy, fz, pot))


def allin_interactions(domain: Domain, bins: CellBins, kernel: PairKernel,
                       box, interpret: Optional[bool] = None
                       ) -> Tuple[Array, Array]:
    """All-in-SM kernel -> per-particle (forces, potential)."""
    with jax.named_scope("pair"):
        fx, fy, fz, pot = allin_forces(
            bins.planes, bins.slot_id, box=tuple(box), m_c=bins.m_c,
            kernel=kernel, cutoff2=float(domain.cutoff) ** 2,
            interpret=_interpret(interpret))
    return _to_particles(domain, bins, fx, fy, fz, pot)


def _to_particles(domain, bins, fx, fy, fz, pot):
    return dense_to_particles(domain, bins, fx, fy, fz, pot)


def prefix_sum(x: Array, interpret: Optional[bool] = None) -> Array:
    """Paper §6 prefix sum (VMEM kernel)."""
    return _prefix_sum(x, interpret=_interpret(interpret))


def window_attention(q: Array, k: Array, v: Array, *, window: int,
                     blk: int = 128, softcap: float = 0.0,
                     interpret: Optional[bool] = None) -> Array:
    """Pencil-pattern sliding-window attention (see window_attn.py)."""
    return _window_attention(q, k, v, window=window, blk=blk,
                             softcap=softcap, interpret=_interpret(interpret))
