"""X-pencil interaction kernel (paper §5.2) as a Pallas TPU kernel.

Schedule (mirrors Algorithm 5, adapted per DESIGN.md §2):

  grid = (nz, ny, 9)
    (z, y)  — one program per target X-pencil (the paper's thread-block);
    k       — the 9 (dz, dy) neighbor pencils, innermost so the output block
              stays resident in VMEM while neighbors stream through
              (the paper's "load one pencil at a time" loop, with the
              HBM->VMEM DMA double-buffered by the Pallas pipeline — the TPU
              version of overlapping the next pencil's copy with compute).

  BlockSpec staging, on the lane layout of ``_lanes`` (slot on sublanes,
  padded cell on lanes; planes ``(nz+2, ny+2, m_c, L)``):
    target pencil  tile (m_c, L) at (z+1, y+1)       — "registers"
    source pencil  tile (m_c, L) at (z+k//3, y+k%3)  — "shared mem"
    outputs        tile (m_c, L) at (z, y), revisited across k, accumulated.

  The 3*m_c X-window of every target cell is three lane rotations of the
  staged source tile (the dense slot layout makes the window contiguous —
  the paper needs its local-offset prefix sum for this).

VMEM per step: 12 double-buffered (m_c, L) tiles plus the (3*m_c, m_c, L)
pair temporaries (``xpencil_vmem_bytes``) — a few MiB at m_c = 16, far
under budget: exactly the paper's point that pencils, unlike sub-boxes,
leave head-room.

``xpencil_sparse_forces`` below is the occupancy-compacted variant: its grid
runs over the *active* pencils only, with the active-index list
scalar-prefetched so the BlockSpec index maps become data-dependent.
``xpencil_packed_forces`` is the packed-row (CSR) variant on top of that:
each DMA moves ``row_cap`` packed slots plus a prefix-sum offset row
instead of a dense ``(nx+2)*m_c`` row — bytes proportional to the
particles, the paper's few-particles-per-cell fix.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.binning import EMPTY_POS
from ..core.interactions import PairKernel
from ._lanes import (LANES, compiler_params, from_lanes, lane_gather,
                     lane_width, pair_terms, shift_lanes, tile_bytes,
                     to_lanes, window_terms)
from ._platform import resolve_interpret

Array = jnp.ndarray


def _lane_planes(planes: dict, slot_id: Array, m_c: int):
    """The four input planes of a dense kernel on the lane layout."""
    return (to_lanes(planes["x"], m_c, EMPTY_POS),
            to_lanes(planes["y"], m_c, EMPTY_POS),
            to_lanes(planes["z"], m_c, EMPTY_POS),
            to_lanes(slot_id, m_c, -1))


def xpencil_vmem_bytes(nx: int, m_c: int) -> int:
    """VMEM one dense/compacted X-pencil grid step holds: 12 pipelined
    (m_c, L) tiles, double-buffered, plus ~16 live pair temporaries."""
    tile = tile_bytes(m_c, nx + 2)
    return 2 * 12 * tile + 16 * 3 * m_c * tile


def _pencil_step(k, t_refs, s_refs, o_refs, *, m_c: int, kernel: PairKernel,
                 cutoff2: float):
    """One (dz, dy) step: the staged target pencil x one source pencil,
    accumulated into the resident output tiles. Shared by the dense and
    compacted kernels so compaction cannot change a computed value."""

    @pl.when(k == 0)
    def _init():
        for o in o_refs:
            o[...] = jnp.zeros_like(o)

    tgt = tuple(shift_lanes(r[...], 1) for r in t_refs)   # lane x = cell x
    src = tuple(r[...] for r in s_refs)
    terms = window_terms(tgt, src, m_c=m_c, kernel=kernel, cutoff2=cutoff2)
    for o, v in zip(o_refs, terms):
        o[...] += v


def _kernel(*refs, m_c: int, kernel: PairKernel, cutoff2: float):
    _pencil_step(pl.program_id(2), refs[0:4], refs[4:8], refs[8:12],
                 m_c=m_c, kernel=kernel, cutoff2=cutoff2)


@functools.partial(jax.jit, static_argnames=("nx", "m_c", "kernel", "cutoff2", "interpret"))
def xpencil_forces(planes: dict, slot_id: Array, *, nx: int, m_c: int,
                   kernel: PairKernel, cutoff2: float,
                   interpret: Optional[bool] = None
                   ) -> Tuple[Array, Array, Array, Array]:
    """Run the X-pencil kernel over padded planes.

    Args:
      planes: dict with "x","y","z" padded planes (nz+2, ny+2, (nx+2)*m_c).
      slot_id: matching int32 plane, -1 for empty slots.
      interpret: None = native on TPU, interpreter elsewhere (matching
        ``InteractionPlan.interpret``); bool forces the mode.
    Returns:
      (fx, fy, fz, pot), each (nz, ny, nx*m_c) over interior slots.
    """
    interpret = resolve_interpret(interpret)
    lanes = _lane_planes(planes, slot_id, m_c)
    nzp, nyp, _, width = lanes[0].shape
    nz, ny = nzp - 2, nyp - 2
    tile = (None, None, m_c, width)
    row_block = pl.BlockSpec(tile, lambda z, y, k: (z + 1, y + 1, 0, 0))
    nbr_block = pl.BlockSpec(tile, lambda z, y, k: (z + k // 3, y + k % 3,
                                                    0, 0))
    out_block = pl.BlockSpec(tile, lambda z, y, k: (z, y, 0, 0))
    out_shape = jax.ShapeDtypeStruct((nz, ny, m_c, width), lanes[0].dtype)

    body = functools.partial(_kernel, m_c=m_c, kernel=kernel,
                             cutoff2=float(cutoff2))
    outs = pl.pallas_call(
        body,
        grid=(nz, ny, 9),
        in_specs=[row_block] * 4 + [nbr_block] * 4,
        out_specs=[out_block] * 4,
        out_shape=[out_shape] * 4,
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"),
            xpencil_vmem_bytes(nx, m_c)),
        interpret=interpret,
    )(*lanes, *lanes)
    return tuple(from_lanes(o, nx) for o in outs)


# --------------------------------------------------------------------------
# occupancy-compacted variant: grid over *active* pencils only
# --------------------------------------------------------------------------
#
# The dense kernel's grid is (nz, ny, 9) — every pencil pays 10 row DMAs and
# a full masked pair reduction whether or not it holds particles. Here the
# grid is (max_active, 9): the active-pencil index list is *scalar-
# prefetched* (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index
# maps can read it before each step and DMA exactly the rows of the a-th
# active pencil — data-dependent staging, the TPU analogue of a compacted
# thread-block launch. Outputs are compact (max_active, nx*m_c) rows that
# the caller scatters back into the dense planes (padding rows recompute
# pencil 0 and are dropped by the scatter).


def _sparse_kernel(act_ref, *refs, m_c: int, kernel: PairKernel,
                   cutoff2: float):
    del act_ref  # consumed by the BlockSpec index maps, not the body
    _pencil_step(pl.program_id(1), refs[0:4], refs[4:8], refs[8:12],
                 m_c=m_c, kernel=kernel, cutoff2=cutoff2)


@functools.partial(jax.jit, static_argnames=("nx", "ny", "m_c", "kernel",
                                             "cutoff2", "interpret"))
def xpencil_sparse_forces(planes: dict, slot_id: Array, active_zy: Array, *,
                          nx: int, ny: int, m_c: int, kernel: PairKernel,
                          cutoff2: float, interpret: Optional[bool] = None
                          ) -> Tuple[Array, Array, Array, Array]:
    """Run the compacted X-pencil kernel over the active pencils.

    Args:
      planes / slot_id: padded planes as in :func:`xpencil_forces`.
      active_zy: (max_active,) int32 linearized interior pencil ids
        ``z * ny + y``, padded with 0 (``binning.Occupancy.active``); the
        padding recomputes pencil 0 and must be dropped by the caller's
        scatter (``Occupancy.scatter_indices``).
    Returns:
      (fx, fy, fz, pot), each compact ``(max_active, nx*m_c)``: row ``a``
      holds the interior forces of pencil ``active_zy[a]``.
    """
    interpret = resolve_interpret(interpret)
    lanes = _lane_planes(planes, slot_id, m_c)
    width = lanes[0].shape[-1]
    max_active = active_zy.shape[0]

    def tgt_map(a, k, act):
        return (act[a] // ny + 1, act[a] % ny + 1, 0, 0)

    def nbr_map(a, k, act):
        return (act[a] // ny + k // 3, act[a] % ny + k % 3, 0, 0)

    tile = (None, None, m_c, width)
    out_block = pl.BlockSpec((None, m_c, width), lambda a, k, act: (a, 0, 0))
    out_shape = jax.ShapeDtypeStruct((max_active, m_c, width),
                                     lanes[0].dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(max_active, 9),
        in_specs=([pl.BlockSpec(tile, tgt_map)] * 4
                  + [pl.BlockSpec(tile, nbr_map)] * 4),
        out_specs=[out_block] * 4,
    )
    body = functools.partial(_sparse_kernel, m_c=m_c, kernel=kernel,
                             cutoff2=float(cutoff2))
    outs = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[out_shape] * 4,
        compiler_params=compiler_params(("parallel", "arbitrary"),
                                        xpencil_vmem_bytes(nx, m_c)),
        interpret=interpret,
    )(active_zy.astype(jnp.int32), *lanes, *lanes)
    return tuple(from_lanes(o, nx) for o in outs)


# --------------------------------------------------------------------------
# packed-row (CSR) variant: row_cap rows, offset-driven windows
# --------------------------------------------------------------------------
#
# The compacted kernel above still DMAs every active pencil's full dense
# (nx+2)*m_c row; in the few-particles-per-cell regime most of those bytes
# are sentinel padding. This variant reads the packed layout
# (``core.binning.PackedRows``) instead: each DMA moves ``row_cap`` packed
# slots plus an (nx+3)-entry offset row — bytes proportional to the
# particles, not to m_c. The scalar-prefetched active-row ids drive the
# BlockSpec index maps exactly as in the compacted kernel (the same
# data-dependent staging, composed with the packed rows' own CSR offsets,
# which stay *row-local* so a DMA'd row is self-describing); inside the
# body each target slot's 3-cell X-window is re-expanded to the dense
# (3*m_c,) shape by offset/length with in-vreg lane gathers, so every pair
# term, mask and reduction is elementwise identical to the dense kernel's
# — bit-identical results. Packed slots sit on lanes, window slots on the
# leading axis: the (3*m_c, row_cap) pair tile needs no relayout.

def packed_vmem_bytes(nx: int, m_c: int, row_cap: int) -> int:
    """VMEM one packed X-pencil grid step holds: 14 pipelined one-row
    tiles, double-buffered, plus ~24 live (3*m_c, row_cap) temporaries."""
    row = tile_bytes(1, row_cap)
    return (2 * 14 * row + tile_bytes(1, nx + 3) * 2
            + 24 * tile_bytes(3 * m_c, row_cap))


def _packed_kernel(act_ref,                          # scalar-prefetched ids
                   xt_ref, yt_ref, zt_ref, it_ref, ct_ref,
                   xs_ref, ys_ref, zs_ref, is_ref, os_ref,
                   fx_ref, fy_ref, fz_ref, pot_ref,
                   *, nx: int, m_c: int, kernel: PairKernel, cutoff2: float):
    del act_ref  # consumed by the BlockSpec index maps, not the body
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        for o in (fx_ref, fy_ref, fz_ref, pot_ref):
            o[...] = jnp.zeros_like(o)

    rank = jax.lax.broadcasted_iota(jnp.int32, (m_c, LANES), 0)
    for c0 in range(0, xt_ref.shape[-1], LANES):   # one vreg of targets
        sl = slice(c0, c0 + LANES)
        # pad/ghost targets are never unpacked
        tcell = jnp.clip(ct_ref[:, sl], 1, nx)
        # per window cell dc: where its slots start in the source row, and
        # how many there are — (1, 128) per target slot
        starts, counts = [], []
        for dc in range(3):
            lo = lane_gather(os_ref, tcell - 1 + dc)
            starts.append(lo)
            counts.append(lane_gather(os_ref, tcell + dc) - lo)
        valid = jnp.concatenate([rank < c for c in counts], axis=0)
        src = jnp.concatenate([s + rank for s in starts], axis=0)
        src = jnp.where(valid, src, 0)                  # (3*m_c, 128)

        def expand(ref, fill):
            return jnp.where(valid, lane_gather(ref, src), fill)

        terms = pair_terms(
            xt_ref[:, sl], yt_ref[:, sl], zt_ref[:, sl], it_ref[:, sl],
            expand(xs_ref, EMPTY_POS), expand(ys_ref, EMPTY_POS),
            expand(zs_ref, EMPTY_POS), expand(is_ref, jnp.int32(-1)),
            kernel=kernel, cutoff2=cutoff2, axis=0)
        for o, v in zip((fx_ref, fy_ref, fz_ref, pot_ref), terms):
            o[:, sl] += v


@functools.partial(jax.jit, static_argnames=("nx", "ny", "m_c", "row_cap",
                                             "kernel", "cutoff2",
                                             "interpret"))
def xpencil_packed_forces(planes: dict, slot_id: Array, slot_cell: Array,
                          cell_offsets: Array, active_zy: Array, *,
                          nx: int, ny: int, m_c: int, row_cap: int,
                          kernel: PairKernel, cutoff2: float,
                          interpret: Optional[bool] = None
                          ) -> Tuple[Array, Array, Array, Array]:
    """Run the packed-row X-pencil kernel over the given pencil rows.

    Args:
      planes / slot_id / slot_cell / cell_offsets: the packed layout's
        padded planes (``core.binning.PackedRows``) — planes and ids are
        ``(nz+2, ny+2, row_cap)``, offsets ``(nz+2, ny+2, nx+3)``.
      active_zy: (n_rows,) int32 linearized interior pencil ids
        ``z * ny + y`` to iterate — the full ``arange(nz * ny)`` for a
        dense sweep or an ``Occupancy.active`` list for a compacted one
        (padding recomputes pencil 0; drop it with ``scatter_indices``).
    Returns:
      (fx, fy, fz, pot), each compact ``(n_rows, row_cap)``: row ``a``
      holds the packed-slot forces of pencil ``active_zy[a]``.
    """
    interpret = resolve_interpret(interpret)
    n_rows = active_zy.shape[0]
    width = lane_width(row_cap)

    def row(plane, fill, w=width):     # (..., n) -> (..., 1, w) one-row tiles
        n = plane.shape[-1]
        pad = [(0, 0)] * (plane.ndim - 1) + [(0, w - n)]
        return jnp.pad(plane, pad, constant_values=fill)[..., None, :]

    tgt = (row(planes["x"], EMPTY_POS), row(planes["y"], EMPTY_POS),
           row(planes["z"], EMPTY_POS), row(slot_id, -1), row(slot_cell, 0))
    offs = row(cell_offsets, 0, lane_width(nx + 3))

    def tgt_map(a, k, act):
        return (act[a] // ny + 1, act[a] % ny + 1, 0, 0)

    def nbr_map(a, k, act):
        return (act[a] // ny + k // 3, act[a] % ny + k % 3, 0, 0)

    row_tile = (None, None, 1, width)
    off_tile = (None, None, 1, offs.shape[-1])
    out_block = pl.BlockSpec((None, 1, width), lambda a, k, act: (a, 0, 0))
    out_shape = jax.ShapeDtypeStruct((n_rows, 1, width), tgt[0].dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_rows, 9),
        in_specs=([pl.BlockSpec(row_tile, tgt_map)] * 5
                  + [pl.BlockSpec(row_tile, nbr_map)] * 4
                  + [pl.BlockSpec(off_tile, nbr_map)]),
        out_specs=[out_block] * 4,
    )
    body = functools.partial(_packed_kernel, nx=nx, m_c=m_c, kernel=kernel,
                             cutoff2=float(cutoff2))
    outs = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[out_shape] * 4,
        compiler_params=compiler_params(
            ("parallel", "arbitrary"), packed_vmem_bytes(nx, m_c, row_cap)),
        interpret=interpret,
    )(active_zy.astype(jnp.int32), *tgt, *tgt[:4], offs)
    return tuple(o[:, 0, :row_cap] for o in outs)
