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

  Natively (``m_c`` a multiple of 8) each step's window is bounded by
  occupancy. ``slot_bounds`` gives every padded pencil its highest
  occupied slot plus one, a ``(nz+2, ny+2)`` table held whole in SMEM
  (an ordinary SMEM input, not a scalar prefetch, so that ``vmap`` adds
  a grid axis and does not fall back to a loop of kernel launches). A
  step evaluates only the source rows below ``n_src`` of each lane
  rotation, in whole 8-row tiles in a ``fori_loop``, against only the
  target's 8-row tiles below ``n_tgt`` (``_lanes.window_terms``):
  ``3 * 8*ceil(n_src/8)`` x ``8*ceil(n_tgt/8)`` slot pairs in place of
  ``3*m_c x m_c``. The skipped pairs are exact zeros; only the order of
  the float32 additions differs from the full window. Interpret mode
  sums the static full window, bit-identical to the reference schedules.

VMEM per step: 12 double-buffered (m_c, L) tiles, the shifted target tiles
and the tile loop's (8, L) pair temporaries (``xpencil_vmem_bytes``) — well
under a MiB at m_c = 16, far under budget: exactly the paper's point that
pencils, unlike sub-boxes, leave head-room. SMEM holds the bound table
(``xpencil_budget``); a grid of more than about 360 x 360 pencils in
(y, z), whose table would not fit, sums the full window instead.

``xpencil_sparse_forces`` below is the occupancy-compacted variant: its grid
runs over the *active* pencils only, with the active-index list
scalar-prefetched so the BlockSpec index maps become data-dependent; the
bound table is an SMEM input as in the dense kernel.
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
from ._lanes import (LANES, SMEM_LIMIT_BYTES, SUBLANES, compiler_params,
                     from_lanes, lane_gather, lane_width, pair_terms,
                     shift_lanes, tile_bytes, to_lanes, window_terms)
from ._platform import resolve_interpret

Array = jnp.ndarray


def _lane_planes(planes: dict, slot_id: Array, m_c: int):
    """The four input planes of a dense kernel on the lane layout."""
    return (to_lanes(planes["x"], m_c, EMPTY_POS),
            to_lanes(planes["y"], m_c, EMPTY_POS),
            to_lanes(planes["z"], m_c, EMPTY_POS),
            to_lanes(slot_id, m_c, -1))


def slot_bounds(slot_id: Array, m_c: int) -> Array:
    """Per padded pencil, its highest occupied slot plus one:
    ``(nz+2, ny+2)`` int32 from the ``slot_id`` plane the kernel reads.
    Not a count, so ghost copies, open boundaries, dropped rows and any
    slot order leave every occupied slot below it."""
    slot = jnp.arange(slot_id.shape[-1], dtype=jnp.int32) % m_c + 1
    return jnp.max(jnp.where(slot_id >= 0, slot, 0), axis=-1)


def _table_bytes(ny: int, nz: int) -> int:
    """SMEM of the slot-bound table: one int32 row, one entry per padded
    pencil, laid out in whole 128-lane tiles."""
    return 4 * lane_width((ny + 2) * (nz + 2))


def _bounded(ny: int, nz: int, m_c: int, interpret: bool) -> bool:
    """Whether the grid steps bound their window by the slot-bound table:
    natively, where a cell's slots are whole 8-row tiles and the table
    fits SMEM; otherwise each step sums the static full window (in
    interpret mode as the reference schedules do, to the bit)."""
    return (not interpret and m_c % SUBLANES == 0
            and _table_bytes(ny, nz) <= SMEM_LIMIT_BYTES)


def _pencil_pairs(bound: Array, m_c: int) -> Array:
    """Slot pairs each (target pencil, neighbour) grid step evaluates
    natively: ``3 * 8*ceil(n_src/8)`` source rows against the target's
    8-row tiles below ``n_tgt`` (``_lanes.window_terms``), or the full
    ``3*m_c x m_c`` where ``m_c`` is not whole tiles. ``(nz, ny, 9)``."""
    nzp, nyp = bound.shape
    nz, ny = nzp - 2, nyp - 2
    if not _bounded(ny, nz, m_c, interpret=False):
        return jnp.full((nz, ny, 9), 3 * m_c * m_c, jnp.int32)
    tiles = -(-bound // SUBLANES) * SUBLANES
    n_src = jnp.stack([tiles[k // 3:k // 3 + nz, k % 3:k % 3 + ny]
                       for k in range(9)], axis=-1)
    return 3 * n_src * tiles[1:nz + 1, 1:ny + 1, None]


def slot_pair_share(slot_id: Array, m_c: int) -> Array:
    """The share of the dense ``3*m_c x m_c`` slot pairs per grid step
    that the kernel evaluates natively on this ``slot_id`` plane,
    averaged over the dense grid's steps."""
    pairs = _pencil_pairs(slot_bounds(slot_id, m_c), m_c)
    return jnp.mean(pairs / (3 * m_c * m_c))


def xpencil_vmem_bytes(nx: int, m_c: int, bounded: bool) -> int:
    """VMEM one dense/compacted X-pencil grid step holds: 12 pipelined
    (m_c, L) tiles, double-buffered, plus, bounded, the shifted target
    tiles and ~24 live (8, L) pair temporaries of the tile loop, else ~16
    (3*m_c, m_c, L) temporaries of the full window."""
    tile = tile_bytes(m_c, nx + 2)
    if bounded:
        return 2 * 12 * tile + 4 * tile + 24 * tile_bytes(SUBLANES, nx + 2)
    return 2 * 12 * tile + 16 * 3 * m_c * tile


def xpencil_budget(nx: int, ny: int, nz: int, m_c: int) -> Tuple[int, int]:
    """(VMEM bytes per grid step, SMEM bytes of the slot-bound table) of
    the dense/compacted X-pencil kernel natively."""
    bounded = _bounded(ny, nz, m_c, interpret=False)
    return (xpencil_vmem_bytes(nx, m_c, bounded),
            _table_bytes(ny, nz) if bounded else 0)


def _pencil_step(z, y, k, bound_ref, t_refs, s_refs, o_refs, *, nyp: int,
                 m_c: int, kernel: PairKernel, cutoff2: float):
    """Step ``k`` = (dz, dy) of interior target pencil (z, y): the staged
    target pencil x one source pencil, accumulated into the resident
    output tiles. Given a ``bound_ref`` (the slot-bound table as one
    row, ``nyp`` pencils per z), the two pencils' slot bounds bound the
    window. Shared by the dense and compacted kernels so compaction
    cannot change a computed value."""

    @pl.when(k == 0)
    def _init():
        for o in o_refs:
            o[...] = jnp.zeros_like(o)

    bounds = {} if bound_ref is None else dict(
        n_src=bound_ref[0, (z + k // 3) * nyp + y + k % 3],
        n_tgt=bound_ref[0, (z + 1) * nyp + y + 1])
    tgt = tuple(shift_lanes(r[...], 1) for r in t_refs)   # lane x = cell x
    terms = window_terms(tgt, s_refs, m_c=m_c, kernel=kernel,
                         cutoff2=cutoff2, **bounds)
    for o, v in zip(o_refs, terms):
        o[...] += v


def _split_bound(refs, bounded: bool):
    """(the slot-bound table's ref or None, the plane refs)."""
    return (refs[0], refs[1:]) if bounded else (None, refs)


def _kernel(*refs, bounded: bool, nyp: int, **params):
    bound_ref, refs = _split_bound(refs, bounded)
    _pencil_step(pl.program_id(0), pl.program_id(1), pl.program_id(2),
                 bound_ref, refs[0:4], refs[4:8], refs[8:12], nyp=nyp,
                 **params)


def _bound_operand(slot_id: Array, m_c: int, bounded: bool):
    """The slot-bound table, flattened to one ``(1, nzp*nyp)`` row, and
    its whole-array SMEM spec, as leading operand lists (empty where the
    window is not bounded). Under ``vmap`` the row is a block of a
    ``(B, 1, nzp*nyp)`` array, whose last two dimensions Mosaic takes
    whole."""
    if not bounded:
        return [], []
    return ([slot_bounds(slot_id, m_c).reshape(1, -1)],
            [pl.BlockSpec(memory_space=pltpu.SMEM)])


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
    nzp, nyp = slot_id.shape[:2]
    return _dense_call(planes, slot_id, nx=nx, m_c=m_c, kernel=kernel,
                       cutoff2=cutoff2, interpret=interpret,
                       bounded=_bounded(nyp - 2, nzp - 2, m_c, interpret))


def _dense_call(planes, slot_id, *, nx, m_c, kernel, cutoff2, interpret,
                bounded):
    """:func:`xpencil_forces` with the window's bounding given."""
    bound, bound_spec = _bound_operand(slot_id, m_c, bounded)
    lanes = _lane_planes(planes, slot_id, m_c)
    nzp, nyp, _, width = lanes[0].shape
    nz, ny = nzp - 2, nyp - 2
    tile = (None, None, m_c, width)
    row_block = pl.BlockSpec(tile, lambda z, y, k: (z + 1, y + 1, 0, 0))
    nbr_block = pl.BlockSpec(tile, lambda z, y, k: (z + k // 3, y + k % 3,
                                                    0, 0))
    out_block = pl.BlockSpec(tile, lambda z, y, k: (z, y, 0, 0))
    out_shape = jax.ShapeDtypeStruct((nz, ny, m_c, width), lanes[0].dtype)

    body = functools.partial(_kernel, bounded=bounded, nyp=nyp, m_c=m_c,
                             kernel=kernel, cutoff2=float(cutoff2))
    outs = pl.pallas_call(
        body,
        grid=(nz, ny, 9),
        in_specs=bound_spec + [row_block] * 4 + [nbr_block] * 4,
        out_specs=[out_block] * 4,
        out_shape=[out_shape] * 4,
        compiler_params=compiler_params(
            ("parallel", "parallel", "arbitrary"),
            xpencil_vmem_bytes(nx, m_c, bounded)),
        interpret=interpret,
    )(*bound, *lanes, *lanes)
    return tuple(from_lanes(o, nx) for o in outs)


# --------------------------------------------------------------------------
# occupancy-compacted variant: grid over *active* pencils only
# --------------------------------------------------------------------------
#
# The dense kernel's grid is (nz, ny, 9) — every pencil pays 10 row DMAs
# whether or not it holds particles. Here the grid is (max_active, 9): the
# active-pencil index list is *scalar-prefetched*
# (``pltpu.PrefetchScalarGridSpec``), so the BlockSpec index maps can read it before each step and DMA exactly the
# rows of the a-th active pencil — data-dependent staging, the TPU
# analogue of a compacted thread-block launch. Outputs are compact
# (max_active, nx*m_c) rows that the caller scatters back into the dense
# planes (padding rows recompute pencil 0 and are dropped by the scatter).


def _sparse_kernel(act_ref, *refs, bounded: bool, ny: int, **params):
    bound_ref, refs = _split_bound(refs, bounded)
    zy = act_ref[pl.program_id(0)]
    _pencil_step(zy // ny, zy % ny, pl.program_id(1), bound_ref, refs[0:4],
                 refs[4:8], refs[8:12], nyp=ny + 2, **params)


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
    return _sparse_call(planes, slot_id, active_zy, nx=nx, ny=ny, m_c=m_c,
                        kernel=kernel, cutoff2=cutoff2, interpret=interpret,
                        bounded=_bounded(ny, slot_id.shape[0] - 2, m_c,
                                         interpret))


def _sparse_call(planes, slot_id, active_zy, *, nx, ny, m_c, kernel,
                 cutoff2, interpret, bounded):
    """:func:`xpencil_sparse_forces` with the window's bounding given."""
    bound, bound_spec = _bound_operand(slot_id, m_c, bounded)
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
        in_specs=(bound_spec + [pl.BlockSpec(tile, tgt_map)] * 4
                  + [pl.BlockSpec(tile, nbr_map)] * 4),
        out_specs=[out_block] * 4,
    )
    body = functools.partial(_sparse_kernel, bounded=bounded, ny=ny,
                             m_c=m_c, kernel=kernel, cutoff2=float(cutoff2))
    outs = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[out_shape] * 4,
        compiler_params=compiler_params(
            ("parallel", "arbitrary"), xpencil_vmem_bytes(nx, m_c, bounded)),
        interpret=interpret,
    )(active_zy.astype(jnp.int32), *bound, *lanes, *lanes)
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
