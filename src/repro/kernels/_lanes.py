"""The lane layout the Pallas interaction kernels run on, and their shared
pair body.

A TPU vector register is 8 sublanes x 128 lanes, and Mosaic only moves
data between the two axes through explicit relayouts it mostly refuses
(a ``(nx+2)*m_c`` slot row cannot be reshaped to ``(nx, m_c, 1)`` in a
kernel). So the kernels never reshape across them. They read the cell
planes transposed, one padded X-row per ``(m_c, L)`` tile:

    sublane r = slot r of a cell,  lane c = padded cell c,
    L = the row's cell count rounded up to a multiple of 128 lanes
        (lanes past the row are sentinel-filled and never read back).

A target cell's 3-cell X-window is then three lane rotations of the
source row, and the dense window order (cell-major, slot-minor — the
order ``core.strategies`` sums in) is a concatenation on the leading,
untiled axis: every pair term is an elementwise op on whole vregs and the
window reduction is a sum over that leading axis. On the CPU (interpret
mode) XLA reduces a length-``3*m_c`` axis in the same order whichever
axis it is, so these kernels stay bit-identical to the reference
schedules. Natively, the X-pencil kernel bounds its window by occupancy
and sums it in a loop over the occupied 8-row source tiles
(``window_terms`` given ``n_src`` / ``n_tgt``).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.interactions import PairKernel

Array = jnp.ndarray

LANES = 128
SUBLANES = 8

# Scoped VMEM the kernels may ask Mosaic for. A v5e TensorCore has
# 128 MiB of VMEM; the rest is left to Mosaic's own spills and buffers.
VMEM_LIMIT_BYTES = 100 * 2 ** 20
# Scalar memory holding the scalar-prefetched tables and SMEM inputs
# (1 MiB on v5e; the compiler keeps some of it for its own use).
SMEM_LIMIT_BYTES = 512 * 2 ** 10


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def lane_width(n: int) -> int:
    """``n`` lanes rounded up to whole vregs."""
    return round_up(max(n, 1), LANES)


def tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a ``(rows, cols)`` 32-bit tile after (8, 128) padding."""
    return round_up(rows, SUBLANES) * lane_width(cols) * itemsize


def to_lanes(plane: Array, m_c: int, fill) -> Array:
    """``(..., n_cells * m_c)`` slot rows -> ``(..., m_c, L)`` lane tiles
    (slot on sublanes, cell on lanes, lanes past ``n_cells`` = ``fill``)."""
    lead = plane.shape[:-1]
    n_cells = plane.shape[-1] // m_c
    t = jnp.swapaxes(plane.reshape(*lead, n_cells, m_c), -1, -2)
    pad = [(0, 0)] * (len(lead) + 1) + [(0, lane_width(n_cells) - n_cells)]
    return jnp.pad(t, pad, constant_values=fill)


def from_lanes(tiles: Array, n_cells: int) -> Array:
    """Inverse of :func:`to_lanes` for the first ``n_cells`` lanes."""
    t = jnp.swapaxes(tiles[..., :n_cells], -1, -2)
    return t.reshape(*t.shape[:-2], -1)


def shift_lanes(v: Array, k: int) -> Array:
    """``out[..., x] = v[..., x + k]`` (rotating; wrapped lanes are junk
    that only ever lands on lanes the caller discards)."""
    if k == 0:
        return v
    return pltpu.roll(v, v.shape[-1] - k, v.ndim - 1)


def pair_values(tx, ty, tz, tid, sx, sy, sz, sid, *, kernel: PairKernel,
                cutoff2: float):
    """Masked central-force terms of broadcast target/source arrays, one
    per pair: (fx, fy, fz, pot). Same operations in the same order as
    ``core.interactions.pair_contribution``; a masked pair is an exact 0."""
    ddx, ddy, ddz = tx - sx, ty - sy, tz - sz
    r2 = ddx * ddx + ddy * ddy + ddz * ddz
    mask = (sid != tid) & (sid >= 0) & (tid >= 0) & (r2 < cutoff2) & (r2 > 0.0)
    r2s = jnp.where(mask, r2, 1.0)
    w = mask.astype(ddx.dtype)
    s = kernel.coeff(r2s) * w
    pot = kernel.potential(r2s) * w
    return s * ddx, s * ddy, s * ddz, pot


def pair_terms(tx, ty, tz, tid, sx, sy, sz, sid, *, kernel: PairKernel,
               cutoff2: float, axis: int):
    """:func:`pair_values` reduced over the source ``axis``."""
    return tuple(v.sum(axis, keepdims=True) for v in pair_values(
        tx, ty, tz, tid, sx, sy, sz, sid, kernel=kernel, cutoff2=cutoff2))


def window_terms(tgt: Tuple[Array, ...], src: Tuple, *, m_c: int,
                 kernel: PairKernel, cutoff2: float, n_src=None, n_tgt=None):
    """One staged source row against a row of target cells.

    ``tgt`` = (x, y, z, id) ``(m_c, L)`` tiles whose lane ``x`` holds
    target cell ``x``; ``src`` = refs to the same fields of a source row
    whose lane ``c`` holds padded cell ``c``. Target lane ``x`` meets the
    ``3*m_c`` slots of source cells ``x, x+1, x+2``. Returns the four
    window sums, each ``(m_c, L)``, in the window order (cell-major,
    slot-minor).

    ``n_src`` / ``n_tgt`` bound the window by occupancy: no slot at or
    above ``n_src`` holds a source particle, none at or above ``n_tgt`` a
    target. Left out, the whole ``(3*m_c, m_c, L)`` window is one static
    block. Given (``m_c`` a multiple of 8), each 8-row target tile below
    ``n_tgt`` loops over the source rows of each window cell in whole
    8-row tiles, ``ceil(n_src / 8)`` of them, one ``(8, L)`` pair tile
    per row, summed in window order; a target tile at or above ``n_tgt``
    reads 0. The skipped pairs are exact zeros, so the sums are those of
    the full window up to the order of the float32 additions.
    """
    if n_src is None and n_tgt is None:
        lanes = tgt[0].shape[-1]

        def window(ref):              # (m_c, L) -> (3*m_c, m_c, L)
            row = ref[...]
            return jnp.concatenate(
                [jnp.broadcast_to(shift_lanes(row, dc)[:, None, :],
                                  (m_c, m_c, lanes)) for dc in range(3)],
                axis=0)

        out = pair_terms(*(t[None] for t in tgt), *(window(s) for s in src),
                         kernel=kernel, cutoff2=cutoff2, axis=0)
        return tuple(o[0] for o in out)

    assert m_c % SUBLANES == 0, m_c
    src_tiles = (n_src + SUBLANES - 1) // SUBLANES

    def one_tile(i, acc, t, dc):      # 8 source rows of window cell dc
        base = pl.multiple_of(i * SUBLANES, SUBLANES)
        blk = tuple(shift_lanes(r[pl.ds(base, SUBLANES), :], dc)
                    for r in src)
        for rr in range(SUBLANES):
            terms = pair_values(*t, *(b[rr:rr + 1] for b in blk),
                                kernel=kernel, cutoff2=cutoff2)
            acc = tuple(a + v for a, v in zip(acc, terms))
        return acc

    tiles = []
    for t0 in range(0, m_c, SUBLANES):
        t = tuple(v[t0:t0 + SUBLANES] for v in tgt)
        acc = tuple(jnp.zeros(t[0].shape, tgt[0].dtype) for _ in range(4))
        n = jnp.where(t0 < n_tgt, src_tiles, 0)
        for dc in range(3):
            acc = jax.lax.fori_loop(
                0, n, functools.partial(one_tile, t=t, dc=dc), acc)
        tiles.append(acc)
    return tuple(jnp.concatenate([a[i] for a in tiles], axis=0)
                 for i in range(4))


def lane_gather(table_ref, idx: Array) -> Array:
    """``table[0, idx]`` for a ``(1, W)`` table ref (``W`` a multiple of
    128) and ``(R, 128)`` indices in ``[0, W)``.

    Mosaic gathers lanes only within one vreg, so the indices gather from
    each aligned 128-lane chunk of the table and keep the chunk they point
    into. Every value is copied, never combined. A single index row is
    gathered as a full 8-sublane vreg (Mosaic's gather wants whole vregs)
    and cut back.
    """
    rows = idx.shape[0]
    if rows == 1:
        return lane_gather(table_ref,
                           jnp.broadcast_to(idx, (SUBLANES, LANES)))[:1]
    got = jnp.zeros(idx.shape, table_ref.dtype)
    for t0 in range(0, table_ref.shape[-1], LANES):
        part = jnp.broadcast_to(table_ref[:, t0:t0 + LANES], (rows, LANES))
        local = jnp.clip(idx - t0, 0, LANES - 1)
        hit = (idx >= t0) & (idx < t0 + LANES)
        got = jnp.where(hit, jnp.take_along_axis(part, local, axis=1), got)
    return got


def compiler_params(semantics: Tuple[str, ...], vmem_bytes: int):
    """Mosaic parameters for one interaction kernel: the grid's
    dimension semantics and a scoped-VMEM request sized to the kernel's
    own estimate (with head-room for Mosaic's temporaries)."""
    want = max(32 * 2 ** 20, int(vmem_bytes * 1.5))
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=min(want, VMEM_LIMIT_BYTES))


def check_budget(name: str, vmem_bytes: int, smem_bytes: int = 0) -> None:
    """Refuse a kernel whose staged data cannot fit the chip's memories."""
    if vmem_bytes > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"pallas {name} kernel needs {vmem_bytes / 2 ** 20:.1f} MiB of "
            f"VMEM per grid step; the limit is "
            f"{VMEM_LIMIT_BYTES / 2 ** 20:.0f} MiB (TPU VMEM limit)")
    if smem_bytes > SMEM_LIMIT_BYTES:
        raise ValueError(
            f"pallas {name} kernel needs {smem_bytes / 2 ** 10:.1f} KiB of "
            f"SMEM tables; the limit is "
            f"{SMEM_LIMIT_BYTES / 2 ** 10:.0f} KiB (TPU SMEM limit)")
