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
schedules.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from ..core.interactions import PairKernel

Array = jnp.ndarray

LANES = 128
SUBLANES = 8

# Scoped VMEM the kernels may ask Mosaic for. A v5e TensorCore has
# 128 MiB of VMEM; the rest is left to Mosaic's own spills and buffers.
VMEM_LIMIT_BYTES = 100 * 2 ** 20
# Scalar memory holding the scalar-prefetched tables (1 MiB on v5e;
# the compiler keeps some of it for its own use).
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


def pair_terms(tx, ty, tz, tid, sx, sy, sz, sid, *, kernel: PairKernel,
               cutoff2: float, axis: int):
    """Masked central-force terms of broadcast target/source arrays,
    reduced over the source ``axis``. Same operations in the same order
    as ``core.interactions.pair_contribution``."""
    ddx, ddy, ddz = tx - sx, ty - sy, tz - sz
    r2 = ddx * ddx + ddy * ddy + ddz * ddz
    mask = (sid != tid) & (sid >= 0) & (tid >= 0) & (r2 < cutoff2) & (r2 > 0.0)
    r2s = jnp.where(mask, r2, 1.0)
    w = mask.astype(ddx.dtype)
    s = kernel.coeff(r2s) * w
    pot = kernel.potential(r2s) * w
    return ((s * ddx).sum(axis, keepdims=True),
            (s * ddy).sum(axis, keepdims=True),
            (s * ddz).sum(axis, keepdims=True),
            pot.sum(axis, keepdims=True))


def window_terms(tgt: Tuple[Array, ...], src: Tuple[Array, ...], *,
                 m_c: int, kernel: PairKernel, cutoff2: float):
    """One staged source row against a row of target cells.

    ``tgt`` = (x, y, z, id) ``(m_c, L)`` tiles whose lane ``x`` holds
    target cell ``x``; ``src`` = the same fields of a source row whose
    lane ``c`` holds padded cell ``c``. Target lane ``x`` meets the
    ``3*m_c`` slots of source cells ``x, x+1, x+2``. Returns the four
    window sums, each ``(m_c, L)``.
    """
    lanes = tgt[0].shape[-1]

    def window(row):                  # (m_c, L) -> (3*m_c, m_c, L)
        return jnp.concatenate(
            [jnp.broadcast_to(shift_lanes(row, dc)[:, None, :],
                              (m_c, m_c, lanes)) for dc in range(3)],
            axis=0)

    out = pair_terms(*(t[None] for t in tgt), *(window(s) for s in src),
                     kernel=kernel, cutoff2=cutoff2, axis=0)
    return tuple(o[0] for o in out)


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
            f"scalar-prefetched tables; the limit is "
            f"{SMEM_LIMIT_BYTES / 2 ** 10:.0f} KiB (TPU SMEM limit)")
