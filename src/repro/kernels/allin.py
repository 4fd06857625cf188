"""All-in-SM interaction kernel (paper §5.1) as a Pallas TPU kernel.

The paper stages a whole sub-box of cells plus its ghost ring in shared
memory. Halo blocks *overlap* between neighboring sub-boxes, which BlockSpec
tiling cannot express, so this kernel does what a production TPU kernel does
for halos: inputs stay in HBM (``pl.ANY``) and each program issues
explicit overlapping DMAs into VMEM scratch (``make_async_copy``) — the
literal analogue of the paper's dynamic-shared-memory copy-in, with all four
field copies in flight together.

  grid = (gz, gy, gx)            one program per sub-box (paper thread-block)
  scratch = 4 x VMEM (bz+2, by+2, m_c, Lb)   the staged halo block
  outputs = non-overlapping (bz, by, m_c, Lb) blocks.

The planes are on the lane layout of ``_lanes`` (slot on sublanes, cell on
lanes). A DMA may only cut whole 128-lane tiles, so the wrapper lays the
``gx`` X-blocks — each ``bx + 2`` cells with its ghosts — side by side on a
leading axis, ``Lb`` lanes each; the DMA then slices leading axes only.
Inside, the program walks its ``bz * by`` target rows, each meeting its 9
staged neighbor rows through the same window body as the X-pencil kernel.

The paper's verdict — the sub-box footprint kills occupancy — maps directly:
the staged halo is most of the per-step VMEM budget, so the pipeline has no
double-buffer head-room and the DMA latency is exposed. ``traffic.model``
quantifies this; the kernel exists to reproduce the schedule faithfully.
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
from ._lanes import (compiler_params, from_lanes, lane_width, shift_lanes,
                     tile_bytes, to_lanes, window_terms)
from ._platform import resolve_interpret

Array = jnp.ndarray


def allin_vmem_bytes(box: Tuple[int, int, int], m_c: int) -> int:
    """VMEM one All-in-SM program holds: the 4-field staged halo block,
    4 double-buffered output blocks and ~16 live pair temporaries."""
    bx, by, bz = box
    tile = tile_bytes(m_c, bx + 2)
    return ((4 * (bz + 2) * (by + 2) + 2 * 4 * bz * by) * tile
            + 16 * 3 * m_c * tile)


def _kernel(xp, yp, zp, ip,             # HBM-resident X-blocked planes
            fx_ref, fy_ref, fz_ref, pot_ref,
            sx, sy, sz, si, sems,       # VMEM scratch + DMA semaphores
            *, bx: int, by: int, bz: int, m_c: int,
            kernel: PairKernel, cutoff2: float):
    iz, iy, ix = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    staged = (sx, sy, sz, si)
    copies = []
    for j, (src, dst) in enumerate(zip((xp, yp, zp, ip), staged)):
        cp = pltpu.make_async_copy(
            src.at[ix, pl.ds(iz * bz, bz + 2), pl.ds(iy * by, by + 2)],
            dst, sems.at[j])
        cp.start()
        copies.append(cp)
    for cp in copies:
        cp.wait()

    outs = (fx_ref, fy_ref, fz_ref, pot_ref)

    def target_row(r, carry):
        z, y = r // by + 1, r % by + 1
        tgt = tuple(shift_lanes(s[z, y], 1) for s in staged)  # lane x = cell x
        acc = tuple(jnp.zeros_like(t) for t in tgt)
        for dz in (-1, 0, 1):
            for dy in (-1, 0, 1):
                src = tuple(s[z + dz, y + dy] for s in staged)
                terms = window_terms(tgt, src, m_c=m_c, kernel=kernel,
                                     cutoff2=cutoff2)
                acc = tuple(a + t for a, t in zip(acc, terms))
        for o, a in zip(outs, acc):
            o[z - 1, y - 1] = a
        return carry

    jax.lax.fori_loop(0, bz * by, target_row, 0)


@functools.partial(jax.jit, static_argnames=("box", "m_c", "kernel", "cutoff2", "interpret"))
def allin_forces(planes: dict, slot_id: Array, *, box: Tuple[int, int, int],
                 m_c: int, kernel: PairKernel, cutoff2: float,
                 interpret: Optional[bool] = None
                 ) -> Tuple[Array, Array, Array, Array]:
    """Run the All-in-SM kernel. ``box`` = (bx, by, bz) interior sub-box;
    must divide the grid (``core.strategies.subbox_dims`` + divisor shrink).
    ``interpret=None`` resolves by platform (native on TPU, interpreter
    elsewhere), matching ``InteractionPlan.interpret``.
    Returns (fx, fy, fz, pot), each (nz, ny, nx*m_c)."""
    interpret = resolve_interpret(interpret)
    x = planes["x"]
    nzp, nyp, w = x.shape
    nz, ny = nzp - 2, nyp - 2
    nx = w // m_c - 2
    bx, by, bz = box
    assert nx % bx == 0 and ny % by == 0 and nz % bz == 0, (nx, ny, nz, box)
    gz, gy, gx = nz // bz, ny // by, nx // bx
    width = lane_width(bx + 2)

    def blocked(plane, fill):   # (nz+2, ny+2, (nx+2)*m_c) -> (gx, .., m_c, Lb)
        return jnp.stack([
            to_lanes(plane[..., i * bx * m_c:(i * bx + bx + 2) * m_c], m_c,
                     fill) for i in range(gx)])

    args = (blocked(x, EMPTY_POS), blocked(planes["y"], EMPTY_POS),
            blocked(planes["z"], EMPTY_POS), blocked(slot_id, -1))

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    out_block = pl.BlockSpec((None, bz, by, m_c, width),
                             lambda z, y, xk: (xk, z, y, 0, 0))
    out_shape = jax.ShapeDtypeStruct((gx, nz, ny, m_c, width), x.dtype)
    halo = (bz + 2, by + 2, m_c, width)
    scratch = [pltpu.VMEM(halo, x.dtype) for _ in range(3)]
    scratch += [pltpu.VMEM(halo, slot_id.dtype),
                pltpu.SemaphoreType.DMA((4,))]

    body = functools.partial(_kernel, bx=bx, by=by, bz=bz, m_c=m_c,
                             kernel=kernel, cutoff2=float(cutoff2))
    outs = pl.pallas_call(
        body,
        grid=(gz, gy, gx),
        in_specs=[any_spec] * 4,
        out_specs=[out_block] * 4,
        out_shape=[out_shape] * 4,
        scratch_shapes=scratch,
        compiler_params=compiler_params(
            ("parallel", "parallel", "parallel"),
            allin_vmem_bytes(box, m_c)),
        interpret=interpret,
    )(*args)
    # (gx, nz, ny, m_c, Lb) -> (nz, ny, nx*m_c): X-blocks back side by side
    return tuple(
        jnp.concatenate([from_lanes(o[i], bx) for i in range(gx)], axis=-1)
        for o in outs)
