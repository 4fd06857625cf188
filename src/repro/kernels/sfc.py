"""SFC cluster-pair interaction kernel (compressed neighbor list) in Pallas.

The dense/compacted kernels iterate a *grid-shaped* schedule (every pencil,
or every active pencil); this kernel iterates the **compressed cluster-pair
list** of the SFC layout (``binning.SfcClusters``) directly:

  grid = (pair_cap,)
    one program per compressed pair code ``cluster * 32 + k`` — the codes
    array is *scalar-prefetched* (``pltpu.PrefetchScalarGridSpec``), so the
    output/target BlockSpec index maps decode the cluster id from the code
    before each step and DMA exactly that cluster's ``csize * m_c`` target
    tile. Codes are sorted (cluster-major, k-minor), so consecutive
    programs of one cluster revisit the same resident output block and
    accumulate stencil terms in ascending-k order — the exact float
    association of the dense Par-Cell sweep, which is what makes the
    kernel bit-identical to ``cell_dense`` (see strategies.cell_sfc).

  Source staging: the padded SoA planes are staged whole as cell rows
  (``(n_pcells + 1, m_c)``: one row per padded cell, plus one appended
  always-empty sentinel cell); per stencil slot k and cluster cell j, the
  scalar-prefetched table gives the row of the k-shifted cell and a
  dynamic ``pl.ds`` row read fetches its ``m_c`` slots from the staged
  block — the cluster-tile-from-shared-memory evaluation of the CSCS
  follow-up. Target tiles arrive transposed (``(m_c, csize)``: slot on
  sublanes), so cell j's targets are a column that meets the source row
  by broadcasting alone. Sentinel pair codes (pair-list padding) decode
  to the ghost cluster row, whose targets and sources are all sentinels,
  so they accumulate exact zeros and the row is stripped by the wrapper.

Memory: the staged planes occupy VMEM whole and the cell table lives in
SMEM (``sfc_budget``), so the kernel serves small and medium boxes; a
box whose staging would overflow either memory is refused when it is
planned. A production-scale TPU variant would DMA per-cluster halo tiles
instead.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.interactions import PairKernel
from ._lanes import compiler_params, pair_terms, tile_bytes
from ._platform import resolve_interpret

Array = jnp.ndarray


def sfc_budget(n_pcells: int, n_clusters: int, csize: int, m_c: int,
               pair_cap: int) -> Tuple[int, int]:
    """(VMEM, SMEM) bytes of one SFC kernel call: the four staged cell-row
    planes (double-buffered) plus target/output tiles, and the
    scalar-prefetched codes/first/cell tables."""
    vmem = (2 * 4 * tile_bytes(n_pcells + 1, m_c)
            + 2 * 8 * tile_bytes(m_c, csize)
            + 16 * tile_bytes(m_c, m_c))
    smem = 4 * (2 * pair_cap + (n_clusters + 1) * 27 * csize)
    return vmem, smem


def _sfc_kernel(codes_ref, first_ref, cell_ref,      # scalar-prefetched
                xt_ref, yt_ref, zt_ref, it_ref,      # target cluster tile
                xs_ref, ys_ref, zs_ref, is_ref,      # staged cell rows
                fx_ref, fy_ref, fz_ref, pot_ref,
                *, csize: int, m_c: int, kernel: PairKernel,
                cutoff2: float):
    p = pl.program_id(0)
    code = codes_ref[p]
    a = code >> 5
    k = code & 31
    outs = (fx_ref, fy_ref, fz_ref, pot_ref)

    @pl.when(first_ref[p] == 1)
    def _init():                 # first pair of this cluster: zero the tile
        for o in outs:
            o[...] = jnp.zeros_like(o)

    for j in range(csize):       # static unroll over the cluster's cells
        row = pl.ds(cell_ref[(a * 27 + k) * csize + j], 1)
        col = slice(j, j + 1)
        terms = pair_terms(
            xt_ref[:, col], yt_ref[:, col], zt_ref[:, col], it_ref[:, col],
            xs_ref[row, :], ys_ref[row, :], zs_ref[row, :], is_ref[row, :],
            kernel=kernel, cutoff2=cutoff2, axis=1)
        for o, v in zip(outs, terms):
            o[:, col] += v


@functools.partial(jax.jit, static_argnames=("csize", "m_c", "kernel",
                                             "cutoff2", "interpret"))
def cell_sfc_forces(tiles: dict, rows: dict, codes: Array, first: Array,
                    src_cell: Array, *, csize: int, m_c: int,
                    kernel: PairKernel, cutoff2: float,
                    interpret: Optional[bool] = None
                    ) -> Tuple[Array, Array, Array, Array]:
    """Run the SFC pair-list kernel over the compressed codes.

    Args:
      tiles: field name ("x","y","z","id") -> ``(n_clusters + 1, m_c,
        csize)`` transposed target cluster tiles, last row the
        all-sentinel ghost cluster the pair-list padding decodes to.
      rows: same fields -> ``(n_pcells + 1, m_c)`` padded planes as cell
        rows, with one appended sentinel cell.
      codes: (pair_cap,) int32 sorted compressed pair codes.
      first: (pair_cap,) int32, 1 where a program is its cluster's first
        pair (zero-initializes the resident output tile).
      src_cell: ((n_clusters + 1) * 27 * csize,) int32 cell row of cell
        j of cluster a shifted by stencil k (ghost row -> sentinel).
    Returns:
      (fx, fy, fz, pot), each ``(n_clusters + 1, m_c, csize)`` — tiles of
      clusters with no kept pair are *unwritten* (the wrapper masks them).
    """
    interpret = resolve_interpret(interpret)
    xt = tiles["x"]
    n_rows = xt.shape[0]
    n_cells = rows["x"].shape[0]

    def tile_map(p, codes, first, cells):
        return (codes[p] >> 5, 0, 0)

    tile_block = pl.BlockSpec((None, m_c, csize), tile_map)
    row_block = pl.BlockSpec((n_cells, m_c),
                             lambda p, codes, first, cells: (0, 0))
    out_shape = jax.ShapeDtypeStruct((n_rows, m_c, csize), xt.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(codes.shape[0],),
        in_specs=[tile_block] * 4 + [row_block] * 4,
        out_specs=[tile_block] * 4,
    )
    body = functools.partial(_sfc_kernel, csize=csize, m_c=m_c,
                             kernel=kernel, cutoff2=float(cutoff2))
    vmem, _ = sfc_budget(n_cells - 1, n_rows - 1, csize, m_c, codes.shape[0])
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[out_shape] * 4,
        compiler_params=compiler_params(("arbitrary",), vmem),
        interpret=interpret,
    )(codes.astype(jnp.int32), first.astype(jnp.int32),
      src_cell.astype(jnp.int32),
      tiles["x"], tiles["y"], tiles["z"], tiles["id"],
      rows["x"], rows["y"], rows["z"], rows["id"])
