"""Pallas TPU kernels for the paper's hot spots (DESIGN.md §2-3).

xpencil      the paper's X-pencil schedule (BlockSpec pencil staging)
allin        the paper's All-in-SM schedule (manual halo DMA into VMEM)
prefix_sum   the paper's §6 scan (VMEM, 2h-3 vector passes)
window_attn  the technique transferred to LM local attention

Each kernel has a pure-jnp oracle in ref.py and a jit wrapper in ops.py.

The interaction kernels are wired into the plan/execute front door
(``repro.core.api``): importing this package registers them as the
``"pallas"`` backend under the same strategy names as their pure-JAX
oracles, so

    plan(domain, kernel, positions=pos, strategy="xpencil",
         backend="pallas").execute(ParticleState(pos))

runs the Pallas X-pencil kernel (natively on TPU, interpret mode elsewhere)
through exactly the API users already select strategies with.

Each kernel stages what one grid step needs in VMEM and its scalar tables
in SMEM. It registers a budget with its backend entry, which
``core.api.kernel_budget`` reads: ``plan()`` refuses a pallas plan that
runs natively and cannot fit the chip. The estimates count the staged
blocks (double-buffered) plus a guessed number of live pair temporaries;
they were not checked against what Mosaic allocates.
"""

from typing import Callable, Tuple

from ..core.api import InteractionPlan, ParticleState, register_backend
from ..core.binning import (DEFAULT_CSIZE, CellBins, PackedRows, SfcClusters,
                            sfc_cluster_tables)
from ..core.domain import Domain
from . import _lanes
from ._platform import resolve_interpret
from .allin import allin_vmem_bytes
from .ops import (allin_interactions, cell_sfc_interactions, prefix_sum,
                  window_attention, xpencil_interactions,
                  xpencil_packed_interactions, xpencil_sparse_interactions)
from .sfc import sfc_budget
from .xpencil import packed_vmem_bytes, xpencil_vmem_bytes

__all__ = ["allin_interactions", "cell_sfc_interactions", "prefix_sum",
           "window_attention", "xpencil_interactions",
           "xpencil_packed_interactions", "xpencil_sparse_interactions"]

Budget = Tuple[str, int, int]          # (kernel name, VMEM B, SMEM B)


def _fits(estimate: Callable[[InteractionPlan, Domain], Budget]):
    """A ``register_backend`` budget from an estimate: a plan that runs
    natively and cannot fit the chip's memories is refused (the
    interpreter stages nothing in VMEM, so it never is)."""
    def budget(plan: InteractionPlan, dom: Domain) -> Budget:
        need = estimate(plan, dom)
        if not resolve_interpret(plan.interpret):
            _lanes.check_budget(*need)
        return need
    return budget


def _xpencil_budget(plan: InteractionPlan, dom: Domain) -> Budget:
    vmem = xpencil_vmem_bytes(dom.nx, plan.m_c)
    if plan.compact:
        return "xpencil_compact", vmem, 4 * plan.max_active
    return "xpencil", vmem, 0


def _allin_budget(plan: InteractionPlan, dom: Domain) -> Budget:
    return "allin", allin_vmem_bytes(plan.box, plan.m_c), 0


def _packed_budget(plan: InteractionPlan, dom: Domain) -> Budget:
    rows = plan.max_active if plan.compact else dom.nz * dom.ny
    return ("xpencil_packed", packed_vmem_bytes(dom.nx, plan.m_c,
                                                plan.row_cap), 4 * rows)


def _sfc_budget(plan: InteractionPlan, dom: Domain) -> Budget:
    n_clusters = sfc_cluster_tables(dom, DEFAULT_CSIZE).n_clusters
    nx, ny, nz = dom.ncells
    vmem, smem = sfc_budget((nx + 2) * (ny + 2) * (nz + 2), n_clusters,
                            DEFAULT_CSIZE, plan.m_c, plan.pair_cap)
    return "sfc", vmem, smem


# -- plan/execute backend registration (normalized signature) ---------------

@register_backend("pallas", "xpencil", compact=True,
                  budget=_fits(_xpencil_budget))
def _pallas_xpencil(plan: InteractionPlan, bins: CellBins,
                    state: ParticleState):
    if plan.compact:
        return xpencil_sparse_interactions(plan.domain, bins, plan.kernel,
                                           plan.max_active,
                                           interpret=plan.interpret)
    return xpencil_interactions(plan.domain, bins, plan.kernel,
                                interpret=plan.interpret)


@register_backend("pallas", "allin", budget=_fits(_allin_budget))
def _pallas_allin(plan: InteractionPlan, bins: CellBins,
                  state: ParticleState):
    return allin_interactions(plan.domain, bins, plan.kernel, plan.box,
                              interpret=plan.interpret)


@register_backend("pallas", "xpencil", compact=True, layout="packed",
                  budget=_fits(_packed_budget))
def _pallas_xpencil_packed(plan: InteractionPlan, packed: PackedRows,
                           state: ParticleState):
    return xpencil_packed_interactions(
        plan.domain, packed, plan.kernel,
        max_active=plan.max_active if plan.compact else None,
        interpret=plan.interpret)


@register_backend("pallas", "cell_dense", compact=True, layout="sfc",
                  budget=_fits(_sfc_budget))
def _pallas_cell_sfc(plan: InteractionPlan, sfc: SfcClusters,
                     state: ParticleState):
    # compact=True is a no-op for the SFC layout: the compressed pair list
    # IS the compaction (mirrors the reference registration in core.api).
    return cell_sfc_interactions(plan.domain, sfc, plan.kernel,
                                 interpret=plan.interpret)
