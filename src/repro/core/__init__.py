"""Core: the paper's cell-list interaction engine (DESIGN.md §1-2).

The public front door is the plan/execute API (``core.api``):
``plan(...)`` fixes every static choice once, ``plan.execute(state)`` is the
jitted hot path, and backends ("reference" pure-JAX / "pallas" TPU kernels)
register per strategy behind one normalized signature. ``CellListEngine``
and ``compute_interactions`` are compatibility shims over it.
"""

from .domain import Domain
from .api import (ExecutionReport, InteractionPlan, ParticleState, PlanHealth,
                  active_unit_count, backend_matrix, choose_strategy,
                  clear_executor_cache, degradation_ladder, dispatch_count,
                  executor_cache_info, fallback_plan, kernel_budget, plan,
                  plan_health,
                  recompile_count, register_backend, reset_counters,
                  reset_health, set_executor_cache_size, suggest_max_active,
                  suggest_pair_cap, suggest_row_cap, supports_compact,
                  supports_layout)
from .binning import (CellBins, Occupancy, PackedRows, SfcClusters,
                      bin_particles, build_sfc_clusters, decode_pair_codes,
                      dense_to_particles, encode_pair_masks,
                      full_pencil_occupancy, gather_pencil_rows,
                      gather_to_particles, hilbert_decode, hilbert_encode,
                      interior_to_padded, morton_decode, morton_encode,
                      pack_rows, packed_to_particles, padded_row_counts,
                      pencil_occupancy, sfc_cluster_tables, sfc_pair_count,
                      sfc_to_particles, subbox_occupancy, unpack_scatter)
from .engine import CellListEngine, compute_interactions, suggest_m_c
from .interactions import (
    PairKernel,
    make_gravity,
    make_high_flop,
    make_lennard_jones,
    make_low_flop,
    make_sph_density,
    pair_contribution,
)
from .prefix import (
    blelloch_counts,
    exclusive_prefix_sum,
    operation_counts,
    paper_prefix_sum,
)
from .timing import time_fn
from . import autotune, scenarios, strategies, traffic
from .autotune import TuneResult, tune

__all__ = [
    "Domain", "CellBins", "Occupancy", "PackedRows", "bin_particles",
    "gather_to_particles", "gather_pencil_rows", "dense_to_particles",
    "interior_to_padded", "pack_rows", "packed_to_particles",
    "padded_row_counts", "unpack_scatter", "full_pencil_occupancy",
    "pencil_occupancy", "subbox_occupancy",
    "SfcClusters", "build_sfc_clusters", "sfc_cluster_tables",
    "sfc_pair_count", "sfc_to_particles", "encode_pair_masks",
    "decode_pair_codes", "morton_encode", "morton_decode",
    "hilbert_encode", "hilbert_decode", "suggest_pair_cap",
    "ExecutionReport", "InteractionPlan", "ParticleState", "PlanHealth",
    "plan", "register_backend", "kernel_budget",
    "backend_matrix", "choose_strategy", "clear_executor_cache",
    "degradation_ladder", "fallback_plan", "plan_health", "reset_health",
    "dispatch_count", "recompile_count", "reset_counters",
    "executor_cache_info", "set_executor_cache_size",
    "active_unit_count", "suggest_max_active",
    "suggest_row_cap", "supports_compact", "supports_layout",
    "tune", "TuneResult", "time_fn", "autotune",
    "CellListEngine", "compute_interactions", "suggest_m_c",
    "PairKernel", "make_gravity", "make_high_flop", "make_lennard_jones",
    "make_low_flop", "make_sph_density", "pair_contribution",
    "paper_prefix_sum", "exclusive_prefix_sum", "operation_counts",
    "blelloch_counts", "scenarios", "strategies", "traffic",
]
