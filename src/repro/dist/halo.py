"""Halo-exchange primitives: Z-slab partition + ghost-plane ``ppermute``.

The low-level machinery of the distributed execution subsystem
(``repro.dist.engine``). The paper's (nz, ny, nx) cell grid is split into
Z-slabs, one per shard along a mesh axis; each shard bins its own particles
into the slab's padded planes and fills its two ghost Z-planes from the
neighbouring shards — the ghost ring of the paper's layout, crossing chips
instead of staying in HBM.

This module owns the pieces that are pure functions of arrays:

  ``partition_by_shard``    traceable per-shard gather under a static
                            ``cap`` (the shard-capacity analogue of the
                            paper's M_C bound — overloaded shards are
                            detectable, never silently wrong),
  ``exchange_halo``         the ``ppermute`` ghost-plane exchange (periodic
                            Z wraps around the shard ring with the
                            minimum-image coordinate shift; **non-periodic
                            Z boundaries are filled with empty planes** so
                            open boundaries contribute zero ghosts),
  ``shard_loads`` / ``suggest_shard_cap`` / ``suggest_shard_max_active``
                            the host-side occupancy probes behind the plan
                            layer's overflow/replan contract.

The executor that strings them together under ``shard_map`` lives in
``repro.dist.engine``; ``plan(..., backend="halo")`` is the front door.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.binning import (EMPTY_POS, cell_counts, shard_pencil_active,
                            shard_slab_counts)
from ..core.domain import Domain
from ..obs.trace import device_scope

Array = jnp.ndarray

# anything beyond this is sentinel padding, far outside every real box
VALID_MAX = 1.0e7


# --------------------------------------------------------------------------
# shard assignment + load probes (host side, outside jit)
# --------------------------------------------------------------------------

def shard_ids(domain: Domain, positions: Array, n_shards: int) -> Array:
    """(N,) Z-slab shard index per particle (periodic-aware cell coords)."""
    if domain.nz % n_shards:
        raise ValueError(
            f"nz={domain.nz} not divisible by n_shards={n_shards}")
    zc = domain.cell_coords(positions)[:, 2]
    return zc // (domain.nz // n_shards)


def shard_loads(domain: Domain, positions: Array, n_shards: int,
                counts: Array | None = None) -> Array:
    """(n_shards,) particles per Z-slab shard. Pass precomputed per-cell
    ``counts`` (``binning.cell_counts``) to skip the binning pass."""
    if counts is None:
        counts = cell_counts(domain, positions)
    return shard_slab_counts(domain, counts, n_shards)


def suggest_shard_cap(domain: Domain, positions: Array, n_shards: int,
                      slack: float = 1.3, align: int = 8) -> int:
    """One-off static per-shard particle capacity: the busiest shard's load
    with slack, rounded up to ``align`` — the same measure-plus-slack
    contract as ``suggest_m_c``. Particles drift between slabs as they
    move; an exceeded cap is caught by ``InteractionPlan.check_overflow``.
    """
    mx = int(jnp.max(shard_loads(domain, positions, n_shards)))
    cap = max(1, int(mx * slack + 0.999))
    return -(-cap // align) * align


def suggest_shard_max_active(domain: Domain, positions: Array,
                             n_shards: int, slack: float = 1.25,
                             align: int = 8,
                             counts: Array | None = None) -> int:
    """Static per-shard active-pencil bound for the compacted halo path:
    the busiest shard's active (z, y) pencil count with slack, aligned,
    clipped to the slab's total pencil count."""
    if counts is None:
        counts = cell_counts(domain, positions)
    mx = int(jnp.max(shard_pencil_active(domain, counts, n_shards)))
    bound = max(1, int(mx * slack + 0.999))
    bound = -(-bound // align) * align
    return min(bound, (domain.nz // n_shards) * domain.ny)


# --------------------------------------------------------------------------
# traceable partition / scatter-back
# --------------------------------------------------------------------------

def partition_by_shard(domain: Domain, positions: Array,
                       fields: Optional[Dict[str, Array]], n_shards: int,
                       cap: int) -> Tuple[Array, Array, Dict[str, Array]]:
    """Group particles by Z-slab under a static per-shard ``cap``.

    Traceable (runs inside the jitted executor): per shard, a fixed-size
    ``nonzero`` gathers that shard's particle rows; pad rows point past the
    end of the particle array and read the ``EMPTY_POS`` sentinel. Returns
    ``(gather_idx (n_shards * cap,), pos_part (n_shards * cap, 3),
    fields_part)`` — ``gather_idx`` routes shard-local results back to
    particle order (pad entries index ``N`` and are dropped by a
    ``mode='drop'`` scatter).

    If a shard holds more than ``cap`` particles the extra rows are
    *dropped* — the plan layer detects that (``shard_loads`` vs the static
    cap) and replans, exactly like an overflowing ``m_c``.
    """
    n = positions.shape[0]
    shard = shard_ids(domain, positions, n_shards)
    idx = [jnp.nonzero(shard == s, size=cap, fill_value=n)[0]
           for s in range(n_shards)]
    gather_idx = jnp.stack(idx).astype(jnp.int32).reshape(-1)
    pad_pos = jnp.concatenate(
        [positions, jnp.full((1, 3), EMPTY_POS, positions.dtype)])
    pos_part = pad_pos[gather_idx]
    fields_part: Dict[str, Array] = {}
    for k, v in (fields or {}).items():
        fields_part[k] = jnp.concatenate(
            [v, jnp.zeros((1,), v.dtype)])[gather_idx]
    return gather_idx, pos_part, fields_part


def scatter_from_shards(gather_idx: Array, n: int, values: Array) -> Array:
    """Inverse of :func:`partition_by_shard` for per-row shard outputs:
    rows land back at their particle index, pad rows are dropped."""
    out_shape = (n,) + values.shape[1:]
    return jnp.zeros(out_shape, values.dtype).at[gather_idx].set(
        values, mode="drop")


# --------------------------------------------------------------------------
# the ghost-plane exchange (inside shard_map)
# --------------------------------------------------------------------------

@device_scope("exchange")
def exchange_halo(plane: Array, *, axis: str, n_shards: int, nz_loc: int,
                  shard_index: Array, periodic_z: bool, fill,
                  coord_shift: float = 0.0) -> Array:
    """Fill a padded plane's two ghost Z-planes from the neighbouring shards.

    ``plane`` is any per-slot plane of the local ``CellBins`` layout —
    shape ``(nz_loc + 2, ny + 2, (nx + 2) * m_c)``. Each shard sends its
    last interior plane up the ring and its first interior plane down
    (``ppermute``); a periodic global Z wraps around the ring, with
    ``coord_shift`` applied so neighbour coordinates land in this shard's
    local frame (the minimum-image shift — pass the slab height for the
    "z" coordinate plane, 0 for everything else).

    At **non-periodic Z boundaries the ghost planes are filled with
    ``fill``** (the empty sentinel): the bottom shard's below-ghost and the
    top shard's above-ghost must contribute zero ghost particles, never the
    wrapped-around plane the ring permutation would otherwise deliver.
    """
    fwd = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    bwd = [(i, (i - 1) % n_shards) for i in range(n_shards)]
    top = plane[nz_loc:nz_loc + 1]          # last interior plane
    bot = plane[1:2]                        # first interior plane
    from_below = jax.lax.ppermute(top, axis, fwd)
    from_above = jax.lax.ppermute(bot, axis, bwd)
    if coord_shift:                         # neighbour frame -> ours
        from_below = from_below - coord_shift
        from_above = from_above + coord_shift
    if not periodic_z:                      # open Z: border ghosts stay empty
        empty = jnp.full(bot.shape, fill, plane.dtype)
        from_below = jnp.where(shard_index == 0, empty, from_below)
        from_above = jnp.where(shard_index == n_shards - 1, empty,
                               from_above)
    plane = plane.at[0:1].set(from_below)
    return plane.at[nz_loc + 1:nz_loc + 2].set(from_above)
