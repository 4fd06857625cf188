"""Static bounds: one measure, one slack table, one growth rule.

A static bound is a size the jitted executor is traced around: ``m_c``,
``row_cap``, ``shard_cap``, ``pair_cap`` and ``max_active``. Positions that
need more than a bound silently lose interactions, so each follows the
replan contract (``InteractionPlan.replan``, ARCHITECTURE.md): measure with
slack, round up to the alignment, detect overflow, grow only what
overflowed. ``TABLE`` holds each bound's slack, the plans that carry it,
its measure from per-cell counts and its ceiling; ``measure``,
``breached``, ``fit`` (``plan()``'s omitted bounds) and ``grow`` apply it.
``plan()``, ``replan`` / ``overflow_class``, the trajectory and the halo
path call them; the ``suggest_*`` probes are :func:`size` with a caller's
slack.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterable, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from . import strategies as S
from .binning import (padded_row_counts, pencil_counts, sfc_n_clusters,
                      sfc_pair_count, shard_pencil_active, shard_slab_counts,
                      subbox_counts)
from .domain import Domain

Array = jnp.ndarray

ALIGN = 8            # sublane multiple every bound is rounded up to


def align_up(n: int, align: int = ALIGN) -> int:
    """``n`` rounded up to a multiple of ``align``."""
    return -(-int(n) // align) * align


def size(measured: int, slack: float, align: int = ALIGN,
         ceiling: Optional[int] = None) -> int:
    """``measured * slack`` rounded up to ``align``, clipped to ``ceiling``
    (the size that covers everything) but never below ``measured``."""
    n = align_up(max(1, int(measured * slack + 0.999)), align)
    if ceiling is not None:
        n = max(min(n, ceiling), measured)
    return n


# -- work units: the allin sub-box tiling and the pencils ------------------

def allin_box(domain: Domain, m_c: int) -> Tuple[int, int, int]:
    """The allin sub-box for ``m_c``: the VMEM-budget size, shrunk to
    divisors of the grid. A derived static: it moves when ``m_c`` does."""
    return S.shrink_to_divisors(domain, S.subbox_dims(domain, m_c))


def n_units(domain: Domain, strategy: str = "xpencil",
            box: Optional[Tuple[int, int, int]] = None) -> int:
    """Total work units of a schedule (denominator of the fill fraction):
    (z, y) pencils, or allin sub-boxes of the tiling ``box``."""
    if strategy == "allin":
        bx, by, bz = S.shrink_to_divisors(domain, box or allin_box(domain, 1))
        return (domain.nx // bx) * (domain.ny // by) * (domain.nz // bz)
    return domain.nz * domain.ny


def active_units(domain: Domain, counts: Array, strategy: str = "xpencil",
                 box: Optional[Tuple[int, int, int]] = None) -> int:
    """Work units of :func:`n_units` holding at least one particle, from
    per-cell ``counts``."""
    if strategy == "allin":
        box = S.shrink_to_divisors(domain, box or allin_box(domain, 1))
        uc = subbox_counts(domain, counts, box)
    else:
        uc = pencil_counts(domain, counts)
    return int(jnp.sum(uc > 0))


# -- the table --------------------------------------------------------------

def _pairs(p, counts: Array) -> int:
    if p._multi_shard:
        # each slab has its own cluster order: the busiest shard's list
        from ..dist.engine import shard_sfc_pairs
        return int(max(shard_sfc_pairs(p.domain, counts, p.n_shards)))
    return sfc_pair_count(p.domain, counts=counts)


def _active(p, counts: Array) -> int:
    if p._multi_shard:
        return int(jnp.max(shard_pencil_active(p.domain, counts,
                                               p.n_shards)))
    return active_units(p.domain, counts, p.strategy, p.box)


def _unit_ceiling(p) -> int:
    if p._multi_shard:
        return (p.domain.nz // p.n_shards) * p.domain.ny   # slab pencils
    return n_units(p.domain, p.strategy, p.box)


class Bound(NamedTuple):
    name: str
    slack: float
    carried: Callable[[Any], bool]               # plan -> carries it?
    measure: Callable[[Any, Array], int]         # (plan, counts) -> max
    ceiling: Callable[[Any], Optional[int]]      # plan -> covers-all size


# in the order a breach is reported
TABLE = (
    Bound("m_c", 1.5, lambda p: True,
          lambda p, c: int(jnp.max(c)), lambda p: None),
    Bound("row_cap", 1.25, lambda p: p.layout == "packed",
          lambda p, c: int(jnp.max(padded_row_counts(p.domain, c))),
          lambda p: None),
    Bound("shard_cap", 1.3, lambda p: p._multi_shard,
          lambda p, c: int(jnp.max(shard_slab_counts(p.domain, c,
                                                     p.n_shards))),
          lambda p: None),
    Bound("pair_cap", 1.25, lambda p: p.layout == "sfc", _pairs,
          lambda p: None if p._multi_shard
          else sfc_n_clusters(p.domain) * 27),
    Bound("max_active", 1.25, lambda p: p.compact, _active, _unit_ceiling),
)

SLACK: Dict[str, float] = {b.name: b.slack for b in TABLE}
_BY_NAME = {b.name: b for b in TABLE}


def _sized(p, b: Bound, measured: int, m_c_slack: Optional[float],
           align: int) -> int:
    slack = m_c_slack if b.name == "m_c" and m_c_slack else b.slack
    return size(measured, slack, align, b.ceiling(p))


# -- measure, breached, fit, grow ------------------------------------------

def measure(p, counts: Array) -> Dict[str, int]:
    """The observed maximum of every bound ``p`` carries, from per-cell
    ``counts`` (padding rows already left out by the caller)."""
    return {b.name: b.measure(p, counts) for b in TABLE if b.carried(p)}


def breached(p, observed: Dict[str, int]) -> Optional[str]:
    """The first bound of ``p`` the ``observed`` maxima exceed, or None."""
    for b in TABLE:
        if b.carried(p) and observed.get(b.name, 0) > getattr(p, b.name):
            return b.name
    return None


def fit(p, counts: Optional[Array], names: Iterable[str]):
    """``p`` with each bound in ``names`` measured from ``counts`` and
    sized by the table (None where ``p`` does not carry it)."""
    new = {}
    for name in names:
        b = _BY_NAME[name]
        new[name] = (_sized(p, b, b.measure(p, counts), None, ALIGN)
                     if b.carried(p) else None)
    return dataclasses.replace(p, **new)


def grow(p, observed: Dict[str, int], counts: Optional[Array] = None, *,
         m_c_slack: Optional[float] = None, align: int = ALIGN):
    """``p`` with every breached bound re-sized from its observed maximum
    and forced strictly past the old value (up to the ceiling), so growth
    ends. Only what follows a grown bound moves with it: the allin sub-box
    when ``m_c`` grows, and, given ``counts``, a compacted allin plan's
    ``max_active`` on its new tiling (the trajectory's monitors keep
    maxima only: there the next breach grows it). ``m_c_slack``
    overrides the table's slack for ``m_c``."""
    new: Dict[str, Any] = {}
    for b in TABLE:
        old = getattr(p, b.name)
        if b.carried(p) and observed.get(b.name, 0) > old:
            ceiling = b.ceiling(p)
            floor = align_up(old + 1, align)
            if ceiling is not None:
                floor = min(floor, ceiling)
            new[b.name] = max(_sized(p, b, observed[b.name], m_c_slack,
                                     align), floor)
    if "m_c" in new:
        new["box"] = None            # InteractionPlan re-derives it
    q = dataclasses.replace(p, **new)
    if (counts is not None and q.compact and q.strategy == "allin"
            and q.box != p.box):
        b = _BY_NAME["max_active"]
        q = dataclasses.replace(q, max_active=_sized(
            q, b, b.measure(q, counts), m_c_slack, align))
    return q
