"""Measured autotuner: pick schedules by stopwatch, not by model.

The paper's central finding is that the winning schedule (X-pencil vs
All-in-SM vs Par-Part) depends on hardware and fill ratio in ways an
analytical model cannot fully predict — its own Fig. 6/7 results had to be
*measured* on three GPUs. ``strategy="auto"`` trusts the ``core.traffic``
HBM-bytes model alone; ``strategy="autotune"`` (this module) uses the model
only to *prune* the candidate space, then times the survivors with the same
compile-excluded stopwatch the benchmark figures use and returns the
empirically fastest plan.

    result = tune(domain, kernel, positions)        # enumerate -> prune ->
    forces, pot = result.plan.execute(state)        #   time -> pick winner

or through the front door::

    p = plan(domain, kernel, positions=pos, strategy="autotune")

Winners persist in an on-disk JSON cache keyed by (platform, grid shape,
m_c, ppc bucket, kernel identity, backends, candidate-space digest), so
re-tuning the same regime costs one dict lookup and zero timing runs. Point
``REPRO_AUTOTUNE_CACHE`` at a directory to relocate the cache (tests use a
tmpdir); delete the file to invalidate.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import json
import math
import os
import pathlib
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax

from . import strategies as S
from . import traffic
from .api import (InteractionPlan, ParticleState, STRATEGY_NAMES,
                  _allin_box, _max_cell_count, get_backend)
from .bounds import SLACK
from .domain import Domain
from .interactions import PairKernel, make_lennard_jones
from .timing import time_fn
from ..obs import metrics as _obs_metrics
from ..obs.trace import event as _obs_event, trace as _obs_trace

Array = jax.Array

# Bump when the candidate space or cache schema changes: stale entries from
# an older tuner are skipped (and overwritten), not misread.
# v2: dense-vs-compact candidate axis + occupancy bucket in the cache key.
# v3: halo shard-count candidate axis + device count in the cache key (a
#     winner tuned on an 8-device mesh must not answer a 1-device query).
# v4: dense-vs-packed layout axis (Candidate.layout/row_cap). The key's
#     ppc and occupancy buckets already separate the regimes the layout
#     decision depends on; the version bump retires v3 entries whose
#     candidate space lacked packed twins.
# v5: SFC cluster layout axis (Candidate.layout="sfc"/pair_cap): the
#     compressed cluster-pair-list twins of every sfc-capable candidate.
#     Retires v4 entries whose candidate space lacked sfc twins.
CACHE_VERSION = 5

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_CACHE_FILE = "autotune_cache.json"

DEFAULT_BATCH_SIZES = (32, 64, 128)
DEFAULT_TOP_K = 8

# Re-tune accounting: one bump per candidate actually timed with the
# stopwatch (cache hits bump nothing). The serving tier's steady-state
# guarantee — "a warm engine never re-times" — asserts against this
# counter, the autotune analogue of ``core.api.recompile_count``. Lives in
# the process metrics registry (``repro.obs``) next to the dispatch /
# recompile counters, so ``core.api.reset_counters()`` clears it too.
TIMING_RUNS_TOTAL = "repro_autotune_timing_runs_total"
CACHE_TOTAL = "repro_autotune_cache_total"


def timing_run_count() -> int:
    """Stopwatch candidate timings so far (0 across pure cache hits)."""
    return int(_obs_metrics.registry.total(TIMING_RUNS_TOTAL))


def reset_timing_runs() -> None:
    _obs_metrics.registry.reset(TIMING_RUNS_TOTAL)


# --------------------------------------------------------------------------
# candidates
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the tuning space — exactly the static knobs of a plan."""

    strategy: str
    backend: str
    batch_size: int
    m_c: int
    box: Optional[Tuple[int, int, int]] = None   # allin sub-box
    compact: bool = False                        # occupancy-compacted path
    max_active: Optional[int] = None             # static active-unit bound
    n_shards: Optional[int] = None               # halo Z-slabs (None = 1)
    shard_cap: Optional[int] = None              # halo per-shard capacity
    layout: str = "dense"                        # layout: dense|packed|sfc
    row_cap: Optional[int] = None                # static packed-row bound
    pair_cap: Optional[int] = None               # static sfc pair-list bound

    @property
    def distributed(self) -> bool:
        return bool(self.n_shards) and self.n_shards > 1

    def plan(self, domain: Domain, kernel: PairKernel,
             interpret: Optional[bool] = None) -> InteractionPlan:
        if self.distributed:
            # the candidate's backend is the *per-shard* backend; the
            # allin slab tiling is recomputed by the plan for this shard
            # count, so the dense candidate's box is dropped
            return InteractionPlan(
                domain=domain, kernel=kernel, m_c=self.m_c,
                strategy=self.strategy, backend="halo",
                halo_inner=self.backend, batch_size=self.batch_size,
                box=None, interpret=interpret, compact=self.compact,
                max_active=self.max_active, layout=self.layout,
                row_cap=self.row_cap, pair_cap=self.pair_cap,
                n_shards=self.n_shards, shard_cap=self.shard_cap)
        return InteractionPlan(domain=domain, kernel=kernel, m_c=self.m_c,
                               strategy=self.strategy, backend=self.backend,
                               batch_size=self.batch_size, box=self.box,
                               interpret=interpret, compact=self.compact,
                               max_active=self.max_active,
                               layout=self.layout, row_cap=self.row_cap,
                               pair_cap=self.pair_cap)

    def to_json(self) -> dict:
        return {"strategy": self.strategy, "backend": self.backend,
                "batch_size": self.batch_size, "m_c": self.m_c,
                "box": list(self.box) if self.box else None,
                "compact": self.compact, "max_active": self.max_active,
                "n_shards": self.n_shards, "shard_cap": self.shard_cap,
                "layout": self.layout, "row_cap": self.row_cap,
                "pair_cap": self.pair_cap}

    @classmethod
    def from_json(cls, d: dict) -> "Candidate":
        return cls(strategy=d["strategy"], backend=d["backend"],
                   batch_size=int(d["batch_size"]), m_c=int(d["m_c"]),
                   box=tuple(d["box"]) if d.get("box") else None,
                   compact=bool(d.get("compact", False)),
                   max_active=(int(d["max_active"])
                               if d.get("max_active") else None),
                   n_shards=(int(d["n_shards"])
                             if d.get("n_shards") else None),
                   shard_cap=(int(d["shard_cap"])
                              if d.get("shard_cap") else None),
                   layout=d.get("layout", "dense"),
                   row_cap=(int(d["row_cap"])
                            if d.get("row_cap") else None),
                   pair_cap=(int(d["pair_cap"])
                             if d.get("pair_cap") else None))


def enumerate_candidates(domain: Domain, m_c_choices: Sequence[int], *,
                         backends: Sequence[str] = ("reference",),
                         batch_sizes: Sequence[int] = DEFAULT_BATCH_SIZES,
                         strategies: Sequence[str] = STRATEGY_NAMES,
                         extra_allin_boxes: Sequence[Tuple[int, int, int]]
                         = ()) -> List[Candidate]:
    """The candidate space: (strategy, backend, batch_size, m_c, allin box).

    Only (backend, strategy) pairs actually registered survive — the tuner
    can never return an unimplemented combination (``naive_n2`` is the one
    registry-free strategy: the executor special-cases it, so it is emitted
    whenever explicitly requested, once per ``m_c`` — it reads neither
    backend nor batch size). ``batch_size`` is a reference-schedule knob
    (the Pallas kernels ignore it), so Pallas candidates are emitted once
    per remaining axis, pinned to ``min(batch_sizes)`` so the candidate
    space — and the cache key derived from it — does not depend on the
    order callers list batch sizes in.
    """
    out: List[Candidate] = []
    canon_bs = min(batch_sizes)
    for backend in backends:
        for strategy in strategies:
            if strategy == "naive_n2":
                if backend != backends[0]:
                    continue
                bss: Sequence[int] = (canon_bs,)
            else:
                try:
                    get_backend(backend, strategy)
                except ValueError:
                    continue
                bss = batch_sizes if backend == "reference" else (canon_bs,)
            for m_c in dict.fromkeys(m_c_choices):
                boxes: Iterable[Optional[Tuple[int, int, int]]] = (None,)
                if strategy == "allin":
                    boxes = _allin_boxes(domain, m_c, extra_allin_boxes)
                for box in boxes:
                    for bs in dict.fromkeys(bss):
                        out.append(Candidate(strategy, backend, bs, m_c, box))
    return out


def _allin_boxes(domain: Domain, m_c: int,
                 extra: Sequence[Tuple[int, int, int]] = ()
                 ) -> List[Tuple[int, int, int]]:
    """VMEM-budget sub-box plus a small-box alternative (more parallelism,
    less reuse — the trade the paper's §5.1 occupancy discussion is about);
    user-supplied boxes are shrunk to valid grid divisors and appended."""
    boxes = [_allin_box(domain, m_c),
             S.shrink_to_divisors(domain, (2, 2, 2))]
    boxes += [S.shrink_to_divisors(domain, tuple(b)) for b in extra]
    return list(dict.fromkeys(boxes))


def _cost(domain: Domain, avg_ppc: float, c: Candidate,
          fill_for=None) -> float:
    fill = fill_for(c) if (fill_for is not None and c.compact) else 1.0
    return traffic.candidate_cost(domain, c.m_c, avg_ppc, c.strategy,
                                  subbox=c.box, compact=c.compact,
                                  fill=fill, layout=c.layout)


def _audit_pruned(domain: Domain, positions: Array,
                  pruned: Sequence[Candidate], avg_ppc: float,
                  fill_for, counts_box: list) -> None:
    """Model-vs-measured audit of every prune decision (repro.obs.audit).

    Records the "model drift" gauge for each pruned candidate — the exact
    modelled cost that pruned it vs the measured bytes/interaction from the
    real occupancy — so a wrong prune is visible in the registry instead of
    lost. Deduplicated on the model's own inputs (batch-size and backend
    variants share one score); the binning pass is reused from the tuner's
    memo. Audit failures never fail the tune."""
    from ..obs.audit import audit_candidate
    if not counts_box:
        from .binning import cell_counts
        counts_box.append(cell_counts(domain, positions))
    counts = counts_box[0]
    seen = set()
    for c in pruned:
        key = (c.strategy, c.layout, c.compact, c.m_c, c.box)
        if key in seen:
            continue
        seen.add(key)
        try:
            audit_candidate(domain, positions, strategy=c.strategy,
                            m_c=c.m_c, layout=c.layout, compact=c.compact,
                            subbox=c.box, counts=counts,
                            modelled=_cost(domain, avg_ppc, c, fill_for))
        except Exception as e:  # noqa: BLE001 — observability must not
            print(f"autotune: audit of pruned {c} failed: {e!r}",  # bite
                  file=sys.stderr)


def compact_twins(domain: Domain, positions: Array,
                  candidates: Sequence[Candidate]) -> List[Candidate]:
    """The dense-vs-compact candidate axis: for every candidate whose
    (backend, strategy) implements the occupancy-compacted path, a twin
    with ``compact=True`` and a ``max_active`` bound measured from
    ``positions`` (the same slack-plus-alignment contract as ``m_c``)."""
    from .api import suggest_max_active, supports_compact
    twins: List[Candidate] = []
    bounds: Dict[Tuple, int] = {}
    for c in candidates:
        if c.compact or not supports_compact(c.backend, c.strategy):
            continue
        key = ("box", c.box) if c.strategy == "allin" else ("pencil",)
        if key not in bounds:
            bounds[key] = suggest_max_active(domain, positions, c.strategy,
                                             box=c.box)
        twins.append(dataclasses.replace(c, compact=True,
                                         max_active=bounds[key]))
    return list(dict.fromkeys(twins))


def packed_twins(domain: Domain, positions: Array,
                 candidates: Sequence[Candidate]) -> List[Candidate]:
    """The dense-vs-packed layout axis: for every candidate whose
    (backend, strategy) implements the packed-row layout, a twin with
    ``layout="packed"`` and a ``row_cap`` bound measured from
    ``positions`` (the same slack-plus-alignment contract as ``m_c``).
    Applied after :func:`compact_twins`, so compacted candidates get
    packed twins too — the two axes compose."""
    from .api import suggest_row_cap, supports_layout
    twins: List[Candidate] = []
    bound: Optional[int] = None
    for c in candidates:
        if (c.layout != "dense"
                or not supports_layout(c.backend, c.strategy, "packed")):
            continue
        if c.compact and not _supports_packed_compact(c):
            continue
        if bound is None:
            bound = suggest_row_cap(domain, positions)
        twins.append(dataclasses.replace(c, layout="packed", row_cap=bound))
    return list(dict.fromkeys(twins))


def _supports_packed_compact(c: Candidate) -> bool:
    from .api import supports_compact
    return supports_compact(c.backend, c.strategy, "packed")


def sfc_twins(domain: Domain, positions: Array,
              candidates: Sequence[Candidate]) -> List[Candidate]:
    """The SFC cluster-layout axis: for every candidate whose
    (backend, strategy) implements the compressed cluster-pair list, a
    twin with ``layout="sfc"`` and a ``pair_cap`` bound measured from
    ``positions`` (the same slack-plus-alignment contract as ``m_c`` /
    ``row_cap``). Only dense, undistributed candidates get a twin: the
    pair list *is* the compaction (a compact twin would be redundant),
    and the distributed axis composes via :func:`halo_twins` afterwards."""
    from .api import suggest_pair_cap, supports_layout
    twins: List[Candidate] = []
    bound: Optional[int] = None
    for c in candidates:
        if (c.layout != "dense" or c.compact or c.distributed
                or not supports_layout(c.backend, c.strategy, "sfc")):
            continue
        if bound is None:
            bound = suggest_pair_cap(domain, positions)
        twins.append(dataclasses.replace(c, layout="sfc", pair_cap=bound))
    return list(dict.fromkeys(twins))


def halo_twins(domain: Domain, positions: Array,
               candidates: Sequence[Candidate],
               shard_counts: Sequence[int], *,
               device_count: Optional[int] = None) -> List[Candidate]:
    """The shard-count candidate axis: for every cell-schedule candidate, a
    distributed twin per viable shard count — ``backend="halo"`` with the
    candidate's backend as the per-shard inner, a ``shard_cap`` measured
    from ``positions`` (the ``m_c`` contract again), and compacted twins
    re-bounded to the *busiest shard's* active pencils. Shard counts that
    don't divide ``nz`` or exceed the visible devices are skipped."""
    from ..dist.halo import suggest_shard_cap, suggest_shard_max_active
    if device_count is None:
        device_count = jax.device_count()
    twins: List[Candidate] = []
    caps: Dict[int, int] = {}
    bounds: Dict[int, int] = {}
    for ns in dict.fromkeys(shard_counts):
        if ns < 2 or ns > device_count or domain.nz % ns:
            continue
        caps[ns] = suggest_shard_cap(domain, positions, ns)
        for c in candidates:
            if c.distributed:
                continue
            if c.strategy not in ("cell_dense", "xpencil", "allin"):
                continue
            if c.compact and c.strategy == "allin":
                continue                 # no per-slab sub-box occupancy
            max_active = c.max_active
            if c.compact:
                if ns not in bounds:
                    bounds[ns] = suggest_shard_max_active(domain, positions,
                                                          ns)
                max_active = bounds[ns]
            twins.append(dataclasses.replace(
                c, n_shards=ns, shard_cap=caps[ns], box=None,
                max_active=max_active))
    return list(dict.fromkeys(twins))


def prune_candidates(domain: Domain, avg_ppc: float,
                     candidates: Sequence[Candidate],
                     top_k: int = DEFAULT_TOP_K,
                     fill_for=None
                     ) -> Tuple[List[Candidate], List[Candidate]]:
    """Model-guided pruning to ``top_k`` candidates. -> (kept, pruned).

    The ``traffic.candidate_cost`` ranking orders candidates *within* each
    strategy, and strategies are then drained round-robin (cheapest
    strategy first). The model therefore shapes the field but can never
    eliminate a whole strategy by itself — its cost is identical across
    batch-size variants, so a straight global sort would fill ``top_k``
    with duplicates of its favourite schedule and the stopwatch would
    never get to contradict it (the exact failure this tuner exists for).
    Dense and compacted variants of a strategy form separate round-robin
    queues for the same reason: the fill-scaled model must not be able to
    crowd its dense twin (or vice versa) out of the timed field — and so
    do packed-layout variants (whose gather/expand overhead the byte model
    does not see) and distributed (halo) variants per shard count, whose
    ppermute cost the model does not see at all.

    ``fill_for``: optional ``Candidate -> fill fraction`` hook used to
    score compacted candidates (measured occupancy; default 1.0).
    """
    def order_key(c: Candidate):
        return (_cost(domain, avg_ppc, c, fill_for), c.backend,
                c.batch_size, c.m_c, c.box or (), c.compact,
                c.n_shards or 1, c.layout)

    by_strategy: Dict[Tuple[str, bool, int, str], List[Candidate]] = {}
    for c in sorted(candidates, key=order_key):
        by_strategy.setdefault(
            (c.strategy, c.compact, c.n_shards or 1, c.layout),
            []).append(c)
    queues = sorted(by_strategy.values(),
                    key=lambda q: order_key(q[0]))
    interleaved = [c for round_ in itertools.zip_longest(*queues)
                   for c in round_ if c is not None]
    k = max(1, int(top_k))
    kept = interleaved[:k]
    return kept, [c for c in interleaved[k:]]


# --------------------------------------------------------------------------
# on-disk cache
# --------------------------------------------------------------------------

def cache_dir() -> pathlib.Path:
    env = os.environ.get(_CACHE_ENV)
    if env:
        return pathlib.Path(env)
    xdg = os.environ.get("XDG_CACHE_HOME",
                         os.path.join(os.path.expanduser("~"), ".cache"))
    return pathlib.Path(xdg) / "repro_autotune"


def cache_path() -> pathlib.Path:
    return cache_dir() / _CACHE_FILE


def ppc_bucket(avg_ppc: float) -> str:
    """Log2 fill-ratio bucket: nearby fill ratios share a tuning decision
    (the paper's regimes — 1, 10, 100 ppc — land in distinct buckets)."""
    return f"2^{round(math.log2(max(avg_ppc, 0.125)))}"


def occupancy_bucket(fill: float) -> str:
    """Log2 active-pencil-fill bucket for the cache key.

    Mean ppc alone cannot distinguish a uniform gas from a tight blob with
    the same particle count — but those two regimes have different winners
    (compact wins the blob, dense the gas). Bucketing the measured fill
    fraction keeps their cached decisions separate while nearby fills
    share one."""
    return f"occ2^{round(math.log2(min(max(fill, 1.0 / 4096.0), 1.0)))}"


def _kernel_id(kernel: PairKernel) -> str:
    """Stable kernel identity for the disk cache: name plus a digest of the
    value-based identity tuple ``(name, flops, static_params)`` (PairKernel's
    own hash contract), so two kernels sharing a name but differing in FLOPs
    or parameters never share a cached winner. ``hash()`` itself is unusable
    here — Python randomizes string hashes per process."""
    ident = repr((kernel.name, kernel.flops, kernel.static_params))
    return f"{kernel.name}-{hashlib.sha1(ident.encode()).hexdigest()[:10]}"


def cache_key(platform: str, domain: Domain, m_c: int, avg_ppc: float,
              kernel: PairKernel, backends: Sequence[str],
              pencil_fill: float = 1.0,
              device_count: Optional[int] = None) -> str:
    """Mesh-aware: the visible device count is part of the key — the halo
    shard-count axis makes winners mesh-shaped, so a schedule tuned on an
    8-device mesh must never answer a 1-device query (or vice versa)."""
    if device_count is None:
        device_count = jax.device_count()
    return "|".join([
        platform,
        f"dev{device_count}",
        "x".join(str(n) for n in domain.ncells),
        f"mc{m_c}",
        f"ppc{ppc_bucket(avg_ppc)}",
        occupancy_bucket(pencil_fill),
        _kernel_id(kernel),
        "+".join(sorted(backends)),
    ])


def _space_id(candidates: Sequence[Candidate]) -> str:
    """Order-independent digest of a candidate space."""
    blob = "\n".join(sorted(json.dumps(c.to_json(), sort_keys=True)
                            for c in candidates))
    return hashlib.sha1(blob.encode()).hexdigest()[:10]


def _load_cache(path: pathlib.Path) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    return data if isinstance(data, dict) else {}


def _store_cache(path: pathlib.Path, key: str, entry: dict) -> None:
    """Merge one entry into the cache file.

    The tmp file is per-process and the final rename is atomic, so readers
    never see a truncated JSON. Two processes storing *concurrently* can
    still lose one another's new entry (last rename wins) — an acceptable
    cost for a cache whose entries are all re-derivable by re-tuning."""
    path.parent.mkdir(parents=True, exist_ok=True)
    data = _load_cache(path)
    data[key] = entry
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    try:
        with open(tmp, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    finally:
        if tmp.exists():
            tmp.unlink()


# --------------------------------------------------------------------------
# the tuner
# --------------------------------------------------------------------------

@dataclasses.dataclass
class TuneResult:
    """Winner plan plus the evidence: what was timed, what was pruned."""

    plan: InteractionPlan
    candidate: Candidate
    timings: Dict[Candidate, float]          # measured mean seconds
    reps: Dict[Candidate, int]               # stopwatch reps per candidate
    pruned: Tuple[Candidate, ...]            # enumerated but never timed
    cache_hit: bool
    cache_file: str


def tune(domain: Domain, kernel: Optional[PairKernel] = None,
         positions: Optional[Array] = None, *,
         m_c: Optional[int] = None,
         backends: Optional[Sequence[str]] = None,
         batch_sizes: Sequence[int] = DEFAULT_BATCH_SIZES,
         strategies: Sequence[str] = STRATEGY_NAMES,
         box: Optional[Tuple[int, int, int]] = None,
         candidates: Optional[Sequence[Candidate]] = None,
         m_c_slack: float = SLACK["m_c"],
         include_compact: bool = True,
         include_packed: bool = True,
         include_sfc: bool = True,
         shard_counts: Optional[Sequence[int]] = None,
         top_k: int = DEFAULT_TOP_K,
         reps: Optional[int] = None, budget_s: float = 0.5,
         interpret: Optional[bool] = None,
         use_cache: bool = True) -> TuneResult:
    """Measure candidate schedules on ``positions`` and return the fastest.

    Enumerates (strategy, backend, batch_size, m_c, allin box) candidates,
    prunes to ``top_k`` with the traffic model, times each survivor with a
    compile-excluded stopwatch (``core.timing.time_fn``), and returns the
    empirically fastest :class:`InteractionPlan`. Winners persist in the
    JSON cache (``cache_path()``), so the same regime re-tunes for free.

    Args:
      positions: representative positions — required; the tuner times real
        executions and measures the M_C bound from them.
      m_c: pin the slot bound; by default both a tight (slack=1.0) and a
        slacked (``m_c_slack``, the table's 1.5) sublane-aligned bound are
        candidates.
      backends: backends to tune over; default is ``("reference",)`` off-TPU
        (interpret-mode Pallas would time the interpreter, not the kernel)
        and ``("reference", "pallas")`` on TPU.
      box: extra All-in-SM sub-box to try alongside the derived candidates
        (shrunk to grid divisors).
      candidates: explicit candidate list (overrides enumeration; no
        compact twins are added to an explicit list).
      include_compact: add an occupancy-compacted twin for every
        enumerated candidate whose (backend, strategy) implements the
        compacted path — the dense-vs-compact axis of the search. The
        bound is measured from ``positions``.
      include_packed: add a packed-row-layout twin (``layout="packed"``,
        ``row_cap`` measured from ``positions``) for every candidate —
        dense *and* compacted — whose (backend, strategy) implements the
        packed layout: the dense-vs-packed axis of the search.
      include_sfc: add an SFC cluster-layout twin (``layout="sfc"``,
        ``pair_cap`` measured from ``positions``) for every dense
        candidate whose (backend, strategy) implements the compressed
        cluster-pair list: the dense-vs-sfc axis of the search.
      shard_counts: halo shard counts to sweep (the distributed axis —
        every cell-schedule candidate gets a ``backend="halo"`` twin per
        viable count). Default: the full visible device count when more
        than one device is up, nothing on a single device. Pass ``()`` to
        disable the distributed axis entirely.
      top_k: survivors after model pruning; raise it if you suspect the
        model is mis-ranking your regime.
      reps / budget_s: stopwatch controls (see ``time_fn``).
      use_cache: disable to force re-measurement (the winner still
        overwrites the cache entry).
    """
    if positions is None:
        raise ValueError("tune() needs positions (it measures real "
                         "executions, not a model)")
    kernel = kernel or make_lennard_jones()
    platform = jax.default_backend()
    if backends is None:
        backends = (("reference", "pallas") if platform == "tpu"
                    else ("reference",))

    from .api import active_unit_count, n_units
    from .engine import suggest_m_c
    max_count = int(_max_cell_count(domain, positions))
    if m_c is not None:
        m_c_choices = [m_c]
    else:
        m_c_choices = list(dict.fromkeys(
            [suggest_m_c(domain, positions, slack=1.0),
             suggest_m_c(domain, positions, slack=m_c_slack)]))
    key_m_c = min(m_c_choices)
    avg_ppc = positions.shape[0] / domain.n_cells

    # measured occupancy: how many work units are actually active. Keyed
    # per unit type (pencils; sub-boxes per tiling) and memoized — used to
    # score compacted candidates, reject too-small cached bounds, and
    # bucket the cache key (mean ppc alone cannot tell a blob from a gas).
    _occ: Dict[Tuple, Tuple[int, int]] = {}

    def occ_of(c: Candidate) -> Tuple[int, int]:     # (n_active, n_units)
        key_ = ("box", c.box) if c.strategy == "allin" else ("pencil",)
        if key_ not in _occ:
            _occ[key_] = (active_unit_count(domain, positions, c.strategy,
                                            box=c.box),
                          n_units(domain, c.strategy, box=c.box))
        return _occ[key_]

    def fill_for(c: Candidate) -> float:
        n_act, total = occ_of(c)
        return n_act / max(total, 1)

    # measured per-shard maxima, memoized per shard count — the halo
    # analogues of max_count/occ_of for the distributed candidates. The
    # per-cell counts don't depend on the shard count: one binning pass
    # serves every ns.
    _shard_measures: Dict[int, Tuple[int, int]] = {}
    _counts_box: list = []

    def shard_measures(ns: int) -> Tuple[int, int]:
        if ns not in _shard_measures:
            from .binning import (cell_counts, shard_pencil_active,
                                  shard_slab_counts)
            if not _counts_box:
                _counts_box.append(cell_counts(domain, positions))
            counts = _counts_box[0]
            _shard_measures[ns] = (
                int(shard_slab_counts(domain, counts, ns).max()),
                int(shard_pencil_active(domain, counts, ns).max()))
        return _shard_measures[ns]

    # measured packed-row maximum, memoized — the row_cap analogue of
    # max_count for the packed-layout candidates
    _row_max: list = []

    def max_row_count() -> int:
        if not _row_max:
            from .binning import cell_counts, padded_row_counts
            if not _counts_box:
                _counts_box.append(cell_counts(domain, positions))
            _row_max.append(int(jax.numpy.max(
                padded_row_counts(domain, _counts_box[0]))))
        return _row_max[0]

    # measured pair-list size, memoized — the pair_cap analogue of
    # max_row_count for the sfc-layout candidates
    _pair_max: list = []

    def max_pair_count() -> int:
        if not _pair_max:
            from .binning import cell_counts, sfc_pair_count
            if not _counts_box:
                _counts_box.append(cell_counts(domain, positions))
            _pair_max.append(int(sfc_pair_count(domain,
                                                counts=_counts_box[0])))
        return _pair_max[0]

    def active_safe(c: Candidate, strict: bool = True) -> bool:
        if c.layout == "packed":
            if c.row_cap is None:
                if strict:
                    raise ValueError(
                        f"packed candidate {c} has no row_cap bound "
                        "(repro.core.suggest_row_cap measures one)")
                return False
            if c.row_cap < max_row_count():
                return False
        if c.layout == "sfc":
            if c.pair_cap is None:
                if strict:
                    raise ValueError(
                        f"sfc candidate {c} has no pair_cap bound "
                        "(repro.core.suggest_pair_cap measures one)")
                return False
            if c.pair_cap < max_pair_count():
                return False
        if c.distributed:
            ns = c.n_shards
            if ns > jax.device_count() or domain.nz % ns:
                return False
            if c.shard_cap is None:
                if strict:
                    raise ValueError(
                        f"halo candidate {c} has no shard_cap bound "
                        "(repro.dist.halo.suggest_shard_cap measures one)")
                return False
            load, act = shard_measures(ns)
            if c.shard_cap < load:
                return False
            if c.compact:
                return c.max_active is not None and c.max_active >= act
            return True
        if not c.compact:
            return True
        if c.max_active is None:
            if strict:             # caller-supplied candidate: loud error
                raise ValueError(
                    f"compact candidate {c} has no max_active bound "
                    "(repro.core.suggest_max_active measures one)")
            return False           # malformed cache entry: just re-measure
        return c.max_active >= occ_of(c)[0]

    _occ[("pencil",)] = (active_unit_count(domain, positions, "xpencil"),
                         n_units(domain, "xpencil"))
    pencil_fill = _occ[("pencil",)][0] / max(_occ[("pencil",)][1], 1)

    key = cache_key(platform, domain, key_m_c, avg_ppc, kernel, backends,
                    pencil_fill=pencil_fill)
    cfile = cache_path()

    # build the requested candidate space first (cheap — no timing): the
    # cache is only consulted *within* it, so a restricted call
    # (strategies=..., candidates=..., pinned m_c) can never be answered
    # with a cached winner from outside its space
    if candidates is None:
        candidates = enumerate_candidates(
            domain, m_c_choices, backends=backends, batch_sizes=batch_sizes,
            strategies=strategies,
            extra_allin_boxes=(box,) if box is not None else ())
        if include_compact:
            candidates = list(candidates) + compact_twins(
                domain, positions, candidates)
        if include_packed:
            candidates = list(candidates) + packed_twins(
                domain, positions, candidates)
        if include_sfc:
            candidates = list(candidates) + sfc_twins(
                domain, positions, candidates)
        if shard_counts is None:
            # default distributed axis: the full local mesh (one extra
            # twin set), only when there is actually more than one device
            ndev = jax.device_count()
            shard_counts = (ndev,) if ndev > 1 else ()
        if shard_counts:
            candidates = list(candidates) + halo_twins(
                domain, positions, candidates, shard_counts)
    candidates = [c for c in candidates
                  if c.m_c >= max_count and active_safe(c)]
    if not candidates:
        raise ValueError(
            f"no overflow-safe candidates: max cell count {max_count} "
            f"exceeds every candidate m_c")

    # the candidate space is part of the key: a restricted call (explicit
    # strategies/candidates/batch sizes) owns its own entry instead of
    # answering from — or clobbering — the unrestricted one
    key += f"|space{_space_id(candidates)}"

    if use_cache:
        entry = _load_cache(cfile).get(key)
        if entry and entry.get("version") == CACHE_VERSION:
            cand = Candidate.from_json(entry["candidate"])
            # trust the entry only if it is overflow-safe for *these*
            # positions (bucket collisions can cache a smaller bound —
            # for m_c *and* for a compacted max_active) and inside the
            # requested space — otherwise re-measure
            if (cand.m_c >= max_count and active_safe(cand, strict=False)
                    and cand in set(candidates)):
                _obs_metrics.registry.counter(CACHE_TOTAL,
                                              result="hit").inc()
                _obs_event("autotune.cache", result="hit",
                           strategy=cand.strategy, layout=cand.layout)
                return TuneResult(
                    plan=cand.plan(domain, kernel, interpret), candidate=cand,
                    timings={}, reps={}, pruned=(), cache_hit=True,
                    cache_file=str(cfile))
    _obs_metrics.registry.counter(CACHE_TOTAL, result="miss").inc()
    _obs_event("autotune.cache", result="miss", candidates=len(candidates))
    kept, pruned = prune_candidates(domain, avg_ppc, candidates,
                                    top_k=top_k, fill_for=fill_for)
    _audit_pruned(domain, positions, pruned, avg_ppc, fill_for, _counts_box)

    state = ParticleState(positions)
    timings: Dict[Candidate, float] = {}
    nreps: Dict[Candidate, int] = {}
    for cand in kept:
        try:
            p = cand.plan(domain, kernel, interpret)
        except ValueError as e:       # refused by design (cannot fit)
            print(f"autotune: candidate {cand} refused: {e}",
                  file=sys.stderr)
            _obs_event("autotune.candidate_refused", backend=cand.backend,
                       strategy=cand.strategy, layout=cand.layout)
            continue
        # a compile error is a defect of the program, not a slow candidate:
        # it raises instead of quietly handing the win to another backend
        p.compile(state)
        try:
            _obs_metrics.registry.counter(
                TIMING_RUNS_TOTAL, backend=cand.backend,
                strategy=cand.strategy, layout=cand.layout).inc()
            with _obs_trace("autotune.time", backend=cand.backend,
                            strategy=cand.strategy, layout=cand.layout,
                            compact=cand.compact,
                            modelled_bpi=_cost(domain, avg_ppc, cand,
                                               fill_for)) as sp:
                secs, r = time_fn(p.execute, state, reps=reps,
                                  budget_s=budget_s)
                sp.set(seconds_per_call=secs, reps=r)
        except Exception as e:  # noqa: BLE001 — a broken candidate loses,
            print(f"autotune: candidate {cand} failed: {e!r}",  # not the run
                  file=sys.stderr)
            _obs_event("autotune.candidate_failed", backend=cand.backend,
                       strategy=cand.strategy, error=type(e).__name__)
            continue
        timings[cand] = secs
        nreps[cand] = r
    if not timings:
        raise RuntimeError(
            f"autotune: all {len(kept)} timed candidates failed (see stderr)")

    winner = min(timings, key=timings.get)
    _obs_event("autotune.winner", backend=winner.backend,
               strategy=winner.strategy, layout=winner.layout,
               compact=winner.compact,
               seconds_per_call=timings[winner])
    _store_cache(cfile, key, {
        "version": CACHE_VERSION,
        "candidate": winner.to_json(),
        "seconds": timings[winner],
        "platform": platform,
    })
    return TuneResult(plan=winner.plan(domain, kernel, interpret),
                      candidate=winner, timings=timings, reps=nreps,
                      pruned=tuple(pruned), cache_hit=False,
                      cache_file=str(cfile))
