"""SFC cluster layout: curve properties, pair-list algebra, replan, tuning.

The correctness bar (ISSUE 10): the sfc schedules must be *bit-parity*
with their dense ``cell_dense`` oracle — the compressed pair list may only
change which cluster tiles run, never a computed value. (Generic
sfc-vs-dense parity across scenes/backends lives in test_layout_matrix.py;
this file holds the curve/codec properties and the ``pair_cap``
fenceposts named by the issue: exact-cap, cap-overflow growing only that
bound, empty clusters, and periodic 1-cell-thick axes.)

Property tests use hypothesis when available and the deterministic
conftest stand-in otherwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (Domain, ParticleState, bin_particles,
                        build_sfc_clusters, decode_pair_codes,
                        encode_pair_masks, hilbert_decode, hilbert_encode,
                        make_lennard_jones, morton_decode, morton_encode,
                        plan, scenarios, sfc_cluster_tables, sfc_pair_count,
                        suggest_m_c, suggest_pair_cap, supports_compact,
                        supports_layout)
from repro.core import traffic
from repro.core.binning import cell_counts, sfc_n_clusters

KERN = make_lennard_jones()


def _blob(division=6, n=300, seed=0, sigma_frac=0.08, periodic=False):
    dom = Domain.cubic(division, cutoff=1.0, periodic=periodic)
    pos = scenarios.sample_gaussian_blob(
        dom, jax.random.PRNGKey(seed), n, sigma_frac=sigma_frac)
    return dom, pos


# ---------------------------------------------------------------------------
# curve properties (satellite: encode <-> decode round-trip + locality)
# ---------------------------------------------------------------------------

@settings(max_examples=25)
@given(bits=st.integers(1, 6), seed=st.integers(0, 1 << 20))
def test_curve_roundtrip(bits, seed):
    """decode(encode(p)) == p for random coordinates, both curves."""
    rng = np.random.RandomState(seed)
    side = 1 << bits
    ix, iy, iz = rng.randint(0, side, size=(3, 32))
    for enc, dec in ((morton_encode, morton_decode),
                     (hilbert_encode, hilbert_decode)):
        jx, jy, jz = dec(enc(ix, iy, iz, bits), bits)
        np.testing.assert_array_equal(jx, ix)
        np.testing.assert_array_equal(jy, iy)
        np.testing.assert_array_equal(jz, iz)


@pytest.mark.parametrize("bits", [1, 2, 3])
@pytest.mark.parametrize("enc", [morton_encode, hilbert_encode],
                         ids=["morton", "hilbert"])
def test_curve_is_a_bijection_on_the_cube(bits, enc):
    side = 1 << bits
    g = np.arange(side)
    ix, iy, iz = np.meshgrid(g, g, g, indexing="ij")
    codes = enc(ix.ravel(), iy.ravel(), iz.ravel(), bits)
    np.testing.assert_array_equal(np.sort(codes), np.arange(side ** 3))


@pytest.mark.parametrize("bits", [1, 2, 3])
def test_hilbert_locality_beats_morton(bits):
    """Consecutive Hilbert codes are face-adjacent cells (Manhattan step
    exactly 1); Morton jumps farther on average — the locality ordering
    the layout relies on is a measured fact, not folklore."""
    side = 1 << bits
    codes = np.arange(side ** 3)
    hx, hy, hz = hilbert_decode(codes, bits)
    h_step = (np.abs(np.diff(hx)) + np.abs(np.diff(hy))
              + np.abs(np.diff(hz)))
    np.testing.assert_array_equal(h_step, np.ones(side ** 3 - 1))
    mx, my, mz = morton_decode(codes, bits)
    m_step = (np.abs(np.diff(mx)) + np.abs(np.diff(my))
              + np.abs(np.diff(mz)))
    if bits > 1:
        assert m_step.mean() > 1.0                 # morton is not gapless
    assert h_step.mean() <= m_step.mean()


def test_morton_clusters_are_compact_blocks():
    """On a power-of-two grid, csize=4 Morton clusters are 2x2x1 blocks —
    the geometric compactness the cluster tile banks on."""
    dom = Domain.cubic(4, cutoff=1.0)
    t = sfc_cluster_tables(dom, 4, "morton")
    nx, ny = dom.nx, dom.ny
    for cells in t.cluster_cells:
        ix, iy, iz = cells % nx, (cells // nx) % ny, cells // (nx * ny)
        assert ix.max() - ix.min() <= 1
        assert iy.max() - iy.min() <= 1
        assert iz.max() == iz.min()


# ---------------------------------------------------------------------------
# pair-list codec properties (satellite: encode <-> decode inverse)
# ---------------------------------------------------------------------------

@settings(max_examples=25)
@given(n_clusters=st.integers(1, 8), seed=st.integers(0, 1 << 20),
       slack=st.integers(0, 16))
def test_pair_codec_roundtrip(n_clusters, seed, slack):
    """decode(encode(masks)) == masks whenever pair_cap holds every kept
    pair, regardless of padding slack."""
    rng = np.random.RandomState(seed)
    masks = rng.rand(n_clusters, 27) < 0.3
    cap = int(masks.sum()) + slack
    codes = encode_pair_masks(masks, max(cap, 1))
    back = decode_pair_codes(codes, n_clusters)
    np.testing.assert_array_equal(back, masks)
    # padding is the sentinel, and codes are sorted ascending
    assert (np.diff(codes) >= 0).all()
    assert (codes[int(masks.sum()):] == n_clusters * 32).all()


@settings(max_examples=15)
@given(seed=st.integers(0, 1 << 20))
def test_pair_codec_truncation_keeps_a_sorted_prefix(seed):
    """Overflow truncates: the decoded mask is a subset of the input with
    exactly pair_cap survivors — the lowest codes, never garbage."""
    rng = np.random.RandomState(seed)
    masks = rng.rand(6, 27) < 0.5
    total = int(masks.sum())
    if total < 2:
        return
    cap = total // 2
    back = decode_pair_codes(encode_pair_masks(masks, cap), 6)
    assert back.sum() == cap
    assert not (back & ~masks).any()               # subset
    a, k = np.nonzero(masks)
    kept = np.sort(a * 32 + k)[:cap]
    ba, bk = np.nonzero(back)
    np.testing.assert_array_equal(np.sort(ba * 32 + bk), kept)


def test_build_sfc_clusters_matches_host_probe():
    """The traced pair list equals the host probe's count and decodes to
    the exact occupancy bitmask rule."""
    dom, pos = _blob()
    bins = bin_particles(dom, pos, m_c=suggest_m_c(dom, pos))
    n_pairs = sfc_pair_count(dom, pos)
    sfc = build_sfc_clusters(dom, bins, pair_cap=n_pairs + 8)
    assert int(sfc.n_pairs) == n_pairs
    assert not bool(sfc.overflowed)
    masks = decode_pair_codes(np.asarray(sfc.codes),
                              sfc_n_clusters(dom))
    assert int(masks.sum()) == n_pairs
    # every kept pair's target cluster holds at least one particle
    cc = np.asarray(sfc.cluster_counts)
    assert (cc[np.nonzero(masks)[0]] > 0).all()


# ---------------------------------------------------------------------------
# the pair_cap replan contract (satellite: fenceposts)
# ---------------------------------------------------------------------------

def test_pair_cap_hit_exactly_no_overflow():
    """pair_cap == measured pair count: full, not overflowed, still
    bit-identical (the fencepost the truncation must not eat)."""
    dom, pos = _blob()
    exact = sfc_pair_count(dom, pos)
    state = ParticleState(pos)
    p = plan(dom, KERN, positions=pos, strategy="cell_dense",
             layout="sfc", pair_cap=exact)
    assert not p.check_overflow(state)
    f_d, q_d = plan(dom, KERN, positions=pos,
                    strategy="cell_dense").execute(state)
    f_s, q_s = p.execute(state)
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_d))
    np.testing.assert_array_equal(np.asarray(q_s), np.asarray(q_d))


def test_pair_cap_overflow_detected_and_replanned():
    """pair_cap one short of the measured count: overflow detected, replan
    grows *only* pair_cap, and the replanned result is bit-identical."""
    dom, pos = _blob()
    exact = sfc_pair_count(dom, pos)
    state = ParticleState(pos)
    f_d, _ = plan(dom, KERN, positions=pos,
                  strategy="cell_dense").execute(state)

    p0 = plan(dom, KERN, positions=pos, strategy="cell_dense",
              layout="sfc", pair_cap=exact - 1)
    assert p0.check_overflow(state)
    (f1, _), p1 = p0.execute_or_replan(state)
    assert p1.pair_cap > p0.pair_cap
    assert p1.pair_cap >= exact
    assert p1.m_c == p0.m_c                       # only pair_cap grew
    assert p1.max_active == p0.max_active
    assert p1.row_cap == p0.row_cap
    assert not p1.check_overflow(state)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f_d))

    # an overflowed bound really does drop cluster pairs (the thing replan
    # protects against): forces under the short list are wrong
    f_bad, _ = p0.execute(state)
    assert not np.array_equal(np.asarray(f_bad), np.asarray(f_d))


def test_pair_cap_growth_stops_at_the_all_pairs_total():
    """A full pair list on a 3^3 periodic grid: 7 clusters, all 189 pairs
    occupied. Growing a pair_cap just short of it lands on the all-pairs
    total ``n_clusters * 27``, the ceiling of the bound, and not on the
    next multiple of 8 past the old value (192)."""
    dom = Domain.cubic(3, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 200)
    state = ParticleState(pos)
    total = sfc_n_clusters(dom) * 27
    assert sfc_pair_count(dom, pos) == total == 189       # premise
    p = plan(dom, KERN, positions=pos, strategy="cell_dense", layout="sfc")
    assert p.pair_cap == total
    grown = dataclasses.replace(p, pair_cap=184).replan(state)
    assert grown.pair_cap == total
    assert not grown.check_overflow(state)


def test_empty_clusters_cost_no_pairs():
    """Everything in one cell: exactly one cluster holds particles, the
    pair list stays tiny, and the schedule is still bit-identical."""
    dom = Domain.cubic(4, cutoff=1.0)
    pos = jnp.full((7, 3), 0.5)
    bins = bin_particles(dom, pos, m_c=8)
    sfc = build_sfc_clusters(dom, bins, pair_cap=32)
    cc = np.asarray(sfc.cluster_counts)
    assert (cc > 0).sum() == 1
    assert int(sfc.n_pairs) <= 27
    state = ParticleState(pos)
    f_d, _ = plan(dom, KERN, m_c=8, strategy="cell_dense").execute(state)
    f_s, _ = plan(dom, KERN, m_c=8, strategy="cell_dense", layout="sfc",
                  pair_cap=32).execute(state)
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_d))


def test_sfc_periodic_thin_axes_bit_parity():
    """Periodic 1-cell-thick axes (the issue's hardest ghost case): the
    single cell's ghost copies drive the occupancy bitmask, and the sfc
    schedule must reproduce the dense sweep exactly."""
    dom = Domain(box=(1.0, 5.0, 5.0), ncells=(1, 5, 5), cutoff=1.0,
                 periodic=(True, True, False))
    pos = dom.sample_uniform(jax.random.PRNGKey(7), 120)
    state = ParticleState(pos)
    f_d, q_d = plan(dom, KERN, positions=pos,
                    strategy="cell_dense").execute(state)
    f_s, q_s = plan(dom, KERN, positions=pos, strategy="cell_dense",
                    layout="sfc").execute(state)
    np.testing.assert_array_equal(np.asarray(f_s), np.asarray(f_d))
    np.testing.assert_array_equal(np.asarray(q_s), np.asarray(q_d))

    dom2 = Domain(box=(5.0, 1.0, 1.0), ncells=(5, 1, 1), cutoff=1.0,
                  periodic=True)
    pos2 = dom2.sample_uniform(jax.random.PRNGKey(9), 80)
    state2 = ParticleState(pos2)
    f_d2, _ = plan(dom2, KERN, positions=pos2,
                   strategy="cell_dense").execute(state2)
    f_s2, _ = plan(dom2, KERN, positions=pos2, strategy="cell_dense",
                   layout="sfc").execute(state2)
    np.testing.assert_array_equal(np.asarray(f_s2), np.asarray(f_d2))


def test_suggest_pair_cap_bounds_and_clipping():
    dom, pos = _blob()
    exact = sfc_pair_count(dom, pos)
    cap = suggest_pair_cap(dom, pos)
    assert exact <= cap <= sfc_n_clusters(dom) * 27
    assert cap % 8 == 0                           # aligned
    # huge slack clips to the dense stencil total, never beyond
    assert suggest_pair_cap(dom, pos,
                            slack=1e6) == sfc_n_clusters(dom) * 27
    # counts shortcut agrees with the positions path
    assert suggest_pair_cap(dom, counts=cell_counts(dom, pos)) == cap


def test_sfc_plan_validation():
    dom, pos = _blob()
    with pytest.raises(ValueError, match="sfc"):
        plan(dom, KERN, positions=pos, strategy="xpencil", layout="sfc")
    with pytest.raises(ValueError, match="pair_cap|positions"):
        plan(dom, KERN, m_c=16, strategy="cell_dense", layout="sfc")
    assert supports_layout("reference", "cell_dense", "sfc")
    assert supports_layout("pallas", "cell_dense", "sfc")
    assert not supports_layout("reference", "xpencil", "sfc")
    assert not supports_layout("pallas", "allin", "sfc")
    assert supports_compact("reference", "cell_dense", "sfc")


def test_sfc_plans_hash_and_trace_separately():
    dom, pos = _blob()
    pd = plan(dom, KERN, positions=pos, strategy="cell_dense")
    ps = plan(dom, KERN, positions=pos, strategy="cell_dense",
              layout="sfc")
    assert pd != ps and hash(pd) != hash(ps)
    ps2 = plan(dom, KERN, positions=pos, strategy="cell_dense",
               layout="sfc")
    assert ps == ps2                              # same measured bound


# ---------------------------------------------------------------------------
# traffic model + autotuner layout axis
# ---------------------------------------------------------------------------

def test_traffic_sfc_cost_scales_with_fill():
    dom = Domain.cubic(8, cutoff=1.0)
    dense = traffic.candidate_cost(dom, 16, 2.0, "cell_dense")
    sparse = traffic.candidate_cost(dom, 16, 2.0, "cell_dense",
                                    layout="sfc", fill=0.1)
    full = traffic.candidate_cost(dom, 16, 2.0, "cell_dense",
                                  layout="sfc", fill=1.0)
    assert sparse < full                          # the pair list shrinks
    assert sparse < dense                         # and undercuts dense
    rep = traffic.sfc_report(dom, 16, 2.0, fill=0.25)
    assert rep.strategy == "cell_dense_sfc"
    assert rep.hbm_bytes_per_interaction > 0


def test_autotune_sfc_twins_and_safety():
    from repro.core import autotune as at
    dom, pos = _blob()
    cands = at.enumerate_candidates(dom, [suggest_m_c(dom, pos)],
                                    backends=("reference",),
                                    batch_sizes=(32,),
                                    strategies=("cell_dense", "par_part"))
    twins = at.sfc_twins(dom, pos, cands)
    # one sfc twin per dense cell_dense candidate; none for par_part (no
    # sfc path)
    assert {c.strategy for c in twins} == {"cell_dense"}
    assert all(c.layout == "sfc" and c.pair_cap
               and c.pair_cap % 8 == 0 for c in twins)
    # candidate json roundtrip keeps the pair_cap axis
    c = twins[0]
    assert at.Candidate.from_json(c.to_json()) == c
    # a too-small cached pair_cap must be re-measured, not trusted
    res = at.tune(dom, KERN, pos, strategies=("cell_dense",), top_k=4,
                  reps=2, budget_s=0.01, batch_sizes=(32,),
                  candidates=[dataclasses.replace(c, pair_cap=8),
                              dataclasses.replace(c, layout="dense",
                                                  pair_cap=None)])
    assert res.candidate.layout == "dense"        # the unsafe twin filtered


def test_autotune_sfc_candidate_requires_pair_cap():
    from repro.core import autotune as at
    dom, pos = _blob()
    bad = at.Candidate("cell_dense", "reference", 32,
                       suggest_m_c(dom, pos), layout="sfc")
    with pytest.raises(ValueError, match="pair_cap"):
        at.tune(dom, KERN, pos, candidates=[bad], use_cache=False)


# ---------------------------------------------------------------------------
# committed benchmark acceptance + perf-history rendering
# ---------------------------------------------------------------------------

def _bench_sfc_path():
    import pathlib
    return pathlib.Path(__file__).parent.parent / "benchmarks" / \
        "BENCH_sfc.json"


def test_committed_bench_sfc_meets_acceptance():
    """The committed BENCH_sfc.json must contain a clustered case where
    the sfc layout beats the packed layout (ISSUE 10 acceptance)."""
    import json
    records = json.loads(_bench_sfc_path().read_text())
    wins = [r for r in records
            if r["strategy"] == "cell_sfc"
            and r.get("speedup_vs_packed", 0.0) >= 1.0]
    assert wins, ("no committed case where sfc beats packed in "
                  f"{_bench_sfc_path()}")
    assert all(r.get("layout") == "sfc" and "drift" in r
               and r.get("pair_cap") for r in records
               if r["strategy"] == "cell_sfc")


def test_perf_history_renders_committed_sfc_records():
    """The real committed BENCH_sfc.json rendered through perf_history:
    sfc rows carry their layout tag verbatim plus the audit drift."""
    from benchmarks import perf_history
    snapshots = perf_history.collect(_bench_sfc_path().parent,
                                     pattern="BENCH_sfc.json")
    assert len(snapshots) == 1
    ss = perf_history.series(snapshots)
    sfc_keys = [k for k in ss if k[1] == "cell_sfc"]
    assert sfc_keys
    for k in sfc_keys:
        assert perf_history.layout_of(snapshots, k) == "sfc"
        assert perf_history.drift_of(snapshots, k) != "-"
    table = perf_history.format_table(snapshots, ss)
    lines = [ln for ln in table.splitlines() if ",cell_sfc," in ln]
    assert lines and all(ln.endswith(",sfc") for ln in lines)
