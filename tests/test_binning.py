"""Binning pipeline invariants (paper §2 preprocessing)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import Domain, bin_particles, gather_to_particles, suggest_m_c
from repro.core import binning
from repro.core.binning import EMPTY_POS, interior


def _random_case(seed, division, n):
    dom = Domain.cubic(division, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(seed), n)
    return dom, pos


@given(st.integers(0, 10_000), st.sampled_from([2, 3, 4, 6]),
       st.integers(1, 400))
@settings(max_examples=25, deadline=None)
def test_every_particle_lands_in_its_cell(seed, division, n):
    dom, pos = _random_case(seed, division, n)
    m_c = suggest_m_c(dom, pos)
    bins = bin_particles(dom, pos, m_c=m_c)

    # counts sum to N, offsets are the exclusive scan of counts
    counts = np.asarray(bins.counts)
    assert counts.sum() == n
    np.testing.assert_array_equal(
        np.asarray(bins.offsets), np.concatenate([[0], np.cumsum(counts)[:-1]]))

    # the slot of each particle holds its coordinates, in its own cell
    sid = np.asarray(bins.slot_id).reshape(-1)
    xs = np.asarray(bins.planes["x"]).reshape(-1)
    pslot = np.asarray(bins.particle_slot)
    pnp = np.asarray(pos)
    cells = np.asarray(dom.cell_coords(pos))
    nx, ny, nz = dom.ncells
    row = (nx + 2) * m_c
    for i in range(n):
        s = pslot[i]
        assert sid[s] == i
        assert xs[s] == pytest.approx(pnp[i, 0], rel=1e-6)
        z = s // ((ny + 2) * row)
        y = (s // row) % (ny + 2)
        x = (s % row) // m_c
        assert (x - 1, y - 1, z - 1) == tuple(cells[i])

    # every filled slot belongs to exactly one particle (bijection)
    filled = sid[sid >= 0]
    assert len(filled) == n and len(set(filled.tolist())) == n


def test_gather_inverts_scatter():
    dom, pos = _random_case(7, 4, 300)
    m_c = suggest_m_c(dom, pos)
    bins = bin_particles(dom, pos, m_c=m_c)
    for k, col in (("x", 0), ("y", 1), ("z", 2)):
        back = gather_to_particles(bins, bins.planes[k])
        np.testing.assert_allclose(np.asarray(back), np.asarray(pos[:, col]),
                                   rtol=1e-6)


def test_overflow_drops_not_corrupts():
    """m_c smaller than a cell's population: extras are dropped cleanly."""
    dom = Domain.cubic(2, cutoff=1.0)
    pos = jnp.asarray(np.full((40, 3), 0.5, np.float32))  # all in one cell
    bins = bin_particles(dom, pos, m_c=8)
    sid = np.asarray(bins.slot_id)
    assert (sid >= 0).sum() == 8            # capacity respected
    assert int(bins.max_count) == 40        # caller can detect overflow


def test_ghost_ring_empty_when_open():
    dom, pos = _random_case(3, 4, 200)
    m_c = suggest_m_c(dom, pos)
    bins = bin_particles(dom, pos, m_c=m_c)
    sid = np.asarray(bins.slot_id)
    nx, ny, nz = dom.ncells
    assert (sid[0] == -1).all() and (sid[-1] == -1).all()
    assert (sid[:, 0] == -1).all() and (sid[:, ny + 1] == -1).all()
    assert (sid[:, :, :m_c] == -1).all()
    assert (sid[:, :, (nx + 1) * m_c:] == -1).all()
    x = np.asarray(bins.planes["x"])
    assert (x[0] == EMPTY_POS).all()


def test_periodic_ghosts_are_shifted_images():
    dom = Domain.cubic(4, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(5), 300)
    m_c = suggest_m_c(dom, pos)
    bins = bin_particles(dom, pos, m_c=m_c)
    x = np.asarray(bins.planes["x"])
    m = x[:, :, :m_c] < 1e7                 # filled left ghosts
    # left ghost = rightmost interior cell shifted by -Lx
    src = x[:, :, 4 * m_c:5 * m_c]
    np.testing.assert_allclose(x[:, :, :m_c][m], (src - dom.box[0])[m],
                               rtol=1e-6)
    sid = np.asarray(bins.slot_id)
    ghost_ids = sid[:, :, :m_c][sid[:, :, :m_c] >= 0]
    assert (ghost_ids >= 1_000_000_000).all()   # image ids offset


def test_interior_view_shape():
    dom, pos = _random_case(1, 3, 100)
    m_c = suggest_m_c(dom, pos)
    bins = bin_particles(dom, pos, m_c=m_c)
    v = interior(dom, bins.planes["x"], m_c)
    assert v.shape == (3, 3, 3, m_c)


# ---------------------------------------------------------------------------
# periodic ghost slot-id bumping (_fill_periodic_ghosts) on a 1-cell-thick
# axis: the ghost ring of the single x-cell holds that same cell's own
# particles as periodic images. Their slot ids must be bumped (id + 1e9) so
# the schedules' self-mask (sid != tid) excludes only the *true* self-pair,
# never a particle's periodic image.
# ---------------------------------------------------------------------------

def _thin_domain():
    # one cell along x (width 1.2 >= cutoff 1.0), periodic in x only
    return Domain(box=(1.2, 4.0, 4.0), ncells=(1, 4, 4), cutoff=1.0,
                  periodic=(True, False, False))


def test_thin_axis_ghost_ids_are_bumped_images():
    dom = _thin_domain()
    pos = jnp.asarray(np.random.RandomState(0).uniform(
        [0, 0, 0], [1.2, 4, 4], (60, 3)), jnp.float32)
    m_c = suggest_m_c(dom, pos)
    bins = bin_particles(dom, pos, m_c=m_c)
    sid = np.asarray(bins.slot_id)
    interior_ids = sid[:, :, m_c:2 * m_c]
    left, right = sid[:, :, :m_c], sid[:, :, 2 * m_c:]
    # with nx == 1 both ghost columns mirror the single interior column
    filled = interior_ids >= 0
    assert filled.any()
    np.testing.assert_array_equal(left[filled],
                                  interior_ids[filled] + 1_000_000_000)
    np.testing.assert_array_equal(right[filled],
                                  interior_ids[filled] + 1_000_000_000)
    # interior ids themselves are never bumped
    assert (interior_ids[filled] < 1_000_000_000).all()
    # ghost coordinates are the interior shifted by exactly +-Lx
    x = np.asarray(bins.planes["x"])
    np.testing.assert_allclose(x[:, :, :m_c][filled],
                               x[:, :, m_c:2 * m_c][filled] - 1.2,
                               rtol=1e-6)
    # the bumped id passes the schedules' self-mask (a particle interacts
    # with its own periodic image); the raw id does not (never with itself)
    assert (left[filled] != interior_ids[filled]).all()


def test_thin_axis_double_periodic_ghosts_bump_once():
    # corner ghosts crossing two periodic axes must not double-bump (the
    # bump() guard): ids stay in [1e9, 2e9)
    dom = Domain(box=(1.2, 1.2, 4.0), ncells=(1, 1, 4), cutoff=1.0,
                 periodic=(True, True, False))
    pos = jnp.asarray(np.random.RandomState(1).uniform(
        [0, 0, 0], [1.2, 1.2, 4], (30, 3)), jnp.float32)
    m_c = suggest_m_c(dom, pos)
    bins = bin_particles(dom, pos, m_c=m_c)
    sid = np.asarray(bins.slot_id)
    ghosts = sid[sid >= 1_000_000_000]
    assert len(ghosts) > 0
    assert (ghosts < 2_000_000_000).all()


def test_thin_axis_forces_match_minimum_image_oracle():
    """A pair interacting only *through* the periodic boundary of the
    1-cell-thick axis: the cell engine must reproduce the minimum-image
    oracle (the interaction lives entirely in the bumped ghost slots)."""
    from repro.core import ParticleState, make_lennard_jones, plan
    dom = _thin_domain()
    pos = jnp.asarray([[0.05, 1.5, 1.5],        # A
                       [1.15, 1.5, 1.5]],       # B: direct dist 1.1 (> r_c),
                      jnp.float32)              # image dist 0.1 (< r_c)
    kern = make_lennard_jones()
    state = ParticleState(pos)
    f_o, q_o = plan(dom, kern, m_c=8, strategy="naive_n2").execute(state)
    assert float(jnp.abs(q_o).max()) > 0        # the pair really interacts
    for strategy in ("xpencil", "cell_dense", "par_part", "allin"):
        f, q = plan(dom, kern, m_c=8, strategy=strategy).execute(state)
        np.testing.assert_allclose(np.asarray(f), np.asarray(f_o),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=strategy)
        np.testing.assert_allclose(np.asarray(q), np.asarray(q_o),
                                   rtol=3e-4, atol=3e-5, err_msg=strategy)


def test_thin_axis_single_particle_sees_no_self_force():
    """A lone particle's own periodic images sit exactly one box length
    away (>= cutoff by the domain invariant): zero force, zero potential —
    and crucially not NaN, which a broken self-mask would produce."""
    from repro.core import ParticleState, make_lennard_jones, plan
    dom = _thin_domain()
    state = ParticleState(jnp.asarray([[0.6, 2.0, 2.0]], jnp.float32))
    f, q = plan(dom, make_lennard_jones(), m_c=8,
                strategy="xpencil").execute(state)
    np.testing.assert_array_equal(np.asarray(f), np.zeros((1, 3)))
    np.testing.assert_array_equal(np.asarray(q), np.zeros((1,)))


# ---------------------------------------------------------------------------
# the payload sort against the argsort-plus-gather formulation: bin_particles
# sorts (cell id, particle id) with x/y/z and every field carried along; the
# oracle below sorts ids alone and gathers each column by the permutation.
# Every CellBins field must come out bit-identical.
# ---------------------------------------------------------------------------

def _argsort_gather_bins(domain, positions, fields=None, *, m_c, valid=None):
    n = positions.shape[0]
    nx, ny, nz = domain.ncells
    n_cells = domain.n_cells
    coords = domain.cell_coords(positions)
    cids = domain.linearize(coords)
    if valid is None:
        weights = jnp.ones((n,), jnp.int32)
        sort_key = cids
    else:
        weights = valid.astype(jnp.int32)
        cids = jnp.where(valid, cids, 0)
        sort_key = jnp.where(valid, cids, n_cells)
    counts = jax.ops.segment_sum(weights, cids, num_segments=n_cells)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)[:-1]])
    order = jnp.argsort(sort_key, stable=True)
    sorted_key = sort_key[order]
    rank = jnp.arange(n, dtype=jnp.int32) - offsets[
        jnp.clip(sorted_key, 0, n_cells - 1)]
    cxyz = coords[order]
    row_len = (nx + 2) * m_c
    flat = (((cxyz[:, 2] + 1) * (ny + 2) + (cxyz[:, 1] + 1)) * row_len
            + (cxyz[:, 0] + 1) * m_c + rank)
    total = (nz + 2) * (ny + 2) * row_len
    flat = jnp.where((rank < m_c) & (sorted_key < n_cells), flat, total)
    shape = binning.padded_shape(domain, m_c)

    def scatter(values, fill):
        plane = jnp.full((total,), fill, values.dtype)
        return plane.at[flat].set(values[order], mode="drop").reshape(shape)

    planes = {k: scatter(positions[:, i], EMPTY_POS)
              for i, k in enumerate("xyz")}
    for k, v in (fields or {}).items():
        planes[k] = scatter(v, 0.0)
    slot_id = jnp.full((total,), -1, jnp.int32).at[flat].set(
        order.astype(jnp.int32), mode="drop").reshape(shape)
    particle_slot = jnp.zeros((n,), jnp.int32).at[order].set(
        flat.astype(jnp.int32), mode="drop")
    bins = binning.CellBins(planes=planes, slot_id=slot_id, counts=counts,
                            offsets=offsets, particle_slot=particle_slot,
                            m_c=m_c)
    if domain.any_periodic:
        bins = binning._fill_periodic_ghosts(domain, bins)
    return bins


def _grid_case(seed, periodic, n=5000):
    dom = Domain(box=(12.0, 10.0, 8.0), ncells=(12, 10, 8), cutoff=1.0,
                 periodic=periodic)
    pos = dom.sample_uniform(jax.random.PRNGKey(seed), n)
    return dom, pos


def _assert_bins_equal(got, want):
    assert got.m_c == want.m_c
    assert sorted(got.planes) == sorted(want.planes)
    for k in want.planes:
        np.testing.assert_array_equal(np.asarray(got.planes[k]),
                                      np.asarray(want.planes[k]), err_msg=k)
    for k in ("slot_id", "counts", "offsets", "particle_slot"):
        np.testing.assert_array_equal(np.asarray(getattr(got, k)),
                                      np.asarray(getattr(want, k)),
                                      err_msg=k)


@pytest.mark.parametrize("n_fields", [0, 2])
@pytest.mark.parametrize("m_c", [16, 6])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("periodic", [False, True])
def test_payload_sort_matches_argsort_gather(periodic, masked, m_c,
                                             n_fields):
    dom, pos = _grid_case(11, periodic)
    n = pos.shape[0]
    rng = np.random.RandomState(3)
    valid = jnp.asarray(rng.rand(n) > 0.2) if masked else None
    fields = {f"f{i}": jnp.asarray(rng.randn(n), jnp.float32)
              for i in range(n_fields)}
    if m_c == 6:    # small enough that some cells overflow
        assert int(jnp.max(binning.cell_counts(dom, pos, valid))) > m_c
    got = jax.jit(lambda p, f, v: bin_particles(dom, p, f, m_c=m_c,
                                                valid=v))(pos, fields, valid)
    want = _argsort_gather_bins(dom, pos, fields, m_c=m_c, valid=valid)
    _assert_bins_equal(got, want)


@pytest.mark.parametrize("periodic", [False, True])
def test_payload_sort_matches_argsort_gather_under_vmap(periodic):
    dom, _ = _grid_case(0, periodic)
    states = jnp.stack([_grid_case(s, periodic)[1] for s in (21, 22)])
    fields = {"mass": jnp.stack([jnp.full((5000,), 1.5, jnp.float32),
                                 jnp.linspace(0.0, 1.0, 5000)])}
    got = jax.vmap(lambda p, f: bin_particles(dom, p, f, m_c=8))(
        states, fields)
    for b in range(2):
        want = _argsort_gather_bins(
            dom, states[b], {"mass": fields["mass"][b]}, m_c=8)
        _assert_bins_equal(jax.tree.map(lambda a: a[b], got), want)


def _n_row_gathers(jaxpr, n):
    count = 0
    for eqn in jaxpr.eqns:
        rows = eqn.outvars[0].aval.shape[:1]
        if eqn.primitive.name == "gather" and rows == (n,):
            count += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _n_row_gathers(sub, n)
    return count


def test_no_gather_by_the_sort_permutation():
    """One gather with N rows of output is left in binning: the offsets
    lookup behind each row's rank. The columns, the sorted key and the cell
    coordinates come out of the sort, not gathered by its permutation."""
    dom, pos = _grid_case(5, False)
    jaxpr = jax.make_jaxpr(lambda p: bin_particles(dom, p, m_c=8))(pos)
    assert _n_row_gathers(jaxpr.jaxpr, pos.shape[0]) == 1
