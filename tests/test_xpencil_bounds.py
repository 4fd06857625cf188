"""The X-pencil kernel's occupancy bound (interpret mode).

Each grid step evaluates only the source slots below its source pencil's
highest occupied slot, against only the target's 8-row tiles below its
own. The bounded path runs natively only; interpret mode sums the static full
window. These tests check the bound table against a count of the
particles, run the bounded path in interpret mode against the full
window (bit-equal to the same loop at full bounds, float32-close to the
static window), check that ``vmap`` keeps the bounded kernel one launch,
and check the ``slot_pair_share`` counter that says how far the bound
cuts the work.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro import obs
from repro.core import (Domain, ParticleState, bin_particles,
                        make_lennard_jones, plan, scenarios)
from repro.core.binning import pencil_occupancy
from repro.kernels import xpencil as XP
from repro.kernels._lanes import shift_lanes, window_terms

KERN = make_lennard_jones()


def _grid_points(division, per_cell, seed=0, jitter=0.1):
    """``per_cell`` particles in every cell of a unit-cell grid."""
    g = np.stack(np.meshgrid(*[np.arange(division)] * 3, indexing="ij"),
                 -1).reshape(-1, 3)
    rng = np.random.default_rng(seed)
    offs = rng.uniform(0.5 - jitter, 0.5 + jitter, (per_cell, len(g), 3))
    # spread the per-cell copies apart so no two overlap
    offs += (np.arange(per_cell)[:, None, None] - (per_cell - 1) / 2) * 0.05
    return (g[None] + offs).reshape(-1, 3)


def _scene(name):
    """-> (domain, positions, m_c, slot to open a hole in or None)."""
    if name == "uniform":
        dom = Domain.cubic(4, periodic=True)
        return dom, _grid_points(4, 2), 16, None
    if name == "empty_pencils":       # the upper half of z holds nothing
        dom = Domain.cubic(4)
        pos = _grid_points(4, 2)
        return dom, pos[pos[:, 2] < 2.0], 8, None
    if name == "full_pencil":         # one cell at m_c, the rest at 1
        dom = Domain.cubic(4, periodic=True)
        pos = np.concatenate([_grid_points(4, 1),
                              _grid_points(1, 7, jitter=0.0) + 1.0])
        return dom, pos, 8, None
    if name in ("periodic", "open"):
        dom = Domain.cubic(5, periodic=name == "periodic")
        pos = np.asarray(dom.sample_uniform(jax.random.PRNGKey(4), 300))
        return dom, pos, 16, None
    if name == "hole":                # slot 0 of the fullest cell emptied
        dom = Domain.cubic(4)
        pos = np.asarray(dom.sample_uniform(jax.random.PRNGKey(5), 150))
        return dom, pos, 16, 0
    raise KeyError(name)


def _count_bound(dom, pos, m_c):
    """Per padded pencil, the most particles in one of its cells, counted
    from the positions, with the ghost ring copied as the domain wraps."""
    nx, ny, nz = dom.ncells
    cells = np.floor(np.asarray(pos) / np.asarray(dom.cell_width)).astype(int)
    counts = np.zeros((nz, ny, nx), int)
    np.add.at(counts, (cells[:, 2], cells[:, 1], cells[:, 0]), 1)
    per_pencil = np.minimum(counts.max(axis=2), m_c)
    if dom.any_periodic:
        return np.pad(per_pencil, 1, mode="wrap")
    return np.pad(per_pencil, 1)


@pytest.mark.parametrize("name", ["uniform", "empty_pencils", "full_pencil",
                                  "periodic", "open", "hole"])
def test_slot_bounds_match_a_count(name):
    dom, pos, m_c, hole = _scene(name)
    bins = bin_particles(dom, jnp.asarray(pos, jnp.float32), m_c=m_c)
    want = _count_bound(dom, pos, m_c)
    slot_id = np.asarray(bins.slot_id)
    if hole is not None:
        counts = np.asarray(bins.counts).reshape(dom.nz, dom.ny, dom.nx)
        z, y, x = np.unravel_index(counts.argmax(), counts.shape)
        assert counts[z, y, x] >= 2
        slot_id = slot_id.copy()
        cell = slice((x + 1) * m_c, (x + 2) * m_c)
        slot_id[z + 1, y + 1, cell][hole] = -1
        # one slot fewer is occupied; the highest occupied one stays
        assert (slot_id[z + 1, y + 1, cell] >= 0).sum() == counts[z, y, x] - 1
        assert slot_id[z + 1, y + 1, cell][counts[z, y, x] - 1] >= 0
    got = np.asarray(XP.slot_bounds(jnp.asarray(slot_id), m_c))
    assert got.shape == (dom.nz + 2, dom.ny + 2)
    np.testing.assert_array_equal(got, want)
    if name == "full_pencil":
        assert got.max() == m_c
    if name == "empty_pencils":
        assert (got[1:-1][dom.nz // 2 + 1:] == 0).all()


def _kernel_scene(name):
    if name == "blob":                # one dense clump, open box
        dom = Domain.cubic(6)
        pos = scenarios.sample_gaussian_blob(
            dom, jax.random.PRNGKey(3), 280, sigma_frac=0.08)
        return dom, pos, 24
    if name == "uniform":
        dom = Domain.cubic(4, periodic=True)
        return dom, jnp.asarray(_grid_points(4, 2), jnp.float32), 8
    if name == "source_fuller":       # a 12-particle cell among singles
        dom = Domain.cubic(4, periodic=True)
        pos = np.concatenate([_grid_points(4, 1),
                              _grid_points(1, 11, jitter=0.0) + 2.0])
        return dom, jnp.asarray(pos, jnp.float32), 16
    raise KeyError(name)


def _run(variant, dom, bins, m_c, bounded):
    """One kernel variant in interpret mode, its window bounded by the
    slot-bound table (the native path) or the static full window."""
    kw = dict(nx=dom.nx, m_c=m_c, kernel=KERN, cutoff2=1.0, interpret=True,
              bounded=bounded)
    if variant == "dense":
        return XP._dense_call(bins.planes, bins.slot_id, **kw)
    occ = pencil_occupancy(dom, bins.counts, dom.ny * dom.nz)
    return XP._sparse_call(bins.planes, bins.slot_id, occ.active, ny=dom.ny,
                           **kw)


@pytest.mark.parametrize("scene", ["blob", "uniform", "source_fuller"])
@pytest.mark.parametrize("variant", ["dense", "compact"])
def test_bounded_kernel_matches_full_window(variant, scene, monkeypatch):
    """The chip's path, run in interpret mode: bounded by the real table,
    it gives the bits it gives with every bound forced to ``m_c`` (the
    skipped pairs are exact zeros), and the static full window's sums to
    float32 rounding (the tile loop adds in another order)."""
    dom, pos, m_c = _kernel_scene(scene)
    bins = bin_particles(dom, pos, m_c=m_c)
    bound = np.asarray(XP.slot_bounds(bins.slot_id, m_c))
    if scene == "source_fuller":      # a target pencil sparser than its source
        assert bound.max() == 12 and bound.min() == 1
    got = _run(variant, dom, bins, m_c, bounded=True)
    with monkeypatch.context() as m:  # every step at the full m_c
        m.setattr(XP, "slot_bounds",
                  lambda s, m_c: jnp.full(s.shape[:2], m_c, jnp.int32))
        forced = _run(variant, dom, bins, m_c, bounded=True)
    full = _run(variant, dom, bins, m_c, bounded=False)
    for g, f, w in zip(got, forced, full):
        g, w = np.asarray(g), np.asarray(w)
        np.testing.assert_array_equal(g, np.asarray(f))
        np.testing.assert_allclose(g, w, rtol=1e-5,
                                   atol=1e-5 * np.abs(w).max())


@pytest.mark.parametrize("scene", ["blob", "uniform", "source_fuller"])
def test_bounded_kernel_batches_under_vmap(scene):
    """``execute_batch`` vmaps the dense kernel. The bound table is an
    SMEM input, not a scalar prefetch, so ``vmap`` adds a grid axis to
    the one launch (no loop of launches), bit-equal to a loop."""
    dom, pos, m_c = _kernel_scene(scene)
    stack = jnp.stack([pos, pos[::-1] + 0.01])
    bins = jax.vmap(lambda p: bin_particles(dom, p, m_c=m_c))(stack)

    def run(planes, slot_id):
        return XP._dense_call(planes, slot_id, nx=dom.nx, m_c=m_c,
                              kernel=KERN, cutoff2=1.0, interpret=True,
                              bounded=True)

    [call] = [e for e in jax.make_jaxpr(jax.vmap(run))(
        bins.planes, bins.slot_id).eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (2, dom.nz, dom.ny, 9)
    got = jax.vmap(run)(bins.planes, bins.slot_id)
    for i in range(2):
        one = run({k: v[i] for k, v in bins.planes.items()}, bins.slot_id[i])
        for g, o in zip(got, one):
            np.testing.assert_array_equal(np.asarray(g[i]), np.asarray(o))


@pytest.mark.parametrize("per_cell,m_c,want", [(2, 16, 1 / 4),
                                               (8, 8, 1.0),
                                               (2, 12, 1.0)])
def test_slot_pair_share_and_gauge(per_cell, m_c, want):
    """Whole 8-row tiles at 2 per cell: a quarter of m_c 16's pairs; all
    at full occupancy, and all where m_c is not whole tiles (the static
    full window)."""
    dom = Domain.cubic(3, periodic=True)
    pos = jnp.asarray(_grid_points(3, per_cell), jnp.float32)
    p = plan(dom, KERN, m_c=m_c, strategy="xpencil", backend="pallas",
             interpret=True)
    assert p.slot_pair_share(ParticleState(pos)) == pytest.approx(want)
    gauge = obs.metrics.snapshot()["repro_xpencil_slot_pair_share"]
    label = '{backend="pallas",layout="dense",strategy="xpencil"}'
    assert gauge[label] == pytest.approx(want)


@pytest.mark.parametrize("m_c,n_src,n_tgt", [(8, 3, 2), (16, 16, 16),
                                             (16, 5, 9), (40, 21, 17)])
def test_window_terms_bounded_matches_full(m_c, n_src, n_tgt):
    """The bounded window (the chip's tile loop) against the static full
    one, to a float32 tolerance: the loop adds in another order."""
    lanes = 128
    rng = np.random.default_rng(m_c + n_src)

    def plane(n_occ, ids):
        xyz = [rng.uniform(0.0, 3.0, (m_c, lanes)).astype(np.float32)
               for _ in range(3)]
        sid = np.arange(m_c * lanes, dtype=np.int32).reshape(m_c, lanes)
        sid = np.where(np.arange(m_c)[:, None] < n_occ, sid + ids, -1)
        return xyz + [sid.astype(np.int32)]

    tgt, src = plane(n_tgt, 0), plane(n_src, 10 ** 6)

    def body(bounded, *refs):
        t = tuple(shift_lanes(r[...], 1) for r in refs[0:4])
        kw = (dict(n_src=jnp.int32(n_src), n_tgt=jnp.int32(n_tgt))
              if bounded else {})
        for o, v in zip(refs[8:12], window_terms(
                t, refs[4:8], m_c=m_c, kernel=KERN, cutoff2=1.0, **kw)):
            o[...] = v

    out = [jax.ShapeDtypeStruct((m_c, lanes), jnp.float32)] * 4
    full, bounded = (
        pl.pallas_call(functools.partial(body, b), out_shape=out,
                       interpret=True)(*tgt, *src) for b in (False, True))
    for f, g in zip(full, bounded):
        f, g = np.asarray(f), np.asarray(g)
        assert (g[n_tgt:] == 0).all() and (f[n_tgt:] == 0).all()
        np.testing.assert_allclose(g, f, rtol=1e-5,
                                   atol=1e-5 * np.abs(f).max())
