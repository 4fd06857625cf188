"""Every Pallas interaction kernel compiles for a TPU v5e at the sizes
``chip_smoke.py`` runs (a periodic 80^3-cell box, m_c = 16).

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described, not attached. What interpret mode cannot show — block
shapes Mosaic refuses, relayouts it cannot do, VMEM a kernel cannot have —
fails here. A kernel that is too big by design is refused by ``plan()``
with the memory it would overflow named.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file. The tests skip only where the TPU library is not
installed; a topology that cannot be described fails them.

The VMEM estimates ``plan()`` refuses by are not checked against what
Mosaic allocates (the compiled program does not report it); each compile
here does run with the scoped-VMEM limit the estimate requests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import Domain, make_lennard_jones, plan

DIVISION = 80          # chip_smoke.py's box
M_C = 16
ROW_CAP = 208          # a packed row of ~2 particles per cell, with slack
KERN = make_lennard_jones(sigma=0.25, softening=1e-4)
DOM = Domain.cubic(DIVISION, cutoff=1.0, periodic=True)


@pytest.fixture(scope="module")
def topo():
    pytest.importorskip("libtpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # these compiles cannot be read back without a chip: keep them out of
    # any persistent cache the environment names
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _dense_planes(spec):
    nx, ny, nz = DOM.ncells
    shape = (nz + 2, ny + 2, (nx + 2) * M_C)
    return {f: spec(shape) for f in "xyz"}, spec(shape, jnp.int32)


def test_xpencil_dense_compiles(spec):
    from repro.kernels.xpencil import xpencil_forces
    planes, ids = _dense_planes(spec)
    _compile(lambda p, s: xpencil_forces(
        p, s, nx=DOM.nx, m_c=M_C, kernel=KERN, cutoff2=1.0,
        interpret=False), planes, ids)


def test_xpencil_compact_compiles(spec):
    from repro.kernels.xpencil import xpencil_sparse_forces
    planes, ids = _dense_planes(spec)
    _compile(lambda p, s, a: xpencil_sparse_forces(
        p, s, a, nx=DOM.nx, ny=DOM.ny, m_c=M_C, kernel=KERN, cutoff2=1.0,
        interpret=False), planes, ids, spec((DOM.ny * DOM.nz,), jnp.int32))


def test_xpencil_packed_compiles(spec):
    from repro.kernels.xpencil import xpencil_packed_forces
    nx, ny, nz = DOM.ncells
    row = (nz + 2, ny + 2, ROW_CAP)
    _compile(lambda p, s, c, o, a: xpencil_packed_forces(
        p, s, c, o, a, nx=nx, ny=ny, m_c=M_C, row_cap=ROW_CAP, kernel=KERN,
        cutoff2=1.0, interpret=False),
        {f: spec(row) for f in "xyz"}, spec(row, jnp.int32),
        spec(row, jnp.int32), spec((nz + 2, ny + 2, nx + 3), jnp.int32),
        spec((ny * nz,), jnp.int32))


def test_allin_compiles(spec):
    from repro.kernels.allin import allin_forces
    planes, ids = _dense_planes(spec)
    box = plan(DOM, KERN, m_c=M_C, strategy="allin", backend="pallas").box
    _compile(lambda p, s: allin_forces(
        p, s, box=box, m_c=M_C, kernel=KERN, cutoff2=1.0, interpret=False),
        planes, ids)


def test_sfc_compiles_where_it_fits(spec):
    """The SFC kernel stages the whole box, so it is compiled on a box
    that fits (12^3 cells); the smoke's box is refused below."""
    from repro.core.binning import sfc_cluster_tables
    from repro.kernels.sfc import cell_sfc_forces
    dom = Domain.cubic(12, cutoff=1.0, periodic=True)
    n_cl = sfc_cluster_tables(dom, 4).n_clusters
    n_pcells = 14 ** 3
    pair_cap = 27 * n_cl
    tiles = {f: spec((n_cl + 1, M_C, 4), jnp.int32 if f == "id"
                     else jnp.float32) for f in ("x", "y", "z", "id")}
    rows = {f: spec((n_pcells + 1, M_C), jnp.int32 if f == "id"
                    else jnp.float32) for f in ("x", "y", "z", "id")}
    ints = spec((pair_cap,), jnp.int32)
    _compile(lambda t, r, c, f, o: cell_sfc_forces(
        t, r, c, f, o, csize=4, m_c=M_C, kernel=KERN, cutoff2=1.0,
        interpret=False), tiles, rows, ints, ints,
        spec(((n_cl + 1) * 27 * 4,), jnp.int32))


def test_sfc_refused_at_smoke_size():
    """No topology needed: the refusal is plan-time arithmetic."""
    with pytest.raises(ValueError, match="VMEM|SMEM"):
        plan(DOM, KERN, m_c=M_C, strategy="cell_dense", layout="sfc",
             pair_cap=27 * DIVISION ** 3 // 4, backend="pallas",
             interpret=False)


def test_interpreted_plan_is_never_refused():
    """The interpreter stages nothing in VMEM: the same plan in interpret
    mode is accepted."""
    p = plan(DOM, KERN, m_c=M_C, strategy="cell_dense", layout="sfc",
             pair_cap=27 * DIVISION ** 3 // 4, backend="pallas",
             interpret=True)
    assert p.layout == "sfc"


def test_smoke_plans_fit_their_budgets():
    from repro.core import kernel_budget
    for kw in ({"strategy": "xpencil"},
               {"strategy": "xpencil", "compact": True,
                "max_active": DIVISION ** 2},
               {"strategy": "xpencil", "layout": "packed",
                "row_cap": ROW_CAP},
               {"strategy": "allin"}):
        p = plan(DOM, KERN, m_c=M_C, backend="pallas", **kw)
        name, vmem, smem = kernel_budget(p)
        assert 0 < vmem < 100 * 2 ** 20 and smem < 512 * 2 ** 10, name


@pytest.mark.parametrize("n", [64, 1000, 4096])
def test_prefix_sum_compiles(spec, n):
    from repro.kernels.prefix_sum import prefix_sum
    _compile(lambda x: prefix_sum(x, interpret=False), spec((n,)))
